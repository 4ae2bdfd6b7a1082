"""Tests for tools/contract_lint — each checker has at least one
should-flag and one should-pass fixture, plus finding/baseline engine
coverage.  Fixtures are inline sources run through ``lint_sources`` under
synthetic repo-relative paths, so no real tree (and no jax) is needed."""

import json
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.contract_lint import Baseline, lint_sources          # noqa: E402
from tools.contract_lint.__main__ import main as lint_main      # noqa: E402


def lint(path, source, extra=None):
    sources = {path: textwrap.dedent(source)}
    for p, s in (extra or {}).items():
        sources[p] = textwrap.dedent(s)
    return lint_sources(sources)


def rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# CL001 · ladder discipline
# ---------------------------------------------------------------------------

class TestLadderDiscipline:
    REGISTRY = {"src/repro/serve/reg.py":
                'LADDER_LAUNCH_SITES = frozenset({"Svc.launch_rungs"})\n'}

    def test_flags_direct_batched_call_from_serve(self):
        findings = lint("src/repro/serve/svc.py", """\
            class Svc:
                def sneak(self, lo, hi):
                    return kops.prune_ranges_batched_device(lo, hi)
            """, extra=self.REGISTRY)
        assert "CL001" in rules(findings)
        (f,) = [f for f in findings if f.rule == "CL001"]
        assert "prune_ranges_batched_device" in f.message
        assert f.context == "Svc.sneak"

    def test_flags_batched_call_from_flow(self):
        findings = lint("src/repro/core/flow.py", """\
            def run(pipe):
                return kops.join_overlap_batched_tree(pipe)
            """)
        assert "CL001" in rules(findings)

    def test_registered_site_passes_including_nested_thunks(self):
        findings = lint("src/repro/serve/svc.py", """\
            class Svc:
                def launch_rungs(self, lo, hi):
                    def thunk():
                        return kops.prune_ranges_batched_device(lo, hi)
                    return [("device", thunk)]
            """, extra=self.REGISTRY)
        assert "CL001" not in rules(findings)

    def test_out_of_scope_module_passes(self):
        findings = lint("src/repro/kernels/ops.py", """\
            def prune_ranges_batched_host(lo, hi):
                return minmax_prune_batched_ref(lo, hi)
            """)
        assert "CL001" not in rules(findings)


# ---------------------------------------------------------------------------
# CL002 · integrity protocol
# ---------------------------------------------------------------------------

class TestIntegrityProtocol:
    GOOD = """\
        PLANE_FAMILIES = ("stat",)

        class DeviceStatsCache:
            def __init__(self):
                self.entries = {}
                self._stores = {"stat": self.entries}

            def _admit(self, family, key, nbytes):
                self.memory.admit(family, key, nbytes)

            def get(self, key):
                arrays = self._build(key)
                stamp = plane_checksum(arrays)
                self._admit("stat", key, 8)
                return arrays, stamp
        """

    def test_protocol_compliant_getter_passes(self):
        findings = lint("src/repro/core/device_stats.py", self.GOOD)
        assert "CL002" not in rules(findings)

    def test_flags_getter_missing_checksum_and_accounting(self):
        findings = lint("src/repro/core/device_stats.py", """\
            PLANE_FAMILIES = ("stat",)

            class DeviceStatsCache:
                def __init__(self):
                    self.entries = {}
                    self._stores = {"stat": self.entries}

                def tree_plane(self, key):
                    return self.entries[key]
            """)
        msgs = [f.message for f in findings if f.rule == "CL002"]
        assert any("plane_checksum" in m for m in msgs)
        assert any("PlaneMemoryManager" in m for m in msgs)

    def test_verdict_plane_getter_with_protocol_passes(self):
        """A verdict-family getter that re-stamps on replay and touches
        the memory accounting is protocol-compliant."""
        findings = lint("src/repro/core/device_stats.py", """\
            PLANE_FAMILIES = ("verdict",)

            class DeviceStatsCache:
                def __init__(self):
                    self.verdict_planes = {}
                    self._stores = {"verdict": self.verdict_planes}

                def _touch(self, family, key):
                    self.memory.touch(family, key)

                def verdict_plane(self, table, pred, ckey):
                    e = self.verdict_planes[(table.name, ckey)]
                    e.meta["checksum"] = plane_checksum(e.arrays)
                    self._touch("verdict", (table.name, ckey))
                    return e.arrays[0]
            """)
        assert "CL002" not in rules(findings)

    def test_flags_verdict_plane_getter_skipping_protocol(self):
        """A verdict getter that serves rows without checksum stamping or
        byte accounting violates the integrity protocol."""
        findings = lint("src/repro/core/device_stats.py", """\
            PLANE_FAMILIES = ("verdict",)

            class DeviceStatsCache:
                def __init__(self):
                    self.verdict_planes = {}
                    self._stores = {"verdict": self.verdict_planes}

                def verdict_plane(self, table, pred, ckey):
                    return self.verdict_planes[(table.name, ckey)].arrays[0]
            """)
        msgs = [f.message for f in findings if f.rule == "CL002"]
        assert any("plane_checksum" in m for m in msgs)
        assert any("PlaneMemoryManager" in m for m in msgs)

    def test_flags_verdict_store_missing_from_registry(self):
        """Shipping the verdict store without declaring the family in
        PLANE_FAMILIES is exactly what CL002 exists to catch."""
        findings = lint("src/repro/core/device_stats.py", """\
            PLANE_FAMILIES = ("stat",)

            class DeviceStatsCache:
                def __init__(self):
                    self._stores = {"stat": self.entries,
                                    "verdict": self.verdict_planes}
            """)
        msgs = [f.message for f in findings if f.rule == "CL002"]
        assert any("'verdict'" in m and "integrity protocol" in m
                   for m in msgs)

    def test_flags_store_family_not_in_registry(self):
        findings = lint("src/repro/core/device_stats.py", """\
            PLANE_FAMILIES = ("stat",)

            class DeviceStatsCache:
                def __init__(self):
                    self._stores = {"stat": self.entries, "rogue": self.rogue}
            """)
        msgs = [f.message for f in findings if f.rule == "CL002"]
        assert any("'rogue'" in m and "integrity protocol" in m for m in msgs)

    def test_flags_missing_registry(self):
        findings = lint("src/repro/core/device_stats.py", """\
            class DeviceStatsCache:
                def __init__(self):
                    self._stores = {"stat": self.entries}
            """)
        msgs = [f.message for f in findings if f.rule == "CL002"]
        assert any("PLANE_FAMILIES" in m for m in msgs)


# ---------------------------------------------------------------------------
# CL003 · lock discipline
# ---------------------------------------------------------------------------

class TestLockDiscipline:
    def test_flags_guarded_read_outside_lock(self):
        findings = lint("src/repro/core/cache.py", """\
            class Cache:
                def __init__(self):
                    self._lock = threading.RLock()
                    self.entries = {}  # guarded-by: _lock

                def peek(self, k):
                    return self.entries.get(k)
            """)
        (f,) = [f for f in findings if f.rule == "CL003"]
        assert f.context == "Cache.peek"
        assert "'entries'" in f.message

    def test_flags_guarded_write_outside_lock(self):
        findings = lint("src/repro/core/cache.py", """\
            class Cache:
                def __init__(self):
                    self._lock = threading.RLock()
                    self.tick = 0  # guarded-by: _lock

                def bump(self):
                    self.tick += 1
            """)
        assert "CL003" in rules(findings)

    def test_with_lock_scopes_and_nested_functions_pass(self):
        findings = lint("src/repro/core/cache.py", """\
            class Cache:
                def __init__(self):
                    self._lock = threading.RLock()
                    self.entries = {}  # guarded-by: _lock

                def get(self, k):
                    with self._lock:
                        def build():
                            return self.entries[k]
                        return build()

                def _count(self):
                    return len(self.entries)

                def size(self):
                    with self._lock:
                        return self._count()
            """)
        assert "CL003" not in rules(findings)

    def test_private_helper_with_unlocked_caller_flagged(self):
        findings = lint("src/repro/core/cache.py", """\
            class Cache:
                def __init__(self):
                    self._lock = threading.RLock()
                    self.entries = {}  # guarded-by: _lock

                def _count(self):
                    return len(self.entries)

                def size(self):
                    return self._count()
            """)
        assert "CL003" in rules(findings)

    def test_unannotated_fields_ignored(self):
        findings = lint("src/repro/core/cache.py", """\
            class Cache:
                def __init__(self):
                    self._lock = threading.RLock()
                    self.hits = 0

                def bump(self):
                    self.hits += 1
            """)
        assert "CL003" not in rules(findings)


# ---------------------------------------------------------------------------
# CL004 · precision contract
# ---------------------------------------------------------------------------

class TestPrecisionContract:
    def test_flags_raw_astype_in_kernels(self):
        findings = lint("src/repro/kernels/stage.py", """\
            def stage(stats):
                return stats.mins.astype(np.float32)
            """)
        assert "CL004" in rules(findings)

    def test_flags_raw_float32_call(self):
        findings = lint("src/repro/core/bounds.py", """\
            def narrow(b):
                return jnp.float32(b)
            """)
        assert "CL004" in rules(findings)

    def test_widening_helpers_bool_masks_and_constants_pass(self):
        findings = lint("src/repro/kernels/stage.py", """\
            def stage(stats, lo, hi):
                mins = round_down_f32(stats.mins).astype(np.float32)
                demote = ((stats.nulls > 0) | inexact).astype(np.float32)
                pad = np.float32(-np.inf)
                return mins, demote, pad
            """)
        assert "CL004" not in rules(findings)

    def test_out_of_scope_module_passes(self):
        findings = lint("src/repro/serve/glue.py", """\
            def narrow(x):
                return x.astype(np.float32)
            """)
        assert "CL004" not in rules(findings)


# ---------------------------------------------------------------------------
# CL005 · trace safety
# ---------------------------------------------------------------------------

class TestTraceSafety:
    def test_flags_python_if_on_traced_param_in_jitted_fn(self):
        findings = lint("src/repro/kernels/op.py", """\
            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
            """)
        (f,) = [f for f in findings if f.rule == "CL005"]
        assert "`if`" in f.message and "['x']" in f.message

    def test_flags_item_and_nondeterminism_in_kernel_body(self):
        findings = lint("src/repro/kernels/op.py", """\
            def _scan_kernel(x_ref, o_ref):
                t = time.time()
                o_ref[...] = x_ref[...].item() + t
            """)
        msgs = [f.message for f in findings if f.rule == "CL005"]
        assert any(".item()" in m for m in msgs)
        assert any("time.time" in m for m in msgs)

    def test_flags_float_concretization(self):
        findings = lint("src/repro/kernels/op.py", """\
            @functools.partial(jax.jit, static_argnames=("k",))
            def f(x, k):
                return float(x) + k
            """)
        msgs = [f.message for f in findings if f.rule == "CL005"]
        assert any("float" in m for m in msgs)

    def test_flags_profiler_span_in_jitted_fn_and_kernel_body(self):
        findings = lint("src/repro/kernels/op.py", """\
            @jax.jit
            def f(x):
                with span("dispatch"):
                    return x + 1

            def _scan_kernel(x_ref, o_ref):
                with jax.profiler.TraceAnnotation("prune.kernel"):
                    o_ref[...] = x_ref[...]
            """)
        msgs = [f.message for f in findings if f.rule == "CL005"]
        assert any("'span'" in m for m in msgs)
        assert any("jax.profiler.TraceAnnotation" in m for m in msgs)

    def test_span_in_host_wrapper_passes(self):
        findings = lint("src/repro/kernels/op.py", """\
            @jax.jit
            def f(x):
                return x + 1

            def wrapper(x):
                with span("dispatch"):
                    out = f(x)
                with span("d2h"):
                    return np.asarray(out)
            """)
        assert "CL005" not in rules(findings)

    def test_static_argnames_and_closure_config_pass(self):
        findings = lint("src/repro/kernels/op.py", """\
            @functools.partial(jax.jit, static_argnames=("interpret", "k"))
            def f(x, interpret, k):
                if interpret:
                    return x * k
                return x

            def _build(use_kernel):
                def body(x):
                    if use_kernel:
                        return _launch(x)
                    return _ref(x)
                return jax.jit(shard_map(body, mesh))
            """)
        assert "CL005" not in rules(findings)

    def test_kernel_kwonly_config_params_are_static(self):
        findings = lint("src/repro/kernels/op.py", """\
            def _flash_kernel(q_ref, o_ref, *, causal, nk):
                if causal:
                    o_ref[...] = q_ref[...]
            """)
        assert "CL005" not in rules(findings)

    def test_untraced_function_passes(self):
        findings = lint("src/repro/kernels/op.py", """\
            def host_side(x):
                if x > 0:
                    return float(x)
                return time.time()
            """)
        assert "CL005" not in rules(findings)


# ---------------------------------------------------------------------------
# CL006 · counter registration
# ---------------------------------------------------------------------------

class TestCounterRegistration:
    REGISTRY = {"src/repro/serve/resilience.py":
                'COUNTER_REGISTRY = frozenset({"retries", "filter"})\n'}

    def test_flags_unregistered_key_write(self):
        findings = lint("src/repro/serve/svc.py", """\
            class Svc:
                def run(self):
                    self.counters["rogue"] += 1
            """, extra=self.REGISTRY)
        (f,) = [f for f in findings if f.rule == "CL006"]
        assert "'rogue'" in f.message

    def test_flags_unregistered_key_through_alias(self):
        findings = lint("src/repro/serve/svc.py", """\
            class Svc:
                def run(self):
                    c = self.counters
                    c["rogue"] += 1
            """, extra=self.REGISTRY)
        assert "CL006" in rules(findings)

    def test_flags_unregistered_factory_and_bump_keys(self):
        findings = lint("src/repro/serve/svc.py", """\
            def new_svc_counters():
                return dict(retries=0, rogue=0)

            class Svc:
                def run(self):
                    self.counters.bump("mystery", launches=1)
            """, extra=self.REGISTRY)
        msgs = [f.message for f in findings if f.rule == "CL006"]
        assert any("'rogue'" in m for m in msgs)
        assert any("'mystery'" in m for m in msgs)

    def test_registered_keys_pass(self):
        findings = lint("src/repro/serve/svc.py", """\
            def new_svc_counters():
                return dict(retries=0)

            class Svc:
                def run(self):
                    c = self.counters
                    c["retries"] += 1
                    self.counters.bump("filter", launches=1)
            """, extra=self.REGISTRY)
        assert "CL006" not in rules(findings)

    def test_non_counter_dicts_ignored(self):
        findings = lint("src/repro/serve/svc.py", """\
            class Svc:
                def run(self):
                    cfg = {}
                    cfg["anything"] = 1
            """, extra=self.REGISTRY)
        assert "CL006" not in rules(findings)

    def test_flags_unregistered_report_section_write(self):
        """PR 10 shape: the front-end attaches a new section to each
        report's counters dict — the section name itself is a counter
        key and must be registered."""
        findings = lint("src/repro/serve/frontend.py", """\
            class Frontend:
                def _execute(self, rep):
                    rep.counters["latency"] = dict(p50_ms=0.0)
            """, extra=self.REGISTRY)
        (f,) = [f for f in findings if f.rule == "CL006"]
        assert "'latency'" in f.message

    def test_registered_latency_family_passes(self):
        reg = {"src/repro/serve/resilience.py":
               'COUNTER_REGISTRY = frozenset({"latency", "p50_ms"})\n'}
        findings = lint("src/repro/serve/frontend.py", """\
            def new_latency_counters():
                return dict(p50_ms=0.0)

            class Frontend:
                def _execute(self, rep):
                    rep.counters["latency"] = dict(new_latency_counters())
            """, extra=reg)
        assert "CL006" not in rules(findings)


# ---------------------------------------------------------------------------
# finding / baseline engine
# ---------------------------------------------------------------------------

BAD_SERVE = """\
LADDER_LAUNCH_SITES = frozenset()

class Svc:
    def sneak(self, lo):
        return kops.prune_ranges_batched_device(lo)
"""


class TestBaselineEngine:
    def _finding(self, pad_lines=0):
        src = ("\n" * pad_lines) + BAD_SERVE
        (f,) = [f for f in lint("src/repro/serve/svc.py", src)
                if f.rule == "CL001"]
        return f

    def test_baseline_suppresses_matching_finding(self):
        f = self._finding()
        bl = Baseline([dict(rule=f.rule, path=f.path, context=f.context,
                            snippet=f.snippet, justification="test")])
        new, accepted = bl.split([f])
        assert not new and accepted == [f]

    def test_baseline_match_is_line_number_independent(self):
        f = self._finding()
        shifted = self._finding(pad_lines=7)
        assert shifted.line != f.line
        bl = Baseline(Baseline.seed([f], justification="test"))
        new, accepted = bl.split([shifted])
        assert not new and accepted == [shifted]

    def test_edited_snippet_resurfaces(self):
        f = self._finding()
        entry = Baseline.seed([f], justification="test")[0]
        entry["snippet"] = entry["snippet"].replace("lo", "hi")
        new, _ = Baseline([entry]).split([f])
        assert new == [f]

    def test_justification_required(self, tmp_path):
        p = tmp_path / "baseline.json"
        p.write_text(json.dumps({"findings": [
            dict(rule="CL001", path="x.py", context="c", snippet="s")]}))
        try:
            Baseline.load(p)
        except ValueError as exc:
            assert "justification" in str(exc)
        else:
            raise AssertionError("missing justification accepted")

    def test_stale_entries_reported(self):
        bl = Baseline([dict(rule="CL001", path="gone.py", context="c",
                            snippet="s", justification="old")])
        assert bl.unused([]) == [dict(rule="CL001", path="gone.py",
                                      context="c", snippet="s",
                                      justification="old")]


class TestCli:
    def _tree(self, tmp_path):
        serve = tmp_path / "src" / "repro" / "serve"
        serve.mkdir(parents=True)
        (serve / "svc.py").write_text(BAD_SERVE)
        return tmp_path

    def test_exit_one_on_new_finding_and_json_artifact(self, tmp_path,
                                                       monkeypatch, capsys):
        root = self._tree(tmp_path)
        monkeypatch.chdir(root)
        out = root / "findings.json"
        assert lint_main(["src/", "--json", str(out)]) == 1
        report = json.loads(out.read_text())
        assert report["new"] and report["new"][0]["rule"] == "CL001"
        assert "CL001" in capsys.readouterr().out

    def test_exit_zero_with_baseline(self, tmp_path, monkeypatch):
        root = self._tree(tmp_path)
        monkeypatch.chdir(root)
        bl = root / "baseline.json"
        assert lint_main(["src/", "--write-baseline", str(bl)]) == 0
        data = json.loads(bl.read_text())
        for e in data["findings"]:
            e["justification"] = "accepted for test"
        bl.write_text(json.dumps(data))
        assert lint_main(["src/", "--baseline", str(bl)]) == 0

    def test_select_restricts_rules(self, tmp_path, monkeypatch):
        root = self._tree(tmp_path)
        monkeypatch.chdir(root)
        assert lint_main(["src/", "--select", "CL004"]) == 0
        assert lint_main(["src/", "--select", "CL001"]) == 1

    def test_list_rules(self, capsys):
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ("CL001", "CL002", "CL003", "CL004", "CL005", "CL006"):
            assert rule in out


class TestRealTreeClean:
    def test_repo_lints_clean_against_committed_baseline(self, monkeypatch):
        monkeypatch.chdir(REPO)
        assert lint_main(["src/", "--baseline",
                          "tools/contract_lint/baseline.json"]) == 0
