"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per row and, for each module,
writes a machine-readable ``BENCH_<module>.json`` (parsed from the same
rows) into ``--json-dir`` so CI and later sessions can diff numbers
without scraping stdout.  Run with ``PYTHONPATH=src python -m
benchmarks.run`` (add ``--only fig13`` to filter, ``--json-dir ''`` to
disable JSON emission).
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

MODULES = [
    "fig01_pruning_ratios",
    "fig03_adaptive_tree",
    "fig04_filter_impact",
    "tab01_limit_frequency",
    "tab02_limit_applicability",
    "fig06_k_cdf",
    "fig08_topk_sorting",
    "fig09_topk_impact",
    "fig10_join_impact",
    "fig11_flow",
    "fig13_tpch",
    "sec81_iceberg",
    "sec82_predicate_cache",
    "kernels_bench",
    "bench_batched_prune",
    "bench_runtime_prune",
]


class _Tee(io.TextIOBase):
    """Write-through to several text sinks (live stdout + capture buffer)."""

    def __init__(self, *sinks):
        self.sinks = sinks

    def write(self, s):
        for sink in self.sinks:
            sink.write(s)
        return len(s)

    def flush(self):
        for sink in self.sinks:
            sink.flush()


def parse_csv_rows(text: str):
    """name,us_per_call,derived lines -> [{name, us_per_call, derived}]."""
    rows = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.split(",", 2)
        if len(parts) != 3:
            continue
        name, us, derived = parts
        try:
            us_val = float(us)
        except ValueError:
            continue
        rows.append(dict(name=name, us_per_call=us_val, derived=derived))
    return rows


def write_module_json(json_dir: str, name: str, rows, seconds: float) -> str:
    path = os.path.join(json_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(dict(module=name, seconds=seconds, rows=rows), f, indent=2)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<module>.json files "
                         "('' disables)")
    args = ap.parse_args()
    # Modules that write their own artifact (EMITS_OWN_JSON) resolve its
    # location from this env var, so --json-dir governs them too.
    os.environ["BENCH_JSON_DIR"] = args.json_dir
    from repro.compile_cache import use_compile_cache
    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    print("name,us_per_call,derived")
    failures = []
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        buf = io.StringIO()
        # Tee, don't buffer: rows keep streaming live (and survive an
        # interrupt mid-module) while the copy feeds the JSON writer.
        tee = _Tee(sys.stdout, buf)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            with contextlib.redirect_stdout(tee):
                mod.main()
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            continue
        dt = time.time() - t0
        if args.json_dir and not getattr(mod, "EMITS_OWN_JSON", False):
            # A JSON write failure must not fail a benchmark that
            # succeeded.  Modules that write their own richer artifact
            # (EMITS_OWN_JSON) are skipped to avoid near-duplicate files.
            try:
                write_module_json(args.json_dir, name,
                                  parse_csv_rows(buf.getvalue()), dt)
            except OSError as e:
                print(f"# {name}: JSON write failed: {e}", file=sys.stderr)
        print(f"# {name} done in {dt:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"{len(failures)} benchmark module(s) failed")


if __name__ == "__main__":
    main()
