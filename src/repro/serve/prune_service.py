"""PruningService: the workload-facing engine of the device plane.

A production metadata service (paper Sec. 2) answers pruning questions for
*every* query of a heavy workload, not one query at a time.  This service
accepts a batch of ``core.flow.Query`` objects and drives the pipeline's
full **technique sequence** (filter -> LIMIT -> JOIN -> top-k) over them,
batching every device-eligible stage per table group:

  * **filter** (``prune_batch``): each scan's predicate is lowered to
    conjunctive ranges; lowered scans are grouped by table and evaluated
    by one ``minmax_prune_batched`` launch per group against the resident
    [C, P] planes (non-lowerable predicates fall back to the host
    evaluator, counted, never wrong);
  * **join** (``join_hit_batch`` / ``bloom_hit_batch``): build-side
    summaries stay host-side (they are runtime values), but the probe-side
    matching runs on the resident planes — the distinct-key overlap as one
    ``join_overlap_batched`` launch per (table, key column) group against
    the join-key plane, and the Bloom narrow-range enumeration as one
    ``bloom_probe_batched`` launch per group against the enumeration
    plane (non-integer key domains keep the host matcher, counted per
    technique under ``join_bloom``);
  * **top-k** (``topk_init_batch``): the Sec. 5.4 upfront boundary is
    initialized as the k-th largest value over each query's
    fully-matching partitions' resident block-top-k rows — one
    ``topk_init_batched`` launch per (table, order column, direction)
    group.

Kernel launches per stage are therefore bounded by the number of distinct
tables (groups), not by the number of queries, and ``run_batch`` produces
``PruningReport``s bit-identical to per-query ``PruningPipeline.run`` in
the same mode (the batched launches evaluate exactly the same per-query
math, packed).

``PruningPipeline(filter_mode="device")`` delegates each stage here for
single queries (Q=1 batches share the same resident planes).

Counters: ``ServiceCounters`` tracks launches and host fallbacks both in
aggregate and per technique (``counters.technique``), and ``run_batch``
attaches a snapshot to every report (``PruningReport.counters``) so
benchmarks can attribute speedups per stage.

Fleet scale (PR 5): ``budget_bytes`` puts every resident plane family
under one HBM budget (``core.device_stats.PlaneMemoryManager`` — LRU
eviction, in-flight pinning around each batched launch, hit / miss /
eviction / restage-storm counters in ``counters["memory"]``), and
``shard_mesh`` partition-shards every batched launch over a 1-D device
mesh (``launch.mesh.make_plane_mesh``) so a table's planes can outgrow
one device.  ``run_fleet`` drives a many-table workload — thousands of
tables churning through the budget — and ``fleet_summary`` reports the
budget-sizing view.

DML: mutations made through the Table's own streaming methods
(``append_partitions`` / ``drop_partitions`` / ``rewrite_partitions`` /
``update_column``) log ``TableDelta``s, and the resident planes
*delta-sync* on the next batch — appends stage O(ΔP), drops scatter
sentinels, nothing is invalidated (``notify_append/drop/rewrite`` keep
the ``TableVersion`` bookkeeping aligned).  The legacy ``notify_insert /
notify_delete / notify_update`` path still bumps the version and
invalidates outright, forcing a full restage — never wrong, just the
pre-ingest cost.  Updates are column-scoped either way: the join-key /
enum / block-top-k planes of *other* columns stay resident (see
``DeviceStatsCache``).  Per-batch staging work and the ``PlaneEpoch``
each table's launches ran against are attached to every report
(``counters["staging"]`` / ``counters["planes"]``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import expr as E
from ..core.device_stats import (TREE_MIN_GROUPS, DeviceStatsCache,
                                 PlaneEpoch, PlaneMemoryManager)
from ..core.metadata import (FULL_MATCH, NO_MATCH, PARTIAL_MATCH, ScanSet,
                             live_full_scan, mask_dead_partitions)
from ..core.predicate_cache import TableVersion
from ..core.prune_filter import eval_tv, extract_ranges
from ..core.prune_join import DEFAULT_ENUM_LIMIT, BuildSummary
from ..kernels import ops as kops
from ..spans import span
from .resilience import (DegradationLadder, new_latency_counters,
                         new_resilience_counters, resilience_delta,
                         resilience_snapshot)

# Registered DegradationLadder launch sites: the only methods allowed to
# call ``kops.*_batched_*`` entrypoints.  Each builds a rung list that is
# executed exclusively through ``self.ladder.execute`` — that is the PR 6
# degradation contract, and the contract linter (tools/contract_lint,
# rule CL001) flags any batched-kernel call outside these methods.  Add
# a method here ONLY if its launches go through the ladder.
LADDER_LAUNCH_SITES = frozenset({
    "PruningService._filter_rungs",
    "PruningService._verdict_group",
    "PruningService.join_hit_batch",
    "PruningService.bloom_hit_batch",
    "PruningService.topk_init_batch",
    # The async front-end's dispatch path (serve/frontend.py): every
    # launch it triggers goes through run_batch, whose stages execute
    # exclusively via the registered rung builders above — registering
    # the dispatch method keeps the reviewed launch-path list complete.
    "ServingFrontend._execute",
})

# Boundary-init k cap: the kernel's rank-selection merge is quadratic in
# (k bucket + KPLANE), so the per-step comparison tensor must stay well
# inside VMEM — at 128 it is [8, 192, 192] (~1.2MB).  Larger k also gains
# little from the plane (each partition contributes at most KPLANE=64
# witnessed rows); such queries keep the host-only init.
TOPK_INIT_MAX_K = 128


@dataclasses.dataclass
class ServiceCounters:
    queries: int = 0
    scans: int = 0
    launches: int = 0          # batched kernel launches, all techniques
    host_fallbacks: int = 0    # host fallbacks, all techniques
    sharded_launches: int = 0  # launches that ran partition-sharded
    tree_launches: int = 0     # launches that ran the hierarchical path
    # per-technique attribution: {'filter': {'launches': n, 'fallbacks': m}};
    # 'join_bloom' also counts Bloom summaries whose filter a matcher
    # read ('built') or that finished the join stage without it
    # ('deferred'); 'filter' also counts launched constraints compared
    # exactly on a wide column's limbs ('wide') and those whose bounds
    # f32 could not hold, so FULL was demoted ('demoted')
    technique: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)

    def bump(self, tech: str, launches: int = 0, fallbacks: int = 0,
             sharded: int = 0, tree: int = 0, built: int = 0,
             deferred: int = 0, wide: int = 0, demoted: int = 0) -> None:
        t = self.technique.setdefault(tech, dict(launches=0, fallbacks=0))
        t["launches"] += launches
        t["fallbacks"] += fallbacks
        if built or deferred:
            t["built"] = t.get("built", 0) + built
            t["deferred"] = t.get("deferred", 0) + deferred
        if wide or demoted:
            t["wide"] = t.get("wide", 0) + wide
            t["demoted"] = t.get("demoted", 0) + demoted
        self.launches += launches
        self.host_fallbacks += fallbacks
        self.sharded_launches += sharded
        self.tree_launches += tree

    def snapshot(self) -> dict:
        return dict(queries=self.queries, scans=self.scans,
                    launches=self.launches,
                    host_fallbacks=self.host_fallbacks,
                    sharded_launches=self.sharded_launches,
                    tree_launches=self.tree_launches,
                    technique={k: dict(v) for k, v in self.technique.items()})

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        """after - before of two snapshots: the activity in between."""
        out = {k: after[k] - before[k]
               for k in ("queries", "scans", "launches", "host_fallbacks",
                         "sharded_launches", "tree_launches")}
        out["technique"] = {
            t: {f: v - before["technique"].get(t, {}).get(f, 0)
                for f, v in fields.items()}
            for t, fields in after["technique"].items()}
        return out


class PruningService:
    # doorkeeper bound: past this many distinct (table, predicate) keys
    # the seen-set resets rather than grow without bound
    VERDICT_SEEN_CAP = 1 << 17

    def __init__(
        self,
        mode: str = "auto",            # kernel mode: auto|pallas|interpret|ref
        cache: Optional[DeviceStatsCache] = None,
        budget_bytes: Optional[int] = None,  # HBM budget across all resident
                                             # plane families (None: unbounded)
        shard_mesh=None,               # 1-D 'parts' mesh (True: build the
                                       # host plane mesh) — partition-shards
                                       # every batched launch
        fault_injector=None,           # serve.resilience.FaultInjector chaos
                                       # seam (None: zero-overhead disabled)
        backoff=None,                  # resilience.BackoffPolicy for the
                                       # degradation ladder's retries
        deadline_s: Optional[float] = None,  # per-rung deadline (seconds)
        clock=None,                    # injectable monotonic clock (tests)
        sleep=None,                    # injectable sleep (tests: no real
                                       # sleeps under the fake clock)
        integrity_sample: Optional[int] = None,  # cache checksum-verify
                                       # schedule: every n-th read (1 =
                                       # every read; None keeps the
                                       # cache's default)
        tree_fanout: Optional[int] = None,  # hierarchical-plane group size
                                       # (None keeps the cache's default;
                                       # tests shrink it so small tables
                                       # exercise the tree rungs)
        verdict_cache: bool = True,    # device-resident verdict plane:
                                       # dedupe canonical predicates per
                                       # batch and serve repeats without
                                       # a launch (False: PR 8 behavior)
    ):
        if mode == "pallas":
            kops.kernel_interpret(mode)   # raises without a TPU
        self.mode = mode
        if cache is None:
            cache = DeviceStatsCache(
                budget_bytes=budget_bytes, fault_injector=fault_injector,
                **({} if integrity_sample is None
                   else dict(integrity_sample=integrity_sample)),
                **({} if tree_fanout is None
                   else dict(tree_fanout=tree_fanout)))
        elif tree_fanout is not None and cache.tree_fanout != tree_fanout:
            # safe on a shared cache: the tree getter's geometry check
            # rebuilds any entry staged under the old fanout
            cache.tree_fanout = int(tree_fanout)
        else:
            # adopt the chaos/integrity configuration onto a shared cache
            # only where it has none of its own (mirrors the budget rule)
            if fault_injector is not None and cache.fault_injector is None:
                cache.fault_injector = fault_injector
            if integrity_sample is not None:
                cache.integrity_sample = int(integrity_sample)
            if budget_bytes is not None:
                # A shared cache's budget belongs to whoever set it: only
                # adopt ours when none is configured — silently
                # re-budgeting a cache other services share would evict
                # planes they sized their budget for.
                if cache.memory.budget_bytes is None:
                    cache.memory.budget_bytes = budget_bytes
                elif cache.memory.budget_bytes != budget_bytes:
                    raise ValueError(
                        f"cache already budgeted at "
                        f"{cache.memory.budget_bytes} bytes; refusing to "
                        f"re-budget to {budget_bytes}")
        self.cache = cache
        if shard_mesh is True:
            from ..launch.mesh import make_plane_mesh
            shard_mesh = make_plane_mesh()
        self.shard_mesh = shard_mesh
        self.versions: Dict[str, TableVersion] = {}
        self.counters = ServiceCounters()
        # The resilience layer: every batched launch executes through the
        # degradation ladder (verdict -> sharded tree -> tree -> sharded
        # -> device -> host kernel -> host oracle -> passthrough; the
        # verdict rung only with the verdict cache enabled, tree rungs
        # only for tables large enough to carry a resident group plane),
        # so a kernel failure, a torn plane, or a
        # deadline costs pruning quality, never correctness and never an
        # exception out of run_batch.  The counters dict is shared with
        # the ladder so demotions/retries surface per batch under
        # ``PruningReport.counters["resilience"]``.
        self.fault_injector = (fault_injector if fault_injector is not None
                               else cache.fault_injector)
        self.verdict_cache = bool(verdict_cache)
        # doorkeeper for seen-once verdict admission (_verdict_group)
        self._verdict_seen: set = set()
        # (stats uid, pred repr) pairs that validated clean (_validate_query)
        self._validated: set = set()
        self.resilience = new_resilience_counters()
        # Service-lifetime latency/SLO block, written by the async
        # front-end (serve.frontend.ServingFrontend) and surfaced through
        # fleet_summary()["latency"]; stays all-zero for synchronous use.
        self.latency = new_latency_counters()
        self.ladder = DegradationLadder(
            policy=backoff, deadline_s=deadline_s, clock=clock, sleep=sleep,
            counters=self.resilience)

    def _fire(self, site: str) -> None:
        if self.fault_injector is not None:
            self.fault_injector.fire(site)

    @staticmethod
    def _sharded() -> int:
        """1 when the launch that just returned actually ran sharded
        (the kernel wrappers can demote a mesh-eligible launch back to
        unsharded when the jnp-oracle footprint exceeds the slab
        bound — the counter reports what ran, not eligibility)."""
        return 1 if kops.last_launch_shards() > 1 else 0

    @staticmethod
    def _tree_ran(tree: bool) -> int:
        """1 when the launch that just returned ran the hierarchical
        path: a tree rung falls back to the flat launch inside the
        wrapper when the table is too small for the tree geometry or the
        coarse survivors are dense, and ``tree_launches`` counts what
        ran, like ``_sharded``."""
        return 1 if tree and kops.last_tree_stats().get("path") == "tree" \
            else 0

    # -- DML bookkeeping ----------------------------------------------------

    def register(self, table) -> TableVersion:
        tv = self.versions.get(table.name)
        if tv is None:
            tv = TableVersion(table.num_partitions)
            self.versions[table.name] = tv
        return tv

    def notify_insert(self, table_name: str, n_partitions: int) -> None:
        tv = self.versions.get(table_name)
        if tv is not None:
            tv.insert_partitions(n_partitions)
        self.cache.on_insert(table_name)

    def notify_delete(self, table_name: str) -> None:
        tv = self.versions.get(table_name)
        if tv is not None:
            tv.version += 1
        self.cache.on_delete(table_name)

    def notify_update(self, table_name: str, column: str) -> None:
        tv = self.versions.get(table_name)
        if tv is not None:
            tv.version += 1
        self.cache.on_update(table_name, column)

    # -- streaming DML (delta-staged; planes stay resident) ----------------
    # Use these when the mutation went through the Table's own DML methods
    # (append_partitions / drop_partitions / rewrite_partitions /
    # update_column): the table's delta log lets the cache sync resident
    # planes in place, so unlike notify_insert/delete/update nothing is
    # invalidated here — only the TableVersion bookkeeping advances.

    def notify_append(self, table_name: str, n_partitions: int) -> None:
        tv = self.versions.get(table_name)
        if tv is not None:
            tv.insert_partitions(n_partitions)

    def notify_drop(self, table_name: str) -> None:
        tv = self.versions.get(table_name)
        if tv is not None:
            tv.version += 1

    def notify_rewrite(self, table_name: str) -> None:
        tv = self.versions.get(table_name)
        if tv is not None:
            tv.version += 1

    def plane_epoch(self, table) -> Optional[PlaneEpoch]:
        """(version, live count, capacity) of the table's resident plane."""
        return self.cache.plane_epoch(table)

    def prestage(self, queries: Sequence) -> int:
        """Prefetch the stat planes a batch of queries will consume —
        the front-end's double-buffer seam: while batch N's launches run
        on the worker, the batcher thread prestages batch N+1's deltas
        so its getters hit resident planes.

        Threads ``pin_scope`` around the prefetches so the
        ``PlaneMemoryManager`` cannot evict a plane this very call just
        staged while admitting the next table under the budget (launch
        scopes re-pin at launch time; pins are refcounts, so a
        concurrent in-flight launch is never evicted either).  Advisory
        and never raises; returns the number of planes that actually
        staged bytes (also counted in ``staging_snapshot()``'s
        ``prefetch_stages``).
        """
        staged = 0
        seen: set = set()
        with self.cache.pin_scope():
            for q in queries:
                for spec in q.scans.values():
                    tkey = id(spec.table)
                    if tkey in seen:
                        continue
                    seen.add(tkey)
                    if self.cache.prefetch(spec.table,
                                           self.versions.get(spec.table.name)):
                        staged += 1
        return staged

    # -- filter stage -------------------------------------------------------

    @staticmethod
    def _scan_set(tv: np.ndarray, table=None) -> ScanSet:
        if table is not None:
            tv = mask_dead_partitions(tv, table)
        keep = tv > NO_MATCH
        return ScanSet(np.where(keep)[0], tv[keep])

    @staticmethod
    def _passthrough_set(table) -> ScanSet:
        """The ladder's bottom rung: keep every live partition, PARTIAL.

        Never FULL — an uncertified partition declared FULL would let the
        LIMIT cutter and the top-k boundary initializers trust rows the
        predicate was never checked against (the same demotion
        ``flow._prune_scan`` applies with the filter stage disabled)."""
        ss = live_full_scan(table)
        return ScanSet(ss.part_ids,
                       np.full(len(ss), PARTIAL_MATCH, dtype=np.int8))

    def _tree_eligible(self, table) -> bool:
        """Should this table's launches enter at the tree rungs?

        Below ``tree_fanout * TREE_MIN_GROUPS`` partitions the flat
        launch always wins (and small-table suites keep their byte-exact
        staging accounting: no tree plane is ever staged for them)."""
        return (table.stats.num_partitions
                >= self.cache.tree_fanout * TREE_MIN_GROUPS)

    def _device_rungs(self, tech: str, launch_fn, table=None) -> list:
        """The device rungs of a ladder chain: tree rungs first when the
        table is large enough to carry a resident group plane (sharded
        tree only with a mesh), then the flat sharded/unsharded rungs.
        ``launch_fn(mesh, rung_site, tree=False)`` builds the thunk; a
        tree-plane fault (staging failure, torn plane) demotes to the
        flat rungs, which never consult the tree family."""
        rungs = []
        if table is not None and self._tree_eligible(table):
            if self.shard_mesh is not None:
                rungs.append(("sharded_tree", launch_fn(
                    self.shard_mesh, f"launch.{tech}:sharded_tree", True)))
            rungs.append(("tree",
                          launch_fn(None, f"launch.{tech}:tree", True)))
        if self.shard_mesh is not None:
            rungs.append(("sharded",
                          launch_fn(self.shard_mesh, f"launch.{tech}:sharded")))
        rungs.append(("device", launch_fn(None, f"launch.{tech}:device")))
        return rungs

    def _filter_rungs(self, table, range_lists, preds) -> list:
        """The filter stage's full rung chain for one table group.

        Every rung returns the same contract: tv ``[Q, P]`` int8 rows
        (None from the passthrough rung — the caller keeps every live
        partition as PARTIAL).  The tree rungs run the hierarchical
        group pre-pass (bit-identical by the hull argument in
        ``kops.prune_ranges_batched_tree``); the host kernel is exact
        f64 over the same lowered ranges; the host oracle re-evaluates
        each predicate tree — both bit-identical to ``eval_tv`` for
        lowerable predicates, so stopping at any rung costs latency,
        not pruning quality.
        """
        def launch(mesh, site, tree=False):
            def thunk():
                self._fire(site)
                # Pin scope: the planes this launch gathers from must not
                # be evicted (by another table's staging under the
                # budget) while the launch is in flight.
                with self.cache.pin_scope():
                    dstats = self.cache.get(table,
                                            self.versions.get(table.name))
                    if tree:
                        te = self.cache.tree_plane(table, dstats)
                        tv = kops.prune_ranges_batched_tree(
                            range_lists, dstats, te, self.mode, mesh=mesh)
                    else:
                        tv = kops.prune_ranges_batched_device(
                            range_lists, dstats, self.mode, mesh=mesh)
                    self.counters.bump("filter", launches=1,
                                       sharded=self._sharded(),
                                       tree=self._tree_ran(tree),
                                       **kops.last_pack_stats())
                return tv
            return thunk

        def host_kernel():
            self._fire("launch.filter:host_kernel")
            tv = kops.prune_ranges_batched_host(range_lists, table.stats)
            self.counters.bump("filter", fallbacks=1)
            return tv

        def host_oracle():
            self._fire("launch.filter:host_oracle")
            tv = np.stack([np.asarray(eval_tv(pred, table.stats),
                                      dtype=np.int8) for pred in preds])
            self.counters.bump("filter", fallbacks=1)
            return tv

        return self._device_rungs("filter", launch, table=table) + [
            ("host_kernel", host_kernel),
            ("host_oracle", host_oracle),
            ("passthrough", lambda: None),
        ]

    def scan_tv(self, spec) -> Optional[np.ndarray]:
        """Device tv [P] for one scan, or None when it doesn't lower (or
        when the ladder degraded past the host kernel — the caller's own
        host evaluator takes over either way).

        The single-query fast path of the batched plane: resident stats,
        Q padded to one sublane tile.  ``PruningPipeline`` calls this for
        ``filter_mode="device"``.  Counts scans/launches/fallbacks like
        prune_batch (``queries`` is only tracked by the batch API, which
        knows query boundaries).
        """
        self.counters.scans += 1
        ranges = extract_ranges(spec.pred, spec.table.stats)
        if ranges is None:
            self.counters.bump("filter", fallbacks=1)
            return None
        # device rungs + host kernel; the terminal rung hands back None
        # so flow's _prune_scan runs its own eval_tv host path
        rungs = self._filter_rungs(spec.table, [ranges], [spec.pred])[:-2]
        rungs.append(("host_oracle", lambda: None))
        tv_rows, _rung = self.ladder.execute(rungs)
        if tv_rows is None:
            self.counters.bump("filter", fallbacks=1)
            return None
        return tv_rows[0]

    def _verdict_group(self, table, jobs) -> list:
        """One table group's filter verdicts through the verdict cache.

        Jobs are deduped by canonical predicate key *before any launch*
        (``verdict_deduped`` counts the saved duplicates), then the
        unique predicates execute through the ladder with the ``verdict``
        rung on top: serve resident verdict rows (a full-hit batch never
        touches a kernel), launch only the missing predicates through
        the ordinary ``_filter_rungs`` chain, and record the fresh
        verdicts.  A verdict-plane integrity failure fails the rung and
        the ladder demotes to the flat chain — cache-off is a demotion,
        never a wrong answer.  Returns one ``[P]`` int8 row (or None for
        passthrough) per job, duplicates fanned back out.

        Admission is seen-once (a doorkeeper, as in TinyLFU): a
        predicate earns a resident verdict row only on its *second*
        sighting — in-batch repetition counts, so zipf/dashboard traffic
        is admitted on its first batch, while one-shot exploratory
        predicates never pay the record cost (HBM + checksum stamp) on
        top of their launch.
        """
        ckeys = [E.canonical_key(pred) for _, _, _, pred in jobs]
        uniq: Dict[str, int] = {}
        counts: Dict[str, int] = {}
        u_ranges: list = []
        u_preds: list = []
        for (_, _, ranges, pred), ck in zip(jobs, ckeys):
            counts[ck] = counts.get(ck, 0) + 1
            if ck not in uniq:
                uniq[ck] = len(u_preds)
                u_ranges.append(ranges)
                u_preds.append(pred)
        self.resilience["verdict_deduped"] += len(jobs) - len(u_preds)
        u_keys = list(uniq)
        admit = [counts[ck] > 1 or (table.name, ck) in self._verdict_seen
                 for ck in u_keys]
        if len(self._verdict_seen) > self.VERDICT_SEEN_CAP:
            self._verdict_seen.clear()      # doorkeeper reset, TinyLFU-style
        self._verdict_seen.update((table.name, ck) for ck in u_keys)

        def verdict_rung():
            rows: list = [None] * len(u_keys)
            miss: list = []
            # Pin scope: served verdict rows stay resident while the
            # misses' launch consumes the stat planes.
            with self.cache.pin_scope():
                with span("filter.verdict", q=len(u_keys)):
                    for i, (ck, pred) in enumerate(zip(u_keys, u_preds)):
                        row = self.cache.verdict_plane(table, pred, ck)
                        if row is None:
                            miss.append(i)
                        else:
                            rows[i] = row
                    self.resilience["verdict_hits"] += len(u_keys) - len(miss)
                    self.resilience["verdict_misses"] += len(miss)
                if miss:
                    tv_rows, rung = self.ladder.execute(self._filter_rungs(
                        table, [u_ranges[i] for i in miss],
                        [u_preds[i] for i in miss]))
                    if tv_rows is not None:
                        with span("filter.verdict", q=len(miss)):
                            for mi, tv in zip(miss, tv_rows):
                                row = np.asarray(tv, dtype=np.int8)
                                rows[mi] = row
                                if rung != "passthrough" and admit[mi]:
                                    self.cache.verdict_record(
                                        table, u_preds[mi], u_keys[mi], row)
            return rows

        u_rows, _rung = self.ladder.execute(
            [("verdict", verdict_rung)]
            + self._filter_rungs(table, u_ranges, u_preds))
        u_rows = ([None] * len(u_keys) if u_rows is None else list(u_rows))
        return [u_rows[uniq[ck]] for ck in ckeys]

    def prune_batch(self, queries: Sequence) -> List[Dict[str, ScanSet]]:
        """Filter-prune a batch of queries; per-query scan_name -> ScanSet.

        One batched kernel launch per distinct table (not per query),
        executed through the degradation ladder; queries whose predicates
        don't lower are evaluated on the host, and a scan whose every
        prover failed (malformed spec slipping past validation) degrades
        to a keep-everything PARTIAL set — counted, never raised.
        """
        self.counters.queries += len(queries)
        results: List[Dict[str, ScanSet]] = [dict() for _ in queries]
        # id(table) -> (table, [(query idx, scan name, ranges, pred), ...])
        groups: Dict[int, Tuple[object, list]] = {}
        fallbacks: List[Tuple[int, str, object]] = []
        with span("filter.plan", q=len(queries)):
            for qi, q in enumerate(queries):
                for name, spec in q.scans.items():
                    self.counters.scans += 1
                    if isinstance(spec.pred, E.TruePred):
                        results[qi][name] = live_full_scan(spec.table)
                        continue
                    try:
                        ranges = extract_ranges(spec.pred, spec.table.stats)
                    except Exception:
                        # malformed spec (unknown column / bad literal):
                        # isolate to this scan, keep the batch on course
                        self.resilience["errors"] += 1
                        results[qi][name] = self._passthrough_set(spec.table)
                        continue
                    if ranges is None:
                        fallbacks.append((qi, name, spec))
                        continue
                    groups.setdefault(id(spec.table),
                                      (spec.table, []))[1].append(
                        (qi, name, ranges, spec.pred))
        for table, jobs in groups.values():
            if self.verdict_cache:
                rows = self._verdict_group(table, jobs)
            else:
                tv_rows, _rung = self.ladder.execute(self._filter_rungs(
                    table, [ranges for _, _, ranges, _ in jobs],
                    [pred for _, _, _, pred in jobs]))
                rows = ([None] * len(jobs) if tv_rows is None
                        else list(tv_rows))
            # deduped jobs share one tv row OBJECT: materialize the O(P)
            # scan set once per unique row, give each query its own
            # ScanSet over the shared (read-only) arrays
            memo: Dict[int, ScanSet] = {}
            with span("filter.assemble", q=len(jobs),
                      P=table.stats.num_partitions):
                for (qi, name, _ranges, _pred), tv in zip(jobs, rows):
                    if tv is None:
                        results[qi][name] = self._passthrough_set(table)
                        continue
                    ss = memo.get(id(tv))
                    if ss is None:
                        memo[id(tv)] = ss = self._scan_set(tv, table)
                    results[qi][name] = ScanSet(ss.part_ids, ss.match)
        if fallbacks:
            with span("filter.host_eval", q=len(fallbacks)):
                for qi, name, spec in fallbacks:
                    self.counters.bump("filter", fallbacks=1)
                    try:
                        tv = eval_tv(spec.pred, spec.table.stats)
                    except Exception:
                        self.resilience["errors"] += 1
                        results[qi][name] = self._passthrough_set(spec.table)
                        continue
                    results[qi][name] = self._scan_set(tv, spec.table)
        return results

    # -- join stage ---------------------------------------------------------

    def join_device_eligible(self, summary: BuildSummary, table=None,
                             key_col: Optional[str] = None) -> bool:
        """Can this summary's probe-side matching run on the device plane?

        Distinct summaries need their keys finite in f32 (join-key plane
        overlap).  Bloom summaries need the probe table/key column: the
        kernel's narrow-range enumeration hashes *int32* candidates with
        the shared murmur mixer, so the key column must be an
        integer/dictionary domain wholly inside int32 — fractional or
        out-of-range keys keep the host matcher so batched output stays
        bit-identical to it — and the filter must fit the kernel's block
        cap (``kops.BLOOM_MAX_BLOCKS``).  The int32-domain check is the
        cached ``domain_ok`` of the enumeration plane — table-version
        invariant, so eligibility never rescans [P] stats per query.
        Empty summaries are host short-circuits, not kernel work.
        """
        if summary.empty:
            return False
        if summary.kind == "distinct":
            d32 = np.asarray(summary.distinct,
                             dtype=np.float64).astype(np.float32)
            return bool(np.isfinite(d32).all())
        if summary.kind != "bloom" or table is None or key_col is None:
            return False
        if summary.n_blocks > kops.BLOOM_MAX_BLOCKS:
            return False
        if table.stats.column(key_col).kind == "float":
            return False
        return self.cache.enum_plane(table, key_col)[3]

    def join_hit_batch(self, table, key_col: str,
                       summaries: Sequence[BuildSummary],
                       part_ids: Optional[Sequence[np.ndarray]] = None
                       ) -> Optional[np.ndarray]:
        """hit [G, P] for a (table, key column) group — one launch.

        ``part_ids`` optionally restricts the no-Pallas fallback to each
        query's scan set (entries outside it are 0 and must not be read);
        the kernel path always evaluates the resident plane dense.
        Returns None when the ladder degraded past the device rungs —
        the caller's host matcher is this stage's exact terminal rung
        (``prune_probe`` recomputes the overlap from host truth, so a
        degraded join loses latency, never pruning quality).
        """
        def launch(mesh, site, tree=False):
            def thunk():
                self._fire(site)
                with self.cache.pin_scope():
                    pmin, pmax = self.cache.join_key_plane(table, key_col)
                    dist = [s.distinct for s in summaries]
                    if tree:
                        dstats = self.cache.get(table,
                                                self.versions.get(table.name))
                        te = self.cache.tree_plane(table, dstats)
                        hit = kops.join_overlap_batched_tree(
                            dist, pmin, pmax, te,
                            table.stats.col_id(key_col), self.mode,
                            part_ids_lists=part_ids, mesh=mesh)
                    else:
                        hit = kops.join_overlap_batched_device(
                            dist, pmin, pmax, self.mode,
                            part_ids_lists=part_ids, mesh=mesh)
                    self.counters.bump("join", launches=1,
                                       sharded=self._sharded(),
                                       tree=self._tree_ran(tree))
                return hit
            return thunk

        def host_oracle():
            self.counters.bump("join", fallbacks=len(summaries))
            return None

        hit, _rung = self.ladder.execute(
            self._device_rungs("join", launch, table=table)
            + [("host_oracle", host_oracle)])
        return hit

    def bloom_hit_batch(self, table, key_col: str,
                        summaries: Sequence[BuildSummary],
                        part_ids: Optional[Sequence[np.ndarray]] = None,
                        enum_limit: int = DEFAULT_ENUM_LIMIT
                        ) -> Optional[np.ndarray]:
        """hit [G, P] for a (table, key column) group of Bloom summaries —
        one batched narrow-range enumeration launch over the resident
        enumeration plane (``part_ids`` restricts the no-Pallas fallback
        to each query's scan set, like ``join_hit_batch``).  None when
        the ladder degraded to the exact host matcher."""
        def launch(mesh, site, tree=False):
            def thunk():
                self._fire(site)
                with self.cache.pin_scope():
                    pmin, width, wmax, _domain_ok = self.cache.enum_plane(
                        table, key_col)
                    blooms = [s.bloom for s in summaries]
                    if tree:
                        dstats = self.cache.get(table,
                                                self.versions.get(table.name))
                        te = self.cache.tree_plane(table, dstats)
                        hit = kops.bloom_probe_batched_tree(
                            blooms, pmin, width, wmax, enum_limit, te,
                            self.mode, part_ids_lists=part_ids, mesh=mesh)
                    else:
                        hit = kops.bloom_probe_batched_device(
                            blooms, pmin, width, wmax, enum_limit,
                            self.mode, part_ids_lists=part_ids, mesh=mesh)
                    self.counters.bump("join_bloom", launches=1,
                                       sharded=self._sharded(),
                                       tree=self._tree_ran(tree))
                return hit
            return thunk

        def host_oracle():
            self.counters.bump("join_bloom", fallbacks=len(summaries))
            return None

        hit, _rung = self.ladder.execute(
            self._device_rungs("join_bloom", launch, table=table)
            + [("host_oracle", host_oracle)])
        return hit

    def join_hit(self, table, key_col: str, summary: BuildSummary,
                 part_ids: Optional[np.ndarray] = None
                 ) -> Optional[np.ndarray]:
        """hit [P] for one query, or None -> host path (counted per
        technique — ``join`` for distinct, ``join_bloom`` for Bloom —
        unless the summary is empty, which the host handles as a trivial
        wipe)."""
        if not self.join_device_eligible(summary, table, key_col):
            if not summary.empty:
                self.counters.bump(
                    "join_bloom" if summary.kind == "bloom" else "join",
                    fallbacks=1)
            return None
        pid = None if part_ids is None else [part_ids]
        if summary.kind == "distinct":
            hit = self.join_hit_batch(table, key_col, [summary],
                                      part_ids=pid)
        else:
            hit = self.bloom_hit_batch(table, key_col, [summary],
                                       part_ids=pid)
        # None: the ladder degraded to the host matcher terminal rung
        return None if hit is None else hit[0]

    # -- top-k stage --------------------------------------------------------

    def topk_init_batch(self, table, order_col: str, desc: bool,
                        jobs: Sequence[Tuple[ScanSet, int]]) -> List[float]:
        """Per-query upfront boundaries for a (table, column, direction)
        group — one ``topk_init_batched`` launch.

        Each job is ``(scan_set, effective_k)``; the boundary is the k-th
        largest resident block-top-k value over the scan set's
        fully-matching partitions (signed domain), or -inf when fewer
        than k candidates exist.  Launch heaps are sized to the group's
        k bucket; a prefix of a larger heap is the exact smaller-k
        answer, so mixed-k groups share one launch.
        """
        # Jobs whose k is out of the useful range never consult the heap —
        # exclude them up front so they neither widen the group's k bucket
        # (merge cost grows with kb^2) nor force a launch alone.
        live: List[Tuple[int, ScanSet, int]] = []
        any_candidates = False
        for i, (scan, k) in enumerate(jobs):
            if scan.match is None or not (0 < int(k) <= TOPK_INIT_MAX_K):
                continue
            live.append((i, scan, int(k)))
        out = [-np.inf] * len(jobs)
        if not live:
            return out
        P = table.num_partitions
        masks = np.zeros((len(live), P), dtype=np.float32)
        for row, (_i, scan, _k) in enumerate(live):
            full_ids = scan.part_ids[scan.match == FULL_MATCH]
            masks[row, full_ids] = 1.0
            any_candidates |= full_ids.size > 0
        if not any_candidates:
            return out                     # nothing to bound; skip the launch
        kb = kops.k_bucket(max(k for _, _, k in live))

        def launch(mesh, site, tree=False):
            def thunk():
                self._fire(site)
                with self.cache.pin_scope():
                    plane = self.cache.block_topk_plane(table, order_col,
                                                        desc)
                    if tree:
                        dstats = self.cache.get(table,
                                                self.versions.get(table.name))
                        te = self.cache.tree_plane(table, dstats)
                        heap = kops.topk_init_batched_tree(
                            plane, masks, kb, te, self.mode, mesh=mesh)
                    else:
                        heap = kops.topk_init_batched_device(
                            plane, masks, kb, self.mode, mesh=mesh)
                    self.counters.bump("topk", launches=1,
                                       sharded=self._sharded(),
                                       tree=self._tree_ran(tree))
                return heap
            return thunk

        def host_oracle():
            # -inf floors: run_topk's own boundary discovery takes over —
            # a weaker starting boundary, never a wrong result
            self.counters.bump("topk", fallbacks=1)
            return None

        heap, _rung = self.ladder.execute(
            self._device_rungs("topk", launch, table=table)
            + [("host_oracle", host_oracle)])
        if heap is None:
            return out
        for row, (i, _scan, k) in enumerate(live):
            out[i] = float(heap[row, k - 1])
        return out

    def topk_init(self, table, scan: ScanSet, order_col: str, desc: bool,
                  k: int) -> float:
        """One query's upfront boundary from the resident plane (signed)."""
        if (scan.match is None or k <= 0 or k > TOPK_INIT_MAX_K
                or not (scan.match == FULL_MATCH).any()):
            return -np.inf
        return self.topk_init_batch(table, order_col, desc, [(scan, k)])[0]

    # -- workload driver ----------------------------------------------------

    def _validate_query(self, q) -> None:
        """Raise the spec's own error for a malformed query spec.

        Probes each scan's predicate against a one-partition stats slice
        (O(1) per scan, not O(P)) so unknown columns and bad literal
        dtypes surface *here*, at validation time — ``run_batch``
        isolates the raise to this query instead of letting it abort the
        batch mid-launch.  Join/order-by column names are checked the
        same way.

        Validity is a pure function of (stats identity, predicate) — a
        table's schema and dtypes are fixed for its lifetime — so clean
        probes are memoized: repeated traffic re-validates by a set
        lookup instead of a per-query probe walk.  Failed probes are
        never cached (a malformed spec raises every time).
        """
        for spec in q.scans.values():
            stats = spec.table.stats
            vkey = (stats.uid, repr(spec.pred))
            if vkey in self._validated:
                continue
            probe = (stats.select(np.zeros(1, dtype=np.int64))
                     if stats.num_partitions > 1 else stats)
            eval_tv(spec.pred, probe)
            if len(self._validated) > self.VERDICT_SEEN_CAP:
                self._validated.clear()
            self._validated.add(vkey)
        if q.join is not None:
            for scan_name, col in ((q.join.build, q.join.build_key),
                                   (q.join.probe, q.join.probe_key)):
                q.scans[scan_name].table.stats.col_id(col)
        if q.order_by is not None:
            scan_name, col, _desc = q.order_by
            q.scans[scan_name].table.stats.col_id(col)

    def _passthrough_report(self, pipeline, q):
        """A no-prune report for a query the engine refused to run
        (malformed spec / unsalvageable failure): every scan keeps all
        live partitions as PARTIAL, no technique applied."""
        from ..core.flow import TechniqueReport
        st = pipeline.make_state(q)
        for name, spec in q.scans.items():
            ss = self._passthrough_set(spec.table)
            st.scan_sets[name] = ss
            st.per_scan[name]["filter"] = TechniqueReport(
                spec.table.num_partitions, len(ss), applied=False,
                detail=dict(path="passthrough"))
        return pipeline.finish(st)

    def run_batch(self, queries: Sequence, pipeline=None) -> List:
        """Full pruning pipelines over a workload, every device-eligible
        stage batched per table group.

        Returns one ``PruningReport`` per query, identical to running
        ``pipeline.run(q)`` per query in the same mode.  Each report
        carries its own copy of THIS batch's counter delta (not the
        service-lifetime totals) for per-stage attribution, including the
        resilience block (``counters["resilience"]``: retries, demotions
        per rung, passthroughs, deadline hits, isolated errors) and the
        plane-integrity block (``counters["integrity"]``).

        Failure contract: ``run_batch`` never raises for a query-shaped
        problem.  Malformed specs are caught at validation time and
        returned as no-prune passthrough reports (``errors`` counter);
        launch/staging/plane failures degrade through the ladder inside
        each stage; an unexpected batch-level failure falls back to
        per-query execution, and a query that still fails gets a
        passthrough report.
        """
        from ..core.flow import PruningPipeline
        if pipeline is None:
            pipeline = PruningPipeline(filter_mode="device", service=self)
        with span("run_batch", q=len(queries)):
            # Only batch device stages when the pipeline itself declares the
            # device path — a host/adaptive pipeline keeps its own semantics.
            device = not pipeline.adaptive and pipeline.filter_mode == "device"
            with span("account"):
                before = self.counters.snapshot()
                before_staging = self.cache.staging_snapshot()
                before_memory = self.cache.memory.snapshot()
                before_res = resilience_snapshot(self.resilience)
                before_integrity = self.cache.integrity_snapshot()
            # satellite: per-query spec validation — one malformed query
            # becomes one passthrough report, the rest stay on the fast path
            invalid: Dict[int, object] = {}
            valid: List[Tuple[int, object]] = []
            with span("validate", q=len(queries)):
                for i, q in enumerate(queries):
                    try:
                        self._validate_query(q)
                    except Exception:
                        self.resilience["errors"] += 1
                        invalid[i] = q
                    else:
                        valid.append((i, q))
                states = [pipeline.make_state(q) for _, q in valid]
            try:
                for tech in pipeline.techniques:
                    with span("technique." + tech.name, q=len(states)):
                        tech.run_batch(pipeline, states,
                                       service=self if device else None)
                with span("finish", q=len(states)):
                    good = [pipeline.finish(s) for s in states]
            except Exception as exc:
                # Last-resort guard: something outside the ladder's reach
                # broke the batched drive (a host-stage bug, a summary raise).
                # Salvage per query; a query that still fails degrades to a
                # passthrough report instead of taking the batch down.
                self.resilience["salvaged_batches"] += 1
                self.ladder.last_errors["run_batch"] = exc
                good = []
                for _i, q in valid:
                    try:
                        good.append(pipeline.run(q))
                    except Exception:
                        self.resilience["errors"] += 1
                        good.append(self._passthrough_report(pipeline, q))
            reports: List = [None] * len(queries)
            for (i, _q), rep in zip(valid, good):
                reports[i] = rep
            for i, q in invalid.items():
                reports[i] = self._passthrough_report(pipeline, q)
            with span("account"):
                delta = ServiceCounters.delta(before, self.counters.snapshot())
                after_staging = self.cache.staging_snapshot()
                staging = {k: after_staging[k] - before_staging[k]
                           for k in after_staging}
                memory = PlaneMemoryManager.delta(before_memory,
                                                  self.cache.memory.snapshot())
                res = resilience_delta(before_res,
                                       resilience_snapshot(self.resilience))
                after_integrity = self.cache.integrity_snapshot()
                integrity = {k: after_integrity[k] - before_integrity[k]
                             for k in after_integrity}
                # PlaneEpoch per table touched by the batch: what the launches
                # actually ran against (version, live count, capacity) — the
                # check that a delta-staged batch served the same table state
                # a fresh restage would.
                planes: Dict[str, dict] = {}
                for q in queries:
                    for spec in q.scans.values():
                        epoch = self.cache.plane_epoch(spec.table)
                        if epoch is not None:
                            planes[spec.table.name] = dataclasses.asdict(epoch)
                for r in reports:
                    # each report owns its copy — mutating one never leaks
                    r.counters = {**delta,
                                  "technique": {k: dict(v) for k, v
                                                in delta["technique"].items()},
                                  "staging": dict(staging),
                                  "memory": dict(memory),
                                  "resilience": {**res, "demotions":
                                                 dict(res["demotions"])},
                                  "integrity": dict(integrity),
                                  "planes": {k: dict(v)
                                             for k, v in planes.items()}}
            return reports

    def run_fleet(self, batches: Sequence[Sequence], pipeline=None) -> List:
        """The fleet-scale entry point: a *many-table* workload — a
        sequence of query batches (e.g. rounds of skewed table
        popularity) — driven through ``run_batch`` under the configured
        memory budget and shard mesh.

        Returns one report list per batch.  Each batch's reports carry
        that batch's counter deltas (``counters["memory"]`` shows the
        hits / misses / evictions / restage storms the LRU plane manager
        paid for it); ``fleet_summary()`` aggregates the service-lifetime
        view for budget sizing.
        """
        return [self.run_batch(b, pipeline) for b in batches]

    def fleet_summary(self) -> dict:
        """Service-lifetime memory + staging + launch counters: the
        budget-sizing view (is the budget thrashing? what fraction of
        getter traffic hit resident planes?)."""
        mem = self.cache.memory.snapshot()
        total = mem["hits"] + mem["misses"]
        return dict(memory=mem,
                    staging=self.cache.staging_snapshot(),
                    counters=self.counters.snapshot(),
                    resilience=resilience_snapshot(self.resilience),
                    integrity=self.cache.integrity_snapshot(),
                    latency=dict(self.latency),
                    plane_hit_rate=(mem["hits"] / total) if total else 0.0)
