"""Executor: optimized logical plan -> DeviceBatch pipeline.

This is the TPU counterpart of the reference's custom physical path
(`PhysicalPlanner::create_physical_plan` + operator `execute()` streams,
crates/engine/src/physical_planner.rs:23-140, physical_plan.rs:28-47) — with the
key architectural inversion from SURVEY.md §7: instead of streaming RecordBatches
through async operator objects, each pipeline region (scan -> filter -> project)
compiles into ONE jitted function over a DeviceBatch, and blocking operators
(aggregate / join / sort) are separate jitted stages stitched by host code.

Host syncs happen only where shapes must be decided (join candidate totals,
capacity shrinking between stages) — each is one scalar readback.

Jit compile caching is fingerprint-based: (node expression fingerprint, input
batch prototype) -> compiled callable, so repeated queries over the same tables
reuse executables across QueryEngine.execute calls.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from igloo_tpu import types as T
from igloo_tpu.errors import ExecError, NotSupportedError, PlanError
from igloo_tpu.exec import kernels as K
from igloo_tpu.exec.aggregate import (
    AggSpec, agg_out_bounds, aggregate_batch, distinct_batch,
    groups_in_place, minmax_order_arg, pair_sums_for, seg_dims_for,
    stage_scope, uncompacted_filter,
)
from igloo_tpu.exec.batch import (
    DeviceBatch, DeviceColumn, DictInfo, device_columns, from_arrow,
    host_decode_column, pair_halves, round_capacity, to_arrow, wide_values,
)
from igloo_tpu.exec.expr_compile import (
    Compiled, ConstPool, Env, ExprCompiler, _unify_dicts,
)
from igloo_tpu.exec.join import (
    choose_direct_build, choose_match_capacity, direct_join_phase, expand_phase,
    join_batches, make_key_hash_idxs, match_by_search, probe_phase,
)
from igloo_tpu.exec.fused import FusedCompiler, FusionUnsupported
from igloo_tpu.exec.sort_limit import (
    limit_batch, plan_topk, sort_batch, topk_batch,
)
from igloo_tpu.plan import expr as E
from igloo_tpu.plan import logical as L
from igloo_tpu.sql.ast import JoinType
from igloo_tpu.utils import stats, tracing

_SHRINK_FACTOR = 4  # shrink a batch when capacity > factor * needed

import os as _os  # noqa: E402

# print each first-in-process program build (kind + fingerprint) to stderr:
# the last line before a hang names the program whose XLA compile is
# pathological (a profiler sees only one long compile call)
_LOG_COMPILES = _os.environ.get("IGLOO_TPU_LOG_COMPILES", "") == "1"

_SENTINEL = object()  # "use the plan's projection" marker for read_scan_table


class _ScanLoadClock:
    """The three parts of a scan's miss path, timed from inside
    `program.scan_load` and added to counters where the load ends — no child
    spans: the span's self time keeps its meaning. `scan_load.read_us`: the
    provider's read and the Arrow decode, to `read_done`; `scan_load.h2d_us`:
    handing the arrays to the device (`put_s`, which `codec.upload_columns`
    adds to) and, for a load that enters a cache (`wait`), waiting until
    they are there — a `device_put` returns in 0.4 ms whatever its size and
    the copy ends later (43 ms for 256 MB, 0.37 ms for eight rows: chip
    probe, PR 38), so without the wait the span closes with the copy in
    flight and the next program's first wait pays for it. An `ephemeral`
    provider's load (a fragment's dependency, every query) does not wait:
    its one consumer is dispatched next and waits for the device anyway,
    and the copy's tail overlaps that dispatch's host work. `scan_load
    .codec_us`: from the read's end to the last array handed over, less
    `put_s` — host decode, the codec's proofs and narrowing, the f32 pair's
    split, padding. `scan_load.columns` counts the columns loaded. What is
    left of the span (cache bookkeeping, the live lane) is in none of the
    three."""

    __slots__ = ("t0", "t_read", "put_s")

    def __init__(self):
        self.t0 = self.t_read = time.perf_counter()
        self.put_s = 0.0

    def read_done(self) -> None:
        self.t_read = time.perf_counter()

    def done(self, arrays, columns: int, wait: bool = True) -> None:
        t1 = t_put = time.perf_counter()
        if wait:
            jax.block_until_ready(arrays)
            t1 = time.perf_counter()
        tracing.counter("scan_load.read_us",
                        round((self.t_read - self.t0) * 1e6))
        tracing.counter("scan_load.codec_us", max(round(
            (t_put - self.t_read - self.put_s) * 1e6), 0))
        tracing.counter("scan_load.h2d_us",
                        round((self.put_s + t1 - t_put) * 1e6))
        tracing.counter("scan_load.columns", columns)


def read_scan_table(plan: L.Scan, projection=_SENTINEL) -> pa.Table:
    """Host-side scan IO honoring the plan's partition restriction. Replaces
    the reference's whole-table-only reads (parquet_scan.rs streams fixed
    1024-row batches but custom operators are single-stream) with explicit
    provider partitions the distributed planner / chunked executor slice.
    `projection` overrides the plan's (the column-granular scan cache reads
    only the columns it is missing).

    Partitioned reads first consult the query's storage prefetcher
    (storage/prefetch.py, installed by the chunked/GRACE feeds): a partition
    the reader thread already decoded is handed over without touching the
    source (counter `storage.prefetch_hit`); anything else reads
    synchronously."""
    proj = plan.projection if projection is _SENTINEL else projection
    if plan.partition is None:
        return plan.provider.read(projection=proj,
                                  filters=plan.pushed_filters)
    tok_fn = getattr(plan.provider, "partition_token", None)
    if plan.partition_token is not None and tok_fn is not None:
        cur = tok_fn()
        if cur != plan.partition_token:
            from igloo_tpu.errors import SnapshotChanged
            raise SnapshotChanged(
                f"partition index for {plan.table} changed since planning "
                "(source files moved/replaced)", table=plan.table)
    from igloo_tpu.storage import prefetch as _prefetch
    parts = [t for _, t in _prefetch.take_partitioned(
        plan.provider, plan.partition, proj, plan.pushed_filters)]
    return pa.concat_tables(parts) if parts else \
        plan.provider.read(projection=proj,
                           filters=plan.pushed_filters).slice(0, 0)


def batch_proto_key(batch: DeviceBatch):
    """Hashable prototype of a batch: everything that affects tracing. NOTE:
    deliberately dictionary-free — dictionary content reaches compiled code
    through ConstPool arguments, so only const SHAPES (in the pool signature)
    key the compile cache (round-1 verdict fix: content-keyed DictInfo in
    static aux forced a recompile for every new dictionary)."""
    return (batch.schema, batch.capacity,
            tuple(c.nulls is not None for c in batch.columns),
            # carrier-resident columns trace different programs (narrow lane
            # dtypes + in-jit widens), so the carrier form is part of the
            # prototype; data-dependent payloads (offset value) are NOT
            tuple((str(c.values.dtype), c.carrier.key())
                  if c.carrier is not None else None for c in batch.columns))


def expr_fingerprint(exprs) -> str:
    """The key form of a staged program's expressions: `E.shape` (type and
    position of a literal, not its value: the value is an argument)."""
    return E.shape(exprs)


def strip_dicts(batch: DeviceBatch) -> DeviceBatch:
    """Drop host-side metadata (dictionaries, bounds) before a batch crosses
    into jax.jit, so the pytree aux (= compile-cache key) is content-free."""
    from dataclasses import replace
    return DeviceBatch(batch.schema,
                       [replace(c, dictionary=None, bounds=None)
                        for c in batch.columns],
                       batch.live)


def attach_dicts(batch: DeviceBatch, dicts, bounds=None) -> DeviceBatch:
    """Re-attach per-column dictionaries + value bounds (host metadata) to a
    jit output. `bounds` defaults to all-unknown."""
    from dataclasses import replace
    if bounds is None:
        bounds = [None] * len(dicts)
    return DeviceBatch(batch.schema,
                       [replace(c, dictionary=d, bounds=b)
                        for c, d, b in zip(batch.columns, dicts, bounds)],
                       batch.live)


def col_meta(cols) -> tuple[list, list]:
    """(dicts, bounds) of a column list, for attach_dicts after a 1:1 jit."""
    return [c.dictionary for c in cols], [c.bounds for c in cols]


def _note_carrier_ratio(provider, batch: DeviceBatch) -> None:
    """Record the observed HBM carrier/wide byte ratio of a freshly scanned
    batch against its provider instance, so the GRACE trigger and the
    optimizer's join order (chunked.table_lane_bytes) size this table in
    carrier bytes."""
    if provider is None or not batch.columns:
        return
    from igloo_tpu.exec.codec import record_carrier_ratio
    narrow = wide = 0
    for f, c in zip(batch.schema, batch.columns):
        wide += c.capacity * np.dtype(f.dtype.device_dtype()).itemsize
        narrow += c.carrier_nbytes
    record_carrier_ratio(provider, narrow, wide)
    if stats.detail_active():
        # EXPLAIN ANALYZE: which scans ride carriers and how hard — resident
        # vs would-be-wide bytes, per scan op; the float64 columns held as
        # f32 pairs (as wide as they were: what they save is the chip's
        # split pass) by name
        stats.annotate(encoded_lanes=sum(1 for c in batch.columns
                                         if c.carrier is not None),
                       carrier_bytes=narrow, decoded_bytes=wide,
                       f32pair_columns=[
                           f.name for f, c in zip(batch.schema, batch.columns)
                           if c.is_pair])


# per-query D2H accounting at the executor's fetch sites
record_fetch = stats.record_fetch


class _Program:
    """What `_jitted` hands out: the program, each call inside a span.
    After a miss of the in-memory jit cache the first call is
    `program.first_call` — where jax traces, lowers and loads from the
    persistent cache or compiles, synchronously, before it dispatches —
    and is booked as the current operator's compile cost (EXPLAIN ANALYZE);
    every other call is `program.dispatch`, the asynchronous dispatch of a
    program jax already holds. Never cached: the cache keeps the raw fn."""
    __slots__ = ("fn", "kind", "first")

    def __init__(self, fn, kind: str, first: bool):
        self.fn = fn
        self.kind = kind
        self.first = first

    def __call__(self, *args, **kw):
        if not self.first:
            with tracing.span("program.dispatch", kind=self.kind):
                return self.fn(*args, **kw)
        self.first = False
        cm = tracing.span("program.first_call", kind=self.kind)
        try:
            with cm:
                return self.fn(*args, **kw)
        finally:
            stats.record_compile(cm.span.elapsed_s)


class Executor:
    # Speculative join expand: when both inputs fit the budget, expand with
    # capacity max(left, right) WITHOUT syncing on the exact candidate total.
    # That bound is exact for FK joins (every TPC-H join: one side's keys are
    # unique, so total <= max live side); overflow (a genuine many-to-many
    # blowup) only DROPS candidates past the cap — expand masks by the true
    # total — so the deferred device-side `total > cap` flags checked at the
    # final fetch make the fallback (exact re-execution, one sync per join)
    # fully correct. Saves one device->host sync per join (warm Q5 spent
    # 5 of its 7 syncs here).
    _SPECULATIVE_JOIN_BUDGET = 1 << 22

    def __init__(self, jit_cache: Optional[dict] = None, use_jit: bool = True,
                 batch_cache=None, speculate: bool = True, hints=None):
        # shared across queries when the engine passes its own cache dict
        self._cache = jit_cache if jit_cache is not None else {}
        self._use_jit = use_jit
        self._batch_cache = batch_cache  # Optional[BatchCache]
        self._speculate = speculate
        self._hints = hints  # Optional[HintStore] (persistent nhints)
        # ORDER BY + LIMIT fusion handshake (staged tier): _exec_limit sets
        # the hint before descending into its Sort child; _exec_sort consumes
        # it (identity-matched on the plan node) when sort_limit.plan_topk
        # adopts, and raises _limit_taken so _exec_limit skips the mask pass
        self._limit_hint: Optional[tuple] = None
        self._limit_taken = False
        self._deferred_overflow: list = []  # device bools, checked at final fetch
        # (hint key, device int) pairs riding the SAME final fetch: observed
        # live counts that persist as capacity hints for the staged path's
        # adaptive join compaction (mirror of the fused path's ctx.stats)
        self._deferred_stats: list = []

    # --- cache helpers ---

    def _jitted(self, kind: str, fingerprint, build: Callable[[], Callable],
                static_argnums=(), pool: Optional[ConstPool] = None) -> Callable:
        """`pool`: the constants the program is about to be called with. Its
        literal values are in no key (`E.shape`); they are remembered beside
        the program, and a dispatch that finds the program under other
        values than its last one ran with is counted
        (`program.literal_shared`: each was a trace and a compile while a
        key held the value)."""
        key = (kind, fingerprint)
        fn = self._cache.get(key)
        lits = pool.literal_values() if pool is not None else ()
        if lits:
            if fn is not None and self._cache.get(("literals",) + key) != lits:
                tracing.counter("program.literal_shared")
            self._cache[("literals",) + key] = lits
        if fn is None:
            tracing.counter("jit.miss")
            stats.bump_attr("jit_miss")
            if _LOG_COMPILES:
                import sys
                print(f"igloo-compile: {kind} "
                      f"{hash(repr(fingerprint)) & 0xFFFFFFFF:08x} "
                      f"{repr(fingerprint)[:160]}",
                      file=sys.stderr, flush=True)
            fn = build()
            if self._use_jit:
                fn = jax.jit(fn, static_argnums=static_argnums)
            self._cache[key] = fn
            return _Program(fn, kind, first=True)
        tracing.counter("jit.hit")
        stats.bump_attr("jit_hit")
        return _Program(fn, kind, first=False)

    def _bind(self, pool: ConstPool, *batches) -> tuple:
        """A dispatch's arguments -> (*batches without their host metadata,
        the constants pool on the device: LUTs, pack offsets, the literals'
        scalars). One span, or a query's time here reads as `execute` self
        time."""
        with tracing.span("program.bind_args"):
            if pool.n_scalars:
                tracing.counter("program.literal_args", pool.n_scalars)
            return tuple(strip_dicts(b) for b in batches) + \
                (pool.device_args(),)

    # --- entry ---

    def execute(self, plan: L.LogicalPlan) -> DeviceBatch:
        batch = self._exec(plan)
        if self._deferred_overflow or self._deferred_stats:
            deferred, self._deferred_overflow = self._deferred_overflow, []
            stat_pairs, self._deferred_stats = self._deferred_stats, []
            vals, svals = jax.device_get(
                ([f for _, f in deferred], [v for _, v in stat_pairs]))
            self._record_stats(stat_pairs, svals)
            fired = self._fired_deferred(deferred, vals)
            if fired:
                return self._exact_copy().execute(plan)
        return batch

    def _staged_hint(self, key) -> Optional[int]:
        v = self._cache.get(("nhint", key))
        if v is None and self._hints is not None:
            v = self._hints.get(key)
            if v is not None:
                self._cache[("nhint", key)] = v
        return int(v) if v is not None else None

    def _record_stats(self, stats, svals) -> None:
        for (key, _), v in zip(stats, svals):
            self._cache[("nhint", key)] = int(v)
            if self._hints is not None:
                self._hints.put(key, int(v))
        if stats and self._hints is not None:
            self._hints.flush()

    def _record_fired_tag(self, tag) -> None:
        """Negative-cache + counter bookkeeping for ONE fired deferred flag —
        shared by the staged (_fired_deferred) and fused (_fused_run) tiers
        so a tag kind can never gain handling in one and drift in the other."""
        if tag[0] == "dup":
            # THIS side of the join proved to have duplicate keys — the
            # other side may still direct-join
            jfp_core, side = tag[1]
            self._cache[("nodirect", jfp_core, side)] = True
            tracing.counter("join.direct_dup_fallback")

    def _fired_deferred(self, deferred, vals) -> list:
        """Check fetched deferred-flag values; returns the fired tags (empty
        = nothing fired), with negative caches recorded."""
        fired = []
        for (tag, _), v in zip(deferred, vals):
            if bool(v):
                fired.append(tag)
                self._record_fired_tag(tag)
        return fired

    def _exact_copy(self) -> "Executor":
        """A sibling executor with speculation off (shares all caches); used to
        re-run a plan after a deferred speculative-join overflow fired."""
        tracing.counter("join.speculation_overflow")
        return Executor(self._cache, use_jit=self._use_jit,
                        batch_cache=self._batch_cache, speculate=False,
                        hints=self._hints)

    # Above this capacity a final batch is speculatively compacted down to this
    # many lanes before the device->host fetch: most query results fit, so the
    # common case pays ONE roundtrip carrying (count, compacted lanes) instead
    # of either a huge padded transfer or a count sync followed by a fetch.
    # On overflow (count > cap) we pay the exact compact + refetch.
    _FINAL_FETCH_CAPACITY = 1 << 10

    # whole-plan fusion (exec/fused.py): one dispatch + one fetch per query.
    # ShardedExecutor overrides to False (its stages shard_map over a mesh).
    _FUSE = True

    def execute_to_arrow(self, plan: L.LogicalPlan) -> pa.Table:
        # detail-mode stats (EXPLAIN ANALYZE) route to the staged executor:
        # the fused program is ONE dispatch with no internal operator
        # boundaries, so per-operator rows/timings only exist staged
        if self._FUSE and self._use_jit and self._speculate and \
                not stats.detail_active():
            try:
                return self._fused_to_arrow(plan)
            except FusionUnsupported as e:
                tracing.counter("fused.unsupported")
                tracing.counter(f"fused.unsupported.{e.args[0] if e.args else ''}")
        # ONE span for the whole staged fallback (its programs' spans nest
        # inside): a query that leaves the fused path is still attributed
        with tracing.span("staged.execute"):
            return self._staged_to_arrow(plan)

    def _fused_to_arrow(self, plan: L.LogicalPlan, _retry: bool = True) -> pa.Table:
        """Execute via the fused whole-plan program: one dispatch, one fetch
        of (deferred flags, cardinality stats, row count, compacted result).
        Observed live counts update the adaptive capacity hints; a compaction
        overflow triggers ONE repair re-run with the fresh hints, any other
        flag (direct-join duplicates, speculative overflow) an exact staged
        re-run. Oversized results pay an exact compact + full fetch."""
        with stats.op("FusedProgram" if _retry else "FusedProgram(repair)"):
            return self._fused_run(plan, _retry)

    def _fused_run(self, plan: L.LogicalPlan, _retry: bool) -> pa.Table:
        from igloo_tpu.exec.batch import arrow_from_host
        comp = FusedCompiler(self)
        with tracing.span("fused.plan"):
            run, key, meta = comp.compile(plan)
            probe = comp.probe()
        if probe is not None:
            # a wide plan seen for the first time: learn its live counts from
            # its probe (none of its rows), then compile the hinted program
            # from them at once
            tracing.counter("fused.probe")
            jp = self._jitted("fused_probe", key, lambda: probe,
                              pool=comp.pool)
            *leaves, consts = self._bind(comp.pool, *comp.leaves)
            counts = jp(leaves, consts)
            with tracing.span("fused.fetch"):
                self._record_hints(comp, jax.device_get(counts))
            comp = FusedCompiler(self)
            with tracing.span("fused.plan"):
                run, key, meta = comp.compile(plan)
        stats.annotate(nodes=len(comp.fps), leaves=len(comp.leaves))
        # `nofuse` sentinel: armed in the persistent store before a
        # first-in-process fused compile, cleared on success. A process killed
        # mid-compile (a fused join program can take many minutes to
        # compile, and a run's time limit does not wait) leaves it armed;
        # after two strikes later processes route this plan to the staged
        # executor instead of recompiling the program that hung. Finding it
        # armed is counted (`fused.nofuse_armed`), so the quiet demotion
        # that follows can be told from a plan that never fused.
        sentinel = ("nofuse", key)
        first = ("fused", key) not in self._cache
        if first and self._hints is not None:
            strikes = self._hints.get(sentinel) or 0
            if strikes:
                tracing.counter("fused.nofuse_armed")
            if strikes >= 2:
                tracing.counter("fused.nofuse_sentinel")
                raise FusionUnsupported("nofuse_sentinel")
            self._hints.put(sentinel, strikes + 1)
            self._hints.flush()
        jf = self._jitted("fused", key, lambda: run, pool=comp.pool)
        tracing.counter("fused.execute")
        try:
            *leaves, consts = self._bind(comp.pool, *comp.leaves)
            big, spec, n_dev, flags, stats_dev = jf(leaves, consts)
        except BaseException as e:
            # an ordinary exception means the compile did NOT hang — clear
            # the strike so transient failures can't poison fusion forever
            # (a process killed mid-compile never reaches this handler)
            if first and self._hints is not None:
                self._hints.remove(sentinel)
                self._hints.flush()
            raise
        if first and self._hints is not None:
            self._hints.remove(sentinel)
            self._hints.flush()
        with tracing.span("fused.fetch"):
            # blocks until the device is done, then copies D2H
            flags_h, stats_h, n, host_live, host_vals, host_nulls, \
                host_cargs = jax.device_get(
                    (flags, stats_dev, n_dev, spec.live,
                     [c.values for c in spec.columns],
                     [c.nulls for c in spec.columns],
                     [c.carrier_arg for c in spec.columns]))
        with tracing.span("fused.result"):
            record_fetch((host_live, host_vals, host_nulls,
                          pair_halves(spec, host_cargs)))
            stats.set_rows(int(n))
            self._record_hints(comp, stats_h)
            fired = [comp.flag_tags[fid] for fid, v in flags_h.items()
                     if bool(v)]
            for tag in fired:
                self._record_fired_tag(tag)
            if not fired and int(n) <= spec.capacity:
                return arrow_from_host(
                    attach_dicts(spec, meta.dicts, meta.bounds), host_live,
                    host_vals, host_nulls, host_cargs)
        if fired:
            if _retry and all(t[0] == "compact" for t in fired):
                # stale cardinality hints only: repair with the fresh ones
                tracing.counter("fused.compact_repair")
                return self._fused_to_arrow(plan, _retry=False)
            return self._exact_copy().execute_to_arrow(plan)
        # result larger than the fetch window: exact compact + full fetch.
        # Clamp to the batch's own capacity (already a family member): the
        # live count can sit in the hysteresis band just under it, and an
        # un-clamped round would pad the fetch a full family step PAST the
        # rows that exist
        want = min(round_capacity(int(n)), big.capacity)
        fp = ("compact", batch_proto_key(big), want)

        def build():
            def fn(b):
                return K.compact_to(b, want)
            return fn
        out = self._jitted("compact", fp, build)(big)
        return to_arrow(attach_dicts(out, meta.dicts, meta.bounds))

    def _record_hints(self, comp: FusedCompiler, counts: dict) -> None:
        """A fused program's fetched live counts ({stat id: count}) become
        the cardinality hints its plan's next compile adopts."""
        for sid, v in counts.items():
            self._cache[("nhint", comp.stat_keys[sid])] = int(v)
            if self._hints is not None:
                self._hints.put(comp.stat_keys[sid], int(v))
        if self._hints is not None:
            self._hints.flush()

    def _staged_to_arrow(self, plan: L.LogicalPlan) -> pa.Table:
        from igloo_tpu.exec.batch import arrow_from_host
        batch = self._exec(plan)
        deferred, self._deferred_overflow = self._deferred_overflow, []
        stat_pairs, self._deferred_stats = self._deferred_stats, []
        dvals = [f for _, f in deferred]
        dstats = [v for _, v in stat_pairs]
        cap = self._FINAL_FETCH_CAPACITY
        if batch.capacity <= cap:
            flags, svals, host_live, host_vals, host_nulls, host_cargs = \
                jax.device_get(
                    (dvals, dstats, batch.live,
                     [c.values for c in batch.columns],
                     [c.nulls for c in batch.columns],
                     [c.carrier_arg for c in batch.columns]))
            record_fetch((host_live, host_vals, host_nulls,
                          pair_halves(batch, host_cargs)))
            self._record_stats(stat_pairs, svals)
            fired = self._fired_deferred(deferred, flags)
            if fired:
                return self._exact_copy().execute_to_arrow(plan)
            return arrow_from_host(batch, host_live, host_vals, host_nulls,
                                   host_cargs)
        fp = ("spec_compact", batch_proto_key(batch), cap)

        def build():
            def fn(b):
                n = jnp.sum(b.live)
                return K.compact_to(b, cap), n
            return fn
        spec, n_dev = self._jitted("spec_compact", fp, build)(strip_dicts(batch))
        spec = attach_dicts(spec, *col_meta(batch.columns))
        flags, svals, host_n, host_live, host_vals, host_nulls, host_cargs = \
            jax.device_get(
                (dvals, dstats, n_dev, spec.live,
                 [c.values for c in spec.columns],
                 [c.nulls for c in spec.columns],
                 [c.carrier_arg for c in spec.columns]))
        record_fetch((host_live, host_vals, host_nulls,
                      pair_halves(spec, host_cargs)))
        self._record_stats(stat_pairs, svals)
        fired = self._fired_deferred(deferred, flags)
        if fired:
            return self._exact_copy().execute_to_arrow(plan)
        if int(host_n) <= cap:
            return arrow_from_host(spec, host_live, host_vals, host_nulls,
                                   host_cargs)
        # overflow: compact to the exact capacity and refetch (clamped to the
        # batch's own capacity — see the fused path's compact above)
        want = min(round_capacity(int(host_n)), batch.capacity)
        fp = ("compact", batch_proto_key(batch), want)

        def build_full():
            def fn(b):
                return K.compact_to(b, want)
            return fn
        out = self._jitted("compact", fp, build_full)(strip_dicts(batch))
        return to_arrow(attach_dicts(out, *col_meta(batch.columns)))

    def _exec(self, plan: L.LogicalPlan) -> DeviceBatch:
        m = getattr(self, "_exec_" + type(plan).__name__.lower(), None)
        if m is None:
            raise NotSupportedError(f"no executor for {type(plan).__name__}")
        with stats.plan_op(plan):
            out = m(plan)
            if stats.detail_active():
                # EXPLAIN ANALYZE: actual row count, one device sync per op
                n = out.num_live()
                stats.set_rows(n)
                stats.annotate(capacity=out.capacity)
                if not isinstance(plan, L.Scan):
                    # the sync is already paid for: feed the adaptive planner
                    # loop (docs/adaptive.md) — EXPLAIN ANALYZE doubles as
                    # the device tier's cardinality profiler
                    from igloo_tpu.exec.hints import plan_fp
                    fp = plan_fp(plan)
                    if fp is not None:
                        stats.observe_card(fp, n)
        if out.schema is not plan.schema and out.schema != plan.schema:
            # keep plan schema authoritative (names may differ from kernel output)
            out = DeviceBatch(plan.schema, out.columns, out.live)
        return out

    # --- leaves ---

    def _exec_scan(self, plan: L.Scan) -> DeviceBatch:
        batch = self._scan_batch(plan)
        # provider-pinned bounds (GRACE partitions: the UNION range across all
        # partitions) replace per-read exact bounds so every partition keys
        # the same compiled programs; a superset range is always safe for the
        # consumers (direct-join table sizing, packed-key radices)
        fixed = getattr(plan.provider, "fixed_bounds", None) \
            if plan.provider is not None else None
        if fixed:
            from dataclasses import replace
            cols = [replace(c, bounds=fixed.get(f.name, c.bounds))
                    for f, c in zip(batch.schema, batch.columns)]
            batch = DeviceBatch(batch.schema, cols, batch.live)
        return batch

    def _scan_batch(self, plan: L.Scan) -> DeviceBatch:
        # GRACE partition pipeline: the prefetch thread already decoded,
        # narrowed and device_put this bucket while the previous partition's
        # program ran — hand its batch through without touching the caches
        # (the provider lives exactly one partition, so caching it would only
        # pin dead HBM)
        pre = getattr(plan.provider, "prebuilt_batch", None) \
            if plan.provider is not None else None
        if pre is not None:
            return pre
        stable = getattr(plan.provider, "stable_row_order", False)
        # a provider that lives for ONE execution (catalog.EphemeralTable: a
        # fragment's dependency, a chunk's partial result) is read once,
        # under a name no later query asks for: one upload, no cache entry
        once = getattr(plan.provider, "ephemeral", False)
        if self._batch_cache is None or not stable or once:
            # whole-batch path: providers without deterministic row order
            # (e.g. DBAPI SELECTs with no ORDER BY) must never stitch columns
            # from separate reads; they get one read per (projection) and a
            # whole-batch cache entry.
            key = snap = None
            if self._batch_cache is not None and not once:
                from igloo_tpu.exec.cache import provider_snapshot, \
                    read_identity
                key = (plan.table,
                       tuple(plan.projection) if plan.projection is not None
                       else None,
                       read_identity(plan), plan.partition)
                snap = provider_snapshot(plan.provider)
                hit = self._batch_cache.get(key, snap)
                if hit is not None:
                    return hit
            with tracing.span("program.scan_load", table=plan.table):
                clock = _ScanLoadClock()
                table = read_scan_table(plan)
                if plan.projection is not None:
                    table = table.select(plan.projection)
                clock.read_done()
                batch = from_arrow(table, schema=plan.schema, clock=clock)
                clock.done(batch, len(plan.schema), wait=not once)
            _note_carrier_ratio(plan.provider, batch)
            if key is not None:
                self._batch_cache.put(key, batch, snap)
            return batch
        # COLUMN-granular HBM cache: entries are per (table, what was read,
        # partition, column) — `read_identity`: the row groups that survived
        # pruning, never the text of the filter that pruned — so scans with
        # different projections or different literals share the uploaded
        # lanes they have in common: a 22-query sweep uploads each column at
        # most once. Entry values are (DeviceColumn, n_rows, the pushed
        # filters it was loaded under); n makes the live lane
        # reconstructible after its entry is evicted without re-reading a
        # column.
        from igloo_tpu.exec.cache import provider_snapshot, read_identity
        from igloo_tpu.exec.codec import live_lane
        snap = provider_snapshot(plan.provider)
        # the engine's host fast path executes small plans under
        # jax.default_device(cpu); its uploads must not alias the
        # accelerator-resident copies of the same columns
        dev = getattr(jax.config, "jax_default_device", None)
        base = (plan.table, read_identity(plan),
                plan.partition, str(dev) if dev is not None else "default")
        cached = {f.name: self._batch_cache.get(base + ("col", f.name), snap)
                  for f in plan.schema}
        under = E.fingerprint(plan.pushed_filters)
        shared = sum(1 for v in cached.values()
                     if v is not None and v[2] != under)
        if shared:
            tracing.counter("cache.shared_by_filter", shared)
        live = self._batch_cache.get(base + ("live",), snap)
        missing = [f for f in plan.schema if cached[f.name] is None]
        known_n = next((v[1] for v in cached.values() if v is not None), None)
        if live is not None:
            live, live_n = live
        else:
            live_n = None
        if live is None and known_n is not None and not missing:
            cap0 = next(v[0].capacity for v in cached.values() if v is not None)
            live = live_lane(cap0, known_n)
            self._batch_cache.put_entry(base + ("live",), (live, known_n),
                                        snap, live.nbytes, plan.table)
        if not missing and live is not None:
            return DeviceBatch(plan.schema,
                               [cached[f.name][0] for f in plan.schema], live)
        proj = [f.name for f in missing]  # non-empty: all-cached paths return above
        # the miss path (provider read + decode, codec, H2D) is one span: a
        # resident table never opens it
        with tracing.span("program.scan_load", table=plan.table,
                          columns=len(proj)):
            clock = _ScanLoadClock()
            table = read_scan_table(plan, projection=proj).select(proj)
            clock.read_done()
            n = table.num_rows
            if (known_n is not None and n != known_n) or \
                    (live_n is not None and n != live_n):
                # source changed under an identity snapshot: drop, re-read
                self._batch_cache.invalidate_table(plan.table)
                return self._exec_scan(plan)
            cap = int(live.shape[0]) if live is not None else (
                round_capacity(n) if known_n is None
                else next(v[0].capacity for v in cached.values()
                          if v is not None))
            decoded = [host_decode_column(table.column(f.name), f)
                       for f in missing]
            new_cols = device_columns(decoded, missing, cap, clock=clock)
            clock.done(new_cols, len(missing))
            for f, col in zip(missing, new_cols):
                self._batch_cache.put_entry(base + ("col", f.name),
                                            (col, n, under), snap, col.nbytes,
                                            plan.table)
                cached[f.name] = (col, n, under)
            if live is None:
                live = live_lane(cap, n)
                self._batch_cache.put_entry(base + ("live",), (live, n), snap,
                                            live.nbytes, plan.table)
            out = DeviceBatch(plan.schema,
                              [cached[f.name][0] for f in plan.schema], live)
            _note_carrier_ratio(plan.provider, out)
            return out

    def _exec_values(self, plan: L.Values) -> DeviceBatch:
        n = len(plan.rows)
        if len(plan.schema) == 0:
            cap = round_capacity(max(n, 1))
            live = np.zeros(cap, dtype=bool)
            live[:n] = True
            return DeviceBatch(plan.schema, [], jnp.asarray(live))
        arrays = []
        for j, f in enumerate(plan.schema):
            vals = [r[j] for r in plan.rows]
            arrays.append(pa.array(vals, type=_pa_type_for(f.dtype)))
        table = pa.Table.from_arrays(arrays, names=plan.schema.names)
        return from_arrow(table, schema=plan.schema)

    # --- pipeline ops (fused per node; XLA fuses chains of these) ---

    def _compile_exprs(self, exprs, batch: DeviceBatch,
                       comp: Optional[ExprCompiler] = None):
        """Host-compile `exprs` against `batch`'s dictionaries. Returns
        (resolved exprs, compiled, compiler) — resolved exprs carry evaluated
        scalar-subquery literals, so fingerprints built from them key the
        compile cache on the actual values."""
        if comp is None:
            comp = ExprCompiler([c.dictionary for c in batch.columns],
                    bounds=[c.bounds for c in batch.columns])
        resolved = [self._resolve_subqueries(e) for e in exprs]
        return resolved, [comp.compile(e) for e in resolved], comp

    def _exec_filter(self, plan: L.Filter) -> DeviceBatch:
        batch = self._exec(plan.input)
        res, [c], comp = self._compile_exprs([plan.predicate], batch)
        fp = ("filter", expr_fingerprint(res), batch_proto_key(batch),
              comp.pool.signature(), tuple(comp.marks))

        def build():
            def fn(b: DeviceBatch, consts) -> DeviceBatch:
                env = Env.from_batch(b, consts)
                v, nl = c.fn(env)
                keep = b.live & v
                if nl is not None:
                    keep = keep & ~nl
                return DeviceBatch(b.schema, b.columns, keep)
            return fn
        out = self._jitted("filter", fp, build, pool=comp.pool)(
            *self._bind(comp.pool, batch))
        return attach_dicts(out, *col_meta(batch.columns))

    def _exec_project(self, plan: L.Project) -> DeviceBatch:
        batch = self._exec(plan.input)
        res, comps, comp = self._compile_exprs(plan.exprs, batch)
        fp = ("project", expr_fingerprint(res), batch_proto_key(batch),
              plan.schema, comp.pool.signature(), tuple(comp.marks))
        out_schema = plan.schema

        def build():
            def fn(b: DeviceBatch, consts) -> DeviceBatch:
                env = Env.from_batch(b, consts)
                cols = []
                for cc, f in zip(comps, out_schema.fields):
                    v, nl = cc.fn(env)
                    want = f.dtype.device_dtype()
                    if v.dtype != want:
                        v = v.astype(want)
                    cols.append(DeviceColumn(f.dtype, v, nl, None))
                return DeviceBatch(out_schema, cols, b.live)
            return fn
        out = self._jitted("project", fp, build, pool=comp.pool)(
            *self._bind(comp.pool, batch))
        return attach_dicts(out, [cc.out_dict for cc in comps],
                    [cc.out_bounds for cc in comps])

    # --- blocking ops ---

    def _exec_aggregate(self, plan: L.Aggregate) -> DeviceBatch:
        batch = self._exec(plan.input)
        if uncompacted_filter(plan) is None:
            batch = self._adaptive_input(batch, plan.input)
        if plan.distinct_stage == 1:
            tracing.counter("agg.distinct")
        with stage_scope(plan):
            return self._aggregate(batch, plan.group_exprs, plan.aggs,
                                   plan.schema)

    def _aggregate(self, batch, group_exprs, aggs, out_schema) -> DeviceBatch:
        comp = ExprCompiler([c.dictionary for c in batch.columns],
                    bounds=[c.bounds for c in batch.columns])
        gres, groups, _ = self._compile_exprs(group_exprs, batch, comp)
        specs = []
        ares = []
        for a in aggs:
            if a.arg is not None:
                [r], [arg], _ = self._compile_exprs([a.arg], batch, comp)
                ares.append(r)
            else:
                arg = None
            out_dict = arg.out_dict if (arg is not None and a.dtype.is_string) else None
            specs.append(AggSpec(a.func, arg, a.dtype, out_dict,
                                 order_arg=minmax_order_arg(a.func, arg, comp)))
        # direct-scatter eligibility is dictionary-CONTENT-dependent (sizes),
        # so it must join the cache key, not just shape signatures
        n_scatters = sum(2 if a.func is E.AggFunc.AVG else 1 for a in aggs)
        seg_dims = seg_dims_for(groups, n_aggs=n_scatters,
                                input_capacity=batch.capacity)
        # packed-key single-sort path for everything the scatter path rejects:
        # a host decision on bounds/dictionary sizes, so it keys the cache too
        # (the spec's radices are static; its offsets ride the const pool)
        pack_spec = None
        if seg_dims is None and groups:
            pack_spec = K.plan_group_packing(groups, comp.pool)
            if pack_spec is not None:
                tracing.counter("pack.agg")
        pair_sums = pair_sums_for(seg_dims, specs)
        if groups_in_place(seg_dims):
            tracing.counter("agg.groups_in_place")
        fp = ("agg", expr_fingerprint(gres + ares),
              tuple((a.func, a.dtype) for a in aggs),
              batch_proto_key(batch), out_schema,
              comp.pool.signature(), tuple(comp.marks), seg_dims, pack_spec) \
            + (("pair_sums",) if pair_sums else ())

        def build():
            def fn(b: DeviceBatch, consts):
                return aggregate_batch(b, groups, specs, out_schema, consts,
                                       seg_dims=seg_dims, pack_spec=pack_spec,
                                       pair_sums=pair_sums)
            return fn
        out = self._jitted("agg", fp, build, pool=comp.pool)(
            *self._bind(comp.pool, batch))
        stats.annotate(strategy="direct_scatter" if seg_dims is not None
                       else "packed_sort" if pack_spec is not None
                       else "lex_sort")
        out = attach_dicts(out, [g.out_dict for g in groups] +
                           [s.out_dict for s in specs],
                           [None] * len(groups) +
                           agg_out_bounds(aggs, batch.capacity))
        return self._maybe_shrink(out)

    def _exec_distinct(self, plan: L.Distinct) -> DeviceBatch:
        batch = self._adaptive_input(self._exec(plan.input), plan.input)
        fp = ("distinct", batch_proto_key(batch))

        def build():
            return distinct_batch
        out = self._jitted("distinct", fp, build)(strip_dicts(batch))
        out = attach_dicts(out, *col_meta(batch.columns))
        return self._maybe_shrink(out)

    def _adaptive_input(self, batch: DeviceBatch,
                        plan_node: L.LogicalPlan) -> DeviceBatch:
        """Bound a join input's CAPACITY before the probe program compiles:
        XLA compile time on the sorted-probe join grows pathologically with
        lane count (observed: a 2x8.4M-lane probe+expand never finished in
        25 min, while 8.4Mx64 compiles in ~71 s — q18/q21 at SF1), so a side
        whose live count is far below its padded capacity must compact first.
        The live count comes from a persisted per-subtree hint; its first
        observation costs ONE sync, after which dense inputs skip even that
        and sparse ones compact IN-PROGRAM with a deferred overflow flag
        (exact re-run on staleness)."""
        cap = batch.capacity
        if cap <= self._SPECULATIVE_JOIN_BUDGET or not self._speculate \
                or self._use_jit is False:
            return batch
        from igloo_tpu.exec.hints import plan_fp
        fp = plan_fp(plan_node, exact=True)
        if fp is None:
            # no stable hint key for this subtree (subqueries/window/union...):
            # carry the padded lanes rather than pay a num_live() device->host
            # sync on EVERY staged execution
            return batch
        # capacity IS part of this key: an input subtree's capacity comes
        # from its scans (stable run-to-run for the same data), so including
        # it cannot cascade — and it keeps sf1/sf10 executions of the same
        # exprs from sharing live counts (a stale cross-scale hint would
        # force an exact re-run whose unshrunk probes compile pathologically)
        key = ("slive", fp, batch.capacity)
        hint = self._staged_hint(key)
        if hint is None:
            n = batch.num_live()  # one sync, first sight of this subtree only
            self._cache[("nhint", key)] = n
            if self._hints is not None:
                self._hints.put(key, n)
                self._hints.flush()
            stats.observe_card(fp, n)  # sync already paid: adaptive loop
            return self._maybe_shrink(batch, known_live=n)
        want = round_capacity(max(hint, 1))
        # factor 2, not _SHRINK_FACTOR: past the compile budget every halving
        # of padded lanes halves the consumer's whole-program cost — a 2M-live
        # input in 8.4M lanes must not keep its 4x padding (q18's final
        # aggregate sat exactly on the 4x boundary and ran full-width)
        if want * 2 > cap:
            return batch  # dense input: leave as-is, no sync
        jfp = ("acompact_in", batch_proto_key(batch), want)

        def build():
            def fn(b):
                n = jnp.sum(b.live.astype(jnp.int64))
                return K.compact_to(b, want), n, n > want
            return fn
        out, n_dev, ovf = self._jitted("acompact_in", jfp, build)(
            strip_dicts(batch))
        self._deferred_stats.append((key, n_dev))
        self._deferred_overflow.append((("scompact", key), ovf))
        tracing.counter("join.input_compact")
        return attach_dicts(out, *col_meta(batch.columns))

    def _exec_join(self, plan: L.Join) -> DeviceBatch:
        left = self._adaptive_input(self._exec(plan.left), plan.left)
        right = self._adaptive_input(self._exec(plan.right), plan.right)
        pool = ConstPool()
        compL = ExprCompiler([c.dictionary for c in left.columns], pool,
                     bounds=[c.bounds for c in left.columns])
        lres, lk, _ = self._compile_exprs(plan.left_keys, left, compL)
        compR = ExprCompiler([c.dictionary for c in right.columns], pool,
                     bounds=[c.bounds for c in right.columns])
        rres, rk, _ = self._compile_exprs(plan.right_keys, right, compR)
        jt = plan.join_type
        use_lk, use_rk = ([], []) if jt is JoinType.CROSS else (lk, rk)
        lhx = make_key_hash_idxs(use_lk, pool)
        rhx = make_key_hash_idxs(use_rk, pool)
        residual = None
        rres2 = []
        if plan.residual is not None:
            compB = ExprCompiler([c.dictionary for c in left.columns] +
                                 [c.dictionary for c in right.columns], pool)
            r = self._resolve_subqueries(plan.residual)
            rres2 = [r]
            residual = compB.compile(r)
            marks = tuple(compL.marks) + tuple(compR.marks) + tuple(compB.marks)
        else:
            marks = tuple(compL.marks) + tuple(compR.marks)
        fpbase = (expr_fingerprint(lres + rres + rres2),
                  plan.join_type, batch_proto_key(left), batch_proto_key(right),
                  pool.signature(), marks)

        if jt in (JoinType.SEMI, JoinType.ANTI):
            meta_cols = left.columns
        else:
            meta_cols = list(left.columns) + list(right.columns)
        dicts, bnds = col_meta(meta_cols)

        ls, rs, consts = self._bind(pool, left, right)

        # direct "array join" fast path (exec/join.py): dense-integer PK-FK
        # joins become one scatter + one gather; a deferred duplicate flag
        # falls back to the exact sorted-probe path below (via _exact_copy,
        # which runs with _speculate=False and therefore skips this branch).
        # The ("nodirect", jfp) negative cache skips joins whose build side
        # already proved to have duplicate keys.
        jfp_core = (expr_fingerprint(lres + rres + rres2), jt)
        jfp = jfp_core + (left.capacity, right.capacity)
        if self._speculate and use_lk:
            banned = frozenset(
                s for s in ("left", "right")
                if self._cache.get(("nodirect", jfp_core, s)))
            pick = choose_direct_build(use_lk, use_rk, left.capacity,
                                       right.capacity, jt, banned=banned)
            if pick is not None:
                # (blo, tsize) is the canonical positional table — quantized
                # in choose_direct_build so these fingerprint constants are
                # shape-class values, not raw data bounds (jit-key rule)
                side, (blo, tsize), ki = pick
                swapped = side == "left"
                pks = use_rk if swapped else use_lk
                bks = use_lk if swapped else use_rk
                pkey, bkey = pks[ki], bks[ki]
                extra = [(pks[i], bks[i]) for i in range(len(pks)) if i != ki]
                # adaptive capacity: a previous run's observed live count
                # (persisted hint) sizes an IN-PROGRAM compaction — selective
                # joins (q17: 6M-lane probe, ~6k matches) otherwise hand
                # full-width padded batches to every downstream stage, whose
                # static-shape cost scales with CAPACITY, not live rows.
                # Overflow (stale hint) re-runs exactly via _exact_copy.
                # The key MUST be capacity-free: upstream hint adoption
                # changes this join's input capacities, and a cap-dependent
                # key would cascade one adoption level per run (the round-4
                # hfps lesson). A scale change (sf1 -> sf10 data under the
                # same exprs) makes the hint stale instead — the overflow
                # flag repairs it in one exact re-run and re-records.
                hkey = ("sjoin_live", jfp_core)
                hint = self._staged_hint(hkey)
                probe_cap = right.capacity if swapped else left.capacity
                want = None
                if hint is not None:
                    w = round_capacity(max(hint, 1))
                    if w * _SHRINK_FACTOR <= probe_cap:
                        want = w

                def build(want=want):
                    def fn(pb, bb, c):
                        out, dup = direct_join_phase(
                            pb, bb, pkey, bkey, blo, tsize, swapped, jt,
                            residual, plan.schema, c, extra_keys=extra)
                        n = jnp.sum(out.live.astype(jnp.int64))
                        if want is None:
                            return out, dup, n, jnp.asarray(False)
                        return K.compact_to(out, want), dup, n, n > want
                    return fn
                fn = self._jitted(
                    "join_direct",
                    (fpbase, plan.schema, side, blo, tsize, ki, want),
                    build, pool=pool)
                tracing.counter("join.direct")
                stats.annotate(strategy="direct", build_side=side)
                out, dup, n_dev, ovf = fn(
                    rs if swapped else ls, ls if swapped else rs, consts)
                self._deferred_overflow.append(
                    (("dup", (jfp_core, side)), dup))
                self._deferred_stats.append((hkey, n_dev))
                if want is not None:
                    tracing.counter("join.direct_compact")
                    self._deferred_overflow.append(
                        (("scompact", hkey), ovf))
                return attach_dicts(out, dicts[: len(out.columns)],
                                    bnds[: len(out.columns)])

        if jt in (JoinType.SEMI, JoinType.ANTI) and use_lk and \
                self._speculate:
            from igloo_tpu.exec.join import semi_anti_phase
            # windowed sorted membership (no expansion). With a residual the
            # window must cover the build side's duplicate-key runs (TPC-H:
            # <= 7 lineitems per order); a truncated run raises the deferred
            # flag -> exact re-run via _exact_copy (which takes the expand
            # path: correct, possibly slow — the flag is data-dependent and
            # rare by construction)
            win = 2 if residual is None else 12
            # pack the exact-verify lanes (union key ranges across both
            # sides) so each window slot compares one lane, not one per key
            pack_eq = K.plan_pair_packing(use_lk, use_rk, pool)
            if pack_eq is not None:
                tracing.counter("pack.semi")
                consts = pool.device_args()  # re-snapshot with the offsets
            fn = self._jitted(
                "join_semi", fpbase + (win, pack_eq, pool.signature()),
                lambda: (lambda l, r, consts: semi_anti_phase(
                    l, r, use_lk, use_rk, lhx, rhx,
                    jt is JoinType.ANTI, residual, win, consts,
                    pack_eq=pack_eq)), pool=pool)
            tracing.counter("join.semi_sorted")
            stats.annotate(strategy="semi_sorted")
            out, truncated = fn(ls, rs, consts)
            if residual is not None:
                self._deferred_overflow.append(
                    (("semi_window", fpbase), truncated))
            # no shrink sync here: downstream consumers bound their own
            # input capacities adaptively (_adaptive_input)
            return attach_dicts(out, dicts[: len(out.columns)],
                                bnds[: len(out.columns)])

        stats.annotate(strategy="sorted_probe")
        p = self._jitted(
            "join_probe", fpbase,
            lambda: (lambda l, r, consts: probe_phase(
                l, r, use_lk, use_rk, lhx, rhx, consts)),
            pool=pool)(ls, rs, consts)
        spec_cap = round_capacity(max(left.capacity, right.capacity))
        if (self._speculate and jt is not JoinType.CROSS
                and spec_cap <= self._SPECULATIVE_JOIN_BUDGET):
            total = None
            match_cap = spec_cap
            self._deferred_overflow.append((("overflow", jfp),
                                            p.total > match_cap))
        else:
            total = int(p.total)  # the one host sync
            match_cap = choose_match_capacity(total)
        search = match_by_search()
        out = self._jitted(
            "join_expand", (fpbase, plan.schema),
            lambda: (lambda l, r, p, match_cap, consts: expand_phase(
                l, r, p, match_cap, jt, residual, plan.schema, consts,
                match_search=search)),
            static_argnums=(3,), pool=pool)(ls, rs, p, match_cap, consts)
        out = attach_dicts(out, dicts[: len(out.columns)],
                           bnds[: len(out.columns)])
        if total is None:
            # speculative path: carrying padded lanes beats a count sync
            return out
        if jt in (JoinType.INNER, JoinType.CROSS):
            # live rows <= total (residual can only reduce), so the already-
            # synced candidate count bounds the shrink without a second sync
            return self._maybe_shrink(out, known_live=total)
        return self._maybe_shrink(out)

    def _exec_window(self, plan: L.Window) -> DeviceBatch:
        from igloo_tpu.exec.window import compile_window, window_batch
        batch = self._adaptive_input(self._exec(plan.input), plan.input)
        comp = ExprCompiler([c.dictionary for c in batch.columns],
                            bounds=[c.bounds for c in batch.columns])
        wfp, pk, okeys, specs, wdicts, wbounds = compile_window(
            plan, comp, self._resolve_subqueries)
        fp = ("window", wfp, batch_proto_key(batch), plan.schema,
              comp.pool.signature(), tuple(comp.marks))
        asc, nf = list(plan.ascending), list(plan.nulls_first)

        def build():
            def fn(b, consts):
                return window_batch(b, pk, okeys, asc, nf, specs,
                                    plan.schema, consts)
            return fn
        out = self._jitted("window", fp, build, pool=comp.pool)(
            *self._bind(comp.pool, batch))
        dicts, bnds = col_meta(batch.columns)
        return attach_dicts(out, dicts + wdicts, bnds + wbounds)

    def _exec_sort(self, plan: L.Sort) -> DeviceBatch:
        from igloo_tpu.exec.expr_compile import rank_lane
        batch = self._adaptive_input(self._exec(plan.input), plan.input)
        res, keys, comp = self._compile_exprs(plan.keys, batch)
        # ORDER BY over unsorted (high-cardinality) dictionaries sorts ranks
        keys = [rank_lane(k, comp) if k.dtype.is_string else k for k in keys]
        # pack the longest integer-family key prefix into one sort lane
        pack = K.plan_prefix_packing(keys, plan.ascending, plan.nulls_first,
                                     comp.pool)
        if pack is not None:
            tracing.counter("pack.sort")
        hint = self._limit_hint
        if hint is not None and hint[0] == id(plan):
            # ORDER BY + LIMIT fusion: the parent Limit deposited its bounds
            # before descending; adopt a partial top-k when the plan fits
            # (full pack required — one lane totally orders the rows)
            self._limit_hint = None
            _, limit, offset = hint
            k_total = limit + offset
            fp_core = (expr_fingerprint(res), tuple(plan.ascending),
                       tuple(plan.nulls_first), batch_proto_key(batch),
                       comp.pool.signature(), tuple(comp.marks), pack)
            if plan_topk(batch.capacity, k_total, pack, len(keys)):
                out_cap = round_capacity(k_total)

                def tbuild():
                    def fn(b, consts):
                        return topk_batch(b, keys, consts, pack,
                                          limit, offset, out_cap)
                    return fn
                tracing.counter("program.literal_keyed")  # k sizes lanes
                out = self._jitted(
                    "topk", ("topk", fp_core, limit, offset, out_cap),
                    tbuild, pool=comp.pool)(*self._bind(comp.pool, batch))
                self._limit_taken = True
                return attach_dicts(out, *col_meta(batch.columns))
        fp = ("sort", expr_fingerprint(res), tuple(plan.ascending),
              tuple(plan.nulls_first), batch_proto_key(batch),
              comp.pool.signature(), tuple(comp.marks), pack)

        def build():
            def fn(b, consts):
                return sort_batch(b, keys, plan.ascending, plan.nulls_first,
                                  consts, pack=pack)
            return fn
        out = self._jitted("sort", fp, build, pool=comp.pool)(
            *self._bind(comp.pool, batch))
        return attach_dicts(out, *col_meta(batch.columns))

    def _exec_limit(self, plan: L.Limit) -> DeviceBatch:
        if isinstance(plan.input, L.Sort) and plan.limit is not None:
            # deposit the LIMIT bounds for the Sort child (identity-matched
            # there, so an intervening rewrite can never mis-adopt); when the
            # child took the top-k its output already IS the limited batch
            prev = self._limit_hint
            self._limit_hint = (id(plan.input), plan.limit, plan.offset)
            self._limit_taken = False
            try:
                batch = self._exec(plan.input)
            finally:
                self._limit_hint = prev
            if self._limit_taken:
                self._limit_taken = False
                return self._maybe_shrink(batch, known_live=plan.limit)
        else:
            batch = self._exec(plan.input)
        fp = ("limit", plan.limit, plan.offset, batch_proto_key(batch))
        tracing.counter("program.literal_keyed")  # the bounds mask by position

        def build():
            def fn(b):
                return limit_batch(b, plan.limit, plan.offset)
            return fn
        out = self._jitted("limit", fp, build)(strip_dicts(batch))
        out = attach_dicts(out, *col_meta(batch.columns))
        # LIMIT bounds the live count statically — no sync needed
        known = plan.limit if plan.limit is not None else None
        return self._maybe_shrink(out, known_live=known)

    def _exec_union(self, plan: L.Union) -> DeviceBatch:
        batches = [self._exec(ch) for ch in plan.inputs]
        return union_batches(batches, plan.schema)

    def _exec_setopjoin(self, plan: L.SetOpJoin) -> DeviceBatch:
        left = self._maybe_shrink(self._exec_distinct_of(plan.left))
        right = self._maybe_shrink(self._exec_distinct_of(plan.right))
        # align dictionaries via union-batch machinery semantics: keys compare
        # via cross-table hash lanes inside the join kernel, so no remap needed
        lk = [self._col_ref(left, i) for i in range(len(left.schema))]
        rk = [self._col_ref(right, i) for i in range(len(right.schema))]
        jt = JoinType.ANTI if plan.anti else JoinType.SEMI
        return join_batches(left, right, lk, rk, jt, None, plan.schema)

    def _exec_distinct_of(self, plan: L.LogicalPlan) -> DeviceBatch:
        batch = self._exec(plan)
        fp = ("distinct", batch_proto_key(batch))

        def build():
            return distinct_batch
        out = self._jitted("distinct", fp, build)(strip_dicts(batch))
        return attach_dicts(out, *col_meta(batch.columns))

    def _col_ref(self, batch: DeviceBatch, i: int) -> Compiled:
        f = batch.schema.fields[i]
        return Compiled(lambda env, _i=i: (env.values[_i], env.nulls[_i]),
                        f.dtype, batch.columns[i].dictionary)

    # --- scalar subqueries ---

    def _resolve_subqueries(self, e: E.Expr) -> E.Expr:
        def sub(n):
            if isinstance(n, E.ScalarSubquery):
                # memoized on the node: plans are rebuilt per engine.execute,
                # so this caches only within one execution — in particular a
                # fused attempt falling back to the staged path (or a repair
                # re-run) does not re-execute the subquery
                memo = getattr(n, "_resolved_lit", None)
                if memo is not None:
                    return memo
                if not isinstance(n.query, L.LogicalPlan):
                    raise PlanError("unbound scalar subquery reached executor")
                val, dtype = self._eval_scalar(n.query)
                lit = E.Literal(value=val, literal_type=dtype)
                lit.dtype = n.dtype or dtype
                n._resolved_lit = lit
                return lit
            return n
        return E.transform(e, sub)

    def _eval_scalar(self, plan: L.LogicalPlan):
        # scope the deferred speculative-overflow flags to the subquery: its
        # final fetch must not consume (and mask) the outer query's flags
        saved, self._deferred_overflow = self._deferred_overflow, []
        saved_stats, self._deferred_stats = self._deferred_stats, []
        try:
            t = self.execute_to_arrow(plan)
        finally:
            self._deferred_overflow = saved + self._deferred_overflow
            self._deferred_stats = saved_stats + self._deferred_stats
        if t.num_rows > 1:
            raise ExecError("scalar subquery returned more than one row")
        dtype = plan.schema.fields[0].dtype
        if t.num_rows == 0:
            return None, dtype
        v = t.column(0)[0].as_py()
        if dtype.id == T.TypeId.DATE32 and v is not None:
            import datetime as _dt
            v = v.toordinal() - _dt.date(1970, 1, 1).toordinal()
        elif dtype.id == T.TypeId.TIMESTAMP and v is not None:
            import datetime as _dt
            v = (v - _dt.datetime(1970, 1, 1)) // _dt.timedelta(microseconds=1)
        return v, dtype

    # --- capacity management (shape bucketing between stages) ---

    # Below this capacity a batch is cheap enough to carry oversized: skipping
    # the shrink avoids a num_live() device->host sync, which stalls the
    # dispatch pipeline once per stage.
    _SYNC_FREE_CAPACITY = 1 << 16

    def _maybe_shrink(self, batch: DeviceBatch,
                      known_live: Optional[int] = None) -> DeviceBatch:
        if known_live is None and batch.capacity <= self._SYNC_FREE_CAPACITY:
            return batch
        n = batch.num_live() if known_live is None else known_live  # host sync
        want = round_capacity(max(n, 1))
        if batch.capacity > _SHRINK_FACTOR * want:
            fp = ("compact", batch_proto_key(batch), want)

            def build():
                def fn(b):
                    return K.compact_to(b, want)
                return fn
            out = self._jitted("compact", fp, build)(strip_dicts(batch))
            return attach_dicts(out, *col_meta(batch.columns))
        return batch


def union_batches(batches: list[DeviceBatch], out_schema: T.Schema) -> DeviceBatch:
    """UNION ALL: concatenate column-wise; string columns remap through the union
    dictionary host-side first."""
    caps = [b.capacity for b in batches]
    cols = []
    for i, f in enumerate(out_schema):
        want = f.dtype.device_dtype()
        if f.dtype.is_string:
            uni = None
            for b in batches:
                uni, _, _ = _unify_dicts(uni, b.columns[i].dictionary)
            luts = []
            for b in batches:
                _, _, lut = _unify_dicts(uni, b.columns[i].dictionary)
                luts.append(lut)
            # ids must be WIDE (int32 lane) before the LUT remap: a carrier id
            # lane would index the union LUT with offset-shrunk codes
            vals = jnp.concatenate([
                _remap(wide_values(b.columns[i]), luts[j])
                for j, b in enumerate(batches)])
            dct = uni
        else:
            # per-input carriers generally differ across UNION branches (one
            # spec per upload), so this boundary widens eagerly
            vals = jnp.concatenate([
                wide_values(b.columns[i]).astype(want) for b in batches])
            dct = None
        if any(b.columns[i].nulls is not None for b in batches):
            nulls = jnp.concatenate([
                b.columns[i].nulls if b.columns[i].nulls is not None
                else jnp.zeros((caps[j],), dtype=bool)
                for j, b in enumerate(batches)])
        else:
            nulls = None
        cols.append(DeviceColumn(f.dtype, vals, nulls, dct))
    live = jnp.concatenate([b.live for b in batches])
    return DeviceBatch(out_schema, cols, live)


def _remap(ids, lut: np.ndarray):
    if len(lut) == 0:
        return jnp.zeros_like(ids)
    return jnp.take(jnp.asarray(lut), jnp.clip(ids, 0, len(lut) - 1))


def _pa_type_for(d: T.DataType) -> pa.DataType:
    from igloo_tpu.exec.batch import dtype_to_arrow
    return dtype_to_arrow(d)
