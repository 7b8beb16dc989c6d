"""Partition-at-a-time (chunked) execution for tables larger than one
DeviceBatch budget.

Reuses the cluster tier's fragmenting planner (cluster/fragment.py) with every
fragment executed IN-PROCESS: scans stride the provider's partitions
(parquet row groups, CSV files, MemTable splits), decomposable aggregates
become per-chunk partial aggregates merged by a final fragment, and
intermediate results live as host Arrow tables (partials are group-count
sized, not input sized). The device never materializes more than one chunk of
the base table at a time.

Ceiling (documented per the build plan): only decomposable-aggregate-over-scan
pipelines (Q1/Q6 shape) actually stream chunk-at-a-time — `chunk_count` routes
ONLY those here (a DISTINCT aggregate among them: the optimizer splits it into
two grouped aggregates, plan/aggsplit.py, and its first streams). Plans whose
over-budget scan feeds anything else (a bare sort/limit, a join side) would
union all chunks back into one device batch, so they take the normal path
unchanged; over-budget JOIN
trees route through the partitioned GRACE tier instead (exec/grace.py — see
docs/out_of_core.md for the full fallback ladder).

Reference analog: the 1024-row streaming read batches of
crates/engine/src/operators/parquet_scan.rs:54, which flow through operators
one channel at a time but are never exploited for memory-bounded aggregation.
"""
from __future__ import annotations

from typing import Optional

import pyarrow as pa

from igloo_tpu.plan import logical as L
from igloo_tpu.utils import stats, tracing


def estimated_bytes(provider) -> Optional[int]:
    """Best-effort source size, host-side, without reading data."""
    est = getattr(provider, "estimated_bytes", None)
    if est is not None:
        try:
            return est()
        except Exception:
            return None
    return None


def table_lane_bytes(provider) -> Optional[int]:
    """Estimated size of the WHOLE table as device lanes, every column of
    it: the raw estimate times the provider's `bytes_expansion` (compressed
    parquet decodes to ~3-4x its file size as int64/float64 lanes; in-memory
    Arrow tables report decoded bytes already, factor 1), times the carrier
    ratio its last scan measured (codec.carrier_ratio). A measure of tables
    against each other and against the monolithic share: the optimizer's
    join order and build sides (plan/optimizer.py) and the GRACE trigger
    (exec/grace.py) read it. What ONE scan may hold on the device is
    `estimated_lane_bytes`."""
    nb = estimated_bytes(provider)
    if nb is None:
        return None
    from igloo_tpu.exec import codec
    return int(nb * getattr(provider, "bytes_expansion", 1.0)
               * codec.carrier_ratio(provider))


def estimated_lane_bytes(provider, projection=None) -> Optional[int]:
    """The price of a scan: the bytes its lanes will hold on the device,
    from the provider's metadata alone (no data read). A function of the
    file and the columns read (`projection`: None reads every column), so
    the same scan is priced alike whatever ran before it; the chunked
    tier's trigger and chunk count (`chunk_count`) and serving's
    `predict_hbm_bytes` all read THIS.

    A provider that knows its rows and its columns' bounds without reading
    them (`lane_stats`: Parquet footers) is priced lane by lane, as the scan
    lays them out: the scan's capacity times each column's resident width,
    a null lane where a footer counts nulls, and the live lane. A carrier
    narrows the price only where the footer proves it (codec `_shrink_int`'s
    steps): an integer or date column whose statistics' range fits a
    narrower integer, a dictionary column's ids by the row count (a table
    has no more distinct strings than rows). A float64 column is priced at
    8 bytes — whether the codec ships it narrower (whole numbers, decimals),
    or a dictionary holds few strings, is known only once the values are
    read, and a price never follows what another query's scan found. So the
    price is an upper bound of what the scan holds, exact for the columns a
    footer bounds. A provider without such metadata (CSV, a
    MemTable, DBAPI) keeps the conservative price of its whole size times
    `bytes_expansion`."""
    stats_of = getattr(provider, "lane_stats", None)
    stats = stats_of() if stats_of is not None else None
    if stats is None:
        nb = estimated_bytes(provider)
        if nb is None:
            return None
        return int(nb * getattr(provider, "bytes_expansion", 1.0))
    from igloo_tpu.exec.batch import round_capacity
    from igloo_tpu.exec.codec import encoded_enabled, narrow_int_dtype
    rows, cols = stats
    narrow = encoded_enabled()
    per_lane = 1                                    # the live lane: bool
    for f in provider.schema():
        if projection is not None and f.name not in projection:
            continue
        lo, hi, nulls = cols.get(f.name, (None, None, True))
        if f.dtype.is_string:
            lo, hi = 0, rows - 1            # dictionary ids
        lane = f.dtype.device_dtype()
        # lo > hi: a column without a value, the narrowest carrier
        carrier = narrow_int_dtype(lo, hi, lane) \
            if narrow and lo is not None else None
        per_lane += (carrier or lane).itemsize + bool(nulls)
    return round_capacity(rows) * per_lane


def scan_prices(plan: L.LogicalPlan) -> dict:
    """{id(scan): `estimated_lane_bytes` of it} for every scan of `plan`
    that has a provider (None: nobody can size it). One routing decision
    prices each scan ONCE — a price costs a `head` of every file, and on a
    slow file system that is a tenth of a millisecond — and hands the
    prices to `chunk_count`."""
    return {id(sc): estimated_lane_bytes(sc.provider, sc.projection)
            for sc in L.walk_plan(plan)
            if isinstance(sc, L.Scan) and sc.provider is not None}


def priced_scans(plan: L.LogicalPlan) -> int:
    """What the scans of `plan` are priced at, in all (a scan nobody can
    size counts nothing)."""
    return sum(v or 0 for v in scan_prices(plan).values())


def chunk_count(plan: L.LogicalPlan, budget_bytes: int,
                prices: Optional[dict] = None) -> int:
    """How many chunks the largest scan priced over `budget_bytes` needs
    (0 = one program). `prices`: `scan_prices(plan)`, where the caller has
    them already. `budget_bytes` is what one program may scan: the
    resident share of the device (exec/cache.py hbm_budgets) — columns that
    fit it are cached once and every later query hits them — or the number a
    caller was given (`QueryEngine(chunk_budget_bytes=)`, the demotion
    ladder). Only scans that the fragment planner can actually stream —
    i.e. feeding a DECOMPOSABLE aggregate through scan/filter/project nodes —
    count: chunking anything else just unions the chunks back into one batch
    and pays fragment overhead for no memory bound (see module docstring)."""
    from igloo_tpu.cluster.fragment import _is_local
    from igloo_tpu.plan.aggsplit import DECOMPOSABLE
    want = 0
    for node in L.walk_plan(plan):
        if not (isinstance(node, L.Aggregate) and _is_local(node.input) and
                all(a.func in DECOMPOSABLE for a in node.aggs)):
            continue
        for sc in L.walk_plan(node.input):
            if isinstance(sc, L.Scan) and sc.provider is not None and \
                    sc.partition is None:
                nbytes = prices[id(sc)] if prices is not None else \
                    estimated_lane_bytes(sc.provider, sc.projection)
                try:
                    parts = sc.provider.num_partitions()
                except Exception:
                    parts = 1
                if nbytes is not None and nbytes > budget_bytes and parts > 1:
                    # the chunk count is DERIVED from the budget (how many
                    # budget-sized pieces the scan's lanes come to); the only
                    # clamp left is the provider's own partition granularity,
                    # and hitting it means per-chunk memory exceeds the
                    # budget — warn instead of silently un-bounding (the old
                    # hard min(.., 64) did exactly that past 64x budgets)
                    need = -(-nbytes // max(budget_bytes, 1))
                    if need > parts:
                        tracing.counter("chunked.chunks_clamped")
                        tracing.log.warning(
                            "chunked: %d chunks needed to bound memory but "
                            "provider has only %d partitions; per-chunk "
                            "working set will exceed the %d-byte budget",
                            need, parts, budget_bytes)
                    want = max(want, min(parts, need))
    return want


class LocalChunkExecutor:
    """Executes a fragmented plan in-process, one fragment at a time."""

    def __init__(self, catalog, jit_cache: Optional[dict] = None,
                 use_jit: bool = True, batch_cache=None, chunks: int = 4):
        self.catalog = catalog
        self._jit_cache = jit_cache
        self._use_jit = use_jit
        self._batch_cache = batch_cache
        self.chunks = max(2, chunks)

    def execute_to_arrow(self, plan: L.LogicalPlan) -> pa.Table:
        from igloo_tpu.catalog import EphemeralTable
        from igloo_tpu.cluster import serde
        from igloo_tpu.cluster.fragment import FRAG_PREFIX, DistributedPlanner
        from igloo_tpu.exec.executor import Executor

        planner = DistributedPlanner(
            [f"__chunk{i}" for i in range(self.chunks)])
        # chunk slots are not workers: Exchange-rooted shuffle fragments
        # need the worker fragment store + bucket fetch protocol, which the
        # in-process Executor below does not speak — plain partitioned scan
        # fragments only
        planner.shuffle_enabled = False
        frags = planner.plan(plan)

        results: dict[str, pa.Table] = {}
        base = self.catalog

        class _Overlay:
            def get(self, name: str):
                key = name.lower()
                if key.startswith(FRAG_PREFIX):
                    # a chunk's result lives for this execution and its name
                    # holds a per-query id: keyed by position (exec/fused.py
                    # `_c_scan`), so a repeated chunked query finds its merge
                    return EphemeralTable(results[key[len(FRAG_PREFIX):]])
                return base.get(name)

        overlay = _Overlay()
        # deserialize what we can upfront (fragments referencing earlier
        # results resolve later) and enqueue every partitioned scan read, in
        # fragment order, on the storage prefetcher: the reader thread
        # decodes chunk k+1's row groups while chunk k computes on device
        # (docs/storage.md#prefetch; IGLOO_STORAGE_PREFETCH=0 kills it)
        from igloo_tpu.storage import prefetch as _prefetch
        plans: dict[str, L.LogicalPlan] = {}
        items: list[tuple] = []
        for f in frags:
            try:
                p = serde.plan_from_json(f.plan, overlay)
            except Exception:
                continue  # needs a not-yet-computed fragment result
            plans[f.id] = p
            for sc in L.walk_plan(p):
                if isinstance(sc, L.Scan) and sc.provider is not None \
                        and sc.partition:
                    items.extend((sc.provider, i, sc.projection,
                                  sc.pushed_filters) for i in sc.partition)
        # fragments are appended children-first, so sequential order is
        # dependency-safe; chunk results are host Arrow (partials are small)
        with _prefetch.scan_prefetch(items), \
                stats.op("ChunkedExecution", chunks=self.chunks,
                         fragments=len(frags)):
            for i, f in enumerate(frags):
                p = plans.get(f.id)
                if p is None:
                    p = serde.plan_from_json(f.plan, overlay)
                ex = Executor(self._jit_cache, use_jit=self._use_jit,
                              batch_cache=self._batch_cache)
                with stats.op(f"Chunk[{i}]" if i < len(frags) - 1
                              else "ChunkMerge"):
                    results[f.id] = ex.execute_to_arrow(p)
                    # host Arrow row count — free, no device sync
                    stats.set_rows(results[f.id].num_rows)
            out = results[frags[-1].id]
            stats.set_rows(out.num_rows)
        return out
