"""QueryEngine: the session object.

Parity target: reference `QueryEngine` (crates/engine/src/lib.rs:28-62) — a session
wrapping catalog + UDFs with `register_table` and `execute(sql) -> batches` — but
the execution stack underneath is ours end-to-end (parse -> bind -> optimize ->
device execution), not a DataFusion delegation, and errors are raised as
IglooError instead of panicking (reference gap G9: lib.rs:55-56 uses `.expect`).

The built-in `capitalize` UDF mirrors the reference's
(crates/engine/src/lib.rs:71-95: first char upper, rest lower, NULL-preserving).
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import pyarrow as pa

from igloo_tpu import types as T
from igloo_tpu.catalog import Catalog, MemTable, TableProvider
from igloo_tpu.errors import CatalogError, IglooError, PlanError, \
    SnapshotChanged
from igloo_tpu.storage import snapshot as storage_snapshot
from igloo_tpu.exec.executor import Executor
from igloo_tpu.plan import logical as L
from igloo_tpu.plan.binder import Binder
from igloo_tpu.plan.optimizer import last_adaptive_decisions, optimize
from igloo_tpu.sql import ast as A
from igloo_tpu.sql.parser import parse_sql
from igloo_tpu.utils import stats, tracing, watch
from igloo_tpu.utils.tracing import span


@dataclass
class UdfDef:
    """Scalar UDF type signature; execution happens in the expression compiler
    (string UDFs run over dictionaries host-side, numeric ones as jnp lanes)."""
    name: str
    result: T.DataType

    def return_type(self, arg_types):
        return self.result


@dataclass
class QueryResult:
    table: pa.Table
    plan: Optional[L.LogicalPlan] = None
    elapsed_s: float = 0.0
    # per-query telemetry (operator tree, tier, transfer bytes, counter
    # deltas) — populated for SELECT and EXPLAIN ANALYZE
    stats: Optional[stats.QueryStats] = None

    @property
    def num_rows(self) -> int:
        return self.table.num_rows


# process default for QueryEngine(mesh=...): "auto" row-shards across all
# local devices when more than one is visible; the test suite pins this to
# None so the 8-virtual-device CPU mesh exercises single-device paths unless a
# test opts in explicitly
DEFAULT_MESH: object = "auto"


class QueryEngine:
    def __init__(self, catalog: Optional[Catalog] = None, use_jit: bool = True,
                 cache_budget_bytes: Optional[int] = None,
                 chunk_budget_bytes: Optional[int] = None,
                 mesh: object = "default"):
        if mesh == "default":
            mesh = DEFAULT_MESH
        from igloo_tpu.exec.cache import BatchCache, ResidentCache
        self.catalog = catalog if catalog is not None else Catalog()
        self.udfs: dict[str, UdfDef] = {}
        self._jit_cache: dict = {}
        self._use_jit = use_jit
        # what one program may scan (docs/out_of_core.md "The two
        # budgets"). A number given bounds it under both rules of the
        # ladder. None: each rule takes its share of the device's memory
        # (exec/cache.py hbm_budgets) — a decomposable aggregate over a scan
        # is chunked (exec/chunked.py) when the columns it reads are priced
        # over the RESIDENT share (1/2: SF10's seven q1 columns, 2.0 GB at
        # 2^26 lanes, run as one program over resident columns, as a worker's
        # scan fragment does), a join tree goes through GRACE (exec/grace.py)
        # when a table of it is sized over the MONOLITHIC share (1/8, 2.1 GB
        # on the v5e: no join at 2^26 lanes has run on a chip). The shares
        # are read at the first routing decision, so that constructing an
        # engine touches no device
        self._chunk_budget_bytes = chunk_budget_bytes
        self._shares: Optional[tuple] = None
        # multi-chip execution: "auto" = row-shard across all local devices
        # when more than one is visible (parallel/ShardedExecutor); None =
        # single-device; or an explicit jax.sharding.Mesh
        self._mesh_setting = mesh
        self._mesh = None
        # per-THREAD demotion override (serving degradation ladder,
        # docs/serving.md): a constrained chunk budget forces the chunked/
        # GRACE tiers — thread-local because the coordinator runs
        # concurrent queries through ONE engine and only the demoted query
        # must execute constrained
        self._demote_tls = threading.local()
        # HBM batch cache: scan results stay device-resident across queries
        # (the real version of the reference's unenforced CacheConfig, gap G7)
        # under the device's resident share unless the argument overrides it
        self.batch_cache = ResidentCache() if cache_budget_bytes is None \
            else BatchCache(cache_budget_bytes)
        # host-side query-result cache (the reference cache's actual shape:
        # query -> batches, crates/cache/src/lib.rs:20-56), snapshot-validated
        from igloo_tpu.exec.result_cache import ResultCache
        self.result_cache = ResultCache()
        # persistent cardinality hints for adaptive fused execution (beside the
        # XLA compile cache, so a fresh process compiles hinted programs first)
        from igloo_tpu.exec.hints import default_store
        self.hint_store = default_store()
        # reference parity: capitalize registered at construction (lib.rs:41-42)
        self.register_udf(UdfDef("capitalize", T.STRING))
        # SQL-queryable telemetry: SELECT * FROM system.metrics /
        # system.query_log through the normal engine path (system_tables.py)
        from igloo_tpu.system_tables import register_system_tables
        register_system_tables(self.catalog)

    # --- registration ---

    def register_table(self, name: str, provider) -> None:
        if isinstance(provider, pa.Table):
            provider = MemTable(provider)
        self.catalog.register(name, provider)
        # a replaced provider's id() can be reused by the allocator, so identity
        # tokens alone cannot be trusted across re-registration — evict eagerly
        self.batch_cache.invalidate_table(name.lower())
        self.result_cache.invalidate_table(name)

    def deregister_table(self, name: str) -> None:
        self.catalog.deregister(name)
        self.batch_cache.invalidate_table(name.lower())
        self.result_cache.invalidate_table(name)

    def register_udf(self, udf: UdfDef) -> None:
        self.udfs[udf.name.lower()] = udf

    # --- execution ---

    def plan(self, sql: str) -> L.LogicalPlan:
        with span("parse"):
            stmt = parse_sql(sql)
        if not isinstance(stmt, A.SelectStmt):
            raise PlanError("plan() requires a SELECT statement")
        with span("bind+optimize"):
            bound = Binder(self.catalog, udfs=self.udfs).bind(stmt)
            return optimize(bound)

    def execute(self, sql: str) -> pa.Table:
        return self.query(sql).table

    # alias mirroring a Python-session feel
    def sql(self, sql: str) -> pa.Table:
        return self.execute(sql)

    def query(self, sql: str) -> QueryResult:
        t0 = time.perf_counter()
        # before the statement's kind is known, so ahead of the `query` root
        # that only a SELECT opens (stats.collect)
        with span("parse"):
            stmt = parse_sql(sql)
        if isinstance(stmt, A.ShowTablesStmt):
            return QueryResult(pa.table({"table_name": self.catalog.names()}),
                               elapsed_s=time.perf_counter() - t0)
        if isinstance(stmt, A.DescribeStmt):
            schema = self.catalog.get(stmt.table).schema()
            return QueryResult(pa.table({
                "column_name": schema.names,
                "data_type": [repr(f.dtype) for f in schema],
                "nullable": [f.nullable for f in schema],
            }), elapsed_s=time.perf_counter() - t0)
        if isinstance(stmt, A.ExplainStmt):
            bound = Binder(self.catalog, udfs=self.udfs).bind(stmt.query)
            plan = optimize(bound)
            text = L.plan_tree_str(plan)
            for d in last_adaptive_decisions():
                # adaptive reorder attribution (docs/adaptive.md): which
                # greedy order won and whether observations or estimates
                # drove it
                text += (f"\n-- adaptive: strategy={d['strategy']} "
                         f"join_order={d['join_order']} "
                         f"adaptive_source={d['adaptive_source']}")
            qs = None
            if stmt.analyze:
                # EXPLAIN ANALYZE executes through the SAME routing ladder as
                # a real query (host / chunked / GRACE / normal), with stats
                # collection in DETAIL mode: actual per-operator row counts,
                # per-node wall time, compile/execute split, transfer bytes,
                # and GRACE per-partition rollups (docs/observability.md)
                peak0 = stats.device_peak_hbm_bytes()

                def rebind() -> L.LogicalPlan:
                    b = Binder(self.catalog, udfs=self.udfs).bind(stmt.query)
                    return optimize(b)

                with stats.collect(sql, detail=True) as qs:
                    table, plan = self._execute_pinned(plan, rebind)
                    qs.rows = table.num_rows
                self._harvest_adaptive(qs, plan, peak_hbm0=peak0)
                text += "\n-- actual (operator tree):\n"
                text += stats.render_tree(qs)
                delta = qs.counters
                nparts = delta.get("grace.partitions", 0)
                if nparts:
                    text += f"\n-- grace.partitions: {nparts}"
                for ph in ("partition", "join", "merge"):
                    ms = delta.get(f"grace.{ph}_ms", 0)
                    if ms:
                        text += f"\n-- grace.{ph}_s: {ms / 1000:.3f}"
                # persistent XLA compile-cache traffic for THIS query (the
                # jax.monitoring hooks in igloo_tpu/compile_cache.py run on
                # the compiling thread, so the delta is exact)
                cc_hit = delta.get("compile_cache.hit", 0)
                cc_miss = delta.get("compile_cache.miss", 0)
                if cc_hit or cc_miss:
                    text += (f"\n-- compile_cache: hits={cc_hit} "
                             f"misses={cc_miss}")
                # object-store attribution (docs/storage.md): ranged reads,
                # policy retries, prefetcher hits, and whether the query
                # paid a snapshot re-plan
                sreads = delta.get("storage.read", 0)
                sretry = delta.get("storage.snapshot_retry", 0)
                if sreads or sretry:
                    text += (f"\n-- storage: reads={sreads} "
                             f"retries={delta.get('storage.retry', 0)} "
                             f"prefetch_hits="
                             f"{delta.get('storage.prefetch_hit', 0)} "
                             f"snapshot_retries={sretry}")
                # local mesh-tier attribution: did the sharded executor run,
                # across how many chips, at what per-device lane width (the
                # chip-level half of the two-level topology,
                # docs/distributed.md). Keyed on the TIER, not the upload
                # counters: a warm run serves row-sharded batches from the
                # scan cache (zero uploads) but still executes sharded
                uploads = delta.get("mesh.shard_uploads", 0)
                if uploads or qs.tier == "sharded":
                    mesh = self._resolve_mesh()
                    ndev = int(mesh.devices.size) if mesh is not None else 1
                    lanes = delta.get("mesh.sharded_lanes", 0)
                    text += (f"\n-- mesh: devices={ndev} "
                             f"shard_uploads={uploads}")
                    # lane width only when this query actually uploaded —
                    # a warm run's batches come from the scan cache and a
                    # zero-delta division would claim 0 lanes per device
                    if uploads:
                        text += (f" lanes_per_device="
                                 f"{lanes // uploads // max(ndev, 1)}")
                    else:
                        text += " (batches served from the scan cache)"
                if qs.trace_id:
                    # flight-recorder pointer: the executed query's stitched
                    # timeline, queryable in SQL or exportable for Perfetto
                    # (docs/observability.md#distributed-tracing)
                    text += (f"\n-- trace: {qs.trace_id} (SELECT * FROM "
                             "system.query_traces WHERE trace_id = "
                             f"'{qs.trace_id}'; coordinator 'trace' action; "
                             "IGLOO_TRACE_DIR)")
            return QueryResult(pa.table({"plan": text.split("\n")}), plan=plan,
                               elapsed_s=time.perf_counter() - t0, stats=qs)
        if isinstance(stmt, A.CreateTableAsStmt):
            res = self._run_select(stmt.query)
            self.register_table(stmt.name, MemTable(res))
            return QueryResult(pa.table({"status": [f"created {stmt.name}"]}),
                               elapsed_s=time.perf_counter() - t0)
        if isinstance(stmt, A.DropTableStmt):
            if stmt.name.lower() not in self.catalog and not stmt.if_exists:
                raise CatalogError(f"table not found: {stmt.name}")
            if stmt.name.lower() in self.catalog:
                # full deregistration: evicts the table's HBM batches and any
                # cached results sourced from it
                self.deregister_table(stmt.name)
            return QueryResult(pa.table({"status": [f"dropped {stmt.name}"]}),
                               elapsed_s=time.perf_counter() - t0)
        if isinstance(stmt, A.SelectStmt):
            peak0 = stats.device_peak_hbm_bytes()
            with stats.collect(sql) as qs:
                table, plan = self._run_select(stmt, want_plan=True)
                qs.rows = table.num_rows
            self._harvest_adaptive(qs, plan, peak_hbm0=peak0)
            return QueryResult(table, plan=plan,
                               elapsed_s=time.perf_counter() - t0, stats=qs)
        raise IglooError(f"unsupported statement {type(stmt).__name__}")

    def _harvest_adaptive(self, qs: Optional[stats.QueryStats],
                          plan: Optional[L.LogicalPlan],
                          peak_hbm0: int = 0) -> None:
        """Fold a finished query's free cardinality observations into the
        process-wide AdaptiveStats store (docs/adaptive.md): per-subtree
        observed rows, the root cardinality, and — when a join AND both of
        its inputs were observed in this query — the join's input total, so
        selectivity is derivable. Best-effort by contract: stale or missing
        stats mis-route plans, never break them."""
        from igloo_tpu.exec import hints
        peak = 0
        root_fp = hints.plan_fp(plan) if plan is not None else None
        if qs is not None:
            # watchtower baseline check (docs/observability.md#watchtower):
            # BEFORE the adaptive gate — the anomaly detector is independent
            # of IGLOO_ADAPTIVE (its own kill switch is IGLOO_WATCH, checked
            # inside check_query). Runs after stats.collect published the
            # trace, so an escalation's pin() finds it ring-resident.
            # The one post-query watermark read, shared with the adaptive
            # recorder below.
            peak = stats.device_peak_hbm_bytes()
            watch.check_query(
                root_fp,
                qs.elapsed_s, qs=qs, qid=str(qs.qid or ""),
                trace_id=qs.trace_id or "", sql=qs.sql, tier=qs.tier,
                hbm_bytes=(float(peak - peak_hbm0)
                           if peak > peak_hbm0 else 0.0))
        if qs is None or not hints.adaptive_enabled():
            return
        obs = {k: n for k, n in qs.observations if k is not None}
        if root_fp is not None and qs.rows is not None:
            obs[root_fp] = int(qs.rows)
        # device-memory watermark for the admission gate (docs/serving.md).
        # The watermark is process-CUMULATIVE (monotonic), so only a query
        # that RAISED it (`> peak_hbm0`, the caller's pre-query snapshot)
        # may record — otherwise every small query after one big one would
        # inherit the global peak, ratchet its prediction past the serving
        # budget, and demote forever. The recorded value is still an upper
        # bound involving this query, which is the right direction.
        peak_hbm = 0
        if root_fp is not None:
            peak_hbm = peak
            if peak_hbm <= peak_hbm0:
                peak_hbm = 0
        if not obs and not peak_hbm:
            return
        # the CURRENT process-wide store, not one cached at engine
        # construction: reset_adaptive_store() (tests) would otherwise leave
        # a long-lived engine recording into a store no planner reads
        store = hints.adaptive_store()
        for k, n in obs.items():
            store.observe(k, rows=n)
        if peak_hbm:
            store.observe(root_fp, peak_hbm_bytes=int(peak_hbm))
        if plan is not None:
            for node in L.walk_plan(plan):
                if isinstance(node, L.Join):
                    jf = hints.plan_fp(node)
                    lf = hints.plan_fp(node.left)
                    rf = hints.plan_fp(node.right)
                    if jf in obs and lf in obs and rf in obs:
                        store.observe(jf, in_rows=obs[lf] + obs[rf])
        store.flush()
        tracing.counter("adaptive.observed", len(obs))

    @contextlib.contextmanager
    def demoted(self, budget_bytes: Optional[int] = None):
        """Run the enclosed executions on this thread one rung down the
        degradation ladder (docs/serving.md): a constrained `budget_bytes`
        makes `_execute_plan` route over-budget plans to the chunked/GRACE
        tiers at THAT budget. The serving front door uses this when a query
        hits RESOURCE_EXHAUSTED/MemoryError (or is predicted past the whole
        HBM budget) instead of failing it."""
        prev = getattr(self._demote_tls, "budget", None)
        self._demote_tls.budget = budget_bytes
        try:
            yield
        finally:
            self._demote_tls.budget = prev

    def _derived_shares(self) -> tuple:
        if self._shares is None:
            from igloo_tpu.exec.cache import hbm_budgets
            self._shares = hbm_budgets()
        return self._shares

    @property
    def chunk_budget_bytes(self) -> int:
        """The constructor's number, else the device's monolithic share:
        what a table of a join may come to before GRACE partitions it, and
        what the demotion ladder takes its quarter of."""
        if self._chunk_budget_bytes is not None:
            return self._chunk_budget_bytes
        return self._derived_shares()[1]

    def _chunk_budget(self) -> int:
        """`chunk_budget_bytes` under this thread's demotion, if any."""
        override = getattr(self._demote_tls, "budget", None)
        if override is not None:
            return min(int(override), self.chunk_budget_bytes)
        return self.chunk_budget_bytes

    def _scan_budget(self) -> int:
        """What the columns one scan-and-aggregate program reads may be
        priced at (exec/chunked.py chunk_count): a number given — the
        constructor's, this thread's demotion — as `_chunk_budget`; none
        given, the device's resident share, under which the scan cache
        holds them once for every later query."""
        if self._chunk_budget_bytes is not None or \
                getattr(self._demote_tls, "budget", None) is not None:
            return self._chunk_budget()
        return self._derived_shares()[0]

    def _resolve_mesh(self):
        """The execution mesh, resolved once: None for single-device."""
        if self._mesh is None and self._mesh_setting is not None:
            from igloo_tpu.parallel.mesh import resolve_mesh
            self._mesh = resolve_mesh(self._mesh_setting)
            if self._mesh is None:
                self._mesh_setting = None
        return self._mesh

    def _executor(self) -> Executor:
        mesh = self._resolve_mesh()
        if mesh is not None:
            from igloo_tpu.parallel.executor import ShardedExecutor
            return ShardedExecutor(self._jit_cache, use_jit=self._use_jit,
                                   batch_cache=self.batch_cache, mesh=mesh)
        return Executor(self._jit_cache, use_jit=self._use_jit,
                        batch_cache=self.batch_cache, hints=self.hint_store)

    def _execute_plan(self, plan: L.LogicalPlan) -> pa.Table:
        """The full routing ladder shared by _run_select and EXPLAIN ANALYZE:
        sharded executor (a resolved multi-chip mesh) -> chunked tier
        (decomposable aggregates over scans whose columns are priced over
        `_scan_budget`) -> GRACE tier (join trees with a table over
        `_chunk_budget`, exec/grace.py) -> device executor. The mesh takes
        precedence over single-device chunking / out-of-core: the sharded
        executor already bounds per-chip memory by row-sharding, and
        silently chunking would discard the parallelism."""
        from igloo_tpu.exec.chunked import LocalChunkExecutor, \
            chunk_count, scan_prices
        qs = stats.current()
        budget = self._chunk_budget()
        prices = scan_prices(plan)
        tracing.counter("engine.route_priced_bytes",
                        sum(v or 0 for v in prices.values()))
        mesh = self._resolve_mesh()
        chunks = 0 if mesh is not None else \
            chunk_count(plan, self._scan_budget(), prices)
        grace_found = None
        if mesh is None and not chunks:
            from igloo_tpu.exec.grace import find_grace_join
            grace_found = find_grace_join(plan, budget)
        with span("execute"):
            if chunks:
                tracing.counter("engine.chunked_route")
                if qs is not None:
                    qs.tier = "chunked"
                return LocalChunkExecutor(
                    self.catalog, self._jit_cache, use_jit=self._use_jit,
                    batch_cache=self.batch_cache,
                    chunks=chunks).execute_to_arrow(plan)
            if grace_found:
                from igloo_tpu.exec.grace import GraceJoinExecutor
                tracing.counter("engine.grace_route")
                if qs is not None:
                    qs.tier = "grace"
                return GraceJoinExecutor(
                    self.catalog, self._jit_cache, use_jit=self._use_jit,
                    batch_cache=self.batch_cache, hints=self.hint_store,
                    budget_bytes=budget,
                ).execute_to_arrow(plan, grace_found)
            if qs is not None:
                qs.tier = "sharded" if mesh is not None else "device"
            return self._executor().execute_to_arrow(plan)

    def _execute_pinned(self, plan: L.LogicalPlan, rebind):
        """Execute under a pinned storage snapshot (storage/snapshot.py):
        every provider's first snapshot() pins the etags all ranged reads
        then verify. A source mutated mid-query raises SnapshotChanged; the
        engine converts it into exactly ONE re-plan at the new snapshot
        (counter `storage.snapshot_retry`) — caches for the changed table
        dropped, plan re-bound via `rebind()`, execution re-pinned. A
        second mutation during the retry propagates: a source churning
        faster than the query can run is an error, not a livelock."""
        try:
            with storage_snapshot.pinned_scope():
                return self._execute_plan(plan), plan
        except SnapshotChanged as ex:
            tracing.counter("storage.snapshot_retry")
            from igloo_tpu.cluster import events
            events.emit("snapshot_retry", severity="warn",
                        table=ex.table or "")
            tracing.log.warning(
                "storage: snapshot changed mid-query (%s); re-planning once",
                ex)
            if ex.table:
                self.batch_cache.invalidate_table(ex.table)
                self.result_cache.invalidate_table(ex.table)
            plan = rebind()
            with storage_snapshot.pinned_scope():
                return self._execute_plan(plan), plan

    def _run_select(self, stmt: A.SelectStmt, want_plan: bool = False):
        from igloo_tpu.exec.result_cache import plan_cache_key
        state: dict = {}

        def bind() -> L.LogicalPlan:
            with span("bind+optimize"):
                bound = Binder(self.catalog, udfs=self.udfs).bind(stmt)
                p = optimize(bound)
            state["rkey"] = plan_cache_key(p)
            return p

        plan = bind()
        if state["rkey"] is not None:
            hit = self.result_cache.get(state["rkey"])
            if hit is not None:
                qs = stats.current()
                if qs is not None:
                    qs.tier = "result_cache"
                return (hit, plan) if want_plan else hit
        table, plan = self._execute_pinned(plan, bind)
        if state["rkey"] is not None:
            self.result_cache.put(state["rkey"], table)
        if want_plan:
            return table, plan
        return table
