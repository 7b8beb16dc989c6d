"""Worker daemon: registers with the coordinator, heartbeats, executes
fragments, and serves results to peers.

Parity: the reference worker (crates/worker/src/main.rs:14-52 — uuid identity,
register, 5 s heartbeat loop, task service) — but where the reference's
`execute_task` logs and returns "SUBMITTED" and its shuffle fetch returns empty
bytes (crates/worker/src/service.rs:14-32, both stubs), this worker REALLY
executes: it deserializes the fragment's plan, resolves dependency results
(from its own store or by fetching from the PEER worker that produced them —
the worker<->worker transport the reference declared via GetDataForTask and
never built), runs the plan on its local device tier, and serves the result as
an Arrow Flight stream.

Results live in a bytes-budgeted `FragmentStore` (cluster/exchange.py): an
`Exchange`-rooted fragment hash-partitions its result at store time, and
`do_get` tickets address either a whole fragment or ONE bucket slice — the
per-bucket transport that lets a join fragment fetch only its bucket of each
peer's result instead of the whole table. Transfers stream record-batch-wise
in both directions.

Transport is Arrow Flight end-to-end (one stack for control actions and data
streams) instead of the reference's parallel tonic-gRPC + Flight pair.

Flight serves every RPC on its own thread; `execute_fragment` actions are
additionally bounded by a slot semaphore (IGLOO_WORKER_SLOTS, default a
small multiple of the local device count) so concurrent fragment executions
queue instead of racing the device into OOM — the worker-side half of the
serving story (docs/serving.md). `worker.slots_busy` gauges the occupancy.
"""
from __future__ import annotations

import json
import os
import threading
import time
import uuid
from typing import Optional

import pyarrow as pa
import pyarrow.flight as flight

from igloo_tpu.catalog import Catalog, EphemeralTable
from igloo_tpu.cluster import events, exchange, faults, protocol, serde
from igloo_tpu.cluster.fragment import (FRAG_PREFIX, _frag_refs,
                                        _subtree_scan, _with_partition)
from igloo_tpu.exec import encoded
from igloo_tpu.storage import prefetch as _prefetch
from igloo_tpu.cluster import rpc
from igloo_tpu.cluster.rpc import flight_action, flight_stream_batches
from igloo_tpu.cluster.rpc import normalize as _normalize
from igloo_tpu.errors import IglooError
from igloo_tpu.plan import logical as L
from igloo_tpu.utils import flight_recorder, timeseries, tracing


# lock discipline (checked by igloo-lint lock-discipline): Flight serves
# every RPC on its own thread, so two concurrent execute_fragment actions
# race the WorkerServer's lazy mesh resolution — `_mesh`/`_mesh_setting`
# must be read and written under the server lock (the fragment store has its
# own internal lock, see cluster/exchange.py)
_GUARDED_BY = {"_lock": ("_mesh", "_mesh_setting")}


#: worker-side fragment-execution slot bound: Flight runs every RPC on its
#: own thread, so without this two concurrent execute_fragment actions race
#: each other into device OOM. Default = a small multiple of the number of
#: INDEPENDENT execution units the worker has: local devices for a
#: single-device worker (fragments on one device mostly serialize on it
#: anyway; a little oversubscription overlaps host-side decode with device
#: work), but local_devices / mesh_devices for a MESH worker — a sharded
#: fragment occupies every chip of the mesh at once, so 2 x device_count
#: slots would admit 16 whole-mesh fragments against HBM sized for ~2 and
#: invalidate the coordinator's per-host HBM predictions (docs/serving.md).
WORKER_SLOTS_ENV = "IGLOO_WORKER_SLOTS"


def _default_slots(mesh_devices: int = 1) -> int:
    try:
        import jax
        local = jax.local_device_count()
    except Exception:
        return 2
    units = max(1, local // max(mesh_devices, 1))
    return max(2, 2 * units)


def _plan_wants_mesh(plan) -> bool:
    """True when a fragment's plan carries a blocking operator the LOCAL mesh
    tier accelerates (join / aggregate / set op / distinct / window / sort):
    those route through the ShardedExecutor so the fragment runs D-way across
    the worker's chips — the inner level of the two-level parallelism
    (docs/distributed.md). Scan/filter/project (and Exchange-rooted partition)
    fragments stay single-device: their output is gathered host-side for the
    store anyway, and the sharded tier's padded per-device capacities only
    add upload overhead there."""
    return any(isinstance(n, (L.Join, L.Aggregate, L.SetOpJoin, L.Distinct,
                              L.Window, L.Sort))
               for n in L.walk_plan(plan))


def _dep_key(frag_id: str, bucket) -> str:
    """FragmentStore key for a peer-fetched dependency slice. With
    bucket=None this is both the whole-result key and the prefix every slice
    of that dependency shares (how `release` finds them); real fragment ids
    are hex, so `__dep_*` keys cannot collide with produced results."""
    base = f"__dep_{frag_id}:"
    return base if bucket is None else f"{base}{bucket}"


class _OverlayCatalog:
    """Base catalog + per-fragment `__frag_*` dependency tables."""

    def __init__(self, base: Catalog, extra: dict):
        self._base = base
        self._extra = extra

    def get(self, name: str):
        key = name.lower()
        if key in self._extra:
            return self._extra[key]
        return self._base.get(name)


class WorkerServer(flight.FlightServerBase):
    """Flight server half of the worker. Thread-safe: Flight handles each RPC
    on its own thread; the fragment store and engine state are lock-guarded."""

    def __init__(self, location: str, worker_id: Optional[str] = None,
                 use_jit: bool = True, mesh: object = "default",
                 store_budget_bytes: Optional[int] = None,
                 slots: Optional[int] = None, **kw):
        mw = rpc.server_middleware()
        if mw is not None:
            kw.setdefault("middleware", mw)
        ah = rpc.server_auth_handler()
        if ah is not None:
            kw.setdefault("auth_handler", ah)
        rpc.warn_if_open_bind(location.split("://")[-1].rsplit(":", 1)[0],
                              "worker")
        # pick up IGLOO_FAULTS set after import (in-process test clusters)
        faults.refresh()
        super().__init__(location, **kw)
        self.worker_id = worker_id or uuid.uuid4().hex[:12]
        self.advertise: str = location
        self._catalog = Catalog()
        # own results AND peer-fetched dependency slices (under `__dep_*`
        # keys): one bucketed, bytes-budgeted, spill-backed store, so fetched
        # slices count against the same RSS budget as produced results
        self._store = exchange.FragmentStore(store_budget_bytes)
        self._use_jit = use_jit
        self._jit_cache: dict = {}
        self._lock = threading.Lock()
        self._mesh_setting = mesh  # same rule as QueryEngine (resolve_mesh)
        self._mesh = None
        # devices one fragment will occupy (the LOCAL mesh tier): reported to
        # the coordinator at registration/heartbeat so the planner sizes
        # bucket counts with hosts and shard counts with chips
        # (docs/distributed.md "Two-level topology"). Computed once from the
        # SETTING — the lazily resolved mesh spans the same devices.
        from igloo_tpu.parallel.mesh import mesh_device_count
        self.mesh_devices = mesh_device_count(mesh)
        # the HBM scan cache: under the resident share of this worker's
        # device, as a QueryEngine's is (docs/out_of_core.md)
        from igloo_tpu.exec.cache import ResidentCache
        self._batch_cache = ResidentCache()
        # fragment-execution slot bound (env > constructor > device-derived
        # default): concurrent execute_fragment RPCs queue on the semaphore
        # instead of racing the device into OOM (docs/serving.md)
        env = os.environ.get(WORKER_SLOTS_ENV)
        if env:
            slots = int(env)
        if slots is None:
            slots = _default_slots(self.mesh_devices)
        self.slots = max(1, slots)
        self._slots = threading.BoundedSemaphore(self.slots)

    # --- execution ---

    def _executor(self, plan=None):
        # multi-chip worker hosts row-shard fragments across their local
        # devices; same mesh-resolution rule as QueryEngine (so tests pin
        # DEFAULT_MESH and production configures via the constructor).
        # Lazy resolution holds the server lock: Flight runs each RPC on its
        # own thread, and two concurrent fragments must not resolve (and
        # assign) the mesh twice
        with self._lock:
            if self._mesh is None and self._mesh_setting is not None:
                from igloo_tpu.parallel.mesh import resolve_mesh
                self._mesh = resolve_mesh(self._mesh_setting)
                if self._mesh is None:
                    self._mesh_setting = None
            mesh = self._mesh
        if mesh is not None and (plan is None or _plan_wants_mesh(plan)):
            from igloo_tpu.parallel.executor import ShardedExecutor
            return ShardedExecutor(self._jit_cache, use_jit=self._use_jit,
                                   batch_cache=self._batch_cache,
                                   mesh=mesh)
        from igloo_tpu.exec.executor import Executor
        return Executor(self._jit_cache, use_jit=self._use_jit,
                        batch_cache=self._batch_cache)

    def _fetch_dep(self, frag_id: str, addr: str,
                   bucket: Optional[int] = None,
                   nbuckets: Optional[int] = None,
                   deadline: Optional[float] = None) -> pa.Table:
        # own store first: a co-located dependency (or its bucket slice) is a
        # zero-copy local read, not a transfer
        if frag_id in self._store:
            try:
                # partitioned slices are stored in carrier form
                # (cluster/exchange.py put) — widen at the consumption edge
                return encoded.decode_table(
                    self._store.get_table(frag_id, bucket, nbuckets))
            except (KeyError, ValueError) as ex:
                raise IglooError(f"DEP_UNAVAILABLE:{frag_id} local: {ex}")
        dep_key = _dep_key(frag_id, bucket)
        if dep_key in self._store:
            return self._store.get_table(dep_key)
        # peer fetch: the worker that executed the dependency streams it
        # batch-wise; an unreachable peer is reported with a marker the
        # coordinator recognizes (it requeues the dependency on a live
        # worker). `deadline` is the query's remaining budget (shipped by the
        # coordinator as a relative timeout_s) — a HUNG peer becomes
        # DEP_UNAVAILABLE at the deadline instead of wedging the fragment.
        try:
            with tracing.span("exchange.fetch", frag=frag_id,
                              bucket=bucket, addr=addr) as sp:
                ticket = exchange.make_ticket(frag_id, bucket, nbuckets)
                schema, batch_iter = flight_stream_batches(addr, ticket,
                                                           deadline=deadline)
                batches = []
                nbytes = 0
                for batch in batch_iter:
                    batches.append(batch)
                    nbytes += batch.nbytes
                    tracing.counter("exchange.fetch_rows", batch.num_rows)
                    tracing.counter("exchange.fetch_bytes", batch.nbytes)
                # fetch counters above price the WIRE (carrier) bytes; the
                # dep cache below holds the decoded table so co-located
                # dependents never re-widen
                table = encoded.decode_table(
                    pa.Table.from_batches(batches, schema=schema))
                sp.attrs.update(rows=table.num_rows, bytes=nbytes)
        except Exception as ex:
            raise IglooError(f"DEP_UNAVAILABLE:{frag_id} peer {addr}: {ex}")
        # keep the slice in the budgeted store: co-located dependents reuse
        # it instead of re-downloading (it may spill under memory pressure);
        # the coordinator's final "release" drops it
        self._store.put(dep_key, table)
        return table

    def _execute_fragment(self, frag_id: str, plan_json: dict,
                          addr_of: dict, deadline: Optional[float],
                          budget: Optional[int] = None) -> dict:
        """Execute one deserialized dispatch (protocol fields already parsed
        out by `_handle_execute_fragment` — this method is wire-format-free):
        resolve dependencies, run the plan, store the result, and return the
        fragment_stats report. A dispatch carrying `budget` is part of an
        OVERSIZED query (docs/out_of_core.md): Exchange fragments stream
        their scan piece-wise into per-bucket spill segments, and join
        fragments get the worker-local GRACE ladder for residual skew."""
        overlay: dict = {}
        input_rows = 0
        # per-fragment counter delta: thread-isolated, so concurrent
        # fragments on this worker report only their own transfers/compiles
        with tracing.counter_delta() as delta:
            with tracing.span("fragment.plan"):
                t_dep0 = time.perf_counter()
                for ref in _frag_refs(plan_json):
                    dep_id = ref["table"][len(FRAG_PREFIX):]
                    name = ref["table"].lower()
                    if name in overlay:
                        continue
                    t = self._fetch_dep(dep_id, addr_of.get(dep_id, ""),
                                        ref.get("bucket"), ref.get("buckets"),
                                        deadline=deadline)
                    input_rows += t.num_rows
                    overlay[name] = EphemeralTable(t)
                dep_s = time.perf_counter() - t_dep0
                catalog = _OverlayCatalog(self._catalog, overlay)
                plan = serde.plan_from_json(plan_json, catalog)
            partition = salt = None
            if isinstance(plan, L.Exchange):
                # fragment-root exchange: execute the input, hash-partition
                # the result at store time (per-bucket slices + metadata);
                # a salted exchange spreads/replicates the flagged hot
                # bucket (docs/adaptive.md)
                partition = (plan.keys, plan.buckets)
                if plan.salt_role is not None:
                    salt = (plan.salt_bucket, plan.salt, plan.salt_role)
                plan = plan.input
            t0 = time.perf_counter()
            streamed = None
            if partition is not None and budget:
                streamed = self._try_stream_exchange(
                    frag_id, plan, partition, salt, budget, deadline)
            if streamed is not None:
                ex, ent, nrows = streamed
                elapsed = time.perf_counter() - t0
            else:
                with tracing.span("fragment.execute") as sp:
                    ex = self._executor(plan)
                    table = self._run_plan(ex, plan, catalog, budget)
                    sp.attrs = {"rows": table.num_rows,
                                "mesh_devices": int(getattr(ex, "n_dev", 1))}
                nrows = table.num_rows
                elapsed = time.perf_counter() - t0
                with tracing.span("fragment.store"):
                    ent = self._store.put(frag_id, table,
                                          partition=partition, salt=salt)
        tracing.counter("worker.fragments")
        # local mesh-tier attribution: how many chips this fragment ran
        # across (1 = single-device) and its result rows per chip — the
        # per-fragment numbers last_metrics / EXPLAIN ANALYZE surface so the
        # two-level W x D parallelism is verifiable, not assumed
        mesh_devices = int(getattr(ex, "n_dev", 1))
        if mesh_devices > 1:
            tracing.counter("mesh.sharded_fragments")
        # the fragment_stats report, typed through the registry (None deltas
        # are omitted on the wire — consumers read sparsely); result_bytes is
        # the Arrow size of the stored result, which the coordinator's
        # adaptive recording sums per join side
        # a streamed (spilled) entry keeps only the resident tail in
        # `nbytes`; its true result size is the per-bucket meta sum
        result_bytes = ent.nbytes
        if getattr(ent, "bucket_files", None):
            result_bytes = sum(int(m.get("bytes", 0)) for m in ent.meta or [])
        out = protocol.FRAGMENT_STATS.build(
            id=frag_id, rows=nrows,
            elapsed_s=round(elapsed, 6), worker=self.worker_id,
            dep_fetch_s=round(dep_s, 6),
            input_rows=input_rows,
            mesh_devices=mesh_devices,
            mesh_rows_per_device=nrows // mesh_devices,
            result_bytes=result_bytes,
            h2d_bytes=delta.get("xfer.h2d_bytes"),
            d2h_bytes=delta.get("xfer.d2h_bytes"),
            jit_misses=delta.get("jit.miss"),
            cache_hits=delta.get("cache.hit"),
            exchange_rows=delta.get("exchange.fetch_rows"),
            exchange_bytes=delta.get("exchange.fetch_bytes"))
        if partition is not None:
            out["buckets"] = partition[1]
            # UNSALTED per-bucket rows: the coordinator's skew sketch must
            # see the key distribution, not the salted layout
            out["bucket_rows"] = ent.base_rows
            if salt is not None:
                out["salted"] = True
        return out

    def _try_stream_exchange(self, frag_id: str, plan, partition, salt,
                             budget: int, deadline: Optional[float]):
        """Streaming exchange under the out-of-core budget: instead of
        materializing the fragment's whole result and partitioning at store
        time (the classic path builds the full input in RAM first), execute
        the scan subtree ONE provider partition at a time — each piece fed
        by the storage prefetcher — and hash-route it straight into the
        store's per-bucket spill segments (cluster/exchange.py StreamingPut).
        Returns (executor, stored entry, rows) or None when the input has no
        multi-partition scan to stride, in which case the classic path runs
        unchanged."""
        sc = _subtree_scan(plan)
        if sc is None or sc.provider is None:
            return None
        if sc.partition:
            indices = [int(i) for i in sc.partition]
        else:
            try:
                indices = list(range(sc.provider.num_partitions()))
            except Exception:
                return None
        if len(indices) <= 1:
            return None
        keys, nbuckets = partition
        ex = self._executor(plan)
        handle = self._store.stream_put(frag_id, list(keys), nbuckets,
                                        salt=salt, budget_bytes=budget)
        items = [(sc.provider, i, sc.projection, sc.pushed_filters)
                 for i in indices]
        rows = 0
        try:
            with tracing.span("exchange.stream", frag=frag_id,
                              pieces=len(indices), buckets=nbuckets) as sp, \
                    _prefetch.scan_prefetch(items, deadline=deadline):
                for i in indices:
                    piece = _with_partition(plan, (i,))
                    t = ex.execute_to_arrow(piece)
                    rows += t.num_rows
                    handle.append(t)
                with tracing.span("fragment.store"):
                    ent = handle.finish()
                sp.attrs.update(rows=rows)
        except Exception:
            handle.abort()
            raise
        return ex, ent, rows

    def _run_plan(self, ex, plan, catalog, budget: Optional[int]):
        """Run one fragment plan, with the worker-local out-of-core ladder
        in front when the dispatch carries a budget: the planner's buckets
        are budget-sized by construction, so a join fragment whose inputs
        STILL exceed the per-worker budget (residual skew — one hot key
        class) recurses through the single-node GRACE loop locally instead
        of OOMing. Mesh-sharded fragments skip the ladder — row-sharding
        already bounds per-chip bytes."""
        if budget and int(getattr(ex, "n_dev", 1)) <= 1 and \
                any(isinstance(n, L.Join) for n in L.walk_plan(plan)):
            from igloo_tpu.exec.grace import (GraceJoinExecutor,
                                              find_grace_join)
            found = find_grace_join(plan, budget)
            if found is not None:
                tracing.counter("engine.grace_route")
                gx = GraceJoinExecutor(catalog, self._jit_cache,
                                       use_jit=self._use_jit,
                                       batch_cache=self._batch_cache,
                                       budget_bytes=budget)
                return gx.execute_to_arrow(plan, found)
        return ex.execute_to_arrow(plan)

    # --- Flight surface ---

    def _handle_execute_fragment(self, req: dict) -> dict:
        """The execute_fragment action body: parse the dispatch through the
        registry (a malformed payload fails HERE, naming the field), wait
        for an execution slot, run, and return the stats report. Every wire
        field is plucked in this one method — `_execute_fragment` below is
        wire-format-free."""
        disp = protocol.DISPATCH.parse(req)
        frag_id = disp["id"]
        addr_of: dict = {}
        for d in disp["deps"]:
            dep = protocol.DISPATCH_DEP.parse(d)
            addr_of[dep["id"]] = dep["addr"]
        # the coordinator ships the query's remaining budget as a RELATIVE
        # timeout (clocks differ across machines); anchor it here
        timeout_s = disp["timeout_s"]
        deadline = time.time() + timeout_s if timeout_s is not None else None
        # flight-recorder: the dispatch request carries the query's
        # trace context; this worker's span tree (rooted at a fresh
        # request scope — span hygiene for the reused gRPC thread) rides
        # back beside the fragment stats for the coordinator to stitch
        ctx = None
        if disp["trace"]:
            ctx = protocol.TRACE_CTX.parse(disp["trace"])
        trace = None
        if ctx is not None and flight_recorder.enabled():
            trace = flight_recorder.Trace(trace_id=ctx["trace_id"],
                                          qid=frag_id)
        with flight_recorder.request_scope(
                trace, "execute_fragment",
                proc=f"worker:{self.worker_id}",
                parent_id=ctx["parent_id"] if ctx is not None else None,
                frag=frag_id):
            # slot bound: a saturated worker must answer with the
            # WORKER_BUSY marker BEFORE the coordinator's dispatch RPC
            # deadline concludes it is hung (call_timeout_s=120 under a
            # query deadline, the stream bound without one) — so the
            # wait is capped at half a short bound, never the fragment's
            # full deadline. The coordinator REQUEUES a busy fragment
            # without evicting us.
            wait_s = min(timeout_s or 60.0, 60.0) / 2
            t0 = time.perf_counter()
            with tracing.span("worker.slot_wait") as sp:
                ok = self._slots.acquire(timeout=max(wait_s, 0.001))
                sp.attrs = {"acquired": ok}
            if not ok:
                tracing.counter("worker.slot_timeouts")
                raise flight.FlightUnavailableError(
                    f"WORKER_BUSY worker {self.worker_id}: all "
                    f"{self.slots} execution slots busy")
            tracing.gauge_add("worker.slots_busy", 1)
            tracing.histogram("worker.slot_wait_s",
                              time.perf_counter() - t0)
            try:
                out = self._execute_fragment(frag_id, disp["plan"], addr_of,
                                             deadline,
                                             budget=disp["budget"])
            except IglooError as ex:
                raise flight.FlightServerError(f"fragment failed: {ex}")
            finally:
                tracing.gauge_add("worker.slots_busy", -1)
                self._slots.release()
        if trace is not None:
            # read AFTER the scope exit — that is when the thread-local
            # span tree flushes into the trace
            out["spans"] = trace.spans()
        return out

    def do_action(self, context, action):
        # the server end of the call (cluster/rpc.py CLOCKS): what runs
        # around `execute_fragment`'s scope — body decode, protocol parse,
        # the span tree's copy, reply encode — and the small actions whole
        with rpc.Served("worker.serve", rpc.action_kind(
                action.type, protocol.WORKER_ACTIONS)):
            faults.inject(f"worker.do_action.{action.type}")
            body = action.body.to_pybytes() if action.body is not None else b""
            req = json.loads(body) if body else {}
            if action.type == "execute_fragment":
                try:
                    out = self._handle_execute_fragment(req)
                except protocol.ProtocolError as ex:
                    raise flight.FlightServerError(f"bad dispatch payload: {ex}")
                return [json.dumps(out).encode()]
            if action.type == "register_table":
                rt = protocol.REGISTER_TABLE.parse(req)
                provider = serde.provider_from_spec(rt["spec"])
                self._catalog.register(rt["name"], provider)
                self._batch_cache.invalidate_table(rt["name"].lower())
                return [b"{}"]
            if action.type == "release":
                ids = protocol.RELEASE.parse(req)["ids"]
                deps = [k for k in self._store.ids()
                        if any(k.startswith(_dep_key(fid, None)) for fid in ids)]
                self._store.release(ids + deps)
                return [b"{}"]
            if action.type == "ping":
                own = [i for i in self._store.ids() if not i.startswith("__dep_")]
                return [json.dumps({"worker": self.worker_id,
                                    "tables": sorted(self._catalog.names()),
                                    "fragments": len(own),
                                    "slots": self.slots,
                                    "mesh_devices": self.mesh_devices}).encode()]
            if action.type == "metrics":
                # Prometheus text exposition of this worker process's registry
                # (raw bytes, not JSON — scrape via rpc.flight_action_raw)
                return [tracing.prometheus_text().encode()]
            if action.type == "metrics_history":
                # this process's watchtower sampler ring; the coordinator's
                # metrics_history action aggregates these across the fleet
                return [json.dumps(protocol.METRICS_HISTORY.build(
                    samples=timeseries.samples())).encode()]
            raise flight.FlightServerError(f"unknown action {action.type}")

    def list_actions(self, context):
        # straight from the registry: the flight-actions checker holds this
        # surface and do_action's dispatch to the same declaration
        return protocol.action_doc("worker")

    def do_get(self, context, ticket):
        with rpc.Served("worker.serve", "do_get") as served:
            faults.inject("worker.do_get")
            try:
                frag_id, bucket, nbuckets = exchange.parse_ticket(
                    ticket.ticket)
            except protocol.ProtocolError as ex:
                raise flight.FlightServerError(f"bad exchange ticket: {ex}")
            try:
                schema, batches = self._store.stream(frag_id, bucket,
                                                     nbuckets)
            except KeyError:
                raise flight.FlightServerError(f"no such fragment: {frag_id}")
            except ValueError as ex:
                raise flight.FlightServerError(f"bad bucket request: {ex}")

            def counted():
                for b in batches:
                    tracing.counter("exchange.rows", b.num_rows)
                    tracing.counter("exchange.bytes", b.nbytes)
                    yield b
            # encoded partition slices carry dictionary fields, which
            # GeneratorStream would silently drop —
            # rpc.flight_stream_response picks the stream shape that keeps
            # both dictionaries and Flight error statuses intact. No other
            # span covers the serving of a stored result: it is this one's
            return rpc.flight_stream_response(schema, served.stream(
                faults.wrap_stream("worker.do_get", counted()), own=True))


class Worker:
    """Worker lifecycle: serve + register + heartbeat (main.rs:14-52 parity)."""

    #: registration keeps retrying (with backoff) for this long before the
    #: worker gives up — a worker started BEFORE its coordinator must wait
    #: for it, not die instantly (the reference leaves this as a TODO
    #: comment, main.rs:37-38)
    REGISTER_TIMEOUT_ENV = "IGLOO_WORKER_REGISTER_TIMEOUT_S"

    def __init__(self, coordinator: str, host: str = "127.0.0.1",
                 port: int = 0, heartbeat_interval_s: float = 5.0,
                 use_jit: bool = True,
                 store_budget_bytes: Optional[int] = None,
                 register_timeout_s: Optional[float] = None):
        self.server = WorkerServer(f"grpc+tcp://{host}:{port}", use_jit=use_jit,
                                   store_budget_bytes=store_budget_bytes)
        self.server.advertise = f"grpc+tcp://{host}:{self.server.port}"
        self.coordinator = _normalize(coordinator)
        self.heartbeat_interval_s = heartbeat_interval_s
        if register_timeout_s is None:
            import os
            register_timeout_s = float(
                os.environ.get(self.REGISTER_TIMEOUT_ENV, "30"))
        self.register_timeout_s = register_timeout_s
        self._stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        # heartbeat-failure edge detector: log the FIRST consecutive failure
        # (and the recovery), never the repeats — a coordinator outage must
        # not turn every worker's log into a 5s-period spam stream
        self._hb_down = False
        # compile-cache entry names this worker knows the coordinator has
        # (seeded at registration, grown by pushes); touched only by the
        # registering thread and then the heartbeat thread, never both
        self._cache_known: set = set()
        # per-entry consecutive push failures: an entry that keeps failing
        # (e.g. bigger than the transport's message cap) is given up on after
        # a few beats instead of starving every entry that sorts after it
        self._push_failures: dict = {}

    @property
    def address(self) -> str:
        return self.server.advertise

    def start(self) -> None:
        timeseries.start("worker")
        self._register()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           daemon=True)
        self._hb_thread.start()

    def _coordinator_action(self, name: str, payload: dict,
                            deadline: Optional[float] = None) -> dict:
        return flight_action(self.coordinator, name, payload,
                             deadline=deadline)

    def _register(self) -> None:
        """Register with bounded retry + backoff: each attempt already
        carries the RPC policy's own (small) retry budget, so this loop only
        spans the LONG wait — a coordinator that isn't up yet or is
        restarting. Fatal errors (auth, server-side rejection) fail fast."""
        policy = rpc.default_policy()
        deadline = time.time() + self.register_timeout_s
        attempt = 0
        while True:
            try:
                # the give-up deadline bounds each attempt's gRPC timeout
                # too: against a HUNG coordinator (accepts, never answers)
                # one un-deadlined attempt would otherwise block
                # call_timeout_s x (1 + retries) — minutes past the
                # documented register_timeout_s
                resp = self._coordinator_action(
                    "register_worker",
                    serde.worker_info_to_json(
                        self.server.worker_id, self.server.advertise,
                        devices=self.server.mesh_devices,
                        slots=self.server.slots),
                    deadline=deadline)
                break
            except Exception as ex:
                if not rpc.retryable(ex) or self._stop.is_set() or \
                        time.time() >= deadline:
                    raise
                attempt += 1
                tracing.counter("worker.register_retries")
                # cap the step so a short register_timeout still gets many
                # attempts; never sleep past the give-up deadline
                delay = min(policy.backoff_s(attempt) * 10, 2.0,
                            max(deadline - time.time(), 0.05))
                if self._stop.wait(delay):
                    raise
        try:
            self._adopt_compile_cache(resp.get("compile_cache") or {})
        except Exception:
            # pre-warm is an optimization; registration must never fail on it
            tracing.counter("compile_cache.prewarm_failed")

    def _adopt_compile_cache(self, info: dict) -> None:
        """Registration-time cache sync: adopt the coordinator's
        IGLOO_TPU_COMPILE_CACHE setting when this process has none of its
        own, then PRE-WARM by pulling every persistent-cache entry the
        coordinator has that we don't — a fresh worker serves its first
        fragment with the cluster's whole compile history on disk."""
        import os

        from igloo_tpu import compile_cache
        setting = info.get("setting")
        if setting is not None and "IGLOO_TPU_COMPILE_CACHE" not in os.environ:
            compile_cache.configure(setting)
        local = set(compile_cache.entry_names())
        remote = list(info.get("entries") or ())
        # only REMOTE names are "known to the coordinator": local entries the
        # coordinator lacks (compiled before registration, or a pre-seeded
        # cache) must still be pushed on the first heartbeat
        self._cache_known = set(remote)
        if compile_cache.active_dir() is None:
            return
        missing = [n for n in remote if n not in local]
        if not missing:
            return
        # pull in a DAEMON thread: a mature cluster's cache is hundreds of
        # entries (tens of MB each), and blocking _register on the transfer
        # would outlast the membership timeout (coordinator sweeps a worker
        # silent for 15 s) before the heartbeat thread even starts. Pulled
        # names are already in _cache_known (they came from `remote`), so
        # the thread never mutates shared state; write_entry is atomic.
        threading.Thread(target=self._prewarm_pull, args=(missing,),
                         daemon=True).start()

    def _prewarm_pull(self, missing: list) -> None:
        from igloo_tpu import compile_cache
        done = 0
        pulled = 0
        try:
            # one connection for the whole pre-warm (rpc.flight_actions_raw):
            # a connect/teardown per entry would dominate the transfer
            pulls = rpc.flight_actions_raw(
                self.coordinator,
                (("compile_cache_get", protocol.COMPILE_CACHE_GET.build(
                    name=n)) for n in missing))
            for name, data in zip(missing, pulls):
                done += 1
                if data and compile_cache.write_entry(name, data):
                    tracing.counter("compile_cache.pull")
                    pulled += 1
        except Exception:
            # the batch connection died — usually ONE entry past the
            # transport's message cap. Finish per-entry so everything after
            # it still warms (the push side has the same give-up rule);
            # per-entry failures are skipped, not fatal.
            for name in missing[done:]:
                try:
                    data = rpc.flight_action_raw(
                        self.coordinator, "compile_cache_get",
                        protocol.COMPILE_CACHE_GET.build(name=name))
                    if data and compile_cache.write_entry(name, data):
                        tracing.counter("compile_cache.pull")
                        pulled += 1
                except Exception:
                    tracing.counter("compile_cache.prewarm_failed")
        if pulled:
            # one journal event per pre-warm, not per entry
            events.emit("compile_cache_pull", worker=self.server.worker_id,
                        entries=pulled)

    def _push_compile_cache(self) -> None:
        """Heartbeat-time push of entries this worker compiled since the
        last sync, keyed by XLA cache filename — the return leg that makes
        the cache CLUSTER-wide rather than coordinator-seeded."""
        from igloo_tpu import compile_cache
        # only STABLE entries ship: XLA writes cache files non-atomically,
        # and a truncated blob pushed once would pin itself cluster-wide
        stable = compile_cache.entry_names(
            min_age_s=compile_cache.TRANSFER_MIN_AGE_S)
        candidates = [n for n in stable if n not in self._cache_known]
        if not candidates:
            return
        # one connection for the whole beat: a cold bench run leaves dozens
        # of fresh entries, and a connect/teardown per entry on the heartbeat
        # thread would eat into the coordinator's 15s liveness window.
        # `attempted` is appended before each action is yielded, so when
        # result i arrives attempted[i] is its name; entries are read lazily
        # so at most one payload is in memory at a time.
        attempted: list = []

        def actions():
            for name in candidates:
                data = compile_cache.read_entry(name)
                self._cache_known.add(name)
                if data is None:
                    continue
                attempted.append(name)
                yield ("compile_cache_put", protocol.COMPILE_CACHE_PUT.build(
                    name=name, data=compile_cache.encode_entry(data)))

        confirmed = 0
        pushed = 0
        try:
            for i, body in enumerate(rpc.flight_actions_raw(
                    self.coordinator, actions())):
                name = attempted[i]
                confirmed = i + 1
                resp = json.loads(body) if body else {}
                # {"stored": false} is a real failure (coordinator disk
                # error, payload rejected) — counting it as a push would
                # drop the entry from replication forever
                if resp.get("stored"):
                    tracing.counter("compile_cache.push")
                    pushed += 1
                    self._push_failures.pop(name, None)
                else:
                    self._note_push_failure(name)
        except Exception:
            # connection died mid-batch (coordinator restart, or one entry
            # past the transport's message cap): everything unconfirmed
            # retries next beat, with the 3-strike give-up so one poisonous
            # entry can't starve those sorting after it
            for name in attempted[confirmed:]:
                self._note_push_failure(name)
        if pushed:
            # one journal event per heartbeat sync, not per entry; `server`
            # may be absent under the push-only unit harness
            srv = getattr(self, "server", None)
            events.emit("compile_cache_push",
                        worker=srv.worker_id if srv else "", entries=pushed)

    def _note_push_failure(self, name: str) -> None:
        """3-strike bookkeeping: un-know the entry so the next beat retries
        it, until it keeps failing (e.g. past the transport's message cap) —
        then leave it known so entries sorting after it still ship."""
        fails = self._push_failures.get(name, 0) + 1
        self._push_failures[name] = fails
        if fails < 3:
            self._cache_known.discard(name)

    def _heartbeat_loop(self) -> None:
        # retry/backoff the reference leaves as a comment (main.rs:37-38):
        # a failed heartbeat retries next tick; a coordinator that no longer
        # knows us (restarted, or it evicted us during a network blip)
        # answers ok=false and we re-register
        import sys
        while not self._stop.wait(self.heartbeat_interval_s):
            # journal events ride the heartbeat (WORKER_INFO.events); on a
            # failed beat they are requeued so the journal stays lossless
            # across transient outages
            evs = events.drain_forward()
            try:
                resp = self._coordinator_action(
                    "heartbeat",
                    serde.worker_info_to_json(
                        self.server.worker_id, self.server.advertise,
                        devices=self.server.mesh_devices,
                        slots=self.server.slots, events=evs))
                if not resp.get("ok", True):
                    self._register()
                    tracing.counter("worker.reregistrations")
                self._push_compile_cache()
                if self._hb_down:
                    self._hb_down = False
                    print(f"igloo-worker {self.server.worker_id}: heartbeat "
                          f"to {self.coordinator} recovered", file=sys.stderr)
            except Exception as ex:
                events.requeue_forward(evs)
                tracing.counter("worker.heartbeat_failures")
                if not self._hb_down:
                    # log the EDGE, count the repeats: one line per outage
                    self._hb_down = True
                    print(f"igloo-worker {self.server.worker_id}: heartbeat "
                          f"to {self.coordinator} failing "
                          f"({type(ex).__name__}: {ex}); will keep retrying "
                          f"every {self.heartbeat_interval_s}s (further "
                          f"failures counted, not logged)", file=sys.stderr)

    def serve_forever(self) -> None:
        self.server.serve()  # blocks

    def shutdown(self) -> None:
        self._stop.set()
        self.server.shutdown()
        rpc.close_idle_connections()


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="igloo-worker")
    ap.add_argument("coordinator", nargs="?", default="127.0.0.1:50051",
                    help="coordinator address (reference worker takes this "
                         "as argv[1] with the same default)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--config", default=None)
    args = ap.parse_args(argv)

    hb = 5.0
    if args.config:
        from igloo_tpu.config import Config, apply_storage, rpc_policy
        cfg = Config.load(args.config)
        hb = cfg.cluster.heartbeat_interval_s
        # [rpc] config is the base; IGLOO_RPC_* env still wins per-field
        # (the worker's registration, heartbeats, and peer dep-fetches all
        # run under this policy)
        rpc.set_default_policy(rpc.policy_from_env(rpc_policy(cfg)))
        # [storage] likewise: the worker's fragment scans read through the
        # same policy-governed object-store layer the engine uses
        apply_storage(cfg)
    w = Worker(args.coordinator, host=args.host, port=args.port,
               heartbeat_interval_s=hb)
    w.start()
    print(f"igloo-worker {w.server.worker_id} serving on {w.address}, "
          f"coordinator {w.coordinator}", flush=True)
    try:
        w.serve_forever()
    except KeyboardInterrupt:
        w.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
