"""Coordinator: cluster membership, distributed execution, Flight SQL front door.

Parity map against the reference:
- membership + heartbeat: MyCoordinatorService (crates/coordinator/src/
  service.rs:22-51). The reference records `last_seen` and never acts on it
  (gap G6); here a sweeper thread EVICTS silent workers and the executor
  re-dispatches their fragments (fragments are pure functions of their inputs,
  so re-execution is safe — the elastic recovery SURVEY §5.3 calls for).
- wave scheduler: DistributedExecutor (distributed_executor.rs:36-193) — same
  ready-set/wave structure, but plan serialization is real (serde.py; the
  reference ships empty bytes, G1), results are real Arrow IPC streams (the
  reference fabricates a dummy batch, G1), and a server actually implements
  fragment execution (G2).
- front door: IglooFlightSqlService implements 2 of the proto's 10 Flight
  methods and executes the query TWICE (once in get_flight_info for the
  schema, once in do_get — crates/api/src/lib.rs:81-149). Here
  get_flight_info PLANS only (schema comes from the bound plan), do_get
  executes once, and the served surface is: handshake (token auth),
  list_flights, get_flight_info, get_schema, do_get, do_put (table upload),
  do_exchange (cmd = query stream / path = upload + echo), do_action,
  list_actions — plus PollFlightInfo as the `poll_flight_info` action
  (pyarrow's FlightServerBase exposes no server hook for the real RPC).
"""
from __future__ import annotations

import concurrent.futures as cf
import contextlib
import json
import os
import threading
import time
import uuid
import weakref
from dataclasses import dataclass, field
from typing import Optional

import pyarrow as pa
import pyarrow.flight as flight

from igloo_tpu.cluster import events, faults, protocol, rpc, serde, serving
from igloo_tpu.cluster.fragment import DistributedPlanner, QueryFragment
from igloo_tpu.cluster.rpc import flight_action
from igloo_tpu.engine import QueryEngine
from igloo_tpu.errors import (
    DeadlineExceededError, IglooError, QueryCancelledError,
)
from igloo_tpu.utils import flight_recorder, stats, timeseries, tracing, watch

#: default per-query deadline (seconds) for the distributed path; unset or
#: <= 0 = unbounded. Precedence: per-call override > this env var > [rpc]
#: query_deadline_s config (env beats config, like every other [rpc] knob).
#: A PER-CALL deadline_s of 0 is different: it is an already-spent budget
#: and expires the query immediately (matching rpc.call_options, where a
#: deadline in the past still produces DEADLINE_EXCEEDED, not "no deadline")
QUERY_DEADLINE_ENV = "IGLOO_QUERY_DEADLINE_S"

#: how long recovery waits for SOME worker to (re-)register when every
#: worker is momentarily unreachable — a rolling restart or a flaky blip
#: that evicted the whole fleet should stall the query briefly, not fail it
#: (bounded by the query deadline when one is set)
RECOVER_WAIT_S = 5.0

#: front-door result cache for the distributed path (docs/serving.md):
#: repeated dashboard-shaped queries short-circuit admission entirely. "0"
#: disables it — the A/B the test suite and the adaptive/chaos smokes pin
#: (a cached query skips execution, so assertions about what execution DID
#: would otherwise flip on repetition).
RESULT_CACHE_ENV = "IGLOO_SERVING_RESULT_CACHE"

#: distributed results larger than this are not teed into the result cache
#: while being relayed (the coordinator would otherwise materialize what
#: streaming exists to avoid)
RESULT_CACHE_MAX_BYTES = 64 << 20

#: lock discipline for the coordinator's shared state (lint: lock-discipline
#: enforces these module-wide, any receiver). `_lock` covers BOTH instances
#: of the name: Membership's worker map/evicted set and CoordinatorServer's
#: table-spec registry — each is touched by the sweeper thread, the Flight
#: handler pool, and the dispatch pool. `_totals_lock` guards the metrics
#: publish slot (`last_metrics`) and the cumulative per-worker totals; the
#: event-journal ingest delegates to cluster/events.py, whose ring carries
#: its own module-level `_GUARDED_BY`.
_GUARDED_BY = {
    "_lock": ("_workers", "_evicted_ids", "_table_specs"),
    "_queries_lock": ("_queries",),
    "_totals_lock": ("last_metrics", "worker_totals"),
}


def _is_oom(ex: BaseException) -> bool:
    """An out-of-device-memory failure the degradation ladder can absorb:
    a Python MemoryError, XLA's RESOURCE_EXHAUSTED, or either surfacing in
    a worker-reported fragment failure's message."""
    if isinstance(ex, MemoryError):
        return True
    msg = str(ex)
    return ("RESOURCE_EXHAUSTED" in msg or "MemoryError" in msg
            or "Out of memory" in msg or "out of memory" in msg)


def _released_stream(gen, permit):
    """Wrap a result stream so its serving permit releases when the stream
    finishes, errors, or is abandoned unconsumed (weakref finalizer — an
    unstarted generator's close() never enters its finally block).
    `Permit.release` is idempotent, so double-firing is safe."""
    def g():
        try:
            yield from gen
        finally:
            permit.release()
    out = g()
    weakref.finalize(out, permit.release)
    return out


@dataclass
class WorkerState:
    worker_id: str
    addr: str
    last_seen: float
    tables_pushed: set = field(default_factory=set)
    # topology reported at registration/heartbeat (cluster/serde.py
    # worker_info_*): size of the worker's LOCAL mesh — the chips one
    # fragment runs across — and its execution-slot bound. The planner sizes
    # bucket counts with hosts and weights bucket placement with these
    # (docs/distributed.md "Two-level topology").
    devices: int = 1
    slots: int = 0


class Membership:
    """Live-worker registry with liveness eviction (closes reference gap G6:
    `last_seen` recorded at service.rs:43-49 but nothing ever consumes it)."""

    def __init__(self, timeout_s: float = 15.0):
        self.timeout_s = timeout_s
        self._workers: dict[str, WorkerState] = {}
        # ids evicted at least once: a re-registration from one of these is
        # a RECOVERY (journaled worker_recover, not worker_join)
        self._evicted_ids: set = set()
        self._lock = threading.Lock()

    def register(self, worker_id: str, addr: str, devices: int = 1,
                 slots: int = 0) -> None:
        with self._lock:
            rejoin = worker_id in self._evicted_ids
            self._evicted_ids.discard(worker_id)
            self._workers[worker_id] = WorkerState(
                worker_id, addr, time.time(),
                devices=max(int(devices), 1), slots=int(slots))
        tracing.counter("coordinator.workers_registered")
        if rejoin:
            events.emit("worker_recover", worker=worker_id, addr=addr,
                        devices=int(devices), slots=int(slots))
        else:
            events.emit("worker_join", worker=worker_id, addr=addr,
                        devices=int(devices), slots=int(slots))

    def heartbeat(self, worker_id: str, addr: str = "",
                  devices: Optional[int] = None,
                  slots: Optional[int] = None) -> bool:
        """True if known (reference answers ok=false for unknown workers —
        the worker should re-register). `devices`/`slots` refresh the
        topology so a worker whose visible device count or slot bound
        changed (restart behind the same id, hotplugged slice, retuned
        IGLOO_WORKER_SLOTS) is re-planned against reality, not its
        registration-time snapshot."""
        with self._lock:
            w = self._workers.get(worker_id)
            if w is None:
                return False
            w.last_seen = time.time()
            if addr:
                w.addr = addr
            if devices:
                w.devices = max(int(devices), 1)
            if slots:
                w.slots = int(slots)
            return True

    def topology(self) -> dict:
        """addr -> local mesh device count for every live worker."""
        with self._lock:
            return {w.addr: w.devices for w in self._workers.values()}

    def evict(self, worker_id: str) -> None:
        with self._lock:
            known = self._workers.pop(worker_id, None) is not None
            if known:
                self._evicted_ids.add(worker_id)
        tracing.counter("coordinator.workers_evicted")
        if known:
            events.emit("worker_evict", severity="warn", worker=worker_id,
                        reason="unreachable")

    def sweep(self) -> list[str]:
        """Evict workers silent for > timeout; returns evicted ids."""
        cutoff = time.time() - self.timeout_s
        with self._lock:
            dead = [w.worker_id for w in self._workers.values()
                    if w.last_seen < cutoff]
            for wid in dead:
                self._workers.pop(wid, None)
                self._evicted_ids.add(wid)
        for wid in dead:
            tracing.counter("coordinator.workers_evicted")
            events.emit("worker_evict", severity="warn", worker=wid,
                        reason="heartbeat_timeout")
        return dead

    def live(self) -> list[WorkerState]:
        with self._lock:
            return list(self._workers.values())

    def by_addr(self, addr: str) -> Optional[WorkerState]:
        with self._lock:
            for w in self._workers.values():
                if w.addr == addr:
                    return w
        return None


class CancelToken:
    """Cooperative per-query cancellation flag, checked between fragment
    waves, before each dispatch, and per relayed batch."""

    def __init__(self):
        self._ev = threading.Event()

    def cancel(self) -> None:
        self._ev.set()

    @property
    def cancelled(self) -> bool:
        return self._ev.is_set()


class DistributedExecutor:
    """Wave-based fragment scheduler (distributed_executor.rs:36-193 parity,
    with the wire layer real and worker failure handled by re-dispatch:
    fragments are pure functions of their inputs, so losing a worker only
    costs re-execution of the fragments whose sole result copy it held).

    Failure budget: every query runs under an optional DEADLINE (per-call
    override > constructor default > IGLOO_QUERY_DEADLINE_S > [rpc]
    query_deadline_s) and a
    CancelToken registered under its qid (the `cancel_query` Flight action).
    Hung-worker detection is deadline-driven: a dispatch that exceeds its
    per-call RPC deadline is a dead-worker signal and enters the `_recover`
    re-dispatch path — a worker that accepts TCP but never answers costs one
    bounded timeout, not a wedged query. A cancelled or over-deadline query
    releases its FragmentStore results and stops dispatching instead of
    running to completion."""

    def __init__(self, membership: Membership, max_parallel: int = 16,
                 max_recoveries: int = 8,
                 rpc_policy: Optional[rpc.RpcPolicy] = None,
                 default_deadline_s: Optional[float] = None):
        self.membership = membership
        self.max_parallel = max_parallel
        self.max_recoveries = max_recoveries
        self.rpc_policy = rpc_policy   # None -> rpc.default_policy() per call
        if default_deadline_s is None:
            env = os.environ.get(QUERY_DEADLINE_ENV)
            default_deadline_s = float(env) if env else None
        if default_deadline_s is not None and default_deadline_s <= 0:
            default_deadline_s = None  # "0" = explicitly unbounded
        self.default_deadline_s = default_deadline_s
        # per-fragment metrics of the most recent query: the working version
        # of the reference's never-populated QueryComplete{total_rows,
        # execution_time_ms} (distributed.proto:66-69, SURVEY §5.5)
        self.last_metrics: dict = {}
        # CUMULATIVE per-worker fragment totals (fragments / rows / seconds /
        # bytes since coordinator start): the aggregation the coordinator's
        # `metrics` Flight action exports as labeled Prometheus series
        self.worker_totals: dict = {}
        self._totals_lock = threading.Lock()
        # in-flight queries by qid -> CancelToken (cancel_query targets)
        self._queries: dict[str, CancelToken] = {}
        self._queries_lock = threading.Lock()

    def _policy(self) -> rpc.RpcPolicy:
        return self.rpc_policy or rpc.default_policy()

    def cancel(self, qid: str) -> bool:
        """Flip a running query's cancel token; False if qid is unknown
        (already finished, or never existed)."""
        with self._queries_lock:
            tok = self._queries.get(qid)
        if tok is None:
            return False
        tok.cancel()
        return True

    def active_queries(self) -> list[str]:
        with self._queries_lock:
            return list(self._queries)

    def execute(self, fragments: list[QueryFragment],
                deadline_s: Optional[float] = None,
                qid: Optional[str] = None, sql: str = "",
                adaptive_info: Optional[list] = None,
                extra_metrics: Optional[dict] = None,
                trace: Optional[flight_recorder.Trace] = None,
                budget: Optional[int] = None) -> pa.Table:
        schema, gen = self.execute_stream(fragments, deadline_s=deadline_s,
                                          qid=qid, sql=sql,
                                          adaptive_info=adaptive_info,
                                          extra_metrics=extra_metrics,
                                          trace=trace, budget=budget)
        return pa.Table.from_batches(list(gen), schema=schema)

    def execute_stream(self, fragments: list[QueryFragment],
                       deadline_s: Optional[float] = None,
                       qid: Optional[str] = None, sql: str = "",
                       adaptive_info: Optional[list] = None,
                       extra_metrics: Optional[dict] = None,
                       trace: Optional[flight_recorder.Trace] = None,
                       budget: Optional[int] = None
                       ) -> tuple[pa.Schema, object]:
        """Run the fragment waves, then return (schema, batch generator)
        streaming the root result from its worker — the coordinator never
        holds more than one in-flight batch of a distributed result. The
        generator publishes per-query metrics and releases worker-held
        fragment results when it is exhausted (or closed). Cancellation and
        the deadline are checked between waves, before every dispatch, and
        per relayed batch."""
        frags = {f.id: f for f in fragments}
        root_id = fragments[-1].id
        completed: dict[str, str] = {}  # frag id -> worker addr holding result
        pending = set(frags)
        recoveries = 0
        t_start = time.time()
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        # `is not None`, not truthy: a per-call deadline_s of 0 is a spent
        # budget and must expire the query NOW, not run it unbounded
        deadline = t_start + deadline_s if deadline_s is not None else None
        qid = qid or uuid.uuid4().hex[:12]
        token = CancelToken()
        with self._queries_lock:
            self._queries[qid] = token
        if trace is not None:
            # ownership handoff: this query's trace is now published by
            # _finalize (at stream end / error), not by the do_get handler
            trace.deferred = True
            trace.qid = trace.qid or qid
        # per-QUERY metrics dict: concurrent queries each build their own and
        # publish atomically at the end (last_metrics = last finished query).
        # Per-fragment entries attribute wall time to dispatch (RPC + queue)
        # vs execute (worker-reported) vs dep_fetch (peer transfers); the
        # query-level recover_s/fetch_s cover re-dispatch and the root fetch.
        metrics: dict = {"qid": qid, "fragments": [], "recoveries": 0,
                         "recover_s": 0.0, "fetch_s": 0.0, "status": "ok",
                         "deadline_s": deadline_s,
                         "cancelled": False, "deadline_exceeded": False,
                         # every addr a fragment was EVER dispatched to
                         # (set.add is atomic under the GIL; "_"-prefixed
                         # keys never publish): _recover reassigns
                         # frags[fid].worker, so release must remember the
                         # evicted addr too — its handler may still be
                         # running and needs the tombstone
                         "_addrs": set(),
                         # flight-recorder stitching surface: dispatch spans
                         # + worker span trees land here; the root is the
                         # do_get request scope's root span (captured on
                         # THIS thread — the dispatch pool can't read it)
                         "_trace": trace,
                         "_trace_root": flight_recorder.current_root(),
                         # per-worker out-of-core budget of an OVERSIZED
                         # query (docs/out_of_core.md): shipped inside every
                         # dispatch so workers stream-spill / GRACE under it
                         "_budget": budget,
                         "trace_id": trace.trace_id if trace is not None
                         else ""}
        if extra_metrics:
            # serving-path facts (queue_wait_s / priority / demoted) ride
            # beside the execution metrics into last_metrics + query_log
            metrics.update(extra_metrics)
        shuffle_buckets = {f.bucket for f in fragments
                          if f.bucket is not None}
        metrics["shuffle_buckets"] = len(shuffle_buckets)
        # the planner's per-join decision records (strategy / salt /
        # adaptive_source), so last_metrics shows WHY this
        # plan shape was chosen (docs/adaptive.md)
        metrics["adaptive"] = list(adaptive_info or ())
        try:
            with cf.ThreadPoolExecutor(self.max_parallel) as pool:
                while pending:
                    self._check_query(token, deadline, metrics)
                    ready = [frags[fid] for fid in pending
                             if frags[fid].is_ready(set(completed))]
                    if not ready:
                        raise IglooError(
                            "circular dependency in fragment graph")
                    dead: set[str] = set()
                    lost_deps: set[str] = set()
                    busy: list = []
                    # this thread blocked on the dispatch pool: the
                    # fragments' own time is the workers' spans. From the
                    # submit on: the pool's thread and the worker's run
                    # Python at once, and the milliseconds this thread then
                    # waits for the interpreter lock before it reaches
                    # as_completed are theirs, not `query` self time
                    with tracing.span("coordinator.await_fragments"):
                        futs = {pool.submit(self._dispatch, f,
                                            dict(completed), metrics,
                                            deadline, token): f
                                for f in ready}
                        for fut in cf.as_completed(futs):
                            f = futs[fut]
                            try:
                                fut.result()
                            except _WorkerBusy as ex:
                                busy.append((f.id, ex.addr))
                                continue
                            except _WorkerDied as ex:
                                dead.add(ex.addr)
                                continue
                            except _DepLost as ex:
                                lost_deps.add(ex.frag_id)
                                continue
                            completed[f.id] = f.worker
                            pending.discard(f.id)
                    if busy:
                        # saturated-but-ALIVE workers (WORKER_BUSY, all
                        # execution slots occupied): requeue elsewhere
                        # WITHOUT eviction — backpressure is not death, and
                        # the target's slot wait paces the retry loop
                        live_now = self._live_addrs()
                        for i, (fid, addr) in enumerate(busy):
                            others = [a for a in live_now if a != addr]
                            if others:
                                frags[fid].worker = others[i % len(others)]
                            tracing.counter(
                                "coordinator.fragments_requeued_busy")
                            events.emit("fragment_requeue_busy",
                                        qid=qid, worker=addr, frag=fid)
                    for dep_id in lost_deps:
                        # the holder of this dep result is unreachable from a
                        # peer: treat it as dead and re-run the dep
                        dead.add(completed.get(dep_id, ""))
                    if dead:
                        recoveries += 1
                        metrics["recoveries"] = recoveries
                        if recoveries > self.max_recoveries:
                            raise IglooError(
                                "giving up after repeated worker failures")
                        # no budget left: report the deadline, don't burn the
                        # remaining workers on a recovery that cannot finish
                        self._check_query(token, deadline, metrics)
                        t_rec = time.perf_counter()
                        self._recover(dead, frags, completed, pending,
                                      deadline)
                        metrics["recover_s"] += time.perf_counter() - t_rec
                        if trace is not None:
                            trace.add_span(
                                "recover", tracing.epoch(t_rec), time.time(),
                                parent_id=metrics["_trace_root"],
                                proc="coordinator",
                                dead=sorted(dead), recovery=recoveries)
            # open the root stream eagerly: the schema the worker reports is
            # authoritative, and a root holder lost between the last wave and
            # here surfaces now, while the caller can still see the error
            t_fetch = time.perf_counter()
            schema, batch_iter = rpc.flight_stream_batches(
                completed[root_id], root_id, policy=self._policy(),
                deadline=deadline)
        except BaseException as ex:
            self._release(frags, completed, list(frags),
                          metrics["_addrs"])
            self._finalize(qid, metrics, t_start, sql, error=ex, token=token)
            raise

        done = [False]

        def cleanup():
            # idempotent: runs from the generator's finally on the normal
            # path, or from the weakref finalizer when a client abandons the
            # stream before pulling the first batch (a never-started
            # generator's close() does not enter its try/finally)
            if done[0]:
                return
            done[0] = True
            close = getattr(batch_iter, "close", None)
            if close is not None:
                try:
                    close()  # drop the root worker's Flight connection
                except Exception:
                    pass
            self._release(frags, completed, list(frags),
                          metrics["_addrs"])
            # a stream abandoned before its first batch reaches this ONLY
            # through the weakref finalizer — gen()'s except/finally never
            # ran, so finalize here (release-only path: unregisters and
            # publishes the partial trace; the _finalized guard makes this
            # a no-op after any earlier finalize)
            self._finalize(qid, metrics, t_start, sql, token=token)

        def gen():
            total_rows = 0
            try:
                for batch in batch_iter:
                    # over-deadline / cancelled mid-relay: stop streaming,
                    # release worker results (cleanup in finally)
                    self._check_query(token, deadline, metrics)
                    total_rows += batch.num_rows
                    yield batch
                metrics["fetch_s"] = round(time.perf_counter() - t_fetch, 6)
                metrics["total_rows"] = total_rows
                metrics["recoveries"] = recoveries
                if trace is not None:
                    # the root-result relay: open + batch-wise stream from
                    # the root holder (recorded here, where it ends — the
                    # relay spans threads, so a thread-local span cannot).
                    # Top-level, not a child of the "query" root: the relay
                    # OUTLIVES the do_get handler whose scope that root
                    # times, and nesting is containment
                    trace.add_span("fetch", tracing.epoch(t_fetch),
                                   time.time(), proc="coordinator",
                                   rows=total_rows)
                self._finalize(qid, metrics, t_start, sql, completed=True,
                               token=token)
            except BaseException as ex:
                if isinstance(ex, GeneratorExit):
                    # consumer closed the stream early: released, not logged
                    self._finalize(qid, metrics, t_start, sql, token=token)
                else:
                    self._finalize(qid, metrics, t_start, sql, error=ex,
                                   token=token)
                raise
            finally:
                cleanup()
        g = gen()
        weakref.finalize(g, cleanup)
        return schema, g

    # --- internals ---

    def _check_query(self, token: CancelToken, deadline: Optional[float],
                     metrics: dict) -> None:
        """Raise if the query was cancelled or its deadline passed (flags
        recorded in the per-query metrics; counters bump once, at finalize)."""
        if token.cancelled:
            metrics["cancelled"] = True
            raise QueryCancelledError(f"query {metrics['qid']} cancelled")
        if deadline is not None and time.time() >= deadline:
            metrics["deadline_exceeded"] = True
            raise DeadlineExceededError(
                f"query {metrics['qid']} exceeded its "
                f"{metrics['deadline_s']}s deadline")

    def _unregister(self, qid: str, token: CancelToken) -> None:
        """Drop the qid -> CancelToken registration ONLY if it is still this
        query's token: a client that reuses a qid overwrites the slot with
        the NEWER query's token, and the older query's late finalize/cleanup
        must not evict it — that would leave the live query uncancellable
        and invisible to active_queries()."""
        with self._queries_lock:
            if self._queries.get(qid) is token:
                del self._queries[qid]

    def _finalize(self, qid: str, metrics: dict, t_start: float, sql: str,
                  error: Optional[BaseException] = None,
                  completed: bool = False,
                  token: Optional[CancelToken] = None) -> None:
        """Publish a finished query exactly once: last_metrics + cumulative
        worker totals + a system.query_log row (status ok / cancelled /
        deadline_exceeded / error). Called with neither `completed` nor
        `error` (an abandoned stream) it only unregisters the qid — the
        results were released, but nothing finished to report."""
        if token is not None:
            self._unregister(qid, token)
        with self._queries_lock:
            if metrics.get("_finalized"):
                return
            metrics["_finalized"] = True
        # after the last batch, before the client's stream ends (with
        # `coordinator.release`: benchmark `release_ms`)
        with tracing.span("coordinator.finalize"):
            self._publish_finished(qid, metrics, t_start, sql, error,
                                   completed)

    def _publish_finished(self, qid: str, metrics: dict, t_start: float,
                          sql: str, error: Optional[BaseException],
                          completed: bool) -> None:
        # retire the stitched trace exactly once (the _finalized guard),
        # whatever the outcome — a partial trace of a failed or abandoned
        # query is exactly what the timeline is FOR
        flight_recorder.publish(metrics.get("_trace"))
        if error is None and not completed:
            return
        status = "ok"
        if isinstance(error, QueryCancelledError) or metrics["cancelled"]:
            status = "cancelled"
            tracing.counter("query.cancelled")
            events.emit("query_cancelled", severity="warn", qid=qid,
                        trace_id=metrics.get("trace_id", ""))
        elif isinstance(error, DeadlineExceededError) or \
                metrics["deadline_exceeded"]:
            # covers both the wave/relay checks and an rpc-layer
            # DeadlineExceededError raised mid-call
            status = "deadline_exceeded"
            metrics["deadline_exceeded"] = True
            tracing.counter("query.deadline_exceeded")
            events.emit("query_deadline", severity="warn", qid=qid,
                        trace_id=metrics.get("trace_id", ""),
                        deadline_s=metrics.get("deadline_s"))
        elif error is not None:
            status = "error"
        metrics["status"] = status
        # dedupe by fragment id (a fragment re-run after a worker death
        # appends twice; last execution wins)
        by_id: dict = {}
        for info in metrics["fragments"]:
            by_id[info.get("id", len(by_id))] = info
        metrics["fragments"] = list(by_id.values())
        metrics.update(
            recover_s=round(metrics["recover_s"], 6),
            exchange_bytes=sum(i.get("exchange_bytes") or 0
                               for i in metrics["fragments"]),
            execution_time_s=round(time.time() - t_start, 6))
        if status == "ok" and completed:
            # feed the telemetry->planner loop: per-side observed rows /
            # result bytes / skew sketch, under the fingerprint digests the
            # planner tagged the fragments with (docs/adaptive.md)
            self._record_adaptive(metrics["fragments"])
        pub = {k: v for k, v in metrics.items() if not k.startswith("_")}
        # publish under the totals lock: the Flight `last_metrics` handler
        # and the demoted/cached publish paths race this slot otherwise
        with self._totals_lock:
            self.last_metrics = pub
        self._accumulate(pub)
        stats.log_query(sql, elapsed_s=pub["execution_time_s"],
                        tier="distributed", rows=pub.get("total_rows"),
                        status=status, started_at=t_start,
                        queue_wait_s=pub.get("queue_wait_s", 0.0),
                        priority=pub.get("priority", 1),
                        demoted=pub.get("demoted", 0),
                        trace_id=pub.get("trace_id", ""))
        if status == "ok" and completed:
            # watchtower baseline check: judged against this fingerprint's
            # OWN history, then folded in (docs/observability.md#watchtower).
            # After flight_recorder.publish above, so an escalation's pin()
            # finds the trace already ring-resident.
            watch.check_query(
                metrics.get("_plan_fp"), pub["execution_time_s"],
                exchange_bytes=float(pub.get("exchange_bytes") or 0),
                qid=qid, trace_id=pub.get("trace_id", ""), sql=sql,
                tier="distributed", phase=self._dominant_phase(pub))

    @staticmethod
    def _dominant_phase(pub: dict) -> str:
        """Attribute a distributed query's wall time to its widest phase
        (the slow-query record's `dominant_phase` column)."""
        frags = pub.get("fragments") or []
        buckets = {
            "execute": sum(i.get("elapsed_s") or 0.0 for i in frags),
            "dispatch": sum(i.get("dispatch_s") or 0.0 for i in frags),
            "dep_fetch": sum(i.get("dep_fetch_s") or 0.0 for i in frags),
            "fetch": pub.get("fetch_s") or 0.0,
            "recover": pub.get("recover_s") or 0.0,
        }
        name = max(buckets, key=buckets.get)
        return name if buckets[name] > 0 else ""

    def _record_adaptive(self, frag_infos: list) -> None:
        """Fold a finished query's per-fragment reports into the process-wide
        AdaptiveStats store, grouped by the planner's side digests: total
        rows and result bytes per join side, plus the skew sketch (max
        UNSALTED bucket share + hot bucket) from the exchange fragments'
        per-bucket row counts. Best-effort by the stats safety contract."""
        from igloo_tpu.exec import hints
        if not hints.adaptive_enabled():
            return
        try:
            by_key: dict = {}
            for info in frag_infos:
                sk = info.get("stats_key")
                if not sk:
                    continue
                g = by_key.setdefault(sk, {"rows": 0, "bytes": 0,
                                           "bucket_rows": None,
                                           "buckets": None})
                g["rows"] += int(info.get("rows") or 0)
                g["bytes"] += int(info.get("result_bytes") or 0)
                br = info.get("bucket_rows")
                if br:
                    if g["bucket_rows"] is None:
                        g["bucket_rows"] = [0] * len(br)
                        g["buckets"] = info.get("buckets")
                    if len(br) == len(g["bucket_rows"]):
                        g["bucket_rows"] = [a + int(b) for a, b in
                                            zip(g["bucket_rows"], br)]
            if not by_key:
                return
            store = hints.adaptive_store()
            for sk, g in by_key.items():
                fields = {"rows": g["rows"], "bytes": g["bytes"] or None}
                br = g["bucket_rows"]
                if br and sum(br) > 0 and g["buckets"]:
                    hot = max(range(len(br)), key=lambda i: br[i])
                    fields.update(max_share=round(br[hot] / sum(br), 4),
                                  hot_bucket=hot,
                                  nbuckets=int(g["buckets"]))
                store.observe_by_digest(sk, **fields)
            store.flush()
            tracing.counter("adaptive.observed", len(by_key))
        except Exception:
            tracing.counter("adaptive.record_failed")

    def _live_addrs(self) -> list[str]:
        return [w.addr for w in self.membership.live()]

    def _dispatch(self, f: QueryFragment, completed: dict[str, str],
                  metrics: dict, deadline: Optional[float] = None,
                  token: Optional[CancelToken] = None) -> None:
        # the pool thread's own time around the call: request build and
        # encode, reply decode, FRAGMENT_STATS.parse, the span tree's
        # stitching, bookkeeping. The `rpc` attempt is its child; `dispatch`
        # (group wait) is no thread-local span and takes nothing from it
        with tracing.span("coordinator.dispatch_fragment", frag=f.id):
            self._dispatch_fragment(f, completed, metrics, deadline, token)

    def _dispatch_fragment(self, f: QueryFragment, completed: dict[str, str],
                           metrics: dict, deadline: Optional[float],
                           token: Optional[CancelToken]) -> None:
        if token is not None and token.cancelled:
            raise QueryCancelledError("query cancelled")
        # remember the target BEFORE the call: a timed-out dispatch keeps
        # running server-side, and end-of-query release must reach this addr
        # even after _recover reassigns the fragment elsewhere
        metrics["_addrs"].add(f.worker)
        deps = [protocol.DISPATCH_DEP.build(id=d, addr=completed[d])
                for d in f.deps]
        rem = rpc.remaining_s(deadline)
        # ship the remaining budget as a RELATIVE bound (clocks differ
        # across machines): the worker uses it to deadline its own peer
        # dep-fetches so a hung peer can't wedge the fragment either
        timeout_s = round(max(rem, 0.001), 3) if rem is not None else None
        pol = self._policy()
        # flight-recorder: the dispatch span's id ships INSIDE the request
        # as the worker-side parent, so the worker's span tree re-parents
        # under this exact RPC on the stitched timeline
        tr = metrics.get("_trace")
        span_cm = tr.span("dispatch", parent_id=metrics.get("_trace_root"),
                          proc="coordinator", frag=f.id, addr=f.worker) \
            if tr is not None else contextlib.nullcontext()
        try:
            t0 = time.perf_counter()
            with span_cm as span_id:
                # the dispatch payload, typed through the registry; the
                # trace block ships the dispatch span's id as the worker-
                # side parent so the worker's tree stitches under this RPC
                ctx = protocol.TRACE_CTX.build(
                    trace_id=tr.trace_id, parent_id=span_id) \
                    if span_id is not None else None
                req = protocol.DISPATCH.build(id=f.id, plan=f.plan,
                                              deps=deps,
                                              timeout_s=timeout_s,
                                              trace=ctx,
                                              budget=metrics.get("_budget"))
                # retries=0: re-dispatch is the RECOVERY layer's job — an
                # RPC-level retry against the same hung worker would just
                # double the time a dead worker stalls the wave. The
                # per-dispatch bound is the HANG DETECTOR: under a query
                # deadline it is call_timeout_s (clamped to the remaining
                # budget) so rescue fits inside the deadline; without one, a
                # dispatch runs QUERY work and gets the stream budget
                # instead — a slow-but-legitimate fragment must not be
                # misread as a hung worker at the control-action timeout
                info = flight_action(f.worker, "execute_fragment", req,
                                     policy=pol.with_(retries=0),
                                     deadline=deadline,
                                     timeout_s=(pol.call_timeout_s
                                                if deadline is not None
                                                else pol.stream_timeout_s))
            wall = time.perf_counter() - t0
            # typed through the registry: a worker answering with a
            # malformed stats report fails loudly here, naming the field
            info = protocol.FRAGMENT_STATS.parse(info)
            if tr is not None:
                # stitch the worker's span tree into the query trace (and
                # keep the metrics fragments lean — spans are trace data)
                tr.extend(info.pop("spans", None))
            else:
                info.pop("spans", None)
            info["addr"] = f.worker
            if f.kind:
                info["kind"] = f.kind
            if f.bucket is not None:
                info["bucket"] = f.bucket
            if f.stats_key is not None:
                info["stats_key"] = f.stats_key
            # dispatch = RPC wall minus what the worker accounted for
            # (execution + dependency fetches): serialization + network +
            # the worker's action-handler queue
            info["dispatch_s"] = round(max(
                wall - info.get("elapsed_s", 0.0)
                - info.get("dep_fetch_s", 0.0), 0.0), 6)
            metrics["fragments"].append(info)
        except flight.FlightUnauthenticatedError:
            raise  # fatal by classification: never a dead-worker signal
        except DeadlineExceededError:
            raise  # query budget spent before the call could start
        except flight.FlightServerError as ex:
            marker = "DEP_UNAVAILABLE:"
            msg = str(ex)
            if marker in msg:
                dep_id = msg.split(marker, 1)[1].split()[0]
                raise _DepLost(dep_id)
            raise  # execution error on a live worker: surface it
        except Exception as ex:
            if "WORKER_BUSY" in str(ex):
                # all execution slots occupied on a HEALTHY worker: requeue
                # the fragment elsewhere, never evict (docs/serving.md)
                raise _WorkerBusy(f.worker)
            # only RETRYABLE failures are a dead-worker signal:
            # FlightTimedOutError (the hung worker — accepted TCP, never
            # answered), FlightUnavailableError, connection errors. Anything
            # rpc.retryable() calls fatal (internal/cancelled/unknown Flight
            # errors) is a real failure a HEALTHY worker reported —
            # re-dispatching it would evict worker after worker and bury the
            # actual error under "repeated worker failures"
            if rpc.retryable(ex):
                raise _WorkerDied(f.worker)
            raise
        tracing.counter("coordinator.fragments_dispatched")

    def _recover(self, dead_addrs: set[str], frags: dict[str, QueryFragment],
                 completed: dict[str, str], pending: set,
                 deadline: Optional[float] = None) -> None:
        """Evict dead workers, requeue results they held, move their work."""
        import itertools
        for addr in dead_addrs:
            w = self.membership.by_addr(addr)
            if w is not None:
                self.membership.evict(w.worker_id)
        live = self._live_addrs()
        if not live:
            # the whole fleet is momentarily unreachable (rolling restart, a
            # blip that tripped every dispatch at once): evicted-but-alive
            # workers re-register on their next heartbeat — wait for one
            # instead of failing the query instantly
            wait = RECOVER_WAIT_S
            rem = rpc.remaining_s(deadline)
            if rem is not None:
                wait = min(wait, max(rem, 0.0))
            t_end = time.time() + wait
            while not live and time.time() < t_end:
                time.sleep(0.05)
                live = self._live_addrs()
        if not live:
            raise IglooError(
                f"no live workers left (failed: {sorted(dead_addrs)})")
        for fid, holder in list(completed.items()):
            if holder in dead_addrs:
                del completed[fid]
                pending.add(fid)  # pure fragment: safe to re-run
        rr = itertools.cycle(live)
        moved = 0
        for fid in pending:
            if frags[fid].worker not in live:
                frags[fid].worker = next(rr)
                tracing.counter("coordinator.fragments_redispatched")
                moved += 1
        if moved:
            # one journal event per recovery round, not per fragment
            events.emit("fragment_redispatch", severity="warn",
                        fragments=moved, dead=sorted(dead_addrs))

    def _accumulate(self, metrics: dict) -> None:
        """Fold one query's per-fragment stats into the cumulative per-worker
        totals served by the coordinator `metrics` action."""
        with self._totals_lock:
            for info in metrics["fragments"]:
                t = self.worker_totals.setdefault(
                    info.get("worker", info.get("addr", "?")),
                    {"fragments": 0, "rows": 0, "execute_s": 0.0,
                     "dispatch_s": 0.0, "dep_fetch_s": 0.0,
                     "h2d_bytes": 0, "d2h_bytes": 0, "jit_misses": 0,
                     "exchange_bytes": 0})
                t["fragments"] += 1
                t["rows"] += info.get("rows", 0)
                t["execute_s"] += info.get("elapsed_s", 0.0)
                t["dispatch_s"] += info.get("dispatch_s", 0.0)
                t["dep_fetch_s"] += info.get("dep_fetch_s", 0.0)
                t["h2d_bytes"] += info.get("h2d_bytes", 0) or 0
                t["d2h_bytes"] += info.get("d2h_bytes", 0) or 0
                t["jit_misses"] += info.get("jit_misses", 0) or 0
                t["exchange_bytes"] += info.get("exchange_bytes", 0) or 0

    def prometheus_lines(self) -> list:
        """Worker-aggregated fragment stats as labeled Prometheus lines."""
        lines = []
        with self._totals_lock:
            totals = {w: dict(t) for w, t in self.worker_totals.items()}
        for name, key, kind in (
                ("igloo_coordinator_worker_fragments_total", "fragments",
                 "counter"),
                ("igloo_coordinator_worker_fragment_rows_total", "rows", "counter"),
                ("igloo_coordinator_worker_fragment_execute_seconds_total", "execute_s",
                 "counter"),
                ("igloo_coordinator_worker_fragment_dispatch_seconds_total", "dispatch_s",
                 "counter"),
                ("igloo_coordinator_worker_fragment_dep_fetch_seconds_total",
                 "dep_fetch_s", "counter"),
                ("igloo_coordinator_worker_fragment_h2d_bytes_total", "h2d_bytes",
                 "counter"),
                ("igloo_coordinator_worker_fragment_d2h_bytes_total", "d2h_bytes",
                 "counter"),
                ("igloo_coordinator_worker_fragment_jit_misses_total", "jit_misses",
                 "counter"),
                ("igloo_coordinator_worker_exchange_bytes_total",
                 "exchange_bytes", "counter")):
            if totals:
                lines.append(f"# TYPE {name} {kind}")
            for w, t in sorted(totals.items()):
                lines.append(f'{name}{{worker="{w}"}} {t.get(key, 0)}')
        return lines

    def _release(self, frags: dict[str, QueryFragment],
                 completed: dict[str, str], ids: list[str],
                 dispatched=()) -> None:
        # every worker a fragment was ASSIGNED to or EVER dispatched to, not
        # just recorded holders: a wave that errored out mid-collection
        # leaves results on workers whose completions were never processed,
        # and an EVICTED worker (its fragment reassigned by _recover) may
        # still be running the timed-out handler — it needs the release so
        # its store grows a tombstone for the late put
        addrs = set(completed.values()) | \
            {f.worker for f in frags.values()} | set(dispatched)
        with tracing.span("coordinator.release", workers=len(addrs)):
            for addr in addrs:
                try:
                    # short bound, no retries: release is best-effort cleanup
                    # and often targets the very worker that just died
                    flight_action(addr, "release",
                                  protocol.RELEASE.build(ids=ids),
                                  policy=self._policy().with_(retries=0),
                                  timeout_s=10.0)
                except Exception:
                    pass  # worker gone; nothing to release


class _WorkerDied(Exception):
    def __init__(self, addr: str):
        self.addr = addr


class _WorkerBusy(Exception):
    """Dispatch refused with the WORKER_BUSY marker: every execution slot
    on a live worker is occupied. Requeue the fragment, never evict."""

    def __init__(self, addr: str):
        self.addr = addr


class _DepLost(Exception):
    def __init__(self, frag_id: str):
        self.frag_id = frag_id


class CoordinatorServer(flight.FlightServerBase):
    """The cluster's front door + control plane on ONE Flight endpoint."""

    def __init__(self, location: str, worker_timeout_s: float = 15.0,
                 use_jit: bool = True, advertise_host: Optional[str] = None,
                 **kw):
        # trusted-network default; IGLOO_TPU_AUTH_TOKEN installs a shared-
        # token check on every Flight call (see cluster/rpc.py security model)
        mw = rpc.server_middleware()
        if mw is not None:
            kw.setdefault("middleware", mw)
        ah = rpc.server_auth_handler()
        if ah is not None:
            kw.setdefault("auth_handler", ah)
        rpc.warn_if_open_bind(location.split("://")[-1].rsplit(":", 1)[0],
                              "coordinator")
        # pick up IGLOO_FAULTS set after import (in-process test clusters)
        faults.refresh()
        super().__init__(location, **kw)
        if advertise_host is None:
            # endpoint host clients are told to come back to: the bound host
            # (unless wildcard-bound, where loopback is the only safe default)
            host = location.split("://")[-1].rsplit(":", 1)[0]
            advertise_host = host if host and host != "0.0.0.0" else "127.0.0.1"
        self.advertise_host = advertise_host
        # this engine runs only the local fallback and the demotion ladder.
        # The resident share of a chip belongs to the worker that serves it
        # (in the same process or beside it), so the fallback's scan cache
        # keeps the 1 GiB it always had and the two stay under the device
        from igloo_tpu.exec.cache import UNLIMITED_BUDGETS
        self.engine = QueryEngine(use_jit=use_jit,
                                  cache_budget_bytes=UNLIMITED_BUDGETS[0])
        self.membership = Membership(worker_timeout_s)
        self.executor = DistributedExecutor(self.membership)
        # multi-tenant front door (docs/serving.md): bounded per-priority
        # admission, weighted fair dequeue, per-session caps, HBM-gated
        # concurrency; IGLOO_SERVING_QUEUE=0 serializes one query at a time
        self.admission = serving.AdmissionController()
        self._table_specs: dict[str, dict] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._sweeper = threading.Thread(target=self._sweep_loop, daemon=True)
        self._sweeper.start()
        # watchtower sampler (utils/timeseries.py): no-op under IGLOO_WATCH=0
        timeseries.start("coordinator")

    # --- table management ---

    def register_table(self, name: str, provider) -> None:
        """Register on the coordinator AND push to every live worker."""
        import pyarrow as _pa
        from igloo_tpu.catalog import MemTable
        if isinstance(provider, _pa.Table):
            provider = MemTable(provider)
        self.engine.register_table(name, provider)
        spec = serde.provider_to_spec(provider)
        if spec is not None:
            with self._lock:
                self._table_specs[name.lower()] = spec
            for w in self.membership.live():
                try:
                    self._push_table(w, name, spec)
                except Exception:
                    # forget any OLDER version this worker holds, so the next
                    # _sync_worker_tables re-pushes instead of serving stale
                    # rows next to fresh ones on other workers
                    w.tables_pushed.discard(name.lower())

    def _push_table(self, w: WorkerState, name: str, spec: dict) -> None:
        flight_action(w.addr, "register_table",
                      protocol.REGISTER_TABLE.build(name=name, spec=spec))
        w.tables_pushed.add(name.lower())

    def _sync_worker_tables(self, w: WorkerState) -> None:
        with self._lock:
            specs = dict(self._table_specs)
        for name, spec in specs.items():
            if name not in w.tables_pushed:
                self._push_table(w, name, spec)

    # --- query execution ---

    def execute_sql(self, sql: str, stream: bool = False,
                    deadline_s: Optional[float] = None,
                    qid: Optional[str] = None, priority: int = 1,
                    session: str = "",
                    trace: Optional[flight_recorder.Trace] = None):
        """-> pa.Table, or — for `stream=True` on the distributed path —
        (pa.Schema, record-batch generator) so do_get can relay the root
        worker's stream batch-wise instead of materializing it here.
        `deadline_s`/`qid` bound + name the DISTRIBUTED execution (deadline,
        cancel_query); the local fallback paths honor the deadline at their
        checkpoints (before planning, between plan and execute) but are not
        cancellable mid-flight. `priority`/`session` feed the admission
        controller (docs/serving.md): past the queue bound or the session's
        in-flight cap the query is SHED with a retryable serving.ServerBusy
        instead of executing."""
        t_start = time.time()
        deadline = t_start + deadline_s if deadline_s is not None else None
        self._check_local_deadline(deadline, sql, t_start, priority,
                                   planned=False)
        try:
            plan = self.engine.plan(sql)
        except IglooError:
            # non-SELECT statements (SHOW/DESCRIBE/CTAS/...) run locally,
            # un-admitted: metadata ops must work even under full overload
            return self.engine.execute(sql)
        # plan+snapshot-keyed result cache: a repeated dashboard-shaped
        # query short-circuits admission (and all execution) entirely
        rkey = self._result_cache_key(plan)
        if rkey is not None:
            hit = self.engine.result_cache.get(rkey)
            if hit is not None:
                return self._serve_cached(hit, sql, stream, t_start,
                                          priority, qid, trace=trace)
        try:
            permit = self.admission.submit(
                priority=priority, session=session,
                predicted_hbm_bytes=serving.predict_hbm_bytes(plan),
                deadline=deadline)
        except serving.ServerBusy:
            stats.log_query(sql, elapsed_s=time.time() - t_start,
                            tier="serving", status="shed",
                            started_at=t_start, priority=priority)
            events.emit("admission_shed", severity="warn", qid=qid or "",
                        priority=priority)
            raise
        try:
            out = self._execute_admitted(plan, sql, stream, deadline,
                                         deadline_s, qid, permit, rkey,
                                         t_start, trace=trace)
        except BaseException:
            permit.release()
            raise
        if stream and isinstance(out, tuple):
            # the permit rides the stream: concurrency and the HBM
            # reservation are held until the relay finishes (worker-held
            # results live exactly that long)
            schema, gen = out
            return schema, _released_stream(gen, permit)
        permit.release()
        return out

    def _execute_admitted(self, plan, sql: str, stream: bool,
                          deadline: Optional[float],
                          deadline_s: Optional[float], qid: Optional[str],
                          permit: "serving.Permit", rkey, t_start: float,
                          trace: Optional[flight_recorder.Trace] = None):
        """The admitted execution body: distributed when possible, local
        fallback otherwise, with the degradation ladder absorbing OOM."""
        if permit.demote:
            # predicted past the WHOLE HBM budget: first try to spread the
            # over-budget join across the fleet (GRACE partitions become
            # exchange buckets, each worker spills and streams its share —
            # docs/out_of_core.md); when the fleet or plan can't take it,
            # fall back to the exact single-node degradation ladder
            out = self._try_oversized_distributed(
                plan, sql, stream, deadline, deadline_s, qid, permit, rkey,
                trace=trace)
            if out is not None:
                return out
            return self._run_demoted(sql, stream, deadline, t_start, permit)
        with tracing.span("coordinator.plan"):
            planned = self._plan_fragments(plan)
        if planned is None:
            return self._run_local(sql, stream, deadline, t_start, permit)
        topo, planner, plan_key, frags = planned
        tracing.counter("coordinator.distributed_queries")
        # reorder decisions from engine.plan's optimize() above ride beside
        # the fragment-tier broadcast/salt records (docs/adaptive.md)
        from igloo_tpu.plan.optimizer import last_adaptive_decisions
        adaptive_info = last_adaptive_decisions() + planner.adaptive_info
        extra = {"queue_wait_s": round(permit.wait_s, 6),
                 "priority": permit.priority, "demoted": 0,
                 # "_"-prefixed: never published; _finalize judges the
                 # finished query under it
                 "_plan_fp": plan_key,
                 # the topology this query was planned against, published in
                 # last_metrics beside the per-fragment mesh_devices reports
                 "topology": {"workers": len(topo),
                              "devices": topo,
                              "total_shards": sum(topo.values())}}
        try:
            if stream:
                schema, gen = self.executor.execute_stream(
                    frags, deadline_s=deadline_s, qid=qid, sql=sql,
                    adaptive_info=adaptive_info, extra_metrics=extra,
                    trace=trace)
                return schema, self._caching_stream(schema, gen, rkey)
            table = self.executor.execute(frags, deadline_s=deadline_s,
                                          qid=qid, sql=sql,
                                          adaptive_info=adaptive_info,
                                          extra_metrics=extra, trace=trace)
        except Exception as ex:
            if not _is_oom(ex):
                raise
            # a worker (or the relay) ran out of device memory: demote the
            # query down the local ladder instead of failing it
            return self._run_demoted(sql, stream, deadline, t_start, permit)
        self._result_cache_put(rkey, table)
        return table

    def _plan_fragments(self, plan):
        """From the optimized plan to the fragment DAG ready to dispatch:
        -> (topology, planner, watchtower plan key, fragments), or None
        where the query has to run locally."""
        live = self.membership.live()
        if not live:
            # a coordinator with no workers is still a working single-node
            # engine (the reference coordinator main is exactly that)
            return None
        synced = []
        for w in live:
            try:
                self._sync_worker_tables(w)
                synced.append(w)
            except Exception:
                # unreachable mid-sweep: evict now instead of failing every
                # query until the sweeper notices
                self.membership.evict(w.worker_id)
        live = synced
        if not live or not self._distributable(plan):
            # only distribute plans whose base tables every worker resolves
            return None
        # per-worker device counts ride into planning: bucket counts scale
        # with hosts, per-worker shard counts with chips, and heterogeneous
        # clusters get device-weighted bucket placement (two-level
        # parallelism, docs/distributed.md)
        topo = {w.addr: w.devices for w in live}
        planner = DistributedPlanner([w.addr for w in live], topology=topo)
        # watchtower baseline key, captured BEFORE fragmenting: the planner
        # rewrites the tree in place (partial-agg Union merge has no stable
        # key), and the baseline must describe the user's logical plan — the
        # same key the local tier would observe under
        from igloo_tpu.exec import hints
        plan_key = hints.plan_fp(plan)
        return topo, planner, plan_key, planner.plan(plan)

    # --- serving helpers (docs/serving.md) ---

    def _check_local_deadline(self, deadline: Optional[float], sql: str,
                              t_start: float, priority: int,
                              planned: bool = True) -> None:
        """`deadline_s` honored on the LOCAL fallback paths too (the
        distributed executor has its own checks): before planning and
        between plan and execute, surfacing `query.deadline_exceeded` and a
        query-log row exactly like the distributed accounting."""
        if deadline is None or time.time() < deadline:
            return
        tracing.counter("query.deadline_exceeded")
        stats.log_query(sql, elapsed_s=time.time() - t_start, tier="local",
                        status="deadline_exceeded", started_at=t_start,
                        priority=priority)
        where = "execution" if planned else "planning"
        raise DeadlineExceededError(
            f"query exceeded its deadline before local {where}")

    def _run_local(self, sql: str, stream: bool, deadline: Optional[float],
                   t_start: float, permit: "serving.Permit"):
        """Local fallback execution under the serving context, with the
        OOM->demote ladder."""
        self._check_local_deadline(deadline, sql, t_start, permit.priority)
        with stats.serving_context(queue_wait_s=permit.wait_s,
                                   priority=permit.priority):
            try:
                out = self.engine.execute(sql)
            except Exception as ex:
                if not _is_oom(ex):
                    raise
                out = self._demote_ladder(sql, deadline, t_start,
                                          permit.priority)
        return (out.schema, iter(out.to_batches())) if stream else out

    def _try_oversized_distributed(self, plan, sql: str, stream: bool,
                                   deadline: Optional[float],
                                   deadline_s: Optional[float],
                                   qid: Optional[str],
                                   permit: "serving.Permit", rkey,
                                   trace: Optional[
                                       flight_recorder.Trace] = None):
        """Distributed out-of-core attempt for an oversized query: plan the
        over-budget join as per-bucket fragments whose buckets ARE its GRACE
        partitions (cluster/fragment.py `_try_grace_distributed`), spread
        across the live workers, each dispatch carrying the per-worker
        budget so Exchange fragments stream-spill under it. Returns None
        whenever the fleet or the plan can't take it — fewer than two
        synced workers, a non-distributable plan, the planner declining
        (`grace_info` unset), the `IGLOO_GRACE_DISTRIBUTED=0` kill switch,
        or an execution failure — and the caller falls back to the exact
        single-node ladder, byte-identical to the pre-distributed behavior."""
        live = self.membership.live()
        if len(live) < 2:
            return None
        synced = []
        for w in live:
            try:
                self._sync_worker_tables(w)
                synced.append(w)
            except Exception:
                self.membership.evict(w.worker_id)
        live = synced
        if len(live) < 2 or not self._distributable(plan):
            return None
        budget = self._demote_budget()
        topo = {w.addr: w.devices for w in live}
        planner = DistributedPlanner([w.addr for w in live], topology=topo,
                                     budget_bytes=budget)
        # captured before planner.plan rewrites the tree (see
        # _run_distributed): the baseline keys the user's logical plan
        from igloo_tpu.exec import hints
        plan_key = hints.plan_fp(plan)
        try:
            frags = planner.plan(plan)
        except Exception:
            tracing.counter("grace.distributed_planfail")
            return None
        if planner.grace_info is None:
            return None
        tracing.counter("coordinator.distributed_queries")
        from igloo_tpu.plan.optimizer import last_adaptive_decisions
        adaptive_info = last_adaptive_decisions() + planner.adaptive_info
        extra = {"queue_wait_s": round(permit.wait_s, 6),
                 "priority": permit.priority, "demoted": 0,
                 "_plan_fp": plan_key,
                 # per-query out-of-core attribution, published in
                 # last_metrics
                 "oversized": dict(planner.grace_info),
                 "topology": {"workers": len(live),
                              "devices": topo,
                              "total_shards": sum(topo.values())}}
        try:
            # materialized (not relay-streamed) even for stream callers:
            # the caller must still be able to fall back to the exact
            # ladder if a worker dies or OOMs mid-query, which is
            # impossible once a stream has been handed out. Oversized
            # results are post-aggregate and small; the BUCKETS never
            # gather here.
            table = self.executor.execute(frags, deadline_s=deadline_s,
                                          qid=qid, sql=sql,
                                          adaptive_info=adaptive_info,
                                          extra_metrics=extra, trace=trace,
                                          budget=budget)
        except (QueryCancelledError, DeadlineExceededError, serving.ServerBusy):
            raise
        except Exception:
            tracing.counter("grace.distributed_fallback")
            return None
        self._result_cache_put(rkey, table)
        return (table.schema, iter(table.to_batches())) if stream else table

    def _run_demoted(self, sql: str, stream: bool,
                     deadline: Optional[float], t_start: float,
                     permit: "serving.Permit"):
        """Entry for queries pre-flagged by the HBM gate: straight onto the
        ladder."""
        with stats.serving_context(queue_wait_s=permit.wait_s,
                                   priority=permit.priority):
            out = self._demote_ladder(sql, deadline, t_start,
                                      permit.priority)
        # publish: a demoted query must overwrite last_metrics (clients —
        # and the kill-switch A/B — would otherwise read the PREVIOUS
        # query's oversized/fragment attribution as this one's)
        with self.executor._totals_lock:
            self.executor.last_metrics = {
                "qid": "", "status": "ok", "rows": out.num_rows,
                "fragments": [], "recoveries": 0, "demoted": 1,
                "execution_time_s": round(time.time() - t_start, 6)}
        return (out.schema, iter(out.to_batches())) if stream else out

    def _demote_ladder(self, sql: str, deadline: Optional[float],
                       t_start: float, priority: int):
        """The graceful-degradation ladder's one rung: re-run locally with
        a chunk budget constrained to the serving HBM budget (forcing the
        chunked/GRACE out-of-core tiers). It bumps `serving.demoted` + the
        query-log `demoted` column; an OOM there surfaces as the error it
        is."""
        self._check_local_deadline(deadline, sql, t_start, priority)
        tracing.counter("serving.demoted")
        events.emit("query_demoted", severity="warn")
        stats.mark_demoted()
        with self.engine.demoted(budget_bytes=self._demote_budget()):
            return self.engine.execute(sql)

    def _demote_budget(self) -> int:
        """Chunk budget for demoted execution: the serving HBM budget when
        one is configured (that IS the memory the query must fit), else a
        quarter of the engine's normal budget; floored so partition counts
        stay sane."""
        b = self.admission.hbm_budget_bytes or \
            self.engine.chunk_budget_bytes // 4
        return max(int(b), 1 << 20)

    def _result_cache_key(self, plan):
        if os.environ.get(RESULT_CACHE_ENV, "1") == "0":
            return None
        from igloo_tpu.exec.result_cache import plan_cache_key
        return plan_cache_key(plan)

    def _result_cache_put(self, rkey, table: pa.Table) -> None:
        if rkey is not None and table.nbytes <= RESULT_CACHE_MAX_BYTES:
            self.engine.result_cache.put(rkey, table)

    def _serve_cached(self, hit: pa.Table, sql: str, stream: bool,
                      t_start: float, priority: int, qid: Optional[str],
                      trace: Optional[flight_recorder.Trace] = None):
        """A front-door result-cache hit: no admission, no execution —
        publish attributable metrics (`result_cache_hit` in last_metrics,
        a tier=result_cache query-log row) and serve the cached table."""
        elapsed = time.time() - t_start
        tid = trace.trace_id if trace is not None else ""
        with self.executor._totals_lock:
            self.executor.last_metrics = {
                "qid": qid or "", "result_cache_hit": True, "status": "ok",
                "rows": hit.num_rows, "fragments": [], "recoveries": 0,
                "execution_time_s": round(elapsed, 6), "trace_id": tid}
        stats.log_query(sql, elapsed_s=elapsed, tier="result_cache",
                        rows=hit.num_rows, started_at=t_start,
                        priority=priority, trace_id=tid)
        if stream:
            return hit.schema, iter(hit.to_batches())
        return hit

    def _caching_stream(self, schema: pa.Schema, gen, rkey):
        """Relay a distributed result stream while teeing batches into the
        result cache — giving up silently once the result outgrows the
        cacheable bound (materializing huge results here would defeat the
        streaming design)."""
        if rkey is None:
            return gen

        def teed():
            kept: list = []
            nbytes = 0
            for batch in gen:
                if kept is not None:
                    nbytes += batch.nbytes
                    if nbytes > RESULT_CACHE_MAX_BYTES:
                        kept = None
                    else:
                        kept.append(batch)
                yield batch
            if kept is not None:
                self._result_cache_put(
                    rkey, pa.Table.from_batches(kept, schema=schema))
        return teed()

    def _distributable(self, plan) -> bool:
        from igloo_tpu.plan.logical import Scan, walk_plan
        with self._lock:
            known = set(self._table_specs)
        return all(n.table.lower() in known for n in walk_plan(plan)
                   if isinstance(n, Scan))

    # --- liveness sweep ---

    def _sweep_loop(self) -> None:
        while not self._stop.wait(self.membership.timeout_s / 3):
            self.membership.sweep()

    def shutdown(self):  # pragma: no cover - exercised via tests' finally
        self._stop.set()
        super().shutdown()
        rpc.close_idle_connections()

    # --- Flight methods (full surface; reference implements 2 of 9) ---

    def do_action(self, context, action):
        with rpc.Served("coordinator.serve", rpc.action_kind(
                action.type, protocol.COORDINATOR_ACTIONS)):
            faults.inject(f"coordinator.do_action.{action.type}")
            body = action.body.to_pybytes() if action.body is not None else b""
            req = json.loads(body) if body else {}
            if action.type == "cancel_query":
                ok = self.executor.cancel(protocol.CANCEL_QUERY.parse(req)["qid"])
                return [json.dumps({"cancelled": ok}).encode()]
            if action.type == "active_queries":
                return [json.dumps(
                    {"queries": self.executor.active_queries()}).encode()]
            if action.type == "register_worker":
                info = serde.worker_info_from_json(req)
                self.membership.register(info["id"], info["addr"],
                                         devices=info["devices"],
                                         slots=info["slots"])
                w = self.membership.by_addr(info["addr"])
                if w is not None:
                    try:
                        self._sync_worker_tables(w)
                    except Exception:
                        pass
                # propagate the persistent compile-cache setting + entry listing:
                # the worker adopts the setting when it has none of its own and
                # pre-warms by pulling entries it is missing (compile_cache_get),
                # so a fresh worker starts with every program the cluster has
                # ever compiled (docs/compile_cache.md)
                import os
                from igloo_tpu import compile_cache
                return [json.dumps({"compile_cache": {
                    "setting": os.environ.get("IGLOO_TPU_COMPILE_CACHE", "1"),
                    "entries": compile_cache.entry_names(
                        min_age_s=compile_cache.TRANSFER_MIN_AGE_S),
                }}).encode()]
            if action.type == "compile_cache_get":
                # raw entry bytes by XLA cache filename (NOT JSON — workers use
                # rpc.flight_action_raw); empty body = no such entry
                from igloo_tpu import compile_cache
                data = compile_cache.read_entry(
                    protocol.COMPILE_CACHE_GET.parse(req)["name"])
                return [data if data is not None else b""]
            if action.type == "compile_cache_put":
                # worker pushing a freshly compiled entry back to the cluster
                from igloo_tpu import compile_cache
                put = protocol.COMPILE_CACHE_PUT.parse(req)
                stored = compile_cache.write_entry(
                    put["name"], compile_cache.decode_entry(put["data"]))
                return [json.dumps({"stored": stored}).encode()]
            if action.type == "heartbeat":
                info = serde.worker_info_from_json(req)
                # a legacy payload WITHOUT the topology fields must not reset
                # the recorded devices to the codec's default of 1
                ok = self.membership.heartbeat(
                    info["id"], info["addr"],
                    devices=info["devices"] if "devices" in req else None,
                    slots=info["slots"])
                # journal events riding the beat (cluster/events.py; dedup by
                # eid keeps in-process fleets and heartbeat retries honest)
                events.ingest(info["events"], worker=info["id"])
                return [json.dumps({"ok": ok}).encode()]
            if action.type == "register_table":
                rt = protocol.REGISTER_TABLE.parse(req)
                provider = serde.provider_from_spec(rt["spec"])
                self.register_table(rt["name"], provider)
                return [b"{}"]
            if action.type == "cluster_status":
                return [json.dumps({
                    "workers": [{"id": w.worker_id, "addr": w.addr,
                                 "last_seen": w.last_seen,
                                 "devices": w.devices, "slots": w.slots}
                                for w in self.membership.live()],
                    "tables": sorted(self.engine.catalog.names()),
                }).encode()]
            if action.type == "last_metrics":
                with self.executor._totals_lock:
                    pub = self.executor.last_metrics
                return [json.dumps(pub).encode()]
            if action.type == "trace":
                # stitched query timeline by trace_id or qid (neither = most
                # recent); Chrome-trace/Perfetto JSON by default, the raw span
                # record with {"format": "raw"} (raw bytes — flight_action_raw)
                tq = protocol.TRACE_REQUEST.parse(req)
                rec = flight_recorder.get_record(tq["trace_id"], tq["qid"])
                if rec is None:
                    raise flight.FlightServerError(
                        f"no such trace: {tq['trace_id'] or tq['qid'] or '<last>'}")
                if tq["format"] == "raw":
                    return [json.dumps(rec).encode()]
                return [json.dumps(flight_recorder.to_chrome_trace(rec)).encode()]
            if action.type == "serving_status":
                # admission queue / slot / HBM-reservation snapshot
                return [json.dumps(self.admission.snapshot()).encode()]
            if action.type == "metrics":
                # coordinator process registry + worker-aggregated fragment
                # stats, Prometheus text (raw bytes — rpc.flight_action_raw)
                live_w = self.membership.live()
                extra = ["# TYPE igloo_workers_live gauge",
                         f"igloo_workers_live {len(live_w)}",
                         "# TYPE igloo_cluster_devices gauge",
                         f"igloo_cluster_devices {sum(w.devices for w in live_w)}"]
                extra.extend(self.executor.prometheus_lines())
                extra.extend(events.prometheus_lines())
                return [tracing.prometheus_text(extra_lines=extra).encode()]
            if action.type == "ping":
                return [json.dumps({"workers": len(self.membership.live())}).encode()]
            if action.type == "poll_flight_info":
                # body: JSON {"sql": "..."} (do_action parses all bodies as JSON)
                info = self.get_flight_info(
                    context, flight.FlightDescriptor.for_command(
                        protocol.POLL_FLIGHT_INFO.parse(req)["sql"]))
                return [json.dumps({"progress": 1.0, "complete": True}).encode(),
                        info.serialize()]
            if action.type == "metrics_history":
                return [json.dumps(protocol.METRICS_HISTORY.build(
                    samples=self._aggregate_metrics_history())).encode()]
            if action.type == "events":
                er = protocol.EVENTS_REQUEST.parse(req)
                evs = events.events(min_severity=er["min_severity"] or "info",
                                    limit=er["limit"] if er["limit"] else None)
                return [json.dumps(
                    protocol.EVENTS_REPLY.build(events=evs)).encode()]
            if action.type == "slow_queries":
                return [json.dumps(protocol.SLOW_QUERIES_REPLY.build(
                    slow_queries=watch.slow_queries())).encode()]
            if action.type == "watch_status":
                return [json.dumps(self._watch_status()).encode()]
            raise flight.FlightServerError(f"unknown action {action.type}")

    def _aggregate_metrics_history(self) -> list:
        """The fleet's sampler rings: this process's own plus every live
        worker's (fetched via its `metrics_history` action, relabeled with
        the worker id), merged by timestamp. A worker that cannot answer is
        skipped — a telemetry read must never fail on a flaky fleet. Dedup
        by sample id: an in-process fleet shares one ring, and its samples
        must not triple-count."""
        samples = list(timeseries.samples())
        seen = {s.get("sid") for s in samples}
        for w in self.membership.live():
            try:
                resp = flight_action(w.addr, "metrics_history", {},
                                     timeout_s=10.0)
                for s in protocol.METRICS_HISTORY.parse(resp)["samples"]:
                    if s.get("sid") in seen:
                        continue
                    seen.add(s.get("sid"))
                    s = dict(s)
                    s["source"] = f"worker:{w.worker_id}"
                    samples.append(s)
            except Exception:
                pass
        samples.sort(key=lambda s: s.get("ts", 0.0))
        return samples

    def _watch_status(self) -> dict:
        """The one-call ops snapshot behind `igloo top`: throughput and
        latency quantiles over the recent query log, admission state,
        per-worker topology, in-flight qids, and the journal tail."""
        now = time.time()
        window_s = 60.0
        recent = [q.to_record() for q in stats.query_log()
                  if now - q.started_at <= window_s]
        lats = sorted(r["elapsed_s"] for r in recent)

        def pct(q: float) -> float:
            if not lats:
                return 0.0
            return lats[min(max(int(q * len(lats) + 0.999999) - 1, 0),
                            len(lats) - 1)]

        return protocol.WATCH_STATUS.build(
            qps=round(len(recent) / window_s, 4),
            p50_ms=round(pct(0.5) * 1000.0, 3),
            p99_ms=round(pct(0.99) * 1000.0, 3),
            window_s=window_s,
            serving=self.admission.snapshot(),
            workers=[{"id": w.worker_id, "addr": w.addr,
                      "devices": w.devices, "slots": w.slots,
                      "age_s": round(now - w.last_seen, 1)}
                     for w in self.membership.live()],
            active=self.executor.active_queries(),
            events=events.events(limit=20),
            samples=timeseries.samples()[-12:])

    def list_actions(self, context):
        # straight from the registry: the flight-actions checker holds this
        # surface and do_action's dispatch to the same declaration
        return protocol.action_doc("coordinator")

    def get_flight_info(self, context, descriptor):
        with rpc.Served("coordinator.serve", "get_flight_info"):
            sql = self._descriptor_sql(descriptor)
            # plan once for the schema — the reference executes the whole
            # query here and AGAIN in do_get (crates/api/src/lib.rs:81-149)
            schema = self._result_schema(sql)
            endpoint = flight.FlightEndpoint(sql.encode(),
                                             [self._public_location()])
            return flight.FlightInfo(schema, descriptor, [endpoint], -1, -1)

    def get_schema(self, context, descriptor):
        with rpc.Served("coordinator.serve", "get_schema"):
            return flight.SchemaResult(self._result_schema(
                self._descriptor_sql(descriptor)))

    def do_get(self, context, ticket):
        # the server end of the client's call: around the `query` scope the
        # ticket's parse, the Trace, the publish, the stream response. The
        # relay after the handler's return has spans of its own (`fetch`,
        # `coordinator.finalize`, `coordinator.release`); the call's clock
        # runs on to the relay's end
        with rpc.Served("coordinator.serve", "do_get") as served:
            return self._do_get(ticket, served)

    def _do_get(self, ticket, served: "rpc.Served"):
        faults.inject("coordinator.do_get")
        raw = ticket.ticket.decode()
        try:
            # the registry coerces every extended-ticket field HERE, so a
            # mistyped field ("5" for deadline_s, [5] for priority) is a
            # "bad query ticket" error naming the field, not a TypeError
            # surfacing as an opaque gRPC internal error mid-execute
            t = protocol.parse_query_ticket(raw)
        except protocol.ProtocolError as ex:
            raise flight.FlightServerError(f"bad query ticket: {ex}")
        sql, deadline_s, qid = t["sql"], t["deadline_s"], t["qid"]
        # trace_id is the client-chosen trace identity: lets a caller
        # correlate its own telemetry with the stitched server timeline
        priority, session = t["priority"], t["session"]
        trace_id = t["trace_id"]
        trace = None
        if flight_recorder.enabled():
            trace = flight_recorder.Trace(trace_id=trace_id, qid=qid or "",
                                          sql=sql)
        try:
            # span hygiene: the request scope gives this (reused gRPC)
            # thread a fresh span stack per query and stitches whatever the
            # execution records — planning, admission wait, local fallback
            # spans — under one "query" root
            with flight_recorder.request_scope(trace, "query",
                                               proc="coordinator",
                                               qid=qid or ""):
                out = self.execute_sql(sql, stream=True,
                                       deadline_s=deadline_s,
                                       qid=qid, priority=priority,
                                       session=session, trace=trace)
        except serving.ServerBusy as ex:
            # retryable by the client's RpcPolicy classification; carries
            # the retry-after hint in the message (docs/serving.md). Shed
            # queries never publish a trace — under overload the ring would
            # otherwise churn with empty shed records
            raise ex.as_flight_error()
        except IglooError as ex:
            if trace is not None and not trace.deferred:
                flight_recorder.publish(trace)
            raise flight.FlightServerError(str(ex))
        if trace is not None and not trace.deferred:
            # local / cached / non-SELECT paths: the result is materialized,
            # the query is over — publish now. Distributed streams publish
            # from the executor's finalize instead (trace.deferred).
            flight_recorder.publish(trace)
        if isinstance(out, tuple):
            # distributed: relay the root worker's stream batch-wise, via
            # rpc.flight_stream_response so dictionary-bearing result schemas
            # get their dictionary batches written without costing plain
            # schemas their Flight error statuses
            return rpc.flight_stream_response(out[0], served.stream(
                faults.wrap_stream("coordinator.do_get", out[1]), own=False))
        return flight.RecordBatchStream(out)

    def do_put(self, context, descriptor, reader, writer):
        with rpc.Served("coordinator.serve", "do_put"):
            faults.inject("coordinator.do_put")
            name = self._descriptor_table(descriptor)
            table = reader.read_all()
            self.register_table(name, table)

    def do_exchange(self, context, descriptor, reader, writer):
        with rpc.Served("coordinator.serve", "do_exchange"):
            self._do_exchange(descriptor, reader, writer)

    def _do_exchange(self, descriptor, reader, writer):
        """Bidirectional exchange (reference proto flight.proto:127):

        - cmd descriptor: the command is SQL; any uploaded batches are
          ignored and the query's result streams back.
        - path descriptor [table]: uploaded batches register the table (as
          do_put) and the stored table streams back — a round-trip echo a
          stock client can verify; with no uploaded batches the currently
          registered table streams back."""
        faults.inject("coordinator.do_exchange")
        if descriptor.descriptor_type == flight.DescriptorType.CMD:
            sql = descriptor.command.decode()
            try:
                table = self.execute_sql(sql)
            except serving.ServerBusy as ex:
                raise ex.as_flight_error()
            except IglooError as ex:
                raise flight.FlightServerError(str(ex))
            writer.begin(table.schema)
            for batch in table.to_batches():
                writer.write_batch(batch)
            return
        name = self._descriptor_table(descriptor)
        uploaded = None
        try:
            uploaded = reader.read_all()
        except OSError as ex:
            # pyarrow raises ArrowIOError "Client never sent a data message"
            # for a write-less exchange — the one condition where echoing the
            # stored table is the contract. Anything else is a real upload
            # failure and must NOT be masked as a successful-looking echo.
            if "never sent a data message" not in str(ex):
                raise flight.FlightServerError(f"exchange upload failed: {ex}")
        except Exception as ex:
            # mid-stream decode/transport failure: surface it to the client
            raise flight.FlightServerError(f"exchange upload failed: {ex}")
        if uploaded is not None and uploaded.num_rows > 0:
            self.register_table(name, uploaded)
        try:
            table = self.engine.catalog.get(name).read()
        except Exception as ex:
            raise flight.FlightServerError(f"exchange: {ex}")
        writer.begin(table.schema)
        for batch in table.to_batches():
            writer.write_batch(batch)

    # The reference proto also declares PollFlightInfo (flight.proto:92);
    # pyarrow's FlightServerBase has no server hook for it, so the
    # immediate-complete equivalent is served as the "poll_flight_info"
    # action (do_action below): it returns the serialized FlightInfo for a
    # SQL command with progress=1.0 — long-running-query polling semantics
    # collapse to "already complete" because get_flight_info only PLANS.

    def list_flights(self, context, criteria):
        for name in sorted(self.engine.catalog.names()):
            desc = flight.FlightDescriptor.for_path(name)
            sql = f"SELECT * FROM {name}"
            endpoint = flight.FlightEndpoint(sql.encode(),
                                             [self._public_location()])
            yield flight.FlightInfo(self._result_schema(sql), desc,
                                    [endpoint], -1, -1)

    # --- helpers ---

    def _public_location(self) -> str:
        return f"grpc+tcp://{self.advertise_host}:{self.port}"

    @staticmethod
    def _descriptor_sql(descriptor) -> str:
        if descriptor.command:
            return descriptor.command.decode()
        if descriptor.path:
            return f"SELECT * FROM {descriptor.path[0].decode()}"
        raise flight.FlightServerError("descriptor has no SQL command")

    @staticmethod
    def _descriptor_table(descriptor) -> str:
        if descriptor.path:
            return descriptor.path[0].decode()
        if descriptor.command:
            return descriptor.command.decode()
        raise flight.FlightServerError("descriptor has no table name")

    def _result_schema(self, sql: str) -> pa.Schema:
        try:
            plan = self.engine.plan(sql)
        except IglooError as ex:
            raise flight.FlightServerError(str(ex))
        from igloo_tpu.exec.executor import _pa_type_for
        return pa.schema([pa.field(f.name, _pa_type_for(f.dtype), f.nullable)
                          for f in plan.schema])


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="igloo-coordinator")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=50051)
    ap.add_argument("--config", default=None)
    args = ap.parse_args(argv)

    timeout = 15.0
    server = CoordinatorServer(f"grpc+tcp://{args.host}:{args.port}",
                               worker_timeout_s=timeout)
    if args.config:
        from igloo_tpu.config import (
            Config, apply_storage, make_provider, rpc_policy,
        )
        cfg = Config.load(args.config)
        server.membership.timeout_s = cfg.cluster.worker_timeout_s
        # [rpc] config is the base; IGLOO_RPC_* env still wins per-field
        rpc.set_default_policy(rpc.policy_from_env(rpc_policy(cfg)))
        # [storage] likewise (policy + prefetch twins; env wins per-field)
        apply_storage(cfg)
        if cfg.rpc.query_deadline_s is not None and \
                not os.environ.get(QUERY_DEADLINE_ENV):
            # same precedence as every other [rpc] knob: env beats config;
            # a configured 0 means explicitly unbounded
            server.executor.default_deadline_s = \
                cfg.rpc.query_deadline_s or None
        # [serving] section: explicit values flow through the controller's
        # constructor, where IGLOO_SERVING_* env still wins per-field
        sv = cfg.serving
        server.admission = serving.AdmissionController(
            queue_depth=sv.queue_depth,
            max_concurrency=sv.max_concurrency,
            session_inflight=sv.session_inflight,
            hbm_budget_bytes=sv.hbm_budget_bytes,
            weights=sv.weights)
        for t in cfg.tables:
            server.register_table(t.name, make_provider(t))
    print(f"igloo-coordinator serving on grpc+tcp://{args.host}:"
          f"{server.port}", flush=True)
    try:
        server.serve()
    except KeyboardInterrupt:
        server.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
