"""Per-query telemetry: the operator stats tree and the query log.

Where utils/tracing.py holds PROCESS-wide metrics, this module holds
PER-QUERY ones: `QueryStats` carries an operator tree mirroring the physical
execution (rows in/out, wall time, compile-vs-execute split, transfer bytes,
cache hits) plus query-level totals and the per-query counter delta. The
engine opens one with `collect()` around `_execute_plan`; every executor tier
(staged / fused / chunked / GRACE / host) records into the thread-local
current stats through the tiny hooks below, each a no-op costing one
thread-local read when no query is being collected.

Two collection levels keep the hot path honest:

- default (engine.execute): wall times, free row counts
  (host Arrow / numpy shapes), transfer bytes and counter deltas — NO device
  syncs are added, so overhead is a few microseconds per operator;
- detail (EXPLAIN ANALYZE): per-operator ACTUAL row counts, which on the
  device tier cost one `num_live()` sync per blocking operator, and the
  fused whole-plan program is routed to the staged executor so operator
  boundaries exist to observe (docs/observability.md#explain-analyze).

Finished stats land in a process-wide ring (`query_log()`, the backing store
of the `system.query_log` table) and, when IGLOO_QUERY_LOG=path is set, are
appended to that file as JSON lines.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

from igloo_tpu.utils import flight_recorder, tracing

_tls = threading.local()

# guards QueryStats/OpStats numeric fields: normally single-threaded, but a
# worker thread under `adopt()` (the GRACE prefetch thread) records into the
# SAME QueryStats/node as the query thread, and `x += n` is a non-atomic
# read-modify-write
_totals_lock = threading.Lock()

# ring of recent finished QueryStats, process-wide (a coordinator process
# logs every query it executed, whichever engine/executor ran it)
QUERY_LOG_SIZE = int(os.environ.get("IGLOO_QUERY_LOG_SIZE", "256"))
_log_lock = threading.Lock()
_query_log: deque = deque(maxlen=QUERY_LOG_SIZE)
_query_seq = 0


@dataclass
class OpStats:
    """One physical operator's recorded execution."""
    name: str
    wall_s: float = 0.0
    compile_s: float = 0.0
    rows_out: Optional[int] = None
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    attrs: dict = field(default_factory=dict)
    children: list = field(default_factory=list)

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


@dataclass
class QueryStats:
    """Per-query telemetry: totals + the operator tree."""
    sql: str = ""
    started_at: float = 0.0            # unix seconds
    elapsed_s: float = 0.0
    tier: str = "device"               # chunked|grace|device|sharded|...
    rows: Optional[int] = None
    compile_s: float = 0.0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    counters: dict = field(default_factory=dict)  # per-query counter delta
    root: Optional[OpStats] = None
    detail: bool = False
    qid: int = 0
    # terminal outcome: "ok" | "cancelled" | "deadline_exceeded" | "error"
    # (non-ok values come from the distributed tier's deadline/cancel paths)
    status: str = "ok"
    # serving-path fields (coordinator front door, docs/serving.md): how long
    # the query waited in the admission queue, its priority tier, and
    # whether the degradation ladder demoted it (0 = ran at its planned
    # tier)
    queue_wait_s: float = 0.0
    priority: int = 1
    demoted: int = 0
    # flight-recorder trace identity (utils/flight_recorder.py): the key
    # that joins this row with system.query_traces and the Perfetto export
    # ("" when the recorder was off)
    trace_id: str = ""
    # (fingerprint key, observed rows) pairs recorded where a row count was
    # free or already paid for (detail-mode syncs, first-sight
    # adaptive-input syncs); the engine folds them into the process-wide
    # AdaptiveStats store at query end (exec/hints.py, docs/adaptive.md)
    observations: list = field(default_factory=list)

    # --- programmatic access ------------------------------------------------

    def ops(self):
        """Iterate every operator node (pre-order)."""
        if self.root is not None:
            yield from self.root.walk()

    def find_ops(self, prefix: str) -> list:
        return [o for o in self.ops() if o.name.startswith(prefix)]

    @property
    def execute_s(self) -> float:
        """Wall time minus (first-call) compile time: the steady-state cost."""
        return max(self.elapsed_s - self.compile_s, 0.0)

    def to_record(self) -> dict:
        """Flat dict for the query log (system.query_log row / JSONL line)."""
        return {
            "qid": self.qid,
            "ts": round(self.started_at, 6),
            "sql": self.sql,
            "tier": self.tier,
            "rows": -1 if self.rows is None else int(self.rows),
            "elapsed_s": round(self.elapsed_s, 6),
            "compile_s": round(self.compile_s, 6),
            "execute_s": round(self.execute_s, 6),
            "h2d_bytes": int(self.h2d_bytes),
            "d2h_bytes": int(self.d2h_bytes),
            "operators": sum(1 for _ in self.ops()),
            "grace_partitions": int(
                self.counters.get("grace.partitions", 0)),
            "jit_misses": int(self.counters.get("jit.miss", 0)),
            "cache_hits": int(self.counters.get("cache.hit", 0) +
                              self.counters.get("result_cache.hit", 0)),
            "status": self.status,
            "queue_wait_s": round(self.queue_wait_s, 6),
            "priority": int(self.priority),
            "demoted": int(self.demoted),
            "trace_id": self.trace_id,
        }


# --- collection context -----------------------------------------------------


def current() -> Optional[QueryStats]:
    return getattr(_tls, "qstats", None)


def detail_active() -> bool:
    qs = getattr(_tls, "qstats", None)
    return qs is not None and qs.detail


@contextlib.contextmanager
def collect(sql: str = "", detail: bool = False, log: bool = True):
    """Open a QueryStats collection around a query execution. Nested collects
    are ignored (the outer query owns the tree — scalar subqueries and
    re-runs record into it)."""
    if getattr(_tls, "qstats", None) is not None:
        yield _tls.qstats
        return
    global _query_seq
    with _log_lock:
        _query_seq += 1
        qid = _query_seq
    qs = QueryStats(sql=sql, started_at=time.time(), detail=detail, qid=qid)
    # flight-recorder hookup (utils/flight_recorder.py): an ambient trace (a
    # coordinator request scope around this execution) is joined; otherwise
    # a standalone engine opens — and at the end publishes — its own, with
    # keep_roots so same-thread span consumers (CLI --timing) still work
    trace = flight_recorder.current()
    own_scope = None
    if trace is None and not flight_recorder.in_request_scope():
        # IGLOO_TRACE=0 still gets the root (its profiler event and
        # self-time counter), with nothing stitched or retained
        if flight_recorder.enabled():
            trace = flight_recorder.Trace(qid=qid, sql=sql)
        own_scope = flight_recorder.request_scope(trace, "query",
                                                  keep_roots=True)
        own_scope.__enter__()
    if trace is not None:
        qs.trace_id = trace.trace_id
    root = OpStats("Query")
    qs.root = root
    _tls.qstats = qs
    _tls.opstack = [root]
    t0 = time.perf_counter()
    try:
        with tracing.counter_delta() as delta:
            yield qs
    finally:
        qs.elapsed_s = time.perf_counter() - t0
        qs.counters = delta.values()
        # an artificial root with a single child is noise — promote the child
        if len(root.children) == 1 and not root.attrs:
            qs.root = root.children[0]
        # serving-path context (admission wait / priority / demotions) set by
        # the coordinator front door around an in-process engine execution
        sv = getattr(_tls, "serving", None)
        if sv is not None:
            qs.queue_wait_s = sv.get("queue_wait_s", 0.0)
            qs.priority = sv.get("priority", 1)
            qs.demoted = sv.get("demoted", 0)
        _tls.qstats = None
        _tls.opstack = None
        if own_scope is not None:
            own_scope.__exit__(None, None, None)
            flight_recorder.publish(trace)
        if log:
            _append_log(qs)


class _NullOp:
    """Fast no-op `op()` result when no collection is active."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_OP = _NullOp()


class _Op:
    __slots__ = ("node",)

    def __init__(self, node: OpStats):
        self.node = node

    def __enter__(self) -> OpStats:
        _tls.opstack.append(self.node)
        self.node.wall_s = time.perf_counter()
        return self.node

    def __exit__(self, *exc):
        self.node.wall_s = time.perf_counter() - self.node.wall_s
        _tls.opstack.pop()
        return False


def op(name: str, **attrs):
    """Record one operator: `with stats.op("Join(...)"): ...`. Children
    recorded inside nest under it. Returns the OpStats (or None inactive)."""
    qs = getattr(_tls, "qstats", None)
    if qs is None or getattr(_tls, "quiet", 0):
        return _NULL_OP
    node = OpStats(name, attrs=dict(attrs) if attrs else {})
    _tls.opstack[-1].children.append(node)
    return _Op(node)


def op_label(plan, limit: int = 72) -> str:
    """Operator display label for the stats tree: the plan node's name,
    truncated (node_name() embeds full expression reprs)."""
    s = plan.node_name()
    return s if len(s) <= limit else s[: limit - 3] + "..."


def plan_op(plan):
    """`op()` for a plan node — the label (a string build over expression
    reprs) is only computed when a query is actually being recorded, so
    paths with no collection open (cluster fragments) pay one tls read."""
    qs = getattr(_tls, "qstats", None)
    if qs is None or getattr(_tls, "quiet", 0):
        return _NULL_OP
    node = OpStats(op_label(plan))
    _tls.opstack[-1].children.append(node)
    return _Op(node)


@contextlib.contextmanager
def quiet():
    """Suppress op-node creation (totals still accumulate): the GRACE loop
    uses this past the first few partitions so a 1024-partition query does
    not materialize 1024 subtrees — their numbers land in the rollup."""
    _tls.quiet = getattr(_tls, "quiet", 0) + 1
    try:
        yield
    finally:
        _tls.quiet -= 1


def current_op() -> Optional[OpStats]:
    stack = getattr(_tls, "opstack", None)
    return stack[-1] if stack else None


def set_rows(n: int) -> None:
    node = current_op()
    if node is not None:
        node.rows_out = int(n)


def annotate(**attrs) -> None:
    node = current_op()
    if node is not None:
        node.attrs.update(attrs)


def bump_attr(key: str, delta: int = 1) -> None:
    """Increment an integer attr on the current op (per-op hit/miss tallies)."""
    node = current_op()
    if node is not None:
        with _totals_lock:
            node.attrs[key] = node.attrs.get(key, 0) + delta


def observe_card(key, rows: int) -> None:
    """Record one observed subtree cardinality for the adaptive feedback
    loop. Callers only invoke this where the count is already in hand (free
    host/Arrow shapes, a sync another feature paid for) — the hook itself
    must never add device syncs."""
    qs = getattr(_tls, "qstats", None)
    if qs is None:
        return
    with _totals_lock:
        qs.observations.append((key, int(rows)))


def record_compile(seconds: float) -> None:
    qs = getattr(_tls, "qstats", None)
    if qs is None:
        return
    node = current_op()
    with _totals_lock:
        qs.compile_s += seconds
        if node is not None:
            node.compile_s += seconds


def add_transfer(h2d: int = 0, d2h: int = 0) -> None:
    qs = getattr(_tls, "qstats", None)
    if qs is None:
        return
    node = current_op()
    with _totals_lock:
        qs.h2d_bytes += h2d
        qs.d2h_bytes += d2h
        if node is not None:
            node.h2d_bytes += h2d
            node.d2h_bytes += d2h


def host_nbytes(obj) -> int:
    """Total bytes of a nested structure of host arrays (the shape
    `jax.device_get` returns: lists/tuples/dicts of ndarrays + scalars)."""
    if obj is None:
        return 0
    if isinstance(obj, (list, tuple)):
        return sum(host_nbytes(o) for o in obj)
    if isinstance(obj, dict):
        return sum(host_nbytes(o) for o in obj.values())
    nb = getattr(obj, "nbytes", None)
    return int(nb) if nb is not None else 0


def record_fetch(host_objs) -> int:
    """Book one device->host fetch (process counter + current query);
    returns the byte total."""
    n = host_nbytes(host_objs)
    if n:
        tracing.counter("xfer.d2h_bytes", n)
        add_transfer(d2h=n)
    return n


def record_upload(nbytes: int) -> None:
    """Book one host->device upload (process counter + current query)."""
    if nbytes:
        tracing.counter("xfer.h2d_bytes", nbytes)
        add_transfer(h2d=nbytes)


def device_peak_hbm_bytes() -> int:
    """Peak device-memory watermark across local devices (0 when the backend
    reports no memory stats — CPU). Process-cumulative, so per-query use of
    it is an UPPER bound; the admission gate wants conservative."""
    try:
        import jax
        peaks = []
        for d in jax.local_devices():
            ms = getattr(d, "memory_stats", None)
            ms = ms() if callable(ms) else None
            if ms:
                peaks.append(ms.get("peak_bytes_in_use",
                                    ms.get("bytes_in_use", 0)))
        return int(max(peaks)) if peaks else 0
    except Exception:
        return 0


# --- serving context ---------------------------------------------------------


@contextlib.contextmanager
def serving_context(queue_wait_s: float = 0.0, priority: int = 1):
    """Attribute serving-path facts (admission wait, priority tier, ladder
    demotions via `mark_demoted`) to every query-log record the wrapped
    in-process execution produces on this thread — the coordinator's LOCAL
    fallback/demotion paths run through `engine.execute`, whose `collect()`
    has no other way to learn them."""
    prev = getattr(_tls, "serving", None)
    _tls.serving = {"queue_wait_s": float(queue_wait_s),
                    "priority": int(priority), "demoted": 0}
    try:
        yield _tls.serving
    finally:
        _tls.serving = prev


def mark_demoted() -> None:
    """Count one degradation-ladder demotion for the current serving context
    (no-op outside one)."""
    sv = getattr(_tls, "serving", None)
    if sv is not None:
        sv["demoted"] = sv.get("demoted", 0) + 1


# --- cross-thread propagation ----------------------------------------------


def capture() -> tuple:
    """Snapshot (qstats, opstack top, collectors, trace context) for a
    worker thread doing this query's work (GRACE prefetch): its transfers/
    counters land in the right query's totals and its spans in the right
    query's trace (where they visibly overlap the spawning thread's)."""
    return (getattr(_tls, "qstats", None), current_op(),
            tracing.capture_collectors(), flight_recorder.capture())


@contextlib.contextmanager
def adopt(ctx: tuple):
    qs, node, cols, tctx = ctx
    if qs is None:
        # no stats collection, but the parent thread may still hold
        # counter_delta collectors — adopt those regardless
        with flight_recorder.adopt(tctx), tracing.adopt_collectors(cols):
            yield
        return
    _tls.qstats = qs
    _tls.opstack = [node if node is not None else qs.root]
    _tls.quiet = 1  # worker threads contribute totals, not tree nodes
    try:
        with flight_recorder.adopt(tctx), tracing.adopt_collectors(cols):
            yield
    finally:
        _tls.qstats = None
        _tls.opstack = None
        _tls.quiet = 0


# --- query log --------------------------------------------------------------


def _append_log(qs: QueryStats) -> None:
    # process-wide query histograms (system.metrics / Prometheus summaries)
    tracing.histogram("query.latency_s", qs.elapsed_s)
    if qs.compile_s:
        tracing.histogram("query.compile_s", qs.compile_s)
    if qs.rows is not None:
        tracing.histogram("query.rows", qs.rows)
    if qs.h2d_bytes:
        tracing.histogram("query.h2d_bytes", qs.h2d_bytes)
    if qs.d2h_bytes:
        tracing.histogram("query.d2h_bytes", qs.d2h_bytes)
    with _log_lock:
        _query_log.append(qs)
    tracing.REGISTRY.bump_version()  # system tables snapshot on this
    path = os.environ.get("IGLOO_QUERY_LOG")
    if path:
        try:
            with open(path, "a") as f:
                f.write(json.dumps(qs.to_record(), default=str) + "\n")
        except OSError:  # export is best-effort; never fail the query
            tracing.counter("stats.query_log_write_failed")


def log_query(sql: str, elapsed_s: float, tier: str = "distributed",
              rows: Optional[int] = None, status: str = "ok",
              started_at: Optional[float] = None,
              queue_wait_s: float = 0.0, priority: int = 1,
              demoted: int = 0, trace_id: str = "") -> QueryStats:
    """Append a query-log record for a query NOT executed through
    `collect()` — the coordinator's distributed path logs every query here,
    including cancelled / deadline-exceeded / shed ones that never finished
    (their `status` column is how an operator audits what the cluster
    dropped)."""
    global _query_seq
    with _log_lock:
        _query_seq += 1
        qid = _query_seq
    qs = QueryStats(sql=sql, elapsed_s=elapsed_s, tier=tier, rows=rows,
                    status=status, qid=qid,
                    started_at=started_at if started_at is not None
                    else time.time() - elapsed_s,
                    queue_wait_s=queue_wait_s, priority=priority,
                    demoted=demoted, trace_id=trace_id)
    _append_log(qs)
    return qs


def query_log() -> list:
    """Most-recent-last list of finished QueryStats."""
    with _log_lock:
        return list(_query_log)


def clear_query_log() -> None:
    with _log_lock:
        _query_log.clear()
    tracing.REGISTRY.bump_version()


# --- rendering --------------------------------------------------------------


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"  # pragma: no cover - loop always returns


def _fmt_op(o: OpStats) -> str:
    parts = [f"{o.name}:"]
    if o.rows_out is not None:
        parts.append(f"rows={o.rows_out}")
    parts.append(f"wall={o.wall_s * 1e3:.2f}ms")
    if o.compile_s:
        parts.append(f"compile={o.compile_s * 1e3:.1f}ms "
                     f"exec={(o.wall_s - o.compile_s) * 1e3:.2f}ms")
    if o.h2d_bytes:
        parts.append(f"h2d={_fmt_bytes(o.h2d_bytes)}")
    if o.d2h_bytes:
        parts.append(f"d2h={_fmt_bytes(o.d2h_bytes)}")
    for k, v in o.attrs.items():
        parts.append(f"{k}={v}")
    return " ".join(parts)


def render_tree(qs: QueryStats) -> str:
    """EXPLAIN ANALYZE / --timing rendering of the operator tree."""
    head = (f"tier={qs.tier} elapsed={qs.elapsed_s:.4f}s "
            f"compile={qs.compile_s:.4f}s execute={qs.execute_s:.4f}s")
    if qs.rows is not None:
        head += f" rows={qs.rows}"
    if qs.h2d_bytes or qs.d2h_bytes:
        head += (f" h2d={_fmt_bytes(qs.h2d_bytes)}"
                 f" d2h={_fmt_bytes(qs.d2h_bytes)}")
    lines = [head]

    def rec(o: OpStats, indent: int):
        lines.append("  " * indent + _fmt_op(o))
        for c in o.children:
            rec(c, indent + 1)

    if qs.root is not None:
        rec(qs.root, 0)
    return "\n".join(lines)
