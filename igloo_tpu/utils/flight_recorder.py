"""Distributed query flight recorder: cross-process trace stitching and
Perfetto/Chrome-trace export.

utils/tracing.py spans are thread-local and die at the Flight boundary; this
module is what makes them a DISTRIBUTED timeline. Every span carries a
`(trace_id, span_id, parent_id)` identity anchored to wall-clock epoch time
(tracing.epoch), the trace context rides the extended JSON do_get/dispatch
tickets (cluster/coordinator.py, cluster/worker.py), workers return their
span trees beside per-fragment stats, and the coordinator stitches ONE trace
per query out of all of them. A `Trace` is the stitching surface: an
append-only, lock-guarded list of flat span dicts any thread or process can
contribute to.

Consumption paths (docs/observability.md#distributed-tracing):

- `system.query_traces`: one row per span of every ring-resident trace;
- the coordinator's `trace` Flight action: Chrome-trace JSON by trace_id/qid,
  loadable directly in Perfetto (ui.perfetto.dev) or chrome://tracing;
- ``IGLOO_TRACE_DIR``: every finished trace appended as one JSON line to
  ``<dir>/traces.jsonl``;
- ``EXPLAIN ANALYZE`` prints a ``-- trace: <id>`` pointer.

Knobs: ``IGLOO_TRACE=0`` kills the recorder (spans still exist thread-local,
nothing is stitched or retained); ``IGLOO_TRACE_RING`` sizes the ring
(default 32 traces). Neither touches the profiler bridge or the
``span_us.*`` self-time counters, nor the ``rpc`` span and the ``rpc.*``
counters of a call's two ends (cluster/rpc.py): every span made here leaves
through ``tracing.close_span`` like a thread-local one. Overhead with the recorder
ON is a few tens of microseconds per query (id generation + one flatten + a
ring append) plus ~5 us per span — scripts/trace_smoke.py holds the whole to
<2% of a 5 ms query on the CPU; PERF.md §6 has what it costs on the chip.

Cross-host caveat: spans are anchored to each process's own wall clock, so
timelines from different HOSTS carry that clock skew (same-host worker
processes share a clock). Parent/child STRUCTURE is skew-free — it comes
from explicit ids, not timestamps.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Optional

from igloo_tpu.utils import tracing

TRACE_ENV = "IGLOO_TRACE"
TRACE_DIR_ENV = "IGLOO_TRACE_DIR"
RING_ENV = "IGLOO_TRACE_RING"

_tls = threading.local()

# lock discipline (checked by igloo-lint lock-discipline): the ring is
# appended by whichever thread finishes a query and read by system-table
# scans / the trace Flight action; a Trace's span list is appended from
# handler, dispatch-pool, relay, and adopted worker threads at once
_GUARDED_BY = {"_ring_lock": ("_ring", "_pinned"), "_lock": ("_spans",)}

_ring_lock = threading.Lock()
_ring: deque = deque(maxlen=max(int(os.environ.get(RING_ENV, "32") or 32), 1))

# Watchtower retention override (docs/observability.md#watchtower): a trace
# `pin()`ed here survives ring eviction — the slow-query detector pins the
# anomalous query's trace so the evidence is still readable after another
# ring's worth of normal queries has flowed past. Bounded FIFO of LIVE
# Trace objects (straggler spans still land), capped separately from the
# ring so a burst of anomalies cannot grow memory unboundedly.
_PIN_MAX = 32
_pinned: "dict[str, Trace]" = {}


def enabled() -> bool:
    return os.environ.get(TRACE_ENV, "1") != "0"


def _proc_label() -> str:
    return f"pid:{os.getpid()}"


def _tid() -> int:
    # Chrome-trace tids are small ints; the low bits of the thread ident are
    # distinct across one process's live threads, which is all a track needs
    return threading.get_ident() & 0xFFFF


class Trace:
    """One query's cross-process span collection. Thread-safe append-only:
    the coordinator's dispatch pool, the relay generator, adopted worker
    threads, and stitched-in remote span trees all write concurrently."""

    __slots__ = ("trace_id", "qid", "sql", "deferred", "_lock", "_spans")

    def __init__(self, trace_id: Optional[str] = None, qid: str = "",
                 sql: str = ""):
        self.trace_id = str(trace_id) if trace_id else tracing.new_trace_id()
        self.qid = str(qid or "")
        self.sql = sql
        # ownership handoff: the distributed executor publishes at stream
        # end; the do_get handler publishes everything else at handler exit
        self.deferred = False
        self._lock = threading.Lock()
        self._spans: list[dict] = []

    # --- writes -------------------------------------------------------------

    def _append(self, name: str, t0: float, t1: float, span_id: str,
                parent_id: Optional[str], proc: Optional[str],
                tid: Optional[int], attrs: Optional[dict]) -> str:
        d = {"name": name, "id": span_id, "parent": parent_id,
             "proc": proc or _proc_label(),
             "tid": tid if tid is not None else _tid(),
             "t0": t0, "t1": t1}
        if attrs:
            d["args"] = attrs
        with self._lock:
            self._spans.append(d)
        return span_id

    def add_span(self, name: str, t0: float, t1: float,
                 parent_id: Optional[str] = None, proc: Optional[str] = None,
                 tid: Optional[int] = None, **attrs) -> str:
        """Record one completed span by wall-clock epoch bounds — the hook
        for durations measured outside any thread-local scope (the serving
        permit's HBM hold, the coordinator's root-result relay). No child
        is tracked for such a span: its self time is its duration."""
        tracing.close_span(name, None, t1 - t0)
        return self._append(name, t0, t1, tracing.new_span_id(), parent_id,
                            proc, tid, attrs or None)

    @contextlib.contextmanager
    def span(self, name: str, parent_id: Optional[str] = None,
             proc: Optional[str] = None, **attrs):
        """Explicit cross-thread span: yields its span_id BEFORE the body
        runs so callers can ship it as the parent of remote work (the
        coordinator's dispatch span does exactly that). It sits on no
        thread-local stack, so its self time is its duration."""
        sid = tracing.new_span_id()
        annotation = tracing.open_span(name)
        p0 = time.perf_counter()
        try:
            yield sid
        finally:
            p1 = time.perf_counter()
            tracing.close_span(name, annotation, p1 - p0)
            self._append(name, tracing.epoch(p0), tracing.epoch(p1), sid,
                         parent_id, proc, None, attrs or None)

    def add_tree(self, span: tracing.Span, parent_id: Optional[str] = None,
                 proc: Optional[str] = None,
                 tid: Optional[int] = None) -> None:
        """Flatten one thread-local tracing.Span tree into the trace,
        re-parenting its root under `parent_id`."""
        out: list[dict] = []
        if tid is None:
            tid = _tid()

        def rec(s: tracing.Span, parent: Optional[str]) -> None:
            sid = s.span_id or tracing.new_span_id()
            d = {"name": s.name, "id": sid, "parent": parent,
                 "proc": proc or _proc_label(), "tid": tid,
                 "t0": tracing.epoch(s.start),
                 "t1": tracing.epoch(s.end or time.perf_counter())}
            if s.attrs:
                d["args"] = dict(s.attrs)
            out.append(d)
            for c in s.children:
                rec(c, sid)
        rec(span, parent_id)
        with self._lock:
            self._spans.extend(out)

    def extend(self, span_dicts, proc: Optional[str] = None) -> None:
        """Stitch in span dicts a REMOTE process reported (a worker's
        `spans` list riding its fragment report). Malformed entries are
        dropped, not fatal — telemetry must never fail the query."""
        ok = []
        for d in span_dicts or ():
            if isinstance(d, dict) and "name" in d and "t0" in d:
                if proc and not d.get("proc"):
                    d["proc"] = proc
                ok.append(d)
        if ok:
            with self._lock:
                self._spans.extend(ok)

    # --- reads --------------------------------------------------------------

    def spans(self) -> list:
        with self._lock:
            return list(self._spans)

    def to_record(self) -> dict:
        sp = self.spans()
        return {"trace_id": self.trace_id, "qid": self.qid, "sql": self.sql,
                "t0": min((s["t0"] for s in sp), default=0.0),
                "t1": max((s["t1"] for s in sp), default=0.0),
                "spans": sp}


# --- thread-local activation -------------------------------------------------


def current() -> Optional[Trace]:
    """The trace the current thread's request scope is recording into."""
    return getattr(_tls, "trace", None)


def in_request_scope() -> bool:
    """Whether a request scope is open on this thread (it may record into no
    trace: `IGLOO_TRACE=0`)."""
    return getattr(_tls, "scopes", 0) > 0


def current_root() -> Optional[str]:
    """The active request scope's root span id (allocated up front so
    cross-thread spans can parent under it while the request runs)."""
    return getattr(_tls, "root_id", None)


class _RequestScope:
    """One server request's span scope: installs a FRESH thread-local span
    stack (span hygiene — a reused gRPC thread must not accumulate spans or
    interleave unrelated queries) and activates `trace` for this thread. On
    exit the scope's span roots flush into the trace under a root span whose
    id was allocated up front (yielded, and readable via `current_root()`).
    `trace=None` still resets the thread-local state — the hygiene applies
    whether or not anything is recorded — and the root still has its
    profiler event and its `span_us.<name>` (self time: the scope's duration
    minus the span roots opened inside it). A span open on the thread
    around the scope takes the root as its child (`tracing.note_child`), so
    the scope is in nobody's self time twice. Class-based: this sits on the
    per-query hot path."""

    __slots__ = ("trace", "name", "proc", "parent_id", "keep_roots",
                 "attrs", "_tok", "_prev", "_root_id", "_p0", "_annotation")

    def __init__(self, trace: Optional[Trace], name: str,
                 proc: Optional[str], parent_id: Optional[str],
                 keep_roots: bool, attrs: Optional[dict]):
        self.trace = trace
        self.name = name
        self.proc = proc
        self.parent_id = parent_id
        self.keep_roots = keep_roots
        self.attrs = attrs

    def __enter__(self) -> Optional[str]:
        self._tok = tracing.push_scope()
        self._prev = (getattr(_tls, "trace", None),
                      getattr(_tls, "root_id", None),
                      getattr(_tls, "proc", None))
        self._root_id = tracing.new_span_id() \
            if self.trace is not None else None
        _tls.trace = self.trace
        _tls.root_id = self._root_id
        _tls.proc = self.proc
        _tls.scopes = getattr(_tls, "scopes", 0) + 1
        self._annotation = tracing.open_span(self.name)
        self._p0 = time.perf_counter()
        return self._root_id

    def __exit__(self, *exc):
        p1 = time.perf_counter()
        roots = tracing.pop_scope(self._tok, keep_roots=self.keep_roots)
        _tls.trace, _tls.root_id, _tls.proc = self._prev
        _tls.scopes -= 1
        tracing.close_span(self.name, self._annotation, p1 - self._p0, roots)
        # the span around the scope (a Flight handler's) leaves the scope
        # out of its self time
        tracing.note_child(self.name, self._p0, p1, self._root_id or "")
        trace = self.trace
        if trace is not None:
            tid = _tid()
            trace._append(self.name, tracing.epoch(self._p0),
                          tracing.epoch(p1), self._root_id,
                          self.parent_id, self.proc, tid, self.attrs)
            for s in roots:
                trace.add_tree(s, parent_id=self._root_id, proc=self.proc,
                               tid=tid)
        return False


def request_scope(trace: Optional[Trace], name: str,
                  proc: Optional[str] = None,
                  parent_id: Optional[str] = None,
                  keep_roots: bool = False, **attrs) -> _RequestScope:
    return _RequestScope(trace, name, proc, parent_id, keep_roots,
                         attrs or None)


def capture() -> tuple:
    """Snapshot (trace, parent span id, proc label) for a worker thread
    doing this request's work (the GRACE prefetch thread): its spans then
    land in the same trace, visually overlapping the spawning thread's."""
    return (getattr(_tls, "trace", None),
            tracing.current_span_id() or getattr(_tls, "root_id", None),
            getattr(_tls, "proc", None))


@contextlib.contextmanager
def adopt(ctx: tuple):
    """Run a block on a worker thread with a parent thread's trace adopted:
    fresh span scope (hygiene for pooled threads), spans flushed into the
    parent's trace under the captured parent span."""
    trace, parent, proc = ctx
    tok = tracing.push_scope()
    prev = (getattr(_tls, "trace", None), getattr(_tls, "root_id", None),
            getattr(_tls, "proc", None))
    _tls.trace = trace
    _tls.root_id = parent
    _tls.proc = proc
    try:
        yield
    finally:
        roots = tracing.pop_scope(tok)
        _tls.trace, _tls.root_id, _tls.proc = prev
        if trace is not None:
            for s in roots:
                trace.add_tree(s, parent_id=parent, proc=proc)


# --- the trace ring + exports ------------------------------------------------


def publish(trace: Optional[Trace]) -> Optional[dict]:
    """Retire a finished query's trace: append it to the process ring (the
    system.query_traces backing store, snapshot-tokened by the metrics
    registry) and, when IGLOO_TRACE_DIR is set, write its record to
    `<dir>/traces.jsonl`. The ring holds the LIVE Trace — a straggler span
    recorded after publish (the serving permit's hold span outlives the
    stream that published) still lands in ring-backed reads; the JSONL line
    is the publish-time snapshot. Best-effort by the telemetry contract;
    returns the exported record when IGLOO_TRACE_DIR is set, else None (the
    record is built lazily — this runs once per query)."""
    if trace is None:
        return None
    with _ring_lock:
        _ring.append(trace)
    # counter() bumps the registry version too — that is the system-table
    # snapshot invalidation, no separate bump needed
    tracing.counter("trace.published")
    d = os.environ.get(TRACE_DIR_ENV)
    if not d:
        return None
    rec = trace.to_record()
    try:
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "traces.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        tracing.counter("trace.export_failed")
    return rec


def pin(trace_id: Optional[str] = None, qid: Optional[str] = None) -> bool:
    """Force retention of a ring-resident trace beyond ring eviction (the
    watchtower's slow-query escalation, utils/watch.py). Looks the trace up
    by trace_id or qid in the ring (and among already-pinned traces — a
    re-pin refreshes FIFO position); returns False when no such trace is
    resident, True when pinned."""
    if trace_id is None and qid is None:
        return False
    with _ring_lock:
        target = None
        for t in reversed(_ring):
            if ((trace_id is not None and t.trace_id == trace_id)
                    or (qid is not None and t.qid == str(qid))):
                target = t
                break
        if target is None:
            for t in reversed(list(_pinned.values())):
                if ((trace_id is not None and t.trace_id == trace_id)
                        or (qid is not None and t.qid == str(qid))):
                    target = t
                    break
        if target is None:
            return False
        _pinned.pop(target.trace_id, None)
        _pinned[target.trace_id] = target
        while len(_pinned) > _PIN_MAX:
            _pinned.pop(next(iter(_pinned)))
    tracing.counter("trace.pinned")
    return True


def _resident_locked() -> list:
    """Pinned-but-evicted traces first (oldest), then the ring (most recent
    last); a trace both pinned and ring-resident appears once."""
    ring_ids = {t.trace_id for t in _ring}
    out = [t for t in _pinned.values() if t.trace_id not in ring_ids]
    out.extend(_ring)
    return out


def records() -> list:
    """Resident trace records (ring + pinned), most recent last
    (snapshotted at read, so post-publish straggler spans are included)."""
    with _ring_lock:
        traces = _resident_locked()
    return [t.to_record() for t in traces]


def get_record(trace_id: Optional[str] = None,
               qid: Optional[str] = None) -> Optional[dict]:
    """Look a trace up by trace_id or qid; neither = the most recent."""
    with _ring_lock:
        traces = _resident_locked()
    if not traces:
        return None
    if trace_id is None and qid is None:
        return traces[-1].to_record()
    for t in reversed(traces):
        if trace_id is not None and t.trace_id == trace_id:
            return t.to_record()
        if qid is not None and t.qid == str(qid):
            return t.to_record()
    return None


def clear() -> None:
    with _ring_lock:
        _ring.clear()
        _pinned.clear()
    tracing.REGISTRY.bump_version()


# --- Chrome-trace / Perfetto export ------------------------------------------


def to_chrome_trace(rec: dict) -> dict:
    """A trace record as Chrome-trace JSON (the `traceEvents` object form),
    loadable in Perfetto (ui.perfetto.dev) and chrome://tracing. Each span
    becomes one complete ("X") event; each distinct `proc` label becomes a
    pid with a process_name metadata event; timestamps are microseconds
    relative to the trace's first span."""
    base = rec.get("t0") or 0.0
    events: list = []
    pids: dict = {}
    for s in rec.get("spans", ()):
        proc = s.get("proc") or "proc"
        pid = pids.get(proc)
        if pid is None:
            pid = pids[proc] = len(pids) + 1
            events.append({"ph": "M", "name": "process_name", "pid": pid,
                           "tid": 0, "args": {"name": proc}})
        args = dict(s.get("args") or {})
        args["span"] = s.get("id")
        if s.get("parent"):
            args["parent"] = s["parent"]
        events.append({"name": s.get("name", "?"), "ph": "X", "pid": pid,
                       "tid": int(s.get("tid") or 0),
                       "ts": round((s["t0"] - base) * 1e6, 3),
                       "dur": round(max(s.get("t1", s["t0"]) - s["t0"], 0.0)
                                    * 1e6, 3),
                       "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"trace_id": rec.get("trace_id", ""),
                          "qid": rec.get("qid", ""),
                          "sql": rec.get("sql", "")}}
