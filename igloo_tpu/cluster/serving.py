"""Multi-tenant serving front door: admission control, HBM-aware concurrent
scheduling, and load shedding for the coordinator (docs/serving.md).

The Flight SQL endpoint used to run every query on its own gRPC thread with
no bound at all: under concurrent traffic the cluster either serialized on
the device or planned past HBM and crashed — and PR7's fault-tolerance layer
can only clean up after the crash. This module is the Presto-style front
door (PAPERS.md: "Accelerating Presto with GPUs") that turns overload into
bounded latency and *retryable* rejections instead of failures:

- **bounded admission queue**: one FIFO per priority tier, total depth
  bounded; past the bound a query is SHED with a retryable "server busy"
  Flight error carrying a retry-after hint, which the client-side RpcPolicy
  backoff absorbs (`IGLOO_BUSY` marker, cluster/client.py);
- **weighted fair dequeue** across priority tiers (0 = interactive, 1 =
  normal, 2 = batch by default): each admission picks the non-empty tier
  with the lowest served/weight ratio, so a saturating low-priority flood
  cannot starve interactive queries and vice versa;
- **per-session in-flight caps**: one chatty dashboard cannot occupy the
  whole queue (the session id rides the extended do_get ticket);
- **HBM-aware concurrency**: each query carries a predicted device-memory
  footprint — the AdaptiveStats `peak_hbm_bytes` observation for its plan
  fingerprint when one exists, a conservative bytes-of-inputs estimate on
  first sight — and admission reserves it against a cluster HBM budget, so
  concurrent queries never plan past memory. A query predicted to exceed
  the WHOLE budget is admitted alone and pre-flagged for the degradation
  ladder (the coordinator runs it through the chunked/GRACE budget tiers).

Knobs — `[serving]` config section, each overridable by the matching
IGLOO_SERVING_* env var (env wins, like every [rpc] knob):

- ``IGLOO_SERVING_QUEUE`` / ``queue_depth``: total queued-query bound
  (default 64). **0 is the kill switch**: the admission layer disappears
  and queries serialize one at a time — the pre-serving behavior, for A/B.
- ``IGLOO_SERVING_CONCURRENCY`` / ``max_concurrency``: queries allowed to
  execute concurrently (default 4).
- ``IGLOO_SERVING_SESSION_INFLIGHT`` / ``session_inflight``: per-session
  queued+running cap (default 16).
- ``IGLOO_SERVING_HBM_BUDGET`` / ``hbm_budget_bytes``: cluster HBM budget
  in bytes the footprint gate reserves against (default 0 = gate off —
  CPU/dev hosts report no device memory).
- ``IGLOO_SERVING_WEIGHTS`` / ``weights``: comma-separated per-tier
  dequeue weights, highest priority first (default ``4,2,1``; the list
  length defines how many tiers exist).

Fault-injection points (cluster/faults.py): ``serving.admit`` fires on
every submission (an injected error is counted as a shed — the chaos smoke
drives client-side retry through it) and ``serving.dequeue`` on every
admission grant.
"""
from __future__ import annotations

import os
import threading
import time
from collections import Counter, deque
from typing import Optional

from igloo_tpu.cluster import faults
from igloo_tpu.utils import flight_recorder, tracing

# lock discipline (checked by igloo-lint lock-discipline): submissions run on
# Flight RPC threads and releases on whichever thread finishes the query, so
# all queue/slot/reservation state is guarded by the controller's condition
# (a Condition IS a lock as a context manager)
_GUARDED_BY = {"_cond": ("_queues", "_served", "_running", "_reserved",
                         "_running_demote", "_sessions")}

QUEUE_ENV = "IGLOO_SERVING_QUEUE"
CONCURRENCY_ENV = "IGLOO_SERVING_CONCURRENCY"
SESSION_ENV = "IGLOO_SERVING_SESSION_INFLIGHT"
HBM_BUDGET_ENV = "IGLOO_SERVING_HBM_BUDGET"
WEIGHTS_ENV = "IGLOO_SERVING_WEIGHTS"

DEFAULT_QUEUE_DEPTH = 64
DEFAULT_CONCURRENCY = 4
DEFAULT_SESSION_INFLIGHT = 16
DEFAULT_WEIGHTS = (4, 2, 1)

#: marker the shed error carries so clients can tell "server busy, retry
#: after the hint" from other unavailability (cluster/client.py parses it)
BUSY_MARKER = "IGLOO_BUSY"


class ServerBusy(Exception):
    """Load shed: the admission queue (or a per-session cap) is full. Maps
    to a RETRYABLE FlightUnavailableError carrying a retry-after hint, so
    the client-side RpcPolicy backoff absorbs it instead of failing."""

    def __init__(self, reason: str, retry_after_s: float):
        self.retry_after_s = round(retry_after_s, 3)
        super().__init__(
            f"{BUSY_MARKER} server busy ({reason}); "
            f"retry_after_s={self.retry_after_s}")

    def as_flight_error(self):
        import pyarrow.flight as flight
        return flight.FlightUnavailableError(str(self))


def parse_retry_after(msg: str) -> Optional[float]:
    """The retry-after hint out of a shed error's message, or None."""
    marker = "retry_after_s="
    if BUSY_MARKER not in msg or marker not in msg:
        return None
    try:
        tail = msg.split(marker, 1)[1]
        num = ""
        for ch in tail:
            if ch.isdigit() or ch == ".":
                num += ch
            else:
                break
        return float(num)
    except ValueError:
        return None


def _env_int(name: str, fallback: Optional[int], default: int) -> int:
    v = os.environ.get(name)
    if v is not None and v != "":
        return int(v)
    return fallback if fallback is not None else default


def _env_weights(fallback) -> tuple:
    v = os.environ.get(WEIGHTS_ENV)
    if v:
        ws = tuple(max(1, int(x)) for x in v.split(",") if x.strip())
        if ws:
            return ws
    if fallback:
        return tuple(max(1, int(x)) for x in fallback)
    return DEFAULT_WEIGHTS


class Permit:
    """One admitted (or bypassed) query's hold on the serving controller.
    `release()` is idempotent — the streaming path releases from a finally
    AND a weakref finalizer."""

    __slots__ = ("_controller", "wait_s", "priority", "session", "demote",
                 "reserve_bytes", "_mode", "_released", "_trace_ctx", "_t0")

    def __init__(self, controller, priority: int, session: str,
                 demote: bool = False, reserve_bytes: int = 0,
                 wait_s: float = 0.0, mode: str = "admitted"):
        self._controller = controller
        self.priority = priority
        self.session = session
        self.demote = demote                # run via the degradation ladder
        self.reserve_bytes = reserve_bytes  # HBM bytes reserved while running
        self.wait_s = wait_s
        self._mode = mode                   # admitted | serial | bypass
        self._released = False
        # flight-recorder hold span: the permit is granted on the request
        # thread (trace context capturable) but released by whichever thread
        # finishes the stream — so the hold is recorded AT release, into the
        # trace captured here (docs/observability.md#distributed-tracing)
        self._trace_ctx = flight_recorder.capture() \
            if mode == "admitted" else (None, None, None)
        self._t0 = time.time()

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        trace, _parent, proc = self._trace_ctx
        if trace is not None:
            # concurrency-slot + HBM-reservation hold: how long this query
            # occupied its admission (the dark time between "admitted" and
            # "stream finished" that queue-wait alone never showed).
            # Top-level: the hold outlives the request scope's root span
            # (it releases when the result STREAM drains), so nesting it
            # under the root would break containment
            trace.add_span("serving.hbm_hold", self._t0, time.time(),
                           proc=proc,
                           reserve_bytes=self.reserve_bytes,
                           priority=self.priority)
        if self._mode == "admitted":
            self._controller._release(self)
        elif self._mode == "serial":
            self._controller._serial_lock.release()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


class _Waiter:
    __slots__ = ("priority", "session", "reserve_bytes", "demote", "admitted",
                 "abandoned")

    def __init__(self, priority: int, session: str, reserve_bytes: int,
                 demote: bool):
        self.priority = priority
        self.session = session
        self.reserve_bytes = reserve_bytes
        self.demote = demote
        self.admitted = False
        self.abandoned = False


class AdmissionController:
    """The coordinator's admission queue + HBM-aware concurrency gate.

    Explicit constructor arguments override config; the matching
    IGLOO_SERVING_* env var overrides both (env wins, [rpc]-style)."""

    def __init__(self, queue_depth: Optional[int] = None,
                 max_concurrency: Optional[int] = None,
                 session_inflight: Optional[int] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 weights=None):
        self.queue_depth = _env_int(QUEUE_ENV, queue_depth,
                                    DEFAULT_QUEUE_DEPTH)
        self.max_concurrency = max(
            1, _env_int(CONCURRENCY_ENV, max_concurrency,
                        DEFAULT_CONCURRENCY))
        self.session_inflight = max(
            1, _env_int(SESSION_ENV, session_inflight,
                        DEFAULT_SESSION_INFLIGHT))
        self.hbm_budget_bytes = max(
            0, _env_int(HBM_BUDGET_ENV, hbm_budget_bytes, 0))
        self.weights = _env_weights(weights)
        self._cond = threading.Condition()
        self._queues: dict[int, deque] = {
            p: deque() for p in range(len(self.weights))}
        self._served = [0] * len(self.weights)
        self._running = 0
        self._reserved = 0          # HBM bytes reserved by running queries
        self._running_demote = 0    # running over-budget (isolated) queries
        self._sessions: Counter = Counter()
        # kill-switch mode: one query at a time, the pre-serving behavior
        self._serial_lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return self.queue_depth > 0

    # --- submission --------------------------------------------------------

    def submit(self, priority: int = 1, session: str = "",
               predicted_hbm_bytes: int = 0,
               deadline: Optional[float] = None) -> Permit:
        """Block until the query may run; returns its Permit. Sheds with
        ServerBusy when the queue or the session's in-flight cap is full.
        An already-expired deadline bypasses the queue entirely — the
        executor's own deadline accounting must fire, not a shed."""
        try:
            faults.inject("serving.admit")
        except Exception:
            tracing.counter("serving.shed")
            raise
        if not self.enabled:
            # serialized single-query mode (A/B kill switch); a deadline
            # spent while waiting for the one slot surfaces through the
            # executor's own accounting, never as a serving error
            if deadline is not None:
                rem = deadline - time.time()
                if rem <= 0 or not self._serial_lock.acquire(timeout=rem):
                    return Permit(self, priority, session, mode="bypass")
            else:
                self._serial_lock.acquire()
            return Permit(self, priority, session, mode="serial")
        if deadline is not None and time.time() >= deadline:
            return Permit(self, priority, session, mode="bypass")
        priority = min(max(int(priority), 0), len(self.weights) - 1)
        demote = bool(self.hbm_budget_bytes and
                      predicted_hbm_bytes > self.hbm_budget_bytes)
        reserve = min(int(predicted_hbm_bytes), self.hbm_budget_bytes) \
            if self.hbm_budget_bytes else 0
        w = _Waiter(priority, session, reserve, demote)
        t0 = time.perf_counter()
        # timeline: the admission wait is a first-class span — a query slow
        # because it QUEUED (vs executed slowly) is visibly different
        with tracing.span("serving.queue", priority=priority), self._cond:
            if self._sessions[session] >= self.session_inflight:
                tracing.counter("serving.shed")
                tracing.counter("serving.shed_session")
                raise ServerBusy(f"session {session or 'anon'!r} at its "
                                 f"{self.session_inflight}-query in-flight "
                                 "cap", self._retry_after_locked())
            if sum(len(q) for q in self._queues.values()) >= self.queue_depth:
                tracing.counter("serving.shed")
                raise ServerBusy(
                    f"admission queue full ({self.queue_depth})",
                    self._retry_after_locked())
            self._sessions[session] += 1
            self._queues[priority].append(w)
            self._schedule_locked()
            while not w.admitted:
                rem = None if deadline is None else deadline - time.time()
                if rem is not None and rem <= 0:
                    # queue wait ate the budget: hand back a bypass permit so
                    # execution surfaces query.deadline_exceeded through the
                    # normal accounting path instead of a serving error
                    w.abandoned = True
                    self._queues[priority].remove(w)
                    self._sessions[session] -= 1
                    if not self._sessions[session]:
                        del self._sessions[session]
                    self._gauges_locked()
                    return Permit(self, priority, session, mode="bypass",
                                  wait_s=time.perf_counter() - t0)
                self._cond.wait(timeout=rem if rem is not None else 1.0)
        wait = time.perf_counter() - t0
        permit = Permit(self, priority, session, demote=demote,
                        reserve_bytes=reserve, wait_s=wait)
        try:
            faults.inject("serving.dequeue")
        except Exception:
            permit.release()
            tracing.counter("serving.shed")
            raise
        tracing.counter("serving.admitted")
        tracing.histogram("serving.queue_wait_s", wait)
        return permit

    def _release(self, permit: Permit) -> None:
        with self._cond:
            self._running -= 1
            self._reserved -= permit.reserve_bytes
            if permit.demote:
                self._running_demote -= 1
            self._sessions[permit.session] -= 1
            if not self._sessions[permit.session]:
                del self._sessions[permit.session]
            self._schedule_locked()

    # --- scheduling (caller-locked) ----------------------------------------

    def _retry_after_locked(self) -> float:
        """Back-pressure hint: scale with queue pressure, bounded so a
        retrying client polls a draining queue promptly."""
        backlog = sum(len(q) for q in self._queues.values()) + self._running
        return min(0.05 * (1 + backlog), 2.0)

    def _schedule_locked(self) -> None:
        """Admit queued queries while slots + the HBM budget allow; weighted
        fair across tiers, FIFO within one."""
        admitted = False
        while self._running < self.max_concurrency:
            w = self._pick_locked()
            if w is None:
                break
            self._queues[w.priority].popleft()
            self._served[w.priority] += 1
            self._running += 1
            self._reserved += w.reserve_bytes
            if w.demote:
                self._running_demote += 1
            w.admitted = True
            admitted = True
        self._gauges_locked()
        if admitted:
            self._cond.notify_all()

    def _pick_locked(self) -> Optional[_Waiter]:
        """Next admissible waiter: the FIFO head of the tier with the
        lowest served/weight ratio (the weighted-fair rule — ties break
        toward higher priority). Heads only, and ONLY the fairness
        winner's: a winning head that doesn't fit the HBM budget is a
        BARRIER — nothing else admits until running queries drain enough
        for it (running queries always finish or deadline out, so the
        barrier is bounded). Skipping it for other tiers — or for later
        entries in its own tier — would starve a big query forever under
        sustained small-query traffic; when nothing is running, anything
        fits (a single over-budget query runs alone — pre-flagged
        `demote`)."""
        order = sorted((p for p in self._queues if self._queues[p]),
                       key=lambda p: (self._served[p] / self.weights[p], p))
        if not order:
            return None
        w = self._queues[order[0]][0]
        return w if self._fits_locked(w) else None

    def _fits_locked(self, w: _Waiter) -> bool:
        if self._running == 0:
            return True
        if w.demote or self._running_demote:
            # over-budget queries run ALONE: neither beside others (their
            # reservation is the whole budget in spirit) nor with anything
            # admitted beside them — including 0-reserve unsized plans
            return False
        if not self.hbm_budget_bytes:
            return True
        return self._reserved + w.reserve_bytes <= self.hbm_budget_bytes

    def _gauges_locked(self) -> None:
        tracing.gauge("serving.running", self._running)
        tracing.gauge("serving.hbm_reserved_bytes", self._reserved)
        total = 0
        for p, q in self._queues.items():
            total += len(q)
            tracing.gauge(f"serving.queued.p{p}", len(q))
        tracing.gauge("serving.queued", total)

    # --- introspection -----------------------------------------------------

    def snapshot(self) -> dict:
        """Queue/slot state for the coordinator's serving_status action,
        shaped by the registry (cluster/protocol.py SERVING_STATUS)."""
        from igloo_tpu.cluster import protocol
        with self._cond:
            return protocol.SERVING_STATUS.build(
                enabled=self.enabled,
                queue_depth=self.queue_depth,
                max_concurrency=self.max_concurrency,
                session_inflight=self.session_inflight,
                hbm_budget_bytes=self.hbm_budget_bytes,
                weights=list(self.weights),
                running=self._running,
                hbm_reserved_bytes=self._reserved,
                queued={str(p): len(q) for p, q in self._queues.items()},
                sessions=dict(self._sessions),
            )


# --- footprint prediction -----------------------------------------------------


def predict_hbm_bytes(plan) -> int:
    """Predicted device-memory footprint of a bound plan for the admission
    gate: the AdaptiveStats `peak_hbm_bytes` observation for the plan's
    structural fingerprint when one exists (a previous run of the same
    shape MEASURED its watermark), else a conservative first-sight estimate
    — what each scan's lanes will hold on the device (the columns it
    reads, `chunked.estimated_lane_bytes`: the price the routing ladder
    uses), doubled for join/sort intermediates. Over-estimation costs
    concurrency; under-estimation is what the degradation ladder exists to
    absorb (docs/serving.md)."""
    from igloo_tpu.exec import hints
    if hints.adaptive_enabled():
        fp = hints.plan_fp(plan)
        if fp is not None:
            rec = hints.adaptive_store().observed(fp)
            if rec and rec.get("peak_hbm_bytes"):
                return int(rec["peak_hbm_bytes"])
    from igloo_tpu.exec.chunked import priced_scans
    return 2 * priced_scans(plan)
