"""Tracing / timing spans + the process metrics registry.

The reference only has `tracing` calls in its cache crate with no subscriber
ever installed (SURVEY.md §5.1); here the layer is real and has three parts:

- spans: nested timers recorded into a thread-local trace that callers (CLI
  --timing, bench harness) can read. `roots()` is bounded (ROOTS_MAX) so
  long-lived processes — the coordinator in particular — don't leak spans.
- MetricsRegistry: process-wide counters AND histograms (query latency,
  compile time, transfer bytes, rows). Counters stay CUMULATIVE; per-query
  numbers come from `counter_delta()`, a thread-isolated snapshot-diff
  context manager, so concurrent queries can never pollute each other's
  deltas. `prometheus_text()` renders the registry for the cluster's
  `metrics` Flight action.
- one clock: every span, however it is made, enters `open_span` and leaves
  through `close_span` — a `jax.profiler.TraceAnnotation` named
  `igloo:<name>` for its lifetime (a no-op until a profiler session runs;
  then the span lands on `/host:CPU`, on the profiler's clock, beside the
  device's ops) and its SELF time added to the counter `span_us.<name>`.

Every counter/histogram name used in the codebase is cataloged in
docs/observability.md; igloo-lint's metric-names checker (`python -m
igloo_tpu.lint`) fails the verify flow when the two drift.
"""
from __future__ import annotations

import contextlib
import itertools
import logging
import re
import threading
import time
import uuid
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import Optional

# the package's __init__ imports jax before anything of igloo_tpu loads
from jax.profiler import TraceAnnotation as _TraceAnnotation

log = logging.getLogger("igloo_tpu")

_tls = threading.local()

# wall-clock anchor for spans: spans time with perf_counter (cheap, monotonic)
# and `epoch()` maps those instants onto unix time so spans from DIFFERENT
# processes line up on one timeline (utils/flight_recorder.py). Computed once
# at import — NTP drift over a process lifetime is noise at span granularity.
_EPOCH_OFFSET = time.time() - time.perf_counter()

# span identity: ids must be unique ACROSS processes (a stitched trace mixes
# coordinator and worker spans), so a per-process random prefix + a cheap
# atomic counter (itertools.count.__next__ is C-level thread-safe) — ~100x
# cheaper than a uuid4 per span; trace ids use the same scheme (one is
# minted per query, on the hot serving path)
_SPAN_PREFIX = uuid.uuid4().hex[:8]
_span_ids = itertools.count(1)
_trace_ids = itertools.count(1)


def new_span_id() -> str:
    return f"{_SPAN_PREFIX}-{next(_span_ids):x}"


def new_trace_id() -> str:
    return f"{_SPAN_PREFIX}{next(_trace_ids):08x}"


def epoch(perf_t: float) -> float:
    """Map a `time.perf_counter()` instant onto unix epoch seconds."""
    return perf_t + _EPOCH_OFFSET

# spans kept per thread: enough for tooling that reads a few recent queries,
# bounded so a server thread answering queries for days cannot grow without
# limit (the coordinator used to leak its whole query history here)
ROOTS_MAX = 64

# lock discipline (checked by igloo-lint lock-discipline): the registry maps
# are hit from every thread; a CounterDelta's backing Counter is shared with
# adopted worker threads (the GRACE prefetch thread), so all `_data` access
# holds the module-wide _delta_lock
_GUARDED_BY = {"_lock": ("_counters", "_hists", "_gauges", "_version"),
               "_delta_lock": ("_data",)}


@dataclass
class HistogramData:
    """Streaming summary of one histogram: count/sum/min/max (no buckets —
    the consumers are per-query deltas and Prometheus summaries, neither of
    which needs quantiles badly enough to pay per-observation bucketing)."""
    count: int = 0
    sum: float = 0.0
    min: float = float("inf")
    max: float = float("-inf")

    def observe(self, value: float) -> None:
        self.count += 1
        self.sum += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    def as_dict(self) -> dict:
        if not self.count:
            return {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0}
        return {"count": self.count, "sum": self.sum,
                "min": self.min, "max": self.max}


class MetricsRegistry:
    """Thread-safe process metrics: monotonic counters + summary histograms.

    `version()` is a mutation counter — the system.metrics table provider
    uses it as its snapshot token, so the engine's caches invalidate exactly
    when telemetry changed."""

    def __init__(self):
        self._counters: Counter = Counter()
        self._hists: dict[str, HistogramData] = {}
        self._gauges: dict[str, float] = {}
        self._lock = threading.Lock()
        self._version = 0

    def counter(self, name: str, delta: int = 1) -> None:
        with self._lock:
            self._counters[name] += delta
            self._version += 1

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = HistogramData()
            h.observe(value)
            self._version += 1

    def gauge(self, name: str, value: float) -> None:
        """Set a last-value-wins gauge (queue depth, busy slots, reserved
        bytes — instantaneous state, unlike the monotonic counters)."""
        with self._lock:
            self._gauges[name] = float(value)
            self._version += 1

    def gauge_add(self, name: str, delta: float) -> float:
        """Atomically adjust a gauge by `delta`; returns the new value (the
        acquire/release call sites would otherwise read-modify-write race)."""
        with self._lock:
            v = self._gauges.get(name, 0.0) + delta
            self._gauges[name] = v
            self._version += 1
            return v

    def counters(self) -> dict:
        with self._lock:
            return dict(self._counters)

    def histograms(self) -> dict:
        with self._lock:
            return {k: h.as_dict() for k, h in self._hists.items()}

    def gauges(self) -> dict:
        with self._lock:
            return dict(self._gauges)

    def version(self) -> int:
        with self._lock:
            return self._version

    def bump_version(self) -> None:
        """External telemetry sources (the query log ring) share the
        registry's snapshot token by bumping it on their own mutations."""
        with self._lock:
            self._version += 1

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._hists.clear()
            self._gauges.clear()
            self._version += 1


REGISTRY = MetricsRegistry()


def _sanitize(name: str) -> str:
    return re.sub(r"[^a-zA-Z0-9_]", "_", name)


def prometheus_text(prefix: str = "igloo", extra_lines: Optional[list] = None
                    ) -> str:
    """Render the registry in the Prometheus text exposition format —
    conformant enough for a real scraper to ingest without a shim: every
    metric family gets `# HELP` and `# TYPE` lines, counters become
    `<prefix>_<name>_total`, histograms a summary family (its `_count` and
    `_sum` series). Min/max have no standard slot in a summary, so they are
    exposed as their OWN `_min`/`_max` gauge families rather than riding
    untyped under the summary name. `extra_lines` (already formatted,
    HELP/TYPE included where the producer wants them) are appended — the
    coordinator adds its per-worker fragment aggregates and the cluster
    journal's `igloo_events_total{kind=...}` there."""
    lines: list[str] = []
    for name, value in sorted(REGISTRY.counters().items()):
        m = f"{prefix}_{_sanitize(name)}_total"
        lines.append(f"# HELP {m} Cumulative count of {name} "
                     "(docs/observability.md#metrics-catalog).")
        lines.append(f"# TYPE {m} counter")
        lines.append(f"{m} {value}")
    for name, h in sorted(REGISTRY.histograms().items()):
        m = f"{prefix}_{_sanitize(name)}"
        lines.append(f"# HELP {m} Summary of {name} observations "
                     "(docs/observability.md#metrics-catalog).")
        lines.append(f"# TYPE {m} summary")
        lines.append(f"{m}_count {h['count']}")
        lines.append(f"{m}_sum {h['sum']}")
        for bound in ("min", "max"):
            b = f"{m}_{bound}"
            lines.append(f"# HELP {b} All-time {bound} of {name}.")
            lines.append(f"# TYPE {b} gauge")
            lines.append(f"{b} {h[bound]}")
    for name, v in sorted(REGISTRY.gauges().items()):
        m = f"{prefix}_{_sanitize(name)}"
        lines.append(f"# HELP {m} Instantaneous value of {name} "
                     "(docs/observability.md#metrics-catalog).")
        lines.append(f"# TYPE {m} gauge")
        lines.append(f"{m} {v}")
    if extra_lines:
        lines.extend(extra_lines)
    return "\n".join(lines) + "\n"


# --- counters (module-level API, backed by REGISTRY) ------------------------


# guards collector Counters: a collector is thread-local by default, but
# `adopt_collectors` shares it with a worker thread (the GRACE prefetch
# thread), and `c[name] += d` is a non-atomic read-modify-write
_delta_lock = threading.Lock()


def counter(name: str, delta: int = 1) -> None:
    """Bump a process-wide counter (thread-safe). Any `counter_delta()`
    collectors active on the CURRENT thread accumulate the same bump, which
    is what keeps per-query deltas isolated across concurrent queries."""
    REGISTRY.counter(name, delta)
    cols = getattr(_tls, "collectors", None)
    if cols:
        with _delta_lock:
            for c in cols:
                c[name] += delta


def histogram(name: str, value: float) -> None:
    """Record one observation into a process-wide histogram."""
    REGISTRY.observe(name, value)


def gauge(name: str, value: float) -> None:
    """Set a process-wide gauge to an instantaneous value."""
    REGISTRY.gauge(name, value)


def gauge_add(name: str, delta: float) -> float:
    """Atomically adjust a process-wide gauge; returns the new value."""
    return REGISTRY.gauge_add(name, delta)


def counters() -> dict:
    return REGISTRY.counters()


def histograms() -> dict:
    return REGISTRY.histograms()


def gauges() -> dict:
    return REGISTRY.gauges()


def reset_counters() -> None:
    REGISTRY.reset()


class CounterDelta:
    """Live view of the counter bumps made on this thread (plus any adopted
    threads) since the enclosing `counter_delta()` opened. Readable both
    inside and after the `with` block."""

    def __init__(self, data: Counter):
        self._data = data

    def get(self, name: str, default: int = 0) -> int:
        with _delta_lock:
            return self._data.get(name, default)

    def values(self) -> dict:
        with _delta_lock:
            return {k: v for k, v in self._data.items() if v}

    def __getitem__(self, name: str) -> int:
        # same lock as get()/values(): the backing Counter may be mid-update
        # on an adopted worker thread (`c[name] += d` is not atomic)
        with _delta_lock:
            return self._data[name]

    def __contains__(self, name: str) -> bool:
        with _delta_lock:
            return name in self._data


@contextlib.contextmanager
def counter_delta():
    """Per-query counter deltas as a first-class API.

    Yields a CounterDelta that accumulates every `counter()` bump made on the
    current thread while the block is open — NOT a snapshot-diff of the
    process-wide totals, so two threads each inside their own
    `counter_delta()` observe only their own increments. Worker threads an
    operation fans out to (the GRACE prefetch thread) join via
    `adopt_collectors(capture_collectors())`.
    """
    c: Counter = Counter()
    cols = getattr(_tls, "collectors", None)
    if cols is None:
        cols = _tls.collectors = []
    cols.append(c)
    try:
        yield CounterDelta(c)
    finally:
        _remove_by_identity(cols, c)


def _remove_by_identity(cols: list, c) -> None:
    # Counter compares by CONTENT — list.remove would pop a different,
    # equal-content collector (two empty deltas are ==); remove by identity
    for i, x in enumerate(cols):
        if x is c:
            del cols[i]
            return


def capture_collectors() -> tuple:
    """Snapshot of the current thread's active delta collectors, for handing
    to a worker thread that does work on this query's behalf."""
    return tuple(getattr(_tls, "collectors", ()))


@contextlib.contextmanager
def adopt_collectors(cols: tuple):
    """Run a block on a worker thread with a parent thread's collectors
    installed, so its counter bumps land in the parent's deltas too."""
    own = getattr(_tls, "collectors", None)
    if own is None:
        own = _tls.collectors = []
    own.extend(cols)
    try:
        yield
    finally:
        for c in cols:
            _remove_by_identity(own, c)


@dataclass
class Span:
    name: str
    start: float
    end: float = 0.0
    children: list = field(default_factory=list)
    # flight-recorder identity (utils/flight_recorder.py): stable across the
    # wire so a worker's span tree re-parents under the coordinator's
    # dispatch span. `attrs` land in the Perfetto event's args.
    span_id: str = ""
    parent_id: Optional[str] = None
    attrs: Optional[dict] = None

    @property
    def elapsed_s(self) -> float:
        return (self.end or time.perf_counter()) - self.start

    def tree(self, indent: int = 0) -> str:
        lines = [f"{'  ' * indent}{self.name}: {self.elapsed_s * 1e3:.2f}ms"]
        for c in self.children:
            lines.append(c.tree(indent + 1))
        return "\n".join(lines)


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
        _tls.roots = deque(maxlen=ROOTS_MAX)
    return stack


def roots() -> deque:
    _stack()
    return _tls.roots


def reset(counters_too: bool = False) -> None:
    """Clear the thread-local span trace. Counters are process-wide and
    cumulative; per-query numbers come from `counter_delta()`, which cannot
    be polluted by concurrent queries. Pass counters_too=True only in
    single-threaded tooling that owns the whole process."""
    _tls.stack = []
    _tls.roots = deque(maxlen=ROOTS_MAX)
    if counters_too:
        reset_counters()


def push_scope() -> tuple:
    """Install a FRESH thread-local span stack/roots, returning a token for
    `pop_scope`. The flight recorder opens one per server request so a
    long-lived gRPC thread cannot accumulate spans toward the deque bound or
    interleave spans from unrelated queries (span hygiene)."""
    tok = (getattr(_tls, "stack", None), getattr(_tls, "roots", None))
    _tls.stack = []
    _tls.roots = deque(maxlen=ROOTS_MAX)
    return tok


def pop_scope(tok: tuple, keep_roots: bool = False) -> list:
    """Restore the pre-`push_scope` state; returns the spans the scope
    collected. With `keep_roots` the collected roots are re-appended to the
    restored deque so same-thread consumers (CLI --timing via `last_trace`)
    still see them."""
    collected = list(getattr(_tls, "roots", ()))
    _tls.stack, _tls.roots = tok
    if keep_roots and collected:
        _stack()  # re-init if the restored state was never initialized
        _tls.roots.extend(collected)
    return collected


def current_span_id() -> Optional[str]:
    stack = getattr(_tls, "stack", None)
    return stack[-1].span_id if stack else None


def note_child(name: str, start: float, end: float,
               span_id: str = "") -> None:
    """Tell the span open on this thread, if there is one, that
    [`start`, `end`] (`perf_counter` instants) was a span kept somewhere
    else: a request scope swaps the thread's stack, so its root never
    becomes a child of the span around the scope (a Flight handler's
    `*.serve`) by itself, and that span's self time would hold the whole
    query a second time."""
    stack = getattr(_tls, "stack", None)
    if stack:
        stack[-1].children.append(Span(name, start, end, span_id=span_id,
                                       parent_id=stack[-1].span_id))


class _SpanCtx:
    """Class-based span context (a @contextmanager generator costs ~2x as
    much, and spans sit on per-operator and per-RPC paths)."""
    __slots__ = ("span", "annotation")

    def __init__(self, s: Span, annotation):
        self.span = s
        self.annotation = annotation

    def __enter__(self) -> Span:
        return self.span

    def __exit__(self, *exc):
        s = self.span
        s.end = time.perf_counter()
        _tls.stack.pop()
        close_span(s.name, self.annotation, s.end - s.start, s.children)
        return False


def span(name: str, **attrs) -> _SpanCtx:
    annotation = open_span(name)
    s = Span(name, time.perf_counter(), span_id=new_span_id(),
             attrs=attrs or None)
    stack = _stack()
    if stack:
        s.parent_id = stack[-1].span_id
        stack[-1].children.append(s)
    else:
        _tls.roots.append(s)
    stack.append(s)
    return _SpanCtx(s, annotation)


# --- one clock: the enter/exit every span shares ------------------------------
#
# tracing.span, flight_recorder.Trace.span / add_span and
# flight_recorder.request_scope all open with `open_span` and close with
# `close_span`, so a span cannot exist without its profiler event and its
# self-time counter (benchmark/span_layers.json groups the counters by
# layer; PERF.md §3 names the metric each feeds).


def open_span(name: str):
    """Enter the profiler event of a span that starts now; the result goes
    to `close_span`. A TraceMe costs a few tenths of a microsecond while no
    profiler session is active; during one (`jax.profiler.start_trace`) the
    event lands on the opening thread's `/host:CPU` line."""
    annotation = _TraceAnnotation("igloo:" + name)
    annotation.__enter__()
    return annotation


def close_span(name: str, annotation, duration_s: float,
               children=()) -> None:
    """The one exit of every span: leave its profiler event (`None` for a
    span recorded after the fact by its bounds) and add its SELF time —
    `duration_s` minus its direct children's durations, i.e. the part of
    the interval no child span covers — to `span_us.<name>`, in integer
    microseconds."""
    if annotation is not None:
        annotation.__exit__(None, None, None)
    for c in children:
        if c.end:       # a child still open (a span held by a generator)
            duration_s -= c.end - c.start
    counter(f"span_us.{name}", max(round(duration_s * 1e6), 0))


def last_trace(n: int = 2) -> str:
    """Render the `n` most recent root spans of this thread's trace."""
    r = list(roots())
    return "\n".join(s.tree() for s in r[-n:])
