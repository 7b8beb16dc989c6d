"""Cluster event journal (docs/observability.md#watchtower).

One structured, bounded, process-wide journal turning the fleet's
counters into a NARRATIVE: worker join/evict/recover, fragment
re-dispatch and busy-requeue, admission shed, demotions,
deadline/cancel, snapshot retry, corruption quarantine, compile-cache
push/pull, salting/broadcast flips, slow-query escalations. Every event
carries a wall timestamp, a severity, and — where applicable — the
worker id, qid, and trace_id, so an incident is reconstructible from
`system.cluster_events` alone.

Producers call `emit(kind, ...)` with a kind from the event catalog in
docs/observability.md#event-catalog — the event-names lint checker
(igloo_tpu/lint/event_names.py) holds emit sites and catalog to each
other, the same contract the metric-names and span-names checkers
enforce for counters and spans.

Worker events reach the coordinator by riding the heartbeat: the worker
drains its pending queue into the registry-declared `events` field of
WORKER_INFO (cluster/protocol.py) and the coordinator `ingest()`s them
under the sender's worker label. Every event has a process-unique `eid`,
and `ingest` drops eids it has already journaled — an in-process test
fleet (coordinator and workers sharing this module) forwards without
duplicating.

Surfaces: the `system.cluster_events` table, the coordinator `events`
Flight action, Prometheus `igloo_events_total{kind=...}` (via
`prometheus_lines()` on the coordinator's `metrics` action), and JSONL
export to `$IGLOO_TRACE_DIR/events.jsonl`.

`IGLOO_WATCH=0` (utils/timeseries.enabled) makes `emit` a no-op — no
ring writes, no counters, bit-identical to a build without the journal.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Optional

from igloo_tpu.utils import timeseries, tracing

SEVERITIES = ("info", "warn", "error")

_lock = threading.Lock()
_GUARDED_BY = {
    "_lock": ("_ring", "_pending", "_counts", "_seen", "_seen_order"),
}
_ring: deque = deque(maxlen=timeseries.history())
_pending: deque = deque(maxlen=256)   # worker->coordinator forward queue
_counts: dict = {}                    # kind -> cumulative count (unbounded
                                      # in VALUE, bounded in KEYS by catalog)
_seen: set = set()                    # eids already journaled (dedup)
_seen_order: deque = deque()          # FIFO for bounding _seen
_SEEN_MAX = 4096
_eid_seq = itertools.count(1)


def _next_eid() -> str:
    return f"{os.getpid():x}-{next(_eid_seq)}"


def _severity_rank(sev: str) -> int:
    try:
        return SEVERITIES.index(sev)
    except ValueError:
        return 0


def _export(ev: dict) -> None:
    """Best-effort JSONL append beside the trace export — a full disk
    must never take the cluster down (mirrors flight_recorder)."""
    out_dir = os.environ.get("IGLOO_TRACE_DIR")
    if not out_dir:
        return
    try:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, "events.jsonl")
        with open(path, "a", encoding="utf-8") as fh:
            fh.write(json.dumps(ev, default=str) + "\n")
    except OSError:
        tracing.counter("events.export_failed")


def _append_locked(ev: dict) -> None:
    _ring.append(ev)
    _seen.add(ev["eid"])
    _seen_order.append(ev["eid"])
    while len(_seen_order) > _SEEN_MAX:
        _seen.discard(_seen_order.popleft())
    _counts[ev["kind"]] = _counts.get(ev["kind"], 0) + 1


def emit(kind: str, severity: str = "info", worker: str = "",
         qid: str = "", trace_id: str = "", **attrs) -> Optional[dict]:
    """Journal one event. Returns the event dict, or None when the
    watchtower is off. `kind` must be a cataloged event kind
    (docs/observability.md#event-catalog, enforced by lint)."""
    if not timeseries.enabled():
        return None
    ev = {"eid": _next_eid(), "ts": time.time(), "kind": kind,
          "severity": severity if severity in SEVERITIES else "info",
          "worker": worker, "qid": qid, "trace_id": trace_id}
    if attrs:
        ev["attrs"] = attrs
    with _lock:
        _append_locked(ev)
        _pending.append(ev)
    tracing.counter("events.emitted")
    tracing.REGISTRY.bump_version()
    _export(ev)
    return ev


def ingest(evts: list, worker: str = "") -> int:
    """Coordinator side of heartbeat forwarding: journal a batch of
    worker events under the sender's label. Already-seen eids (the
    in-process fleet case, or a heartbeat retry) are dropped. Returns
    how many were new."""
    if not timeseries.enabled() or not evts:
        return 0
    added = 0
    with _lock:
        for ev in evts:
            if not isinstance(ev, dict) or "kind" not in ev:
                continue
            ev = dict(ev)
            ev.setdefault("eid", _next_eid())
            if ev["eid"] in _seen:
                continue
            if worker and not ev.get("worker"):
                ev["worker"] = worker
            _append_locked(ev)
            added += 1
    if added:
        tracing.counter("events.forwarded", added)
        tracing.REGISTRY.bump_version()
    return added


def drain_forward(max_n: int = 64) -> list:
    """Worker side of heartbeat forwarding: pop up to `max_n` pending
    events to ship in WORKER_INFO. Events popped here but lost to a
    failed heartbeat stay journaled locally (the ring is the record;
    forwarding is best-effort)."""
    out: list = []
    with _lock:
        while _pending and len(out) < max_n:
            out.append(_pending.popleft())
    return out


def requeue_forward(evts: list) -> None:
    """Put a drained batch back at the FRONT of the forward queue after a
    failed heartbeat, preserving order (next beat retries them first)."""
    if not evts:
        return
    with _lock:
        for ev in reversed(evts):
            _pending.appendleft(ev)


def events(min_severity: str = "info", limit: Optional[int] = None) -> list:
    """Journal contents, oldest first, at or above `min_severity`."""
    floor = _severity_rank(min_severity)
    with _lock:
        out = [e for e in _ring
               if _severity_rank(e.get("severity", "info")) >= floor]
    if limit is not None and limit >= 0:
        out = out[-limit:]
    return out


def counts() -> dict:
    with _lock:
        return dict(_counts)


def prometheus_lines(prefix: str = "igloo") -> list:
    """Labeled per-kind totals for the coordinator `metrics` action's
    extra_lines — the registry's own counters are unlabeled, so the
    journal carries the {kind=...} dimension itself."""
    with _lock:
        snap = dict(_counts)
    if not snap:
        return []
    m = f"{prefix}_events_total"
    lines = [f"# HELP {m} Cluster journal events by kind "
             "(docs/observability.md#event-catalog).",
             f"# TYPE {m} counter"]
    for kind in sorted(snap):
        lines.append(f'{m}{{kind="{kind}"}} {snap[kind]}')
    return lines


def clear() -> None:
    """Tests only: drop journal state and re-bound the ring from the
    current IGLOO_WATCH_HISTORY."""
    global _ring
    with _lock:
        _ring = deque(maxlen=timeseries.history())
        _pending.clear()
        _counts.clear()
        _seen.clear()
        _seen_order.clear()
