"""Profiler trace -> device busy time, per-op totals, idle gaps by host phase.

One reduction for every cell and every PR. Input is what
`jax.profiler.ProfileData` reads from an `.xplane.pb` (`load`), flattened to
plain lists so that the arithmetic (`reduce`) can be tested on a recorded
trace without JAX (tests/test_trace_reduce.py).

Planes of a TPU trace: `/device:TPU:<n>` carries the line `XLA Ops` (one
event per executed HLO op, device clock mapped onto the profiler's timeline)
beside `XLA Modules` and `Steps`; `/host:CPU` carries one line per host
thread with JAX's own TraceMe events and the harness's `bench:*`
annotations. All times are nanoseconds on the profiler's one timeline.
"""
from __future__ import annotations

import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
#: the line whose events are single device operations; modules (whole
#: programs) only where a trace has no op line
OP_LINES = ("XLA Ops", "XLA Modules")
HOST_PLANE = "/host:CPU"
#: the harness wraps the traced window in this annotation
WINDOW = "bench:window"
#: gaps longer than this many get a label of their own; the rest are summed
LABELLED_GAPS = 400


_HLO = re.compile(r"^(%\S+) = (\(.*?\)|\S+) ([\w\-]+)\(")


def short(name: str) -> str:
    """An op event's name is its whole HLO line; keep `%name opcode type`."""
    m = _HLO.match(name)
    if not m:
        return name[:100]
    out, kind, op = m.group(1), m.group(2), m.group(3)
    return f"{out} {op} {kind[:60]}"


def load(path: str) -> list:
    """[{name, lines: [{name, events: [(name, start_ns, dur_ns)]}]}]"""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        keep_all = plane.name == HOST_PLANE
        lines = []
        for line in plane.lines:
            if not keep_all and line.name not in OP_LINES:
                continue
            lines.append({"name": line.name, "events": [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(starts: np.ndarray, ends: np.ndarray) -> tuple:
    """Merged (starts, ends) of possibly overlapping intervals."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.ones(len(s), dtype=bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], e[last]


def find_window(planes: list) -> tuple:
    """(start_ns, end_ns) of the harness's window annotation, or None."""
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW:
                    return start, start + dur
    return None


def _host_events(planes: list, t0: float, t1: float) -> tuple:
    names, starts, ends = [], [], []
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if dur > 0 and start < t1 and start + dur > t0 \
                        and name != WINDOW:
                    names.append(name)
                    starts.append(start)
                    ends.append(start + dur)
    return names, np.asarray(starts), np.asarray(ends)


def _label(mid: float, names: list, starts, ends) -> str:
    """What the host was doing at `mid`: the harness's innermost `bench:`
    phase, then the innermost other host event (any thread)."""
    inside = np.flatnonzero((starts <= mid) & (ends >= mid))
    if len(inside) == 0:
        return "no host event"
    by_len = inside[np.argsort((ends - starts)[inside], kind="stable")]
    phase = next((names[i] for i in by_len
                  if names[i].startswith("bench:")), None)
    other = next((names[i] for i in by_len
                  if not names[i].startswith("bench:")), None)
    return " | ".join(x for x in (phase, other) if x)


def reduce(planes: list, top: int = 10):
    """-> {window_s, busy_s, devices, device_ops, idle_gaps, n_events} over
    the harness's window, or None where the trace holds no window
    annotation or no device operation in it (a CPU run). `busy_s` is the
    union of the op intervals, averaged over the device planes; `device_ops`
    the `top` ops by summed device time; `idle_gaps` the device-idle seconds
    summed by what the host was doing, `top` largest."""
    window = find_window(planes)
    if window is None:
        return None
    t0, t1 = window
    busy, ops, gaps_s, gaps_e, n_events, devices = [], {}, [], [], 0, 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        by_name = {ln["name"]: ln for ln in plane["lines"]}
        line = next((by_name[n] for n in OP_LINES if n in by_name), None)
        if line is None:
            continue
        devices += 1
        ev = [(n, s, s + d) for n, s, d in line["events"]
              if d > 0 and s < t1 and s + d > t0]
        n_events += len(ev)
        starts = np.clip(np.asarray([s for _, s, _ in ev]), t0, t1)
        ends = np.clip(np.asarray([e for _, _, e in ev]), t0, t1)
        for (name, _, _), s, e in zip(ev, starts, ends):
            ops[name] = ops.get(name, 0.0) + (e - s)
        us, ue = _union(starts, ends)
        busy.append(float((ue - us).sum()))
        # the complement of the union inside the window
        gs = np.concatenate(([t0], ue))
        ge = np.concatenate((us, [t1]))
        keep = ge > gs
        gaps_s.append(gs[keep])
        gaps_e.append(ge[keep])
    if not devices or not n_events:
        return None
    gs, ge = np.concatenate(gaps_s), np.concatenate(gaps_e)
    order = np.argsort(gs - ge, kind="stable")          # longest first
    names, hs, he = _host_events(planes, t0, t1)
    idle = {}
    for i in order[:LABELLED_GAPS]:
        label = _label((gs[i] + ge[i]) / 2, names, hs, he)
        idle[label] = idle.get(label, 0.0) + (ge[i] - gs[i]) / devices
    rest = order[LABELLED_GAPS:]
    if len(rest):
        idle[f"{len(rest)} shorter gaps"] = \
            float((ge[rest] - gs[rest]).sum()) / devices

    def ranked(d, scale):
        return [[short(k), float(v * scale)] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (t1 - t0) * 1e-9,
            "busy_s": sum(busy) / devices * 1e-9,
            "devices": devices, "n_events": n_events,
            "device_ops": ranked(ops, 1e-9 / devices),
            "idle_gaps": ranked(idle, 1e-9)}
