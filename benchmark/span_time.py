"""Self time of the program's spans, by layer, from its counters.

The program adds every span's self time (duration minus its direct
children's, integer microseconds) to the counter `span_us.<name>` where the
span ends; `run["counters"]` holds the deltas over the window. span_layers.json
assigns each span name to one layer of PERF.md section 3, or to `wait`
(blocked on another thread, whose own spans carry that time). Self times
add up without double counting, also where fragments overlap."""
from __future__ import annotations

import functools
import json
import os

PREFIX = "span_us."
WAIT = "wait"


@functools.lru_cache(maxsize=None)
def groups() -> dict:
    """{group: [span name | `prefix.*`]} of span_layers.json."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "span_layers.json")) as f:
        return json.load(f)["groups"]


def groups_of(span: str) -> list:
    """The groups `span` falls in: those that name it exactly, else those
    with a `prefix.*` pattern over it (one group, by span_layers.json's
    test; none for a span nobody gave a layer)."""
    exact = [g for g, names in groups().items() if span in names]
    if exact:
        return exact
    return [g for g, names in groups().items()
            if any(n.endswith(".*") and span.startswith(n[:-1])
                   for n in names)]


def spans_ms(run: dict):
    """{span: mean self time per query of the window, ms}, or None where
    there is nothing to read: no query, or a program without such counters
    (a tree from before the spans had them) — the metric is then left out,
    not reported as 0."""
    n = len(run["queries"])
    moved = {k[len(PREFIX):]: v / n / 1e3 for k, v in run["counters"].items()
             if k.startswith(PREFIX)} if n else {}
    return moved or None


def layers_ms(run: dict):
    """{group: its spans' self time per query, ms}; a span that falls in no
    group, or in two, is in no sum."""
    spans = spans_ms(run)
    if spans is None:
        return None
    out = dict.fromkeys(groups(), 0.0)
    for span, ms in spans.items():
        mine = groups_of(span)
        if len(mine) == 1:
            out[mine[0]] += ms
    return out


def layer_ms(run: dict, group: str):
    layers = layers_ms(run)
    return None if layers is None else layers[group]


def span_ms(run: dict, span: str):
    """One span's self time per query, ms (0 where it never ran)."""
    spans = spans_ms(run)
    return None if spans is None else spans.get(span, 0.0)


def unattributed_ms(run: dict):
    """Mean client-side latency minus every layer's self time per query,
    floored at 0: the part of a query no span covers. `wait` is another
    thread's work and is not subtracted."""
    layers = layers_ms(run)
    if layers is None:
        return None
    q = run["queries"]
    covered = sum(ms for group, ms in layers.items() if group != WAIT)
    return max(1e3 * sum(x["latency_s"] for x in q) / len(q) - covered, 0.0)
