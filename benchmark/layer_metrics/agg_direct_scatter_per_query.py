"""Layer: programs. `agg.direct_scatter` delta over the window per query:
the aggregates lowered to one scatter into dense-int segments
(`igloo_tpu/exec/aggregate.py seg_dims_for`), once per such aggregate of a
plan walk. TPC-H q13 reads 1.0: its count per customer (1.5 M segments at
SF10) scatters, and its second GROUP BY, on a count with no host-known
bounds, sorts; a drop means the count per customer fell to the multi-lane
sort. Nothing to read in a program that does not count the path (no
`agg.direct_scatter` after warm-up)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "agg.direct_scatter" not in tracing.counters():
        return None
    return run["counters"].get("agg.direct_scatter", 0) / n
