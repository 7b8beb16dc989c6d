"""Layer: programs. `agg.groups_in_place` delta over the window per query:
the direct-scatter aggregates that leave their groups where their segment
ids put them, those wider than `kernels.SMALL_NSEG` segments
(`igloo_tpu/exec/aggregate.py groups_in_place`), once per such aggregate
of a plan walk. TPC-H q13 reads 1.0: its count per customer, 1.5 M groups
at SF10, under the second GROUP BY. A drop means the aggregate compacts its
groups again. Nothing to read in a program that does not count the rule
(no `agg.groups_in_place` after warm-up)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "agg.groups_in_place" not in tracing.counters():
        return None
    return run["counters"].get("agg.groups_in_place", 0) / n
