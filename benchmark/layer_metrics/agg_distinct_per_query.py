"""Layer: programs. `agg.distinct` delta over the window per query: the
aggregates whose DISTINCT arguments the optimizer's rewrite split into two
grouped aggregates (`igloo_tpu/plan/aggsplit.py split_distinct`), counted
by the fused and the staged executor once per such aggregate of a plan walk.
TPC-H Q16 reads 1.0: its COUNT(DISTINCT ps_suppkey). A drop means the
aggregate no longer reaches the executors as the two stages. Nothing to
read in a program that does not count the rewrite (no `agg.distinct` after
warm-up)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "agg.distinct" not in tracing.counters():
        return None
    return run["counters"].get("agg.distinct", 0) / n
