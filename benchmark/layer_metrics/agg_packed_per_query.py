"""Layer: programs. `pack.agg` delta over the window per query: the grouped
aggregates that sort their keys as ONE packed integer lane
(`igloo_tpu/exec/kernels.py plan_group_packing`) instead of the multi-lane
lex chain, once per such aggregate of a plan walk. TPC-H q13 reads 1.0 where
its second GROUP BY, on a count, carries the bound the count's input
capacity gives it (`igloo_tpu/exec/aggregate.py agg_out_bounds`); q3 reads
1.0, its three-key GROUP BY. A drop means a GROUP BY fell to the lex chain.
Nothing to read in a program that does not count the path (no `pack.agg`
after warm-up)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "pack.agg" not in tracing.counters():
        return None
    return run["counters"].get("pack.agg", 0) / n
