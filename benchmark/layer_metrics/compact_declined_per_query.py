"""Layer: programs. `fused.compact_declined` delta over the window per
query: adopted cardinality hints that WOULD have compacted a filter's live
rows and whose consumer declined (an aggregate without group expressions
reads them in one masked pass: `igloo_tpu/exec/aggregate.py
uncompacted_filter`). `scan_agg`: 0.5, q6's scan fragment in every second
query. 0 where nothing declined, and from a program that lacks the counter
(an absent counter did not move)."""


def read(run: dict):
    n = len(run["queries"])
    return run["counters"].get("fused.compact_declined", 0) / n if n else None
