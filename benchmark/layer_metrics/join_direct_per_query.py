"""Layer: programs. `join.direct_routes` delta over the window per query:
the joins a query lowered to the positional (direct) route, fused or
staged (`igloo_tpu/exec/join.py choose_direct_build`), once per join of a
plan walk. TPC-H q3 reads 2.0; a drop means a join fell off the route (to
the sorted probe, or to the staged executor when the fused compiler cannot
take the sorted probe at that width). Nothing to read in a program that
does not count the route (no `join.direct_routes` after warm-up)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "join.direct_routes" not in tracing.counters():
        return None
    return run["counters"].get("join.direct_routes", 0) / n
