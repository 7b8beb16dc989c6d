"""Layer: programs. `jit.miss` delta over the window per query: traces +
lowerings that the in-memory jit cache did not absorb (the served
final-merge fragment re-traces on every execution: PERF.md)."""


def read(run: dict):
    n = len(run["queries"])
    return run["counters"].get("jit.miss", 0) / n if n else None
