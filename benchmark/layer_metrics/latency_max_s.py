"""Layer: front door. Slowest query of the window (where a window holds
tens of queries, its 95th percentile would be this)."""


def read(run: dict):
    return max((q["latency_s"] for q in run["queries"]), default=None)
