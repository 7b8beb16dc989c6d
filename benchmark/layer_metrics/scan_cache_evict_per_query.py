"""Layer: scan + codec. `cache.evict` delta over the window per query:
entries the HBM scan cache dropped to stay inside its byte budget. 0 where
the columns the traffic reads fit it (an absent counter did not move)."""


def read(run: dict):
    n = len(run["queries"])
    return run["counters"].get("cache.evict", 0) / n if n else None
