"""Layer: scan + codec. `xfer.h2d_bytes` delta over the window per query, in
MB (10^6 bytes): what a query uploads. With the tables resident in the HBM
scan cache that is its fragments' dependency tables alone; a cache too small
for the columns the traffic reads uploads them again in every query. A
counter that did not move is absent from the deltas: 0."""


def read(run: dict):
    n = len(run["queries"])
    return run["counters"].get("xfer.h2d_bytes", 0) / n / 1e6 if n else None
