"""Layer: session. `engine.route_priced_bytes` delta over the window per
query, in MB (10^6 bytes): what the routing ladder priced the query's scans
at — the lanes of the columns they read, from file metadata alone
(`igloo_tpu/exec/chunked.py estimated_lane_bytes`). Read beside
`peak_hbm_mb`: the price against what is held; a price far above it
chunks, and reserves at admission, memory the query never uses. The same
traffic reads the same number in every window: it is a function of the
plans and the files. Nothing to read in a program that does not count what
it prices (it has no `engine.route_priced_bytes`: warm-up routes every
query of the traffic, so a program that counts has counted before the
window)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "engine.route_priced_bytes" not in tracing.counters():
        return None
    return run["counters"].get("engine.route_priced_bytes", 0) / n / 1e6
