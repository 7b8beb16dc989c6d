"""Layer: scan + codec. `scan_load.h2d_us` per query, ms: the part of a
scan's miss path (span `program.scan_load`, metric `scan_load_ms`) spent
handing the columns to the device and, where the load enters a cache,
waiting once, inside the span, until they are there (a fragment's dependency
table, consumed by the program dispatched next, is handed over and not
waited for). 0 where no scan missed in the window. Nothing to read in a
program without the counter: set-up's cold load moves it in one that has
it."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "scan_load.h2d_us" not in tracing.counters():
        return None
    return run["counters"].get("scan_load.h2d_us", 0) / n / 1e3
