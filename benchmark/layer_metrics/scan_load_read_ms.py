"""Layer: scan + codec. `scan_load.read_us` per query, ms: the part of a
scan's miss path (span `program.scan_load`, metric `scan_load_ms`) that is
the provider's read and the Arrow decode. With `scan_load_h2d_ms`: the
codec (host decode, proofs, narrowing, an f32 pair's split) is
`scan_load_ms` less both. 0 where no scan missed in the window. Nothing to
read in a program without the counter: set-up's cold load moves it in one
that has it."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "scan_load.read_us" not in tracing.counters():
        return None
    return run["counters"].get("scan_load.read_us", 0) / n / 1e3
