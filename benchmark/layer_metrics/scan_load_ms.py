"""Layer: scan + codec. Self time per query of `program.scan_load`: a scan's
miss path (provider read + decode, codec, H2D of the columns the HBM scan
cache did not hold). In a steady window only a merge fragment's dependency
tables pass through it; 0 where it did not close in the window at all.
Nothing to read in a program from before it had the span: there not even
set-up's cold load has closed one."""
import span_time


def read(run: dict):
    from igloo_tpu.utils import tracing
    if span_time.PREFIX + "program.scan_load" not in tracing.counters():
        return None
    return span_time.span_ms(run, "program.scan_load")
