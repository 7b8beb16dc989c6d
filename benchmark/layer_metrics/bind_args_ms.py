"""Layer: programs. Self time per query of `program.bind_args`: building a
dispatch's arguments — the batches stripped of their host metadata, the
constants pool put on the device (LUTs, pack offsets, the literals' scalar
vectors). A part of `programs_host_ms`. Nothing to read in a program from
before it had the span (that time read as `execute` self time there)."""
import span_time


def read(run: dict):
    from igloo_tpu.utils import tracing
    if span_time.PREFIX + "program.bind_args" not in tracing.counters():
        return None
    return span_time.span_ms(run, "program.bind_args")
