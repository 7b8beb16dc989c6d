"""Layer: programs. Self time per query of `program.first_call` alone: the
first call of a program the in-memory jit cache did not hold (trace + lower
+ persistent-cache load or compile). Its count is `jit_miss_per_query`."""
import span_time


def read(run: dict):
    return span_time.span_ms(run, "program.first_call")
