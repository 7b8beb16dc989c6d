"""Layer: programs. `program.literal_shared` delta over the window per
query: dispatches that found their program in the in-memory jit cache under
literal values other than the ones it last ran with (docs/observability.md).
While a program's key held its literals' values each of these was a trace
and an XLA compile inside a user's query. `scan_agg_streams`: 1.0, every
scan fragment follows one under the other stream's parameters; `scan_agg`:
0.0, a text is repeated. Nothing to read in a program from before it bound
literals as arguments (it has no `program.literal_args`)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "program.literal_args" not in tracing.counters():
        return None
    return run["counters"].get("program.literal_shared", 0) / n
