"""Layer: programs. `program.literal_keyed` delta over the window per query:
literals whose VALUE a program key still holds (a string, NULL, a function's
literal argument, a LIKE pattern, an IN list's length, LIMIT's bounds),
counted in the plan walk. Each distinct value of one is a program of its
own: a compile inside a user's query. q1 and q6 hold none: 0.0. Nothing to
read in a program from before it bound literals as arguments (it has no
`program.literal_args`)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "program.literal_args" not in tracing.counters():
        return None
    return run["counters"].get("program.literal_keyed", 0) / n
