"""Layer: programs. `join.direct_table_bytes` delta over the window per
query, in MB (10^6 bytes): the positional tables a query's direct joins
build (`igloo_tpu/exec/join.py choose_direct_build`: slots x 4 bytes, one
int32 row id a slot), counted once per join of a plan walk. Read beside
`peak_hbm_mb`: what the joins' tables take of what the query holds; it
rises where a key's range widens (the spec's sparse order keys: 2^27 slots
at SF10) and reads 0 where every join left the positional route. Nothing to
read in a program that does not count its tables (no
`join.direct_table_bytes` after warm-up, which plans every query of the
traffic)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "join.direct_table_bytes" not in tracing.counters():
        return None
    return run["counters"].get("join.direct_table_bytes", 0) / n / 1e6
