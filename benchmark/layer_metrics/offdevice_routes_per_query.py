"""Layer: session. `engine.chunked_route` + `engine.grace_route` +
`engine.host_route` deltas over the window per query: queries the engine's
routing ladder (`QueryEngine._execute_plan`) sent somewhere other than one
program on the device — the chunked tier, GRACE, the numpy host tier. 0.0
where every query of the window ran on tier `device` (counters that did not
move are absent from the deltas). The routing decision itself stays inside
`query`'s self time (`session_self_ms`)."""

ROUTES = ("engine.chunked_route", "engine.grace_route", "engine.host_route")


def read(run: dict):
    n = len(run["queries"])
    if not n:
        return None
    return sum(run["counters"].get(name, 0) for name in ROUTES) / n
