"""Both ends of the program's Flight calls, by kind, from its counters.

The program counts every RPC attempt where it ends at the CLIENT end —
`rpc.calls.<kind>` and `rpc.client_us.<kind>` (integer microseconds; a
stream's to the end of its batches) — and every handler at the SERVER end,
first line to return or stream end, under the caller's kind name:
`rpc.server_us.<kind>` (igloo_tpu/cluster/rpc.py `call` / `Served`,
docs/observability.md "Transport"). A kind is `do_get` or `action.<name>`;
the `DistributedClient` prefixes its own with `client.`: its `client.do_get`
is a query's outermost call, whose server end is the coordinator's `do_get`;
its `client.action.<name>` are a harness's or an operator's calls
(`last_metrics` once a query, inside the window and outside every latency)
and are in no sum here. Nor are the calls a worker's own loop makes on its
own clock (`BACKGROUND`). `run["counters"]` holds the deltas over the window;
in every deployment of this benchmark both ends of a call share the
process's one registry, so client less server is the wire."""
from __future__ import annotations

import span_time

CALLS = "rpc.calls."
CLIENT = "rpc.client_us."
SERVER = "rpc.server_us."
OWN = "client."
#: a worker's heartbeat loop: beats, registration, compile-cache pushes
BACKGROUND = ("action.heartbeat", "action.register_worker",
              "action.compile_cache_get", "action.compile_cache_put")


def counts() -> bool:
    """Whether the program has such counters at all: set-up makes dozens of
    calls, so a program that counts has counted before the window."""
    from igloo_tpu.utils import tracing
    return any(k.startswith(CALLS) for k in tracing.counters())


def query_kinds(counters: dict) -> list:
    """The kinds of call the window's queries made: the client's own stream
    and every kind that is neither the client's nor a worker's loop's."""
    kinds = (k[len(CALLS):] for k in counters if k.startswith(CALLS))
    return sorted(k for k in kinds if k not in BACKGROUND
                  and (k == OWN + "do_get" or not k.startswith(OWN)))


def server_kind(kind: str) -> str:
    return kind[len(OWN):] if kind.startswith(OWN) else kind


def per_query(run: dict, total: float):
    n = len(run["queries"])
    return total / n if n else None


def calls_per_query(run: dict):
    c = run["counters"]
    return per_query(run, sum(c[CALLS + k] for k in query_kinds(c)))


def wire_ms_by_kind(run: dict) -> dict:
    """{server kind: client end less server end per query, ms}; the client's
    `client.do_get` and the coordinator's `do_get` to the worker are one
    kind at the server end, and are paired with both servers' time."""
    c = run["counters"]
    n = len(run["queries"])
    out: dict = {}
    for k in query_kinds(c):
        out[server_kind(k)] = out.get(server_kind(k), 0) + c.get(CLIENT + k, 0)
    return {k: (us - c.get(SERVER + k, 0)) / n / 1e3
            for k, us in out.items()} if n else {}


def wire_ms(run: dict):
    return sum(wire_ms_by_kind(run).values()) if run["queries"] else None


def span_us(run: dict, *names: str) -> int:
    return sum(run["counters"].get(span_time.PREFIX + n, 0) for n in names)


def handler_ms(run: dict):
    """Self time per query of the handlers' spans and of the dispatch's own
    side, less the handlers of calls that are no query's: an action without
    a request scope is `*.serve` self time whole, so its server end's time
    is what it added."""
    c = run["counters"]
    mine = {server_kind(k) for k in query_kinds(c)}
    others = sum(v for k, v in c.items()
                 if k.startswith(SERVER) and k[len(SERVER):] not in mine)
    us = span_us(run, "coordinator.serve", "worker.serve",
                 "coordinator.dispatch_fragment") - others
    return per_query(run, max(us, 0) / 1e3)


def release_ms(run: dict):
    c = run["counters"]
    us = c.get(CLIENT + "action.release", 0) \
        + span_us(run, "coordinator.release", "coordinator.finalize")
    return per_query(run, us / 1e3)
