"""Layer: transport. Flight calls a query makes, per query: the window's
`rpc.calls.<kind>` deltas summed over the client's own `client.do_get` and
every kind without the `client.` prefix (rpc_time.py: a harness's
`client.action.*` calls and a worker's heartbeat loop are no query's). A
served scan query makes 6.0: `client.do_get`, two `action.execute_fragment`,
the `action.ping` probe and the `do_get` of the root stream,
`action.release`; each attempt of a retried call counts. Nothing to read in
a program whose calls have no counters."""
import rpc_time


def read(run: dict):
    return rpc_time.calls_per_query(run) if rpc_time.counts() else None
