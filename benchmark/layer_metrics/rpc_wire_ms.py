"""Layer: transport. Per query, over the kinds of call a query makes
(rpc_calls_per_query's), the client end's `rpc.client_us.<kind>` less the
server end's `rpc.server_us.<kind>` — `client.do_get` paired with the
coordinator's `do_get` —: what a call costs outside both programs' own
code: gRPC, Flight framing, the hand-off between threads and, with three
roles in one process, the interpreter lock. Both ends are in one registry
in every deployment of this benchmark. Nothing to read in a program whose
calls have no counters."""
import rpc_time


def read(run: dict):
    return rpc_time.wire_ms(run) if rpc_time.counts() else None
