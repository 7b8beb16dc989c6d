"""Layer: transport. Self time per query of the spans `coordinator.serve`,
`worker.serve` (a Flight handler around its request scope: ticket or body
decode, protocol parse, the span tree's copy, reply encode; the serving of
the root result) and `coordinator.dispatch_fragment` (the dispatch's own
side: request build and encode, reply decode, stats parse, stitching): the
(de)serialisation and plumbing at both ends of a query's calls. The handlers
of calls that are no query's (the harness's `last_metrics`, a worker's
heartbeat) are taken off by their `rpc.server_us.<kind>`. Nothing to read
in a program whose calls have no counters."""
import rpc_time


def read(run: dict):
    return rpc_time.handler_ms(run) if rpc_time.counts() else None
