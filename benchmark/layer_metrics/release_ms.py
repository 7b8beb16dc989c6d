"""Layer: front door. What runs between a query's last batch and the end of
the client's stream, per query: the client end of the `release` call
(`rpc.client_us.action.release`) and the self time of the spans
`coordinator.release` and `coordinator.finalize` (trace publish, query-log
row, totals). Nothing to read in a program whose calls have no counters."""
import rpc_time


def read(run: dict):
    return rpc_time.release_ms(run) if rpc_time.counts() else None
