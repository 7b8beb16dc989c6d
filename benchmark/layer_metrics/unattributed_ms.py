"""Layer: uncovered. Mean client-side latency minus every layer's self time
per query, floored at 0: what no span covers (Flight/gRPC transport, the
RPC around a fragment, whatever a later change forgets to give a span)."""
import span_time


def read(run: dict):
    return span_time.unattributed_ms(run)
