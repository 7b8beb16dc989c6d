"""Layer: session. Self time per query of the engine's spans (group
`session`): parse, bind + optimize, the routing ladder and the stats
bookkeeping around the executor. In a served cell this is the coordinator's
engine planning the query."""
import span_time


def read(run: dict):
    return span_time.layer_ms(run, "session")
