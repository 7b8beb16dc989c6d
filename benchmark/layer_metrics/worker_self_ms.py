"""Layer: worker. Self time per query of the worker's spans around the
programs (group `worker`): slot wait, dependency overlay and plan decoding,
executor building, result store, exchange. Served deployments only."""
import span_time


def read(run: dict):
    return span_time.layer_ms(run, "worker")
