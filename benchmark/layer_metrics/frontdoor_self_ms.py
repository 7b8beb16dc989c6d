"""Layer: front door. Self time per query of the client's, the admission
queue's and the coordinator's spans (span_layers.json, group `front door`):
ticket building, admission, fragment planning, the root result's relay.
Served deployments only."""
import span_time


def read(run: dict):
    return span_time.layer_ms(run, "front door")
