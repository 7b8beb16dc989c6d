"""Layer: device. Self time per query of the blocking fetch (group
`device`, the span `fused.fetch`): the host waiting for the device to
finish, then the D2H copy. Beside `device_busy_ms` (trace) it says how much
of the device's work the host waits through."""
import span_time


def read(run: dict):
    return span_time.layer_ms(run, "device")
