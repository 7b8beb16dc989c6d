"""Layer: programs. Host self time per query of getting a program to the
device and its result back (group `programs`): plan walk and leaf lookup,
first calls (trace + lower + cache load), dispatches, hint flush and Arrow
building. The blocking fetch is `device_wait_ms`."""
import span_time


def read(run: dict):
    return span_time.layer_ms(run, "programs")
