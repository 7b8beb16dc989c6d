"""Layer: scan + codec. `peak_bytes_in_use` of the fullest chip after the
window, in MB (10^6 bytes)."""


def read(run: dict):
    return run["memory_peak_bytes"] / 1e6 if run["memory_peak_bytes"] else None
