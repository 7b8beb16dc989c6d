"""Layer: XLA on the chip. Union of device-op intervals in the traced
window, per query completed in it."""


def read(run: dict):
    t, n = run["trace"], len(run["queries"])
    return 1e3 * t["busy_s"] / n if t and n else None
