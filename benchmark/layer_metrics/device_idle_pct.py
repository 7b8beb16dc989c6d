"""Layer: device. 100 x (1 - union of device-op intervals / traced window)."""


def read(run: dict):
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
