"""Layer: programs. `compile_cache.miss` delta over the window: programs
compiled inside it. 0 is the expected value (set-up warms every shape)."""


def read(run: dict):
    return run["counters"].get("compile_cache.miss", 0)
