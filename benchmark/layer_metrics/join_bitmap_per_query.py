"""Layer: programs. `join.bitmap_probes` delta over the window per query:
the lazy direct inner joins (single key, no residual, under a hint that
shrinks their output) whose full-width probe reads the positional table's
occupancy bits, and the table itself only at the hinted width
(`igloo_tpu/exec/fused.py _c_join_direct`, `exec/join.py
direct_bitmap_probe`), once per such join of a plan walk. TPC-H q3 reads
2.0 where both its joins are lazy; a drop means a join went back to reading
the table at full width. Nothing to read in a program that does not count
the path (no `join.bitmap_probes` after warm-up)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "join.bitmap_probes" not in tracing.counters():
        return None
    return run["counters"].get("join.bitmap_probes", 0) / n
