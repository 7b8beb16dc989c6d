"""Layer: kernels. The least time the chip's memory could take to read what
the window's queries read, over the device-busy time of the traced window.

Numerator: for every query of the window, rows of each table it reads x the
TPC-H spec widths of the columns it reads (the traffic file's `reads`):
the query's logical bytes, the same whatever implements the scan.
Peak: HBM bytes/s of this `device_kind` from peaks.json; a kind that is
not in the table is an error, not a default. Bound by bytes, not FLOPs."""
import json
import os


def read(run: dict):
    t = run["trace"]
    if not t or not t["busy_s"]:
        return None
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "peaks.json")) as f:
        peak = json.load(f)[run["device"]["kind"]]["hbm_bytes_per_s"]
    per_query = {q["name"]: sum(run["rows"][tbl] * sum(cols.values())
                                for tbl, cols in q["reads"].items())
                 for q in run["traffic"]["queries"]}
    logical = sum(per_query[q["name"]] for q in run["queries"])
    return 100.0 * (logical / peak) / t["busy_s"]
