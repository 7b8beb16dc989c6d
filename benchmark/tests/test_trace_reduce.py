"""The trace -> (busy union, per-op totals, idle gaps) reduction, on a
synthetic trace with known answers and on a small trace recorded on the
chip (recorded_trace.json.gz: PR 27, tpch_sf1_served.scan_agg)."""
import gzip
import json
import os

import pytest
import trace_reduce
from conftest import HERE

MS = 1e6


def synthetic():
    ops = [("fusion.1", 10 * MS, 20 * MS),    # 10..30
           ("sort.2", 25 * MS, 15 * MS),      # 25..40 overlaps: union 10..40
           ("fusion.1", 60 * MS, 10 * MS),    # 60..70
           ("copy.3", 95 * MS, 20 * MS)]      # 95..115: clipped at 100
    host = [(trace_reduce.WINDOW, 0.0, 100 * MS),
            ("bench:q1:execute", 0.0, 58 * MS),
            ("PjitFunction(run)", 41 * MS, 15 * MS),
            ("bench:q1:last_info", 71 * MS, 20 * MS)]
    return [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops},
                {"name": "XLA Modules", "events": [("jit_run", 0, 99 * MS)]}]},
            {"name": "/host:CPU", "lines": [{"name": "python3",
                                             "events": host}]}]


def test_synthetic_busy_ops_and_gaps():
    r = trace_reduce.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.045)        # 30 + 10 + 5 ms
    assert r["devices"] == 1 and r["n_events"] == 4
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.030)
    assert ops["sort.2"] == pytest.approx(0.015)
    assert ops["copy.3"] == pytest.approx(0.005)      # clipped to the window
    gaps = dict(r["idle_gaps"])
    # 0..10 and 40..60 (midpoint 50 lies in the Pjit event) and 70..95
    assert gaps["bench:q1:execute"] == pytest.approx(0.010)
    assert gaps["bench:q1:execute | PjitFunction(run)"] == pytest.approx(0.020)
    assert gaps["bench:q1:last_info"] == pytest.approx(0.025)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_no_window_or_no_device_gives_nothing():
    planes = synthetic()
    assert trace_reduce.reduce(planes[:1]) is None      # no host plane
    assert trace_reduce.reduce(planes[1:]) is None      # no device plane
    planes[0]["lines"][0]["events"] = []
    planes[0]["lines"][1]["events"] = []
    assert trace_reduce.reduce(planes) is None          # nothing ran


def test_recorded_chip_trace():
    path = os.path.join(HERE, "recorded_trace.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    r = trace_reduce.reduce(rec["planes"])
    want = rec["expected"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["device_ops"][0][0] == want["top_op"]
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
