"""`compact_declined_per_query` (ISSUE 31): its arithmetic over a recorded
run's counters, what it reads from a program without the counter, its entry
in `BENCHMARK.json`, and a number from a rehearsal of each cell that lists
it."""
import os

import pytest
from conftest import BENCH, last_line
from test_sf10_cell import run_of
from test_span_metrics import reader

NAME = "compact_declined_per_query"
CELLS = ["tpch_sf10_served.scan_agg", "tpch_sf1_served.scan_agg"]
# the window's counter deltas of the change's traced SF10 run (my chip run,
# PR 31, seed 3300002003: 212 queries, 106 q1 and 106 q6, two fragments
# each), cut to what the programs layer reads
RECORDED = {"fused.compact_declined": 106, "fused.execute": 424,
            "jit.hit": 424, "span_us.fused.plan": 332_945}


@pytest.mark.parametrize("counters,n,want", [
    (RECORDED, 212, 0.5),
    ({"fused.compact_declined": 23}, 46, 0.5),      # a parent-length window
    # the parent's program has no such counter, and a window in which no
    # hint was declined does not move it: 0, not nothing, and no error
    ({k: v for k, v in RECORDED.items() if k != "fused.compact_declined"},
     212, 0.0),
    ({}, 46, 0.0),
    (RECORDED, 0, None),                     # a window without a query
], ids=["recorded", "23_over_46", "absent_counter", "no_counters",
        "no_queries"])
def test_arithmetic_and_nothing_to_read(counters, n, want):
    assert reader(NAME)(run_of(counters, n)) == want


def test_entry(bench_json):
    [entry] = [m for m in bench_json["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "programs",
                     "moves": "queries_per_s", "workloads": CELLS}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", NAME + ".py"))


@pytest.mark.parametrize("cell", CELLS)
def test_a_rehearsal_reads_it(run_py, capsys, cell):
    # at SF 0.01 every capacity is under ADAPTIVE_CAPACITY, so no hint could
    # compact and none is declined: the line holds the metric, at 0.0
    rc = run_py.main(["--workload", cell, "--rehearse-sf", "0.01",
                      "--seed", "3300000311", "--seconds", "1.5",
                      "--trace", "1"])
    res = last_line(capsys.readouterr().out)
    assert rc == 1 and res["failed"] == 0
    assert res["metrics"][NAME] == {"value": 0.0, "unit": "count"}
