"""`join_bitmap_per_query`: its entry, its arithmetic, nothing to read from
a program that does not count the path, and 2.0 in a rehearsal of the SF10
q3 cell, where both of q3's joins are lazy."""
import os

import pytest
from conftest import BENCH, last_line
from test_span_metrics import reader, run_of

NAME = "join_bitmap_per_query"
CELLS = ["tpch_sf10_embedded_speckeys.join_topk",
         "tpch_sf1_embedded.join_topk"]


def test_entry(bench_json):
    m = next(m for m in bench_json["per_layer"] if m["name"] == NAME)
    assert m["workloads"] == CELLS
    assert (m["unit"], m["better"], m["layer"], m["moves"], m["source"]) == \
        ("count", "higher", "programs", "queries_per_s", "program_counter")
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", NAME + ".py"))


def test_arithmetic_and_nothing_to_read(monkeypatch):
    from igloo_tpu.utils import tracing
    read = reader(NAME)
    program = {"join.bitmap_probes": 1}
    monkeypatch.setattr(tracing, "counters", lambda: program)
    moved = {"join.bitmap_probes": 8}
    assert read(run_of(moved, latencies=(1.0,) * 4)) == 2.0
    # a window in which no join took the path reads 0, not nothing
    assert read(run_of({"span_us.query": 7}, latencies=(1.0,) * 4)) == 0.0
    assert read(run_of(moved, latencies=())) is None
    # a program that does not count the path: nothing to read, no raise
    program.clear()
    assert read(run_of(moved, latencies=(1.0,) * 4)) is None


def test_rehearsal_reads_both_joins(run_py, capsys):
    rc = run_py.main(["--workload", CELLS[0], "--rehearse-sf", "0.01",
                      "--seed", "4100000301", "--seconds", "1.5",
                      "--trace", "1"])
    res = last_line(capsys.readouterr().out)
    assert rc == 1 and res["correct"] is False        # not a TPU run
    assert res["metrics"][NAME]["value"] == pytest.approx(2.0)
    assert res["metrics"]["join_direct_per_query"]["value"] == 2.0
