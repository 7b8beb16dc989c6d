"""`agg_in_place_per_query`: its entry, its arithmetic, nothing to read from
a program that does not count the rule, and 1.0 in a rehearsal of the q13
cell (its count per customer, under the second GROUP BY). Lists of cells are
held by membership: later PRs append."""
import os

import pytest
from conftest import BENCH, last_line
from test_span_metrics import reader, run_of

NAME = "agg_in_place_per_query"
CELLS = ["tpch_sf10_embedded_custdist.customer_distribution"]


def test_entry(bench_json):
    m = next(m for m in bench_json["per_layer"] if m["name"] == NAME)
    assert set(CELLS) <= set(m["workloads"])
    assert (m["unit"], m["better"], m["layer"], m["moves"], m["source"]) == \
        ("count", "higher", "programs", "queries_per_s", "program_counter")
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    cells = {c["name"] for c in bench_json["workloads"]}
    assert set(m["workloads"]) <= cells


def test_arithmetic_and_nothing_to_read(monkeypatch):
    from igloo_tpu.utils import tracing
    read = reader(NAME)
    program = {"agg.groups_in_place": 1}
    monkeypatch.setattr(tracing, "counters", lambda: program)
    moved = {"agg.groups_in_place": 4}
    assert read(run_of(moved, latencies=(1.0,) * 4)) == 1.0
    # a window in which no aggregate left its groups reads 0, not nothing
    assert read(run_of({"span_us.query": 7}, latencies=(1.0,) * 4)) == 0.0
    assert read(run_of(moved, latencies=())) is None
    # a program that does not count the rule: nothing to read, no raise
    program.clear()
    assert read(run_of(moved, latencies=(1.0,) * 4)) is None


def test_rehearsal_reads_one_aggregate_in_place(run_py, capsys):
    rc = run_py.main(["--workload", CELLS[0], "--rehearse-sf", "0.05",
                      "--seed", "2440000301", "--seconds", "1.5",
                      "--trace", "1"])
    res = last_line(capsys.readouterr().out)
    assert rc == 1 and res["correct"] is False        # not a TPU run
    assert res["metrics"][NAME]["value"] == pytest.approx(1.0)
