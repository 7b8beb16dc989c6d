"""`agg_packed_per_query`: its entry, its arithmetic, nothing to read from
a program that does not count the path, and 1.0 in rehearsals of the q13
cell (its second GROUP BY, on a count) and of a q3 cell (its three-key
GROUP BY). Lists of cells are held by membership: later PRs append."""
import os

import pytest
from conftest import BENCH, last_line
from test_span_metrics import reader, run_of

NAME = "agg_packed_per_query"
CELLS = ["tpch_sf10_embedded_custdist.customer_distribution",
         "tpch_sf10_embedded_speckeys.join_topk",
         "tpch_sf1_embedded.join_topk"]


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
    program = {"pack.agg": 1}
    monkeypatch.setattr(tracing, "counters", lambda: program)
    moved = {"pack.agg": 4}
    assert read(run_of(moved, latencies=(1.0,) * 4)) == 1.0
    # a window in which no aggregate packed reads 0, not nothing
    assert read(run_of({"span_us.query": 7}, latencies=(1.0,) * 4)) == 0.0
    assert read(run_of(moved, latencies=())) is None
    # a program that does not count the path: nothing to read, no raise
    program.clear()
    assert read(run_of(moved, latencies=(1.0,) * 4)) is None


@pytest.mark.parametrize("cell,sf", [(CELLS[0], "0.05"), (CELLS[2], "0.01")])
def test_rehearsal_reads_one_packed_aggregate(run_py, capsys, cell, sf):
    rc = run_py.main(["--workload", cell, "--rehearse-sf", sf,
                      "--seed", "2430000301", "--seconds", "1.5",
                      "--trace", "1"])
    res = last_line(capsys.readouterr().out)
    assert rc == 1 and res["correct"] is False        # not a TPU run
    assert res["metrics"][NAME]["value"] == pytest.approx(1.0)
