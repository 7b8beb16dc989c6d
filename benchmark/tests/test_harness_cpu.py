"""The harness end to end on the CPU at SF 0.01: every phase runs, the last
line parses, and the run does not pass as a measurement. Then `correct`:
its control (the reference one precision down) and the fault a cell can have
(an answer altered where it is produced) both have to come out false, by a
number of the comparison and not by the missing chip."""
import os
import subprocess
import sys

import pytest
from conftest import ROOT, last_line

SERVED, EMBEDDED = "tpch_sf1_served.scan_agg", "tpch_sf1_embedded.join_topk"
COMPARISON = ("max_rel_err", "wrong_cells", "failed_queries",
              "fallback_counters", "empty_window")


def run(run_py, capsys, *args) -> tuple:
    rc = run_py.main(list(args))
    return rc, last_line(capsys.readouterr().out)


def failing(result: dict) -> set:
    return {k for k, c in result["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("cell,trace", [(SERVED, 0), (SERVED, 1),
                                        (EMBEDDED, 0), (EMBEDDED, 1)])
def test_rehearsal_runs_every_phase_and_never_passes(run_py, capsys,
                                                     bench_json, cell, trace):
    rc, res = run(run_py, capsys, "--workload", cell, "--seed", "3000000019",
                  "--seconds", "1.5", "--trace", str(trace),
                  "--rehearse-sf", "0.01")
    assert rc == 1 and res["correct"] is False
    assert failing(res) == {"not_a_tpu_run"}          # all it compared held
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in bench_json[kind]
                if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) <= set(declared)
    for name, m in res["metrics"].items():
        assert m["unit"] == declared[name]["unit"]
    if not trace:
        assert set(res["metrics"]) == set(declared)
        assert res["metrics"]["queries_per_s"]["value"] > 0
    else:
        # no device plane in a CPU trace: trace metrics are left out, never 0
        assert "device_idle_pct" not in res["metrics"]
        assert "busy_s" not in res["device"]
        assert res["metrics"]["compiles_in_window"]["value"] == 0


def test_refuses_without_a_tpu():
    """No --rehearse-sf: no accelerator, so no result line and exit != 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", SERVED, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode not in (0, 1)
    assert p.stdout.strip() == "" and "refused" in p.stderr


@pytest.mark.parametrize("cell", [SERVED, EMBEDDED])
def test_control_float32_is_not_correct(run_py, capsys, cell):
    rc, res = run(run_py, capsys, "--workload", cell, "--seed", "77",
                  "--seconds", "1", "--trace", "0", "--rehearse-sf", "0.01",
                  "--control", "float32")
    assert res["correct"] is False
    assert failing(res) & {"max_rel_err", "wrong_cells"}
    c = res["checks"]["max_rel_err"]
    assert c["value"] > 10 * c["limit"] or res["checks"]["wrong_cells"]["value"]


def altered(table, rel):
    """The first float column's first cell off by `rel` (relative)."""
    import pyarrow as pa
    import pyarrow.compute as pc
    for i, f in enumerate(table.schema):
        if pa.types.is_floating(f.type) and table.num_rows:
            col = table.column(i).combine_chunks()
            bump = pa.array([1.0 + rel] + [1.0] * (len(col) - 1), f.type)
            return table.set_column(i, f, pc.multiply(col, bump))
    return table


@pytest.mark.parametrize("rel,fails", [(1e-6, True), (0.0, False)])
def test_embedded_answer_altered_where_produced(run_py, capsys, monkeypatch,
                                                rel, fails):
    from igloo_tpu.engine import QueryEngine
    real = QueryEngine._execute_plan
    monkeypatch.setattr(QueryEngine, "_execute_plan",
                        lambda self, plan: altered(real(self, plan), rel))
    rc, res = run(run_py, capsys, "--workload", EMBEDDED, "--seed", "78",
                  "--seconds", "1", "--trace", "0", "--rehearse-sf", "0.01")
    assert ("max_rel_err" in failing(res)) is fails
    assert bool(failing(res) & set(COMPARISON)) is fails


@pytest.mark.parametrize("fault", ["altered", "row_dropped"])
def test_served_answer_altered_where_produced(run_py, capsys, monkeypatch,
                                              fault):
    """Every fragment the worker runs returns a broken table."""
    from igloo_tpu.cluster.worker import WorkerServer
    real = WorkerServer._run_plan

    def broken(self, ex, plan, catalog, budget):
        table = real(self, ex, plan, catalog, budget)
        return (altered(table, 1e-6) if fault == "altered"
                else table.slice(0, max(table.num_rows - 1, 0)))
    monkeypatch.setattr(WorkerServer, "_run_plan", broken)
    rc, res = run(run_py, capsys, "--workload", SERVED, "--seed", "79",
                  "--seconds", "1", "--trace", "0", "--rehearse-sf", "0.01")
    assert res["correct"] is False
    assert failing(res) & {"max_rel_err", "wrong_cells", "failed_queries"}
