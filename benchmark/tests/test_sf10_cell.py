"""The SF10 served cell: it resolves to its files, a rehearsal at SF 0.01
runs every phase and never passes, its control is not `correct`, each
per-layer metric the cell brought reads a number from a rehearsal's run, it
stages `datagen.py`'s own rows, and a run whose scan cache cannot hold the
traffic's columns ends in warm-up."""
import json
import os

import pyarrow as pa
import pytest
from conftest import BENCH, ROOT, last_line
from test_span_metrics import reader

CELL, SMALL = "tpch_sf10_served.scan_agg", "tpch_sf1_served.scan_agg"
NEW_METRICS = {"h2d_mb_per_query": "MB", "scan_cache_evict_per_query": "count",
               "scan_load_ms": "ms"}


def run(run_py, capsys, *args) -> tuple:
    rc = run_py.main(["--workload", CELL, "--rehearse-sf", "0.01", *args])
    return rc, last_line(capsys.readouterr().out)


def test_the_cell_resolves_to_its_files(run_py, bench_json):
    spec = run_py.resolve(CELL)
    small = run_py.resolve(SMALL)
    config = spec["config"]
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == "scan_agg"
    assert config["scale_factor"] == config["published_scale_factor"] == 10.0
    assert config["reduced"] == ["workers"] and config["workers"] == 1
    # the small control shares everything but the scale: the same traffic,
    # queries, oracle, guarantees and assumptions; the deployment is
    # `served` held to its layout, the rows are `datagen`'s (tests below)
    assert spec["traffic"] == small["traffic"]
    assert config["deployment"] == "served_resident"
    assert config["datagen"] == "datagen_reads"
    assert set(small["config"]["assumed"]) < set(config["assumed"])
    for key in ("guarantees", "precision", "trace_seconds",
                "key_distribution"):
        assert config[key] == small["config"][key], key
    assert {m["name"] for m in spec["end_to_end"]} == {"queries_per_s",
                                                       "setup_s"}
    # every per-layer metric of the small control, and nothing else
    assert ([m["name"] for m in spec["per_layer"]]
            == [m["name"] for m in small["per_layer"]])
    for name, unit in NEW_METRICS.items():
        m = next(m for m in bench_json["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL, SMALL] and m["unit"] == unit
        assert m["layer"] == "scan + codec" and m["moves"] == "queries_per_s"
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf10_served.json")) as f:
        assert json.load(f) == config


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_every_phase_and_never_passes(run_py, capsys,
                                                     bench_json, trace):
    rc, res = run(run_py, capsys, "--seed", "3300000307", "--seconds", "1.5",
                  "--trace", str(trace))
    assert rc == 1 and res["correct"] is False
    failing = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failing == {"not_a_tpu_run"}               # all it compared held
    assert res["attempted"] >= 2 and res["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in bench_json[kind]
                if CELL in m.get("workloads", [CELL])}
    assert set(res["metrics"]) <= declared
    if not trace:
        assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
        return
    # the metrics the cell brought each read a number: after warm-up the
    # base columns are hits, so a query uploads and loads only its merge
    # fragment's dependency table
    metrics = res["metrics"]
    for name, unit in NEW_METRICS.items():
        assert metrics[name]["unit"] == unit
        assert isinstance(metrics[name]["value"], float), name
    assert 0 < metrics["h2d_mb_per_query"]["value"] < 0.01
    assert metrics["scan_cache_evict_per_query"]["value"] == 0.0
    assert 0 < metrics["scan_load_ms"]["value"] \
        < metrics["programs_host_ms"]["value"]
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["jit_miss_per_query"]["value"] == 0


def test_control_float32_is_not_correct(run_py, capsys):
    rc, res = run(run_py, capsys, "--seed", "3300000311", "--seconds", "1",
                  "--trace", "0", "--control", "float32")
    assert res["correct"] is False
    c = res["checks"]["max_rel_err"]
    assert c["value"] > 10 * c["limit"] or res["checks"]["wrong_cells"]["value"]


def test_a_scan_cache_that_drops_columns_ends_the_run(run_py, capsys,
                                                      monkeypatch):
    """What the parent of the PR that derived the budgets does at SF10, at
    SF 0.01: seven columns of 2^16 lanes under a 256 KB cache."""
    from igloo_tpu.exec import cache
    monkeypatch.setattr(cache, "UNLIMITED_BUDGETS", (1 << 18, 2 << 30))
    with pytest.raises(RuntimeError, match="warm-up q1: RuntimeError: the "
                                           "scan cache dropped"):
        run(run_py, capsys, "--seed", "3300000313", "--seconds", "1",
            "--trace", "0")


@pytest.mark.parametrize("seed", [7, 3300000401])
@pytest.mark.parametrize("every_column", [False, True])
def test_staging_is_datagens_rows_cut_to_the_columns_read(
        run_py, monkeypatch, seed, every_column):
    datagen = run_py.load_module("", "datagen")
    cut = run_py.load_module("", "datagen_reads")
    reads = cut.read_columns()
    assert reads["lineitem"] >= set(
        run_py.resolve(CELL)["traffic"]["queries"][0]["reads"]["lineitem"])
    tables = ["lineitem", "orders", "nation"]
    whole = datagen.gen_tables(sf=0.01, seed=seed, tables=tables)
    if every_column:    # a traffic mix that reads a column drawn last
        reads = {name: set(t.column_names) for name, t in whole.items()}
        monkeypatch.setattr(cut, "read_columns", lambda: reads)
    got = cut.gen_tables(sf=0.01, seed=seed, tables=tables)
    for name, table in whole.items():
        want = table.select([c for c in table.column_names
                             if c in reads.get(name, table.column_names)])
        assert isinstance(got[name], pa.Table)
        assert got[name].equals(want), name
        assert got[name].schema.equals(want.schema), name
    if not every_column:
        assert got["lineitem"].num_columns < whole["lineitem"].num_columns


def run_of(counters: dict, n: int = 4) -> dict:
    return {"queries": [{"name": "q", "latency_s": 1.0, "info": {}}] * n,
            "counters": counters, "trace": None}


def test_new_metrics_arithmetic_and_nothing_to_read(monkeypatch):
    from igloo_tpu.utils import tracing
    program = {"span_us.program.scan_load": 123_456}     # since the start
    monkeypatch.setattr(tracing, "counters", lambda: program)
    moved = {"xfer.h2d_bytes": 8_000_000, "cache.evict": 6,
             "span_us.program.scan_load": 10_000, "span_us.fused.plan": 7}
    assert reader("h2d_mb_per_query")(run_of(moved)) == pytest.approx(2.0)
    assert reader("scan_cache_evict_per_query")(run_of(moved)) == 1.5
    assert reader("scan_load_ms")(run_of(moved)) == pytest.approx(2.5)
    # a counter that did not move is absent from the deltas: 0, not nothing
    quiet = {"span_us.fused.plan": 7}
    assert reader("h2d_mb_per_query")(run_of(quiet)) == 0.0
    assert reader("scan_cache_evict_per_query")(run_of(quiet)) == 0.0
    assert reader("scan_load_ms")(run_of(quiet)) == 0.0
    # a program without the span (the parent of the PR that added it: not
    # even set-up's cold load closed one), a program without span counters,
    # or a window without a query: nothing to read, and no error
    program.clear()
    assert reader("scan_load_ms")(run_of(quiet)) is None
    program["span_us.program.scan_load"] = 1
    assert reader("scan_load_ms")(run_of({})) is None
    for name in NEW_METRICS:
        assert reader(name)(run_of(moved, n=0)) is None
