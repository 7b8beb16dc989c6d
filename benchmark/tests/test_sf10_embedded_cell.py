"""The SF10 embedded cell (PR 36): it resolves to its files, which are the
served SF10 configuration's but for the deployment; a rehearsal at SF 0.01
runs every phase and never passes; its control is not `correct`; the two
per-layer metrics it brought (`offdevice_routes_per_query`,
`route_priced_mb`) have their entries, their arithmetic and nothing to read
from a program without the counter; and `embedded_resident` ends a run whose
engine takes a query in chunks, or whose scan cache cannot hold the
traffic's columns."""
import functools
import json
import os

import pyarrow.parquet as pq
import pytest
from conftest import BENCH, ROOT, last_line
from test_span_metrics import reader, run_of

CELL, SMALL = "tpch_sf10_embedded.scan_agg", "tpch_sf1_embedded.scan_agg"
SERVED = "tpch_sf10_served.scan_agg"
Q3 = "tpch_sf1_embedded.join_topk"
NEW_METRICS = {"offdevice_routes_per_query": ("count", [SMALL, CELL, Q3]),
               "route_priced_mb": ("MB", [SMALL, CELL])}


def run(run_py, capsys, *args) -> tuple:
    rc = run_py.main(["--workload", CELL, "--rehearse-sf", "0.01", *args])
    return rc, last_line(capsys.readouterr().out)


def test_the_cell_resolves_to_its_files(run_py, bench_json):
    spec, small = run_py.resolve(CELL), run_py.resolve(SMALL)
    served = run_py.resolve(SERVED)
    config = spec["config"]
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == "scan_agg"
    assert len(spec["cell"]["why"]) <= 200
    # the served SF10 configuration, key for key, but for the deployment and
    # what names it; the cut is the small embedded configuration's
    assert list(config) == list(served["config"])
    differs = {k for k in config if config[k] != served["config"][k]}
    assert differs == {"name", "source", "deployment", "layout",
                       "reduced_why"}
    assert config["deployment"] == "embedded_resident"
    assert config["scale_factor"] == config["published_scale_factor"] == 10.0
    assert config["reduced"] == ["workers"] and config["workers"] == 1
    assert config["guarantees"]["float_rel_tol"] == 1e-9
    assert config["datagen"] == "datagen_reads"
    # its small control: the same traffic, queries, oracle and guarantees
    assert spec["traffic"] == small["traffic"] == served["traffic"]
    assert config["guarantees"] == small["config"]["guarantees"]
    entry = next(c for c in bench_json["configs"]
                 if c["name"] == "tpch_sf10_embedded")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/tpch_sf10_embedded.json"
    assert {m["name"] for m in spec["end_to_end"]} == {"queries_per_s",
                                                       "setup_s"}
    # every per-layer metric of the small control, the declined compaction
    # of q6 at 2^26 lanes, and the two it brought
    mine = {m["name"] for m in spec["per_layer"]}
    assert mine >= {m["name"] for m in small["per_layer"]}
    assert mine >= {"compact_declined_per_query", "scan_roofline_pct",
                    "peak_hbm_mb", *NEW_METRICS}
    for name, (unit, cells) in NEW_METRICS.items():
        m = next(m for m in bench_json["per_layer"] if m["name"] == name)
        assert m["workloads"][:len(cells)] == cells and m["unit"] == unit
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            "session", "queries_per_s", "program_counter", "lower")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf10_embedded.json")) as f:
        assert json.load(f) == config


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_every_phase_and_never_passes(run_py, capsys,
                                                     bench_json, trace):
    rc, res = run(run_py, capsys, "--seed", "3600000307", "--seconds", "1.5",
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
    metrics = res["metrics"]
    assert metrics["offdevice_routes_per_query"] == {"value": 0.0,
                                                     "unit": "count"}
    # q1 prices seven columns and q6 four, at 2^16 lanes, in every window
    assert metrics["route_priced_mb"]["unit"] == "MB"
    assert metrics["route_priced_mb"]["value"] == pytest.approx(
        (1 << 16) * (39 + 27) / 2 / 1e6)
    # after warm-up every column is a hit: nothing uploaded, loaded, dropped
    assert metrics["h2d_mb_per_query"]["value"] == 0.0
    assert metrics["scan_load_ms"]["value"] == 0.0
    assert metrics["scan_cache_evict_per_query"]["value"] == 0.0
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["jit_miss_per_query"]["value"] == 0
    # under ADAPTIVE_CAPACITY no hint could compact: none is declined
    assert metrics["compact_declined_per_query"]["value"] == 0.0


def test_control_float32_is_not_correct(run_py, capsys):
    rc, res = run(run_py, capsys, "--seed", "3600000311", "--seconds", "1",
                  "--trace", "0", "--control", "float32")
    assert res["correct"] is False
    c = res["checks"]["max_rel_err"]
    assert c["value"] > 10 * c["limit"] or res["checks"]["wrong_cells"]["value"]


def window(counters: dict, queries: int = 4) -> dict:
    return run_of(counters, latencies=(1.0,) * queries)


def test_new_metrics_arithmetic_and_nothing_to_read(monkeypatch):
    from igloo_tpu.utils import tracing
    routes, priced = (reader(n) for n in NEW_METRICS)
    program = {"engine.route_priced_bytes": 9_000_000_000}   # since the start
    monkeypatch.setattr(tracing, "counters", lambda: program)
    moved = {"engine.chunked_route": 2, "engine.grace_route": 1,
             "engine.host_route": 1, "engine.route_priced_bytes": 8_000_000}
    assert routes(window(moved)) == 1.0
    assert routes(window({"engine.chunked_route": 1}, queries=20)) == 0.05
    assert priced(window(moved)) == pytest.approx(2.0)
    # counters that did not move are absent from the deltas: 0, not nothing
    quiet = {"span_us.query": 7}
    assert routes(window(quiet)) == 0.0
    assert priced(window(quiet)) == 0.0
    assert routes(window(moved, queries=0)) is None
    assert priced(window(moved, queries=0)) is None
    # a program from before the ladder counted what it prices: nothing to
    # read, the metric is left out and nothing raises; its routes still read
    program.clear()
    program["engine.chunked_route"] = 5
    assert priced(window(moved)) is None
    assert routes(window({"engine.chunked_route": 4})) == 1.0


# --- embedded_resident holds the engine to its layout -------------------------

@pytest.fixture()
def staged(run_py, tmp_path):
    """SF 0.01 `lineitem`, staged as the cell stages it but in ten row
    groups (at SF10 the default row group makes 58), and q1's text."""
    spec = run_py.resolve(CELL)
    datagen = run_py.load_module("", spec["config"]["datagen"])
    table = datagen.gen_tables(sf=0.01, seed=3600000317,
                               tables=["lineitem"])["lineitem"]
    pq.write_table(table, str(tmp_path / "lineitem.parquet"),
                   row_group_size=table.num_rows // 10 + 1)
    return str(tmp_path), spec["traffic"]["queries"]


def test_a_query_taken_in_chunks_is_an_error(run_py, staged, monkeypatch):
    """An engine whose ladder takes q1 in chunks (as the parent of the PR
    that brought the cell would a `lineitem` file of sixteen columns: it
    prices the whole file against an eighth of the chip), at SF 0.01 under
    a forced chunk budget."""
    from igloo_tpu import engine
    stage_dir, queries = staged
    builder = run_py.load_module("deployments", "embedded_resident")
    dep = builder.build(stage_dir, ["lineitem"])
    try:
        for q in queries:                   # as the layout says: no error
            dep.clear_result_cache()
            dep.execute(q["text"])
            assert dep.last_info() == {"executed_on_device": True,
                                       "where": "tier device"}
    finally:
        dep.close()
    monkeypatch.setattr(engine, "QueryEngine", functools.partial(
        engine.QueryEngine, chunk_budget_bytes=1 << 16))
    dep = builder.build(stage_dir, ["lineitem"])
    try:
        dep.clear_result_cache()
        dep.execute(queries[0]["text"])
        with pytest.raises(RuntimeError, match="ran on tier chunked, not as "
                                               "one program on tier device"):
            dep.last_info()
        # and run.py's loop counts it: warm-up raises, the window fails it
        rec = run_py.one_query(dep, queries[0])
        assert rec["error"].startswith("RuntimeError: the query ran on tier")
    finally:
        dep.close()


def test_a_scan_cache_that_drops_columns_ends_the_run(run_py, capsys,
                                                      monkeypatch):
    """Seven columns of 2^16 lanes under a 256 KB cache (one row group:
    nothing to chunk by), through run.py: the run ends in warm-up."""
    from igloo_tpu.exec import cache
    monkeypatch.setattr(cache, "UNLIMITED_BUDGETS", (1 << 18, 2 << 30))
    with pytest.raises(RuntimeError, match="warm-up q1: RuntimeError: the "
                                           "scan cache dropped"):
        run(run_py, capsys, "--seed", "3600000313", "--seconds", "1",
            "--trace", "0")
