"""The SF10 q3 cell over the spec's sparse order keys: it resolves to its
files, which are the in-process SF10 configuration's but for the data
generator, the deployment, the key distribution and the source; a rehearsal
at SF 0.01 runs every phase and never passes; its control is not
`correct`; the two per-layer metrics it brought have their entries, their
arithmetic and nothing to read from a program without the counters; and
`embedded_fused` ends a run whose plan the fused compiler refuses, before
executing it."""
import os

import pytest
from conftest import BENCH, last_line
from test_span_metrics import reader, run_of

CELL, SMALL = ("tpch_sf10_embedded_speckeys.join_topk",
               "tpch_sf1_embedded.join_topk")
SIBLING = "tpch_sf10_embedded.scan_agg"
NEW_METRICS = {"direct_table_mb": ("MB", "lower"),
               "join_direct_per_query": ("count", "higher")}


def run(run_py, capsys, *args) -> tuple:
    rc = run_py.main(["--workload", CELL, "--rehearse-sf", "0.01", *args])
    return rc, last_line(capsys.readouterr().out)


def test_the_cell_resolves_to_its_files(run_py, bench_json):
    spec, small = run_py.resolve(CELL), run_py.resolve(SMALL)
    sibling = run_py.resolve(SIBLING)
    config = spec["config"]
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == "join_topk"
    assert len(spec["cell"]["why"]) <= 200
    # the in-process SF10 configuration, key for key, but for these
    assert list(config) == list(sibling["config"])
    differs = {k for k in config if config[k] != sibling["config"][k]}
    assert differs <= {"name", "source", "datagen", "deployment",
                       "key_distribution", "trace_seconds"}
    assert config["datagen"] == "datagen_spec_keys"
    assert config["deployment"] == "embedded_fused"
    assert "sparse" in config["key_distribution"]
    assert config["reduced"] == ["workers"]
    assert config["trace_seconds"] in (20, 40)
    # the SF1 q3 cell's traffic, queries, oracle and guarantees
    assert spec["traffic"] == small["traffic"]
    assert config["guarantees"] == small["config"]["guarantees"]
    entry = next(c for c in bench_json["configs"]
                 if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{config['name']}.json"
    # not latency_p95_s: ~20 queries a window make a 95th percentile a max
    assert {m["name"] for m in spec["end_to_end"]} == {"queries_per_s",
                                                       "setup_s"}
    mine = {m["name"] for m in spec["per_layer"]}
    assert mine >= {m["name"] for m in small["per_layer"]}
    assert mine >= {"h2d_mb_per_query", "scan_cache_evict_per_query",
                    "scan_load_ms", "route_priced_mb", *NEW_METRICS}
    for name, (unit, better) in NEW_METRICS.items():
        m = next(m for m in bench_json["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL, SMALL]
        assert (m["unit"], m["better"], m["layer"], m["moves"], m["source"]) \
            == (unit, better, "programs", "queries_per_s", "program_counter")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_every_phase_and_never_passes(run_py, capsys,
                                                     bench_json, trace):
    rc, res = run(run_py, capsys, "--seed", "4000000301", "--seconds", "1.5",
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
    # two positional joins a q3: orders (keys 1..60,000: 2^18 slots) and
    # customer (1..1,500: 2^11), four bytes a slot
    assert metrics["join_direct_per_query"]["value"] == 2.0
    assert metrics["direct_table_mb"]["value"] == pytest.approx(
        ((1 << 18) + (1 << 11)) * 4 / 1e6)
    assert metrics["offdevice_routes_per_query"]["value"] == 0.0
    assert metrics["scan_cache_evict_per_query"]["value"] == 0.0
    assert metrics["h2d_mb_per_query"]["value"] == 0.0
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["jit_miss_per_query"]["value"] == 0


def test_control_float32_is_not_correct(run_py, capsys):
    rc, res = run(run_py, capsys, "--seed", "4000000303", "--seconds", "1",
                  "--trace", "0", "--control", "float32")
    assert res["correct"] is False
    c = res["checks"]["max_rel_err"]
    assert c["value"] > 10 * c["limit"] or res["checks"]["wrong_cells"]["value"]


def test_new_metrics_arithmetic_and_nothing_to_read(monkeypatch):
    from igloo_tpu.utils import tracing
    table_mb, routes = (reader(n) for n in NEW_METRICS)
    program = {"join.direct_table_bytes": 1, "join.direct_routes": 1}
    monkeypatch.setattr(tracing, "counters", lambda: program)
    moved = {"join.direct_table_bytes": 4 * 553_648_128,
             "join.direct_routes": 8}
    assert table_mb(run_of(moved, latencies=(1.0,) * 4)) == \
        pytest.approx(553.648128)
    assert routes(run_of(moved, latencies=(1.0,) * 4)) == 2.0
    # a window in which no join took the route reads 0, not nothing
    quiet = {"span_us.query": 7}
    assert routes(run_of(quiet, latencies=(1.0,) * 4)) == 0.0
    assert table_mb(run_of(quiet, latencies=(1.0,) * 4)) == 0.0
    assert routes(run_of(moved, latencies=())) is None
    # a program that does not count the route: nothing to read, no raise
    program.clear()
    assert routes(run_of(moved, latencies=(1.0,) * 4)) is None
    assert table_mb(run_of(moved, latencies=(1.0,) * 4)) is None


# --- embedded_fused holds the engine to one program a query -------------------

@pytest.fixture()
def staged(run_py, tmp_path):
    """SF 0.01 of the tables q3 reads, staged as the cell stages them."""
    spec = run_py.resolve(CELL)
    kept, _ = run_py.stage(spec["config"], spec["traffic"], 0.01, 4000000307,
                           str(tmp_path))
    return str(tmp_path), sorted(kept), spec["traffic"]["queries"][0]


def test_a_plan_the_fused_compiler_refuses_ends_the_run(run_py, staged,
                                                        monkeypatch):
    """With the positional table's limit and the sorted probe's budget both
    under q3's widths, the fused compiler refuses the plan: the deployment
    says so before anything executes (no program runs, no staged fall), and
    run.py's loop counts it as a failed query."""
    from igloo_tpu.exec import join
    from igloo_tpu.exec.executor import Executor
    from igloo_tpu.utils import tracing
    stage_dir, tables, q3 = staged
    builder = run_py.load_module("deployments", "embedded_fused")
    dep = builder.build(stage_dir, tables)
    try:                                # as the layout says: no error
        rec = run_py.one_query(dep, q3)
        assert rec["error"] is None
        assert rec["info"] == {"executed_on_device": True,
                               "where": "tier device"}
    finally:
        dep.close()
    monkeypatch.setattr(join, "UNLIMITED_DIRECT_SLOTS", 1 << 10)
    monkeypatch.setattr(Executor, "_SPECULATIVE_JOIN_BUDGET", 1 << 10)
    dep = builder.build(stage_dir, tables)
    try:
        with tracing.counter_delta() as d:
            with pytest.raises(RuntimeError, match="the fused compiler "
                               "refuses the plan .join needs a host capacity"):
                dep.execute(q3["text"])
        assert "fused.execute" not in d and "span_us.staged.execute" not in d
        assert d.get("join.direct_over_budget") >= 1
        rec = run_py.one_query(dep, q3)
        assert rec["error"].startswith("RuntimeError: the fused compiler "
                                       "refuses the plan")
    finally:
        dep.close()


def test_a_fall_to_the_staged_executor_is_an_error(run_py, staged,
                                                   monkeypatch):
    """A plan that passed its verdict and later falls (here: the sentinel
    of a fused compile that never finished, armed twice) fails the query."""
    stage_dir, tables, q3 = staged
    builder = run_py.load_module("deployments", "embedded_fused")
    dep = builder.build(stage_dir, tables)
    try:
        dep.verdict(q3["text"])
        dep._judged.add(q3["text"])
        from igloo_tpu.exec import fused

        def refuse(self, plan, _retry=True):
            raise fused.FusionUnsupported("nofuse_sentinel")
        from igloo_tpu.exec.executor import Executor
        monkeypatch.setattr(Executor, "_fused_to_arrow", refuse)
        dep.clear_result_cache()
        dep.execute(q3["text"])
        with pytest.raises(RuntimeError, match="fall.s. to the staged "
                                               "executor during the query"):
            dep.last_info()
    finally:
        dep.close()
