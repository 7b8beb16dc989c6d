"""The SF10 Q16 cell: it resolves to its configuration, traffic, query,
oracle, data generator and deployment files; its configuration is the
in-process SF10 Q13 configuration key for key, and differs from it in its
name, source, tables, key distribution, data generator and `assumed` lines;
its query is the specification's validation text; a rehearsal runs every
phase, never passes, and reads one DISTINCT aggregate and two positional
joins a query; the per-layer metric it brought lists it and reads nothing
from a program without its counter. Lists of other cells, and the metrics a
cell reports, are held by membership."""
import os

import pytest
from conftest import BENCH, last_line
from test_span_metrics import reader, run_of

CELL = "tpch_sf10_embedded_suppcnt.parts_supplier_relationship"
SIBLING = "tpch_sf10_embedded_custdist.customer_distribution"
NEW_METRICS = {"agg_distinct_per_query": "agg.distinct"}
GENERIC = ("device_busy_ms", "device_idle_pct", "device_wait_ms",
           "compiles_in_window", "jit_miss_per_query", "warmup_s",
           "peak_hbm_mb", "session_host_ms", "session_self_ms",
           "programs_host_ms", "retrace_ms", "unattributed_ms",
           "h2d_mb_per_query", "scan_cache_evict_per_query", "scan_load_ms",
           "bind_args_ms", "route_priced_mb", "scan_roofline_pct",
           "direct_table_mb", "join_direct_per_query",
           "agg_packed_per_query", "agg_direct_scatter_per_query",
           "agg_in_place_per_query")


def test_the_cell_resolves_to_its_files(run_py, bench_json):
    spec, sibling = run_py.resolve(CELL), run_py.resolve(SIBLING)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    assert cell["chips"] == 1
    assert cell["traffic"] == "parts_supplier_relationship"
    assert len(cell["why"]) <= 200
    assert list(config) == list(sibling["config"])
    differs = {k for k in config if config[k] != sibling["config"][k]}
    assert differs <= {"name", "source", "tables", "key_distribution",
                       "datagen", "assumed"}
    assert config["reduced"] == ["workers"] and config["scale_factor"] == 10
    assert config["deployment"] == "embedded_fused"
    assert config["datagen"] == "datagen_spec_supplier"
    assert set(config["tables"]) == {"part", "partsupp", "supplier"}
    assert any("4.2.3" in a and "S_COMMENT" in a for a in config["assumed"])
    for kind, name in (("deployments", config["deployment"]),
                       ("", config["datagen"])):
        assert os.path.isfile(os.path.join(BENCH, kind, f"{name}.py"))
    entry = next(c for c in bench_json["configs"]
                 if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "Q16" in entry["source"] and "2.4.16" in entry["source"]
    assert entry["reduced"] == config["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{config['name']}.json"
    # q16 alone, the spec's validation text, its own pandas reference
    assert (traffic["loop"], traffic["clients"], traffic["order"]) == \
        ("closed", 1, "round_robin")
    [q] = traffic["queries"]
    assert q["name"] == "q16" and q["oracle"] == "tpch_pandas_suppcnt:q16"
    assert q["reads"] == {
        "partsupp": {"ps_partkey": 4, "ps_suppkey": 4},
        "part": {"p_partkey": 4, "p_brand": 10, "p_type": 25, "p_size": 4},
        "supplier": {"s_suppkey": 4, "s_comment": 101}}
    text = " ".join(q["text"].split())
    for part in ("count(distinct ps_suppkey) as supplier_cnt",
                 "p_brand <> 'Brand#45'",
                 "p_type not like 'MEDIUM POLISHED%'",
                 "p_size in (49, 14, 23, 45, 19, 3, 36, 9)",
                 "s_comment like '%Customer%Complaints%'",
                 "order by supplier_cnt desc, p_brand, p_type, p_size;"):
        assert part in text
    assert "limit" not in text.lower()
    oracle = run_py.load_module("oracle", "tpch_pandas_suppcnt")
    assert callable(oracle.q16)
    assert {m["name"] for m in spec["end_to_end"]} >= {"queries_per_s",
                                                       "setup_s"}
    mine = {m["name"] for m in spec["per_layer"]}
    assert mine >= set(GENERIC) | set(NEW_METRICS)
    for name in NEW_METRICS:
        m = next(m for m in bench_json["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"]
        assert (m["unit"], m["better"], m["layer"], m["moves"], m["source"]) \
            == ("count", "higher", "programs", "queries_per_s",
                "program_counter")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


def test_no_other_cell_stages_what_q16_reads(run_py, bench_json):
    """`datagen_reads.read_columns` is a union over every traffic file: no
    other cell's traffic reads part, partsupp or supplier, so no other
    cell's staging moves."""
    for w in bench_json["workloads"]:
        if w["name"] == CELL:
            continue
        for q in run_py.resolve(w["name"])["traffic"]["queries"]:
            assert not {"part", "partsupp", "supplier"} & set(q["reads"])


def test_rehearsal_reads_one_distinct_aggregate_and_two_joins(run_py, capsys,
                                                             bench_json):
    rc = run_py.main(["--workload", CELL, "--rehearse-sf", "0.05",
                      "--seed", "4600000401", "--seconds", "1",
                      "--trace", "1"])
    res = last_line(capsys.readouterr().out)
    assert rc == 1 and res["correct"] is False
    failing = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failing == {"not_a_tpu_run"}               # all it compared held
    assert res["attempted"] >= 1 and res["failed"] == 0
    declared = {m["name"] for m in bench_json["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    metrics = res["metrics"]
    assert set(metrics) <= declared
    assert metrics["agg_distinct_per_query"]["value"] == 1.0
    assert metrics["join_direct_per_query"]["value"] == 2.0
    assert metrics["direct_table_mb"]["value"] > 0
    for name in ("scan_cache_evict_per_query", "h2d_mb_per_query",
                 "compiles_in_window", "jit_miss_per_query"):
        assert metrics[name]["value"] == 0


@pytest.mark.parametrize("name,counter", sorted(NEW_METRICS.items()))
def test_new_metric_arithmetic_and_nothing_to_read(monkeypatch, name,
                                                   counter):
    from igloo_tpu.utils import tracing
    read = reader(name)
    program = {counter: 1}
    monkeypatch.setattr(tracing, "counters", lambda: program)
    assert read(run_of({counter: 4}, latencies=(1.0,) * 4)) == 1.0
    # a window in which nothing took the path reads 0, not nothing
    assert read(run_of({"span_us.query": 7}, latencies=(1.0,) * 4)) == 0.0
    assert read(run_of({counter: 4}, latencies=())) is None
    # a program that does not count the path: nothing to read, no raise
    program.clear()
    assert read(run_of({counter: 4}, latencies=(1.0,) * 4)) is None
