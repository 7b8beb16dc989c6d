"""The SF10 Q13 cell: it resolves to its configuration, traffic, query,
oracle, data generator and deployment files; its configuration is the
in-process spec-key SF10 configuration key for key but for its name, source,
data generator and one more `assumed` line; its data generator is the
spec-key one's but for the order comments and customer keys, drawn as the
spec draws them; a rehearsal runs every phase, never passes, and reads one
positional join and one scatter aggregate a query; the per-layer metric it
brought lists it, and reads nothing from a program without its counter.
Lists of other cells are held by membership."""
import os

import pytest
from conftest import BENCH, last_line
from test_span_metrics import reader, run_of

CELL = "tpch_sf10_embedded_custdist.customer_distribution"
SIBLING = "tpch_sf10_embedded_speckeys.join_topk"
NEW_METRICS = {"agg_direct_scatter_per_query": "agg.direct_scatter"}
GENERIC = ("device_busy_ms", "device_idle_pct", "device_wait_ms",
           "compiles_in_window", "jit_miss_per_query", "warmup_s",
           "peak_hbm_mb", "session_host_ms", "session_self_ms",
           "programs_host_ms", "retrace_ms", "unattributed_ms",
           "h2d_mb_per_query", "scan_cache_evict_per_query", "scan_load_ms",
           "offdevice_routes_per_query", "direct_table_mb",
           "join_direct_per_query", "scan_roofline_pct",
           "bind_args_ms", "route_priced_mb")


def test_the_cell_resolves_to_its_files(run_py, bench_json):
    spec, sibling = run_py.resolve(CELL), run_py.resolve(SIBLING)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    assert cell["chips"] == 1 and cell["traffic"] == "customer_distribution"
    assert len(cell["why"]) <= 200
    # the spec-key SF10 configuration, key for key, but for these
    assert list(config) == list(sibling["config"])
    differs = {k for k in config if config[k] != sibling["config"][k]}
    assert differs == {"name", "source", "assumed", "datagen"}
    assert config["assumed"][:-1] == sibling["config"]["assumed"]
    assert "4.2.2.14" in config["assumed"][-1]
    assert config["reduced"] == ["workers"] and config["scale_factor"] == 10
    assert config["deployment"] == "embedded_fused"
    assert config["datagen"] == "datagen_spec_text"
    for kind, name in (("deployments", config["deployment"]),
                       ("", config["datagen"])):
        assert os.path.isfile(os.path.join(BENCH, kind, f"{name}.py"))
    entry = next(c for c in bench_json["configs"]
                 if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "Q13" in entry["source"] and "2.4.13" in entry["source"]
    assert entry["reduced"] == config["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{config['name']}.json"
    # q13 alone, the spec's validation text, the pandas reference
    assert (traffic["loop"], traffic["clients"], traffic["order"]) == \
        ("closed", 1, "round_robin")
    [q] = traffic["queries"]
    assert q["name"] == "q13" and q["oracle"] == "tpch_pandas:q13"
    assert q["reads"] == {"customer": {"c_custkey": 4},
                          "orders": {"o_custkey": 4, "o_orderkey": 4,
                                     "o_comment": 79}}
    from igloo_tpu.bench.tpch import QUERIES
    assert q["text"].split() == QUERIES["q13"].split()
    oracle = run_py.load_module("oracle", "tpch_pandas")
    assert callable(oracle.q13)
    # ~15 queries a window: a 95th percentile would be the slowest one
    assert {m["name"] for m in spec["end_to_end"]} == {"queries_per_s",
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


def test_the_data_is_the_spec_keys_but_comments_and_customer_keys(run_py):
    """Every column the spec-key generator stages, value for value, but
    `o_comment` (19-78 characters of the grammar's text, one an order) and
    `o_custkey` (uniform over the keys that are not multiples of 3)."""
    import numpy as np
    import pyarrow.compute as pc
    text = run_py.load_module("", "datagen_spec_text")
    keys = run_py.load_module("", "datagen_spec_keys")
    tables = ["customer", "orders"]
    got = text.gen_tables(sf=0.02, seed=4200000407, tables=tables)
    base = keys.gen_tables(sf=0.02, seed=4200000407, tables=tables)
    for name in tables:
        assert got[name].column_names == base[name].column_names
        for col in got[name].column_names:
            same = got[name].column(col).equals(base[name].column(col))
            assert same == (col not in ("o_comment", "o_custkey")), col
    orders = got["orders"]
    comments = orders.column("o_comment")
    lengths = pc.utf8_length(comments).to_numpy()
    assert lengths.min() >= 19 and lengths.max() <= 78
    assert abs(lengths.mean() - 48.5) < 0.5
    assert pc.count_distinct(comments).as_py() > 0.99 * len(comments)
    share = pc.mean(pc.match_like(comments, "%special%requests%")).as_py()
    assert 0.005 < share < 0.02
    cust = orders.column("o_custkey").to_numpy()
    n_cust = got["customer"].num_rows
    assert cust.min() >= 1 and cust.max() <= n_cust
    assert not np.any(cust % 3 == 0)
    ones, twos = np.sum(cust % 3 == 1), np.sum(cust % 3 == 2)
    assert abs(ones - twos) < 0.05 * len(cust)


def test_staging_brings_the_comment(run_py):
    """`datagen_reads` keeps the union of every traffic file's reads, so
    the orders the q3 cells stage carry `o_comment` too."""
    reads = run_py.load_module("", "datagen_reads").read_columns()
    assert {"o_comment", "o_custkey", "o_orderkey"} <= reads["orders"]
    assert "c_custkey" in reads["customer"]


def test_rehearsal_reads_one_join_and_one_scatter(run_py, capsys,
                                                  bench_json):
    """At SF 0.5 (75,000 customers) the count per customer takes the
    big-segment branch, as at SF10."""
    rc = run_py.main(["--workload", CELL, "--rehearse-sf", "0.5",
                      "--seed", "4200000301", "--seconds", "1",
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
    for name in ("agg_direct_scatter_per_query", "join_direct_per_query"):
        assert metrics[name]["value"] == 1.0
    # q13 binds no scalar literal (`program.literal_args`, which the
    # reader asks for): nothing to read, though its LIKE pattern is keyed
    assert "literal_keyed_per_query" not in metrics
    assert metrics["route_priced_mb"]["value"] > 0
    assert metrics["bind_args_ms"]["value"] >= 0
    assert metrics["direct_table_mb"]["value"] > 0
    for name in ("offdevice_routes_per_query", "scan_cache_evict_per_query",
                 "h2d_mb_per_query", "compiles_in_window",
                 "jit_miss_per_query"):
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
