"""The two cells of PR 34. `tpch_sf10_served_qgen.scan_agg_streams` resolves
to its files; the literals of its SQL texts are its traffic file's
parameters and its reference's arguments; a rehearsal at SF 0.01 runs every
phase and holds every check but `not_a_tpu_run`; its control is not
`correct`; `served_params` ends a run whose query of a known shape uploads a
column; the three metrics it brought read a number from the program and
nothing from one without the counters. `tpch_sf1_embedded.scan_agg` is the
`scan_agg` traffic on the embedded configuration, files that were there."""
import json
import os
import re

import pytest
from conftest import BENCH, ROOT, last_line
from test_span_metrics import reader

CELL = "tpch_sf10_served_qgen.scan_agg_streams"
BASE = "tpch_sf10_served.scan_agg"
FLOOR = "tpch_sf1_embedded.scan_agg"
NEW_METRICS = {"literal_shared_per_query": "count",
               "literal_keyed_per_query": "count", "bind_args_ms": "ms"}


def run(run_py, capsys, cell, *args) -> tuple:
    rc = run_py.main(["--workload", cell, "--rehearse-sf", "0.01", *args])
    return rc, last_line(capsys.readouterr().out)


def test_the_cell_resolves_to_its_files(run_py, bench_json):
    spec, base = run_py.resolve(CELL), run_py.resolve(BASE)
    config, was = spec["config"], base["config"]
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "scan_agg_streams"
    # `tpch_sf10_served` word for word, but for what it lists as assumed:
    # queries carry per-stream substitution parameters
    same = set(was) - {"name", "source", "deployment", "layout", "guarantees",
                       "reduced", "reduced_why", "assumed"}
    for key in same:
        assert config[key] == was[key], key
    assert config["scale_factor"] == config["published_scale_factor"] == 10.0
    assert config["deployment"] == "served_params"
    assert config["reduced"] == ["workers", "streams"]
    assert config["streams"] == 2 and "5.3.4" in config["published_streams"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    for clause in ("2.4.1.3", "2.4.6.3", "5.3"):
        assert clause in config["source"]
    assert len(config["source"]) <= 200
    gone = [a for a in was["assumed"] if a not in config["assumed"]]
    assert len(gone) == 1 and gone[0].startswith("fixed validation parameter")
    for key in ("answers", "float_rel_tol"):
        assert config["guarantees"][key] == was["guarantees"][key]
    assert config["guarantees"]["execution"].startswith(
        was["guarantees"]["execution"])
    assert "uploads no table column" in config["guarantees"]["execution"]
    # every per-layer metric of its sibling, then the three it brought
    names = [m["name"] for m in spec["per_layer"]]
    assert names == [m["name"] for m in base["per_layer"]]
    assert names[-3:] == list(NEW_METRICS)
    for name, unit in NEW_METRICS.items():
        m = next(m for m in bench_json["per_layer"] if m["name"] == name)
        assert m["unit"] == unit and m["layer"] == "programs"
        assert m["workloads"][0] == CELL and FLOOR in m["workloads"]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf10_served_qgen.json")) as f:
        assert json.load(f) == config


def test_the_floor_cell_is_made_of_files_that_were_there(run_py, bench_json):
    spec = run_py.resolve(FLOOR)
    assert spec["traffic"] == run_py.resolve(BASE)["traffic"]
    assert spec["config"] == run_py.resolve(
        "tpch_sf1_embedded.join_topk")["config"]
    assert {m["name"] for m in spec["end_to_end"]} == {"queries_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert {"scan_roofline_pct", "session_host_ms", "scan_load_ms",
            "h2d_mb_per_query", "peak_hbm_mb", "bind_args_ms"} <= names
    assert not names & {"frontdoor_ms", "fragment_ms", "worker_self_ms"}


def test_texts_parameters_and_reference_hold_the_same_values(run_py):
    traffic = run_py.resolve(CELL)["traffic"]
    assert [q["name"] for q in traffic["queries"]] == [
        "q6_s00", "q6_s01", "q1_s00", "q1_s01"]
    shape_of = run_py.load_module("deployments", "served_params").shape_of
    oracle = run_py.load_module("oracle", "tpch_pandas_params")
    calls = []
    for fn in ("q1", "q6"):      # what each entry point hands q1 / q6
        oracle.__dict__[fn] = (lambda name: lambda t, *a: calls.append(
            (name, a)))(fn)
    texts = {}
    for q in traffic["queries"]:
        p, sql = q["parameters"], q["text"]
        texts.setdefault(q["name"][:2], []).append(sql)
        module, func = q["oracle"].split(":")
        assert module == "tpch_pandas_params"
        getattr(oracle, func)(None)
        if q["name"].startswith("q1"):
            assert 60 <= p["DELTA"] <= 120                 # clause 2.4.1.3
            assert re.search(rf"INTERVAL '{p['DELTA']}' DAY", sql)
            assert calls[-1] == ("q1", (p["DELTA"],))
            continue
        year = int(p["DATE"][:4])                          # clause 2.4.6.3
        assert 1993 <= year <= 1997 and p["DATE"][4:] == "-01-01"
        assert 0.02 <= p["DISCOUNT"] <= 0.09 and p["QUANTITY"] in (24, 25)
        assert sql.count(f"DATE '{p['DATE']}'") == 2
        lo, hi = p["DISCOUNT"] - 0.01, p["DISCOUNT"] + 0.01
        assert f"BETWEEN {lo:.2f} AND {hi:.2f}" in sql
        assert f"l_quantity < {p['QUANTITY']}" in sql
        assert calls[-1] == ("q6", (year, p["DISCOUNT"], p["QUANTITY"]))
    run_py.load_module.cache_clear()
    # two streams: two texts of one shape per query, and two shapes in all
    for pair in texts.values():
        assert pair[0] != pair[1] and shape_of(pair[0]) == shape_of(pair[1])
    assert shape_of(texts["q1"][0]) != shape_of(texts["q6"][0])
    assert "'BUILDING'" in shape_of("WHERE s = 'BUILDING' AND d < DATE "
                                    "'1995-03-15' LIMIT 10")


@pytest.mark.parametrize("cell,trace", [(CELL, 0), (CELL, 1), (FLOOR, 1)])
def test_rehearsal_runs_every_phase_and_never_passes(run_py, capsys,
                                                     bench_json, cell, trace):
    rc, res = run(run_py, capsys, cell, "--seed", "3400000307",
                  "--seconds", "1.5", "--trace", str(trace))
    assert rc == 1 and res["correct"] is False
    failing = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failing == {"not_a_tpu_run"}               # all it compared held
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert res["attempted"] % (4 if cell == CELL else 2) == 0   # whole rounds
    if not trace:
        assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
        return
    metrics = res["metrics"]
    for name, unit in NEW_METRICS.items():
        assert metrics[name]["unit"] == unit
    # every query's scan fragment follows one under the other stream's
    # parameters; a repeated text (the floor cell) shares nothing
    assert metrics["literal_shared_per_query"]["value"] == \
        (1.0 if cell == CELL else 0.0)
    assert metrics["literal_keyed_per_query"]["value"] == 0.0
    assert 0 < metrics["bind_args_ms"]["value"] \
        < metrics["programs_host_ms"]["value"]
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["jit_miss_per_query"]["value"] == 0
    assert metrics["scan_cache_evict_per_query"]["value"] == 0.0
    assert metrics["h2d_mb_per_query"]["value"] < 0.01


def test_control_float32_is_not_correct(run_py, capsys):
    rc, res = run(run_py, capsys, CELL, "--seed", "3400000311", "--seconds",
                  "1", "--trace", "0", "--control", "float32")
    assert res["correct"] is False and res["attempted"] == 4
    c = res["checks"]["max_rel_err"]
    assert c["value"] > 10 * c["limit"] or res["checks"]["wrong_cells"]["value"]


def test_a_known_shape_that_uploads_a_column_ends_the_run(run_py, capsys,
                                                          monkeypatch):
    """What the parent does at SF10 on its second query: here the third
    query's upload is faked (q1 of stream 00 is a new shape and may load;
    q1 of stream 01 is a known one)."""
    served_params = run_py.load_module("deployments", "served_params")
    calls = {"n": 0}
    real = served_params._uploaded

    def uploaded():
        calls["n"] += 1     # read before and after each query
        return real() + (64 << 20 if calls["n"] >= 8 else 0)
    monkeypatch.setattr(served_params, "_uploaded", uploaded)
    with pytest.raises(RuntimeError, match="warm-up q1_s01: RuntimeError: a "
                                           "query whose shape this process "
                                           "had run uploaded"):
        run(run_py, capsys, CELL, "--seed", "3400000313", "--seconds", "1",
            "--trace", "0")
    assert calls["n"] == 8


def run_of(counters: dict, n: int = 4) -> dict:
    return {"queries": [{"name": "q", "latency_s": 1.0, "info": {}}] * n,
            "counters": counters, "trace": None}


def test_new_metrics_arithmetic_and_nothing_to_read(monkeypatch):
    from igloo_tpu.utils import tracing
    program = {"program.literal_args": 40, "span_us.program.bind_args": 9}
    monkeypatch.setattr(tracing, "counters", lambda: program)
    window = {"program.literal_shared": 4, "program.literal_keyed": 2,
              "span_us.program.bind_args": 1_000, "span_us.query": 5}
    assert reader("literal_shared_per_query")(run_of(window)) == 1.0
    assert reader("literal_keyed_per_query")(run_of(window)) == 0.5
    assert reader("bind_args_ms")(run_of(window)) == 0.25
    # counters that did not move in the window are absent from its deltas
    assert reader("literal_shared_per_query")(run_of({})) == 0.0
    assert reader("literal_keyed_per_query")(run_of({})) == 0.0
    assert reader("bind_args_ms")(run_of({"span_us.query": 5})) == 0.0
    # a program from before PR 34 has neither the counters nor the span:
    # nothing to read, the metric is left out and nothing raises
    monkeypatch.setattr(tracing, "counters", lambda: {"jit.miss": 3})
    for name in NEW_METRICS:
        assert reader(name)(run_of(window)) is None
