"""BENCHMARK.json keeps to the contract's characters, and every name in it
resolves to its file."""
import json
import os
import re

import pytest
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys(bench_json):
    assert set(bench_json) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert bench_json["paths"] == ["benchmark"]
    assert 1 <= bench_json["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_names_units_and_lines(bench_json):
    b = bench_json
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        got = [x["name"] for x in b[k]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    assert all(line_ok(w) for w in b["command"])
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert "setup_s" in [m["name"] for m in b["end_to_end"]]


def test_everything_named_resolves_to_its_file(bench_json):
    b = bench_json
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"] for w in b["workloads"]}
    assert {w["config"] for w in b["workloads"]} == set(configs)
    for c in b["configs"]:
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(
            BENCH, "deployments", conf["deployment"] + ".py"))
        assert os.path.isfile(os.path.join(BENCH, conf["datagen"] + ".py"))
    for w in b["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        for q in traffic["queries"]:
            assert os.path.isfile(os.path.join(BENCH, q["sql"]))
            module = q["oracle"].split(":")[0]
            assert os.path.isfile(os.path.join(BENCH, "oracle",
                                               module + ".py"))
            # q5 never finishes cold, q18 costs 5 min cold (PERF.md)
            assert q["name"] not in ("q5", "q18")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["end_to_end"]:
        assert os.path.isfile(os.path.join(BENCH, "end_to_end",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert line_ok(m["layer"]) and m["moves"] in e2e
        moved_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= moved_in, m["name"]


@pytest.mark.parametrize("cell_key", ["end_to_end", "per_layer"])
def test_every_cell_reports_enough(bench_json, cell_key):
    for w in bench_json["workloads"]:
        mine = [m["name"] for m in bench_json[cell_key]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= (2 if cell_key == "end_to_end" else 1)
        if cell_key == "end_to_end":
            assert "setup_s" in mine
