"""`rpc_connects_per_query` (PR 35): its entry in BENCHMARK.json, its
arithmetic, nothing to read from a program that does not count its
connections, and a traced rehearsal of a served cell at SF 0.01 in which a
warm cluster's queries make no connection."""
import json

from conftest import last_line
from test_span_metrics import reader, run_of

NAME = "rpc_connects_per_query"
SERVED = ["tpch_sf1_served.scan_agg", "tpch_sf10_served.scan_agg",
          "tpch_sf10_served_qgen.scan_agg_streams"]


def window(counters: dict, queries: int = 4) -> dict:
    return run_of(counters, latencies=(1.0,) * queries)


def test_entry(run_py, bench_json):
    m = bench_json["per_layer"][-1]
    assert m == {"name": NAME, "unit": "count", "better": "lower",
                 "source": "program_counter", "layer": "front door",
                 "moves": "queries_per_s", "workloads": SERVED}
    for cell in SERVED:
        spec = run_py.resolve(cell)
        assert spec["per_layer"][-1]["name"] == NAME
        assert "queries_per_s" in {e["name"] for e in spec["end_to_end"]}
    # the bypass cells make no RPC and do not report it
    for cell in ("tpch_sf1_embedded.scan_agg", "tpch_sf1_embedded.join_topk"):
        assert NAME not in {p["name"] for p in run_py.resolve(cell)["per_layer"]}


def test_arithmetic_and_nothing_to_read(monkeypatch):
    from igloo_tpu.utils import tracing
    read = reader(NAME)
    monkeypatch.setattr(tracing, "counters", lambda: {"rpc.conn_opened": 3})
    assert read(window({"rpc.conn_opened": 16})) == 4.0   # each attempt connects
    assert read(window({"rpc.conn_opened": 1}, queries=20)) == 0.05
    # a counter that did not move in the window is absent from its deltas
    assert read(window({"rpc.conn_reused": 16})) == 0.0
    assert read(window({}, queries=0)) is None
    # a program from before the pool counts no connection: nothing to read,
    # the metric is left out and nothing raises
    monkeypatch.setattr(tracing, "counters", lambda: {"rpc.retries": 2})
    assert read(window({"rpc.retries": 1})) is None


def test_rehearsal_of_a_served_cell_makes_no_connection(run_py, capsys):
    rc = run_py.main(["--workload", SERVED[0], "--rehearse-sf", "0.01",
                      "--seed", "3500000311", "--seconds", "1.5",
                      "--trace", "1"])
    out = capsys.readouterr().out
    res = last_line(out)
    assert rc == 1 and res["failed"] == 0 and res["attempted"] >= 2
    assert res["metrics"][NAME] == {"value": 0.0, "unit": "count"}
    # and the window's counters say why: every attempt rode a kept connection
    moved = next(json.loads(ln) for ln in out.splitlines()
                 if '"counters"' in ln and '"phase": "window"' in ln)["counters"]
    assert moved["rpc.conn_reused"] >= 4 * res["attempted"]
    assert "rpc.conn_opened" not in moved
