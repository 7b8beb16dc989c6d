"""The six per-layer metrics of PR 38 — `rpc_calls_per_query`, `rpc_wire_ms`,
`rpc_handler_ms`, `release_ms`, `scan_load_read_ms`, `scan_load_h2d_ms` —:
their entries in BENCHMARK.json (found by name: later PRs append), their
arithmetic on a synthetic window with known answers, nothing to read from a
program without the counters, the harness's own calls in no sum, the new
span names in exactly one group each, and a traced rehearsal of a served
cell at SF 0.01."""
import json

import pytest
import span_time
from conftest import last_line
from test_span_metrics import catalog_names, reader, run_of

SERVED = ["tpch_sf1_served.scan_agg", "tpch_sf10_served.scan_agg",
          "tpch_sf10_served_qgen.scan_agg_streams"]
ENTRIES = {
    "rpc_calls_per_query": ("count", "program_counter", "transport"),
    "rpc_wire_ms": ("ms", "program_counter", "transport"),
    "rpc_handler_ms": ("ms", "program_span", "transport"),
    "release_ms": ("ms", "program_span", "front door"),
    "scan_load_read_ms": ("ms", "program_counter", "scan + codec"),
    "scan_load_h2d_ms": ("ms", "program_counter", "scan + codec"),
}

# a window of two queries; microseconds
WINDOW = {
    # the six calls of each query
    "rpc.calls.client.do_get": 2, "rpc.client_us.client.do_get": 60_000,
    "rpc.calls.action.execute_fragment": 4,
    "rpc.client_us.action.execute_fragment": 40_000,
    "rpc.server_us.action.execute_fragment": 36_000,
    "rpc.calls.action.ping": 2, "rpc.client_us.action.ping": 1_000,
    "rpc.server_us.action.ping": 100,
    "rpc.calls.do_get": 2, "rpc.client_us.do_get": 3_000,
    # one registry: the coordinator's 57,000 and the worker's 1,400
    "rpc.server_us.do_get": 58_400,
    "rpc.calls.action.release": 2, "rpc.client_us.action.release": 1_400,
    "rpc.server_us.action.release": 200,
    # the harness's own, once a query, and a worker's beat: in no sum
    "rpc.calls.client.action.last_metrics": 2,
    "rpc.client_us.client.action.last_metrics": 2_200,
    "rpc.server_us.action.last_metrics": 300,
    "rpc.calls.action.heartbeat": 1, "rpc.client_us.action.heartbeat": 900,
    "rpc.server_us.action.heartbeat": 500,
    # spans
    "span_us.coordinator.serve": 700,       # 300 of it last_metrics',
    "span_us.worker.serve": 2_000,          # 500 the heartbeat's
    "span_us.coordinator.dispatch_fragment": 1_100,
    "span_us.coordinator.release": 150, "span_us.coordinator.finalize": 450,
    "span_us.coordinator.plan": 9_999, "span_us.rpc": 99_999,
    # the merge fragment's dependency table, once a query
    "span_us.program.scan_load": 7_000,
    "scan_load.read_us": 40, "scan_load.codec_us": 4_000,
    "scan_load.h2d_us": 2_400, "scan_load.columns": 16,
}
WANT = {
    "rpc_calls_per_query": 6.0,
    # (60000 + 3000 - 58400) + (40000 - 36000) + 900 + 1200, over 2, in ms
    "rpc_wire_ms": 5.35,
    # 700 + 2000 + 1100 - (300 + 500), over 2
    "rpc_handler_ms": 1.5,
    # 1400 + 150 + 450, over 2
    "release_ms": 1.0,
    "scan_load_read_ms": 0.02,
    "scan_load_h2d_ms": 1.2,
}


@pytest.fixture
def counting(monkeypatch):
    """A program that has the counters: set-up has moved them."""
    from igloo_tpu.utils import tracing
    monkeypatch.setattr(tracing, "counters", lambda: dict(WINDOW))


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_entry(name, run_py, bench_json):
    mine = [m for m in bench_json["per_layer"] if m["name"] == name]
    assert len(mine) == 1
    m = mine[0]
    unit, source, layer = ENTRIES[name]
    assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
    assert m["better"] == "lower" and m["moves"] == "queries_per_s"
    assert set(m["workloads"]) >= set(SERVED)
    for cell in SERVED:
        spec = run_py.resolve(cell)
        assert name in {p["name"] for p in spec["per_layer"]}
        assert "queries_per_s" in {e["name"] for e in spec["end_to_end"]}
    # the embedded cells make no call and load no dependency table
    for cell in ("tpch_sf1_embedded.scan_agg", "tpch_sf1_embedded.join_topk"):
        assert name not in {p["name"]
                            for p in run_py.resolve(cell)["per_layer"]}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_arithmetic(name, counting):
    assert reader(name)(run_of(WINDOW)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_harness_and_the_heartbeat_are_in_no_sum(name, counting):
    mine = {k: v for k, v in WINDOW.items()
            if "last_metrics" not in k and "heartbeat" not in k}
    # their handlers were `*.serve` self time whole
    mine["span_us.coordinator.serve"] -= 300
    mine["span_us.worker.serve"] -= 500
    assert reader(name)(run_of(mine)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_nothing_to_read_without_the_counters(name, monkeypatch):
    """The parent of the PR that added them: the metric is left out and
    nothing raises."""
    from igloo_tpu.utils import tracing
    monkeypatch.setattr(tracing, "counters", lambda: {
        "rpc.conn_opened": 3, "rpc.retries": 1, "span_us.rpc": 5,
        "span_us.program.scan_load": 9})
    old = {"rpc.conn_reused": 8, "span_us.program.scan_load": 7_000,
           "span_us.coordinator.plan": 1_000}
    assert reader(name)(run_of(old)) is None
    assert reader(name)(run_of({})) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_window_without_queries_reads_nothing(name, counting):
    assert reader(name)(run_of(WINDOW, latencies=())) is None


def test_a_window_in_which_nothing_missed_reads_zero(counting):
    quiet = {k: v for k, v in WINDOW.items()
             if not k.startswith("scan_load.")}
    assert reader("scan_load_read_ms")(run_of(quiet)) == 0.0
    assert reader("scan_load_h2d_ms")(run_of(quiet)) == 0.0


def test_wire_by_kind_pairs_the_clients_stream_with_the_coordinators():
    import rpc_time
    by = rpc_time.wire_ms_by_kind(run_of(WINDOW))
    assert by == pytest.approx({"do_get": 2.3, "action.execute_fragment": 2.0,
                                "action.ping": 0.45, "action.release": 0.6})
    assert rpc_time.query_kinds(WINDOW) == [
        "action.execute_fragment", "action.ping", "action.release",
        "client.do_get", "do_get"]


def test_the_new_spans_have_exactly_one_layer_each():
    names = catalog_names()
    for name, group in (("coordinator.serve", "front door"),
                        ("coordinator.dispatch_fragment", "front door"),
                        ("coordinator.release", "front door"),
                        ("coordinator.finalize", "front door"),
                        ("worker.serve", "worker"), ("rpc", "wait")):
        assert name in names
        assert span_time.groups_of(name) == [group]


def test_rehearsal_of_a_served_cell_reads_all_six(run_py, capsys):
    rc = run_py.main(["--workload", SERVED[0], "--rehearse-sf", "0.01",
                      "--seed", "3800000311", "--seconds", "1.5",
                      "--trace", "1"])
    out = capsys.readouterr().out
    res = last_line(out)
    assert rc == 1 and res["failed"] == 0 and res["attempted"] >= 2
    got = {k: res["metrics"][k]["value"] for k in ENTRIES}
    assert got["rpc_calls_per_query"] == 6.0
    assert got["rpc_wire_ms"] > 0 and got["rpc_handler_ms"] > 0
    assert got["release_ms"] > 0
    # the merge fragment's dependency table, every query
    assert 0 < got["scan_load_read_ms"] + got["scan_load_h2d_ms"] \
        <= res["metrics"]["scan_load_ms"]["value"]
    moved = next(json.loads(ln) for ln in out.splitlines()
                 if '"counters"' in ln and '"phase": "window"' in ln)["counters"]
    n = res["attempted"]
    assert moved["rpc.calls.client.action.last_metrics"] == n
    assert moved["rpc.calls.action.execute_fragment"] == 2 * n
    assert moved["scan_load.columns"] >= n
