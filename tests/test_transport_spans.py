"""Both ends of every Flight call have a clock (cluster/rpc.py CLOCKS): the
client end counts `rpc.calls.<kind>` and `rpc.client_us.<kind>` whether or
not a trace records, the server end `rpc.server_us.<kind>`, and what a
handler does around its request scope is `*.serve` self time — once: the
scope is the serve span's child. A scan's miss path splits into three
counters inside `program.scan_load`. An embedded query touches none of it."""
import importlib.util
import time
from pathlib import Path

import pyarrow.flight as flight
import pyarrow.parquet as pq
import pytest

from igloo_tpu.bench.tpch import QUERIES, gen_tables
from igloo_tpu.cluster import protocol, rpc
from igloo_tpu.cluster.client import DistributedClient
from igloo_tpu.cluster.coordinator import CoordinatorServer
from igloo_tpu.cluster.worker import Worker
from igloo_tpu.connectors.parquet import ParquetTable
from igloo_tpu.engine import QueryEngine
from igloo_tpu.utils import flight_recorder, tracing

from test_fault_cluster import _FlakyServer

REPO = Path(__file__).resolve().parent.parent

#: the calls one served scan query makes: the client's stream, a dispatch
#: per fragment (scan + merge), the probe and the open of the root stream,
#: the release
SIX = {"client.do_get": 1, "action.execute_fragment": 2, "action.ping": 1,
       "do_get": 1, "action.release": 1}
#: a worker's own loop, on its own clock: no query's calls (a worker some
#: earlier test file left running in this process may still beat)
BACKGROUND = {"action.heartbeat", "action.register_worker"}


def moved(before: dict, prefix: str) -> dict:
    """{name without prefix: delta since `before`} of the process's counters."""
    after = tracing.counters()
    return {k[len(prefix):]: v - before.get(k, 0) for k, v in after.items()
            if k.startswith(prefix) and v != before.get(k, 0)}


@pytest.fixture(scope="module")
def span_time():
    """benchmark/span_time.py, which groups the spans by layer for the
    ledger's metrics, loaded by path (benchmark/ is no package)."""
    spec = importlib.util.spec_from_file_location(
        "bench_span_time", REPO / "benchmark" / "span_time.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    path = tmp_path_factory.mktemp("transport") / "lineitem.parquet"
    pq.write_table(gen_tables(sf=0.005, seed=7)["lineitem"], path)
    return str(path)


@pytest.fixture(scope="module")
def cluster(lineitem):
    """One coordinator, one worker, one client, in this process; a beat far
    beyond the module, so that no `action.heartbeat` falls into a query."""
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=600.0)
    worker = Worker(f"127.0.0.1:{coord.port}", port=0,
                    heartbeat_interval_s=600.0)
    client = None
    try:
        worker.start()
        deadline = time.time() + 20
        while not coord.membership.live() and time.time() < deadline:
            time.sleep(0.02)
        assert len(coord.membership.live()) == 1
        coord.register_table("lineitem", ParquetTable(lineitem))
        client = DistributedClient(f"127.0.0.1:{coord.port}")
        for _ in range(3):      # programs traced, hints adopted, table resident
            client.execute(QUERIES["q6"])
        yield coord, worker, client
    finally:
        if client is not None:
            client.close()
        worker.shutdown()
        coord.shutdown()


def one_query(client) -> tuple:
    """-> (client latency in µs, {rpc.* deltas}, {span: self µs})."""
    before = dict(tracing.counters())
    t0 = time.perf_counter()
    out = client.execute(QUERIES["q6"])
    latency_us = (time.perf_counter() - t0) * 1e6
    assert out.num_rows == 1
    return latency_us, moved(before, "rpc."), moved(before, "span_us.")


def wire_us(r: dict) -> dict:
    """{kind: client end less server end}: the client's own `do_get` pairs
    with the coordinator's, which one registry adds to the worker's."""
    cl = {k[len("client_us."):]: v for k, v in r.items()
          if k.startswith("client_us.")}
    sv = {k[len("server_us."):]: v for k, v in r.items()
          if k.startswith("server_us.")}
    cl["do_get"] = cl.get("do_get", 0) + cl.pop("client.do_get", 0)
    assert set(cl) - BACKGROUND == set(sv) - BACKGROUND
    return {k: cl[k] - sv[k] for k in cl if k not in BACKGROUND}


@pytest.mark.parametrize("trace", ["1", "0"])
def test_a_served_query_counts_both_ends_of_its_six_calls(cluster, trace,
                                                          monkeypatch):
    monkeypatch.setenv(flight_recorder.TRACE_ENV, trace)
    _, _, client = cluster
    for _ in range(2):          # the second run of the query: the same six
        _, r, spans = one_query(client)
        calls = {k[len("calls."):]: v for k, v in r.items()
                 if k.startswith("calls.")
                 and k[len("calls."):] not in BACKGROUND}
        assert calls == SIX
        wire = wire_us(r)
        assert set(wire) == set(SIX) - {"client.do_get"}
        for kind, us in wire.items():
            assert us >= 0, (kind, r)
            assert r[f"server_us.{kind}"] > 0, (kind, r)
        # with no trace to record into, `fetch` and `dispatch` do not exist;
        # every attempt's `rpc` span does
        assert ("fetch" in spans) == (trace == "1")
        assert spans["rpc"] > 0
        for name in ("coordinator.serve", "worker.serve",
                     "coordinator.dispatch_fragment", "coordinator.release",
                     "coordinator.finalize"):
            assert spans.get(name, 0) > 0, (name, spans)


@pytest.mark.parametrize("trace", ["1", "0"])
def test_self_times_do_not_count_a_scope_twice(cluster, trace, monkeypatch,
                                               span_time):
    """A handler's `*.serve` span encloses the `query` / `execute_fragment`
    scope: were the scope not its child, the sum of self times would pass
    the client's latency by the whole query. And little is left dark: the
    spans and the wire together are most of the latency."""
    monkeypatch.setenv(flight_recorder.TRACE_ENV, trace)
    _, _, client = cluster
    latency = covered = wire = 0.0
    for _ in range(4):
        lat, r, spans = one_query(client)
        latency += lat
        covered += sum(us for name, us in spans.items()
                       if span_time.groups_of(name) != [span_time.WAIT])
        wire += sum(wire_us(r).values())
    assert covered <= latency
    assert covered + wire >= 0.8 * latency


def test_a_scope_is_the_child_of_the_span_around_it():
    before = dict(tracing.counters())
    with tracing.span("worker.serve") as outer:
        time.sleep(0.002)
        with flight_recorder.request_scope(None, "execute_fragment"):
            with tracing.span("fragment.execute"):
                time.sleep(0.01)
        time.sleep(0.002)
    d = moved(before, "span_us.")
    assert d["fragment.execute"] >= 10_000
    assert 4_000 <= d["worker.serve"] < 10_000
    assert sum(d.values()) == pytest.approx(outer.elapsed_s * 1e6, abs=5)
    # with no span around it a scope notes itself to nobody
    with flight_recorder.request_scope(None, "query"):
        pass
    assert not tracing._stack()


def test_a_failed_attempt_and_its_retry_are_two_calls_of_one_kind():
    srv = _FlakyServer(failures=1)
    fast = rpc.RpcPolicy(retries=2, backoff_base_s=0.01, backoff_jitter=0.0)
    try:
        with tracing.counter_delta() as delta:
            assert rpc.flight_action(f"127.0.0.1:{srv.port}", "ping",
                                     policy=fast) == {"ok": True}
        assert srv.calls == 2
        assert delta.get("rpc.calls.action.ping") == 2
        assert delta.get("rpc.retries") == 1
        assert delta.get("rpc.client_us.action.ping") > 0
        assert delta.get("span_us.rpc") > 0
        # a server of another program has no clock: the kind has one end
        assert "rpc.server_us.action.ping" not in delta
    finally:
        srv.shutdown()


def test_kinds_are_a_closed_set(cluster):
    _, worker, client = cluster
    assert rpc.action_kind("release", protocol.WORKER_ACTIONS) \
        == "action.release"
    assert rpc.action_kind("x'); drop", protocol.WORKER_ACTIONS) \
        == "action.unknown"
    before = dict(tracing.counters())
    with pytest.raises(flight.FlightServerError):
        rpc.flight_action(f"127.0.0.1:{worker.server.port}", "last_metrics")
    assert set(moved(before, "rpc.server_us.")) == {"action.unknown"}
    # the client's admin calls are kinds of their own
    before = dict(tracing.counters())
    client.last_metrics()
    r = moved(before, "rpc.")
    assert r["calls.client.action.last_metrics"] == 1
    assert r["client_us.client.action.last_metrics"] \
        >= r["server_us.action.last_metrics"] > 0


def test_a_scan_miss_splits_into_three_counters_and_a_hit_into_none(lineitem):
    engine = QueryEngine()
    engine.register_table("lineitem", ParquetTable(lineitem))
    with tracing.counter_delta() as cold:
        engine.execute(QUERIES["q6"])
    parts = [cold.get(f"scan_load.{p}_us") for p in ("read", "codec", "h2d")]
    assert all(us > 0 for us in parts), parts
    assert cold.get("scan_load.columns") == 4       # q6 reads four columns
    # counters, not child spans: the span's self time holds all three
    assert sum(parts) <= cold.get("span_us.program.scan_load")
    for _ in range(2):          # hints adopted, programs traced again
        engine.execute(QUERIES["q6"])
    with tracing.counter_delta() as warm:
        engine.execute(QUERIES["q6"])
    touched = [k for k in warm.values()
               if k.startswith(("rpc.", "scan_load."))
               or k in ("span_us.program.scan_load", "span_us.rpc")
               or k.endswith(".serve")]
    assert touched == []
    assert warm.get("span_us.query") > 0
