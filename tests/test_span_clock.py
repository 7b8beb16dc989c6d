"""One clock for the program's spans: every span, however it is made, is a
`jax.profiler.TraceAnnotation` named `igloo:<name>` for its lifetime and
adds its SELF time to the counter `span_us.<name>` (utils/tracing.py
`open_span` / `close_span`). The per-layer metrics of benchmark/ read those
counters; the ledger's idle gaps read the profiler events."""
import glob
import importlib.util
import os
import time
from pathlib import Path

import numpy as np
import pyarrow as pa
import pytest

from igloo_tpu.engine import QueryEngine
from igloo_tpu.utils import flight_recorder, stats, tracing

REPO = Path(__file__).resolve().parent.parent

SPAN = "span_us."


def span_deltas(before: dict) -> dict:
    """{span name: microseconds added since `before`} (process-wide)."""
    after = tracing.counters()
    return {k[len(SPAN):]: v - before.get(k, 0) for k, v in after.items()
            if k.startswith(SPAN) and v != before.get(k, 0)}


def busy(seconds: float) -> None:
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        pass


# --- (a) self time ------------------------------------------------------------


def test_self_times_of_a_tree_sum_to_the_roots_duration():
    before = tracing.counters()
    with tracing.span("query") as root:
        busy(0.004)
        with tracing.span("bind+optimize") as a:
            busy(0.003)
        with tracing.span("execute") as b:
            busy(0.002)
            with tracing.span("fused.plan") as c:
                busy(0.005)
    d = span_deltas(before)
    assert set(d) == {"query", "bind+optimize", "execute", "fused.plan"}
    # one rounding to a microsecond per span
    assert sum(d.values()) == pytest.approx(root.elapsed_s * 1e6, abs=4)
    # a parent's counter leaves out what its children cover
    assert d["query"] == pytest.approx(
        (root.elapsed_s - a.elapsed_s - b.elapsed_s) * 1e6, abs=2)
    assert d["execute"] == pytest.approx(
        (b.elapsed_s - c.elapsed_s) * 1e6, abs=2)
    assert d["fused.plan"] == pytest.approx(c.elapsed_s * 1e6, abs=2)
    assert 3500 < d["query"] < root.elapsed_s * 1e6 - 9000


@pytest.mark.parametrize("recorded", [True, False],
                         ids=["IGLOO_TRACE=1", "IGLOO_TRACE=0"])
def test_request_scope_root_excludes_the_spans_inside_it(recorded):
    """The `query` / `execute_fragment` roots leave through the same exit:
    duration minus the span roots opened in the scope — with or without a
    trace to stitch into."""
    trace = flight_recorder.Trace() if recorded else None
    before = tracing.counters()
    t0 = time.perf_counter()
    with flight_recorder.request_scope(trace, "execute_fragment"):
        busy(0.003)
        with tracing.span("fragment.plan"):
            busy(0.004)
        with tracing.span("fragment.execute"):
            busy(0.002)
    wall = (time.perf_counter() - t0) * 1e6
    d = span_deltas(before)
    assert set(d) == {"execute_fragment", "fragment.plan",
                      "fragment.execute"}
    assert 2900 < d["execute_fragment"] < wall - 5900
    assert sum(d.values()) <= wall + 3
    if recorded:
        by_name = {s["name"]: s for s in trace.spans()}
        root = by_name["execute_fragment"]
        # the root is on the same anchored clock as its children
        assert root["t0"] <= by_name["fragment.plan"]["t0"]
        assert root["t1"] >= by_name["fragment.execute"]["t1"]


def test_spans_without_tracked_children_count_their_duration():
    """`Trace.add_span` (bounds, after the fact) and `Trace.span` (explicit,
    cross-thread) have no children on a thread-local stack: self time is
    the duration."""
    tr = flight_recorder.Trace()
    before = tracing.counters()
    now = time.time()
    tr.add_span("fetch", now - 0.25, now)
    with tr.span("dispatch") as sid:
        with tracing.span("rpc"):
            busy(0.003)
    d = span_deltas(before)
    assert d["fetch"] == 250_000
    assert d["dispatch"] >= d["rpc"] >= 2900        # not subtracted
    assert {s["name"]: s["id"] for s in tr.spans()}["dispatch"] == sid


def test_counters_do_not_depend_on_the_recorder(monkeypatch):
    """IGLOO_TRACE=0: nothing stitched or retained, the standalone engine's
    spans and their counters are all there."""
    monkeypatch.setenv("IGLOO_TRACE", "0")
    flight_recorder.clear()
    e = QueryEngine(use_jit=False)
    e.register_table("t", pa.table({"a": [3, 1, 2]}))
    before = tracing.counters()
    res = e.query("SELECT a FROM t ORDER BY a")
    d = span_deltas(before)
    assert {"parse", "query", "bind+optimize", "execute"} <= set(d)
    assert not res.stats.trace_id and flight_recorder.records() == []


# --- (b) the profiler's clock -------------------------------------------------


@pytest.fixture(scope="module")
def trace_reduce():
    """benchmark/trace_reduce.py, the reduction the ledger's idle gaps come
    from, loaded by path (benchmark/ is no package)."""
    spec = importlib.util.spec_from_file_location(
        "bench_trace_reduce", REPO / "benchmark" / "trace_reduce.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_spans_land_on_the_profilers_host_plane(tmp_path, trace_reduce):
    """Under a profiler session one query leaves `igloo:<span>` events on
    /host:CPU, each inside the annotation the test opened around the query:
    the spans are on the clock the device's ops are on."""
    import jax
    e = QueryEngine()
    rng = np.random.default_rng(5)
    e.register_table("t", pa.table({"k": rng.integers(0, 7, 500),
                                    "v": rng.random(500)}))
    sql = "SELECT k, sum(v) AS s FROM t GROUP BY k ORDER BY k"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tables = []
        for tag in ("test:first", "test:second"):
            e.result_cache.clear()
            with jax.profiler.TraceAnnotation(tag):
                tables.append(e.execute(sql))
    finally:
        jax.profiler.stop_trace()
    assert tables[0].to_pydict() == tables[1].to_pydict()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "the profiler wrote no .xplane.pb"
    host = [p for p in trace_reduce.load(files[0])
            if p["name"] == trace_reduce.HOST_PLANE]
    events = [ev for p in host for ln in p["lines"] for ev in ln["events"]]
    outer = {name: (start, start + dur) for name, start, dur in events
             if name.startswith("test:")}
    assert set(outer) == {"test:first", "test:second"}

    def inside(tag):
        t0, t1 = outer[tag]
        return {name for name, start, dur in events
                if name.startswith("igloo:")
                and t0 <= start and start + dur <= t1}
    always = {"igloo:parse", "igloo:query", "igloo:bind+optimize",
              "igloo:execute", "igloo:fused.plan", "igloo:fused.fetch",
              "igloo:fused.result"}
    assert inside("test:first") >= always | {"igloo:program.first_call"}
    second = inside("test:second")
    assert second >= always | {"igloo:program.dispatch"}
    assert "igloo:program.first_call" not in second


# --- (c) the served path ------------------------------------------------------


def test_distributed_query_moves_the_served_spans():
    """client -> coordinator -> two workers: the spans of the host phases
    move their counters — `program.first_call` with no QueryStats open on
    the worker (the served re-trace was never timed by the program)."""
    from igloo_tpu.catalog import MemTable
    from igloo_tpu.cluster.client import DistributedClient
    from igloo_tpu.cluster.coordinator import CoordinatorServer
    from igloo_tpu.cluster.worker import Worker
    rng = np.random.default_rng(11)
    n = 600
    orders = pa.table({"o_id": np.arange(n, dtype=np.int64),
                       "o_cust": rng.integers(0, 48, n),
                       "o_total": np.round(rng.random(n) * 100, 2)})
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0)
    caddr = f"127.0.0.1:{coord.port}"
    workers = [Worker(caddr, port=0, heartbeat_interval_s=0.5)
               for _ in range(2)]
    seen = []
    real = stats.record_compile
    try:
        for w in workers:
            w.start()
        deadline = time.time() + 20
        while len(coord.membership.live()) < 2 and time.time() < deadline:
            time.sleep(0.05)
        assert len(coord.membership.live()) == 2
        coord.register_table("orders", MemTable(orders, partitions=2))
        client = DistributedClient(caddr)
        before = tracing.counters()
        # the in-process workers' fragments run on Flight's threads here
        stats.record_compile = lambda s: seen.append(stats.current())
        got = client.execute("SELECT o_cust, sum(o_total) AS s FROM orders "
                             "GROUP BY o_cust ORDER BY o_cust")
        d = span_deltas(before)
        m = client.last_metrics()
        client.close()
    finally:
        stats.record_compile = real
        for w in workers:
            w.shutdown()
        coord.shutdown()
    assert got.num_rows == 48 and len(m["fragments"]) >= 2
    for name in ("client.execute", "client.wait", "parse", "bind+optimize",
                 "coordinator.plan", "coordinator.await_fragments",
                 "fragment.plan", "fragment.execute", "program.first_call"):
        assert d.get(name, 0) > 0, (name, d)
    # every first call of a program on a worker was timed with no
    # QueryStats open there
    assert seen and all(qs is None for qs in seen)
    # the client's wait covers the coordinator's and the workers' work
    assert d["client.wait"] > d["coordinator.await_fragments"]
