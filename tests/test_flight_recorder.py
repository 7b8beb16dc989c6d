"""Flight recorder: span identity, request-scope hygiene, cross-process
trace stitching over a 2-worker in-process cluster, the GRACE prefetch
overlap, and exports (system.query_traces / trace action /
IGLOO_TRACE_DIR)."""
import json
import time

import numpy as np
import pyarrow as pa
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.cluster import rpc
from igloo_tpu.cluster.client import DistributedClient
from igloo_tpu.cluster.coordinator import CoordinatorServer
from igloo_tpu.cluster.worker import Worker
from igloo_tpu.engine import QueryEngine
from igloo_tpu.utils import flight_recorder, stats, tracing


# --- span identity + scope hygiene (no cluster needed) -----------------------


def test_spans_carry_identity_and_epoch():
    with tracing.span("query") as outer:
        with tracing.span("execute", step=1) as inner:
            pass
    assert outer.span_id and inner.span_id
    assert outer.span_id != inner.span_id
    assert inner.parent_id == outer.span_id
    assert inner.attrs == {"step": 1}
    # epoch anchoring: perf_counter instants map near time.time()
    assert abs(tracing.epoch(outer.start) - time.time()) < 5.0


def test_request_scope_isolates_and_flushes():
    """Satellite: a reused server thread must neither accumulate spans
    toward the deque bound nor interleave spans from unrelated requests."""
    tracing.reset()
    with tracing.span("query"):
        pass
    before = len(tracing.roots())
    tr1 = flight_recorder.Trace(qid="a")
    tr2 = flight_recorder.Trace(qid="b")
    for tr, name in ((tr1, "execute"), (tr2, "fetch")):
        with flight_recorder.request_scope(tr, "query", proc="p"):
            with tracing.span(name):
                pass
    # each request's trace holds only ITS spans, under its own root
    n1 = {s["name"] for s in tr1.spans()}
    n2 = {s["name"] for s in tr2.spans()}
    assert n1 == {"query", "execute"} and n2 == {"query", "fetch"}
    # the handler thread's own roots were untouched by both requests
    assert len(tracing.roots()) == before


def test_request_scope_none_trace_still_resets():
    tracing.reset()
    with flight_recorder.request_scope(None, "query"):
        with tracing.span("execute"):
            pass
    assert len(tracing.roots()) == 0  # scope spans never leak to the thread


def test_adopted_thread_spans_land_in_trace():
    import threading
    tr = flight_recorder.Trace(qid="x")
    with flight_recorder.request_scope(tr, "query", proc="p"):
        ctx = flight_recorder.capture()

        def work():
            with flight_recorder.adopt(ctx):
                with tracing.span("grace.prefetch", partition=0):
                    pass
        t = threading.Thread(target=work)
        t.start()
        t.join()
    names = {s["name"] for s in tr.spans()}
    assert "grace.prefetch" in names


def test_local_engine_query_publishes_trace(engine_factory=None):
    e = QueryEngine(use_jit=False)
    e.register_table("t", pa.table({"a": [1, 2, 3]}))
    res = e.query("SELECT a FROM t ORDER BY a")
    assert res.stats.trace_id
    rec = flight_recorder.get_record(trace_id=res.stats.trace_id)
    assert rec is not None
    names = {s["name"] for s in rec["spans"]}
    assert "query" in names and "execute" in names
    # query_log joins on the same id
    log = e.execute("SELECT trace_id FROM system.query_log").to_pydict()
    assert res.stats.trace_id in log["trace_id"]
    # system.query_traces serves the spans
    rows = e.execute(
        "SELECT name FROM system.query_traces "
        f"WHERE trace_id = '{res.stats.trace_id}'").to_pydict()
    assert "execute" in rows["name"]


def test_trace_kill_switch(monkeypatch):
    monkeypatch.setenv("IGLOO_TRACE", "0")
    e = QueryEngine(use_jit=False)
    e.register_table("t", pa.table({"a": [1]}))
    before = len(flight_recorder.records())
    res = e.query("SELECT a FROM t")
    assert res.stats.trace_id == ""
    assert len(flight_recorder.records()) == before


def test_trace_dir_jsonl_export(tmp_path, monkeypatch):
    monkeypatch.setenv("IGLOO_TRACE_DIR", str(tmp_path / "traces"))
    e = QueryEngine(use_jit=False)
    e.register_table("t", pa.table({"a": [1, 2]}))
    e.execute("SELECT count(*) FROM t")
    lines = (tmp_path / "traces" / "traces.jsonl").read_text().splitlines()
    rec = json.loads(lines[-1])
    assert rec["trace_id"] and rec["spans"]
    assert {"name", "id", "proc", "t0", "t1"} <= set(rec["spans"][0])


def test_chrome_trace_export_shape():
    tr = flight_recorder.Trace(qid="q", sql="SELECT 1")
    with flight_recorder.request_scope(tr, "query", proc="coordinator"):
        with tracing.span("execute"):
            pass
    tr.add_span("execute_fragment", time.time(), time.time() + 0.01,
                proc="worker:w1")
    ct = flight_recorder.to_chrome_trace(tr.to_record())
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in ct["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in ms} == {"coordinator", "worker:w1"}
    assert len(xs) == 3
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    assert ct["otherData"]["trace_id"] == tr.trace_id


def test_explain_analyze_trace_pointer():
    e = QueryEngine(use_jit=False)
    e.register_table("t", pa.table({"a": [3, 1, 2]}))
    res = e.query("EXPLAIN ANALYZE SELECT a FROM t ORDER BY a")
    text = "\n".join(res.table.column("plan").to_pylist())
    assert f"-- trace: {res.stats.trace_id}" in text


def test_device_trace_bridge(monkeypatch):
    """The profiler bridge is always on: every span a query opens — the
    request-scope root and the programs' first calls and dispatches
    included — enters one `igloo:<name>` TraceAnnotation and leaves it, in
    order, whether or not a profiler session is running; results are what
    they are without it."""
    log = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    sql = "SELECT a, sum(v) AS s FROM t GROUP BY a ORDER BY a"
    plain = QueryEngine(use_jit=False)
    plain.register_table("t", pa.table({"a": [3, 1, 2], "v": [1.0, 2.0, 3.0]}))
    want = plain.execute(sql).to_pydict()
    e = QueryEngine(use_jit=False)
    e.register_table("t", pa.table({"a": [3, 1, 2], "v": [1.0, 2.0, 3.0]}))
    monkeypatch.setattr(tracing, "_TraceAnnotation", Recording)
    assert e.execute(sql).to_pydict() == want
    e.result_cache.clear()
    assert e.execute(sql).to_pydict() == want
    open_now = []
    for what, name in log:            # properly nested, all closed
        if what == "enter":
            open_now.append(name)
        else:
            assert open_now.pop() == name
    assert not open_now
    entered = [name for what, name in log if what == "enter"]
    assert entered.count("igloo:query") == 2
    assert {"igloo:parse", "igloo:bind+optimize", "igloo:execute",
            "igloo:program.first_call", "igloo:program.dispatch"} \
        <= set(entered)
    # the first execution traces every program, the second none
    second = entered[len(entered) - entered[::-1].index("igloo:parse") - 1:]
    assert "igloo:program.first_call" not in second


# --- cross-process stitching (2-worker in-process cluster) -------------------


@pytest.fixture(scope="module")
def trace_cluster():
    rng = np.random.default_rng(11)
    n = 600
    orders = pa.table({"o_id": np.arange(n, dtype=np.int64),
                       "o_cust": rng.integers(0, 48, n),
                       "o_total": np.round(rng.random(n) * 100, 2)})
    cust = pa.table({"c_id": np.arange(48, dtype=np.int64),
                     "c_name": pa.array([f"c{i:02d}" for i in range(48)])})
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0,
                              use_jit=False)
    caddr = f"127.0.0.1:{coord.port}"
    workers = [Worker(caddr, port=0, heartbeat_interval_s=0.5, use_jit=False)
               for _ in range(2)]
    try:
        for w in workers:
            w.start()
        deadline = time.time() + 20
        while len(coord.membership.live()) < 2 and time.time() < deadline:
            time.sleep(0.05)
        assert len(coord.membership.live()) == 2
        coord.register_table("orders", MemTable(orders, partitions=2))
        coord.register_table("cust", MemTable(cust, partitions=2))
        yield {"coord": coord, "addr": caddr}
    finally:
        for w in workers:
            w.shutdown()
        coord.shutdown()


SHUFFLE_SQL = ("SELECT o.o_id, c.c_name, o.o_total FROM orders o "
               "JOIN cust c ON o.o_cust = c.c_id ORDER BY o.o_id")


def test_distributed_trace_stitches_both_workers(trace_cluster):
    """Acceptance: ONE trace per distributed query containing coordinator
    dispatch spans and BOTH workers' fragment spans under a single
    trace_id, with monotonic parent/child nesting."""
    client = DistributedClient(trace_cluster["addr"])
    client.execute(SHUFFLE_SQL, qid="qtrace1", trace_id="cafe0123cafe0123")
    m = client.last_metrics()
    client.close()
    assert m["trace_id"] == "cafe0123cafe0123"
    raw = json.loads(rpc.flight_action_raw(
        trace_cluster["addr"], "trace",
        {"trace_id": "cafe0123cafe0123", "format": "raw"}))
    spans = raw["spans"]
    assert {s.get("proc") for s in spans
            if s["name"] == "execute_fragment"} == \
        {f"worker:{w['id']}" for w in json.loads(rpc.flight_action_raw(
            trace_cluster["addr"], "cluster_status"))["workers"]}
    names = {s["name"] for s in spans}
    assert {"query", "dispatch", "execute_fragment", "fragment.execute",
            "exchange.partition", "exchange.fetch", "serving.queue",
            "fetch"} <= names
    # monotonic nesting: every child sits inside its parent (same-host
    # clock here, so only float rounding needs an epsilon)
    by_id = {s["id"]: s for s in spans}
    for s in spans:
        p = by_id.get(s.get("parent"))
        if s.get("parent") is not None:
            assert p is not None, f"dangling parent on {s['name']}"
        if p is not None:
            assert s["t0"] >= p["t0"] - 0.005, (s["name"], p["name"])
            assert s["t1"] <= p["t1"] + 0.005, (s["name"], p["name"])
    # the worker trees hang under coordinator dispatch spans
    frag_roots = [s for s in spans if s["name"] == "execute_fragment"]
    assert all(by_id[s["parent"]]["name"] == "dispatch" for s in frag_roots)


def test_trace_action_chrome_export(trace_cluster):
    client = DistributedClient(trace_cluster["addr"])
    client.execute(SHUFFLE_SQL, qid="qtrace2")
    client.close()
    ct = json.loads(rpc.flight_action_raw(trace_cluster["addr"], "trace",
                                          {"qid": "qtrace2"}))
    assert isinstance(ct["traceEvents"], list) and ct["traceEvents"]
    procs = {e["args"]["name"] for e in ct["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "coordinator" in procs
    assert sum(p.startswith("worker:") for p in procs) == 2
    for e in ct["traceEvents"]:
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0


def test_distributed_query_log_carries_trace_id(trace_cluster):
    client = DistributedClient(trace_cluster["addr"])
    client.execute(SHUFFLE_SQL, qid="qtrace3", trace_id="beef4567beef4567")
    client.close()
    coord = trace_cluster["coord"]
    log = coord.engine.execute(
        "SELECT trace_id, tier FROM system.query_log").to_pydict()
    idx = log["trace_id"].index("beef4567beef4567")
    assert log["tier"][idx] == "distributed"
    # the stitched spans are queryable on the same key
    rows = coord.engine.execute(
        "SELECT name, proc FROM system.query_traces "
        "WHERE trace_id = 'beef4567beef4567'").to_pydict()
    assert "dispatch" in rows["name"]
    assert any(p.startswith("worker:") for p in rows["proc"])


# --- GRACE prefetch overlap --------------------------------------------------


def test_grace_pipeline_prefetch_overlaps_compute(tmp_path):
    """Satellite: the double-buffer's win is visible — prefetch spans (the
    upload of partition p+1) overlap compute spans (partition p's join)."""
    import pyarrow.parquet as pq

    from igloo_tpu.connectors.parquet import ParquetTable
    rng = np.random.default_rng(0)
    n = 30_000
    fact = pa.table({"fk": rng.integers(0, 400, n), "v": rng.random(n)})
    dim = pa.table({"k": np.arange(400, dtype=np.int64),
                    "tag": pa.array([f"t{i % 5}" for i in range(400)])})
    pf, pd_ = str(tmp_path / "fact.parquet"), str(tmp_path / "dim.parquet")
    pq.write_table(fact, pf, row_group_size=4000)
    pq.write_table(dim, pd_)
    e = QueryEngine(use_jit=False, chunk_budget_bytes=64 << 10)
    e.register_table("fact", ParquetTable(pf))
    e.register_table("dim", ParquetTable(pd_))
    res = e.query("SELECT tag, sum(v) AS s FROM fact JOIN dim ON fk = k "
                  "GROUP BY tag ORDER BY tag")
    assert res.stats.counters.get("grace.pipeline"), \
        "query did not run the double-buffered GRACE loop"
    rec = flight_recorder.get_record(trace_id=res.stats.trace_id)
    pre = [s for s in rec["spans"] if s["name"] == "grace.prefetch"]
    par = [s for s in rec["spans"] if s["name"] == "grace.partition"]
    assert pre and par
    overlapping = sum(1 for a in pre for b in par
                      if a["t0"] < b["t1"] and b["t0"] < a["t1"])
    assert overlapping >= 1, "no prefetch span overlapped a compute span"
