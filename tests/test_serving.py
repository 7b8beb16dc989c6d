"""Serving front-door tests (docs/serving.md): admission queue bounds,
retryable shed, weighted fair dequeue, HBM-gate arithmetic, the graceful-
degradation ladder, worker execution-slot bounds, and the
IGLOO_SERVING_QUEUE=0 kill switch — plus a hundreds-of-clients soak behind
`-m slow`.

Counter assertions diff absolute `tracing.counters()` snapshots (not
`counter_delta`): serving/worker bumps happen on Flight RPC threads, which
a thread-isolated delta on the test thread would never see.
"""
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.flight as flight
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.cluster import rpc, serving
from igloo_tpu.cluster.client import DistributedClient
from igloo_tpu.cluster.coordinator import CoordinatorServer
from igloo_tpu.cluster.serving import AdmissionController, ServerBusy
from igloo_tpu.cluster.worker import Worker, WorkerServer
from igloo_tpu.engine import QueryEngine
from igloo_tpu.errors import DeadlineExceededError, IglooError
from igloo_tpu.utils import stats, tracing


def _counter(name: str) -> int:
    return tracing.counters().get(name, 0)


def _wait_until(pred, timeout=5.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


# --- admission controller units ----------------------------------------------


def test_queue_bound_sheds_with_retry_after():
    c = AdmissionController(queue_depth=2, max_concurrency=1,
                            session_inflight=16)
    running = c.submit()
    waiters = []

    def enqueue():
        p = c.submit()
        waiters.append(p)
        p.release()  # one slot: each admitted waiter must free it

    ts = [threading.Thread(target=enqueue, daemon=True) for _ in range(2)]
    for t in ts:
        t.start()
    _wait_until(lambda: sum(c.snapshot()["queued"].values()) == 2,
                msg="two queued")
    with pytest.raises(ServerBusy) as ei:
        c.submit()
    msg = str(ei.value)
    assert serving.BUSY_MARKER in msg
    hint = serving.parse_retry_after(msg)
    assert hint is not None and 0 < hint <= 2.0
    running.release()
    for t in ts:
        t.join(timeout=5)
    _wait_until(lambda: len(waiters) == 2, msg="waiters admitted")
    snap = c.snapshot()
    assert snap["running"] == 0 and sum(snap["queued"].values()) == 0


def test_session_inflight_cap_sheds():
    c = AdmissionController(queue_depth=16, max_concurrency=8,
                            session_inflight=1)
    p = c.submit(session="dash")
    with pytest.raises(ServerBusy, match="dash"):
        c.submit(session="dash")
    # other sessions unaffected
    q = c.submit(session="other")
    p.release()
    q.release()
    # the capped session admits again after release
    c.submit(session="dash").release()


def test_weighted_fair_dequeue_starvation_free():
    """A saturating low-priority flood must not starve high priority, and
    high priority must not starve the flood either (weighted shares)."""
    c = AdmissionController(queue_depth=64, max_concurrency=1,
                            session_inflight=64, weights=[4, 1])
    gate = c.submit(priority=0)  # hold the single slot while queues fill
    order: list = []

    def client(pri):
        p = c.submit(priority=pri)
        order.append(pri)  # admissions are serialized (one slot)
        p.release()

    ts = [threading.Thread(target=client, args=(1,), daemon=True) for _ in range(8)]
    ts += [threading.Thread(target=client, args=(0,), daemon=True) for _ in range(4)]
    for t in ts:
        t.start()
    _wait_until(lambda: sum(c.snapshot()["queued"].values()) == 12,
                msg="12 queued")
    gate.release()
    for t in ts:
        t.join(timeout=10)
    assert len(order) == 12, order
    first6 = order[:6]
    # every high-priority query lands early (weight 4 vs 1)...
    assert [p for p in order if p == 0] == [0, 0, 0, 0]
    assert first6.count(0) == 4, order
    # ...but the flood still progresses while high priority is queued
    assert first6.count(1) >= 1, order


def test_hbm_gate_arithmetic():
    c = AdmissionController(queue_depth=8, max_concurrency=4,
                            session_inflight=16, hbm_budget_bytes=100)
    a = c.submit(predicted_hbm_bytes=60)
    assert a.reserve_bytes == 60 and not a.demote
    admitted: list = []

    def sub(pred):
        admitted.append(c.submit(predicted_hbm_bytes=pred))

    t = threading.Thread(target=sub, args=(50,), daemon=True)
    t.start()
    _wait_until(lambda: sum(c.snapshot()["queued"].values()) == 1,
                msg="50-byte query queued")
    time.sleep(0.1)
    # 60 + 50 > 100: stays queued until the reservation frees
    assert not admitted and c.snapshot()["hbm_reserved_bytes"] == 60
    a.release()
    t.join(timeout=5)
    assert len(admitted) == 1
    assert c.snapshot()["hbm_reserved_bytes"] == 50
    admitted[0].release()
    # predicted past the WHOLE budget: admitted alone, pre-flagged demote,
    # reservation clamped to the budget
    big = c.submit(predicted_hbm_bytes=500)
    assert big.demote and big.reserve_bytes == 100
    t2 = threading.Thread(target=sub, args=(10,), daemon=True)
    t2.start()
    time.sleep(0.15)
    assert len(admitted) == 1  # nothing runs beside the over-budget query
    big.release()
    t2.join(timeout=5)
    assert len(admitted) == 2
    admitted[1].release()


def test_expired_deadline_bypasses_queue():
    c = AdmissionController(queue_depth=1, max_concurrency=1)
    running = c.submit()
    # deadline already spent: no queueing, no shed — the executor's own
    # deadline accounting must produce the error
    p = c.submit(deadline=time.time() - 1.0)
    assert c.snapshot()["running"] == 1  # no slot consumed
    p.release()
    running.release()


def test_kill_switch_serializes():
    c = AdmissionController(queue_depth=0)
    assert not c.enabled
    peak = [0]
    cur = [0]
    lock = threading.Lock()

    def run():
        with c.submit():
            with lock:
                cur[0] += 1
                peak[0] = max(peak[0], cur[0])
            time.sleep(0.05)
            with lock:
                cur[0] -= 1

    ts = [threading.Thread(target=run, daemon=True) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    assert peak[0] == 1, "kill switch must serialize to one query at a time"


def test_predict_hbm_bytes_observed_and_first_sight(monkeypatch, tmp_path):
    from igloo_tpu.exec import hints
    e = QueryEngine(use_jit=False)
    n = 1000
    e.register_table("t", MemTable(pa.table(
        {"a": np.arange(n, dtype=np.int64)})))
    plan = e.plan("SELECT a FROM t")
    first = serving.predict_hbm_bytes(plan)
    assert first == 2 * n * 8  # decoded lanes x2 for intermediates
    fp = hints.plan_fp(plan)
    assert fp is not None
    hints.adaptive_store().observe(fp, peak_hbm_bytes=123456)
    assert serving.predict_hbm_bytes(plan) == 123456
    # kill switch falls back to the estimate
    monkeypatch.setenv("IGLOO_ADAPTIVE", "0")
    assert serving.predict_hbm_bytes(plan) == first


# --- coordinator front door (no workers) -------------------------------------


@pytest.fixture()
def front():
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", use_jit=False)
    rng = np.random.default_rng(9)
    n = 4000
    coord.register_table("t", MemTable(pa.table({
        "a": np.arange(n, dtype=np.int64),
        "g": rng.integers(0, 16, n),
        "v": rng.random(n)})))
    try:
        yield coord
    finally:
        coord.shutdown()


def test_local_fallback_honors_deadline(front):
    before = _counter("query.deadline_exceeded")
    with pytest.raises(DeadlineExceededError, match="deadline"):
        front.execute_sql("SELECT count(*) AS c FROM t", deadline_s=0.0)
    assert _counter("query.deadline_exceeded") == before + 1
    rec = stats.query_log()[-1].to_record()
    assert rec["status"] == "deadline_exceeded"


def test_demotion_ladder_reactive_oom(front):
    """An execution that OOMs is retried one rung down (constrained chunk
    budget) instead of failing; the counter and query-log column record it."""
    engine = front.engine
    original = engine._execute_plan

    def oom_unless_constrained(plan):
        if engine._chunk_budget() >= engine.chunk_budget_bytes:
            raise MemoryError("fake RESOURCE_EXHAUSTED")
        return original(plan)

    engine._execute_plan = oom_unless_constrained
    try:
        before = _counter("serving.demoted")
        out = front.execute_sql(
            "SELECT g, SUM(v) AS s FROM t GROUP BY g ORDER BY g")
        assert out.num_rows == 16
        assert _counter("serving.demoted") == before + 1
        rec = stats.query_log()[-1].to_record()
        assert rec["demoted"] == 1 and rec["status"] == "ok"
    finally:
        engine._execute_plan = original


def test_demotion_ladder_forced_low_hbm_budget(front):
    """The HBM gate pre-demotes a query predicted past the whole budget —
    it runs budget-constrained and still answers correctly."""
    front.admission.hbm_budget_bytes = 1 << 10
    try:
        before = _counter("serving.demoted")
        out = front.execute_sql("SELECT count(*) AS c FROM t")
        assert out.to_pydict() == {"c": [4000]}
        assert _counter("serving.demoted") == before + 1
    finally:
        front.admission.hbm_budget_bytes = 0


def test_non_select_statements_skip_admission(front):
    # metadata ops must work even when admission would shed every SELECT
    front.admission = AdmissionController(queue_depth=2, max_concurrency=1)
    hold = front.admission.submit()
    try:
        out = front.execute_sql("SHOW TABLES")
        assert "t" in out.column("table_name").to_pylist()
    finally:
        hold.release()


# --- Flight-level shed + retry ----------------------------------------------


def test_shed_is_retryable_over_flight(front):
    front.admission = AdmissionController(queue_depth=1, max_concurrency=1,
                                          session_inflight=16)
    addr = f"127.0.0.1:{front.port}"
    hold = front.admission.submit()
    filler: list = []
    t = threading.Thread(target=lambda: filler.append(
        front.admission.submit()), daemon=True)
    t.start()
    _wait_until(lambda: sum(
        front.admission.snapshot()["queued"].values()) == 1,
        msg="queue full")
    with DistributedClient(addr) as client:
        before = _counter("serving.shed")
        retries_before = _counter("client.busy_retries")
        t0 = time.perf_counter()
        with pytest.raises(IglooError, match="server busy"):
            client.execute("SELECT count(*) AS c FROM t", busy_wait_s=0.4)
        assert time.perf_counter() - t0 < 5.0
        assert _counter("serving.shed") > before
        assert _counter("client.busy_retries") > retries_before
        # raw Flight classification: shed is UNAVAILABLE, i.e. retryable
        raw = rpc.connect(addr)
        try:
            with pytest.raises(flight.FlightUnavailableError) as ei:
                raw.do_get(flight.Ticket(
                    b"SELECT count(*) AS c FROM t")).read_all()
            assert rpc.retryable(ei.value)
        finally:
            raw.close()
        # capacity frees -> the same client call now succeeds
        hold.release()
        t.join(timeout=5)
        for p in filler:
            p.release()
        got = client.execute("SELECT count(*) AS c FROM t", busy_wait_s=10.0)
        assert got.to_pydict() == {"c": [4000]}


# --- worker execution slots --------------------------------------------------


def _slot_worker(slots: int):
    server = WorkerServer("grpc+tcp://127.0.0.1:0", use_jit=False,
                          mesh=None, slots=slots)
    state = {"cur": 0, "peak": 0}
    lock = threading.Lock()

    def fake_fragment(frag_id, plan_json, addr_of, deadline, budget=None):
        with lock:
            state["cur"] += 1
            state["peak"] = max(state["peak"], state["cur"])
        time.sleep(0.15)
        with lock:
            state["cur"] -= 1
        return {"id": frag_id, "rows": 0, "elapsed_s": 0.0,
                "worker": server.worker_id}

    server._execute_fragment = fake_fragment
    return server, state


def test_worker_slot_bound_serializes_fragments():
    server, state = _slot_worker(slots=1)
    addr = f"127.0.0.1:{server.port}"
    try:
        errs: list = []

        def call(i):
            try:
                rpc.flight_action(addr, "execute_fragment",
                                  {"id": f"f{i}", "plan": {}})
            except Exception as ex:  # pragma: no cover - fails the assert
                errs.append(ex)

        ts = [threading.Thread(target=call, args=(i,), daemon=True)
              for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=10)
        assert not errs
        assert state["peak"] == 1, \
            "slot bound must serialize concurrent fragment executions"
        assert tracing.gauges().get("worker.slots_busy") == 0
    finally:
        server.shutdown()


def test_worker_slot_timeout_answers_retryably():
    server, state = _slot_worker(slots=1)
    addr = f"127.0.0.1:{server.port}"
    try:
        t = threading.Thread(target=lambda: rpc.flight_action(
            addr, "execute_fragment", {"id": "long", "plan": {}}),
            daemon=True)
        t.start()
        _wait_until(lambda: state["cur"] == 1, msg="slot occupied")
        before = _counter("worker.slot_timeouts")
        with pytest.raises(flight.FlightUnavailableError, match="slots"):
            rpc.flight_action(addr, "execute_fragment",
                              {"id": "starved", "plan": {},
                               "timeout_s": 0.02},
                              policy=rpc.default_policy().with_(retries=0))
        assert _counter("worker.slot_timeouts") == before + 1
        t.join(timeout=10)
    finally:
        server.shutdown()


# --- distributed result cache ------------------------------------------------


def test_distributed_result_cache_short_circuits(monkeypatch):
    monkeypatch.setenv("IGLOO_SERVING_RESULT_CACHE", "1")
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", use_jit=False,
                              worker_timeout_s=60.0)
    caddr = f"127.0.0.1:{coord.port}"
    worker = Worker(caddr, port=0, heartbeat_interval_s=0.5, use_jit=False)
    try:
        worker.start()
        _wait_until(lambda: len(coord.membership.live()) == 1,
                    timeout=10, msg="worker registered")
        n = 2000
        coord.register_table("orders", MemTable(pa.table({
            "k": np.arange(n, dtype=np.int64) % 50,
            "v": np.arange(n, dtype=np.float64)}), partitions=2))
        sql = "SELECT k, COUNT(*) AS c FROM orders GROUP BY k ORDER BY k"
        dq_before = _counter("coordinator.distributed_queries")
        first = coord.execute_sql(sql)
        assert _counter("coordinator.distributed_queries") == dq_before + 1
        hits_before = _counter("result_cache.hit")
        second = coord.execute_sql(sql)
        # served from the front-door cache: no new distributed execution
        assert _counter("coordinator.distributed_queries") == dq_before + 1
        assert _counter("result_cache.hit") == hits_before + 1
        assert second.to_pydict() == first.to_pydict()
        assert coord.executor.last_metrics.get("result_cache_hit") is True
        rec = stats.query_log()[-1].to_record()
        assert rec["tier"] == "result_cache"
        # source change invalidates: a re-registered table must re-execute
        coord.register_table("orders", MemTable(pa.table({
            "k": np.zeros(10, dtype=np.int64),
            "v": np.ones(10, dtype=np.float64)}), partitions=2))
        third = coord.execute_sql(sql)
        assert third.num_rows == 1
        assert _counter("coordinator.distributed_queries") == dq_before + 2
    finally:
        worker.shutdown()
        coord.shutdown()


# --- serving fault points ----------------------------------------------------


def test_serving_fault_points_count_as_shed(front):
    from igloo_tpu.cluster import faults
    faults.install("serving.admit:error:1.0:2", seed=3)
    try:
        before = _counter("serving.shed")
        for _ in range(2):
            with pytest.raises(flight.FlightUnavailableError):
                front.execute_sql("SELECT count(*) AS c FROM t")
        assert _counter("serving.shed") == before + 2
        # rule budget spent: the next query admits normally
        out = front.execute_sql("SELECT count(*) AS c FROM t")
        assert out.to_pydict() == {"c": [4000]}
    finally:
        faults.clear()


# --- review-pass regressions -------------------------------------------------


def test_barrier_prevents_big_head_starvation_and_demote_isolation():
    """A fairness-winning head that doesn't fit is a BARRIER (nothing
    admits past it, so sustained small traffic can't starve it), and an
    over-budget (demote) query runs truly alone — 0-reserve plans
    included."""
    c = AdmissionController(queue_depth=8, max_concurrency=4,
                            session_inflight=16, hbm_budget_bytes=100,
                            weights=[4, 2, 1])
    small = c.submit(predicted_hbm_bytes=30)            # tier 1, running
    got: dict = {}

    def sub(name, pred, pri):
        got[name] = c.submit(predicted_hbm_bytes=pred, priority=pri)

    threading.Thread(target=sub, args=("big", 500, 1), daemon=True).start()
    _wait_until(lambda: sum(c.snapshot()["queued"].values()) == 1,
                msg="big queued")
    threading.Thread(target=sub, args=("s2", 10, 1), daemon=True).start()
    threading.Thread(target=sub, args=("s0", 10, 0), daemon=True).start()
    # tier 0 is the fairness winner and fits -> admitted; tier 1's head
    # (big) is a barrier, so s2 behind it must NOT be admitted
    _wait_until(lambda: "s0" in got, msg="tier-0 small admitted")
    time.sleep(0.1)
    assert "big" not in got and "s2" not in got
    small.release()
    got["s0"].release()
    # drained to zero running: the over-budget head admits, ALONE —
    # s2 (10 bytes, would arithmetically fit) stays out while it runs
    _wait_until(lambda: "big" in got, msg="big admitted after drain")
    assert got["big"].demote
    time.sleep(0.1)
    assert "s2" not in got, "nothing may run beside a demote-flagged query"
    got["big"].release()
    _wait_until(lambda: "s2" in got, msg="s2 admitted after big released")
    got["s2"].release()


def test_peak_hbm_recorded_only_when_query_raises_watermark(monkeypatch):
    """The device watermark is process-cumulative: a query that did NOT
    raise it must not inherit the global peak (which would ratchet every
    recurring query's prediction past the budget and demote it forever)."""
    from igloo_tpu.exec import hints
    from igloo_tpu.utils import stats as stats_mod
    e = QueryEngine(use_jit=False)
    e.register_table("t", MemTable(pa.table(
        {"a": np.arange(100, dtype=np.int64)})))
    plan = e.plan("SELECT a FROM t")
    fp = hints.plan_fp(plan)
    readings = iter([500, 500])  # before == after: watermark not raised
    monkeypatch.setattr(stats_mod, "device_peak_hbm_bytes",
                        lambda: next(readings))
    e.execute("SELECT a FROM t")
    rec = hints.adaptive_store().observed(fp)
    assert not (rec or {}).get("peak_hbm_bytes")
    readings = iter([500, 800])  # this query RAISED the watermark
    e.result_cache.clear()
    e.execute("SELECT a FROM t")
    assert hints.adaptive_store().observed(fp)["peak_hbm_bytes"] == 800


def test_client_busy_retries_do_not_consume_transport_budget():
    cl = DistributedClient.__new__(DistributedClient)
    cl.addr = "fake"
    cl._policy = rpc.default_policy().with_(retries=1, backoff_base_s=0.01,
                                            backoff_jitter=0)
    calls = {"n": 0}

    class FakeReader:
        def read_all(self):
            return pa.table({"a": [1]})

    class FakeClient:
        def do_get(self, ticket, opts=None):
            calls["n"] += 1
            if calls["n"] <= 2:  # two sheds must not touch the retry budget
                raise flight.FlightUnavailableError(
                    "IGLOO_BUSY server busy (test); retry_after_s=0.01")
            if calls["n"] == 3:  # then one transient transport failure
                raise flight.FlightUnavailableError("transient blip")
            return FakeReader()

    cl._client = FakeClient()
    got = cl.execute("SELECT 1", busy_wait_s=5.0)
    assert got.num_rows == 1 and calls["n"] == 4


def test_worker_busy_requeues_without_eviction():
    """A saturated worker answers WORKER_BUSY before the dispatch deadline;
    the coordinator moves the fragment to another worker WITHOUT evicting
    the busy one."""
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", use_jit=False,
                              worker_timeout_s=60.0)
    caddr = f"127.0.0.1:{coord.port}"
    workers = [Worker(caddr, port=0, heartbeat_interval_s=0.5,
                      use_jit=False) for _ in range(2)]
    try:
        for w in workers:
            w.start()
        _wait_until(lambda: len(coord.membership.live()) == 2,
                    timeout=10, msg="workers registered")
        n = 1000
        coord.register_table("t", MemTable(pa.table({
            "k": np.arange(n, dtype=np.int64) % 10,
            "v": np.arange(n, dtype=np.float64)})))
        # a sort-over-scan plan fragments as ONE root fragment, assigned to
        # the FIRST worker in the planner's list: occupy every slot there
        # so exactly one busy wait (deadline/2 = 3s) precedes the requeue
        target_addr = [w.addr for w in coord.membership.live()][0]
        target = next(w for w in workers if w.address == target_addr)
        held = 0
        while target.server._slots.acquire(blocking=False):
            held += 1
        assert held >= 1
        before = _counter("coordinator.fragments_requeued_busy")
        out = coord.execute_sql(
            "SELECT k FROM t ORDER BY k LIMIT 5", deadline_s=6.0)
        assert out.num_rows == 5
        assert _counter("coordinator.fragments_requeued_busy") > before
        assert len(coord.membership.live()) == 2, \
            "busy worker must NOT be evicted"
    finally:
        for _ in range(held):
            target.server._slots.release()
        for w in workers:
            w.shutdown()
        coord.shutdown()


# --- config plumbing ---------------------------------------------------------


def test_serving_config_section_and_env_wins(tmp_path, monkeypatch):
    from igloo_tpu.config import Config
    p = tmp_path / "cfg.toml"
    p.write_text("""
[serving]
queue_depth = 7
max_concurrency = 2
session_inflight = 3
hbm_budget_bytes = 1024
weights = [5, 1]
""")
    cfg = Config.load(str(p))
    sv = cfg.serving
    assert (sv.queue_depth, sv.max_concurrency, sv.session_inflight,
            sv.hbm_budget_bytes, sv.weights) == (7, 2, 3, 1024, [5, 1])
    c = AdmissionController(queue_depth=sv.queue_depth,
                            max_concurrency=sv.max_concurrency,
                            session_inflight=sv.session_inflight,
                            hbm_budget_bytes=sv.hbm_budget_bytes,
                            weights=sv.weights)
    assert c.queue_depth == 7 and c.weights == (5, 1)
    # env beats config, [rpc]-style
    monkeypatch.setenv("IGLOO_SERVING_QUEUE", "11")
    c2 = AdmissionController(queue_depth=sv.queue_depth)
    assert c2.queue_depth == 11


# --- soak: hundreds of clients, 2 workers, fairness (slow tier) --------------


@pytest.mark.slow  # 40.1-52.2 s alone on the CPU (PR 32)
def test_concurrent_soak_throughput_and_fairness():
    """200 concurrent clients vs a 2-worker cluster: everything completes
    (throughput) and the weighted fair dequeue orders waits by tier. The
    queue is sized to hold the whole burst so admission order — not the
    priority-blind shed/retry lottery — decides latency; shedding itself
    is covered by the fast tests and scripts/serving_smoke.py."""
    import os
    os.environ["IGLOO_SERVING_QUEUE"] = "256"
    os.environ["IGLOO_SERVING_CONCURRENCY"] = "3"
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", use_jit=False,
                              worker_timeout_s=60.0)
    caddr = f"127.0.0.1:{coord.port}"
    workers = [Worker(caddr, port=0, heartbeat_interval_s=1.0,
                      use_jit=False) for _ in range(2)]
    try:
        for w in workers:
            w.start()
        _wait_until(lambda: len(coord.membership.live()) == 2,
                    timeout=15, msg="workers registered")
        rng = np.random.default_rng(2)
        n = 1000
        data = pa.table({"k": rng.integers(0, 40, n), "v": rng.random(n)})
        coord.register_table("orders", MemTable(data, partitions=2))
        sql = "SELECT k, COUNT(*) AS c FROM orders GROUP BY k ORDER BY k"
        local = QueryEngine(use_jit=False)
        local.register_table("orders", MemTable(data))
        want = local.execute(sql).to_pydict()
        N = 200
        by_tier: dict = {0: [], 1: [], 2: []}
        failures: list = []
        lock = threading.Lock()

        def one(i):
            pri = i % 3
            try:
                with DistributedClient(caddr) as c:
                    t0 = time.perf_counter()
                    got = c.execute(sql, priority=pri,
                                    session=f"s{i % 16}",
                                    busy_wait_s=300.0)
                    dt = time.perf_counter() - t0
                assert got.to_pydict() == want
                with lock:
                    by_tier[pri].append(dt)
            except Exception as ex:
                with lock:
                    failures.append(f"{i}: {ex}")

        ts = [threading.Thread(target=one, args=(i,), daemon=True)
              for i in range(N)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=600)
        wall = time.perf_counter() - t0
        assert not failures, failures[:5]
        done = sum(len(v) for v in by_tier.values())
        assert done == N, f"{done}/{N} finished"
        assert wall < 500, f"soak took {wall:.0f}s"
        # weighted fairness: interactive tier waits less than batch on
        # average; batch still completes (starvation-free by completion)
        mean0 = sum(by_tier[0]) / len(by_tier[0])
        mean2 = sum(by_tier[2]) / len(by_tier[2])
        assert mean0 < mean2, (mean0, mean2)
    finally:
        for w in workers:
            w.shutdown()
        coord.shutdown()
        os.environ.pop("IGLOO_SERVING_QUEUE", None)
        os.environ.pop("IGLOO_SERVING_CONCURRENCY", None)
