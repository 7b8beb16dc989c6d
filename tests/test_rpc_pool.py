"""The Flight connection pool of cluster/rpc.py: check out, check in, what
goes back and what never does, the bounds, and the failure model unmoved
(docs/distributed.md "Failure model": a first attempt may ride a kept
connection, every retry a new one).

Stub servers on loopback, no cluster but for the last three tests; the
counters are read process-wide where a server's threads bump them."""
import json
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.flight as flight
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.cluster import faults, rpc
from igloo_tpu.cluster.client import DistributedClient
from igloo_tpu.cluster.coordinator import CoordinatorServer
from igloo_tpu.cluster.worker import Worker
from igloo_tpu.utils import tracing

from test_fault_cluster import _FlakyServer

FAST = rpc.RpcPolicy(retries=3, backoff_base_s=0.01, backoff_jitter=0.0)


@pytest.fixture(autouse=True)
def _cold_pool():
    faults.clear()
    rpc.close_idle_connections()
    yield
    faults.clear()
    rpc.close_idle_connections()


class _Stub(flight.FlightServerBase):
    """Answers every action; serves `batches` record batches per do_get."""

    def __init__(self, port: int = 0, batches: int = 3, **kw):
        super().__init__(f"grpc+tcp://127.0.0.1:{port}", **kw)
        self.batches = batches
        self.calls = 0
        self.hold = None   # a threading.Barrier: actions meet there

    @property
    def addr(self) -> str:
        return f"127.0.0.1:{self.port}"

    def do_action(self, context, action):
        self.calls += 1
        if self.hold is not None:
            self.hold.wait(10)
        if action.type == "boom":
            raise flight.FlightServerError("the query itself failed")
        return [json.dumps({"ok": True}).encode()]

    def do_get(self, context, ticket):
        t = pa.table({"a": np.arange(4)})
        return flight.GeneratorStream(
            t.schema, (b for _ in range(self.batches) for b in t.to_batches()))


@pytest.fixture
def stub():
    srv = _Stub()
    try:
        yield srv
    finally:
        srv.shutdown()


def _moved(before: dict, name: str) -> int:
    return tracing.counters().get(name, 0) - before.get(name, 0)


# --- check out, check in -----------------------------------------------------


def test_second_action_reuses_the_connection(stub):
    assert rpc.flight_action(stub.addr, "ping") == {"ok": True}
    assert rpc.idle_connections(stub.addr) == 1
    with tracing.counter_delta() as delta:
        assert rpc.flight_action(stub.addr, "ping") == {"ok": True}
    assert delta.get("rpc.conn_reused") == 1
    assert not delta.get("rpc.conn_opened")
    assert rpc.idle_connections(stub.addr) == 1
    # the pool keys by the NORMALIZED address: both spellings are one peer
    with tracing.counter_delta() as delta:
        rpc.flight_action_raw(f"grpc+tcp://{stub.addr}", "ping")
    assert delta.get("rpc.conn_reused") == 1


def test_failed_attempt_discards_and_every_retry_opens():
    srv = _FlakyServer(failures=2)
    addr = f"127.0.0.1:{srv.port}"
    try:
        with tracing.counter_delta() as delta:
            assert rpc.flight_action(addr, "ping", policy=FAST) == {"ok": True}
        assert srv.calls == 3
        assert delta.get("rpc.retries") == 2
        assert delta.get("rpc.conn_opened") == 3    # cold: 1 + 2 retries
        assert rpc.idle_connections(addr) == 1      # the one that succeeded
        # warm: the first attempt rides the kept connection, fails, and is
        # closed; the retry opens its own — never the pool's
        srv.failures_left = 1
        with tracing.counter_delta() as delta:
            assert rpc.flight_action(addr, "ping", policy=FAST) == {"ok": True}
        assert srv.calls == 5
        assert delta.get("rpc.retries") == 1
        assert delta.get("rpc.conn_reused") == 1
        assert delta.get("rpc.conn_opened") == 1
        assert rpc.idle_connections(addr) == 1
    finally:
        srv.shutdown()


def test_exhausted_retry_budget_leaves_nothing_idle():
    srv = _FlakyServer(failures=100)
    addr = f"127.0.0.1:{srv.port}"
    try:
        with pytest.raises(flight.FlightUnavailableError):
            rpc.flight_action(addr, "ping", policy=FAST.with_(retries=1))
        assert srv.calls == 2
        assert rpc.idle_connections() == 0
    finally:
        srv.shutdown()


def test_fatal_error_discards_without_retry(stub):
    rpc.flight_action(stub.addr, "ping")
    with tracing.counter_delta() as delta:
        with pytest.raises(flight.FlightServerError):
            rpc.flight_action(stub.addr, "boom", policy=FAST)
    assert stub.calls == 2 and not delta.get("rpc.retries")
    assert delta.get("rpc.conn_reused") == 1
    assert rpc.idle_connections(stub.addr) == 0


def test_retry_drops_the_peers_idle_connections(stub):
    """An injected client-side fault takes no connection; the retry it
    causes opens its own and first closes what the pool held for that peer,
    so an address never keeps more than were in use at once."""
    rpc.flight_action(stub.addr, "ping")
    faults.install("client.action.ping:error:1.0:1")
    with tracing.counter_delta() as delta:
        assert rpc.flight_action(stub.addr, "ping", policy=FAST) == {"ok": True}
    assert delta.get("rpc.retries") == 1
    assert delta.get("rpc.conn_opened") == 1
    assert not delta.get("rpc.conn_reused")
    assert rpc.idle_connections(stub.addr) == 1


# --- streams -----------------------------------------------------------------


def test_exhausted_stream_returns_its_connection(stub):
    schema, batches = rpc.flight_stream_batches(stub.addr, "t")
    assert rpc.idle_connections() == 0          # the stream holds it
    assert sum(b.num_rows for b in batches) == 12
    assert rpc.idle_connections(stub.addr) == 1
    with tracing.counter_delta() as delta:
        _, again = rpc.flight_stream_batches(stub.addr, "t")
        assert len(list(again)) == 3
    assert delta.get("rpc.conn_reused") == 1    # probe + do_get rode it
    assert not delta.get("rpc.conn_opened")
    assert rpc.idle_connections(stub.addr) == 1


@pytest.fixture
def closed(monkeypatch):
    """The connections the pool's code closed, in order."""
    seen = []
    quietly = rpc._close_quietly
    monkeypatch.setattr(rpc, "_close_quietly",
                        lambda c: (seen.append(c), quietly(c)))
    return seen


def test_stream_closed_after_first_batch_closes_its_connection(stub, closed):
    _, batches = rpc.flight_stream_batches(stub.addr, "t")
    assert next(batches).num_rows == 4
    batches.close()
    assert len(closed) == 1 and rpc.idle_connections() == 0
    # and the next call is answered, on a connection of its own
    with tracing.counter_delta() as delta:
        assert rpc.flight_action(stub.addr, "ping") == {"ok": True}
    assert delta.get("rpc.conn_opened") == 1


def test_stream_dropped_unstarted_closes_its_connection(stub, closed):
    import gc
    before = dict(tracing.counters())
    _, batches = rpc.flight_stream_batches(stub.addr, "t")
    del batches              # never started: only the finalizer can clean up
    gc.collect()
    assert len(closed) == 1 and rpc.idle_connections() == 0
    assert _moved(before, "rpc.conn_opened") == 1
    assert rpc.flight_action(stub.addr, "ping") == {"ok": True}


def test_stream_that_raises_closes_its_connection():
    class _Breaks(_Stub):
        def do_get(self, context, ticket):
            t = pa.table({"a": np.arange(4)})

            def gen():
                yield t.to_batches()[0]
                raise flight.FlightUnavailableError("holder died mid-stream")
            return flight.GeneratorStream(t.schema, gen())
    srv = _Breaks()
    try:
        _, batches = rpc.flight_stream_batches(srv.addr, "t")
        with pytest.raises(flight.FlightUnavailableError):
            list(batches)
        assert rpc.idle_connections() == 0
    finally:
        srv.shutdown()


def test_action_batch_returns_or_closes_its_connection(stub):
    bodies = list(rpc.flight_actions_raw(
        stub.addr, [("ping", None), ("ping", {"x": 1})]))
    assert [json.loads(b) for b in bodies] == [{"ok": True}] * 2
    assert rpc.idle_connections(stub.addr) == 1
    with tracing.counter_delta() as delta:
        gen = rpc.flight_actions_raw(stub.addr, [("ping", None), ("boom", None)])
        assert json.loads(next(gen)) == {"ok": True}
        with pytest.raises(flight.FlightServerError):
            next(gen)
    assert delta.get("rpc.conn_reused") == 1
    assert rpc.idle_connections() == 0


# --- threads, bounds, peers that come and go ---------------------------------


def test_threads_hold_distinct_connections(stub):
    n = 4
    stub.hold = threading.Barrier(n)   # no action answers before all n arrive
    before = dict(tracing.counters())

    def wave():
        errs = []

        def call():
            try:
                rpc.flight_action(stub.addr, "ping", timeout_s=20.0)
            except Exception as ex:   # surfaced below
                errs.append(ex)
        ts = [threading.Thread(target=call) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert not errs
    wave()
    assert _moved(before, "rpc.conn_opened") == n
    assert rpc.idle_connections(stub.addr) == n
    wave()                              # all n idle ones go out again
    assert _moved(before, "rpc.conn_opened") == n
    assert _moved(before, "rpc.conn_reused") == n
    assert rpc.idle_connections(stub.addr) == n


def test_stress_no_connection_is_shared_or_lost(stub, closed, monkeypatch):
    """More threads than cores, a short switch interval, a bound small
    enough to evict: a connection is one holder's at a time, and every one
    made ends idle or closed."""
    import sys
    monkeypatch.setattr(rpc._ConnPool, "MAX_IDLE", 5)
    before = dict(tracing.counters())
    in_use, seen_twice, errs = set(), [], []
    guard = threading.Lock()

    def work():
        try:
            for i in range(40):
                lease = rpc._Lease(stub.addr)
                with guard:
                    if id(lease.client) in in_use:
                        seen_twice.append(id(lease.client))
                    in_use.add(id(lease.client))
                list(lease.client.do_action(flight.Action("ping", b"")))
                with guard:
                    in_use.discard(id(lease.client))
                lease.discard() if i % 7 == 3 else lease.release()
                rpc.flight_action(stub.addr, "ping")
        except Exception as ex:
            errs.append(ex)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=work) for _ in range(24)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert not errs and not seen_twice
    assert stub.calls == 24 * 40 * 2
    idle = rpc.idle_connections()
    assert 1 <= idle <= 5
    assert _moved(before, "rpc.conn_opened") == len(closed) + idle
    assert len(set(map(id, closed))) == len(closed)     # none closed twice


def test_peer_restarted_on_its_port_is_answered():
    srv = _Stub()
    port, addr = srv.port, srv.addr
    try:
        rpc.flight_action(addr, "ping")
        assert rpc.idle_connections(addr) == 1
        srv.shutdown()
        # down: the kept connection fails, the retries' new ones too
        with pytest.raises(flight.FlightUnavailableError):
            rpc.flight_action(addr, "ping", policy=FAST.with_(retries=1))
        assert rpc.idle_connections() == 0
        srv = _Stub(port)
        assert rpc.flight_action(addr, "ping") == {"ok": True}
        # restarted UNDER a kept connection: the default policy answers
        srv.shutdown()
        srv = _Stub(port)
        assert rpc.flight_action(addr, "ping") == {"ok": True}
        assert srv.calls == 1
    finally:
        srv.shutdown()


def test_lru_bound_over_many_addresses(monkeypatch):
    monkeypatch.setattr(rpc._ConnPool, "MAX_IDLE", 4)
    servers = [_Stub() for _ in range(7)]
    try:
        for s in servers:
            rpc.flight_action(s.addr, "ping")
        assert rpc.idle_connections() == 4
        assert [rpc.idle_connections(s.addr) for s in servers] == \
            [0, 0, 0, 1, 1, 1, 1]
        # a reuse refreshes its address: the next eviction takes another's
        rpc.flight_action(servers[3].addr, "ping")
        rpc.flight_action(servers[0].addr, "ping")
        assert [rpc.idle_connections(s.addr) for s in servers] == \
            [1, 0, 0, 1, 0, 1, 1]
    finally:
        for s in servers:
            s.shutdown()


def test_kept_connection_carries_no_token(monkeypatch):
    monkeypatch.setenv(rpc.AUTH_TOKEN_ENV, "s3cret")
    srv = _Stub(middleware=rpc.server_middleware())
    try:
        assert rpc.flight_action(srv.addr, "ping") == {"ok": True}
        monkeypatch.delenv(rpc.AUTH_TOKEN_ENV)
        with tracing.counter_delta() as delta:
            with pytest.raises(flight.FlightUnauthenticatedError):
                rpc.flight_action(srv.addr, "ping", policy=FAST)
        assert delta.get("rpc.conn_reused") == 1   # the SAME connection
        assert rpc.idle_connections() == 0
    finally:
        srv.shutdown()


# --- a real cluster ----------------------------------------------------------


def _cluster():
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0,
                              use_jit=False)
    # a heartbeat far beyond the test: its thread must not check a
    # connection in behind a shutdown
    worker = Worker(f"127.0.0.1:{coord.port}", port=0,
                    heartbeat_interval_s=60.0, use_jit=False)
    worker.server._mesh_setting = None
    worker.start()
    deadline = time.time() + 20
    while not coord.membership.live() and time.time() < deadline:
        time.sleep(0.02)
    assert len(coord.membership.live()) == 1
    rng = np.random.default_rng(5)
    coord.register_table("t", MemTable(pa.table({
        "k": rng.integers(0, 8, 20_000),
        "v": rng.random(20_000)}), partitions=2))
    return coord, worker


SQL = "SELECT k, COUNT(*) AS n, SUM(v) AS s FROM t GROUP BY k ORDER BY k"


def test_served_query_on_a_warm_cluster_opens_no_connection():
    coord, worker = _cluster()
    try:
        with DistributedClient(f"127.0.0.1:{coord.port}") as client:
            want = client.execute(SQL)
            before = dict(tracing.counters())
            for _ in range(3):
                assert client.execute(SQL).equals(want)
            assert _moved(before, "rpc.conn_opened") == 0
            # per query: two or more dispatches, the root stream, a release
            assert _moved(before, "rpc.conn_reused") >= 3 * 4
            assert rpc.idle_connections(f"127.0.0.1:{worker.server.port}") >= 1
    finally:
        worker.shutdown()
        coord.shutdown()


@pytest.mark.parametrize("first", ["coordinator", "worker"])
def test_shutdown_leaves_the_pool_empty(first):
    coord, worker = _cluster()
    try:
        with DistributedClient(f"127.0.0.1:{coord.port}") as client:
            assert client.execute(SQL).num_rows == 8
        assert rpc.idle_connections() >= 2   # to the worker, to the coordinator
        (coord if first == "coordinator" else worker).shutdown()
        assert rpc.idle_connections() == 0
    finally:
        worker.shutdown()
        coord.shutdown()
    assert rpc.idle_connections() == 0
