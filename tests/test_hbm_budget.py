"""The HBM budgets are shares of the device's memory (exec/cache.py
hbm_budgets), read by the engine and the worker alike; and what the scan
cache's budget decides on the served path: with room for the columns the
traffic reads a query uploads them once and then hits, without it every
query evicts and uploads again — the answers equal to the pandas oracle
both ways."""
import os
import re
import subprocess
import sys
import time

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.bench import tpch, tpch_pandas
from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import cache
from igloo_tpu.exec.cache import BatchCache
from igloo_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GB = 1 << 30


class _Device:
    """A real device whose memory_stats() is the test's."""

    def __init__(self, real, stats):
        self._real, self._stats = real, stats

    def memory_stats(self):
        return self._stats

    def __getattr__(self, name):
        return getattr(self._real, name)


def fake_limits(monkeypatch, *limits):
    """jax.local_devices() -> one device per entry of `limits`: a number is
    its `bytes_limit`, None a backend that reports no stats (XLA:CPU), {} one
    that reports stats without a limit."""
    real = jax.local_devices()[0]
    devs = [_Device(real, {"bytes_limit": x, "peak_bytes_in_use": 0}
                    if isinstance(x, int) else x) for x in limits]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)


@pytest.mark.parametrize("limits,want", [
    ((16 * GB,), (8 * GB, 2 * GB)),              # one v5e-sized chip
    ((16 * GB, 12 * GB, 16 * GB), (6 * GB, 3 * GB // 2)),  # the tightest decides
    ((None,), cache.UNLIMITED_BUDGETS),          # XLA:CPU: no stats at all
    (({},), cache.UNLIMITED_BUDGETS),            # stats without a limit
    ((16 * GB, None), cache.UNLIMITED_BUDGETS),
])
def test_budgets_are_shares_of_bytes_limit(monkeypatch, limits, want):
    fake_limits(monkeypatch, *limits)
    assert cache.hbm_budgets() == want


def test_the_cpu_backend_keeps_the_constants():
    """No CPU test changes its route: 1 GiB resident, 2 GiB monolithic."""
    assert cache.hbm_budgets() == (1 << 30, 2 << 30)
    eng = QueryEngine()
    assert eng.batch_cache.budget_bytes == 1 << 30
    assert eng.chunk_budget_bytes == 2 << 30


def test_engine_takes_the_shares_and_arguments_override(monkeypatch):
    fake_limits(monkeypatch, 16 * GB)
    eng = QueryEngine()
    assert eng.batch_cache.budget_bytes == 8 * GB
    assert eng.chunk_budget_bytes == 2 * GB
    eng = QueryEngine(cache_budget_bytes=5 << 20, chunk_budget_bytes=7 << 20)
    assert eng.batch_cache.budget_bytes == 5 << 20
    assert eng._chunk_budget() == 7 << 20
    eng = QueryEngine(chunk_budget_bytes=1 << 20)        # one override alone
    assert eng.batch_cache.budget_bytes == 8 * GB
    assert eng.chunk_budget_bytes == 1 << 20


def test_no_constant_hbm_budget_is_left():
    for path in ("igloo_tpu/engine.py", "igloo_tpu/cluster/worker.py"):
        with open(os.path.join(ROOT, path)) as f:
            text = f.read()
        assert not re.search(r"\b[12] << 30\b", text), path
        assert "ResidentCache()" in text, path


def test_construction_starts_no_backend():
    """The shares are read at the first put or routing decision: an engine
    or a coordinator on a TPU host does not claim the chip by being built
    (a worker beside it may need it first). And the coordinator's engine,
    which runs only the fallback, leaves the resident share to the worker."""
    code = (
        "import jax._src.xla_bridge as xb\n"
        "from igloo_tpu.cluster.coordinator import CoordinatorServer\n"
        "from igloo_tpu.engine import QueryEngine\n"
        "from igloo_tpu.exec.cache import ResidentCache\n"
        "eng = QueryEngine()\n"
        "coord = CoordinatorServer('grpc+tcp://127.0.0.1:0')\n"
        "assert not xb._backends, list(xb._backends)\n"
        "assert isinstance(eng.batch_cache, ResidentCache)\n"
        "assert coord.engine.batch_cache.budget_bytes == 1 << 30\n"
        "assert not xb._backends\n"
        "assert eng.chunk_budget_bytes == 2 << 30 and xb._backends\n"
        "coord.shutdown()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


def test_too_large_counts_an_entry_over_the_whole_budget():
    c = BatchCache(100)
    with tracing.counter_delta() as d:
        c.put_entry(("t", "a"), object(), "snap", 101, "t")
        c.put_entry(("t", "b"), object(), "snap", 100, "t")
    assert d.get("cache.too_large") == 1
    assert len(c) == 1 and c.nbytes == 100
    assert c.get(("t", "a"), "snap") is None
    assert "cache.evict" not in d


def test_scan_load_is_catalogued_and_in_exactly_one_group():
    """The span of a scan's miss path: in docs/observability.md's catalog
    and in one layer of the benchmark's span_layers.json (by its pattern)."""
    import json
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        text = f.read()
    start = text.index("### Span catalog")
    section = text[start:text.index("\n## ", start)]
    assert "| `program.scan_load` |" in section
    with open(os.path.join(ROOT, "benchmark", "span_layers.json")) as f:
        groups = json.load(f)["groups"]
    mine = [g for g, names in groups.items() if any(
        n == "program.scan_load"
        or (n.endswith(".*") and "program.scan_load".startswith(n[:-1]))
        for n in names)]
    assert mine == ["programs"]


# --- the served q1/q6 loop ----------------------------------------------------

READS = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
         "l_discount", "l_tax", "l_shipdate"]


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    """SF 0.01 `lineitem` as Parquet, and the oracle's answers on it."""
    table = tpch.gen_tables(sf=0.01, seed=20261001)["lineitem"]
    path = str(tmp_path_factory.mktemp("sf001") / "lineitem.parquet")
    pq.write_table(table, path)
    df = table.select(READS).to_pandas()
    epoch = np.datetime64("1970-01-01")
    df["l_shipdate"] = ((df["l_shipdate"].to_numpy().astype("datetime64[D]")
                         - epoch).astype(np.int64))
    t = {"lineitem": df}
    return path, table, tpch_pandas.q1(t), tpch_pandas.q6(t)


def assert_answers(got_q1: pa.Table, got_q6: pa.Table, want_q1, want_q6):
    g = got_q1.to_pandas()
    assert g["l_returnflag"].tolist() == want_q1["l_returnflag"].tolist()
    assert g["l_linestatus"].tolist() == want_q1["l_linestatus"].tolist()
    assert g["count_order"].tolist() == want_q1["count_order"].tolist()
    for col in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
                "avg_qty", "avg_price", "avg_disc"):
        np.testing.assert_allclose(g[col], want_q1[col], rtol=1e-9)
    assert got_q6.num_rows == 1
    np.testing.assert_allclose(got_q6.column(0)[0].as_py(), want_q6,
                               rtol=1e-9)


@pytest.mark.parametrize("bytes_limit,resident", [
    (16 * GB, True),        # the derived budget of a 16 GB chip: 8 GB
    (1 << 20, False),       # 512 KB: less than the seven columns' lanes
])
def test_served_scan_loop_under_the_cache_budget(monkeypatch, lineitem,
                                                 bytes_limit, resident):
    from igloo_tpu.cluster.client import DistributedClient
    from igloo_tpu.cluster.coordinator import CoordinatorServer
    from igloo_tpu.cluster.worker import Worker
    from igloo_tpu.connectors.parquet import ParquetTable
    path, table, want_q1, want_q6 = lineitem
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0)
    caddr = f"127.0.0.1:{coord.port}"
    worker = Worker(caddr, port=0, heartbeat_interval_s=0.5)
    scan_cache = worker.server._batch_cache
    with monkeypatch.context() as m:
        # the worker's cache reads its share of the device where it is first
        # asked for it
        fake_limits(m, bytes_limit)
        assert scan_cache.budget_bytes == bytes_limit // 2
    assert coord.engine.batch_cache.budget_bytes == 1 << 30
    client = None
    try:
        worker.start()
        deadline = time.time() + 20
        while not coord.membership.live() and time.time() < deadline:
            time.sleep(0.05)
        assert coord.membership.live()
        coord.register_table("lineitem", ParquetTable(path))
        client = DistributedClient(caddr)
        rounds = []
        for _ in range(3):
            before = tracing.counters()
            q1 = client.execute(tpch.QUERIES["q1"])
            assert len(client.last_metrics()["fragments"]) == 2
            q6 = client.execute(tpch.QUERIES["q6"])
            assert len(client.last_metrics()["fragments"]) == 2
            after = tracing.counters()
            rounds.append({k: after[k] - before.get(k, 0) for k in after
                           if after[k] != before.get(k, 0)})
            assert_answers(q1, q6, want_q1, want_q6)
    finally:
        if client is not None:
            client.close()
        worker.shutdown()
        coord.shutdown()
    # what the seven columns take on the device, at the least: the narrowest
    # carrier is a byte a row
    floor = table.num_rows * len(READS)
    first, later = rounds[0], rounds[1:]
    assert first["xfer.h2d_bytes"] > floor
    assert first.get("span_us.program.scan_load", 0) > 0
    assert "cache.too_large" not in first
    if resident:
        assert scan_cache.nbytes > floor
        for r in later:
            # only the merge fragments' dependency tables are uploaded
            assert r.get("cache.evict", 0) == 0
            assert r["xfer.h2d_bytes"] < 64 << 10
            assert r["cache.hit"] >= 11          # 7 + 4 base columns
    else:
        assert scan_cache.nbytes <= scan_cache.budget_bytes
        for r in later:
            assert r["cache.evict"] > 0
            assert r["xfer.h2d_bytes"] > floor
