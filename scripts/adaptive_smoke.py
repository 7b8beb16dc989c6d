#!/usr/bin/env python
"""Adaptive hot-key salting smoke (scripts/validate.sh, docs/adaptive.md).

Spins a coordinator + 2 worker SUBPROCESSES (real parallelism — the skew fix
salting buys is cross-worker, and in-process worker threads would serialize
the split halves on the GIL) and runs a join whose probe side carries one
pathologically hot key (~98% of rows land in one hash bucket — exactly the
case docs/distributed.md used to document as unwinnable). The first run
records the skew sketch; the next plan salts the exchange. The smoke asserts
the full loop:

  1. the salted plan is CORRECT (identical to single-node execution),
  2. `adaptive.salted` > 0 and the hot bucket's work actually spread across
     BOTH workers,
  3. the salted run beats the unsalted plan (IGLOO_ADAPTIVE=0) on the same
     warmed cluster — skew goes from serialized-on-one-worker to split.

Scenario shape (why these numbers): the hot key is a SENTINEL absent from
the build side, so the hot rows join to nothing (no fanout explosion) and
all the skewed cost is the hot fragment's probe work — the thing salting
splits. Hot rows (~392k) pad to the 2^20 canonical capacity while the salted
halves (~196k) fit 2^18, so the split also shrinks padded work, not just
wall-clock placement. The build side is SHORT in rows but WIDE in bytes (pad
column), so the broadcast switch correctly declines (replicating it would
ship more bytes than the exchange) while per-bucket build work stays
negligible — the timed A/B isolates exactly the skew the salt fixes.

~2 min on the virtual CPU mesh (worker subprocesses jit-compile cold).

This is a CPU drive: the parent (which runs a coordinator engine) and both
worker subprocesses run with JAX_PLATFORMS=cpu — on a host with a chip they
would otherwise fight over it (a chip belongs to one process).
"""
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["IGLOO_TPU_COMPILE_CACHE"] = "0"
# repeated identical SQL must EXECUTE (this smoke asserts what execution
# did), not serve from the front-door result cache (docs/serving.md)
os.environ["IGLOO_SERVING_RESULT_CACHE"] = "0"
os.environ["JAX_PLATFORMS"] = "cpu"  # before jax starts; children get it too
import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402
import pyarrow.parquet as pq  # noqa: E402

import igloo_tpu.engine as _eng  # noqa: E402

_eng.DEFAULT_MESH = None

from igloo_tpu.cluster.client import DistributedClient  # noqa: E402
from igloo_tpu.cluster.coordinator import CoordinatorServer  # noqa: E402
from igloo_tpu.connectors.parquet import ParquetTable  # noqa: E402
from igloo_tpu.engine import QueryEngine  # noqa: E402
from igloo_tpu.exec import hints  # noqa: E402
from igloo_tpu.utils import tracing  # noqa: E402

HOT_SHARE = 0.98
HOT_KEY = 999_999       # matches NO build row: skew cost is pure probe work
N_PROBE = 400_000
N_BUILD = 8_000
PAD = 4096              # build bytes > probe bytes -> broadcast declines

SQL = ("SELECT o.o_cust, o.o_total, o.o_a, o.o_b, c.c_pad "
       "FROM orders o LEFT JOIN cust c ON o.o_cust = c.c_id")
COLS = ("o_cust", "o_total", "o_a", "o_b", "c_pad")


def _write_tables(tmp: str) -> tuple[str, str]:
    rng = np.random.default_rng(11)
    # ~98% of probe rows carry the sentinel -> one hash bucket dominates;
    # the rest spread over 10x the build keyspace (~10% of them match)
    keys = np.where(rng.random(N_PROBE) < HOT_SHARE, HOT_KEY,
                    rng.integers(0, N_BUILD * 10, N_PROBE)).astype(np.int64)
    orders = pa.table({"o_cust": keys,
                       "o_total": rng.integers(0, 10_000, N_PROBE),
                       "o_a": rng.integers(0, 1 << 40, N_PROBE),
                       "o_b": rng.integers(0, 1 << 40, N_PROBE)})
    cust = pa.table({"c_id": np.arange(N_BUILD, dtype=np.int64),
                     "c_pad": pa.array(["x" * PAD] * N_BUILD)})
    po = os.path.join(tmp, "orders.parquet")
    pc = os.path.join(tmp, "cust.parquet")
    # ONE row group per table -> one exchange fragment per side, so the hot
    # bucket arrives as a single ~392k-row slice (canonical capacity 2^20)
    # and the salted halves as ~196k slices (2^18): the salt shrinks the
    # PADDED join shape 4x, not just the row count. Split row groups would
    # pad each half-slice back to the full slice's 2^18 band and the A/B
    # would measure pure placement, which CPU contention then eats.
    pq.write_table(orders, po)
    pq.write_table(cust, pc)
    return po, pc


def _norm(table) -> list:
    d = table.to_pydict()
    return sorted(zip(*(d[c] for c in COLS)),
                  key=lambda r: tuple((v is None, v) for v in r))


def _timed(client, trials=3):
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        client.execute(SQL)
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="igloo_adaptive_smoke_")
    po, pc = _write_tables(tmp)

    # single-node reference FIRST, with the adaptive loop disabled and the
    # store reset after: the local engine harvests observations under the
    # SAME structural fingerprints the cluster planner reads, which would
    # let run 1 below plan from "observed" stats it never measured
    os.environ["IGLOO_ADAPTIVE"] = "0"
    local = QueryEngine(use_jit=False)
    local.register_table("orders", ParquetTable(po))
    local.register_table("cust", ParquetTable(pc))
    want = _norm(local.execute(SQL))
    del os.environ["IGLOO_ADAPTIVE"]
    hints.reset_adaptive_store()

    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0,
                              use_jit=False)
    caddr = f"127.0.0.1:{coord.port}"
    # single-device workers: the cross-worker parallelism under test is the
    # two PROCESSES
    wenv = dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=1")
    procs = [subprocess.Popen(
        [sys.executable, "-m", "igloo_tpu.cluster.worker", caddr],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO,
        env=wenv)
        for _ in range(2)]
    try:
        deadline = time.time() + 90
        while len(coord.membership.live()) < 2 and time.time() < deadline:
            for p in procs:
                assert p.poll() is None, p.stdout.read()
            time.sleep(0.2)
        assert len(coord.membership.live()) == 2, "workers never registered"
        coord.register_table("orders", ParquetTable(po))
        coord.register_table("cust", ParquetTable(pc))
        client = DistributedClient(caddr)

        # run 1 (adaptive on, no observations yet): plain exchange, records
        # the skew sketch — and compiles/warms the unsalted plan's programs
        got = client.execute(SQL)
        m1 = client.last_metrics()
        assert _norm(got) == want, "first (unsalted) run: wrong result"
        assert any(d.get("strategy") == "shuffle"
                   for d in m1.get("adaptive", [])), m1.get("adaptive")

        # timed A/B on the warmed cluster: kill switch = the old plan
        os.environ["IGLOO_ADAPTIVE"] = "0"
        unsalted_s = _timed(client)
        mu = client.last_metrics()
        assert mu.get("adaptive") == [], "kill switch still planned adaptively"
        del os.environ["IGLOO_ADAPTIVE"]

        c0 = tracing.counters()
        client.execute(SQL)     # warm the salted plan's programs untimed
        salted_s = _timed(client)
        c1 = tracing.counters()
        ms = client.last_metrics()
        got2 = client.execute(SQL)
        assert _norm(got2) == want, "salted run: wrong result"

        salted = c1.get("adaptive.salted", 0) - c0.get("adaptive.salted", 0)
        assert salted > 0, "adaptive.salted never bumped"
        dec = [d for d in ms.get("adaptive", [])
               if d.get("strategy") == "salted"]
        assert dec, f"no salted decision in last_metrics: {ms.get('adaptive')}"
        joins = [f for f in ms["fragments"] if f.get("kind") == "join"]
        hot = dec[0]["hot_bucket"]
        nb = dec[0]["buckets"]
        hot_workers = {f["worker"] for f in joins
                       if f.get("bucket") == hot or f.get("bucket", -1) >= nb}
        assert len(hot_workers) == 2, \
            f"hot-bucket work not spread across both workers: {joins}"
        assert salted_s < unsalted_s, \
            (f"salted plan ({salted_s:.2f}s) did not beat the unsalted one "
             f"({unsalted_s:.2f}s)")
        print(f"adaptive smoke: OK — max_share={dec[0]['max_share']}, "
              f"salted {salted_s:.2f}s vs unsalted {unsalted_s:.2f}s "
              f"({unsalted_s / salted_s:.2f}x), hot bucket {hot} split "
              f"across {len(hot_workers)} workers")
        client.close()
        return 0
    finally:
        for p in procs:
            p.terminate()
        coord.shutdown()


if __name__ == "__main__":
    raise SystemExit(main())
