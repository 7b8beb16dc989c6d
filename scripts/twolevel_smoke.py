#!/usr/bin/env python
"""Two-level parallelism smoke + chips x hosts scaling curve
(scripts/validate.sh; docs/distributed.md "Two-level topology").

Default mode: an in-process coordinator + 2 worker SUBPROCESSES, each given 2
virtual devices (`XLA_FLAGS=--xla_force_host_platform_device_count=2`) and
the production mesh default (`DEFAULT_MESH="auto"` — nothing pinned). Runs a
distributed join and asserts via `last_metrics` that BOTH levels engaged:

- the fragment tier hash-partitioned across both workers (shuffle buckets,
  join fragments on both), and
- the mesh tier ran INSIDE each worker (`mesh_devices == 2` on every join
  fragment — the worker routed the fragment through ShardedExecutor over its
  local 2-device mesh), with rows identical to single-device execution.

`--scaling` measures the same join at 1x1 / 1x2 / 2x1 / 2x2
(workers x per-worker devices) and emits one JSON line. Wall
times on virtual CPU
devices measure PLUMBING (dispatch, exchange, H2D resharding), not compute
scaling — the block's value is the per-topology `mesh_devices`/fragment
attribution that proves W x D composition, plus a trend line for regressions.

`--worker` is the subprocess entry: it must set the device count BEFORE jax
initializes, which is why workers cannot be in-process threads here (one
process = one backend = one device count).

This is a CPU drive: the parent (which runs a coordinator engine) and every
worker it starts run with JAX_PLATFORMS=cpu. On a host with a chip, a parent
and children that all reached for it would fight over it — a chip belongs
to one process.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _force_cpu(devices: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"  # before jax starts; children inherit
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    os.environ["IGLOO_TPU_COMPILE_CACHE"] = "0"
    os.environ["IGLOO_SERVING_RESULT_CACHE"] = "0"
    # stable plan shape across the cold and warm run: with adaptive stats on,
    # the warm plan flips to a broadcast join (the cold run's observed build
    # bytes say so) and the shuffle/scaling assertions would race that flip
    os.environ["IGLOO_ADAPTIVE"] = "0"


def worker_main(coordinator: str, devices: int) -> int:
    """Subprocess entry: a REAL production-shaped worker — mesh setting left
    at the module default ("auto"), so with devices > 1 it resolves a local
    mesh and routes join/agg fragments through the ShardedExecutor."""
    _force_cpu(devices)
    from igloo_tpu.cluster.worker import Worker
    # use_jit=True: mesh fragments run compiled shard_map programs — the
    # production path, and ~30x faster than eager shard_map on CPU (the warm
    # runs in the scaling curve measure the post-compile steady state)
    w = Worker(coordinator, port=0, heartbeat_interval_s=0.5, use_jit=True)
    w.start()
    print(f"WORKER-READY {w.address} devices={w.server.mesh_devices}",
          flush=True)
    try:
        w.serve_forever()
    except KeyboardInterrupt:
        w.shutdown()
    return 0


def _data():
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(3)
    n = 4000
    orders = pa.table({"o_id": np.arange(n, dtype=np.int64),
                       "o_cust": rng.integers(0, 256, n),
                       "o_total": np.round(rng.random(n) * 100, 2)})
    cust = pa.table({"c_id": np.arange(256, dtype=np.int64),
                     "c_name": pa.array([f"c{i:03d}" for i in range(256)])})
    return orders, cust


SQL = ("SELECT c.c_name, COUNT(*) AS n, SUM(o.o_total) AS s FROM orders o "
       "JOIN cust c ON o.o_cust = c.c_id GROUP BY c.c_name ORDER BY c.c_name")


def _assert_rows_equal(got, want) -> None:
    import numpy as np
    g, w = got.to_pydict(), want.to_pydict()
    assert list(g) == list(w), (list(g), list(w))
    for k in g:
        if got.column(k).type == "double":
            # sharded SUM reduces in a different order; bit-equality is not
            # the contract for floats, row identity is
            np.testing.assert_allclose(np.array(g[k], dtype=float),
                                       np.array(w[k], dtype=float),
                                       rtol=1e-9, err_msg=k)
        else:
            assert g[k] == w[k], k


class Cluster:
    """Coordinator in THIS process + `hosts` worker subprocesses with
    `devices` virtual devices each."""

    def __init__(self, hosts: int, devices: int):
        from igloo_tpu.cluster.coordinator import CoordinatorServer
        self.coord = CoordinatorServer("grpc+tcp://127.0.0.1:0",
                                       worker_timeout_s=60.0, use_jit=False)
        self.addr = f"127.0.0.1:{self.coord.port}"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
        env["IGLOO_TPU_COMPILE_CACHE"] = "0"
        self.procs = []
        # any failure past this point must tear down what already started:
        # a half-built cluster would otherwise leak worker subprocesses (and
        # the coordinator's port) into the rest of the validate/bench run
        try:
            self.procs = [subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--worker",
                 self.addr, "--devices", str(devices)],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT) for _ in range(hosts)]
            deadline = time.time() + 90
            while len(self.coord.membership.live()) < hosts and \
                    time.time() < deadline:
                for p in self.procs:
                    if p.poll() is not None:
                        out = p.stdout.read().decode(errors="replace")
                        raise RuntimeError(f"worker died rc={p.returncode}:\n"
                                           f"{out[-2000:]}")
                time.sleep(0.1)
            live = self.coord.membership.live()
            assert len(live) == hosts, f"only {len(live)}/{hosts} registered"
        except BaseException:
            self.shutdown()
            raise
        self.topology = self.coord.membership.topology()

    def shutdown(self) -> None:
        for p in self.procs:
            p.kill()
        for p in self.procs:
            p.wait()
        self.coord.shutdown()


def _run_topology(hosts: int, devices: int, orders, cust) -> dict:
    from igloo_tpu.catalog import MemTable
    from igloo_tpu.cluster.client import DistributedClient
    cl = Cluster(hosts, devices)
    try:
        cl.coord.register_table("orders", MemTable(orders, partitions=2))
        cl.coord.register_table("cust", MemTable(cust, partitions=2))
        client = DistributedClient(cl.addr)
        t0 = time.perf_counter()
        got = client.execute(SQL)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        client.execute(SQL)
        warm = time.perf_counter() - t0
        m = client.last_metrics()
        client.close()
        joins = [f for f in m["fragments"] if f.get("kind") == "join"]
        return {"hosts": hosts, "devices_per_worker": devices,
                "total_shards": sum(cl.topology.values()),
                "cold_s": round(cold, 4), "warm_s": round(warm, 4),
                "rows": got.num_rows,
                "shuffle_buckets": m.get("shuffle_buckets", 0),
                "join_fragments": len(joins),
                "join_workers": len({f["worker"] for f in joins}),
                # across ALL fragments (1-worker topologies have no "join"
                # kind fragments; the mesh runs inside the root fragment)
                "mesh_devices": sorted({f.get("mesh_devices", 1)
                                        for f in m["fragments"]}) or [1],
                "topology_block": m.get("topology"),
                "_table": got, "_metrics": m}
    finally:
        cl.shutdown()


def smoke() -> int:
    orders, cust = _data()
    rec = _run_topology(2, 2, orders, cust)
    m = rec.pop("_metrics")
    got = rec.pop("_table")

    # single-device reference, same process
    from igloo_tpu.catalog import MemTable
    from igloo_tpu.engine import QueryEngine
    local = QueryEngine(use_jit=False, mesh=None)
    local.register_table("orders", MemTable(orders))
    local.register_table("cust", MemTable(cust))
    _assert_rows_equal(got, local.execute(SQL))

    # fragment tier: hash exchange across both workers
    assert rec["shuffle_buckets"] >= 2, m
    assert rec["join_workers"] == 2, \
        f"join fragments not spread across both workers: {m['fragments']}"
    # mesh tier: every join fragment ran sharded over the worker's 2 chips
    joins = [f for f in m["fragments"] if f.get("kind") == "join"]
    assert all(f.get("mesh_devices") == 2 for f in joins), joins
    assert all(f.get("mesh_rows_per_device") is not None for f in joins)
    # topology reached the coordinator: 2 hosts x 2 chips
    topo = m.get("topology") or {}
    assert topo.get("workers") == 2 and topo.get("total_shards") == 4, topo
    print(f"twolevel smoke: OK — {len(joins)} join fragments sharded "
          f"2-way on 2 workers (total_shards={topo['total_shards']}, "
          f"buckets={rec['shuffle_buckets']})")
    return 0


def scaling() -> int:
    orders, cust = _data()
    curve = []
    for hosts, devices in ((1, 1), (1, 2), (2, 1), (2, 2)):
        rec = _run_topology(hosts, devices, orders, cust)
        rec.pop("_metrics")
        rec.pop("_table")
        curve.append(rec)
        print(f"twolevel {hosts}x{devices}: cold={rec['cold_s']}s "
              f"warm={rec['warm_s']}s shards={rec['total_shards']} "
              f"mesh_devices={rec['mesh_devices']}", file=sys.stderr,
              flush=True)
    block = {"query": SQL, "rows": {"orders": orders.num_rows,
                                    "cust": cust.num_rows},
             "note": "virtual CPU devices: times measure plumbing "
                     "(dispatch/exchange/resharding), not compute scaling",
             "curve": curve}
    print(json.dumps(block), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", metavar="COORD", default=None)
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--scaling", action="store_true")
    args = ap.parse_args()
    if args.worker:
        return worker_main(args.worker, args.devices)
    _force_cpu(1)  # coordinator process: planning only, one device is fine
    if args.scaling:
        return scaling()
    return smoke()


if __name__ == "__main__":
    sys.exit(main())
