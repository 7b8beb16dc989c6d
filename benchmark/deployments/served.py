"""`served`: one CoordinatorServer + one Worker on loopback, in this process
(a child could not have the chip), tables registered as Parquet on the
coordinator, queried by a DistributedClient over Arrow Flight. Set-up copied
from chip_smoke.py:run_served (PR 22 ran it on the chip)."""
from __future__ import annotations

import os
import time


class Deployment:
    def __init__(self, stage_dir: str, tables: list):
        from igloo_tpu.cluster.client import DistributedClient
        from igloo_tpu.cluster.coordinator import CoordinatorServer
        from igloo_tpu.cluster.worker import Worker
        from igloo_tpu.connectors.parquet import ParquetTable
        self.coord = CoordinatorServer("grpc+tcp://127.0.0.1:0",
                                       worker_timeout_s=600.0)
        self.worker = self.client = None
        try:
            caddr = f"127.0.0.1:{self.coord.port}"
            self.worker = Worker(caddr, port=0, heartbeat_interval_s=1.0)
            self.worker.start()
            deadline = time.monotonic() + 30
            while (not self.coord.membership.live()
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            if not self.coord.membership.live():
                raise RuntimeError(
                    "worker never registered with the coordinator")
            for name in tables:
                self.coord.register_table(name, ParquetTable(
                    os.path.join(stage_dir, f"{name}.parquet")))
            self.worker_id = self.worker.server.worker_id
            self.client = DistributedClient(caddr)
        except BaseException:
            self.close()
            raise

    def clear_result_cache(self) -> None:
        # the front door's cache: a repeat must execute, not be looked up
        self.coord.engine.result_cache.clear()

    def execute(self, sql: str):
        """The timed call: returns once the client holds the whole table."""
        return self.client.execute(sql)

    def last_info(self) -> dict:
        """Where the last query ran (one more Flight action; outside the
        timed call, inside the window)."""
        m = self.client.last_metrics()
        frags = m.get("fragments") or []
        off = [f.get("worker") for f in frags
               if f.get("worker") != self.worker_id]
        ok = (bool(frags) and not off and not m.get("result_cache_hit")
              and not m.get("demoted") and m.get("status", "ok") == "ok")
        return {"executed_on_device": ok,
                "where": (f"{len(frags)} fragments, {len(off)} off the "
                          f"worker, status {m.get('status')}, "
                          f"result_cache_hit {m.get('result_cache_hit')}, "
                          f"demoted {m.get('demoted')}"),
                "fragments": len(frags),
                "fragment_s": sum(f.get("elapsed_s") or 0.0 for f in frags),
                "queue_wait_s": m.get("queue_wait_s") or 0.0,
                "exchange_bytes": m.get("exchange_bytes") or 0}

    def close(self) -> None:
        if self.client is not None:
            self.client.close()
        if self.worker is not None:
            self.worker.shutdown()
        self.coord.shutdown()


def build(stage_dir: str, tables: list) -> Deployment:
    return Deployment(stage_dir, tables)
