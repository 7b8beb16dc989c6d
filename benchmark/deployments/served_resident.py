"""`served_resident`: the `served` deployment, held to the layout its
configuration states: every column the traffic reads stays resident in the
worker's HBM scan cache. A query during which a scan cache of this process
evicted an entry, or dropped one for being over its whole budget
(`cache.evict`, `cache.evicted`, `cache.too_large`), is an error: in warm-up
it ends the run (exit 1), in the window it counts under `failed_queries`.

Why a run should end there: at the scale such a configuration has, a scan
cache that cannot hold the traffic's columns decodes Parquet and uploads
gigabytes again on every query (PERF.md: 36 s a query at SF10 under a 1 GiB
cache), warm-up alone outlasts the time a run is given, and what the window
would measure is the Parquet reader. The counters are read beside the timed
call, before it in `clear_result_cache` and after it in `last_info`.
"""
from __future__ import annotations

import importlib.util
import os

DROPPED = ("cache.evict", "cache.evicted", "cache.too_large")


def _served():
    """deployments/served.py, by its path (this directory is no package)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "served.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_deployments_served", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _dropped() -> int:
    from igloo_tpu.utils import tracing
    now = tracing.counters()
    return sum(now.get(name, 0) for name in DROPPED)


class Deployment(_served().Deployment):
    def clear_result_cache(self) -> None:
        super().clear_result_cache()
        self._before = _dropped()

    def last_info(self) -> dict:
        dropped = _dropped() - self._before
        if dropped:
            raise RuntimeError(
                f"the scan cache dropped {dropped} entries during the query: "
                "the columns the traffic reads are not resident, as the "
                "configuration's layout says they are")
        return super().last_info()


def build(stage_dir: str, tables: list) -> Deployment:
    return Deployment(stage_dir, tables)
