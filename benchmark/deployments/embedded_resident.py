"""`embedded_resident`: the `embedded` deployment, held to the layout its
configuration states: every column the traffic reads is resident in the
engine's HBM scan cache after warm-up, and a query is one program on tier
`device`. A query that ran on another tier (`chunked`, `grace`, `host`,
`result_cache`), or during which a scan cache of this process evicted an
entry or dropped one for being over its whole budget (`cache.evict`,
`cache.evicted`, `cache.too_large`), is an error: in warm-up it ends the
run (exit 1), in the window it counts under `failed_queries`.

Why a run should end there: at the scale such a configuration has, an
engine that takes the scan in chunks holds chunk entries beside (or in
place of) the columns, re-reads Parquet whenever they do not fit, and what
the window would measure is the out-of-core tier and the Parquet reader,
not the 2^26-lane programs the cell is there for; `embedded` alone would
run warm-up, window and oracle to the end and only then report every query
under `failed_queries`. The counters are read beside the timed call, before
it in `clear_result_cache` and after it in `last_info`, as
`served_resident` reads them.
"""
from __future__ import annotations

import importlib.util
import os

DROPPED = ("cache.evict", "cache.evicted", "cache.too_large")


def _embedded():
    """deployments/embedded.py, by its path (this directory is no package)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "embedded.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_deployments_embedded", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _dropped() -> int:
    from igloo_tpu.utils import tracing
    now = tracing.counters()
    return sum(now.get(name, 0) for name in DROPPED)


class Deployment(_embedded().Deployment):
    def clear_result_cache(self) -> None:
        super().clear_result_cache()
        self._before = _dropped()

    def last_info(self) -> dict:
        info = super().last_info()
        if not info["executed_on_device"]:
            raise RuntimeError(
                f"the query ran on {info['where']}, not as one program on "
                "tier device over resident columns, as the configuration's "
                "layout says it does")
        dropped = _dropped() - self._before
        if dropped:
            raise RuntimeError(
                f"the scan cache dropped {dropped} entries during the query: "
                "the columns the traffic reads are not resident, as the "
                "configuration's layout says they are")
        return info


def build(stage_dir: str, tables: list) -> Deployment:
    return Deployment(stage_dir, tables)
