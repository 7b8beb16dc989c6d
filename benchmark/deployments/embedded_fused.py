"""`embedded_fused`: `embedded_resident`, held to one more rule of its
configuration's layout: a query is ONE fused program. On the first sight of
a query's text, before it executes, the engine's fused compiler is asked
for its verdict on the plan; a plan it refuses (`FusionUnsupported`) is an
error. After every query the counters of a fall to the staged executor
(`fused.unsupported`, `fused.nofuse_sentinel`) must be unmoved. In warm-up
an error ends the run (exit 1), in the window it counts under
`failed_queries`.

Why: a plan the fused compiler refuses runs on the staged executor, one
program a node with a host sync between them, and its tier still reads
`device`, so `embedded_resident` cannot tell it from one program. At TPC-H
SF10 that staged q3 runs a sorted probe over the 2^26-lane `lineitem` for
minutes before it fails; asking for the verdict first ends such a run in
warm-up, once the scans are loaded, instead.
"""
from __future__ import annotations

import importlib.util
import os

FELL = ("fused.unsupported", "fused.nofuse_sentinel")


def _embedded_resident():
    """deployments/embedded_resident.py, by its path (no package here)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "embedded_resident.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_deployments_embedded_resident", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fell() -> int:
    from igloo_tpu.utils import tracing
    now = tracing.counters()
    return sum(now.get(name, 0) for name in FELL)


class Deployment(_embedded_resident().Deployment):
    def __init__(self, stage_dir: str, tables: list):
        super().__init__(stage_dir, tables)
        self._judged: set = set()

    def verdict(self, sql: str) -> None:
        """The fused compiler's verdict on the plan of `sql`: raises where
        it would leave the query to the staged executor. Loads the plan's
        scans, as its first execution would; runs no program."""
        from igloo_tpu.exec.fused import FusedCompiler, FusionUnsupported
        plan = self.engine.plan(sql)
        try:
            FusedCompiler(self.engine._executor()).compile(plan)
        except FusionUnsupported as ex:
            raise RuntimeError(
                f"the fused compiler refuses the plan ({ex}): the query "
                "would run on the staged executor, not as one program as "
                "the configuration's layout says") from ex

    def clear_result_cache(self) -> None:
        super().clear_result_cache()
        self._fell_before = _fell()

    def execute(self, sql: str):
        if sql not in self._judged:
            self.verdict(sql)
            self._judged.add(sql)
        return super().execute(sql)

    def last_info(self) -> dict:
        info = super().last_info()
        fell = _fell() - self._fell_before
        if fell:
            raise RuntimeError(
                f"{fell} fall(s) to the staged executor during the query: "
                "it did not run as one fused program, as the "
                "configuration's layout says")
        return info


def build(stage_dir: str, tables: list) -> Deployment:
    return Deployment(stage_dir, tables)
