"""`served_params`: the `served_resident` deployment, held besides to what a
configuration whose queries carry substitution parameters states: *a query
whose shape this process has run uploads no table column.*

A query's shape is its text with the numbers and the quoted dates and
numbers masked (`DATE '1996-01-01'`, `INTERVAL '68' DAY`, `0.04`, `25`): two
parameter sets of one TPC-H query are one shape. `xfer.h2d_bytes` is read
beside the timed call, before it in `clear_result_cache` and after it in
`last_info`, as `served_resident` reads evictions. A query whose shape has
run before and during which more than `UPLOAD_LIMIT` bytes went to the
device is an error: in warm-up it ends the run (exit 1), in the window it
counts under `failed_queries`. What a steady query uploads is its
fragments' dependency tables, 296 B a query; one column of a 60 M-row
`lineitem` is 240-480 MB.

Why a run should end there: a program that names a resident column by the
text of the filter pushed into its scan decodes 60 M rows of Parquet and
uploads four to seven columns again for every parameter set it meets (a
minute each at SF10), holds a copy per set until the resident budget is
passed, and what the window would measure is the Parquet reader.
"""
from __future__ import annotations

import importlib.util
import os
import re

#: bytes a query of a known shape may upload: far above the dependency
#: tables of a steady query, far below one column of the table
UPLOAD_LIMIT = 16 << 20
_PARAMETER = re.compile(r"'[0-9][0-9.:\- ]*'|\b[0-9]+(?:\.[0-9]+)?\b")


def shape_of(sql: str) -> str:
    """`sql` with its numbers and its quoted dates and numbers masked."""
    return _PARAMETER.sub("?", " ".join(sql.split()))


def _served_resident():
    """deployments/served_resident.py, by its path (no package here)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "served_resident.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_deployments_served_resident", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _uploaded() -> int:
    from igloo_tpu.utils import tracing
    return tracing.counters().get("xfer.h2d_bytes", 0)


class Deployment(_served_resident().Deployment):
    def __init__(self, stage_dir: str, tables: list):
        super().__init__(stage_dir, tables)
        self._shapes_run: set = set()
        self._shape = None
        self._uploaded_before = 0

    def clear_result_cache(self) -> None:
        super().clear_result_cache()
        self._uploaded_before = _uploaded()

    def execute(self, sql: str):
        self._shape = shape_of(sql)
        return super().execute(sql)

    def last_info(self) -> dict:
        moved = _uploaded() - self._uploaded_before
        known = self._shape in self._shapes_run
        self._shapes_run.add(self._shape)
        if known and moved > UPLOAD_LIMIT:
            raise RuntimeError(
                f"a query whose shape this process had run uploaded "
                f"{moved} bytes (limit {UPLOAD_LIMIT}): a table's columns "
                "went to the device again for a new parameter set")
        return super().last_info()


def build(stage_dir: str, tables: list) -> Deployment:
    return Deployment(stage_dir, tables)
