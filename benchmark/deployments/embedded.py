"""`embedded`: an in-process QueryEngine over the staged Parquet files (the
reference's crates/engine + CLI local mode). Session, scan + codec,
programs, device; no front door, no fragments."""
from __future__ import annotations

import os


class Deployment:
    def __init__(self, stage_dir: str, tables: list):
        from igloo_tpu.connectors.parquet import ParquetTable
        from igloo_tpu.engine import QueryEngine
        self.engine = QueryEngine()
        for name in tables:
            self.engine.register_table(name, ParquetTable(
                os.path.join(stage_dir, f"{name}.parquet")))
        self._stats = None

    def clear_result_cache(self) -> None:
        self.engine.result_cache.clear()

    def execute(self, sql: str):
        """The timed call: returns once the whole Arrow table is held."""
        res = self.engine.query(sql)
        self._stats = res.stats
        return res.table

    def last_info(self) -> dict:
        """Where the last query ran; read outside the timed call."""
        st = self._stats
        return {"executed_on_device": st.tier == "device",
                "where": f"tier {st.tier}"}

    def close(self) -> None:
        self.engine = None


def build(stage_dir: str, tables: list) -> Deployment:
    return Deployment(stage_dir, tables)
