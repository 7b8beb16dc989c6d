"""Catalog: table name -> provider.

Counterpart of the reference's `MemoryCatalog` (crates/common/src/catalog.rs:5-27,
a name -> Arc<dyn TableProvider> map) — but the provider interface is ours: providers
expose an engine `Schema` and produce pyarrow data host-side with projection and
filter pushdown; the executor moves it into HBM (SURVEY.md §2 #9: "catalog service:
table name -> {format, location, schema, partitioning, device placement}").
"""
from __future__ import annotations

import threading
from typing import Optional, Protocol, runtime_checkable

import pyarrow as pa

from igloo_tpu.errors import CatalogError
from igloo_tpu.exec.batch import schema_from_arrow
from igloo_tpu.types import Schema


@runtime_checkable
class TableProvider(Protocol):
    """A registered table. `read` returns a pyarrow Table containing (at least) the
    requested columns; `filters` are bound Expr the provider MAY pre-apply
    (best-effort pruning — the engine always re-applies them exactly)."""

    def schema(self) -> Schema: ...

    def read(self, projection: Optional[list[str]] = None,
             filters: Optional[list] = None) -> pa.Table: ...

    def num_partitions(self) -> int:
        """How many independently readable chunks exist (files / row groups); the
        distributed planner uses this for scan placement."""
        ...

    def read_partition(self, index: int, projection: Optional[list[str]] = None,
                       filters: Optional[list] = None) -> pa.Table: ...


class MemTable:
    """In-memory table over a pyarrow Table (reference uses DataFusion MemTable for
    the CLI's sample `users` table, crates/igloo/src/main.rs:59-77)."""

    # repeated reads return identical row order (column-granular scan cache)
    stable_row_order = True

    def __deepcopy__(self, memo):
        # providers are shared by plan/expression copies (see copy_plan)
        return self

    def __init__(self, table: pa.Table, partitions: int = 1):
        self._table = table
        self._schema = schema_from_arrow(table.schema)
        self._partitions = max(1, min(partitions, max(table.num_rows, 1)))

    @staticmethod
    def from_pydict(d: dict, schema: Optional[pa.Schema] = None) -> "MemTable":
        return MemTable(pa.table(d, schema=schema))

    def schema(self) -> Schema:
        return self._schema

    def read(self, projection=None, filters=None) -> pa.Table:
        t = self._table
        if projection is not None:
            t = t.select(projection)
        return t

    def num_partitions(self) -> int:
        return self._partitions

    def surviving_parts(self, filters, partition=None):
        """Filters prune nothing here (exec/cache.py read_identity)."""
        return None

    def read_partition(self, index: int, projection=None, filters=None) -> pa.Table:
        n = self._table.num_rows
        per = (n + self._partitions - 1) // self._partitions if n else 0
        t = self._table.slice(index * per, per)
        if projection is not None:
            t = t.select(projection)
        return t

    def estimated_bytes(self) -> int:
        return self._table.nbytes


class EphemeralTable(MemTable):
    """A result that is the input of ONE execution: a fragment's dependency
    on a worker, a chunk's partial aggregate in the chunked tier. Its table
    name carries a per-query id; `ephemeral` tells the fused compiler to key
    a scan of it by position instead (exec/fused.py `_c_scan`), so the
    consumer's program is built once, not per query."""

    ephemeral = True


class Catalog:
    """Thread-safe name -> provider registry (the coordinator serves one per
    cluster; the reference wraps a plain HashMap, catalog.rs:10-27)."""

    def __init__(self):
        self._tables: dict[str, TableProvider] = {}
        # the `system.` namespace (system.metrics / system.query_log,
        # igloo_tpu/system_tables.py): resolvable by the binder like any
        # table but hidden from SHOW TABLES / list_flights, and shielded
        # from register/deregister so user DDL cannot shadow or drop it
        self._system: dict[str, TableProvider] = {}
        self._lock = threading.RLock()

    def register(self, name: str, provider: TableProvider) -> None:
        key = name.lower()
        if key.startswith("system.") or key in ("system",):
            # the system namespace is read-only by contract: registering a
            # user table over it would shadow live telemetry silently
            raise CatalogError(f"cannot register table in the reserved "
                               f"system namespace: {name}")
        with self._lock:
            self._tables[key] = provider

    def register_system(self, name: str, provider: TableProvider) -> None:
        with self._lock:
            self._system[name.lower()] = provider

    def deregister(self, name: str) -> None:
        with self._lock:
            self._tables.pop(name.lower(), None)

    def get(self, name: str) -> TableProvider:
        with self._lock:
            p = self._tables.get(name.lower())
            if p is None:
                p = self._system.get(name.lower())
        if p is None:
            raise CatalogError(f"table not found: {name}")
        return p

    def maybe_get(self, name: str) -> Optional[TableProvider]:
        with self._lock:
            return self._tables.get(name.lower()) or \
                self._system.get(name.lower())

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._tables)

    def system_names(self) -> list[str]:
        with self._lock:
            return sorted(self._system)

    def __contains__(self, name: str) -> bool:
        with self._lock:
            return name.lower() in self._tables
