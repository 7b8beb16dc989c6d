"""HBM-resident batch cache with byte-budget LRU eviction.

The reference's cache (crates/cache/src/lib.rs:20-56) maps query strings to
RecordBatch vectors and declares a `CacheConfig{capacity}` it never enforces
(gap G7). This is the real version, adapted to the TPU memory hierarchy: the
cached value is a `DeviceBatch` whose column lanes are already resident in HBM,
so a hit skips Parquet/CSV decode, dictionary encoding, AND the host->HBM
transfer. The byte budget is enforced with LRU eviction; entries are validated
against a provider *snapshot token* so source changes invalidate stale batches
(the CDC hook — see igloo_tpu/cdc.py, replacing the reference's empty cdc
crate, crates/cdc/src/lib.rs:9).
"""
from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

from igloo_tpu.exec.batch import DeviceBatch
from igloo_tpu.utils import stats
from igloo_tpu.utils.tracing import counter


# Shares of ONE device's `memory_stats()["bytes_limit"]` (docs/out_of_core.md
# "The two budgets"). A program's inputs ARE resident columns, so the other
# half of the device is for its temporaries:
# - RESIDENT: what the scan cache may keep, and so what the columns ONE
#   scan-and-aggregate program reads may be priced at before the chunked tier
#   takes it (exec/chunked.py chunk_count) — columns that fit are cached once
#   and every later query hits them. Rests on every chip run of the served
#   SF10 cells since PR 30 (q1 / q6 as one program over 2^26 lanes, seven
#   columns resident, 2.0 GB: PERF_LEDGER.jsonl) and on the in-process SF10
#   cell of PR 36.
# - MONOLITHIC: the largest table a JOIN tree may hold before GRACE
#   partitions it (exec/grace.py find_grace_join). It keeps on a 16 GB chip
#   the 2 GiB that every join on a chip was routed under: a join's
#   intermediates at full width are several times its inputs, and no join at
#   2^26 lanes has run on a chip (ROADMAP A2a). Raising it takes that run.
RESIDENT_SHARE = 1 / 2
MONOLITHIC_SHARE = 1 / 8
# where the backend reports no limit (XLA:CPU): the constants the engine had
# before the budgets were derived, so that no CPU route changes
UNLIMITED_BUDGETS = (1 << 30, 2 << 30)
# - DIRECT TABLE: what ONE direct join's positional table (exec/join.py
#   choose_direct_build) may take of the monolithic share. The table is
#   one of the join's temporaries beside its probe lanes, so it gets half:
#   on a v5e (monolithic 2.11 GB) 2^27 int32 slots (537 MB, TPC-H Q3's
#   order keys at SF10 as the spec spaces them) and not 2^28 (1.07 GB).
DIRECT_TABLE_SHARE = 1 / 2


def _bytes_limit():
    """The smallest local device's `bytes_limit` (a mesh row-shards evenly,
    so the fullest chip is the tightest), or None where the backend reports
    none. This starts the backend."""
    import jax
    limits = [(d.memory_stats() or {}).get("bytes_limit")
              for d in jax.local_devices()]
    if not limits or not all(limits):
        return None
    return min(limits)


def hbm_budgets() -> tuple:
    """(resident, monolithic) bytes for this process's devices: the scan
    cache's budget, which is also the chunked tier's threshold, and the GRACE
    trigger's, shared by `QueryEngine` and the cluster worker. This starts
    the backend: call it where a device is needed anyway, not at
    construction."""
    limit = _bytes_limit()
    if limit is None:
        return UNLIMITED_BUDGETS
    return int(limit * RESIDENT_SHARE), int(limit * MONOLITHIC_SHARE)


def direct_table_budget():
    """Bytes one direct join's positional table may take on this process's
    devices (`DIRECT_TABLE_SHARE` of the monolithic share), or None where
    the backend reports no limit: the caller keeps its fixed size there."""
    limit = _bytes_limit()
    if limit is None:
        return None
    return int(limit * MONOLITHIC_SHARE * DIRECT_TABLE_SHARE)


def scan_table_key(name: str) -> str:
    """Canonical cache key for a table name: the binder sets Scan.table to the
    last dotted component lowercased (plan/binder.py), so every invalidation
    path must reduce qualified catalog names ("db.tbl") the same way."""
    return name.split(".")[-1].lower()


@dataclass
class CacheEntry:
    value: object          # DeviceBatch (BatchCache) / pa.Table (ResultCache)
    snapshot: object
    nbytes: int
    tables: frozenset = frozenset()  # scanned tables (invalidate_table match)


class SnapshotLRU:
    """Thread-safe byte-budget LRU with snapshot validation — the shared core
    of the HBM scan cache (BatchCache) and the host query-result cache
    (exec/result_cache.ResultCache). Subclasses set `counter_prefix` and
    `_match_table` (how invalidate_table selects entries). `capacity` is an
    optional ENTRY-count bound enforced beside the byte budget (the
    reference's declared-but-never-enforced CacheConfig.capacity, gap G7):
    byte budgets alone let thousands of tiny entries pile up, which bloats
    every invalidation sweep."""

    counter_prefix = "cache"

    def __init__(self, budget_bytes: int = 1 << 30,
                 capacity: Optional[int] = None):
        self._budget_bytes = int(budget_bytes)
        self.capacity = int(capacity) if capacity is not None else None
        self._entries: OrderedDict = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def budget_bytes(self) -> int:
        return self._budget_bytes

    def get(self, key, snapshot: object):
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                counter(f"{self.counter_prefix}.miss")
                return None
            if e.snapshot != snapshot:
                # source changed underneath us: invalidate
                self._bytes -= e.nbytes
                del self._entries[key]
                self.misses += 1
                counter(f"{self.counter_prefix}.invalidated")
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            counter(f"{self.counter_prefix}.hit")
            # per-operator attribution in the query stats tree (a scan node
            # served from HBM shows cache_hit=N instead of upload bytes)
            stats.bump_attr(f"{self.counter_prefix}_hit")
            return e.value

    def put(self, key, value, snapshot: object, nbytes: int,
            tables: frozenset = frozenset()) -> None:
        budget = self.budget_bytes
        if nbytes > budget:
            # larger than the whole budget: never cacheable, so every scan
            # of it decodes and uploads again
            counter(f"{self.counter_prefix}.too_large")
            return
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._entries[key] = CacheEntry(value, snapshot, nbytes, tables)
            self._bytes += nbytes
            while self._bytes > budget and self._entries:
                _, ev = self._entries.popitem(last=False)
                self._bytes -= ev.nbytes
                self.evictions += 1
                counter(f"{self.counter_prefix}.evict")
            while self.capacity is not None and \
                    len(self._entries) > self.capacity:
                _, ev = self._entries.popitem(last=False)
                self._bytes -= ev.nbytes
                self.evictions += 1
                counter(f"{self.counter_prefix}.evicted")

    def _match_table(self, key, entry: CacheEntry, table_key: str) -> bool:
        raise NotImplementedError

    def invalidate_table(self, table: str) -> int:
        """Drop every entry sourced from `table` (CDC invalidation bus entry
        point). Returns the number of entries dropped. `table` may be a
        qualified catalog name; it is canonicalized to the scan key."""
        tk = scan_table_key(table)
        with self._lock:
            doomed = [k for k, e in self._entries.items()
                      if self._match_table(k, e, tk)]
            for k in doomed:
                self._bytes -= self._entries.pop(k).nbytes
            return len(doomed)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    @property
    def nbytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        return len(self._entries)


class BatchCache(SnapshotLRU):
    """HBM scan cache. Two entry shapes, both with key[0] = table name:

    - column-granular (providers with stable row order):
      (table, what was read, partition, 'col', name) ->
      (DeviceColumn, n_rows, the pushed filters it was loaded under) and
      (table, what was read, partition, 'live') -> live lane array;
      scans assemble batches from these so overlapping projections share the
      uploaded lanes (written via `put_entry`).
    - whole-batch (order-unstable providers, e.g. DBAPI):
      (table, projection, what was read, partition) -> DeviceBatch (via
      `put`).

    "What was read" is `read_identity(plan)`: None wherever the pushed
    filters pruned nothing."""

    counter_prefix = "cache"

    def put(self, key: tuple, batch: DeviceBatch, snapshot: object) -> None:
        super().put(key, batch, snapshot, batch.nbytes())

    def put_entry(self, key: tuple, value: object, snapshot: object,
                  nbytes: int, table: str) -> None:
        """Column-granular entries; `table` must equal key[0] (invalidation)."""
        assert key and key[0] == table
        super().put(key, value, snapshot, nbytes)

    def _match_table(self, key, entry, table_key: str) -> bool:
        return bool(key) and key[0] == table_key


class ResidentCache(BatchCache):
    """The HBM scan cache of a `QueryEngine` or a cluster worker, under the
    device's resident share (`hbm_budgets`). The share is read at the first
    put and not here: constructing an engine or a coordinator touches no
    device."""

    def __init__(self):
        super().__init__(0)
        self._share: Optional[int] = None

    @property
    def budget_bytes(self) -> int:
        if self._share is None:
            self._share = hbm_budgets()[0]
        return self._share


def read_identity(plan) -> object:
    """What names a scan's resident columns besides table, snapshot and
    partition: what a read under the scan's pushed filters RETURNS, not the
    text of the filters. The filters are pushed so that a provider may prune
    (Parquet drops row groups by their statistics); the engine applies every
    one again, exactly, in the program. A provider that prunes says which
    parts survive (`surviving_parts`: None when all do — every TPC-H table —
    and then no filter is in the name: two queries that read the same bytes
    hold one copy, and a new literal loads nothing). A provider that does
    not say may apply its filters to the rows (DBAPI renders a WHERE): there
    the filters, values included, name the entry."""
    if not plan.pushed_filters:
        return None
    surviving = getattr(plan.provider, "surviving_parts", None)
    if surviving is not None:
        return surviving(plan.pushed_filters, plan.partition)
    from igloo_tpu.plan.expr import fingerprint
    return fingerprint(plan.pushed_filters)


def provider_snapshot(provider) -> object:
    """Snapshot token for a provider: changes iff the underlying data may have
    changed. Providers may implement `snapshot()` (file connectors return
    mtimes/sizes); the fallback is provider IDENTITY, correct for immutable
    in-memory tables (re-registering a table creates a new provider).

    The identity token is a weakref, not `id()`: a bare id is reused by the
    allocator once the provider is freed, so a cache entry could validate
    against a DIFFERENT provider that happens to land on the same address —
    the exact staleness bug the GRACE partition loop hit (its providers now
    carry explicit snapshot() tokens, but any other transient provider would
    re-create it). Two live refs to the same provider compare equal; a dead
    ref compares equal only to itself, so entries for freed providers can
    never validate again."""
    snap = getattr(provider, "snapshot", None)
    if callable(snap):
        return snap()
    try:
        return weakref.ref(provider)
    except TypeError:
        # non-weakrefable (slotted C extension): identity is best-effort;
        # such providers are long-lived connector objects, not loop-allocated
        return id(provider)  # lint: allow(cache-key)
