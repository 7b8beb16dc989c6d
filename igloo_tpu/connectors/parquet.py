"""Parquet connector.

Replaces the reference's ParquetScanExec (crates/engine/src/operators/parquet_scan.rs:
40-85 — deprecated reader API, 1024-row batches through an mpsc channel). TPU
design: decode host-side via pyarrow's C++ Parquet reader with column projection
AND row-group pruning from pushed-down predicates (min/max statistics), then one
`device_put` of whole columns into HBM (exec/batch.from_arrow).

Every byte comes through the object-store layer (igloo_tpu/storage,
docs/storage.md): reads are policy-retried ranged GETs verified against the
query's pinned snapshot etags (a source mutated mid-query raises
`SnapshotChanged` → ONE engine re-plan, never a torn result), a vanished
file is a snapshot change rather than a raw FileNotFoundError, and a row
group whose bytes no longer parse is quarantined behind a typed
`CorruptObjectError` naming file + row group.
"""
from __future__ import annotations

import datetime as _dt
import glob as _glob
import os
from typing import Optional

import pyarrow as pa
import pyarrow.parquet as pq

from igloo_tpu.errors import ConnectorError, SnapshotChanged, StorageError
from igloo_tpu.exec.batch import schema_from_arrow
from igloo_tpu.plan import expr as E
from igloo_tpu.storage import local_store, quarantine
from igloo_tpu.storage import snapshot as _snapshot
from igloo_tpu.types import Schema


class ParquetTable:
    """One file, a directory of files, or a glob pattern — optionally on an
    explicit `store` (any storage.ObjectStore; default local filesystem)."""

    # deterministic file/row-group order -> scans may be cached per column
    stable_row_order = True
    # compressed columnar files decode to ~3-4x their size as int64/float64
    # device lanes (device-memory budgets scale estimates by this)
    bytes_expansion = 3.5

    def __init__(self, path: str, store=None):
        import threading
        self.path = path
        self._store = store if store is not None else local_store()
        self._parts = None  # lazy (file, row_group) partition index
        self._lanes = None  # (the files' etags + sizes, their lane_stats())
        self._pruned: dict = {}  # (file, etag, predicates) -> groups kept
        self._plock = threading.Lock()  # guards _files/_parts (Flight threads)
        self._files = _expand_store(self._store, path)
        if not self._files:
            raise ConnectorError(f"no parquet files at {path}")
        try:
            self._arrow_schema = pq.read_schema(
                self._store.open_input(self._files[0], table=path))
        except Exception as ex:  # corrupt/fake file (reference gap G8)
            raise ConnectorError(f"cannot read parquet schema from "
                                 f"{self._files[0]}: {ex}") from None
        self._schema = schema_from_arrow(self._arrow_schema)

    def schema(self) -> Schema:
        return self._schema

    def __deepcopy__(self, memo):
        # providers are SHARED by plan copies (plan/logical.copy_plan shares
        # them deliberately); expression deepcopies that reach a provider
        # through a bound subquery plan must not clone it — the partition
        # lock isn't picklable and cloning would fork cache identity
        return self

    def snapshot(self):
        """Cache/CDC token: changes when any underlying file's store etag
        changes (re-lists directory/glob paths so added files are seen — and
        drops the stale partition index when the file set moved). Inside a
        query's pinned scope (storage/snapshot.py) the first call pins the
        token AND the per-file etags every ranged read then verifies."""
        tok, _etags = _snapshot.pin(self, self._snapshot_now)
        return tok

    def _snapshot_now(self) -> tuple:
        files = _expand_store(self._store, self.path)
        with self._plock:
            if files and files != self._files:
                self._files = files
                self._parts = None
            files = list(self._files)
        return self._store.snapshot_token(files)

    def _partition_index(self) -> list[tuple[str, int]]:
        """(file, row_group) pairs — the scan's parallel/chunking unit. Row
        groups (not whole files) so a single large file still distributes
        across workers / chunks (reference analog: fixed 1024-row read batches,
        parquet_scan.rs:54, which never leave the single stream). Lock-guarded:
        Flight serves fragments on concurrent threads, and snapshot() may drop
        the index when the file set moves. A file that vanishes between the
        list and the metadata read is a SNAPSHOT CHANGE, not a crash: it is
        dropped here, and the pinned-etag verification on the surviving reads
        tells the engine to re-plan."""
        with self._plock:
            if self._parts is None:
                parts: list[tuple[str, int]] = []
                for f in self._files:
                    try:
                        n = pq.ParquetFile(
                            self._store.open_input(f, table=self.path)
                        ).metadata.num_row_groups
                    except (FileNotFoundError, SnapshotChanged):
                        continue  # vanished between list and head
                    except Exception:
                        n = 1
                    parts.extend((f, i) for i in range(max(n, 1)))
                self._parts = parts
            return self._parts

    def num_partitions(self) -> int:
        return len(self._partition_index())

    def partition_token(self) -> str:
        """Stable fingerprint of the (file, row_group) partition index. Plans
        capture it at planning time; read_scan_table verifies it before
        partitioned reads, so an index rebuilt mid-query (snapshot() re-glob
        after a file replace) errors instead of silently reading wrong rows
        when only the layout — not the length — changed."""
        import hashlib
        parts = self._partition_index()
        return hashlib.sha1(repr(parts).encode()).hexdigest()

    def estimated_bytes(self) -> Optional[int]:
        return self._store.files_bytes(self._files)

    def lane_stats(self) -> Optional[tuple]:
        """(rows, {column: (lo, hi, nulls)}) of the table, from the files'
        footers alone: what a scan of it is priced from before any data is
        read (exec/chunked.py estimated_lane_bytes). `lo` and `hi` bound an
        integer or date32 column's values (the physical integers of the
        row groups' statistics: the days of a date), None where a row group
        states none or the column is of another type: the width of its
        carrier is then not known before the data is. `nulls`: some row
        group counts a null, or does not say. Remembered per version of the
        files (their etags and sizes: one `head` each, what
        `estimated_bytes` costs; the file SET is the one the last
        `snapshot()` listed): a routing decision over files that have not
        changed opens nothing, and pins nothing.
        None when a footer cannot be read: the price is then the file's."""
        with self._plock:
            files = list(self._files)
        tok = self._store.snapshot_token(files)[0]
        memo = self._lanes
        if memo is not None and memo[0] == tok:
            return memo[1]
        bounded = {f.name for f in self._arrow_schema
                   if pa.types.is_integer(f.type) or pa.types.is_date32(f.type)}
        rows, cols = 0, {}
        try:
            for path in files:
                meta = pq.ParquetFile(self._open(path)).metadata
                rows += meta.num_rows
                for g in range(meta.num_row_groups):
                    rg = meta.row_group(g)
                    for c in range(rg.num_columns):
                        _fold_stats(cols, rg.column(c), bounded)
            stats = (rows, cols)
        except Exception:
            # a price is best effort, as `estimated_bytes` is: the read that
            # follows raises what is wrong with the file, typed
            stats = None
        self._lanes = (tok, stats)
        return stats

    def surviving_parts(self, filters, partition=None):
        """What a read of `partition` (None: the table) under `filters`
        returns, for the name of what a scan cache keeps of it
        (exec/cache.py read_identity): None when the filters prune no row
        group — uniform data, every TPC-H table — else the (file, row group)
        pairs that survive. Pruning is remembered per file version and
        predicate set: a resident table asks once per literal set."""
        preds = _simple_preds(filters)
        if not preds:
            return None
        _tok, etags = _snapshot.pin(self, self._snapshot_now)
        index = self._partition_index()
        want = index if partition is None else \
            [index[i] for i in partition if i < len(index)]
        alive: dict = {}
        for path in dict.fromkeys(f for f, _ in want):
            key = (path, etags.get(path), preds)
            if key not in self._pruned:
                if len(self._pruned) >= 256:
                    self._pruned.clear()
                self._pruned[key] = _prune_by(
                    pq.ParquetFile(self._open(path)).metadata, preds)
            alive[path] = self._pruned[key]
        kept = tuple((f, rg) for f, rg in want
                     if alive[f] is None or rg in alive[f])
        return None if len(kept) == len(want) else kept

    def _open(self, path: str):
        """Open one data file for verified ranged reads: the etag pinned by
        this query's snapshot() (if any) is enforced at open and on every
        read; a vanished file maps to SnapshotChanged — the typed signal the
        engine converts into one bounded re-plan."""
        pins = _snapshot.pinned_etags(self)
        want = pins.get(path) if pins is not None else None
        try:
            return self._store.open_input(path, want_etag=want,
                                          table=self.path)
        except FileNotFoundError:
            raise SnapshotChanged(
                f"parquet file vanished: {path} (table {self.path})",
                table=self.path, key=path) from None

    def read(self, projection: Optional[list[str]] = None,
             filters: Optional[list] = None) -> pa.Table:
        tables = [self._read_file(f, projection, filters) for f in self._files]
        return pa.concat_tables(tables) if len(tables) > 1 else tables[0]

    def read_partition(self, index: int, projection=None, filters=None) -> pa.Table:
        try:
            # the index is mutable (snapshot() re-lists): a planned partition
            # id that is now out of range means the file set shrank — a
            # SNAPSHOT CHANGE the engine converts into one bounded re-plan,
            # not a bare IndexError
            path, rg = self._partition_index()[index]
        except IndexError:
            raise SnapshotChanged(
                f"parquet partition {index} out of range for {self.path} "
                "(source files moved/replaced)", table=self.path) from None
        fh = self._open(path)
        quarantine.check(path, fh.etag, rg, table=self.path)
        try:
            pf = pq.ParquetFile(fh)
            if rg >= pf.metadata.num_row_groups:
                # the file shrank under an unpinned read: a snapshot change
                # (never corruption — the bytes parse fine)
                raise SnapshotChanged(
                    f"parquet file {path} has {pf.metadata.num_row_groups} "
                    f"row groups, planned index {rg} (table {self.path})",
                    table=self.path, key=path)
            groups = _prune_row_groups(pf, filters)
            if groups is not None and rg not in groups:
                return pf.schema_arrow.empty_table() if projection is None \
                    else pf.schema_arrow.empty_table().select(projection)
            return pf.read_row_groups([rg], columns=projection)
        except (SnapshotChanged, StorageError):
            raise  # already typed (mutation / retries spent) — never corrupt
        except MemoryError as ex:
            # transient pressure (pa.ArrowMemoryError subclasses this), not
            # bad bytes: quarantining would brick the row group for the
            # process lifetime — surface per-query instead
            raise ConnectorError(
                f"parquet partition {index} read failed for {self.path}: "
                f"{ex}") from None
        except Exception as ex:
            # the store served the pinned bytes and they did not parse:
            # corruption, fatal for THIS (file, row group) — quarantined
            raise quarantine.record(path, fh.etag, rg, str(ex),
                                    table=self.path) from None

    def _read_file(self, path: str, projection, filters) -> pa.Table:
        fh = self._open(path)
        quarantine.check(path, fh.etag, -1, table=self.path)
        try:
            pf = pq.ParquetFile(fh)
            groups = _prune_row_groups(pf, filters)
            if groups is None:
                t = pf.read(columns=projection)
            else:
                t = pf.read_row_groups(groups, columns=projection)
            return t
        except (SnapshotChanged, StorageError):
            raise
        except MemoryError as ex:   # transient pressure, never quarantined
            raise ConnectorError(
                f"parquet read failed for {path}: {ex}") from None
        except Exception as ex:
            raise quarantine.record(path, fh.etag, -1, str(ex),
                                    table=self.path) from None


#: (lo, hi, nulls) of a column no chunk has been folded into: lo > hi
_NO_VALUES = (0, -1, False)


def _fold_stats(cols: dict, chunk, bounded: set) -> None:
    """Widen `cols[name]` = (lo, hi, nulls) by one column chunk's footer
    statistics (`ParquetTable.lane_stats`)."""
    name = chunk.path_in_schema
    lo, hi, nulls = cols.get(name, _NO_VALUES)
    st = chunk.statistics
    nulls = nulls or st is None or not st.has_null_count or st.null_count > 0
    if name not in bounded or lo is None:
        lo = hi = None
    elif st is None or not st.has_min_max:
        if st is None or st.num_values:     # values, and no bounds for them
            lo = hi = None
    elif lo > hi:
        lo, hi = st.min_raw, st.max_raw
    else:
        lo, hi = min(lo, st.min_raw), max(hi, st.max_raw)
    cols[name] = (lo, hi, nulls)


def files_bytes(files: list[str]) -> Optional[int]:
    """Total on-disk size of a connector's files (chunked-execution sizing)."""
    try:
        return sum(os.path.getsize(f) for f in files)
    except OSError:
        return None


def file_snapshot(files: list[str]) -> tuple:
    """(path, mtime_ns, size) per file — the cache/CDC invalidation token for
    file-backed connectors (igloo_tpu/exec/cache.py, igloo_tpu/cdc.py)."""
    out = []
    for f in files:
        try:
            st = os.stat(f)
            out.append((f, st.st_mtime_ns, st.st_size))
        except OSError:
            out.append((f, -1, -1))
    return tuple(out)


def _expand(path: str) -> list[str]:
    if os.path.isdir(path):
        return sorted(_glob.glob(os.path.join(path, "**", "*.parquet"),
                                 recursive=True))
    if any(ch in path for ch in "*?["):
        return sorted(_glob.glob(path))
    return [path] if os.path.exists(path) else []


def _expand_store(store, path: str, suffix: str = ".parquet") -> list[str]:
    """File set for `path` on any ObjectStore backend: a plain key lists
    itself, a glob matches, a prefix/directory lists recursively filtered
    to `suffix` (the LocalStore case reproduces `_expand` exactly)."""
    keys = store.list_prefix(path)
    if keys == [path] or any(ch in path for ch in "*?["):
        return sorted(keys)   # plain key or explicit glob: take as matched
    return sorted(k for k in keys if k.endswith(suffix))


def _prune_row_groups(pf: pq.ParquetFile, filters) -> Optional[list[int]]:
    """Row-group pruning from column statistics for simple `col <op> literal`
    predicates. Best-effort: returning None means read everything (the engine
    re-applies every filter exactly)."""
    preds = _simple_preds(filters)
    return _prune_by(pf.metadata, preds) if preds else None


def _simple_preds(filters) -> tuple:
    return tuple(p for p in map(_simple_pred, filters or ()) if p is not None)


def _prune_by(meta, preds) -> Optional[list[int]]:
    """The row groups of a file whose statistics admit every `preds`
    (`_simple_pred` triples), or None when all do."""
    name_to_idx = {meta.schema.column(i).path: i
                   for i in range(meta.num_columns)}
    keep = []
    for g in range(meta.num_row_groups):
        rg = meta.row_group(g)
        alive = True
        for col, op, val in preds:
            ci = name_to_idx.get(col)
            if ci is None:
                continue
            st = rg.column(ci).statistics
            if st is None or not st.has_min_max:
                continue
            mn, mx = _stat_value(st.min), _stat_value(st.max)
            if mn is None or mx is None:
                continue
            try:
                if op == ">" and mx <= val:
                    alive = False
                elif op == ">=" and mx < val:
                    alive = False
                elif op == "<" and mn >= val:
                    alive = False
                elif op == "<=" and mn > val:
                    alive = False
                elif op == "=" and (val < mn or val > mx):
                    alive = False
            except TypeError:
                continue
            if not alive:
                break
        if alive:
            keep.append(g)
    if len(keep) == meta.num_row_groups:
        return None
    return keep


_OPS = {E.BinOp.GT: ">", E.BinOp.GTE: ">=", E.BinOp.LT: "<", E.BinOp.LTE: "<=",
        E.BinOp.EQ: "="}
_FLIP = {">": "<", ">=": "<=", "<": ">", "<=": ">=", "=": "="}


def _simple_pred(e: E.Expr):
    """col <op> literal (either order) -> (col_name, op, python_value)."""
    if not isinstance(e, E.Binary) or e.op not in _OPS:
        return None
    l, r = e.left, e.right
    if isinstance(l, E.Column) and isinstance(r, E.Literal):
        col, lit, op = l, r, _OPS[e.op]
    elif isinstance(r, E.Column) and isinstance(l, E.Literal):
        col, lit, op = r, l, _FLIP[_OPS[e.op]]
    else:
        return None
    v = lit.value
    if v is None:
        return None
    if lit.literal_type is not None and lit.literal_type.id.value == "date32":
        v = _dt.date(1970, 1, 1) + _dt.timedelta(days=int(v))
    return (col.name.split(".")[-1], op, v)


def _stat_value(v):
    if isinstance(v, bytes):
        try:
            return v.decode("utf-8")
        except UnicodeDecodeError:
            return None
    return v
