"""Cross-worker shuffle exchange: hash partitioning + the bucketed,
bytes-budgeted fragment store.

The reference declares `FragmentType::Shuffle` (crates/coordinator/src/
fragment.rs:12) and never constructs it; its worker shuffle fetch returns
empty bytes (crates/worker/src/service.rs:26-32). This module is the real
thing for the Flight/fragment tier (the TPU mesh tier has its own all_to_all
shuffle in parallel/shuffle.py — see docs/distributed.md):

- `bucket_ids` assigns every row of an Arrow table to one of N buckets by a
  deterministic hash of its join-key columns. The hash is a pure function of
  the key BYTES (strings go through the native hash64.c dictionary path, the
  same primitive GRACE partitioning uses), so two workers hashing the two
  sides of a join agree on bucket placement without coordination.
- `FragmentStore` replaces the worker's `dict[str, pa.Table]` result map: a
  fragment result is held as a list of record batches with optional per-bucket
  partition metadata (rows/bytes per bucket), under a configurable bytes
  budget. Results that push the store over budget spill to Arrow IPC files
  and are served batch-at-a-time off disk — a multi-GB fragment
  result never needs to be resident to be transferred.
- do_get tickets address either a whole fragment (`<frag_id>`) or one bucket
  slice (JSON `{"frag": id, "bucket": b, "nbuckets": n}`) — the wire format
  of the per-bucket exchange the distributed planner emits for joins.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Iterator, Optional

import numpy as np
import pyarrow as pa

from igloo_tpu.cluster import protocol
from igloo_tpu.utils import tracing

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_MIX = np.uint64(0xC2B2AE3D27D4EB4F)

# stream granularity: small enough that one in-flight batch is cheap to
# buffer on both ends, large enough that per-message overhead amortizes
BATCH_ROWS = 65536

STORE_BUDGET_ENV = "IGLOO_FRAGMENT_STORE_BYTES"
DEFAULT_STORE_BUDGET = 1 << 30

# lock discipline (checked by igloo-lint lock-discipline): FragmentStore is
# hit concurrently by Flight RPC threads (execute_fragment stores, do_get
# streams, release drops) — every access to the entry map and its spill
# bookkeeping must hold the store lock or sit in a `*_locked` method
_GUARDED_BY = {"_lock": ("_entries", "_seq", "_tmpdir", "_released")}

#: released-fragment tombstones kept (FIFO): big enough to cover every id a
#: burst of queries can release while one abandoned execution drags on,
#: small enough to never matter (ids are 12-byte hex)
TOMBSTONE_CAP = 4096


# --- deterministic key hashing ----------------------------------------------


def _column_vals(col, typ) -> np.ndarray:
    """Canonical pre-mix uint64 lane for one key column (process-independent:
    strings hash their bytes via native hash64.c, numerics use a canonical
    int64/bit pattern). Nulls read as 0 — they only need a consistent ROUTE,
    equality semantics stay with the join that consumes the bucket. The
    per-column avalanche (multiply + shift-xor) happens downstream."""
    import pyarrow.compute as pc

    from igloo_tpu.exec.batch import hash64_bytes
    if pa.types.is_dictionary(typ) or pa.types.is_string(typ) or \
            pa.types.is_large_string(typ):
        if not pa.types.is_dictionary(col.type):
            col = col.dictionary_encode()
        dvals = np.asarray(col.dictionary.to_numpy(zero_copy_only=False),
                           dtype=object)
        ids = np.asarray(pc.fill_null(col.indices, 0)).astype(np.int64)
        return hash64_bytes(dvals, seed=0)[ids] if len(dvals) else \
            np.zeros(len(col), dtype=np.uint64)
    if pa.types.is_floating(typ):
        v = np.asarray(col.cast(pa.float64()).fill_null(0.0),
                       dtype=np.float64)
        # canonicalize -0.0 -> +0.0 and NaN -> one bit pattern so equal keys
        # (SQL equality) always share a bucket
        v = v + 0.0
        v = np.where(np.isnan(v), np.float64(0.0), v)
        return v.view(np.uint64)
    if pa.types.is_date32(typ):
        col = col.cast(pa.int32())
    return np.asarray(col.cast(pa.int64()).fill_null(0)).astype(np.uint64)


def _hash_column(col, typ) -> np.ndarray:
    """uint64 hash lane for one key column: canonical value + avalanche."""
    vals = _column_vals(col, typ)
    h = vals * _GOLDEN
    return h ^ (h >> np.uint64(29))


def key_hash(table: pa.Table, key_indices: list[int]) -> np.ndarray:
    """Combined uint64 hash of the key columns named by position."""
    h = np.full(table.num_rows, np.uint64(0x243F6A8885A308D3),
                dtype=np.uint64)
    for i in key_indices:
        col = table.column(i)
        col = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
        c = _hash_column(col, table.schema.field(i).type)
        h = (h ^ c) * _MIX
        h ^= h >> np.uint64(33)
    return h


def bucket_ids(table: pa.Table, key_indices: list[int],
               nbuckets: int) -> np.ndarray:
    """int64 bucket id per row (high-bits mix so the modulus is independent
    of the low bits local join sorts use)."""
    h = key_hash(table, key_indices)
    return ((h >> np.uint64(17)) % np.uint64(nbuckets)).astype(np.int64)


def partition_table(table: pa.Table, key_indices: list[int],
                    nbuckets: int,
                    salt: Optional[tuple] = None) -> list[pa.Table]:
    """Split `table` into bucket slices by key hash: ONE stable argsort +
    boundary slices (zero-copy views of the reordered table), the same shape
    as GRACE's `_split_by_hash`. With `salt` (see `salted_partition`) the
    result has `nbuckets + salt - 1` slices."""
    slices, _base = salted_partition(table, key_indices, nbuckets, salt)
    return slices


def salted_partition(table: pa.Table, key_indices: list[int], nbuckets: int,
                     salt: Optional[tuple] = None
                     ) -> tuple[list[pa.Table], np.ndarray]:
    """(bucket slices, BASE per-bucket row counts). `salt` is
    (hot_bucket, S, role) — the wire fields of a salted `L.Exchange`:

    - role "probe": rows of `hot_bucket` are spread round-robin across
      {hot_bucket} + S-1 extra buckets (ids nbuckets..nbuckets+S-2); every
      probe row lands in exactly ONE bucket, so probe-preserving join
      semantics (INNER/LEFT/SEMI/ANTI with the probe on the preserved side)
      are untouched.
    - role "build": rows of `hot_bucket` stay in place AND are replicated
      into each extra bucket, so every salted fragment sees every build row
      that could match its probe slice. Only the hot BUCKET replicates —
      1/nbuckets of the side per extra bucket — which is what makes salting
      affordable when the build side is too big to broadcast.

    The returned base counts are always for the UNSALTED partitioning: the
    skew sketch the coordinator records must describe the key distribution,
    not the salted layout (else one salted run would erase the very skew
    signal that justified it)."""
    if salt is not None:
        hot, s_total, role = salt
        extra = max(int(s_total) - 1, 0)
    else:
        hot, extra, role = None, 0, None
    total = nbuckets + extra
    if table.num_rows == 0:
        return ([table.slice(0, 0) for _ in range(total)],
                np.zeros(nbuckets, dtype=np.int64))
    pid = bucket_ids(table, key_indices, nbuckets)
    base_counts = np.bincount(pid, minlength=nbuckets).astype(np.int64)
    if extra and role == "probe":
        idx = np.nonzero(pid == hot)[0]
        r = np.arange(len(idx)) % (extra + 1)
        pid = pid.copy()
        pid[idx[r > 0]] = nbuckets + r[r > 0] - 1
        tracing.counter("exchange.salted")
        tracing.counter("exchange.salted_rows", len(idx))
    elif extra and role == "build":
        rep = np.nonzero(pid == hot)[0]
        take = np.concatenate([np.arange(table.num_rows, dtype=np.int64)] +
                              [rep] * extra)
        pid = np.concatenate(
            [pid] + [np.full(len(rep), nbuckets + j, dtype=pid.dtype)
                     for j in range(extra)])
        table = table.take(take)
        tracing.counter("exchange.salted")
        tracing.counter("exchange.salted_rows", len(rep) * extra)
    sorted_tbl = table.take(np.argsort(pid, kind="stable"))
    counts = np.bincount(pid, minlength=total)
    out, off = [], 0
    for b in range(total):
        c = int(counts[b])
        out.append(sorted_tbl.slice(off, c))
        off += c
    return out, base_counts


# --- do_get ticket codec -----------------------------------------------------


def make_ticket(frag_id: str, bucket: Optional[int] = None,
                nbuckets: Optional[int] = None) -> bytes:
    """Encode through the registry (cluster/protocol.py EXCHANGE_TICKET); a
    whole-fragment request stays the bare id so stock clients keep working."""
    if bucket is None:
        return frag_id.encode()
    return json.dumps(protocol.EXCHANGE_TICKET.build(
        frag=frag_id, bucket=bucket, nbuckets=nbuckets)).encode()


def parse_ticket(raw: bytes) -> tuple[str, Optional[int], Optional[int]]:
    t = protocol.parse_exchange_ticket(raw)
    return t["frag"], t["bucket"], t["nbuckets"]


# --- the bytes-budgeted fragment store --------------------------------------


@dataclass
class _Stored:
    schema: pa.Schema
    batches: Optional[list]            # list[pa.RecordBatch]; None = spilled
    nbytes: int
    nbuckets: Optional[int] = None     # hash-partition bucket count (incl. salt)
    ranges: Optional[list] = None      # per-bucket (start, count) batch ranges
    meta: Optional[list] = None        # per-bucket {"rows": .., "bytes": ..}
    spill_path: Optional[str] = None
    seq: int = 0                       # insertion order (spill oldest first)
    rows: int = 0
    # UNSALTED per-bucket row counts: the skew sketch the coordinator
    # records into AdaptiveStats (salting must not mask the skew signal)
    base_rows: Optional[list] = None
    # streaming entries (StreamingPut): per-bucket lists of spill SEGMENT
    # paths written while the result was still arriving — a bucket's full
    # content is its segments' batches followed by its resident range
    bucket_files: Optional[list] = None


def _chunk(table: pa.Table) -> list:
    return table.to_batches(max_chunksize=BATCH_ROWS)


def measured_nbytes(batches) -> int:
    """Resident bytes of a batch list with shared buffers counted ONCE.
    Bucket slices of one reordered table share its physical buffers, and
    every slice of a dictionary column references the WHOLE unified
    dictionary — so summing per-batch `nbytes` prices that dictionary once
    PER BUCKET and the spill budget evicts 3-4x early on dictionary/
    carrier-heavy results. Buffer-address dedupe measures what is actually
    resident."""
    seen: set = set()
    total = 0

    def add(arr):
        nonlocal total
        for buf in arr.buffers():
            if buf is not None and buf.address not in seen:
                seen.add(buf.address)
                total += buf.size
    for b in batches:
        for col in b.columns:
            d = getattr(col, "dictionary", None)
            if d is not None:
                add(d)
            add(col)
    return total


def _plain(table: pa.Table) -> pa.Table:
    """Dictionary columns cast to their value type. Streaming spill segments
    are written incrementally to Arrow IPC files, and the FILE format forbids
    the dictionary replacement that per-chunk dictionaries would need — so
    the streaming path stores plain lanes and leaves dictionary unification
    to the no-spill finish (which rides the classic encoded path)."""
    if not any(pa.types.is_dictionary(f.type) for f in table.schema):
        return table
    cols, fields = [], []
    for i, f in enumerate(table.schema):
        col = table.column(i)
        if pa.types.is_dictionary(f.type):
            col = col.cast(f.type.value_type)
            f = pa.field(f.name, f.type.value_type, f.nullable)
        cols.append(col)
        fields.append(f)
    return pa.table(cols, schema=pa.schema(fields))


class FragmentStore:
    """Thread-safe fragment-result store with a resident-bytes budget.

    `put` accepts an optional partition spec (key column indices, bucket
    count): the result is hash-partitioned ONCE at store time and per-bucket
    rows/bytes metadata recorded, so every later bucket request is a slice,
    not a scan. When resident bytes exceed the budget, whole results spill
    (oldest first) to Arrow IPC files in a private temp dir and are served
    batch-at-a-time off disk — the budget bounds worker RSS, not result size."""

    def __init__(self, budget_bytes: Optional[int] = None):
        if budget_bytes is None:
            budget_bytes = int(os.environ.get(STORE_BUDGET_ENV,
                                              DEFAULT_STORE_BUDGET))
        self.budget_bytes = max(budget_bytes, 1 << 20)
        self._entries: dict[str, _Stored] = {}
        self._lock = threading.Lock()
        self._seq = 0
        self._tmpdir: Optional[str] = None
        # release tombstones: a dispatch the coordinator timed out (hung
        # worker) or cancelled keeps RUNNING server-side — gRPC deadlines
        # cancel the call, not the handler. When it finally finishes, its
        # `put` must not resurrect a result the query already released (the
        # coordinator will never release it again -> permanent RSS leak).
        # Fragment ids are per-query uuids, never reused, so dropping any
        # put of a released id is always correct.
        self._released: OrderedDict = OrderedDict()

    # --- writes ---

    def put(self, frag_id: str, table: pa.Table,
            partition: Optional[tuple[list[int], int]] = None,
            salt: Optional[tuple] = None) -> _Stored:
        if partition is not None:
            from igloo_tpu.exec import encoded
            keys, nb = partition
            # store-time hash partition on the query timeline: per-bucket
            # slices of THIS fragment's result, the exchange's shuffle write.
            # Partitioned results ship ENCODED (exec/encoded.py): strings
            # dictionary-encode ONCE on the whole input — the hash routes by
            # dictionary VALUES, so placement is unchanged and every bucket
            # slice shares one unified dictionary instead of rebuilding one
            # per record batch — and numerics narrow per slice under ONE
            # global spec, applied AFTER routing (hashing an offset carrier
            # would misroute keys across the two sides of a join). The peer
            # decodes on fetch (cluster/worker.py _fetch_dep); spilled
            # entries write the carrier bytes to disk as-is.
            with tracing.span("exchange.partition", buckets=nb,
                              rows=table.num_rows, salted=salt is not None):
                table = encoded.encode_strings(table)
                plan = encoded.plan_numeric(table)
                slices, base = salted_partition(table, list(keys), nb, salt)
                batches, ranges, meta = [], [], []
                schema = None
                for s in slices:
                    s = encoded.apply_numeric(s, plan)
                    schema = s.schema if schema is None else schema
                    bs = _chunk(s)
                    ranges.append((len(batches), len(bs)))
                    batches.extend(bs)
                    meta.append({"rows": s.num_rows,
                                 "bytes": sum(b.nbytes for b in bs)})
            tracing.counter("exchange.partitions")
            tracing.counter("exchange.partition_rows", table.num_rows)
            # MEASURED resident bytes, shared buffers counted once: the
            # bucket slices view ONE reordered table (and one unified
            # dictionary per string column), so per-batch nbytes sums would
            # over-report 3-4x on dictionary/carrier-heavy results and make
            # the spill budget evict that much early
            ent = _Stored(schema=schema, batches=batches,
                          nbytes=measured_nbytes(batches),
                          nbuckets=len(slices), ranges=ranges, meta=meta,
                          rows=table.num_rows,
                          base_rows=[int(c) for c in base])
            tracing.counter("exchange.partition_bytes", ent.nbytes)
        else:
            batches = _chunk(table)
            ent = _Stored(schema=table.schema, batches=batches,
                          nbytes=measured_nbytes(batches),
                          rows=table.num_rows)
        return self._install(frag_id, ent)

    def stream_put(self, frag_id: str, keys: list[int], nbuckets: int,
                   salt: Optional[tuple] = None,
                   budget_bytes: Optional[int] = None) -> "StreamingPut":
        """Incremental hash-partitioned write: the caller appends row-group
        sized chunks as they arrive (the streaming exchange — the producer
        never materializes its whole result), and `finish()` installs the
        entry. Chunks are hash-routed into per-bucket accumulators on
        append; when resident bytes cross half of `budget_bytes` (the QUERY
        out-of-core budget; defaults to the store budget) every bucket's
        resident batches flush to its open IPC segment file. A result that
        never spilled finishes through the classic encoded `put` path
        (dictionary unification + numeric narrowing intact,
        docs/compressed_execution.md)."""
        return StreamingPut(self, frag_id, keys, nbuckets, salt,
                            budget_bytes=budget_bytes)

    def _install(self, frag_id: str, ent: _Stored) -> _Stored:
        # a `__dep_<fid>:...` slice is released alongside fragment <fid>, so
        # its orphan check keys on the owning fragment id
        base = frag_id
        if base.startswith("__dep_"):
            base = base[len("__dep_"):].split(":", 1)[0]
        with self._lock:
            if frag_id in self._released or base in self._released:
                tracing.counter("exchange.orphan_dropped")
                self._drop_files_of(ent)
                return ent
            self._seq += 1
            ent.seq = self._seq
            self._entries[frag_id] = ent
            self._enforce_budget_locked()
        return ent

    @staticmethod
    def _drop_files_of(ent: _Stored) -> None:
        paths = list(ent.bucket_files and
                     [p for fs in ent.bucket_files for p in fs] or [])
        if ent.spill_path:
            paths.append(ent.spill_path)
        for p in paths:
            try:
                os.unlink(p)
            except OSError:
                pass

    def _segment_path_locked(self, name: str) -> str:
        if self._tmpdir is None:
            self._tmpdir = tempfile.mkdtemp(prefix="igloo-fragstore-")
        return os.path.join(self._tmpdir,
                            f"{name}.arrow".replace("/", "_"))

    def _segment_path(self, name: str) -> str:
        with self._lock:
            return self._segment_path_locked(name)

    def _enforce_budget_locked(self) -> None:
        while self.resident_bytes_locked() > self.budget_bytes:
            resident = [(e.seq, fid) for fid, e in self._entries.items()
                        if e.batches is not None]
            if len(resident) == 0:
                return
            _, fid = min(resident)
            self._spill_locked(fid)

    def resident_bytes_locked(self) -> int:
        return sum(e.nbytes for e in self._entries.values()
                   if e.batches is not None)

    def resident_bytes(self) -> int:
        with self._lock:
            return self.resident_bytes_locked()

    def _spill_locked(self, frag_id: str) -> None:
        ent = self._entries[frag_id]
        if ent.bucket_files is not None:
            # streaming entry: the resident TAIL of each bucket moves to a
            # new per-bucket segment (appended after the ones StreamingPut
            # wrote), so bucket addressing survives the spill
            with tracing.span("exchange.spill", bytes=ent.nbytes):
                for b in range(ent.nbuckets):
                    start, count = ent.ranges[b]
                    if count <= 0:
                        continue
                    path = self._segment_path_locked(f"{frag_id}.b{b}.tail")
                    with pa.OSFile(path, "wb") as f, \
                            pa.ipc.new_file(f, ent.schema) as w:
                        for batch in ent.batches[start:start + count]:
                            w.write_batch(batch)
                    ent.bucket_files[b].append(path)
            ent.batches = None
            ent.ranges = [(0, 0)] * ent.nbuckets
            tracing.counter("exchange.spills")
            tracing.counter("exchange.spill_bytes", ent.nbytes)
            return
        path = self._segment_path_locked(frag_id)
        with tracing.span("exchange.spill", bytes=ent.nbytes):
            with pa.OSFile(path, "wb") as f, \
                    pa.ipc.new_file(f, ent.schema) as w:
                for b in ent.batches:
                    w.write_batch(b)
        ent.spill_path = path
        ent.batches = None
        tracing.counter("exchange.spills")
        tracing.counter("exchange.spill_bytes", ent.nbytes)

    def release(self, ids: list[str]) -> None:
        with self._lock:
            for fid in ids:
                self._released[fid] = None
                self._released.move_to_end(fid)
                ent = self._entries.pop(fid, None)
                if ent is not None:
                    self._drop_files_of(ent)
            while len(self._released) > TOMBSTONE_CAP:
                self._released.popitem(last=False)

    # --- reads ---

    def __contains__(self, frag_id: str) -> bool:
        with self._lock:
            return frag_id in self._entries

    def ids(self) -> list[str]:
        with self._lock:
            return list(self._entries)

    def bucket_meta(self, frag_id: str) -> Optional[list]:
        with self._lock:
            ent = self._entries.get(frag_id)
            return list(ent.meta) if ent is not None and ent.meta else None

    def _entry_range_locked(self, frag_id: str, bucket: Optional[int],
                            nbuckets: Optional[int]):
        ent = self._entries.get(frag_id)
        if ent is None:
            raise KeyError(frag_id)
        if bucket is None:
            return ent, 0, -1  # -1 = every batch
        if ent.nbuckets is None:
            raise ValueError(f"fragment {frag_id} is not hash-partitioned")
        if nbuckets is not None and nbuckets != ent.nbuckets:
            raise ValueError(
                f"fragment {frag_id} partitioned into {ent.nbuckets} "
                f"buckets, request asked for {nbuckets}")
        if not 0 <= bucket < ent.nbuckets:
            raise ValueError(f"bucket {bucket} out of range")
        start, count = ent.ranges[bucket]
        return ent, start, count

    def stream(self, frag_id: str, bucket: Optional[int] = None,
               nbuckets: Optional[int] = None
               ) -> tuple[pa.Schema, Iterator]:
        """(schema, batch iterator) for a fragment result or one bucket slice.
        Resident entries iterate their in-memory batches; spilled entries read
        one batch at a time from the IPC file (plain buffered reads, NOT a
        memory map: mapped pages would count against this process's RSS for
        the whole stream, defeating the budget), so serving never
        re-materializes the whole result."""
        with self._lock:
            ent, start, count = self._entry_range_locked(frag_id, bucket,
                                                         nbuckets)
            batches = list(ent.batches) if ent.batches is not None else None
            spill = ent.spill_path
            files = ([list(fs) for fs in ent.bucket_files]
                     if ent.bucket_files is not None else None)

        def gen():
            if files is not None:
                # streaming entry: a bucket is its spill segments' batches
                # followed by its resident tail; a whole-fragment read walks
                # every bucket (consumers concat, order is irrelevant)
                sel_files = [p for fs in files for p in fs] if bucket is None \
                    else list(files[bucket])
                for path in sel_files:
                    src = pa.OSFile(path, "rb")
                    try:
                        reader = pa.ipc.open_file(src)
                        for i in range(reader.num_record_batches):
                            yield reader.get_batch(i)
                    finally:
                        src.close()
                if batches is not None:
                    sel = batches if count < 0 \
                        else batches[start:start + count]
                    for b in sel:
                        yield b
                return
            if batches is not None:
                sel = batches if count < 0 else batches[start:start + count]
                for b in sel:
                    yield b
                return
            src = pa.OSFile(spill, "rb")
            try:
                reader = pa.ipc.open_file(src)
                n = reader.num_record_batches if count < 0 else count
                s = 0 if count < 0 else start
                for i in range(s, s + n):
                    yield reader.get_batch(i)
            finally:
                src.close()
        return ent.schema, gen()

    def get_table(self, frag_id: str, bucket: Optional[int] = None,
                  nbuckets: Optional[int] = None) -> pa.Table:
        schema, it = self.stream(frag_id, bucket, nbuckets)
        return pa.Table.from_batches(list(it), schema=schema)


class StreamingPut:
    """Incremental hash-partitioned writer (one producer thread; the store's
    lock guards only the shared install/segment-path steps).

    `append` routes each row-group-sized chunk into per-bucket accumulators;
    when routed-but-unflushed bytes cross the flush threshold (half the store
    budget) EVERY bucket's resident batches are appended to that bucket's open
    IPC segment file and dropped. Flushing all buckets — not just the largest
    — is what actually frees memory: the bucket slices of one routed chunk
    are zero-copy views of a single reordered table, so holding any one of
    them holds them all.

    `finish` installs the entry. A result that never flushed is re-submitted
    through the classic encoded `put` (dictionary-unify once, narrow per
    slice); proven-small data pays one extra in-RAM hash pass to keep the
    PR 16 carrier savings. A flushed result installs as a `bucket_files`
    entry: plain lanes, per-bucket segment files plus the resident tail."""

    def __init__(self, store: FragmentStore, frag_id: str, keys: list[int],
                 nbuckets: int, salt: Optional[tuple],
                 budget_bytes: Optional[int] = None):
        self._store = store
        self._frag_id = frag_id
        self._keys = list(keys)
        self._nbuckets = int(nbuckets)
        self._salt = salt
        extra = max(int(salt[1]) - 1, 0) if salt is not None else 0
        self._total = self._nbuckets + extra
        # flush threshold tracks the QUERY's out-of-core budget when given
        # (the worker store's own budget is sized for caching, not spilling)
        base_budget = budget_bytes if budget_bytes else store.budget_bytes
        self._flush_bytes = max(base_budget // 2, 1 << 19)
        self._schema: Optional[pa.Schema] = None
        self._buckets: list[list] = [[] for _ in range(self._total)]
        self._bucket_rows = [0] * self._total
        self._bucket_bytes = [0] * self._total
        self._base = np.zeros(self._nbuckets, dtype=np.int64)
        self._rows = 0
        self._bytes = 0
        self._resident = 0
        self._spilled = False
        # per-bucket (path, OSFile, ipc writer) — opened at first flush of
        # the bucket, closed in finish()/abort(); the IPC FILE footer only
        # lands on close, and nothing reads a segment before install
        self._writers: list = [None] * self._total

    def append(self, table: pa.Table) -> None:
        table = _plain(table)
        if self._schema is None:
            self._schema = table.schema
        elif table.schema != self._schema:
            table = table.cast(self._schema)
        if table.num_rows == 0:
            return
        tracing.counter("exchange.stream_chunks")
        slices, base = salted_partition(table, self._keys, self._nbuckets,
                                        self._salt)
        self._base += base
        self._rows += table.num_rows
        chunk_batches = []
        for b, s in enumerate(slices):
            if s.num_rows == 0:
                continue
            bs = _chunk(s)
            self._buckets[b].extend(bs)
            self._bucket_rows[b] += s.num_rows
            self._bucket_bytes[b] += sum(x.nbytes for x in bs)
            chunk_batches.extend(bs)
        got = measured_nbytes(chunk_batches)
        self._resident += got
        self._bytes += got
        if self._resident > self._flush_bytes:
            self._flush()

    def _writer(self, b: int):
        if self._writers[b] is None:
            path = self._store._segment_path(f"{self._frag_id}.b{b}")
            f = pa.OSFile(path, "wb")
            self._writers[b] = (path, f, pa.ipc.new_file(f, self._schema))
        return self._writers[b][2]

    def _flush(self) -> None:
        with tracing.span("exchange.spill", bytes=self._resident,
                          streaming=True):
            for b in range(self._total):
                bs = self._buckets[b]
                if not bs:
                    continue
                w = self._writer(b)
                for batch in bs:
                    w.write_batch(batch)
                self._buckets[b] = []
        tracing.counter("exchange.spills")
        tracing.counter("exchange.spill_bytes", self._resident)
        self._resident = 0
        self._spilled = True

    def _close_writers(self) -> list[list[str]]:
        files: list[list[str]] = [[] for _ in range(self._total)]
        for b, w in enumerate(self._writers):
            if w is None:
                continue
            path, f, writer = w
            writer.close()
            f.close()
            files[b] = [path]
            self._writers[b] = None
        return files

    def finish(self) -> _Stored:
        if self._schema is None:
            raise ValueError("stream_put finished without any append")
        if not self._spilled:
            # proved under budget: one concat + the classic encoded put
            whole = pa.Table.from_batches(
                [b for bs in self._buckets for b in bs], schema=self._schema)
            self._buckets = [[] for _ in range(self._total)]
            return self._store.put(self._frag_id, whole,
                                   partition=(self._keys, self._nbuckets),
                                   salt=self._salt)
        files = self._close_writers()
        batches, ranges, meta = [], [], []
        for b in range(self._total):
            bs = self._buckets[b]
            ranges.append((len(batches), len(bs)))
            batches.extend(bs)
            meta.append({"rows": self._bucket_rows[b],
                         "bytes": self._bucket_bytes[b]})
        ent = _Stored(schema=self._schema, batches=batches,
                      nbytes=measured_nbytes(batches),
                      nbuckets=self._total, ranges=ranges, meta=meta,
                      rows=self._rows,
                      base_rows=[int(c) for c in self._base],
                      bucket_files=files)
        tracing.counter("exchange.partitions")
        tracing.counter("exchange.partition_rows", self._rows)
        tracing.counter("exchange.partition_bytes", self._bytes)
        return self._store._install(self._frag_id, ent)

    def abort(self) -> None:
        """Drop everything (producer failed mid-stream): close and unlink
        any segment files, release the accumulators."""
        for files in self._close_writers():
            for p in files:
                try:
                    os.unlink(p)
                except OSError:
                    pass
        self._buckets = [[] for _ in range(self._total)]
        self._resident = 0
