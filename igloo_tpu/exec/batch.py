"""DeviceBatch: the TPU-resident columnar batch.

This is the engine's universal data representation on device, playing the role Arrow
`RecordBatch` plays in the reference (reference crates/engine/src/physical_plan.rs:10-17
streams RecordBatch between operators). Design differences are deliberate TPU choices:

- **Static shapes.** Every column is padded to a power-of-two `capacity`; a `live`
  boolean lane marks real rows. Filters do not compact (the reference's FilterExec
  eagerly materializes filtered batches, crates/engine/src/operators/filter.rs:39-68);
  we AND into the selection mask so downstream ops fuse into one XLA computation with
  no dynamic shapes. Compaction happens only where required (joins, shuffles, output),
  via a stable sort on the mask — still static-shaped.

- **Strings never touch HBM.** String columns are dictionary-encoded at scan time;
  the device sees int32 ids. Small dictionaries (<= HIGH_CARD_THRESHOLD uniques) are
  lexicographically sorted, so ORDER BY / MIN / MAX / range predicates work directly
  on ids; high-cardinality dictionaries stay UNSORTED (`DictInfo.is_sorted=False` —
  never compare such ids for order; order-sensitive operators must go through
  `DictInfo.ranks()` via `expr_compile.rank_lane`). Equality/LIKE/functions evaluate
  host-side over the dictionary and become id-lookups on device; cross-table string
  comparisons (join keys) go through per-entry 64-bit hashes (see `DictInfo.hashes`).

- **Nulls are a separate bool lane** (True = null), mirroring Arrow validity bitmaps
  but kept as full bool lanes for VPU-friendly masking.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from igloo_tpu.types import (
    BOOL, DATE32, FLOAT32, FLOAT64, INT32, INT64, STRING, TIMESTAMP,
    DataType, Field, Schema, TypeId,
)

from igloo_tpu.exec.capacity import MIN_CAPACITY, canonical_capacity


def round_capacity(n: int) -> int:
    """Pad row counts to the canonical shape family so XLA recompiles rarely
    (shape bucketing; cf. SURVEY.md §7 hard part 5). Delegates to the
    engine-wide capacity policy (exec/capacity.py): exact pow2 for small
    batches, a coarser geometric family with hysteresis above 2^16 so
    neighboring scale factors lower to the same compiled programs."""
    return canonical_capacity(n)


# 64-bit mixing constants (splitmix64 finalizer) used for dictionary/string hashing.
_SM64_C1 = np.uint64(0xBF58476D1CE4E5B9)
_SM64_C2 = np.uint64(0x94D049BB133111EB)


def hash64_bytes(values: Sequence[object], seed: int = 0) -> np.ndarray:
    """Host-side 64-bit FNV-1a + splitmix64-finalized hash of string values
    (dictionary entries). Prefers the native C path (igloo_tpu.native,
    hash64.c — per-entry byte loop in C); falls back to a numpy
    implementation vectorized over entries (the python-level loop is over the
    max string LENGTH, not entries×bytes). Both produce identical results."""
    n = len(values)
    if n == 0:
        return np.empty(0, dtype=np.uint64)
    bufs = [(v.encode("utf-8") if isinstance(v, str) else bytes(v)) if v is not None else None
            for v in values]
    from igloo_tpu import native
    fast = native.hash64_batch(bufs, seed)
    if fast is not None:
        return fast
    # numpy fallback: bound the (entries x max_len) working matrix — a
    # 6M-entry comment column would otherwise materialize gigabytes at once.
    # Chunk over the ALREADY-encoded bufs (not `values`) so nothing encodes twice.
    _CHUNK = 1 << 18
    if n > _CHUNK:
        return np.concatenate([_hash64_np(bufs[i: i + _CHUNK], seed)
                               for i in range(0, n, _CHUNK)])
    return _hash64_np(bufs, seed)


def _hash64_np(bufs: list, seed: int) -> np.ndarray:
    n = len(bufs)
    lengths = np.asarray([len(b) if b is not None else 0 for b in bufs], dtype=np.int64)
    none_mask = np.asarray([b is None for b in bufs], dtype=bool)
    max_len = int(lengths.max()) if n else 0
    mat = np.zeros((n, max_len), dtype=np.uint64)
    if max_len:
        flat = np.frombuffer(b"".join(b for b in bufs if b is not None), dtype=np.uint8)
        starts = np.zeros(n, dtype=np.int64)
        np.cumsum(lengths[:-1], out=starts[1:])
        rows, cols = np.nonzero(np.arange(max_len)[None, :] < lengths[:, None])
        mat[rows, cols] = flat[starts[rows] + cols]
    with np.errstate(over="ignore"):
        h = np.full(n, np.uint64(seed) + np.uint64(0x9E3779B97F4A7C15), dtype=np.uint64)
        prime = np.uint64(0x100000001B3)
        for j in range(max_len):
            active = j < lengths
            nh = (h ^ mat[:, j]) * prime
            h = np.where(active, nh, h)
        # splitmix64 finalize
        h ^= h >> np.uint64(30)
        h *= _SM64_C1
        h ^= h >> np.uint64(27)
        h *= _SM64_C2
        h ^= h >> np.uint64(31)
        h[none_mask] = np.uint64(seed) ^ np.uint64(0x9E3779B97F4A7C15)
    return h


@dataclass(frozen=True)
class DictInfo:
    """Host-side dictionary for a STRING column.

    values:  np object array of python strings. `is_sorted` marks the normal
             (lexicographically sorted) encoding, where ids double as ranks and
             order comparisons work directly on id lanes. High-cardinality
             columns (> HIGH_CARD_THRESHOLD uniques, e.g. TPC-H comment
             columns) skip the sort: ids are first-occurrence order
             (is_sorted=False) — equality/grouping/joins/output still work on
             ids, and order-sensitive operators gather through the lazily
             computed `ranks()` LUT instead.
    hashes:  uint64[len] per-entry hash (seed 0)   — device-gatherable for join keys.
    hashes2: uint64[len] independent hash (seed 1) — collision guard (128-bit effective).
    """
    values: np.ndarray
    hashes: np.ndarray
    hashes2: np.ndarray
    is_sorted: bool = True

    @staticmethod
    def from_values(values: Sequence[object]) -> "DictInfo":
        arr = np.asarray(list(values), dtype=object)
        return DictInfo(arr, hash64_bytes(arr, seed=0), hash64_bytes(arr, seed=1))

    def ranks(self) -> np.ndarray:
        """int32[len]: lexicographic rank per id. Identity for sorted
        dictionaries; computed once (and cached) for unsorted ones — only
        queries that actually ORDER/MIN/MAX/compare the column pay the sort."""
        r = getattr(self, "_ranks", None)
        if r is None:
            if self.is_sorted:
                r = np.arange(len(self.values), dtype=np.int32)
            else:
                order = np.argsort(self.values.astype(str), kind="stable")
                r = np.empty(len(self.values), dtype=np.int32)
                r[order] = np.arange(len(self.values), dtype=np.int32)
            object.__setattr__(self, "_ranks", r)
        return r

    def __len__(self) -> int:
        return len(self.values)

    # DictInfo rides in jit static aux data (pytree aux of DeviceColumn): hash/eq
    # by content fingerprint so identical dictionaries share compile-cache entries.
    def _fingerprint(self) -> int:
        fp = getattr(self, "_fp", None)
        if fp is None:
            fp = hash((len(self.values), self.hashes.tobytes()))
            object.__setattr__(self, "_fp", fp)
        return fp

    def __hash__(self) -> int:
        return self._fingerprint()

    def __eq__(self, other) -> bool:
        # exact content equality (only reached after a fingerprint bucket match,
        # so the array compare is rare): a fingerprint collision must NOT alias
        # two dictionaries in the jit compile cache
        return isinstance(other, DictInfo) and \
            self._fingerprint() == other._fingerprint() and \
            np.array_equal(self.hashes, other.hashes) and \
            np.array_equal(self.hashes2, other.hashes2)


@dataclass
class DeviceColumn:
    """One column: a padded device lane + optional null lane + host dictionary.

    When `carrier` is set, `values` holds the NARROW transfer carrier
    (exec/codec.py) rather than the engine lane dtype — the compressed form is
    the resident form. Operators that need actual values widen at the point of
    use via `wide_values` (in-jit: XLA fuses the cast/divide into the
    consumer, so the wide lane exists only transiently inside the program);
    operators that only move/mask/gather rows (filters via masks, compaction,
    resize, exchange staging) keep the carrier untouched. `carrier_arg` is the
    0-d runtime payload (real offset / scale divisor) matching the CANONICAL
    spec in `carrier` — see codec.upload_columns for why it is runtime data.
    For an f32-pair carrier (`carrier.pair`, PR 37) it is the LOW half of the
    float64 lane instead: a `[capacity]` f32 lane beside `values`, the high
    half. A pair is the form a column is RESIDENT and read in; whatever moves
    rows goes through `map_rows`, which widens it first."""
    dtype: DataType
    values: jax.Array              # [capacity], carrier dtype when `carrier` is set
    nulls: Optional[jax.Array]     # [capacity] bool, True = null; None = no nulls
    dictionary: Optional[DictInfo] = None  # STRING columns only
    # host-side (lo, hi) value bounds for integer-family columns, computed at
    # scan time and propagated (never widened) through filters/joins/sorts.
    # Powers the direct "array join" fast path (exec/join.py direct_join):
    # dense PK-FK joins become scatter+gather instead of sorts. None = unknown.
    bounds: Optional[tuple] = None
    carrier: Optional["WidenSpec"] = None   # codec.WidenSpec; None = wide lane
    carrier_arg: Optional[jax.Array] = None  # 0-d offset/scale payload

    @property
    def capacity(self) -> int:
        return self.values.shape[0]

    @property
    def is_pair(self) -> bool:
        """An f32-pair carrier: `carrier_arg` is a per-row lane."""
        return self.carrier is not None and self.carrier.pair

    @property
    def carrier_nbytes(self) -> int:
        """Resident bytes of the value lane in the form it is held: the
        carrier's, and 8 B a lane for an f32 pair (both halves, once)."""
        if self.is_pair:
            return self.values.nbytes + self.carrier_arg.nbytes
        return self.values.nbytes

    @property
    def nbytes(self) -> int:
        """Resident bytes of every per-row lane: the carrier and the nulls."""
        return self.carrier_nbytes + (
            self.nulls.nbytes if self.nulls is not None else 0)

    def with_nulls(self, nulls: Optional[jax.Array]) -> "DeviceColumn":
        return replace(self, nulls=nulls)

    def map_rows(self, fn) -> "DeviceColumn":
        """The column with `fn` (a gather, a slice, a pad: anything that maps
        a `[n]` lane to an `[m]` one, whatever its dtype) applied to each of
        its per-row lanes. THE way to move rows: a narrow carrier rides along
        untouched. An f32 pair does not: it is widened first (in-trace: inside
        one program the wide value is the same two f32 arrays the chip's split
        wrote before, intermediates the compiler may keep in fast memory) and
        leaves as a wide lane — gathering its halves as two parameters out of
        HBM cost a q3 20 ms of 204 on the v5e (PERF.md, PR 37). Bounds are
        dropped, as every row mover did."""
        col = materialize(self) if self.is_pair else self
        return replace(
            col, values=fn(col.values),
            nulls=fn(col.nulls) if col.nulls is not None else None,
            bounds=None)


def wide_values(col: DeviceColumn) -> jax.Array:
    """The column's engine-lane values, widening the resident carrier in-jit
    if there is one. THE single decode point for device operators: call this
    (inside a jitted program — Env.from_batch does) instead of reading
    `.values` wherever actual values are consumed. Traced or eager."""
    spec = col.carrier
    if spec is None:
        return col.values
    if spec.pair:
        return spec.widen(col.values, lo_arg=col.carrier_arg)
    if spec.scale != 1.0:
        return spec.widen(col.values, scale_arg=col.carrier_arg)
    if spec.offset:
        return spec.widen(col.values, offset_arg=col.carrier_arg)
    return spec.widen(col.values)


def f32_halves(col: DeviceColumn):
    """The (hi, lo) float32 halves of a float64 column, where the form it is
    RESIDENT in holds them with no arithmetic: an f32 pair's two lanes; a
    carrier that float32 holds exactly and that widens by a cast alone (the
    f32 round trip, an integer of at most 16 bits), with a zero low half.
    None for every other form. What a pair-folded SUM reads in place of
    `wide_values` (kernels.seg_reduce): no decode and no split."""
    spec = col.carrier
    if spec is None or spec.lane != "float64":
        return None
    if spec.pair:
        return col.values, col.carrier_arg
    if spec.scale == 1.0 and not spec.offset and \
            col.values.dtype in (jnp.float32, jnp.int8, jnp.int16):
        hi = col.values.astype(jnp.float32)
        return hi, jnp.zeros_like(hi)
    return None


def materialize(col: DeviceColumn) -> DeviceColumn:
    """Eagerly widen a column to its engine lane (carrier dropped). Boundary
    escape hatch for code paths that cannot carry the carrier metadata —
    today: sharding a batch across the device mesh (parallel/mesh.py), where a
    0-d carrier_arg cannot take a row-sharded PartitionSpec."""
    if col.carrier is None:
        return col
    return replace(col, values=wide_values(col), carrier=None, carrier_arg=None)


def materialize_batch(batch: "DeviceBatch") -> "DeviceBatch":
    if all(c.carrier is None for c in batch.columns):
        return batch
    return replace(batch, columns=[materialize(c) for c in batch.columns])


@dataclass
class DeviceBatch:
    """A batch of rows resident in device memory (HBM)."""
    schema: Schema
    columns: list[DeviceColumn]
    live: jax.Array                # [capacity] bool selection mask

    @property
    def capacity(self) -> int:
        return int(self.live.shape[0])

    def column(self, name: str) -> DeviceColumn:
        return self.columns[self.schema.index_of(name)]

    def num_live(self) -> int:
        """Host sync: count of selected rows."""
        return int(jnp.sum(self.live))

    def nbytes(self) -> int:
        return self.live.nbytes + sum(c.nbytes for c in self.columns)

    # ---- construction -------------------------------------------------------

    @staticmethod
    def empty(schema: Schema, capacity: int = MIN_CAPACITY) -> "DeviceBatch":
        cols = []
        for f in schema:
            vals = jnp.zeros((capacity,), dtype=f.dtype.device_dtype())
            cols.append(DeviceColumn(f.dtype, vals, None,
                                     DictInfo.from_values([]) if f.dtype.is_string else None))
        return DeviceBatch(schema, cols, jnp.zeros((capacity,), dtype=bool))


# --- pytree registration: DeviceBatch/DeviceColumn flow straight through jax.jit
# (arrays are leaves; dtype/schema/dictionaries are static aux so the compile
# cache keys on them — shape bucketing + dictionary fingerprints keep it small)

jax.tree_util.register_pytree_node(
    DeviceColumn,
    # carrier_arg is a leaf (0-d runtime payload, or a pair's per-row low
    # half; a None simply vanishes from the leaf list), the canonical
    # WidenSpec is static aux (frozen/hashable)
    # so the compile cache keys on carrier form — wide vs int8-offset vs
    # scaled-decimal columns compile distinct programs, as they must.
    lambda c: ((c.values, c.nulls, c.carrier_arg),
               (c.dtype, c.dictionary, c.bounds, c.carrier)),
    lambda aux, ch: DeviceColumn(aux[0], ch[0], ch[1], aux[1],
                                 aux[2] if len(aux) > 2 else None,
                                 aux[3] if len(aux) > 3 else None,
                                 ch[2] if len(ch) > 2 else None),
)

jax.tree_util.register_pytree_node(
    DeviceBatch,
    lambda b: ((b.columns, b.live), b.schema),
    lambda aux, ch: DeviceBatch(aux, ch[0], ch[1]),
)


# ---------------------------------------------------------------------------
# Arrow <-> device conversion (the host/HBM boundary; replaces the reference's
# in-process RecordBatch streaming, crates/engine/src/operators/parquet_scan.rs:40-85)
# ---------------------------------------------------------------------------

_ARROW_TO_TYPE = {
    pa.bool_(): BOOL,
    pa.int8(): INT32, pa.int16(): INT32, pa.int32(): INT32,
    pa.uint8(): INT32, pa.uint16(): INT32,
    pa.int64(): INT64, pa.uint32(): INT64, pa.uint64(): INT64,
    pa.float32(): FLOAT32,
    pa.float64(): FLOAT64,
    pa.date32(): DATE32,
    pa.string(): STRING, pa.large_string(): STRING, pa.utf8(): STRING,
}


def arrow_type_to_dtype(t: pa.DataType) -> DataType:
    if t in _ARROW_TO_TYPE:
        return _ARROW_TO_TYPE[t]
    if pa.types.is_timestamp(t):
        return TIMESTAMP
    if pa.types.is_decimal(t):
        return FLOAT64  # TPC-H decimals computed in float64 on device
    if pa.types.is_dictionary(t):
        return arrow_type_to_dtype(t.value_type)
    if pa.types.is_date64(t):
        return DATE32
    raise TypeError(f"unsupported arrow type {t}")


def schema_from_arrow(s: pa.Schema) -> Schema:
    return Schema([Field(f.name, arrow_type_to_dtype(f.type), f.nullable) for f in s])


def dtype_to_arrow(d: DataType) -> pa.DataType:
    return {
        TypeId.BOOL: pa.bool_(), TypeId.INT32: pa.int32(), TypeId.INT64: pa.int64(),
        TypeId.FLOAT32: pa.float32(), TypeId.FLOAT64: pa.float64(),
        TypeId.STRING: pa.string(), TypeId.DATE32: pa.date32(),
        TypeId.TIMESTAMP: pa.timestamp("us"), TypeId.NULL: pa.int32(),
    }[d.id]


# above this many distinct values a column keeps its dictionary UNSORTED
# (first-occurrence order from Arrow's C++ hash encoder): sorting millions of
# near-unique strings host-side (e.g. TPC-H l_comment at SF1, ~6M uniques)
# would dwarf query time, and only order-sensitive operators need ranks
HIGH_CARD_THRESHOLD = 1 << 16


def _encode_string_column(arr: pa.ChunkedArray, dict_info: Optional[DictInfo]):
    """Dictionary-encode via Arrow's C++ hash encoder. Small dictionaries are
    re-sorted so ids double as lexicographic ranks; high-cardinality ones stay
    unsorted (DictInfo.is_sorted=False, see HIGH_CARD_THRESHOLD). If
    `dict_info` is given, ids are assigned against it (table-unified
    dictionary); values absent from it are an error (scan builds the union up
    front)."""
    combined = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    null_mask = None
    if combined.null_count:
        null_mask = np.asarray(combined.is_null())

    if dict_info is None:
        if not pa.types.is_dictionary(combined.type):
            combined = combined.dictionary_encode()
        import pyarrow.compute as pc
        indices = pc.fill_null(combined.indices, 0)
        ids = np.asarray(indices).astype(np.int32)
        dvals = combined.dictionary.to_numpy(zero_copy_only=False)
        dvals = np.asarray(dvals, dtype=object)
        if len(dvals) <= HIGH_CARD_THRESHOLD:
            order = np.argsort(dvals.astype(str), kind="stable")
            lut = np.empty(len(dvals), dtype=np.int32)
            lut[order] = np.arange(len(dvals), dtype=np.int32)
            if len(dvals):
                ids = lut[ids]
            dict_info = DictInfo.from_values(dvals[order])
        else:
            dict_info = DictInfo(dvals, hash64_bytes(dvals, seed=0),
                                 hash64_bytes(dvals, seed=1), is_sorted=False)
        return ids, null_mask, dict_info

    # pre-unified dictionary: assign ids against it
    if pa.types.is_dictionary(combined.type):
        combined = combined.cast(pa.string()) \
            if not pa.types.is_large_string(combined.type.value_type) \
            else combined.cast(pa.large_string())
    np_vals = combined.to_numpy(zero_copy_only=False)
    safe = np.asarray(["" if v is None else v for v in np_vals], dtype=object)
    if len(dict_info) == 0:
        if len(np_vals) and not all(v is None for v in np_vals):
            raise ValueError("string values present but unified dictionary is empty")
        return np.zeros(len(np_vals), dtype=np.int32), null_mask, dict_info
    if dict_info.is_sorted:
        dstr = dict_info.values.astype(str)
        ids = np.searchsorted(dstr, safe.astype(str)).astype(np.int32)
        ids = np.clip(ids, 0, len(dict_info) - 1)
        ok = dstr[ids] == safe.astype(str)
    else:
        # vectorized lookup against an UNSORTED dictionary: binary-search the
        # rank-ordered values (ranks() caches the sort) instead of a per-row
        # python dict probe — O(rows log uniques) in numpy C, not an
        # interpreter loop over millions of rows
        ranks = dict_info.ranks()
        order = np.empty(len(ranks), dtype=np.int64)
        order[ranks] = np.arange(len(ranks))
        dstr = dict_info.values.astype(str)
        sorted_vals = dstr[order]
        svals = safe.astype(str)
        pos = np.clip(np.searchsorted(sorted_vals, svals), 0,
                      len(sorted_vals) - 1)
        ok = sorted_vals[pos] == svals
        ids = np.where(ok, order[pos], 0).astype(np.int32)
    if null_mask is not None:
        ok = ok | null_mask
    if not ok.all():
        missing = sorted({str(v) for v, o in zip(safe, ok) if not o})[:5]
        raise ValueError(f"string values not in unified dictionary: {missing}")
    return ids, null_mask, dict_info


def _arrow_column_to_numpy(arr: pa.ChunkedArray, dtype: DataType):
    combined = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    if pa.types.is_decimal(combined.type):
        combined = combined.cast(pa.float64())
    if pa.types.is_timestamp(combined.type):
        combined = combined.cast(pa.timestamp("us"))
    null_mask = None
    if combined.null_count:
        null_mask = np.asarray(pa.compute.is_null(combined).to_numpy(zero_copy_only=False))
        if dtype.id == TypeId.BOOL:
            fill = False
        elif dtype.is_float:
            fill = 0.0
        else:
            # fill integer-family nulls with the non-null MIN, not 0: null
            # lanes are masked everywhere (like dead lanes), but a 0 fill in
            # e.g. a timestamp column would drag the value range to [0, hi]
            # and defeat the offset-shrink transfer codec (exec/codec.py)
            mn = pa.compute.min(combined).as_py()
            fill = 0 if mn is None else mn
        combined = pa.compute.fill_null(combined, fill)
    np_vals = combined.to_numpy(zero_copy_only=False)
    np_vals = np.asarray(np_vals).astype(dtype.device_dtype(), copy=False)
    return np_vals, null_mask


_BOUNDED_IDS = (TypeId.INT32, TypeId.INT64, TypeId.DATE32, TypeId.TIMESTAMP)


def _int_bounds(np_vals: np.ndarray, null_mask, dtype: DataType):
    """(min, max) over non-null values of an integer-family column; None when
    the column is empty, all-null, or not integer-typed. Host-side stats that
    ride DeviceColumn.bounds into the planner's join-strategy choice."""
    if dtype.id not in _BOUNDED_IDS or len(np_vals) == 0:
        return None
    valid = np_vals if null_mask is None else np_vals[~null_mask]
    if len(valid) == 0:
        return None
    return (int(valid.min()), int(valid.max()))




def host_decode_column(arr: pa.ChunkedArray, f: Field,
                       dictionaries: Optional[dict[str, DictInfo]] = None):
    """Arrow column -> host-side (np_vals, null_mask, dinfo, bounds) in the
    engine lane dtype (string columns become int32 dictionary ids)."""
    if f.dtype.is_string:
        pre = dictionaries.get(f.name) if dictionaries else None
        ids, null_mask, dinfo = _encode_string_column(arr, pre)
        return ids, null_mask, dinfo, None
    np_vals, null_mask = _arrow_column_to_numpy(arr, f.dtype)
    bounds = _int_bounds(np_vals, null_mask, f.dtype)
    return np_vals, null_mask, None, bounds


def device_columns(decoded: list, fields: list, cap: int,
                   device=None, clock=None) -> list[DeviceColumn]:
    """Upload host-decoded columns as DeviceColumns, narrowed losslessly
    (exec/codec.py) — and kept narrow: the carrier array IS the resident
    `values` lane, with the WidenSpec riding along so operators widen at the
    point of use. Dead lanes (index >= n) carry the codec pad value — kernels
    must never read them unmasked (they were arbitrary zeros before too)."""
    from igloo_tpu.exec.codec import upload_columns
    plans = []
    for (np_vals, null_mask, _dinfo, _bounds) in decoded:
        lane = np_vals.dtype
        plans.append((np_vals, lane, cap))
        if null_mask is not None:
            plans.append((null_mask, None, cap))
    dev = upload_columns(plans, device=device, clock=clock)
    cols: list[DeviceColumn] = []
    i = 0
    for f, (np_vals, null_mask, dinfo, bounds) in zip(fields, decoded):
        dev_vals, spec, carg = dev[i]
        i += 1
        nulls = None
        if null_mask is not None:
            nulls = dev[i][0]
            i += 1
        cols.append(DeviceColumn(f.dtype, dev_vals, nulls, dinfo, bounds,
                                 spec, carg))
    return cols


def from_arrow(
    table: pa.Table,
    schema: Optional[Schema] = None,
    capacity: Optional[int] = None,
    dictionaries: Optional[dict[str, DictInfo]] = None,
    device=None,
    null_fields: Optional[set] = None,
    clock=None,
) -> DeviceBatch:
    """pyarrow Table -> DeviceBatch (host decode -> narrowed device_put into
    HBM -> on-device widen, one dispatch for the whole batch). Columns named
    in `null_fields` always get a null lane (all-False when the data has no
    nulls): the GRACE partition pipeline forces one shape per leaf across all
    partitions so null-free buckets key the same compiled programs as bucket
    siblings that do carry nulls. `clock` is handed to
    `codec.upload_columns`."""
    from igloo_tpu.exec.codec import live_lane
    if schema is None:
        schema = schema_from_arrow(table.schema)
    n = table.num_rows
    cap = capacity or round_capacity(n)
    decoded = [host_decode_column(table.column(f.name), f, dictionaries)
               for f in schema]
    if null_fields:
        decoded = [(v, np.zeros(n, dtype=bool)
                    if nm is None and f.name in null_fields else nm, di, b)
                   for f, (v, nm, di, b) in zip(schema, decoded)]
    cols = device_columns(decoded, list(schema), cap, device=device,
                          clock=clock)
    return DeviceBatch(schema, cols, live_lane(cap, n, device=device))


def to_arrow(batch: DeviceBatch) -> pa.Table:
    """DeviceBatch -> pyarrow Table on host, dropping dead lanes, decoding dictionaries,
    re-applying null masks. Order of surviving rows is preserved.

    All device buffers are fetched in ONE `jax.device_get` call: it issues every
    per-array copy_to_host_async before blocking, so the host waits on the
    device once instead of once per column."""
    host_live, host_vals, host_nulls, host_cargs = jax.device_get(
        (batch.live, [c.values for c in batch.columns],
         [c.nulls for c in batch.columns],
         [c.carrier_arg for c in batch.columns]))
    from igloo_tpu.utils.stats import record_fetch
    record_fetch((host_live, host_vals, host_nulls,
                  pair_halves(batch, host_cargs)))
    return arrow_from_host(batch, host_live, host_vals, host_nulls, host_cargs)


def pair_halves(batch: DeviceBatch, host_cargs) -> list:
    """Of a batch's fetched carrier args, the per-row ones (a pair's low
    half): what a fetch's byte count adds to values and nulls."""
    return [hc for c, hc in zip(batch.columns, host_cargs) if c.is_pair]


def arrow_from_host(batch: DeviceBatch, host_live, host_vals, host_nulls,
                    host_cargs=None) -> pa.Table:
    """Build the pyarrow Table from already-fetched host copies of a batch's
    device buffers (see `to_arrow`; the executor also calls this directly after
    a speculative compact-and-fetch). Carrier-resident columns are fetched
    NARROW (the whole point) and widened here on the host, after the dead-lane
    drop and before dictionary/date/timestamp decode — bit-identical to the
    device widen (codec.host_widen)."""
    if host_cargs is None:
        if any(c.carrier is not None for c in batch.columns):
            host_cargs = jax.device_get(
                [c.carrier_arg for c in batch.columns])
        else:
            host_cargs = [None] * len(batch.columns)
    from igloo_tpu.exec.codec import host_widen
    idx = np.nonzero(host_live)[0]
    arrays, fields = [], []
    for f, c, hv, hn, hc in zip(batch.schema, batch.columns, host_vals,
                                host_nulls, host_cargs):
        vals = hv[idx]
        nulls = hn[idx] if hn is not None else None
        if c.carrier is not None:
            vals = host_widen(c.carrier, vals,
                              hc[idx] if c.is_pair else hc)
        if f.dtype.is_string:
            d = c.dictionary.values if c.dictionary is not None and len(c.dictionary) else np.asarray([], dtype=object)
            if len(d):
                ids = np.clip(vals, 0, len(d) - 1)
                py = d[ids]
            else:
                py = np.asarray([""] * len(vals), dtype=object)
            if nulls is not None:
                py = py.copy()
                py[nulls] = None
            arrays.append(pa.array(py, type=pa.string()))
        elif f.dtype.id == TypeId.DATE32:
            a = pa.array(vals.astype("int32"), type=pa.int32()).cast(pa.date32())
            if nulls is not None:
                a = pa.compute.if_else(pa.array(~nulls), a, pa.scalar(None, type=pa.date32()))
            arrays.append(a)
        elif f.dtype.id == TypeId.TIMESTAMP:
            a = pa.array(vals.astype("int64"), type=pa.int64()).cast(pa.timestamp("us"))
            if nulls is not None:
                a = pa.compute.if_else(pa.array(~nulls), a, pa.scalar(None, type=pa.timestamp("us")))
            arrays.append(a)
        else:
            if nulls is not None:
                arrays.append(pa.array(vals, mask=nulls))
            else:
                arrays.append(pa.array(vals))
        fields.append(pa.field(f.name, arrays[-1].type, f.nullable))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))
