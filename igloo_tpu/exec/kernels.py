"""Shared device kernel primitives.

These are the building blocks the reference implements as per-row Rust loops
(hash_join.rs:116-211 row-at-a-time build/probe, filter.rs:47-57 per-batch eval) —
re-designed as static-shape, whole-column XLA programs:

- key normalization: any column -> int64 "key lane(s)" whose ordering/equality
  matches SQL semantics (floats via order-preserving bit tricks, strings via
  sorted-dictionary ids or dictionary hash lanes for cross-table equality)
- lexicographic argsort via iterated stable sorts (the TPU-friendly way to sort
  multi-key rows: no comparators, just k stable sorts of an index permutation)
- group boundary detection + segment ids for segment-reduce aggregation
- selection-mask compaction (stable partition live-to-front) — the static-shape
  replacement for the reference's eager `filter_record_batch`
- 64-bit avalanche hashing for multi-lane join keys (verified exactly afterwards,
  so collisions cost slots, never correctness)
"""
from __future__ import annotations

from dataclasses import replace
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from igloo_tpu import types as T
from igloo_tpu.exec.batch import (
    DeviceBatch, DeviceColumn, DictInfo, materialize,
)

# splitmix64 constants (public-domain finalizer)
_C1 = np.int64(np.uint64(0xBF58476D1CE4E5B9).astype(np.int64))
_C2 = np.int64(np.uint64(0x94D049BB133111EB).astype(np.int64))
_GOLDEN = np.int64(np.uint64(0x9E3779B97F4A7C15).astype(np.int64))


def mix64(x: jax.Array) -> jax.Array:
    """splitmix64 avalanche over an int64 lane."""
    x = x.astype(jnp.int64)
    ux = x.astype(jnp.uint64)
    ux = ux ^ (ux >> np.uint64(30))
    ux = ux * np.uint64(0xBF58476D1CE4E5B9)
    ux = ux ^ (ux >> np.uint64(27))
    ux = ux * np.uint64(0x94D049BB133111EB)
    ux = ux ^ (ux >> np.uint64(31))
    return ux.astype(jnp.int64)


def hash_lanes(lanes: list[jax.Array], nulls: list[Optional[jax.Array]]) -> jax.Array:
    """Combine key lanes into one well-mixed int64 per row. NULL contributes a
    distinct tag so (1, NULL) != (1, 2) pre-verification."""
    h = jnp.full(lanes[0].shape, _GOLDEN, dtype=jnp.int64)
    for lane, nl in zip(lanes, nulls):
        v = lane.astype(jnp.int64)
        if nl is not None:
            v = jnp.where(nl, np.int64(-0x61C8864680B583EB), v)
        h = mix64(h ^ mix64(v))
    return h


def normalize_float(x: jax.Array):
    """Canonicalize a float lane for grouping/hashing WITHOUT 64-bit bitcasts
    (the TPU X64 rewriter does not implement f64<->s64 bitcast-convert): returns
    (vnorm, nan_flag) where -0.0 -> +0.0 and every NaN collapses to 0.0 with the
    flag set. Equality on (vnorm, nan_flag) == SQL grouping equality; ordering on
    them (NaN flag as a more significant lane) == SQL "NaN sorts greatest"."""
    xf = x
    xf = jnp.where(xf == 0.0, jnp.zeros((), xf.dtype), xf)
    nan = jnp.isnan(xf)
    return jnp.where(nan, jnp.zeros((), xf.dtype), xf), nan


def float_hash_int_lanes(x: jax.Array) -> list[jax.Array]:
    """Deterministic int64 lanes for hashing a float lane, bitcast-free: integer
    part + scaled fraction + nan flag. Equal floats always map to equal lanes
    (required); nearby floats may collide (harmless — joins verify exactly)."""
    vnorm, nan = normalize_float(x)
    v = vnorm.astype(jnp.float64)
    # clamp so .astype(int64) is defined, keep determinism
    bounded = jnp.clip(v, -9.0e15, 9.0e15)
    ipart = bounded.astype(jnp.int64)
    frac = (bounded - ipart.astype(jnp.float64)) * np.float64(2.0 ** 52)
    return [ipart, frac.astype(jnp.int64), nan.astype(jnp.int64)]


def sort_lanes_for(v: jax.Array, null: Optional[jax.Array], is_float: bool,
                   ascending: bool, nulls_first: bool) -> list[tuple]:
    """Decompose one sort key into [(lane, ascending_flag), ...] most-significant
    first: null ordering lane, NaN lane (floats; NaN sorts greatest), value lane.
    Works for any lane dtype jnp.argsort accepts — no int64 bit tricks."""
    lanes: list[tuple] = []
    if null is None:
        nkey = jnp.zeros(v.shape, dtype=jnp.int32)
    else:
        nkey = jnp.where(null, np.int32(-1 if nulls_first else 1), np.int32(0))
    lanes.append((nkey, True))
    if is_float:
        vnorm, nan = normalize_float(v)
        lanes.append((nan.astype(jnp.int32), ascending))  # NaN greatest
        val = vnorm
    else:
        val = v
    if null is not None:
        val = jnp.where(null, jnp.zeros((), val.dtype), val)
    lanes.append((val, ascending))
    return lanes


def group_lanes_for(v: jax.Array, is_float: bool) -> list[jax.Array]:
    """Equality lanes for grouping: floats become (nan_flag, vnorm)."""
    if is_float:
        vnorm, nan = normalize_float(v)
        return [nan.astype(jnp.int32), vnorm]
    return [v]


def _argsort_dir(lane: jax.Array, ascending: bool) -> jax.Array:
    if ascending:
        return jnp.argsort(lane, stable=True)
    if lane.dtype == jnp.bool_:
        lane = lane.astype(jnp.int32)
    return jnp.argsort(-lane, stable=True)


def lex_argsort(lanes: list, live: jax.Array) -> jax.Array:
    """Stable lexicographic argsort. `lanes` = [(lane, ascending), ...]
    most-significant first. Dead rows always sort last. Returns permutation."""
    n = live.shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    # iterated stable sorts from least-significant lane to most-significant
    for lane, asc in reversed(lanes):
        perm = perm[_argsort_dir(jnp.take(lane, perm), asc)]
    # dead rows last (most significant)
    perm = perm[jnp.argsort(jnp.take(~live, perm), stable=True)]
    return perm


# ---------------------------------------------------------------------------
# Packed sort keys: fuse a multi-lane lexicographic key into ONE integer lane.
#
# The multi-lane chain (lex_argsort) pays one full stable sort per lane; a
# TPC-H q18-shaped group-by carries 5 keys = 10+ lanes = 10+ 8M-lane sorts.
# When every key is integer-family (ints, dates, timestamps, bools, dictionary
# ids) with host-known value bounds (scan stats, DeviceColumn.bounds), the keys
# bit-pack into one minimal-width integer digit string whose ordering equals
# the lexicographic ordering — ONE argsort replaces the whole chain, and when
# the digits fit 30 bits the lane is int32, halving sort bytes.
#
# Encoding per key (radix `card`, runtime offset `lo`):
#   value digit vd = v - lo            (descending keys: vd = card-2 - vd)
#   nulls-first:    digit = 0 for NULL else vd + 1
#   nulls-last:     digit = card-1 for NULL else vd
# Digits combine most-significant-first: acc = acc * card + digit. Radices are
# rounded to powers of two and offsets ride the ConstPool as RUNTIME data, so
# two executions whose bounds differ only in position (data refreshes, GRACE
# partitions) share one compiled program — only the radix bucket is static.
#
# Fallback ladder: packed int32 (<= 30 digit bits) -> packed int64 (<= 62) ->
# the multi-lane lex_argsort chain. One bit is always reserved for the
# dead-row sentinel (packed_sort_key), hence 62/30, not 63/31.
# ---------------------------------------------------------------------------

PACK_BITS_I64 = 62
PACK_BITS_I32 = 30


def _pack_card(lo: int, hi: int) -> int:
    """Per-key digit radix: power-of-two bucket of (span + NULL digit + 1
    headroom slot, so nulls-first and nulls-last encodings share one radix)."""
    span = int(hi) - int(lo) + 1
    card = 2
    while card < span + 2:
        card <<= 1
    return card


def _key_pack_range(k):
    """Host-known (lo, hi) value range of one key (a Compiled-shaped object:
    .dtype / .out_dict / .out_bounds), or None when the key cannot pack.
    Strings pack by dictionary id — callers that need ORDER semantics must
    ensure ids are ranks (sorted dictionary) before planning."""
    dt = k.dtype
    if dt.id == T.TypeId.BOOL:
        return (0, 1)
    if dt.is_string:
        d = k.out_dict
        if d is None:
            return None
        return (0, max(len(d) - 1, 0))
    if (dt.is_integer or dt.is_temporal) and k.out_bounds is not None:
        return (int(k.out_bounds[0]), int(k.out_bounds[1]))
    return None


def _build_pack_spec(ranges: list, ascending: list, nulls_first: list, pool):
    """(lane_tag, offsets_pool_idx, ((card, asc, nulls_first), ...)) or None
    when the digits exceed the int64 budget. Hashable: safe in jit cache keys."""
    digits = []
    offsets = []
    total = 1
    for (lo, hi), asc, nf in zip(ranges, ascending, nulls_first):
        card = _pack_card(lo, hi)
        total *= card
        if total > (1 << PACK_BITS_I64):
            return None
        offsets.append(int(lo))
        digits.append((card, bool(asc), bool(nf)))
    lane = "i32" if total <= (1 << PACK_BITS_I32) else "i64"
    oidx = pool.add(np.asarray(offsets, dtype=np.int64))
    return (lane, oidx, tuple(digits))


def plan_group_packing(keys: list, pool):
    """Pack plan for GROUP BY keys: grouping equality is symmetric, so ANY
    subset of the keys may fuse into the packed lane (unlike ORDER BY, which
    is limited to a prefix) — a q18-shaped 5-key group-by with one float key
    packs the other four; the aggregate kernel then folds the float's
    null/NaN flags into the packed lane's spare bits and sorts TWO lanes
    instead of 10+. Returns (spec, packed_key_indices) or None when packing
    would not drop at least one sort pass (fewer than 2 packable keys, unless
    that single packable key is the whole key set)."""
    if not keys:
        return None
    ranges = []
    idxs = []
    total = 1
    for i, k in enumerate(keys):
        r = _key_pack_range(k)
        if r is None:
            continue
        card = _pack_card(*r)
        if total * card > (1 << PACK_BITS_I64):
            continue
        total *= card
        ranges.append(r)
        idxs.append(i)
    if not idxs or (len(idxs) < 2 and len(idxs) != len(keys)):
        return None
    n = len(idxs)
    spec = _build_pack_spec(ranges, [True] * n, [True] * n, pool)
    if spec is None:
        return None
    return spec, tuple(idxs)


def plan_prefix_packing(keys: list, ascending, nulls_first, pool):
    """Longest packable key PREFIX (most-significant keys first) for ORDER BY:
    returns (spec, n_keys_packed) or None. A partial pack still pays: the
    prefix collapses to one lex_argsort lane ahead of the unpackable tail."""
    ranges = []
    total = 1
    for k in keys:
        if k.dtype.is_string and \
                (k.out_dict is None or not k.out_dict.is_sorted):
            break
        r = _key_pack_range(k)
        if r is None:
            break
        if total * _pack_card(*r) > (1 << PACK_BITS_I64):
            break
        total *= _pack_card(*r)
        ranges.append(r)
    npk = len(ranges)
    if npk == 0:
        return None
    spec = _build_pack_spec(ranges, list(ascending)[:npk],
                            list(nulls_first)[:npk], pool)
    if spec is None:
        return None
    return spec, npk


def plan_pair_packing(left_keys: list, right_keys: list, pool):
    """Shared pack spec for a join's residual-equality lanes: every key pair
    must be integer-family on BOTH sides with host-known bounds; the digit
    range is the union of the two sides' ranges (so equal values share a digit
    across tables). Strings never qualify — their ids are per-dictionary."""
    if not left_keys or len(left_keys) != len(right_keys):
        return None
    ranges = []
    for lk, rk in zip(left_keys, right_keys):
        if lk.dtype.is_string or rk.dtype.is_string:
            return None
        rl, rr = _key_pack_range(lk), _key_pack_range(rk)
        if rl is None or rr is None:
            return None
        ranges.append((min(rl[0], rr[0]), max(rl[1], rr[1])))
    n = len(ranges)
    return _build_pack_spec(ranges, [True] * n, [True] * n, pool)


def pack_key_lane(spec: tuple, vals: list, nulls: list,
                  consts: tuple) -> jax.Array:
    """Jit-traceable: normalized mixed-radix key digits -> one int lane whose
    ascending order IS the keys' lexicographic order (per-key direction and
    null placement baked into the digits). NULL lanes are replaced BEFORE the
    radix combine, so garbage values under a null mask cannot poison other
    keys' digits; dead-lane garbage wraps harmlessly and is overwritten by the
    packed_sort_key sentinel before any consumer reads it."""
    lane_tag, oidx, digits = spec
    offsets = consts[oidx]
    acc = None
    for i, ((card, asc, nf), v, nl) in enumerate(zip(digits, vals, nulls)):
        vd = v.astype(jnp.int64) - offsets[i]
        if not asc:
            vd = np.int64(card - 2) - vd
        if nf:
            d = vd + np.int64(1)
            if nl is not None:
                d = jnp.where(nl, np.int64(0), d)
        else:
            d = vd
            if nl is not None:
                d = jnp.where(nl, np.int64(card - 1), d)
        acc = d if acc is None else acc * np.int64(card) + d
    if lane_tag == "i32":
        return acc.astype(jnp.int32)
    return acc


def packed_sort_key(packed: jax.Array, live: jax.Array) -> jax.Array:
    """Displace dead rows to the dtype max so one argsort orders live rows by
    key AND sorts dead rows last. Digits use at most 62 (int64) / 30 (int32)
    bits, so the sentinel never collides with a live key."""
    return jnp.where(live, packed, jnp.iinfo(packed.dtype).max)


def group_segments(sorted_lanes: list, sorted_nulls: list,
                   sorted_live: jax.Array):
    """Given key lanes already permuted into sorted order, return
    (segment_id per row int32, is_group_start bool). Dead rows get segment id
    pointing at a trailing dummy segment."""
    n = sorted_live.shape[0]
    differs = jnp.zeros((n - 1,), dtype=bool) if n > 1 else jnp.zeros((0,), dtype=bool)
    for lane, nl in zip(sorted_lanes, sorted_nulls):
        dval = lane[1:] != lane[:-1]
        if nl is not None:
            n1, n0 = nl[1:], nl[:-1]
            # adjacent rows differ unless both NULL or both equal non-NULL
            # (SQL GROUP BY treats NULLs as one group)
            d = (n1 != n0) | (~n1 & ~n0 & dval)
        else:
            d = dval
        differs = differs | d
    first = jnp.ones((1,), dtype=bool) if n > 0 else jnp.zeros((0,), dtype=bool)
    boundary = jnp.concatenate([first, differs | (sorted_live[1:] != sorted_live[:-1])]) \
        if n > 1 else first
    start = boundary & sorted_live
    seg = jnp.cumsum(start.astype(jnp.int32)) - 1
    seg = jnp.where(sorted_live & (seg >= 0), seg, max(n - 1, 0))
    return seg.astype(jnp.int32), start


# At or below this many segments, segment reductions are masked reductions
# instead of XLA scatter ops (on TPU a scatter over an 8M-row lane costs
# ~300 ms): seg_reduce hands every (lane, segment id) pair to a variadic
# lax.reduce, whose ONE fusion reads `seg` and each lane once and keeps an
# accumulator per pair. That pass is bound by the selects and adds, not by
# HBM, so above the threshold its O(nseg * N) work loses to the O(N) scatter.
SMALL_NSEG = 64

# (lane, segment) accumulators of one variadic reduce; a longer list is cut
# into several. An accumulator is one operand, or an f32 pair's two: a pair
# counts ONCE, as the float64 operand it stands for always was two f32 words
# to the chip's compiler. The bound exists because at 64 float64 operands the
# TPU compiler stops fusing the selects into the reduce and writes every
# operand to HBM. Under it a whole Q1 (6 lanes x 6 feasible segments; five of
# the lanes pairs: 66 f32 operands) is ONE fusion: no segment mask and no
# product reaches HBM. Measured on a v5e at 2^26 lanes (PERF.md §6, PR 33):
# that Q1 takes 28.4 ms as one reduce, 29.7 as two of 18, 31.0 as six of 6;
# 288 operands (6 lanes x 48 segments) take 142 ms under this bound and 122
# under 16 or 24 — a shape no query of the benchmark has; lower the bound
# with a measurement of one that does. With its five float64 sums folded as
# pairs that Q1 is still one fusion in the chip's op list, 9.70 ms a q1 for
# 15.22 (PERF.md §5, PR 39); tests/test_tpu_compile.py holds the compiler
# to it without a chip.
MAX_REDUCE_OPERANDS = 48


def two_sum(a, b):
    """Error-free addition (Knuth): s + e == a + b exactly, in a's dtype."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def pair_add(x, y):
    """x + y of (hi, lo) f32 pairs, each standing for f64(hi) + f64(lo): the
    double-single sum a SUM needs, 13 f32 operations and 2 for the guard
    where the chip's general float64 add takes 27. The last two lines
    renormalise (|lo| <= ulp(hi) / 2): without them `lo` grows through a long
    fold and rounds at its own size (2^20 sequential charges: over 1e-11 of
    their sum for under 1e-12 with them; tests/test_kernels.py holds the
    difference). A non-finite `hi` (a +-Inf or NaN element, an overflow)
    makes two_sum's error term NaN, which `h = s + t` would carry into `hi`:
    the guard keeps it out, `hi` then reads what a plain f32 sum reads, and
    `lo` is NaN and of no account (`_widen_pair`)."""
    s, e = two_sum(x[0], y[0])
    t = e + x[1] + y[1]
    t = jnp.where(t == t, t, jnp.zeros((), t.dtype))
    h = s + t
    return h, t - (h - s)


def split_f32_pair(v: jax.Array):
    """float64 -> its (hi, lo) f32 halves, in-trace (codec.split_pair is the
    host's): on a chip whose float64 IS this pair, the halves it holds."""
    hi = v.astype(jnp.float32)
    return hi, (v - hi.astype(v.dtype)).astype(jnp.float32)


def _widen_pair(hi, lo):
    wide = hi.astype(jnp.float64)
    return jnp.where(jnp.isfinite(hi), wide + lo.astype(jnp.float64), wide)


class _SegOp(NamedTuple):
    fold: Callable      # (x, y) -> x op y
    identity: Callable  # dtype -> what an empty segment reads
    scatter: Callable   # the jax.ops.segment_* of the large-domain arm


_SEG_OPS = {
    "sum": _SegOp(jnp.add, lambda dt: jnp.zeros((), dt), jax.ops.segment_sum),
    "min": _SegOp(jnp.minimum, lambda dt: _ident_max(dt),
                  jax.ops.segment_min),
    "max": _SegOp(jnp.maximum, lambda dt: _ident_min(dt),
                  jax.ops.segment_max),
}


def seg_reduce(lanes: list, seg: jax.Array, nseg: int,
               seg_ids=None, pair_sums: bool = False) -> list:
    """Segment-reduce several lanes over ONE segment lane. `lanes` is a list
    of (values, op) with op in "sum" / "min" / "max"; returns one [nseg] array
    per lane, in the lane's dtype. `seg_ids` (static ints, default every id)
    are the segments that can hold a row: at SMALL_NSEG segments or fewer only
    they are reduced, every other slot reads the op's identity (0 / dtype max
    / dtype min) — what an empty segment reads; above, a scatter per lane
    fills every slot. Per element the arithmetic is where(seg == i, v, identity)
    folded in the lane's own dtype; only the association order is XLA's.

    A float64 "sum" lane may be given as its (hi, lo) f32 halves, and with
    `pair_sums` every float64 "sum" lane is split into them in-trace: the
    one-pass arm folds the halves with `pair_add` and widens each segment's
    pair once, into a float64 [nseg]. The caller asks for it where, and only
    where, the device's float64 is that pair (codec._f32pair_ok, read while
    the program is planned): there the fold is the float64 sum in fewer
    operations; on a real float64 it would lose five bits."""
    if nseg > SMALL_NSEG:
        return [_SEG_OPS[op].scatter(
                    _widen_pair(*v) if isinstance(v, tuple) else v, seg,
                    num_segments=nseg) for v, op in lanes]
    # a lane's operands: one, or a folded float64 sum's two halves
    halves = [v if isinstance(v, tuple) else
              split_f32_pair(v) if pair_sums and op == "sum"
              and v.dtype == jnp.float64 else (v,) for v, op in lanes]
    ids = list(range(nseg)) if seg_ids is None else list(seg_ids)
    idents = [tuple(_SEG_OPS[op].identity(a.dtype) for a in h)
              for h, (_, op) in zip(halves, lanes)]
    folds = [pair_add if len(h) == 2 else
             (lambda x, y, f=_SEG_OPS[op].fold: (f(x[0], y[0]),))
             for h, (_, op) in zip(halves, lanes)]
    pairs = [(k, i) for k in range(len(lanes)) for i in ids]
    found = {}
    for at in range(0, len(pairs), MAX_REDUCE_OPERANDS):
        chunk = pairs[at:at + MAX_REDUCE_OPERANDS]
        # operands, identities and results are lists of 1- or 2-tuples: one
        # per accumulator (lax.reduce flattens the tree)
        outs = jax.lax.reduce(
            [tuple(jnp.where(seg == i, a, z)
                   for a, z in zip(halves[k], idents[k])) for k, i in chunk],
            [idents[k] for k, _ in chunk],
            lambda xs, ys, chunk=chunk: [folds[k](x, y) for (k, _), x, y
                                         in zip(chunk, xs, ys)],
            (0,))
        found.update(zip(chunk, outs))
    out = []
    for k, h in enumerate(halves):
        cols = [jnp.stack([found.get((k, i), idents[k])[j]
                           for i in range(nseg)]) for j in range(len(h))]
        out.append(cols[0] if len(h) == 1 else _widen_pair(*cols))
    return out


def seg_sum(vals: jax.Array, seg: jax.Array, nseg: int) -> jax.Array:
    return seg_reduce([(vals, "sum")], seg, nseg)[0]


def seg_min(vals: jax.Array, seg: jax.Array, nseg: int) -> jax.Array:
    return seg_reduce([(vals, "min")], seg, nseg)[0]


def seg_max(vals: jax.Array, seg: jax.Array, nseg: int) -> jax.Array:
    return seg_reduce([(vals, "max")], seg, nseg)[0]


def _ident_max(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _ident_min(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def compact_perm(live: jax.Array) -> jax.Array:
    """Stable permutation bringing live rows to the front."""
    return jnp.argsort(~live, stable=True)


def _take_column(c: DeviceColumn, idx: jax.Array) -> DeviceColumn:
    # the carrier spec/arg ride along: a row gather permutes carrier lanes
    # as happily as wide ones (bounds dropped; an f32 pair leaves wide)
    return c.map_rows(lambda a: jnp.take(a, idx))


def apply_perm(batch: DeviceBatch, perm: jax.Array) -> DeviceBatch:
    cols = [_take_column(c, perm) for c in batch.columns]
    return DeviceBatch(batch.schema, cols, jnp.take(batch.live, perm))


def gather_batch(batch: DeviceBatch, idx: jax.Array,
                 valid: Optional[jax.Array] = None,
                 null_pad: bool = False) -> list[DeviceColumn]:
    """Gather rows of all columns by `idx`. When `null_pad` and valid is given,
    out-of-match rows become NULL (outer-join padding)."""
    safe = jnp.clip(idx, 0, batch.capacity - 1)
    cols = []
    for c in batch.columns:
        g = _take_column(c, safe)
        if null_pad and valid is not None:
            pad = ~valid
            g = replace(g, nulls=pad if g.nulls is None else (g.nulls | pad))
        cols.append(g)
    return cols


def concat_columns(parts_cols: list) -> list[DeviceColumn]:
    """Concatenate, column by column, the parts of a join's output (static
    shapes). Per-column carriers are consistent across parts — every part of
    a column gathers, or null-pads in carrier dtype, from the same source
    batch — so the output keeps the first part's spec/arg; but for an f32
    pair, which a part that moved rows no longer is (`map_rows`): those
    columns concatenate wide. A part without a null lane contributes
    all-False where another part has one."""
    out = []
    for parts in zip(*parts_cols):
        if any(p.is_pair for p in parts):
            parts = [materialize(p) for p in parts]
        nulls = None
        if any(p.nulls is not None for p in parts):
            nulls = jnp.concatenate([
                p.nulls if p.nulls is not None
                else jnp.zeros((p.capacity,), dtype=bool) for p in parts])
        out.append(replace(
            parts[0], values=jnp.concatenate([p.values for p in parts]),
            nulls=nulls, bounds=None))
    return out


def compact_to(batch: DeviceBatch, capacity: int) -> DeviceBatch:
    """Compact live rows to the front AND resize to `capacity` in one step,
    slicing the permutation BEFORE the column gathers so every gather is
    output-sized. The equivalent apply_perm(compact_perm)+resize pair gathers
    every column at FULL input width first — at 8M lanes x 8 columns that is
    ~0.5s of wasted HBM traffic per compaction on a v5e (XLA does not sink the
    later slice into the gather operand). Rows past `capacity` are dropped;
    callers guarantee (or flag-check) that live count fits."""
    perm = compact_perm(batch.live)
    if capacity < perm.shape[0]:
        perm = perm[:capacity]
    cols = [_take_column(c, perm) for c in batch.columns]
    live = jnp.take(batch.live, perm)
    if capacity > perm.shape[0]:
        return resize_batch(DeviceBatch(batch.schema, cols, live), capacity)
    return DeviceBatch(batch.schema, cols, live)


def resize_to(values: jax.Array, capacity: int, fill=0) -> jax.Array:
    n = values.shape[0]
    if n == capacity:
        return values
    if n > capacity:
        return values[:capacity]
    pad = jnp.full((capacity - n,), fill, dtype=values.dtype)
    return jnp.concatenate([values, pad])


def resize_batch(batch: DeviceBatch, capacity: int) -> DeviceBatch:
    """Change a batch's static capacity (host-decided; used for shape bucketing
    after host-synced row counts). Live rows must already be compacted when
    shrinking."""
    if capacity == batch.capacity:
        return batch
    # carrier survives a resize: the zero pad is dead lanes (masked), and
    # a zero carrier widening to the offset is still a masked lane (a zero
    # fill is False in a null lane)
    cols = [c.map_rows(lambda a: resize_to(a, capacity))
            for c in batch.columns]
    return DeviceBatch(batch.schema, cols, resize_to(batch.live, capacity, fill=False))
