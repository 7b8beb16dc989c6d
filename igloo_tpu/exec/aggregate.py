"""Group-by / aggregate kernel.

The reference has NO aggregation in its custom engine (DataFusion handles it on the
working path; the custom physical planner lowers only scan/filter/project/join,
physical_planner.rs:23-140). This is the TPU design from SURVEY.md §7 step 4:
sort-based segment reduction — one fused XLA computation, static shapes:

    keys -> lexicographic stable argsort -> contiguous groups -> boundary flags
         -> segment_sum/min/max over static segment count (= capacity)

Output capacity equals input capacity; row `i` of the output is group `i`
(compacted to the front, `live` marks real groups; a direct-scatter aggregate
wider than SMALL_NSEG segments leaves its groups at their segment ids:
groups_in_place). No hashing: grouping equality is exact lane comparison
after the sort, so no collision handling is needed.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from igloo_tpu import types as T
from igloo_tpu.exec import kernels as K
from igloo_tpu.exec.batch import (
    DeviceBatch, DeviceColumn, DictInfo, f32_halves,
)
from igloo_tpu.exec.expr_compile import Compiled, Env
from igloo_tpu.plan import logical as L
from igloo_tpu.plan.expr import AggFunc, Column, fingerprint
from igloo_tpu.utils import tracing


@dataclass(frozen=True)
class AggSpec:
    func: AggFunc
    arg: Optional[Compiled]       # None only for COUNT_STAR
    out_dtype: T.DataType
    out_dict: Optional[DictInfo]  # MIN/MAX over strings keep the arg dictionary
    # MIN/MAX over an UNSORTED (high-cardinality) string dictionary: ids are
    # not ranks, so comparisons run on this rank lane while values/output stay
    # ids (executor wires expr_compile.rank_lane here)
    order_arg: Optional[Compiled] = None


def minmax_order_arg(func: AggFunc, arg: Optional[Compiled],
                     comp) -> Optional[Compiled]:
    """Rank lane for MIN/MAX over an unsorted high-cardinality string
    dictionary (see AggSpec.order_arg); None when ids already order correctly."""
    if func not in (AggFunc.MIN, AggFunc.MAX) or arg is None or \
            arg.out_dict is None or arg.out_dict.is_sorted:
        return None
    from igloo_tpu.exec.expr_compile import rank_lane
    return rank_lane(arg, comp)


#: the direct-scatter aggregate's "small segment space" bound: at or under
#: this many segments seg_dims_for scatters unconditionally; above it the
#: scatter path needs few aggregates and a segment space near the batch's
DIRECT_SEG_SMALL_LIMIT = 1 << 16
_DENSE_INT_SEG_LIMIT = 1 << 23


def seg_dims_for(groups: list[Compiled],
                 n_aggs: Optional[int] = None,
                 input_capacity: Optional[int] = None) -> Optional[tuple]:
    """If every group key is directly indexable — a dictionary-encoded string
    (ids in [0, len)), a boolean, or (round 5) an integer-family column with
    host-known dense bounds — return per-key (bucket count, offset) pairs
    (+1 bucket for NULL). The aggregate then scatters straight into
    `prod(dims)` segments instead of lex-sorting every input lane (the sort
    is O(n log n) over the FULL batch capacity; Q1 groups 8M lanes into 6
    buckets, and q18's sum-per-orderkey groups 8M lanes into 6M dense-int
    segments — 1 scatter instead of a multi-lane sort).

    Large dense-int segment spaces (> 2^16) are only worth one scatter per
    aggregate, so they require `n_aggs` (callers that cannot bound the
    scatter count — the sharded partial path — omit it and keep the small
    limit). Host-side decision: callers must fold the result into their jit
    cache key."""
    dims = []
    for g in groups:
        if g.dtype is T.BOOL:
            dims.append((3, 0))
        elif g.dtype.is_string and g.out_dict is not None:
            dims.append((len(g.out_dict.values) + 1, 0))
        elif (g.dtype.is_integer or g.dtype.is_temporal) and \
                g.out_bounds is not None:
            lo, hi = g.out_bounds
            dims.append((int(hi) - int(lo) + 2, int(lo)))
        else:
            return None
    prod = 1
    for d, _off in dims:
        prod *= d
    if not dims or prod <= 0:
        return None
    if prod > DIRECT_SEG_SMALL_LIMIT:
        # the big-segment branch trades one ~1s scatter per aggregate value
        # for the multi-lane sort: only worth it when the scatter count is
        # small (AVG = sum+count = 2 scatters) AND the segment space does not
        # dwarf the batch (bounds are GLOBAL scan stats — a filtered 64K-lane
        # batch grouping by a 6M-wide key must keep the sort path, not
        # allocate 8M-segment outputs).
        if n_aggs is None or n_aggs > 2 or prod > _DENSE_INT_SEG_LIMIT:
            return None
        if input_capacity is None or prod > 2 * input_capacity:
            return None
    tracing.counter("agg.direct_scatter")
    return tuple(dims)


def pair_sums_for(seg_dims: Optional[tuple], aggs: list[AggSpec]) -> bool:
    """Whether `_direct_aggregate` folds its float64 SUM/AVG lanes as f32
    pairs (kernels.pair_add): where the one-pass arm reduces them
    (SMALL_NSEG segments or fewer) and the device's float64 IS that pair —
    the verdict of the codec's one-time canary, asked here, while a program
    is planned, and only by a plan that has such a lane. Host-side decision:
    callers fold the result into their jit cache key, as `seg_dims`."""
    if seg_dims is None or _segment_space(seg_dims)[1] > K.SMALL_NSEG or \
            not any(a.func in (AggFunc.SUM, AggFunc.AVG) and
                    _acc_dtype(a) is jnp.float64 for a in aggs):
        return False
    from igloo_tpu.exec import codec
    return codec._f32pair_ok()


def _segment_space(seg_dims: tuple) -> tuple:
    """(key combinations, output capacity) of a direct aggregate: the
    capacity holds them and the slot dead rows land in, padded."""
    from igloo_tpu.exec.batch import round_capacity
    prod = 1
    for d, _off in seg_dims:
        prod *= d
    return prod, round_capacity(prod + 1)


def aggregate_batch(batch: DeviceBatch, groups: list[Compiled],
                    aggs: list[AggSpec], out_schema: T.Schema,
                    consts: tuple = (),
                    seg_dims: Optional[tuple] = None,
                    pack_spec: Optional[tuple] = None,
                    pair_sums: bool = False):
    # seg_dims entries are (bucket_count, value_offset) pairs — see
    # seg_dims_for
    """Pure, jit-traceable: DeviceBatch -> DeviceBatch of one row per group.
    Output columns carry no dictionaries — the executor re-attaches them.
    `seg_dims` (from seg_dims_for, included in the caller's cache key) selects
    the direct-scatter fast path; output capacity is then the padded segment
    count, not the input capacity. `pack_spec` (kernels.plan_group_packing,
    also part of the caller's cache key) is (spec, packed key indices): the
    indexed keys fuse into ONE int lane, collapsing their share of the
    multi-lane lex_argsort chain to a single sort pass — when every key packs
    (all-integer group-bys) the whole chain becomes one argsort, and a
    q18-shaped 5-key group-by with one float key sorts 3 lanes instead of
    10+. `pair_sums` (pair_sums_for, part of the caller's cache key too):
    the direct path's float64 sums fold as f32 pairs."""
    env = Env.from_batch(batch, consts)
    cap = batch.capacity
    live = batch.live

    # evaluate group keys once
    gvals: list[jax.Array] = []
    gnulls: list[Optional[jax.Array]] = []
    for g in groups:
        v, nl = g.fn(env)
        gvals.append(v)
        gnulls.append(nl)

    if not groups:
        return _global_aggregate(env, aggs, out_schema, live)

    if seg_dims is not None and len(seg_dims) == len(groups):
        return _direct_aggregate(env, groups, gvals, gnulls, aggs, out_schema,
                                 live, seg_dims, pair_sums)

    # sort path. With a pack_spec, the indexed keys fuse into ONE packed lane
    # (NULL is a digit, so no separate null lanes for them). Grouping never
    # cares about lane SIGNIFICANCE order — only equal-key adjacency — so any
    # unpacked keys' null/NaN flags AND the live bit fold into the packed
    # lane's spare high bits when they fit: a q18-shaped group-by (4 packable
    # keys + 1 float) then sorts TWO lanes (float value, folded packed)
    # instead of the 11-pass lex chain; an all-packed group-by sorts ONE.
    packed = None
    packed_idx: tuple = ()
    rest: list = []
    if pack_spec is not None:
        spec, packed_idx = pack_spec
        packed = K.pack_key_lane(spec, [gvals[i] for i in packed_idx],
                                 [gnulls[i] for i in packed_idx], consts)
        rest = [i for i in range(len(groups)) if i not in packed_idx]
        pack_bits = sum(card.bit_length() - 1 for card, _, _ in spec[2])
        n_flags = 1 + sum((1 if groups[i].dtype.is_float else 0) +
                          (1 if gnulls[i] is not None else 0) for i in rest)
    if packed is not None and not rest:
        # every key packed: one argsort (dead rows via the packed sentinel)
        perm = jnp.argsort(K.packed_sort_key(packed, live), stable=True)
        s_lanes, s_nulls = [jnp.take(packed, perm)], [None]
    elif packed is not None and pack_bits + n_flags <= 63:
        # folded mixed path: value lanes (null-masked; floats NaN-normalized)
        # sort first, the folded lane [dead | flags | packed digits] sorts
        # last — its dead bit replaces lex_argsort's trailing live pass
        lane = packed.astype(jnp.int64)
        shift = pack_bits
        value_lanes: list = []
        for i in rest:
            v, nl, g = gvals[i], gnulls[i], groups[i]
            if nl is not None:
                # mask BEFORE deriving the NaN flag: this branch compares raw
                # lanes with no null awareness (s_nulls is all-None), so
                # under-null storage — which may be NaN on one row and finite
                # on another — must collapse to one canonical value or the
                # NULL group would split
                v = jnp.where(nl, jnp.zeros((), v.dtype), v)
            if g.dtype.is_float:
                vnorm, nan = K.normalize_float(v)
                lane = lane + (nan.astype(jnp.int64) << shift)
                shift += 1
                v = vnorm
            if nl is not None:
                lane = lane + (nl.astype(jnp.int64) << shift)
                shift += 1
            value_lanes.append(v)
        lane = lane + ((~live).astype(jnp.int64) << shift)
        shift += 1
        if shift <= 31:
            lane = lane.astype(jnp.int32)
        perm = jnp.arange(cap, dtype=jnp.int32)
        for v in reversed(value_lanes):
            perm = jnp.take(perm,
                            jnp.argsort(jnp.take(v, perm), stable=True))
        perm = jnp.take(perm,
                        jnp.argsort(jnp.take(lane, perm), stable=True))
        s_lanes = [jnp.take(lane, perm)] + \
            [jnp.take(v, perm) for v in value_lanes]
        s_nulls = [None] * len(s_lanes)
    else:
        # lex chain over the unpacked keys — equality lanes (string ids are
        # already ranks; floats decompose into nan-flag + normalized-value
        # lanes, no 64-bit bitcasts, TPU-safe) — led by the packed lane when
        # one exists (subset pack whose fold flags overflowed the spare bits)
        flat_lanes: list = [packed] if packed is not None else []
        flat_nulls: list = [None] if packed is not None else []
        sort_lanes: list = [(packed, True)] if packed is not None else []
        for i, (v, nl, g) in enumerate(zip(gvals, gnulls, groups)):
            if i in packed_idx:
                continue
            for eq in K.group_lanes_for(v, g.dtype.is_float):
                flat_lanes.append(eq)
                flat_nulls.append(nl)
            sort_lanes.extend(K.sort_lanes_for(v, nl, g.dtype.is_float,
                                               True, False))
        perm = K.lex_argsort(sort_lanes, live)
        s_lanes = [jnp.take(l, perm) for l in flat_lanes]
        s_nulls = [jnp.take(nl, perm) if nl is not None else None
                   for nl in flat_nulls]
    s_live = jnp.take(live, perm)
    seg, start = K.group_segments(s_lanes, s_nulls, s_live)
    num_groups = jnp.sum(start.astype(jnp.int32))

    # sorted segments are CONTIGUOUS runs, so segment boundaries come from the
    # start flags (no scatter): row k of the output is segment k, whose first
    # sorted position is the k-th True in `start` — compact_perm lists those
    # positions ascending. bounds = (start_idx, end_idx) per output row.
    start_idx = K.compact_perm(start)  # [cap] int32; rows >= num_groups garbage
    nxt = jnp.concatenate([start_idx[1:], jnp.full((1,), cap, jnp.int32)])
    k_idx = jnp.arange(cap, dtype=jnp.int32)
    end_idx = jnp.where(k_idx + 1 < num_groups, nxt, jnp.int32(cap)) - 1
    end_idx = jnp.clip(end_idx, 0, cap - 1)
    bounds = (start_idx, end_idx)
    first_pos = start_idx

    out_cols: list[DeviceColumn] = []
    # group key output columns
    for v, nl, g in zip(gvals, gnulls, groups):
        sv = jnp.take(jnp.take(v, perm), first_pos)
        snl = jnp.take(jnp.take(nl, perm), first_pos) if nl is not None else None
        # out_dict here is trace-time metadata: correct for eager (direct) use;
        # under the executor's jit cache it may be stale on a cache hit, so the
        # executor re-attaches current dictionaries after every call
        out_cols.append(DeviceColumn(g.dtype, sv.astype(g.dtype.device_dtype())
                                     if sv.dtype != g.dtype.device_dtype() else sv,
                                     snl, g.out_dict))

    # aggregates via segment reductions over sorted order
    for spec in aggs:
        out_cols.append(_reduce_one(spec, env, perm, seg, s_live, cap,
                                    bounds))

    out_live = jnp.arange(cap, dtype=jnp.int32) < num_groups
    return DeviceBatch(out_schema, out_cols, out_live)


def _run_sum(vals: jax.Array, bounds) -> jax.Array:
    """Per-segment sum over CONTIGUOUS (sorted) segments as cumsum boundary
    differences — gathers only, no scatter (a TPU scatter over a full lane
    costs ~300ms; this is one bandwidth-bound pass + two gathers).

    INTEGER lanes only: int cumsum differences are exact (wraparound cancels),
    while a float cumsum would (a) let one group's inf/NaN poison every LATER
    group (inf - inf = NaN at the boundary difference) and (b) round each
    group at the magnitude of the global running sum instead of its own.
    Float sums keep the isolated segment reduction."""
    start_idx, end_idx = bounds
    cs = jnp.cumsum(vals)
    before = jnp.where(start_idx > 0,
                       jnp.take(cs, jnp.clip(start_idx - 1, 0, None)),
                       jnp.zeros((), cs.dtype))
    return jnp.take(cs, end_idx) - before


def _acc_dtype(spec: AggSpec):
    """SUM/AVG accumulate in float64, or in int64 for an integer SUM."""
    return jnp.float64 if (spec.out_dtype.is_float or
                           spec.func is AggFunc.AVG) else jnp.int64


def _order_lane(spec: AggSpec, env: Env, v: jax.Array, perm=None):
    """MIN/MAX compare on this lane, not on the value: (lane, lo, hi) with
    lo/hi its identities. Floats are NaN-normalized (NaN orders as +inf),
    everything else widens to int64; an unsorted string dictionary compares
    by its rank lane (AggSpec.order_arg). `perm` permutes the rank lane as
    the caller permuted `v`."""
    src = v
    if spec.order_arg is not None:
        ov, _ = spec.order_arg.fn(env)
        src = ov if perm is None else jnp.take(ov, perm)
    if spec.arg.dtype.is_float:
        vnorm, nan = K.normalize_float(src)
        lane = jnp.where(nan, jnp.asarray(jnp.inf, vnorm.dtype), vnorm)
        return lane, jnp.asarray(-jnp.inf, lane.dtype), \
            jnp.asarray(jnp.inf, lane.dtype)
    return src.astype(jnp.int64), jnp.iinfo(jnp.int64).min, \
        jnp.iinfo(jnp.int64).max


def _reduce_one(spec: AggSpec, env: Env, perm, seg, s_live, cap,
                bounds) -> DeviceColumn:
    """The sort path's segment reduction for one aggregate. `perm` sorts rows
    into segment order; output arrays have length `cap`. `bounds` = per-output
    -row (start, end) sorted positions of the contiguous segments: INTEGER
    sums (counts, int SUM) run scatter-free via cumsum differences (see
    _run_sum for why floats don't), everything else through K.seg_sum /
    seg_min / seg_max (scatters; at a capacity of SMALL_NSEG or less the
    one-pass masked reduce over every segment id)."""
    def ssum(vals):
        if jnp.issubdtype(vals.dtype, jnp.integer):
            return _run_sum(vals, bounds)
        return K.seg_sum(vals, seg, cap)

    if spec.func is AggFunc.COUNT_STAR:
        cnt = ssum(s_live.astype(jnp.int64))
        return DeviceColumn(T.INT64, cnt, None, None)

    v, nl = spec.arg.fn(env)
    sv = jnp.take(v, perm)
    snl = jnp.take(nl, perm) if nl is not None else None
    valid = s_live if snl is None else (s_live & ~snl)
    n_valid = ssum(valid.astype(jnp.int64))
    all_null = n_valid == 0

    if spec.func is AggFunc.COUNT:
        return DeviceColumn(T.INT64, n_valid, None, None)

    if spec.func is AggFunc.SUM or spec.func is AggFunc.AVG:
        acc_dtype = _acc_dtype(spec)
        sval = jnp.where(valid, sv.astype(acc_dtype), jnp.zeros((), acc_dtype))
        return _sum_column(spec, ssum(sval), n_valid, all_null)

    # MIN / MAX: sentinel-masked segment reduce on a comparable lane, then an
    # exact gather of the original value at a winning position (so e.g. a NaN
    # winner comes back as NaN, not as its +inf ordering surrogate)
    pos = jnp.arange(cap, dtype=jnp.int32)
    lane, lo, hi = _order_lane(spec, env, sv, perm)
    if spec.func is AggFunc.MIN:
        keyed = jnp.where(valid, lane, hi)
        best_lane = K.seg_min(keyed, seg, cap)
    else:
        keyed = jnp.where(valid, lane, lo)
        best_lane = K.seg_max(keyed, seg, cap)
    # recover a row index holding the winning lane value for exact value gather
    is_best = valid & (keyed == jnp.take(best_lane, seg))
    best_pos = K.seg_min(jnp.where(is_best, pos, jnp.int32(cap)), seg, cap)
    best_pos = jnp.clip(best_pos, 0, cap - 1)
    out_val = jnp.take(sv, best_pos)
    return DeviceColumn(spec.out_dtype, out_val, all_null, spec.out_dict)


def _resident_halves(spec: AggSpec, env: Env):
    """The f32 halves of a SUM/AVG argument that is a bare column whose
    resident form holds them (batch.f32_halves), else None."""
    e = spec.arg.expr
    if not isinstance(e, Column) or env.columns is None:
        return None
    return f32_halves(env.columns[e.index])


def _sum_column(spec: AggSpec, total, n_valid, all_null) -> DeviceColumn:
    """SUM / AVG output from a group's total and its count of non-NULLs."""
    if spec.func is AggFunc.AVG:
        denom = jnp.where(all_null, 1, n_valid).astype(jnp.float64)
        return DeviceColumn(T.FLOAT64, total / denom, all_null, None)
    return DeviceColumn(spec.out_dtype,
                        total.astype(spec.out_dtype.device_dtype()),
                        all_null, None)


def _global_aggregate(env: Env, aggs: list[AggSpec], out_schema: T.Schema,
                      live: jax.Array) -> DeviceBatch:
    """No GROUP BY: plain masked reductions — no segment scatter (the old path
    scattered into `capacity` segments to produce ONE row, allocating and
    reducing an input-sized output per aggregate; warm SF1 Q6 spent ~2.7s
    there). Emits exactly one row even over empty input (SQL: COUNT=0,
    SUM=NULL); output capacity MIN_CAPACITY."""
    from igloo_tpu.exec.batch import MIN_CAPACITY

    def one_row(scalar, dtype, is_null=None):
        lane = jnp.zeros((MIN_CAPACITY,), dtype=dtype).at[0].set(
            scalar.astype(dtype))
        nl = None
        if is_null is not None:
            nl = jnp.zeros((MIN_CAPACITY,), dtype=bool).at[0].set(is_null)
        return lane, nl

    out_cols: list[DeviceColumn] = []
    for spec in aggs:
        if spec.func is AggFunc.COUNT_STAR:
            lane, _ = one_row(jnp.sum(live.astype(jnp.int64)), jnp.int64)
            out_cols.append(DeviceColumn(T.INT64, lane, None, None))
            continue
        v, nl = spec.arg.fn(env)
        valid = live if nl is None else (live & ~nl)
        n_valid = jnp.sum(valid.astype(jnp.int64))
        all_null = n_valid == 0
        if spec.func is AggFunc.COUNT:
            lane, _ = one_row(n_valid, jnp.int64)
            out_cols.append(DeviceColumn(T.INT64, lane, None, None))
        elif spec.func in (AggFunc.SUM, AggFunc.AVG):
            acc_dtype = _acc_dtype(spec)
            total = jnp.sum(jnp.where(valid, v.astype(acc_dtype),
                                      jnp.zeros((), acc_dtype)))
            if spec.func is AggFunc.AVG:
                denom = jnp.where(all_null, 1, n_valid).astype(jnp.float64)
                lane, nlo = one_row(total / denom, jnp.float64, all_null)
                out_cols.append(DeviceColumn(T.FLOAT64, lane, nlo, None))
            else:
                lane, nlo = one_row(total, spec.out_dtype.device_dtype(),
                                    all_null)
                out_cols.append(DeviceColumn(spec.out_dtype, lane, nlo, None))
        else:  # MIN / MAX with exact winning-row gather (NaN stays NaN)
            lane_v, lo, hi = _order_lane(spec, env, v)
            keyed = jnp.where(valid, lane_v,
                              hi if spec.func is AggFunc.MIN else lo)
            best = jnp.argmin(keyed) if spec.func is AggFunc.MIN \
                else jnp.argmax(keyed)
            lane, nlo = one_row(jnp.take(v, best),
                                spec.out_dtype.device_dtype(), all_null)
            out_cols.append(DeviceColumn(spec.out_dtype, lane, nlo,
                                         spec.out_dict))
    out_live = jnp.zeros((MIN_CAPACITY,), dtype=bool).at[0].set(True)
    return DeviceBatch(out_schema, out_cols, out_live)


def uncompacted_filter(plan: L.Aggregate) -> Optional[L.Filter]:
    """The Filter whose live rows `plan` reads best where they lie, or None.

    An adopted cardinality hint compacts a node's output so that what
    consumes it runs at the hinted width. Whether that pays is the
    CONSUMER's to say: an aggregate with no group expressions is
    `_global_aggregate`, one masked pass over its input, and a compaction in
    front of it (a lane-wide sort, then a gather per column) costs several
    such passes and saves none. So it asks its input not to compact; the
    request passes through Project nodes (row-wise, width-preserving) to the
    first Filter under them. Under a join, a distinct or a sub-aggregate the
    answer is None: what they compact is theirs to decide. THE one place
    this rule lives: the fused compiler (FusedCompiler._c_aggregate) and the
    staged one (Executor._exec_aggregate) both ask it, so the two programs of
    one plan cannot disagree."""
    if plan.group_exprs or any(a.distinct for a in plan.aggs):
        return None
    node = plan.input
    while isinstance(node, L.Project):
        node = node.input
    return node if isinstance(node, L.Filter) else None


def stage_scope(plan: L.Aggregate):
    """The named scope of a DISTINCT aggregate's stage (plan/aggsplit.py
    split_distinct): `distinct_stage1` / `distinct_stage2`, so that a dump
    of the program names the stage's ops; no scope for any other
    aggregate."""
    if plan.distinct_stage:
        return jax.named_scope(f"distinct_stage{plan.distinct_stage}")
    return contextlib.nullcontext()


def groups_in_place(seg_dims: Optional[tuple]) -> bool:
    """Whether a direct-scatter aggregate over `seg_dims` (seg_dims_for's
    verdict) leaves its groups where their segment ids put them, `live` its
    group mask, instead of compacting them to the front: where its segment
    space is wider than K.SMALL_NSEG, the width at which seg_reduce
    scatters. There the compaction is a sort and a gather per column over
    the whole space (TPC-H q13 at SF10 on a v5e: one sort and two gathers at
    2^22 lanes, 67 ms of a 2.09 s query), and no consumer needs it: every
    operator reads `live`, as it does over a Filter's lanes, and the
    compaction was stable, so the live groups keep their order (segment-id
    order). At or under the width it moves at most 64 lanes, and the
    program keeps its form (TPC-H q1). A function of `seg_dims`, which every
    caller's program key holds already; the executors ask it once per
    aggregate of a plan walk for the counter `agg.groups_in_place`."""
    return seg_dims is not None and \
        _segment_space(seg_dims)[1] > K.SMALL_NSEG


def agg_out_bounds(aggs: list, input_capacity: int) -> list:
    """Host-known (lo, hi) value bounds of an aggregate's output columns, one
    per aggregate (`plan.expr.Aggregate`s): a COUNT or COUNT(*) of a
    non-DISTINCT aggregate counts at most every lane of its input, so
    (0, input capacity); every other output None. A GROUP BY or ORDER BY
    over such a count then chooses as it does for any bounded integer key
    (seg_dims_for, kernels.plan_group_packing / plan_prefix_packing): TPC-H
    q13's count of customers per order count sorts one packed lane. THE one
    place this rule lives: the fused compiler (FusedCompiler._c_aggregate)
    and the staged one (Executor._aggregate) both read it."""
    return [(0, int(input_capacity))
            if a.func in (AggFunc.COUNT, AggFunc.COUNT_STAR) and not a.distinct
            else None for a in aggs]


def _feasible_segments(seg_dims: tuple, gnulls: list) -> list:
    """The segment ids of _direct_aggregate that can hold a live row: every
    digit combination, less digit 0 (the NULL bucket) of a key that reached
    the aggregate without a null lane — a trace-time fact. Q1: two dictionary
    keys without nulls, (4 - 1) x (3 - 1) = 6 ids of a padded 16."""
    ids = [0]
    for (d, _off), nl in zip(seg_dims, gnulls):
        digits = range(d) if nl is not None else range(1, d)
        ids = [s * d + c for s in ids for c in digits]
    return ids


def _direct_aggregate(env: Env, groups: list[Compiled], gvals, gnulls,
                      aggs: list[AggSpec], out_schema: T.Schema,
                      live: jax.Array,
                      seg_dims: tuple,  # ((count, offset), ...)
                      pair_sums: bool = False) -> DeviceBatch:
    """Direct grouping for small indexable keys (see seg_dims_for): segment
    id = mixed-radix combination of (NULL?0:key+1) digits. Skips the
    full-capacity lex sort; output capacity = padded segment count (small).

    What the aggregates reduce is collected first and handed to K.seg_reduce
    together, each distinct lane once: the live count, one count per distinct
    null lane, one sum lane per distinct argument (by E.fingerprint of the
    expression it was compiled from) and accumulator dtype, one best-value
    lane per MIN/MAX; MIN/MAX take a second such round for the winning
    position. At SMALL_NSEG segments or fewer that is one pass over the
    lanes, into the feasible segments only; above, one scatter per lane.

    With `pair_sums` (pair_sums_for) a float64 sum lane is folded as its two
    f32 halves: those of the column itself where the argument is a bare
    column resident in a form that holds them (batch.f32_halves: no decode,
    no split), else split in-trace from the computed float64 value.

    Above SMALL_NSEG segments the groups stay where their segment ids put
    them, live where a row reached them (groups_in_place); at or under it
    they are compacted to the front."""
    cap = live.shape[0]
    prod, nseg = _segment_space(seg_dims)
    dead = nseg - 1  # dead rows land here; >= prod, never a real key combo
    seg = jnp.zeros((cap,), dtype=jnp.int32)
    for v, nl, (d, off) in zip(gvals, gnulls, seg_dims):
        comp = (v - off).astype(jnp.int32) + 1 if off else \
            v.astype(jnp.int32) + 1
        if nl is not None:
            comp = jnp.where(nl, 0, comp)
        seg = seg * jnp.int32(d) + comp
    seg = jnp.where(live, seg, jnp.int32(dead))
    # listed only for seg_reduce's one-pass arm: above the threshold a scatter
    # fills every slot, and a dense integer domain has millions of ids
    seg_ids = _feasible_segments(seg_dims, gnulls) \
        if nseg <= K.SMALL_NSEG else None

    # round 1: counts, sums, each MIN/MAX's best lane value. A count never
    # passes the batch's capacity, an int32: counted so, widened after.
    lanes = {"live": (live.astype(jnp.int32), "sum")}  # key -> (lane, op)
    args: dict = {}  # argument key -> (values, its non-NULL live rows,
    #                  the key of their count)
    null_lanes = []  # (a null lane, the key of its count): x and x * 2 carry
    #                  ONE lane object, found again with `is`
    plans = []       # per spec: (argument key, lane key); None: COUNT(*)
    for i, spec in enumerate(aggs):
        if spec.func is AggFunc.COUNT_STAR:
            plans.append(None)
            continue
        # a Compiled built by hand says nothing of what it computes, and one
        # that binds a literal computes it from a value no key holds: each
        # by its index. AVG(x) beside SUM(x) is what shares.
        akey = fingerprint(spec.arg.expr) if spec.arg.expr is not None \
            and not spec.arg.literals else i
        if akey not in args:
            v, nl = spec.arg.fn(env)
            if nl is None:
                args[akey] = (v, live, "live")
            else:
                ckey = next((k for n, k in null_lanes if n is nl), None)
                if ckey is None:
                    ckey = ("n", akey)
                    null_lanes.append((nl, ckey))
                    lanes[ckey] = ((live & ~nl).astype(jnp.int32), "sum")
                args[akey] = (v, live & ~nl, ckey)
        v, valid, _ = args[akey]
        lkey = None
        if spec.func in (AggFunc.SUM, AggFunc.AVG):
            acc = _acc_dtype(spec)
            lkey = ("sum", akey, np.dtype(acc).name)
            if lkey not in lanes:
                halves = _resident_halves(spec, env) \
                    if pair_sums and acc is jnp.float64 else None
                if halves is not None:
                    zero = jnp.zeros((), jnp.float32)
                    lanes[lkey] = (tuple(jnp.where(valid, h, zero)
                                         for h in halves), "sum")
                else:
                    lanes[lkey] = (jnp.where(valid, v.astype(acc),
                                             jnp.zeros((), acc)), "sum")
        elif spec.func in (AggFunc.MIN, AggFunc.MAX):
            op = "min" if spec.func is AggFunc.MIN else "max"
            lkey = (op, akey)
            if lkey not in lanes:
                lane, lo, hi = _order_lane(spec, env, v)
                lanes[lkey] = (jnp.where(valid, lane,
                                         hi if op == "min" else lo), op)
        plans.append((akey, lkey))
    found = dict(zip(lanes, K.seg_reduce(list(lanes.values()), seg, nseg,
                                         seg_ids, pair_sums)))

    # round 2: a row index holding each MIN/MAX's winning lane value, for an
    # exact gather of the original value (a NaN winner comes back as NaN,
    # not as its +inf ordering surrogate)
    pos = jnp.arange(cap, dtype=jnp.int32)
    pos_lanes = {}  # a MIN/MAX's lane key -> (its candidate positions, "min")
    for spec, plan in zip(aggs, plans):
        if spec.func in (AggFunc.MIN, AggFunc.MAX) and \
                plan[1] not in pos_lanes:
            akey, lkey = plan
            is_best = args[akey][1] & \
                (lanes[lkey][0] == jnp.take(found[lkey], seg))
            pos_lanes[lkey] = (jnp.where(is_best, pos, jnp.int32(cap)), "min")
    best_pos = dict(zip(pos_lanes, K.seg_reduce(list(pos_lanes.values()), seg,
                                                nseg, seg_ids)))
    if seg_ids is not None:
        tracing.counter("agg.onepass_segments", len(seg_ids))
        tracing.counter("agg.onepass_lanes", len(lanes) + len(pos_lanes))
        if pair_sums:
            tracing.counter("agg.pair_sum_lanes", sum(
                1 for v, op in lanes.values() if op == "sum" and
                (isinstance(v, tuple) or v.dtype == jnp.float64)))

    counts = found["live"]
    group_mask = (counts > 0) & (jnp.arange(nseg) < prod)

    # group VALUES decode from the segment index (every seg_dims kind is a
    # bijection of its digit): no first-occurrence seg_min scatter — at
    # dense-int scale (6M segments over 8M lanes) each scatter is ~1 s on TPU
    out_cols: list[DeviceColumn] = []
    segid = jnp.arange(nseg, dtype=jnp.int64)
    digits = []
    rest = segid
    for d, _off in reversed(seg_dims):
        digits.append(rest % d)
        rest = rest // d
    digits.reverse()
    for digit, (d, off), g, nl in zip(digits, seg_dims, groups, gnulls):
        raw = jnp.clip(digit - 1, 0, d - 2) + off
        out_cols.append(DeviceColumn(
            g.dtype, raw.astype(g.dtype.device_dtype()),
            (digit == 0) if nl is not None else None,
            g.out_dict))
    for spec, plan in zip(aggs, plans):
        if plan is None:
            out_cols.append(DeviceColumn(
                T.INT64, counts.astype(jnp.int64), None, None))
            continue
        akey, lkey = plan
        v, _valid, ckey = args[akey]
        n_valid = found[ckey].astype(jnp.int64)
        all_null = n_valid == 0
        if spec.func is AggFunc.COUNT:
            out_cols.append(DeviceColumn(T.INT64, n_valid, None, None))
        elif spec.func in (AggFunc.MIN, AggFunc.MAX):
            at = jnp.clip(best_pos[lkey], 0, cap - 1)
            out_cols.append(DeviceColumn(
                spec.out_dtype, jnp.take(v, at), all_null, spec.out_dict))
        else:
            out_cols.append(_sum_column(spec, found[lkey], n_valid, all_null))

    if groups_in_place(seg_dims):
        return DeviceBatch(out_schema, out_cols, group_mask)
    # compact live groups to the front (segment-id order = NULL-first
    # dictionary-rank order); aggregate output row order is not semantic
    perm_small = K.compact_perm(group_mask)
    n_groups = jnp.sum(group_mask.astype(jnp.int32))
    out_cols = [DeviceColumn(c.dtype, jnp.take(c.values, perm_small),
                             jnp.take(c.nulls, perm_small)
                             if c.nulls is not None else None, c.dictionary)
                for c in out_cols]
    out_live = jnp.arange(nseg, dtype=jnp.int32) < n_groups
    return DeviceBatch(out_schema, out_cols, out_live)


def distinct_batch(batch: DeviceBatch) -> DeviceBatch:
    """SELECT DISTINCT: group by every column, no aggregates."""
    groups = []
    for i, (f, c) in enumerate(zip(batch.schema, batch.columns)):
        comp = Compiled(lambda env, _i=i: (env.values[_i], env.nulls[_i]),
                        f.dtype, c.dictionary)
        groups.append(comp)
    return aggregate_batch(batch, groups, [], batch.schema)
