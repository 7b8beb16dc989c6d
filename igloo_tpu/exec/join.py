"""Join kernel: two-phase sorted-probe equi-join with exact verification.

Replaces the reference's HashJoinExec (crates/engine/src/operators/hash_join.rs),
whose build side is a row-at-a-time HashMap keyed by debug-formatted strings
(:116-127) and whose probe emits 1-row batches (:165-211), with right/full outer
unmatched rows never emitted (gap G4). The TPU design:

  phase P (device): normalize keys to int64 lanes, combine to a mixed 64-bit hash,
      stable-sort the build side by hash, binary-search each probe row's hash range
      -> per-row candidate counts, total count (one scalar)
  host: one sync for the total -> choose padded output capacity (power-of-two
      bucketing keeps the compile cache small)
  phase E (device): expand candidates (prefix-sum + searchsorted inversion),
      gather both sides, verify EXACT key equality (hash collisions only waste
      padded slots, never emit wrong rows), apply the residual predicate, derive
      matched flags, and null-pad unmatched preserved-side rows for outer joins.

All join types: inner/left/right/full/cross/semi/anti (+ null-aware anti for
NOT IN). Strings join via per-entry dictionary hash lanes (128-bit effective with
the verify lane), so differently-dictionary-encoded tables join exactly.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from igloo_tpu import types as T
from igloo_tpu.exec import kernels as K
from igloo_tpu.exec.batch import (
    DeviceBatch, DeviceColumn, round_capacity, wide_values,
)
from igloo_tpu.exec.expr_compile import Compiled, Env
from igloo_tpu.sql.ast import JoinType
from igloo_tpu.utils import tracing


@dataclass
class _KeyLanes:
    """One join key, normalized: int64 lanes feeding the hash, equality lanes
    compared exactly during verification, and the null flag."""
    hash_ints: list
    eq_lanes: list
    null: object  # Optional[jax.Array]


@dataclass
class _Probe:
    """Device results of the probe phase (phase P)."""
    perm_r: jax.Array      # build-side sort permutation
    lower: jax.Array       # [cap_l] first candidate position per probe row
    counts: jax.Array      # [cap_l] candidate count per probe row
    prefix: jax.Array      # [cap_l] exclusive prefix sum of counts
    total: jax.Array       # scalar int64
    l_lanes: list          # per-key _KeyLanes on left
    r_lanes: list          # per-key _KeyLanes on right


# pytree registration so _Probe/_KeyLanes cross jit boundaries (probe runs in one
# jitted phase, expand in another; the probe result is a pytree of arrays)
jax.tree_util.register_pytree_node(
    _KeyLanes,
    lambda k: ((k.hash_ints, k.eq_lanes, k.null), None),
    lambda aux, ch: _KeyLanes(ch[0], ch[1], ch[2]),
)
jax.tree_util.register_pytree_node(
    _Probe,
    lambda p: ((p.perm_r, p.lower, p.counts, p.prefix, p.total,
                p.l_lanes, p.r_lanes), None),
    lambda aux, ch: _Probe(*ch),
)


def make_key_hash_idxs(keys: list[Compiled], pool) -> list:
    """Register per-dictionary-entry hash lanes in the const pool for every
    string-typed key. The hashes feed the jitted probe as runtime data, so a
    new dictionary (new table contents) never forces a join recompile."""
    idxs = []
    for k in keys:
        if k.dtype.is_string:
            d = k.out_dict
            h1 = d.hashes.view(np.int64) if d is not None and len(d) \
                else np.zeros(1, np.int64)
            h2 = d.hashes2.view(np.int64) if d is not None and len(d) \
                else np.zeros(1, np.int64)
            idxs.append((pool.add(h1), pool.add(h2)))
        else:
            idxs.append(None)
    return idxs


def _key_lanes(batch: DeviceBatch, keys: list[Compiled], hash_idxs: list,
               consts: tuple) -> list[_KeyLanes]:
    env = Env.from_batch(batch, consts)
    out = []
    for k, hx in zip(keys, hash_idxs):
        v, nl = k.fn(env)
        if k.dtype.is_string:
            # dictionary hash lanes: equal strings -> equal lanes across tables;
            # 128-bit effective equality with the second lane
            h1, h2 = consts[hx[0]], consts[hx[1]]
            ids = jnp.clip(v, 0, h1.shape[0] - 1)
            l1, l2 = jnp.take(h1, ids), jnp.take(h2, ids)
            out.append(_KeyLanes([l1], [l1, l2], nl))
        elif k.dtype.is_float:
            vnorm, nan = K.normalize_float(v)
            out.append(_KeyLanes(K.float_hash_int_lanes(v),
                                 [vnorm, nan.astype(jnp.int32)], nl))
        else:
            lane = v.astype(jnp.int64)
            out.append(_KeyLanes([lane], [lane], nl))
    return out


def probe_phase(left: DeviceBatch, right: DeviceBatch,
                left_keys: list[Compiled], right_keys: list[Compiled],
                l_hash_idxs=None, r_hash_idxs=None,
                consts: tuple = ()) -> _Probe:
    """Jit-traceable. CROSS join = empty key lists (constant key)."""
    cap_l, cap_r = left.capacity, right.capacity
    if l_hash_idxs is None:
        l_hash_idxs = [None] * len(left_keys)
    if r_hash_idxs is None:
        r_hash_idxs = [None] * len(right_keys)
    if left_keys:
        l_lanes = _key_lanes(left, left_keys, l_hash_idxs, consts)
        r_lanes = _key_lanes(right, right_keys, r_hash_idxs, consts)
        l_hash = K.hash_lanes([h for kl in l_lanes for h in kl.hash_ints],
                              [kl.null for kl in l_lanes
                               for _ in kl.hash_ints])
        r_hash = K.hash_lanes([h for kl in r_lanes for h in kl.hash_ints],
                              [kl.null for kl in r_lanes
                               for _ in kl.hash_ints])
        l_keynull = _any_null(l_lanes, cap_l)
        r_keynull = _any_null(r_lanes, cap_r)
        # NULL keys never equal anything: displace to side-distinct sentinels
        l_hash = jnp.where(l_keynull, np.int64(-0x0123456789ABCDEF), l_hash)
        r_hash = jnp.where(r_keynull, np.int64(0x0FEDCBA987654321), r_hash)
    else:
        l_lanes, r_lanes = [], []
        l_hash = jnp.zeros((cap_l,), dtype=jnp.int64)
        r_hash = jnp.zeros((cap_r,), dtype=jnp.int64)

    # dead build rows displaced to the max sentinel (sorted last); any accidental
    # live MAX-hash rows are rejected by exact verification
    sort_key = jnp.where(right.live, r_hash, jnp.iinfo(jnp.int64).max)
    perm_r = jnp.argsort(sort_key, stable=True)

    lower, upper = _probe_bounds(sort_key, l_hash)
    counts = jnp.where(left.live, (upper - lower).astype(jnp.int64), 0)
    prefix = jnp.cumsum(counts) - counts
    total = jnp.sum(counts)
    return _Probe(perm_r, lower, counts.astype(jnp.int32),
                  prefix.astype(jnp.int64), total, l_lanes, r_lanes)


def _probe_bounds(build_key: jax.Array, probe_key: jax.Array):
    """Per-probe-element lower/upper insertion positions in the sorted build
    multiset, with ONE combined sort, no searchsorted: on TPU a searchsorted
    over an 8M-query lane lowers to a ~23-pass gather loop (~1.5s), and the
    previous design paid one full (m+n)-lane stable sort PER bound (probe-first
    tie-break for lower, build-first for upper). This version packs the side
    tag into the key's low bit — hash bit 0 is dropped to make room (a 63-bit
    hash; collisions only add verify-rejected candidates, never wrong rows) —
    so a single stable sort orders every equal-key run probes-first:

      lower(probe at sorted pos i) = builds strictly before i
                                   = builds before the run (they all follow
                                     the run's probes)
      upper(probe at sorted pos i) = builds up to the END of its equal-key run
                                     (run end via one reverse min-scan)

    Both bounds then scatter back to the probe's original index. Net: one
    argsort + one cumsum + one scan instead of two argsorts + two cumsums."""
    m = build_key.shape[0]
    n = probe_key.shape[0]
    total = m + n
    pos = jnp.arange(total, dtype=jnp.int32)
    mask = np.int64(-2)  # ~1: drop the hash's low bit for the side tag
    keys = jnp.concatenate([probe_key & mask, (build_key & mask) | np.int64(1)])
    perm = jnp.argsort(keys, stable=True)
    sk = jnp.take(keys, perm)
    is_build = jnp.take(pos >= n, perm)
    # builds at-or-before each sorted position; probes carry "builds before"
    cb = jnp.cumsum(is_build.astype(jnp.int32))
    lower = cb - is_build.astype(jnp.int32)
    # end of each equal-key run (tag bit ignored): reverse running min over
    # run-final positions
    krun = sk | np.int64(1)
    last = jnp.concatenate([krun[1:] != krun[:-1],
                            jnp.ones((1,), dtype=bool)])
    end_idx = jax.lax.associative_scan(
        jnp.minimum, jnp.where(last, pos, jnp.int32(total)), reverse=True)
    upper = jnp.take(cb, end_idx)
    # scatter both bounds back to each probe element's original index. Build
    # elements route to the POSITIVE out-of-bounds sentinel `m + n`: negative
    # indices would WRAP (jnp normalizes them before mode="drop" applies) and
    # clobber probe slots
    orig = jnp.take(pos, perm)
    target = jnp.where(is_build, jnp.int32(total), orig)
    lo_out = jnp.zeros((n,), dtype=jnp.int32).at[target].set(
        lower, mode="drop")
    up_out = jnp.zeros((n,), dtype=jnp.int32).at[target].set(
        upper, mode="drop")
    return lo_out, up_out


def _any_null(lanes: list[_KeyLanes], cap) -> jax.Array:
    out = jnp.zeros((cap,), dtype=bool)
    for kl in lanes:
        if kl.null is not None:
            out = out | kl.null
    return out


def semi_anti_phase(left: DeviceBatch, right: DeviceBatch,
                    left_keys: list, right_keys: list,
                    lhx: list, rhx: list, anti: bool,
                    residual: Optional[Compiled] = None,
                    window: int = 2, consts: tuple = (),
                    pack_eq: Optional[tuple] = None):
    """SEMI/ANTI without candidate expansion: membership is a sorted search
    over the build side's combined key hash with EXACT verify-lane equality
    at a `window`-slot run. The expand program (scatter-max ownership +
    associative scan + full-width gathers) hangs XLA's server-side compiler
    at multi-million-lane match capacities (observed: 25+ min on TPC-H q18's
    semi at SF1); this shape is a sort + searchsorted + a handful of gathers,
    and SEMI/ANTI only ever need a per-left-row boolean anyway.

    Without a residual the window only covers hash collisions (2 slots).
    With one (EXISTS ... AND extra-condition, e.g. q21), every candidate in
    the key's duplicate run must be tested: the window widens and a
    `truncated` flag reports any left row whose run may extend past it —
    the caller re-runs exactly (deferred overflow protocol).

    `pack_eq` (kernels.plan_pair_packing, part of the caller's cache key)
    fuses the per-key exact-verify lanes into ONE packed lane per side, so
    each window slot pays one gather+compare instead of one per key.

    Returns (DeviceBatch, truncated flag)."""
    l_lanes = _key_lanes(left, left_keys, lhx, consts)
    r_lanes = _key_lanes(right, right_keys, rhx, consts)

    def combined(lanes, live):
        flat, nulls = [], []
        valid = live
        for kl in lanes:
            for ln in kl.hash_ints:
                flat.append(ln.astype(jnp.int64))
                nulls.append(kl.null)
            if kl.null is not None:
                valid = valid & ~kl.null  # null keys never equi-match
        return K.hash_lanes(flat, nulls), valid

    lh, lvalid = combined(l_lanes, left.live)
    rh, rvalid = combined(r_lanes, right.live)
    big = jnp.int64(0x7FFFFFFFFFFFFFFF)
    rmasked = jnp.where(rvalid, rh, big)
    order = jnp.argsort(rmasked)
    rsorted = jnp.take(rmasked, order)
    rv_sorted = jnp.take(rvalid, order)
    if pack_eq is not None:
        # integer-family keys only (planner-guaranteed): each key's eq_lanes
        # is its single value lane, and the union-range digits make equal
        # values share a digit across the two tables — the window loop below
        # then pays ONE gather+compare per slot instead of one per key. NULL
        # digits collide at 0, but null keys are already excluded from
        # lvalid/rvalid.
        l_eq = [K.pack_key_lane(pack_eq, [kl.eq_lanes[0] for kl in l_lanes],
                                [kl.null for kl in l_lanes], consts)]
        r_packed = K.pack_key_lane(pack_eq,
                                   [kl.eq_lanes[0] for kl in r_lanes],
                                   [kl.null for kl in r_lanes], consts)
        r_eq = [jnp.take(r_packed, order)]
    else:
        r_eq = [jnp.take(ln.astype(jnp.int64), order)
                for kl in r_lanes for ln in kl.eq_lanes]
        l_eq = [ln.astype(jnp.int64) for kl in l_lanes for ln in kl.eq_lanes]
    lo = jnp.searchsorted(rsorted, lh)
    cap_r = right.capacity
    member = jnp.zeros(left.capacity, dtype=bool)
    truncated = jnp.asarray(False)
    last_keyeq = None
    for off in range(window):
        j = jnp.clip(lo + off, 0, cap_r - 1)
        keyeq = jnp.take(rv_sorted, j)
        for le, re_ in zip(l_eq, r_eq):
            keyeq = keyeq & (le == jnp.take(re_, j))
        ok = keyeq
        if residual is not None:
            ridx = jnp.take(order, j)
            # residual reads VALUES: widen resident carriers in-trace (fused)
            r_vals = [jnp.take(wide_values(c), ridx) for c in right.columns]
            r_nulls = [jnp.take(c.nulls, ridx) if c.nulls is not None
                       else None for c in right.columns]
            env = Env([wide_values(c) for c in left.columns] + r_vals,
                      [c.nulls for c in left.columns] + r_nulls, consts)
            rv, rn = residual.fn(env)
            ok = ok & rv
            if rn is not None:
                ok = ok & ~rn
        member = member | ok
        last_keyeq = keyeq
    if residual is not None and last_keyeq is not None:
        # a key-equal candidate at the FINAL slot means the duplicate run may
        # continue beyond the window for that row: unverified candidates
        # could flip membership — flag for an exact re-run
        # rows NOT yet matched whose run may continue: more candidates could
        # flip them to matched (changing SEMI keeps and ANTI drops alike)
        truncated = jnp.any(last_keyeq & lvalid & left.live & ~member)
    member = member & lvalid
    keep = left.live & (~member if anti else member)
    return DeviceBatch(left.schema, left.columns, keep), truncated


def match_by_search() -> bool:
    """Host-side choice, made by the two compilers when they plan a sorted
    probe join, of how `expand_phase` finds each slot's probe row: a
    searchsorted inversion of the prefix lane everywhere but on a TPU, where
    the scatter + cummax scan stands in (see there). A constant of the
    process's backend, so it rides no cache key."""
    if jax.default_backend() == "tpu":
        return False
    tracing.counter("join.match_search")
    return True


def expand_phase(left: DeviceBatch, right: DeviceBatch, p: _Probe,
                 match_cap: int, join_type: JoinType,
                 residual: Optional[Compiled],
                 out_schema: T.Schema, consts: tuple = (),
                 match_search: bool = False) -> DeviceBatch:
    """Jit-traceable (match_cap static). Builds the output batch.
    `match_search` (see `match_by_search`) picks the searchsorted inversion
    over the default scatter + cummax scan for slot ownership."""
    cap_l = left.capacity

    # --- candidate expansion: slot j -> (probe row, j-th candidate) ---
    j = jnp.arange(match_cap, dtype=jnp.int64)
    if match_search:
        # the prefix lane is sorted (cumsum), so the owner of slot j is the
        # LAST row whose start is <= j — zero-count rows share their
        # successor's start and lose the right-insertion tie to the true
        # owner; stragglers die on the offset bound below
        probe_idx = jnp.clip(
            jnp.searchsorted(p.prefix, j, side="right").astype(jnp.int32) - 1,
            0, cap_l - 1)
    else:
        # probe row owning each slot: scatter each row's index at its start
        # slot, then a running max fills its run. (a searchsorted over the
        # 8M-lane prefix costs ~1.5s on TPU — a 23-pass gather loop — vs
        # ~0.3s for scatter+cummax; zero-count rows share their successor's
        # start slot and lose the scatter-max tie to the true owner, which
        # has the larger index)
        starts = jnp.clip(p.prefix, 0, match_cap - 1).astype(jnp.int32)
        row_ids = jnp.arange(cap_l, dtype=jnp.int32)
        owner = jnp.zeros((match_cap,), dtype=jnp.int32).at[starts].max(
            jnp.where(p.counts > 0, row_ids, 0), mode="drop")
        probe_idx = jax.lax.associative_scan(jnp.maximum, owner)
        probe_idx = jnp.clip(probe_idx, 0, cap_l - 1)
    in_range = j < p.total
    offset = (j - jnp.take(p.prefix, probe_idx)).astype(jnp.int32)
    # rows with count 0 can be hit when prefix repeats; reject by offset bound
    cnt = jnp.take(p.counts, probe_idx)
    in_range = in_range & (offset >= 0) & (offset < cnt)
    r_pos = jnp.take(p.lower, probe_idx) + offset
    r_idx = jnp.take(p.perm_r, jnp.clip(r_pos, 0, right.capacity - 1))

    # --- exact verification (hash collisions die here, never in the output) ---
    ok = in_range & jnp.take(left.live, probe_idx) & jnp.take(right.live, r_idx)
    for lk, rk in zip(p.l_lanes, p.r_lanes):
        for llane, rlane in zip(lk.eq_lanes, rk.eq_lanes):
            ok = ok & (jnp.take(llane, probe_idx) == jnp.take(rlane, r_idx))
        if lk.null is not None:
            ok = ok & ~jnp.take(lk.null, probe_idx)
        if rk.null is not None:
            ok = ok & ~jnp.take(rk.null, r_idx)

    # --- gather both sides once (the residual env and the output columns
    # share the same indices; SEMI/ANTI never read these and XLA prunes the
    # dead gathers from their traces) ---
    l_cols = K.gather_batch(left, probe_idx)
    r_cols = K.gather_batch(right, r_idx)

    # --- residual predicate over combined row ---
    if residual is not None:
        env = Env([wide_values(c) for c in l_cols + r_cols],
                  [c.nulls for c in l_cols] + [c.nulls for c in r_cols], consts)
        rv, rn = residual.fn(env)
        ok = ok & rv & (~rn if rn is not None else True)

    # --- matched flags, computed only for the join types that read them (a
    # TPU scatter over a full lane costs ~300ms; INNER needs neither flag) ---
    l_matched = r_matched = None
    if join_type in (JoinType.LEFT, JoinType.FULL, JoinType.SEMI,
                     JoinType.ANTI):
        # probe_idx is NONDECREASING (slots for one probe row are contiguous
        # by construction), so "row i has a verified match" is a cumsum range
        # query — gathers only, no scatter:
        #   matched[i] = cumsum(ok)[prefix[i] + counts[i] - 1] - cumsum(ok)[prefix[i] - 1] > 0
        c = jnp.cumsum(ok.astype(jnp.int64))
        hi = p.prefix + p.counts.astype(jnp.int64)  # exclusive end slot
        hi_idx = jnp.clip(hi - 1, 0, match_cap - 1).astype(jnp.int32)
        lo = p.prefix
        c_before = jnp.where(lo > 0,
                             jnp.take(c, jnp.clip(lo - 1, 0,
                                                  match_cap - 1).astype(jnp.int32)),
                             jnp.int64(0))
        in_cap = hi <= match_cap  # overflowed rows handled by the re-run
        l_matched = in_cap & (p.counts > 0) & \
            ((jnp.take(c, hi_idx) - c_before) > 0)
    if join_type in (JoinType.RIGHT, JoinType.FULL):
        # build side order is arbitrary -> keep the scatter (rare join types)
        ok32 = ok.astype(jnp.int32)
        r_matched = jnp.zeros((right.capacity,), dtype=jnp.int32) \
            .at[r_idx].max(ok32, mode="drop") > 0

    if join_type is JoinType.SEMI:
        return DeviceBatch(out_schema, left.columns, left.live & l_matched)
    if join_type is JoinType.ANTI:
        # NOT IN null semantics live in the binder-built residual (binder.py
        # _rewrite_in_subquery), not here — plain anti is correct as-is
        return DeviceBatch(out_schema, left.columns, left.live & ~l_matched)

    # --- inner part: verified expanded rows, NOT compacted (live rows stay
    # mask-scattered across the match_cap slots; every downstream operator is
    # selection-mask aware, and the compaction here was a full match_cap-wide
    # argsort per join, ~1s at SF1) ---
    parts_cols = [l_cols + r_cols]
    parts_live = [ok]

    if join_type in (JoinType.LEFT, JoinType.FULL):
        lm = left.live & ~l_matched
        lperm = K.compact_perm(lm)
        lu_live = jnp.take(lm, lperm)
        lu_cols = K.gather_batch(left, lperm)
        pad_r = _null_cols(right, left.capacity)
        parts_cols.append(lu_cols + pad_r)
        parts_live.append(lu_live)
    if join_type in (JoinType.RIGHT, JoinType.FULL):
        rm = right.live & ~r_matched
        rperm = K.compact_perm(rm)
        ru_live = jnp.take(rm, rperm)
        ru_cols = K.gather_batch(right, rperm)
        pad_l = _null_cols(left, right.capacity)
        parts_cols.append(pad_l + ru_cols)
        parts_live.append(ru_live)

    # concatenate parts (static shapes: match_cap + cap_l? + cap_r?)
    out_cols = K.concat_columns(parts_cols)
    out_live = jnp.concatenate(parts_live)
    if len(parts_live) > 1:
        # outer joins: compact the concatenated parts into contiguous rows.
        # Inner joins skip this — their single part stays MASK-SCATTERED (see
        # above; anything that later needs compaction, e.g. resize_batch,
        # must compact first) and the argsort here costs a ~2M-lane sort
        return K.apply_perm(DeviceBatch(out_schema, out_cols, out_live),
                            K.compact_perm(out_live))
    return DeviceBatch(out_schema, out_cols, out_live)


def _null_cols(batch: DeviceBatch, cap: int) -> list[DeviceColumn]:
    # zeros in the CARRIER dtype (concat parts must agree; wide for an f32
    # pair, as every part that moved rows is); an offset carrier widens pad
    # zeros to its offset, but every pad lane is null here — masked at
    # output, bit-identical
    return [c.map_rows(lambda a: jnp.zeros((cap,), dtype=a.dtype))
            .with_nulls(jnp.ones((cap,), dtype=bool)) for c in batch.columns]


def choose_match_capacity(total: int) -> int:
    return round_capacity(max(int(total), 1))


# ---------------------------------------------------------------------------
# Direct "array join": the fast path for dense-integer-key PK-FK joins (all of
# TPC-H). When one side's single join key is an integer whose host-known value
# bounds (DeviceColumn.bounds, computed at scan time) span a small dense range,
# that side becomes the BUILD side of a positional table: one scatter writes
# build row ids at slot (key - lo), and each probe row finds its unique match
# with one gather — no hashing, no sorting. This replaces the sorted-probe
# path's 2-3 large stable sorts (~1s at SF1 Q3) with one scatter + one gather
# (~20ms). Correctness does NOT depend on the uniqueness guess: a slot-count
# check sets a deferred flag when build keys collide, and the executor re-runs
# the plan through the exact sorted-probe path (same mechanism as speculative
# capacity overflow). Key equality is exact BY CONSTRUCTION (slot index = key),
# so there is no verify phase at all.
# ---------------------------------------------------------------------------

# what a positional table costs per slot: one int32 build-row id (direct_probe
# allocates the table and nothing else of its size: the duplicate check is a
# sum over `table >= 0`, one reduction that keeps no array; the occupancy bits
# direct_bitmap_probe reads are 1/32 of it)
DIRECT_SLOT_BYTES = 4
# where the backend reports no memory limit (XLA:CPU): the fixed widest table
# the engine had before the limit was derived (64 MiB), so no CPU pick changes
UNLIMITED_DIRECT_SLOTS = 1 << 24


def direct_table_slots() -> int:
    """The most slots a positional table may have on this process's devices:
    its share of the chip's memory (exec/cache.py direct_table_budget) over
    DIRECT_SLOT_BYTES. The fused compiler and the staged executor both pick
    under it (choose_direct_build)."""
    from igloo_tpu.exec.cache import direct_table_budget
    budget = direct_table_budget()
    if budget is None:
        return UNLIMITED_DIRECT_SLOTS
    return budget // DIRECT_SLOT_BYTES


def _direct_key_ok(c: Compiled) -> bool:
    return c.dtype.is_integer or c.dtype.id == T.TypeId.DATE32


def choose_direct_build(lks: list, rks: list, left_cap: int,
                        right_cap: int, join_type: JoinType,
                        banned: frozenset = frozenset()):
    """Pick the build side + key for a direct join, or None when inapplicable.
    Returns (side, (base, table_size), key_idx) with side in {"left",
    "right"}; (base, table_size) is the CANONICAL positional table
    (exec/capacity.canonical_direct_table) — size quantized to the capacity
    family and base grid-aligned, so the raw key bounds never become program
    constants and neighboring scale factors share one compiled join. A
    (side, key) qualifies when its table fits `direct_table_slots()`
    and the side's row capacity could plausibly be unique over that range
    (cap <= its canonical table size: any padded batch whose live rows fit
    the range fits the table, whatever the family's padding ratio or
    hysteresis — a looser-than-exact test whose wrong picks the runtime
    duplicate flag repairs and negative-caches); among qualifiers the
    smaller side wins (PK side in every FK join). Remaining key
    pairs become post-gather equality checks, so every key must be
    integer-family. The runtime duplicate check backstops a wrong pick;
    `banned` carries sides that PROVED duplicated on earlier runs (the
    ("nodirect", jfp_core, side) negative cache), so the other side still
    gets its chance. Called once per join of a plan walk, so its counters
    are per query: `join.direct_routes` and `join.direct_table_bytes` for a
    pick, `join.direct_over_budget` for a join declined only for the size
    of its table."""
    from igloo_tpu.exec.capacity import canonical_direct_table
    if join_type is JoinType.CROSS or not lks:
        return None
    if not all(_direct_key_ok(c) for c in lks + rks):
        return None
    limit = direct_table_slots()
    options = []
    over = False
    for side, keys, cap in (("right", rks, right_cap), ("left", lks, left_cap)):
        if side in banned:
            continue
        for i, key in enumerate(keys):
            b = key.out_bounds
            if b is None:
                continue
            rng = int(b[1]) - int(b[0]) + 1
            if rng > limit:             # its table has at least rng slots
                over = True
                continue
            base, tsize = canonical_direct_table(int(b[0]), int(b[1]))
            if cap > tsize:
                continue
            if tsize > limit:
                over = True
                continue
            options.append((cap, rng, side, (base, tsize), i))
    if not options:
        tracing.counter("join.direct_ineligible")
        if over:
            tracing.counter("join.direct_over_budget")
        return None
    options.sort(key=lambda o: (o[0], o[1], o[2], o[4]))
    _, _, side, table, idx = options[0]
    tracing.counter("join.direct_routes")
    tracing.counter("join.direct_table_bytes", table[1] * DIRECT_SLOT_BYTES)
    return side, table, idx


def direct_probe(probe: DeviceBatch, build: DeviceBatch,
                 probe_key: Compiled, build_key: Compiled,
                 lo: int, table_size: int, swapped: bool,
                 residual: Optional[Compiled], consts: tuple,
                 extra_keys: Sequence = ()):
    """Probe half of the direct array join, jit-traceable: build the
    positional table (one scatter), probe it (one gather), verify extra key
    pairs and the residual. Returns (ok, safe_bidx, dup) WITHOUT
    materializing any output columns — callers gather lazily (the fused
    compiler compacts first; XLA prunes residual gathers of unread columns).
    `dup` is a device bool: True iff two valid build rows shared a slot
    (result must be discarded and the plan re-run on the exact path)."""
    bcap = build.capacity
    table, dup = _direct_table(build, build_key, lo, table_size, consts)
    p_ok, pslot = _probe_slots(probe, probe_key, lo, table_size, consts)
    bidx = jnp.take(table, pslot)
    ok = p_ok & (bidx >= 0)
    safe_bidx = jnp.clip(bidx, 0, bcap - 1)
    ok = verify_extra_keys(ok, probe, build, safe_bidx, extra_keys, consts)
    if residual is not None:
        b_cols = K.gather_batch(build, safe_bidx)
        p_cols = list(probe.columns)
        l_cols, r_cols = (b_cols, p_cols) if swapped else (p_cols, b_cols)
        env = Env([wide_values(c) for c in l_cols + r_cols],
                  [c.nulls for c in l_cols] + [c.nulls for c in r_cols],
                  consts)
        rv, rn = residual.fn(env)
        ok = ok & rv & (~rn if rn is not None else True)
    return ok, safe_bidx, dup


def _direct_table(build: DeviceBatch, build_key: Compiled, lo: int,
                  table_size: int, consts: tuple):
    """The positional table (one scatter of build row ids at key - lo; -1
    where no row lands) and the duplicate flag."""
    bkey, bnull = build_key.fn(Env.from_batch(build, consts))
    valid_b = build.live if bnull is None else (build.live & ~bnull)
    slot = bkey.astype(jnp.int64) - lo
    in_rng = (slot >= 0) & (slot < table_size)
    valid_b = valid_b & in_rng
    # invalid rows displace to the out-of-bounds slot -> dropped by the scatter
    slot = jnp.where(valid_b, slot, table_size).astype(jnp.int32)
    row_ids = jnp.arange(build.capacity, dtype=jnp.int32)
    table = jnp.full((table_size,), -1, jnp.int32).at[slot].max(
        row_ids, mode="drop")
    # duplicate build keys: two rows target one slot -> fewer filled slots
    # than valid rows. One O(table_size) reduction, no second scatter.
    dup = jnp.sum((table >= 0).astype(jnp.int64)) < \
        jnp.sum(valid_b.astype(jnp.int64))
    return table, dup


def _probe_slots(probe: DeviceBatch, probe_key: Compiled, lo: int,
                 table_size: int, consts: tuple):
    """(p_ok, slot): whether a probe row's key can match (live, not null,
    inside the table) and its slot, clipped into the table, as int32."""
    pkey, pnull = probe_key.fn(Env.from_batch(probe, consts))
    pslot = pkey.astype(jnp.int64) - lo
    p_ok = (pslot >= 0) & (pslot < table_size) & probe.live
    if pnull is not None:
        p_ok = p_ok & ~pnull
    return p_ok, jnp.clip(pslot, 0, table_size - 1).astype(jnp.int32)


def occupancy_words(table_size: int) -> int:
    """Words of a positional table's occupancy bits: 32 slots a word,
    rounded up to a power of two so that a slot's word and bit are a mask
    and a shift."""
    return 1 << max((table_size - 1).bit_length() - 5, 0)


def occupancy_bits(table: jax.Array) -> jax.Array:
    """Pack `table >= 0` into u32 words: slot s is bit s // W of word
    s % W (W = occupancy_words). Packing then ORs 32 contiguous slices,
    one pass over the table in one fusion; a word of 32 CONSECUTIVE slots
    makes the TPU compiler relayout the table to 32-wide rows padded to
    128 lanes (four times its size in temporaries)."""
    words = occupancy_words(table.shape[0])
    if table.shape[0] < 32 * words:
        table = jnp.pad(table, (0, 32 * words - table.shape[0]),
                        constant_values=-1)
    bits = jnp.zeros((words,), jnp.uint32)
    for b in range(32):
        occ = table[b * words:(b + 1) * words] >= 0
        bits = bits | (occ.astype(jnp.uint32) << b)
    return bits


def direct_bitmap_probe(probe: DeviceBatch, build: DeviceBatch,
                        probe_key: Compiled, build_key: Compiled,
                        lo: int, table_size: int, consts: tuple):
    """`direct_probe` for a single-key join without a residual, for callers
    that need the row ids only at a narrower width: the match mask is read
    from the table's occupancy bits, not from the table. The bits of a
    2^27-slot table are 16 MiB, which the TPU compiler keeps in the core's
    own memory, where the table (537 MB) is read from HBM: 8.7 ns a probe
    row against 25.7 on a v5e (PERF.md §6, step 0). Returns (ok, table, slot,
    dup): `ok` is direct_probe's; a row's build row id is
    `table[slot]`, read after the caller has narrowed `slot`."""
    table, dup = _direct_table(build, build_key, lo, table_size, consts)
    p_ok, pslot = _probe_slots(probe, probe_key, lo, table_size, consts)
    words = occupancy_words(table_size)
    shift = words.bit_length() - 1
    word = jnp.take(occupancy_bits(table), pslot & (words - 1))
    hit = (word >> (pslot >> shift).astype(jnp.uint32)) & 1
    return p_ok & (hit != 0), table, pslot, dup


def direct_join_phase(probe: DeviceBatch, build: DeviceBatch,
                      probe_key: Compiled, build_key: Compiled,
                      lo: int, table_size: int, swapped: bool,
                      join_type: JoinType, residual: Optional[Compiled],
                      out_schema: T.Schema, consts: tuple = (),
                      extra_keys: Sequence = ()):
    """Jit-traceable single-pass direct join. `swapped` means the plan's LEFT
    input is the build side (probe = plan right). `extra_keys` are further
    (probe key, build key) equi-pairs of a multi-key join, verified by exact
    equality after the gather (the positional table handles one key; a
    duplicate under that key alone still raises `dup`, so multi-key uniqueness
    is never assumed). Returns (DeviceBatch, dup)."""
    jt = join_type
    bcap, pcap = build.capacity, probe.capacity
    ok, safe_bidx, dup = direct_probe(probe, build, probe_key, build_key,
                                      lo, table_size, swapped, residual,
                                      consts, extra_keys)
    b_cols = K.gather_batch(build, safe_bidx)
    p_cols = [replace(c, bounds=None) for c in probe.columns]
    l_cols, r_cols = (b_cols, p_cols) if swapped else (p_cols, b_cols)

    # which original side is preserved / reduced to a mask
    probe_is_left = not swapped
    if jt in (JoinType.SEMI, JoinType.ANTI):
        if probe_is_left:
            keep = probe.live & ok if jt is JoinType.SEMI else probe.live & ~ok
            return DeviceBatch(out_schema, probe.columns, keep), dup
        matched = _build_matched(ok, safe_bidx, bcap)
        keep = build.live & matched if jt is JoinType.SEMI \
            else build.live & ~matched
        return DeviceBatch(out_schema, build.columns, keep), dup

    probe_preserved = (jt is JoinType.FULL
                       or (jt is JoinType.LEFT and probe_is_left)
                       or (jt is JoinType.RIGHT and not probe_is_left))
    build_preserved = (jt is JoinType.FULL
                       or (jt is JoinType.LEFT and not probe_is_left)
                       or (jt is JoinType.RIGHT and probe_is_left))

    if probe_preserved:
        # unmatched probe rows stay inline with a null-padded build side
        main_live = probe.live
        pad = ~ok
        b_cols = [replace(c, nulls=pad if c.nulls is None
                          else (c.nulls | pad)) for c in b_cols]
        l_cols, r_cols = (b_cols, p_cols) if swapped else (p_cols, b_cols)
    else:
        main_live = ok

    parts_cols = [l_cols + r_cols]
    parts_live = [main_live]
    if build_preserved:
        matched = _build_matched(ok, safe_bidx, bcap)
        um = build.live & ~matched
        uperm = K.compact_perm(um)
        u_live = jnp.take(um, uperm)
        u_cols = K.gather_batch(build, uperm)
        pad_cols = _null_cols(probe, bcap)
        parts_cols.append((u_cols + pad_cols) if swapped
                          else (pad_cols + u_cols))
        parts_live.append(u_live)

    if len(parts_cols) == 1:
        return DeviceBatch(out_schema, parts_cols[0], parts_live[0]), dup
    out_live = jnp.concatenate(parts_live)
    return DeviceBatch(out_schema, K.concat_columns(parts_cols), out_live), dup


def verify_extra_keys(ok: jax.Array, probe: DeviceBatch, build: DeviceBatch,
                      safe_bidx: jax.Array, extra_keys, consts) -> jax.Array:
    """Fold the remaining equi-key pairs of a multi-key direct join into the
    match mask: exact integer equality, SQL null semantics (NULL matches
    nothing)."""
    for pk_c, bk_c in extra_keys:
        pv, pn = pk_c.fn(Env.from_batch(probe, consts))
        bv, bn = bk_c.fn(Env.from_batch(build, consts))
        ok = ok & (pv.astype(jnp.int64) ==
                   jnp.take(bv, safe_bidx).astype(jnp.int64))
        if pn is not None:
            ok = ok & ~pn
        if bn is not None:
            ok = ok & ~jnp.take(bn, safe_bidx)
    return ok


def _build_matched(ok: jax.Array, safe_bidx: jax.Array, bcap: int) -> jax.Array:
    """Per-build-row matched flag: scatter-max of ok at each probe's match."""
    tgt = jnp.where(ok, safe_bidx, bcap)
    return jnp.zeros((bcap,), jnp.int32).at[tgt].max(
        ok.astype(jnp.int32), mode="drop") > 0


def join_batches(left: DeviceBatch, right: DeviceBatch,
                 left_keys: list[Compiled], right_keys: list[Compiled],
                 join_type: JoinType, residual: Optional[Compiled],
                 out_schema: T.Schema,
                 probe_jit: Optional[Callable] = None,
                 expand_jit: Optional[Callable] = None,
                 pool=None) -> DeviceBatch:
    """Host-side driver: probe (device) -> one host sync for the candidate count
    -> expand (device). `probe_jit`/`expand_jit` let the executor pass cached
    jax.jit-wrapped phases; defaults run them eagerly. `pool` must be the
    ConstPool the keys/residual were compiled against (a fresh one otherwise);
    key hash lanes are registered into it."""
    from igloo_tpu.exec.expr_compile import ConstPool
    if join_type is JoinType.CROSS:
        left_keys, right_keys = [], []
    if pool is None:
        pool = ConstPool()
    lhx = make_key_hash_idxs(left_keys, pool)
    rhx = make_key_hash_idxs(right_keys, pool)
    consts = pool.device_args()
    pf = probe_jit or (lambda l, r, c: probe_phase(
        l, r, left_keys, right_keys, lhx, rhx, c))
    ef = expand_jit or (lambda l, r, p, mc, c: expand_phase(
        l, r, p, mc, join_type, residual, out_schema, c))
    p = pf(left, right, consts)
    total = int(p.total)  # the one host sync
    match_cap = choose_match_capacity(total)
    return ef(left, right, p, match_cap, consts)
