"""Sort / Limit / Offset kernels.

The reference delegates ORDER BY/LIMIT to DataFusion entirely (no custom operator).
TPU design: multi-key sort = k iterated stable argsorts over order-normalized int64
lanes (kernels.lex_argsort) — no comparators, fully static shapes. When a prefix of
the keys is integer-family with host-known bounds, it packs into ONE lane
(kernels.plan_prefix_packing; see docs/sort_keys.md), collapsing the chain — a
fully packed ORDER BY is a single argsort that also handles dead-row placement.
LIMIT is a mask over the running live-row count, not a truncation, so shapes
stay put.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from igloo_tpu.exec import kernels as K
from igloo_tpu.exec.batch import DeviceBatch
from igloo_tpu.exec.expr_compile import Compiled, Env
from igloo_tpu.utils import tracing


def sort_batch(batch: DeviceBatch, keys: list[Compiled], ascending: list[bool],
               nulls_first: list[bool], consts: tuple = (),
               pack: Optional[tuple] = None) -> DeviceBatch:
    """Jit-traceable stable sort; dead rows end up last. `pack` (a host
    decision from kernels.plan_prefix_packing, part of the caller's cache key)
    is (spec, n) with the first n keys fused into one packed lane."""
    env = Env.from_batch(batch, consts)
    vals, nls = [], []
    for k in keys:
        v, nl = k.fn(env)
        vals.append(v)
        nls.append(nl)
    lanes = []
    start = 0
    if pack is not None:
        spec, start = pack
        packed = K.pack_key_lane(spec, vals[:start], nls[:start], consts)
        if start == len(keys):
            # every key packed: one argsort orders rows AND sinks dead rows
            perm = jnp.argsort(K.packed_sort_key(packed, batch.live),
                               stable=True)
            return K.apply_perm(batch, perm)
        lanes.append((packed, True))
    for k, v, nl, asc, nf in zip(keys[start:], vals[start:], nls[start:],
                                 ascending[start:], nulls_first[start:]):
        lanes.extend(K.sort_lanes_for(v, nl, k.dtype.is_float, asc, nf))
    perm = K.lex_argsort(lanes, batch.live)
    return K.apply_perm(batch, perm)


def plan_topk(cap: int, k: int, pack: Optional[tuple], n_keys: int) -> bool:
    """Host decision (the callers fold it into their cache keys): does a
    LIMIT over ORDER BY take `topk_batch` in place of the full sort? `k` is
    LIMIT + OFFSET. The prefix packing must cover EVERY sort key — one lane
    then totally orders the rows, where a partial pack still needs the
    lexicographic tiebreak sort — and the LIMIT must leave at least half the
    batch out, or a partial top-k buys nothing over the direct sort."""
    if k <= 0 or pack is None or pack[1] != n_keys or 2 * k > cap:
        return False
    tracing.counter("topk.alg")
    return True


def topk_batch(batch: DeviceBatch, keys: list[Compiled],
               consts: tuple, pack: tuple,
               limit: int, offset: int, out_cap: int) -> DeviceBatch:
    """Jit-traceable fused ORDER BY + LIMIT where `plan_topk` says so: a
    `lax.top_k` over the fully packed sort lane replaces the full argsort.
    Its ties are lowest-index-first, so the selected positions are the
    stable sort's first LIMIT+OFFSET, and the output batch shrinks to
    `out_cap` (the LIMIT's capacity family member) instead of carrying the
    input capacity with a mask. Rows are bit-identical to ``sort_batch`` +
    ``limit_batch``."""
    env = Env.from_batch(batch, consts)
    vals, nls = [], []
    for k in keys:
        v, nl = k.fn(env)
        vals.append(v)
        nls.append(nl)
    spec, _ = pack
    packed = K.pack_key_lane(spec, vals, nls, consts)
    k_total = limit + offset
    perm = jax.lax.top_k(-K.packed_sort_key(packed, batch.live),
                         k_total)[1].astype(jnp.int32)
    if out_cap > k_total:
        perm = jnp.concatenate(
            [perm, jnp.zeros((out_cap - k_total,), perm.dtype)])
    cols = K.gather_batch(batch, perm)
    io = jnp.arange(out_cap)
    live = jnp.take(batch.live, perm) & (io >= offset) & (io < k_total)
    return DeviceBatch(batch.schema, cols, live)


def limit_batch(batch: DeviceBatch, limit, offset: int = 0) -> DeviceBatch:
    """Jit-traceable: keep live rows (offset, offset+limit] in current row order."""
    cum = jnp.cumsum(batch.live.astype(jnp.int64))
    keep = batch.live & (cum > offset)
    if limit is not None:
        keep = keep & (cum <= offset + limit)
    return DeviceBatch(batch.schema, batch.columns, keep)
