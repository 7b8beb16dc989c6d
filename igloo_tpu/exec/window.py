"""Window-function kernel: one jit-traceable segmented-scan pass.

The reference executes window functions through DataFusion's engine
(crates/engine/src/lib.rs:54-57 — its custom operators have no window
support). TPU design, all static shapes:

    sort rows by (partition keys, order keys)  ->  contiguous partitions
    -> per-row positions + peer-group boundaries from lane comparisons
    -> ranks / running aggregates as cumsum differences and segmented scans
       (gathers only on the hot paths — no full-capacity scatters)
    -> inverse permutation restores the original row order

Semantics: with ORDER BY, aggregates use the SQL default frame (RANGE
UNBOUNDED PRECEDING .. CURRENT ROW): peers — rows tied on the order keys —
share the value at the END of their peer group. Without ORDER BY the frame is
the whole partition. MIN/MAX running variants use a segmented associative
scan; NULL arguments are skipped (do not contribute), and COUNT counts only
non-null arguments.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from igloo_tpu import types as T
from igloo_tpu.exec import kernels as K
from igloo_tpu.exec.batch import DeviceBatch, DeviceColumn
from igloo_tpu.exec.expr_compile import Compiled, Env
from igloo_tpu.plan import expr as E
from igloo_tpu.plan.expr import AggFunc


@dataclass(frozen=True)
class WinSpec:
    """One compiled window function over the node's shared OVER spec."""
    kind: str                      # row_number|rank|dense_rank|lag|lead|agg
    agg_func: Optional[AggFunc] = None
    arg: Optional[Compiled] = None       # agg argument / lag-lead value
    offset: int = 1                      # lag/lead
    out_dtype: T.DataType = T.INT64


def compile_window(plan, comp, resolve) -> tuple:
    """Shared host-side compile for a L.Window node (staged executor + fused
    compiler): returns (fingerprint_parts, part_keys, order_keys, specs,
    out_dicts, out_bounds) where out_dicts/bounds cover ONLY the appended
    window columns. `resolve` is the executor's scalar-subquery resolver."""
    from igloo_tpu.errors import NotSupportedError
    from igloo_tpu.exec.expr_compile import rank_lane
    pres = [resolve(e) for e in plan.partition_exprs]
    ores = [resolve(e) for e in plan.order_exprs]
    part_keys = [comp.compile(e) for e in pres]
    order_keys = [comp.compile(e) for e in ores]
    # ORDER over unsorted (high-cardinality) dictionaries sorts ranks
    order_keys = [rank_lane(k, comp) if k.dtype.is_string else k
                  for k in order_keys]
    specs: list[WinSpec] = []
    out_dicts: list = []
    out_bounds: list = []
    fps: list = []
    for w in plan.funcs:
        if w.func == "agg":
            a = w.agg
            arg = None
            if a.arg is not None:
                r = resolve(a.arg)
                arg = comp.compile(r)
                fps.append(E.shape(r))
                if arg.dtype.is_string:
                    raise NotSupportedError(
                        "string arguments to windowed aggregates are not "
                        "supported yet")
            specs.append(WinSpec("agg", a.func, arg, out_dtype=w.dtype))
            fps.append(("agg", a.func, w.dtype))
            out_dicts.append(None)
        elif w.func in ("lag", "lead"):
            r = resolve(w.args[0])
            arg = comp.compile(r)
            offset = int(w.args[1].value) if len(w.args) > 1 else 1
            specs.append(WinSpec(w.func, arg=arg, offset=offset,
                                 out_dtype=w.dtype))
            fps.append((w.func, E.shape(r), offset, w.dtype))
            out_dicts.append(arg.out_dict)
        else:
            specs.append(WinSpec(w.func, out_dtype=w.dtype))
            fps.append((w.func,))
            out_dicts.append(None)
        out_bounds.append(None)
    fp = (E.shape(pres), E.shape(ores),
          tuple(plan.ascending), tuple(plan.nulls_first), tuple(fps))
    return fp, part_keys, order_keys, specs, out_dicts, out_bounds


def _seg_scan(op, vals, start):
    """Segmented inclusive scan: restart `op` at every True in `start`."""
    def combine(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf, bv, op(av, bv))
    _, out = jax.lax.associative_scan(combine, (start, vals))
    return out


def window_batch(batch: DeviceBatch, part_keys: list[Compiled],
                 order_keys: list[Compiled], ascending: list[bool],
                 nulls_first: list[bool], specs: list[WinSpec],
                 out_schema: T.Schema, consts: tuple = ()) -> DeviceBatch:
    """Jit-traceable: input batch -> input columns + one column per spec.
    Output rows keep the ORIGINAL lane positions (and the original live
    mask); only the appended values are computed in window order."""
    env = Env.from_batch(batch, consts)
    cap = batch.capacity
    live = batch.live

    part_lanes: list = []
    part_nulls: list = []
    sort_lanes: list = []
    for k in part_keys:
        v, nl = k.fn(env)
        for lane in K.group_lanes_for(v, k.dtype.is_float):
            part_lanes.append(lane)
            part_nulls.append(nl)
        sort_lanes.extend(K.sort_lanes_for(v, nl, k.dtype.is_float, True,
                                           False))
    order_lanes: list = []
    order_nulls: list = []
    for k, a, nf in zip(order_keys, ascending, nulls_first):
        v, nl = k.fn(env)
        for lane in K.group_lanes_for(v, k.dtype.is_float):
            order_lanes.append(lane)
            order_nulls.append(nl)
        sort_lanes.extend(K.sort_lanes_for(v, nl, k.dtype.is_float, a, nf))

    perm = K.lex_argsort(sort_lanes, live)
    s_live = jnp.take(live, perm)
    pos = jnp.arange(cap, dtype=jnp.int32)

    def changed(lanes, nulls):
        """True where the sorted row differs from its predecessor on any
        lane (null-aware); row 0 always True."""
        flag = pos == 0
        for lane, nl in zip(lanes, nulls):
            sv = jnp.take(lane, perm)
            prev = jnp.concatenate([sv[:1], sv[:-1]])
            diff = sv != prev
            if nl is not None:
                sn = jnp.take(nl, perm)
                pn = jnp.concatenate([sn[:1], sn[:-1]])
                diff = diff | (sn != pn)
            flag = flag | diff
        return flag

    if part_lanes:
        seg_start = changed(part_lanes, part_nulls)
    else:
        seg_start = pos == 0
    # dead rows sort last; give each its own segment so nothing leaks
    seg_start = seg_start | ~s_live
    peer_start = seg_start | (changed(order_lanes, order_nulls)
                              if order_lanes else jnp.zeros((cap,), bool))

    seg_start_pos = jax.lax.associative_scan(
        jnp.maximum, jnp.where(seg_start, pos, 0))
    peer_start_pos = jax.lax.associative_scan(
        jnp.maximum, jnp.where(peer_start, pos, 0))
    peer_end_pos = _end_positions(peer_start, pos, cap)
    seg_end_pos = _end_positions(seg_start, pos, cap)

    out_cols = list(batch.columns)
    inv = jnp.zeros((cap,), jnp.int32).at[perm].set(pos)

    def unsort(vals, nulls=None):
        v = jnp.take(vals, inv)
        n = jnp.take(nulls, inv) if nulls is not None else None
        return v, n

    for spec, f in zip(specs, out_schema.fields[len(batch.columns):]):
        if spec.kind == "row_number":
            win = (pos - seg_start_pos + 1).astype(jnp.int64)
            v, n = unsort(win)
        elif spec.kind == "rank":
            win = (peer_start_pos - seg_start_pos + 1).astype(jnp.int64)
            v, n = unsort(win)
        elif spec.kind == "dense_rank":
            cnp = jnp.cumsum(peer_start.astype(jnp.int64))
            win = cnp - jnp.take(cnp, seg_start_pos) + 1
            v, n = unsort(win)
        elif spec.kind in ("lag", "lead"):
            av, an = spec.arg.fn(env)
            sv = jnp.take(av, perm)
            sn = jnp.take(an, perm) if an is not None else None
            off = spec.offset if spec.kind == "lag" else -spec.offset
            src = pos - off
            valid = (src >= seg_start_pos) & (src <= seg_end_pos) & s_live
            safe = jnp.clip(src, 0, cap - 1)
            win = jnp.take(sv, safe)
            wn = ~valid
            if sn is not None:
                wn = wn | jnp.take(sn, safe)
            v, n = unsort(win, wn)
        else:  # aggregate over the window
            v, n = _window_agg(spec, env, perm, s_live, seg_start_pos,
                               seg_end_pos, peer_end_pos,
                               bool(order_lanes), cap)
            v, n = unsort(v, n)
        want = f.dtype.device_dtype()
        if v.dtype != want:
            v = v.astype(want)
        out_cols.append(DeviceColumn(f.dtype, v, n, None))
    return DeviceBatch(out_schema, out_cols, live)


def _end_positions(start_flags, pos, cap):
    """Last position of each row's run, given run-start flags: the NEXT
    start position scanned from the right, minus one."""
    import jax as _jax
    nxt = jnp.concatenate([
        jnp.where(start_flags[1:], pos[1:], cap).astype(jnp.int32),
        jnp.full((1,), cap, jnp.int32)])
    return _jax.lax.associative_scan(jnp.minimum, nxt, reverse=True) - 1


def _window_agg(spec: WinSpec, env: Env, perm, s_live, seg_start_pos,
                seg_end_pos, peer_end_pos, has_order: bool, cap):
    """SUM/COUNT/AVG/MIN/MAX over the frame. With ORDER BY: running value at
    the row's peer-group END (RANGE default frame); else whole partition
    (= value at the segment's last row, broadcast via the running scan at
    segment end)."""
    func = spec.agg_func
    if spec.arg is not None:
        av, an = spec.arg.fn(env)
        sv = jnp.take(av, perm)
        valid = s_live if an is None else (s_live & ~jnp.take(an, perm))
    else:  # COUNT(*)
        sv = jnp.ones((cap,), jnp.int64)
        valid = s_live

    at = peer_end_pos if has_order else seg_end_pos

    if func in (AggFunc.SUM, AggFunc.AVG, AggFunc.COUNT,
                AggFunc.COUNT_STAR):
        acc = jnp.float64 if (func is AggFunc.AVG or
                              (func is AggFunc.SUM and
                               spec.out_dtype.is_float)) else jnp.int64
        vals = jnp.where(valid, sv.astype(acc), jnp.zeros((), acc))
        cnt1 = valid.astype(jnp.int64)
        cs = jnp.cumsum(vals)
        cc = jnp.cumsum(cnt1)
        before_v = jnp.where(seg_start_pos > 0,
                             jnp.take(cs, jnp.clip(seg_start_pos - 1, 0,
                                                   None)),
                             jnp.zeros((), acc))
        before_c = jnp.where(seg_start_pos > 0,
                             jnp.take(cc, jnp.clip(seg_start_pos - 1, 0,
                                                   None)),
                             jnp.int64(0))
        total = jnp.take(cs, at) - before_v
        count = jnp.take(cc, at) - before_c
        if func in (AggFunc.COUNT, AggFunc.COUNT_STAR):
            return count, None
        if func is AggFunc.AVG:
            denom = jnp.where(count == 0, 1, count).astype(jnp.float64)
            return total / denom, count == 0
        return total, count == 0
    # MIN / MAX: segmented running scan on a sentinel-masked lane, read at
    # the frame end, then exact value via the winning-lane trick is overkill
    # here — integers/floats compare directly (strings go through rank ids
    # upstream; not supported as window agg args yet)
    if spec.arg is not None and spec.arg.dtype.is_float:
        lane = sv.astype(jnp.float64)
        ident = jnp.asarray(jnp.inf if func is AggFunc.MIN else -jnp.inf,
                            jnp.float64)
    else:
        lane = sv.astype(jnp.int64)
        ident = jnp.asarray(jnp.iinfo(jnp.int64).max if func is AggFunc.MIN
                            else jnp.iinfo(jnp.int64).min, jnp.int64)
    masked = jnp.where(valid, lane, ident)
    op = jnp.minimum if func is AggFunc.MIN else jnp.maximum
    seg_start = jnp.arange(cap, dtype=jnp.int32) == seg_start_pos
    run = _seg_scan(op, masked, seg_start)
    cnt = _seg_scan(jnp.add, valid.astype(jnp.int64), seg_start)
    out = jnp.take(run, at)
    none = jnp.take(cnt, at) == 0
    return out, none
