"""Host (numpy) execution tier for small queries.

A device query pays a fixed floor (dispatch, padded static-shape lanes, one
readback) that a query whose sources total a few MB may not earn back; what
that floor is on the current chip is not measured yet (PERF.md; ROADMAP
A4/C1 decide this tier's fate on it). XLA:CPU is not the answer for such
queries: the engine's device kernels are static-shape/sort-based designs
(the right trade on a TPU), and replaying them on a small host loses ~3-10x to
numpy's dynamic-shape primitives (measured: 1-core XLA:CPU argsort of 1M int64
= 0.34 s vs numpy 0.13 s, and the padded-lane kernels multiply that).

So the host tier is a third executor with HOST-shaped algorithms: compact
arrays, dynamic shapes, np.unique/searchsorted joins and bincount/reduceat
aggregation — the same logical operators, re-designed for the memory hierarchy
they run on, exactly like the device kernels are designed for theirs. It
covers the plan/expression surface small analytical queries use; anything else
raises HostUnsupported and the engine falls back to the device path (the
routing threshold lives in QueryEngine.host_route_bytes).

The reference has no analog (its engine IS a host engine); parity-wise this
replaces nothing.

Semantics mirror the device expression compiler (exec/expr_compile.py):
3-valued logic with separate null lanes, x/0 -> NULL, SQL truncating integer
division, date lanes in days / timestamps in microseconds.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Optional

import numpy as np
import pyarrow as pa

from igloo_tpu import types as T
from igloo_tpu.errors import ExecError, PlanError
from igloo_tpu.exec.batch import DictInfo, host_decode_column
from igloo_tpu.exec.expr_compile import like_lut, like_match
from igloo_tpu.plan import expr as E
from igloo_tpu.plan import logical as L
from igloo_tpu.sql.ast import JoinType
from igloo_tpu.utils import stats, tracing


class HostUnsupported(Exception):
    """Plan/expression feature outside the host tier; caller falls back."""


@dataclass
class HCol:
    dtype: T.DataType
    values: np.ndarray                 # lane dtype; STRING = int32 codes
    nulls: Optional[np.ndarray]        # bool, True = null; None = no nulls
    dict: Optional[DictInfo] = None    # STRING columns


@dataclass
class HBatch:
    schema: T.Schema
    cols: list
    n: int

    def col(self, i: int) -> HCol:
        return self.cols[i]

    def take(self, idx: np.ndarray) -> "HBatch":
        return HBatch(self.schema,
                      [HCol(c.dtype, c.values[idx],
                            c.nulls[idx] if c.nulls is not None else None,
                            c.dict) for c in self.cols], len(idx))

    def mask(self, m: np.ndarray) -> "HBatch":
        return self.take(np.nonzero(m)[0])


def _or_nulls(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a | b


def _valid(n, nulls):
    return np.ones(n, dtype=bool) if nulls is None else ~nulls


def _materialize_str(c: HCol) -> np.ndarray:
    """codes+dict -> numpy unicode array (null lanes hold '')."""
    if c.dict is None or len(c.dict) == 0:
        return np.full(len(c.values), "", dtype=object).astype(str)
    return c.dict.values.astype(str)[np.clip(c.values, 0, len(c.dict) - 1)]


def _civil_from_days(days: np.ndarray):
    d64 = days.astype("datetime64[D]")
    y = d64.astype("datetime64[Y]").astype(np.int64) + 1970
    m = d64.astype("datetime64[M]").astype(np.int64) % 12 + 1
    day = (d64 - d64.astype("datetime64[M]")).astype(np.int64) + 1
    return y.astype(np.int32), m.astype(np.int32), day.astype(np.int32)


class HostExecutor:
    """Executes a bound+optimized LogicalPlan with numpy. One instance per
    query (subquery resolution recurses through `self`)."""

    # cross joins beyond this many output rows are not a "small query"
    _CROSS_LIMIT = 4_000_000

    def __init__(self, catalog=None, scan_cache=None):
        self.catalog = catalog
        # host-RAM decoded-column cache (SnapshotLRU), engine-owned: decode +
        # dictionary-encode of a column happens once, not once per query —
        # the pandas baseline gets its DataFrames pre-loaded, so must we
        self._scan_cache = scan_cache
        # intra-query structural memo: a scalar subquery usually shares its
        # join/aggregate subtree with the outer query (TPC-H q11/q15/q22);
        # executing the identical subtree once halves those queries. HBatches
        # are immutable by convention, so sharing is safe.
        self._memo: dict = {}

    # ---- public ----------------------------------------------------------

    def execute_to_arrow(self, plan: L.LogicalPlan) -> pa.Table:
        tracing.counter("host.execute")
        return to_arrow(self._exec(plan))

    # ---- dispatch --------------------------------------------------------

    def _exec(self, plan: L.LogicalPlan) -> HBatch:
        m = getattr(self, "_exec_" + type(plan).__name__.lower(), None)
        if m is None:
            raise HostUnsupported(type(plan).__name__)
        key = None
        if isinstance(plan, (L.Join, L.Aggregate)):
            key = self._plan_fp(plan)
            hit = self._memo.get(key) if key is not None else None
            if hit is not None:
                served = _serve_by_name(hit, plan.schema)
                if served is not None:
                    tracing.counter("host.memo_hit")
                    with stats.plan_op(plan):
                        stats.set_rows(served.n)
                        stats.annotate(memo="hit")
                    return served
        with stats.plan_op(plan):
            out = m(plan)
            # numpy row counts are host values: actual rows are FREE on this
            # tier, recorded at every collection level
            stats.set_rows(out.n)
        if not isinstance(plan, L.Scan):
            # feed the adaptive planner loop (docs/adaptive.md): the count is
            # already in hand, so the observation is one tuple append. The
            # fingerprint recursion is the real cost on this sub-0.1s tier,
            # so it only runs when the loop is on and no memo key exists
            from igloo_tpu.exec.hints import adaptive_enabled
            if adaptive_enabled():
                fp = key if key is not None else self._plan_fp(plan)
                if fp is not None:
                    stats.observe_card(fp, out.n)
        if out.schema is not plan.schema and out.schema != plan.schema:
            out = HBatch(plan.schema, out.cols, out.n)
        if key is not None and (key not in self._memo or
                                len(out.schema) >
                                len(self._memo[key].schema)):
            self._memo[key] = out
        return out

    @classmethod
    def _plan_fp(cls, plan: L.LogicalPlan):
        """Projection-INSENSITIVE structural fingerprint (exec/hints.plan_fp,
        shared with every AdaptiveStats producer/consumer): a scalar
        subquery's join subtree then hits the outer query's memo entry even
        though pruning gave it a narrower scan, and the hit is served by
        name (_serve_by_name) — TPC-H q2/q11/q15/q22 halve."""
        from igloo_tpu.exec.hints import plan_fp
        return plan_fp(plan, exact=True)

    # ---- leaves ----------------------------------------------------------

    def _exec_scan(self, plan: L.Scan) -> HBatch:
        from igloo_tpu.exec.executor import read_scan_table
        cache = self._scan_cache
        stable = getattr(plan.provider, "stable_row_order", False)
        if cache is None or not stable:
            table = read_scan_table(plan)
            if plan.projection is not None:
                table = table.select(plan.projection)
            cols = []
            for f in plan.schema:
                vals, nulls, dinfo, _b = host_decode_column(
                    table.column(f.name), f)
                cols.append(HCol(f.dtype, vals, nulls, dinfo))
            return HBatch(plan.schema, cols, table.num_rows)
        from igloo_tpu.exec.cache import provider_snapshot, read_identity
        snap = provider_snapshot(plan.provider)
        base = (plan.table, read_identity(plan), plan.partition, "host")
        if not plan.schema.fields:  # zero-column scan: only the count matters
            table = read_scan_table(plan)
            return HBatch(plan.schema, [], table.num_rows)
        cached = {f.name: cache.get(base + (f.name,), snap)
                  for f in plan.schema}
        missing = [f for f in plan.schema if cached[f.name] is None]
        known_n = next((v[1] for v in cached.values() if v is not None),
                       None)
        if missing:
            proj = [f.name for f in missing]
            table = read_scan_table(plan, projection=proj).select(proj)
            if known_n is not None and table.num_rows != known_n:
                # source changed under an identity snapshot: never stitch
                # columns from different row sets
                cache.invalidate_table(plan.table)
                return self._exec_scan(plan)
            for f in missing:
                vals, nulls, dinfo, _b = host_decode_column(
                    table.column(f.name), f)
                col = HCol(f.dtype, vals, nulls, dinfo)
                nb = vals.nbytes + (nulls.nbytes if nulls is not None else 0)
                cache.put_entry(base + (f.name,), (col, table.num_rows),
                                snap, nb, plan.table)
                cached[f.name] = (col, table.num_rows)
        n = next(v[1] for v in cached.values())
        return HBatch(plan.schema,
                      [cached[f.name][0] for f in plan.schema], n)

    def _exec_values(self, plan: L.Values) -> HBatch:
        from igloo_tpu.exec.batch import from_arrow  # noqa: F401  (parity)
        n = len(plan.rows)
        cols = []
        for j, f in enumerate(plan.schema):
            vals = [r[j] for r in plan.rows]
            arr = pa.array(vals, type=_pa_for(f.dtype))
            v, nulls, dinfo, _ = host_decode_column(arr, f)
            cols.append(HCol(f.dtype, v, nulls, dinfo))
        return HBatch(plan.schema, cols, n)

    # ---- row-wise --------------------------------------------------------

    def _exec_filter(self, plan: L.Filter) -> HBatch:
        b = self._exec(plan.input)
        v, nulls = self._eval_bool(plan.predicate, b)
        keep = v & _valid(b.n, nulls)
        return b.mask(keep)

    def _exec_project(self, plan: L.Project) -> HBatch:
        b = self._exec(plan.input)
        cols = [self._eval_col(e, b, f.dtype)
                for e, f in zip(plan.exprs, plan.schema)]
        return HBatch(plan.schema, cols, b.n)

    def _exec_limit(self, plan: L.Limit) -> HBatch:
        b = self._exec(plan.input)
        lo = plan.offset
        hi = b.n if plan.limit is None else min(b.n, lo + plan.limit)
        return b.take(np.arange(lo, max(lo, hi)))

    # ---- sort ------------------------------------------------------------

    def _sort_order(self, b: HBatch, keys, ascending, nulls_first,
                    stable=True) -> np.ndarray:
        lex = []  # np.lexsort: LAST key is primary
        for e, asc, nf in reversed(list(zip(keys, ascending, nulls_first))):
            c = self._eval_col(e, b, e.dtype)
            if c.dtype.is_string:
                if c.dict is not None:
                    k = c.dict.ranks().astype(np.int64)[
                        np.clip(c.values, 0, max(len(c.dict) - 1, 0))] \
                        if len(c.dict or []) else np.zeros(b.n, np.int64)
                else:
                    sv = c.values.astype(str)
                    k = np.unique(sv, return_inverse=True)[1]
            elif c.dtype.id == T.TypeId.BOOL:
                k = c.values.astype(np.int64)
            else:
                k = c.values
            if not asc:
                if k.dtype.kind == "f":
                    k = -k
                else:
                    k = -(k.astype(np.int64))
            nullk = np.zeros(b.n, dtype=np.int8)
            if c.nulls is not None:
                nullk = np.where(c.nulls, -1 if nf else 1, 0).astype(np.int8)
            lex.append(k)
            lex.append(nullk)
        return np.lexsort(lex) if lex else np.arange(b.n)

    def _exec_sort(self, plan: L.Sort) -> HBatch:
        b = self._exec(plan.input)
        order = self._sort_order(b, plan.keys, plan.ascending,
                                 plan.nulls_first)
        return b.take(order)

    # ---- distinct --------------------------------------------------------

    def _group_codes(self, cols: list, n: int) -> tuple:
        """-> (inverse codes int64[n], n_groups). Null participates as its own
        value (SQL GROUP BY/DISTINCT treat nulls as equal)."""
        if not cols:
            return np.zeros(n, dtype=np.int64), 1 if n else 0
        invs, cards = [], []
        for c in cols:
            if c.dtype.is_string and c.dict is not None:
                base = c.values.astype(np.int64)
                card = max(len(c.dict), 1)
            else:
                vals = c.values
                nan = None
                if vals.dtype.kind == "f":
                    # canonicalize -0.0; NaN gets its OWN slot below (mapping
                    # it onto inf would merge two distinct SQL groups)
                    nan = np.isnan(vals)
                    vals = np.where(nan, 0.0, vals + 0.0)
                u, base = np.unique(vals, return_inverse=True)
                card = max(len(u), 1)
                if nan is not None and nan.any():
                    base = np.where(nan, card, base)
                    card += 1
            if c.nulls is not None:
                base = np.where(c.nulls, card, base)
                card += 1
            invs.append(base.astype(np.int64))
            cards.append(card)
        total_bits = sum(int(np.ceil(np.log2(max(cd, 2)))) for cd in cards)
        if total_bits < 62:
            mixed = invs[0]
            for iv, cd in zip(invs[1:], cards[1:]):
                mixed = mixed * cd + iv
        else:
            _, mixed = np.unique(np.stack(invs, axis=1), axis=0,
                                 return_inverse=True)
        _, first, inv = np.unique(mixed, return_index=True,
                                  return_inverse=True)
        return inv, len(first)

    def _exec_distinct(self, plan: L.Distinct) -> HBatch:
        b = self._exec(plan.input)
        inv, _k = self._group_codes(b.cols, b.n)
        # first occurrence of each group, in input order
        first = np.zeros(0, dtype=np.int64)
        if b.n:
            order = np.argsort(inv, kind="stable")
            boundaries = np.ones(b.n, dtype=bool)
            boundaries[1:] = inv[order][1:] != inv[order][:-1]
            first = np.sort(order[boundaries])
        return b.take(first)

    # ---- aggregate -------------------------------------------------------

    def _group_direct(self, gcols: list, n: int):
        """Sort-free grouping: when every key is a dense-int / dictionary /
        bool lane, group ids are direct offsets and the key VALUES decode
        from the slot id — no np.unique (a full sort) and no representative
        gather. Returns (inv, card, decode) or None for the generic path."""
        parts = []  # (card, decoder(slots)->HCol)
        inv = None
        prod = 1  # running COMBINED cardinality: the direct arrays (bincount
        # targets, per-aggregate outputs) are prod-sized, so the same dense
        # bound that limits each key's span must limit their product — two
        # ~4n-span keys would otherwise attempt ~16n^2-slot allocations
        for c in gcols:
            nulls = c.nulls if c.nulls is not None and c.nulls.any() else None
            if c.dtype.is_string and c.dict is not None:
                card = max(len(c.dict), 1)
                codes = c.values.astype(np.int64)

                def dec(slots, isnull, c=c):
                    return HCol(c.dtype, slots.astype(np.int32),
                                isnull, c.dict)
            elif c.dtype.id == T.TypeId.BOOL:
                card = 2
                codes = c.values.astype(np.int64)

                def dec(slots, isnull, c=c):
                    return HCol(c.dtype, slots.astype(bool), isnull)
            elif c.values.dtype.kind in "iu":
                if n == 0:
                    lo, hi = 0, 0
                else:
                    lo, hi = int(c.values.min()), int(c.values.max())
                span = hi - lo + 1
                if span > 4 * n + 1024:
                    return None  # sparse keys: direct slots would explode
                card = span
                codes = (c.values - lo).astype(np.int64)

                def dec(slots, isnull, c=c, lo=lo):
                    return HCol(c.dtype,
                                (slots + lo).astype(c.values.dtype), isnull)
            else:
                return None  # float keys: generic path
            if nulls is not None:
                codes = np.where(nulls, card, codes)
                card += 1
                base_dec = dec

                def dec(slots, isnull, base_dec=base_dec, card=card):
                    isn = slots == card - 1
                    col = base_dec(np.where(isn, 0, slots), None)
                    return replace(col, nulls=isn if isn.any() else None)
            prod *= card
            if prod > 4 * n + 1024:
                return None  # combined slot space would dwarf the input
            parts.append((card, dec))
            inv = codes if inv is None else inv * card + codes
        card = prod

        def decode(slots):
            cols, rest = [], slots
            for cd, dec in reversed(parts):
                cols.append((dec, rest % cd))
                rest = rest // cd
            return [dec(sl, None) for dec, sl in reversed(cols)]
        return inv, card, decode

    def _exec_aggregate(self, plan: L.Aggregate) -> HBatch:
        b = self._exec(plan.input)
        gcols = [self._eval_col(e, b, e.dtype) for e in plan.group_exprs]
        no_groups = not plan.group_exprs
        if no_groups:
            inv = np.zeros(b.n, dtype=np.int64)
            out_cols = []
            for agg, f in zip(plan.aggs, plan.schema.fields):
                out_cols.append(self._agg_one(agg, f.dtype, b, inv, 1))
            return HBatch(plan.schema, out_cols, 1)
        direct = self._group_direct(gcols, b.n) if b.n else None
        if direct is not None:
            inv, card, decode = direct
            occupied = np.bincount(inv, minlength=card) > 0
            slots = np.nonzero(occupied)[0]
            out_cols = decode(slots)
            for agg, f in zip(plan.aggs, plan.schema.fields[len(gcols):]):
                full = self._agg_one(agg, f.dtype, b, inv, card)
                out_cols.append(HCol(full.dtype, full.values[slots],
                                     full.nulls[slots]
                                     if full.nulls is not None else None,
                                     full.dict))
            return HBatch(plan.schema, out_cols, len(slots))
        inv, k = self._group_codes(gcols, b.n)
        # representative row per group (group order is unspecified by SQL)
        if b.n:
            order = np.argsort(inv, kind="stable")
            bnd = np.ones(b.n, dtype=bool)
            bnd[1:] = inv[order][1:] != inv[order][:-1]
            reps = order[bnd]
        else:
            reps = np.zeros(0, dtype=np.int64)
        out_cols = [HCol(c.dtype, c.values[reps],
                         c.nulls[reps] if c.nulls is not None else None,
                         c.dict) for c in gcols]
        nk = len(reps)
        for agg, f in zip(plan.aggs, plan.schema.fields[len(gcols):]):
            out_cols.append(self._agg_one(agg, f.dtype, b, inv, nk))
        return HBatch(plan.schema, out_cols, nk)

    def _agg_one(self, agg: E.Aggregate, out_dtype, b: HBatch,
                 inv: np.ndarray, k: int) -> HCol:
        AF = E.AggFunc
        if agg.func is AF.COUNT_STAR:
            cnt = np.bincount(inv, minlength=k).astype(np.int64)
            return HCol(out_dtype, cnt, None)
        c = self._eval_col(agg.arg, b, agg.arg.dtype)
        valid = _valid(b.n, c.nulls)
        vinv, n_valid = inv[valid], int(valid.sum())
        if agg.distinct:
            if agg.func not in (AF.COUNT, AF.SUM, AF.AVG, AF.MIN, AF.MAX):
                raise HostUnsupported(f"distinct {agg.func}")
            vals = c.values[valid]
            if c.dtype.is_string and c.dict is not None:
                code = vals.astype(np.int64)
            else:
                code = np.unique(vals, return_inverse=True)[1]
            pair = vinv * (int(code.max()) + 1 if len(code) else 1) + code
            _, first = np.unique(pair, return_index=True)
            vinv, vals = vinv[first], vals[first]
            n_valid = len(first)
            c = replace(c, values=vals)
        else:
            vals = c.values[valid]
        if agg.func is AF.COUNT:
            cnt = np.bincount(vinv, minlength=k).astype(np.int64)
            return HCol(out_dtype, cnt, None)
        counts = np.bincount(vinv, minlength=k)
        empty = counts == 0
        if agg.func in (AF.SUM, AF.AVG):
            if c.dtype.is_string:
                raise HostUnsupported("sum over strings")
            if vals.dtype.kind == "f":
                s = np.bincount(vinv, weights=vals, minlength=k)
            elif len(vals) == 0 or (len(vals) * max(abs(int(vals.max())),
                                                    abs(int(vals.min())),
                                                    1)) < (1 << 53):
                # every possible partial sum fits float64 exactly: bincount's
                # C loop beats np.add.at's per-element ufunc dispatch ~10x
                s = np.bincount(vinv, weights=vals.astype(np.float64),
                                minlength=k).astype(np.int64)
            else:
                s = np.zeros(k, dtype=np.int64)
                np.add.at(s, vinv, vals.astype(np.int64))
            if agg.func is AF.AVG:
                out = s / np.maximum(counts, 1)
                return HCol(out_dtype, out.astype(np.float64),
                            empty if empty.any() else None)
            out = s.astype(out_dtype.device_dtype())
            return HCol(out_dtype, out, empty if empty.any() else None)
        # MIN / MAX via sort + reduceat-style first/last per group
        if c.dtype.is_string and c.dict is not None:
            ranks = c.dict.ranks().astype(np.int64)
            sortv = ranks[np.clip(vals, 0, max(len(c.dict) - 1, 0))] \
                if len(c.dict) else np.zeros(len(vals), np.int64)
        else:
            sortv = vals
        order = np.lexsort((sortv, vinv))
        sv, si = vinv[order], vals[order]
        bnd = np.ones(len(sv), dtype=bool)
        if len(sv):
            bnd[1:] = sv[1:] != sv[:-1]
        out = np.zeros(k, dtype=vals.dtype)
        if len(sv):
            if agg.func is AF.MIN:
                out[sv[bnd]] = si[bnd]
            else:
                last = np.roll(bnd, -1)
                out[sv[last]] = si[last]
        return HCol(out_dtype, out, empty if empty.any() else None, c.dict)

    # ---- join ------------------------------------------------------------

    def _key_codes(self, lcols: list, rcols: list, nl: int, nr: int):
        """Shared int64 encoding of the two sides' key tuples.
        Returns (lkey, rkey, lvalid, rvalid)."""
        lparts, rparts = [], []
        lvalid = np.ones(nl, dtype=bool)
        rvalid = np.ones(nr, dtype=bool)
        for lc, rc in zip(lcols, rcols):
            if lc.dtype.is_string or rc.dtype.is_string:
                # join string keys on BOTH per-entry hashes (seed 0 + seed 1,
                # 128-bit effective — the device join's collision guard,
                # exec/batch.py DictInfo)
                for attr in ("hashes", "hashes2"):
                    lv = _str_hash_lane(lc, nl, attr)
                    rv = _str_hash_lane(rc, nr, attr)
                    lparts.append(lv)
                    rparts.append(rv)
                if lc.nulls is not None:
                    lvalid &= ~lc.nulls
                if rc.nulls is not None:
                    rvalid &= ~rc.nulls
                continue
            else:
                lv, rv = lc.values, rc.values
                if lv.dtype.kind == "f" or rv.dtype.kind == "f":
                    lv = lv.astype(np.float64).view(np.int64)
                    rv = rv.astype(np.float64).view(np.int64)
                else:
                    lv = lv.astype(np.int64)
                    rv = rv.astype(np.int64)
            lparts.append(lv)
            rparts.append(rv)
            if lc.nulls is not None:
                lvalid &= ~lc.nulls
            if rc.nulls is not None:
                rvalid &= ~rc.nulls
        if len(lparts) == 1:
            return lparts[0], rparts[0], lvalid, rvalid
        both = np.concatenate(
            [np.stack(lparts, axis=1), np.stack(rparts, axis=1)], axis=0)
        _, inv = np.unique(both, axis=0, return_inverse=True)
        return inv[:nl].astype(np.int64), inv[nl:].astype(np.int64), \
            lvalid, rvalid

    def _probe(self, lkey, rkey, lval, rval):
        """Probe phase -> (cnt[left], lo[left], rpos): row i of the left
        matches build rows rpos[lo[i] : lo[i]+cnt[i]].

        Dense build-key ranges use a counting-sort direct probe (the host
        analog of the device's direct array join, exec/join.py direct_probe):
        O(n + range) with no comparison sort. Sparse ranges fall back to
        sort + searchsorted, with a single-probe shortcut when the build keys
        are unique (every TPC-H PK side)."""
        rv = rkey[rval]
        rpos_all = np.nonzero(rval)[0]
        n_build = len(rv)
        if n_build == 0:
            return (np.zeros(len(lkey), dtype=np.int64),
                    np.zeros(len(lkey), dtype=np.int64),
                    np.zeros(0, dtype=np.int64))
        lo_k, hi_k = int(rv.min()), int(rv.max())
        rng = hi_k - lo_k + 1
        if 0 < rng <= max(1 << 22, 4 * n_build):
            codes = rv - lo_k
            counts = np.bincount(codes, minlength=rng)
            starts = np.zeros(rng + 1, dtype=np.int64)
            np.cumsum(counts, out=starts[1:])
            order = np.argsort(codes, kind="stable")
            rpos = rpos_all[order]
            in_range = lval & (lkey >= lo_k) & (lkey <= hi_k)
            lc = np.where(in_range, lkey - lo_k, 0)
            cnt = np.where(in_range, counts[lc], 0)
            lo = np.where(in_range, starts[:-1][lc], 0)
            return cnt, lo, rpos
        order = np.argsort(rv, kind="stable")
        rpos = rpos_all[order]
        rsorted = rv[order]
        lo = np.searchsorted(rsorted, lkey, side="left")
        unique_build = n_build < 2 or \
            bool((rsorted[1:] != rsorted[:-1]).all())
        if unique_build:
            safe = np.clip(lo, 0, n_build - 1)
            cnt = np.where(lval & (rsorted[safe] == lkey), 1, 0)
        else:
            hi = np.searchsorted(rsorted, lkey, side="right")
            cnt = np.where(lval, hi - lo, 0)
        return cnt.astype(np.int64), lo, rpos

    # --- inner-join chain reorder ----------------------------------------

    def _flatten_inner(self, plan: L.Join):
        """Flatten a left-deep INNER equi-join spine whose keys are all plain
        column refs -> (rels, edges, residuals); None when the shape doesn't
        apply. Edges/residual column indexes are global (the spine is
        left-deep, so each node's concat schema is a prefix)."""
        rels: list = []
        edges: list = []      # (global left col, global right col)
        residuals: list = []  # exprs over the full concat schema

        def rec(node) -> bool:
            if isinstance(node, L.Join) and node.join_type is JoinType.INNER \
                    and node.left_keys and \
                    all(isinstance(k, E.Column) for k in
                        node.left_keys + node.right_keys):
                if not rec(node.left):
                    return False
                lw = len(node.left.schema)
                rels.append(node.right)
                for lk, rk in zip(node.left_keys, node.right_keys):
                    edges.append((lk.index, lw + rk.index))
                if node.residual is not None:
                    residuals.append(node.residual)
                return True
            rels.append(node)
            return True

        if not rec(plan):
            return None
        return (rels, edges, residuals) if len(rels) >= 3 else None

    def _exec_inner_chain(self, plan: L.Join, flat) -> HBatch:
        """Execute a flattened inner-join chain smallest-connected-first with
        EXACT input cardinalities (an optimizer would estimate; the host tier
        has the real numbers in hand). Yields the same rows as the written
        order; column order is restored at the end (no copy — HCol lists
        permute by reference)."""
        rels, edges, residuals = flat
        batches = [self._exec(r) for r in rels]
        offsets, off = [], 0
        for r in rels:
            offsets.append(off)
            off += len(r.schema)

        def rel_of(g: int) -> int:
            for i in range(len(rels) - 1, -1, -1):
                if g >= offsets[i]:
                    return i
            return 0

        order = [int(np.argmin([b.n for b in batches]))]
        remaining = [i for i in range(len(rels)) if i not in order]
        while remaining:
            conn = [i for i in remaining
                    if any(rel_of(a) in order and rel_of(bb) == i or
                           rel_of(bb) in order and rel_of(a) == i
                           for a, bb in edges)]
            pool = conn or remaining  # disconnected: cross join (guarded)
            nxt = min(pool, key=lambda i: batches[i].n)
            order.append(nxt)
            remaining.remove(nxt)

        # run the chain; cur maps global col idx -> position in cur batch
        placed = {order[0]}
        cur = batches[order[0]]
        pos = {offsets[order[0]] + k: k
               for k in range(len(rels[order[0]].schema))}
        consumed = [False] * len(edges)
        for i in order[1:]:
            rb = batches[i]
            lkeys, rkeys = [], []
            for ei, (a, bb) in enumerate(edges):
                if consumed[ei]:
                    continue
                if rel_of(a) in placed and rel_of(bb) == i:
                    lkeys.append(cur.cols[pos[a]])
                    rkeys.append(rb.cols[bb - offsets[i]])
                    consumed[ei] = True
                elif rel_of(bb) in placed and rel_of(a) == i:
                    lkeys.append(cur.cols[pos[bb]])
                    rkeys.append(rb.cols[a - offsets[i]])
                    consumed[ei] = True
            if lkeys:
                lkey, rkey, lval, rval = self._key_codes(
                    lkeys, rkeys, cur.n, rb.n)
                # build on the SMALLER side (the probe pays O(probe) passes,
                # the build pays the argsort)
                if rb.n <= cur.n:
                    cnt, lo, rpos = self._probe(lkey, rkey, lval, rval)
                    total = int(cnt.sum())
                    lidx = np.repeat(np.arange(cur.n), cnt)
                    starts = np.repeat(lo, cnt)
                    offs = np.arange(total) - np.repeat(
                        np.cumsum(cnt) - cnt, cnt)
                    ridx = rpos[starts + offs]
                else:
                    cnt, lo, rpos = self._probe(rkey, lkey, rval, lval)
                    total = int(cnt.sum())
                    ridx = np.repeat(np.arange(rb.n), cnt)
                    starts = np.repeat(lo, cnt)
                    offs = np.arange(total) - np.repeat(
                        np.cumsum(cnt) - cnt, cnt)
                    lidx = rpos[starts + offs]
            else:
                if cur.n * rb.n > self._CROSS_LIMIT:
                    raise HostUnsupported("cross join too large")
                lidx = np.repeat(np.arange(cur.n), rb.n)
                ridx = np.tile(np.arange(rb.n), cur.n)
            cur = _join_output(None, cur, rb, lidx, ridx, None, None)
            base = len(pos)
            for k in range(len(rels[i].schema)):
                pos[offsets[i] + k] = base + k
            placed.add(i)
        # cyclic edges never consumed at placement: equality post-filters
        for ei, (a, bb) in enumerate(edges):
            if not consumed[ei]:
                ca, cb = cur.cols[pos[a]], cur.cols[pos[bb]]
                eq = self._numeric_binary(E.BinOp.EQ, ca, cb, None, cur) \
                    if not ca.dtype.is_string else \
                    self._string_compare(E.BinOp.EQ, ca, cb, cur)
                cur = cur.mask(eq.values & _valid(cur.n, eq.nulls))
        # restore written column order (plan.schema) by list permutation
        cols = [cur.cols[pos[g]] for g in range(off)]
        out = HBatch(plan.schema, cols, cur.n)
        for res in residuals:
            v, nulls = self._eval_bool(res, out)
            out = out.mask(v & _valid(out.n, nulls))
        tracing.counter("host.chain_reorder")
        return out

    def _exec_join(self, plan: L.Join) -> HBatch:
        if plan.join_type is JoinType.INNER:
            flat = self._flatten_inner(plan)
            if flat is not None:
                return self._exec_inner_chain(plan, flat)
        lb = self._exec(plan.left)
        rb = self._exec(plan.right)
        jt = plan.join_type
        if jt is JoinType.CROSS or not plan.left_keys:
            if lb.n * rb.n > self._CROSS_LIMIT:
                raise HostUnsupported("cross join too large")
            lidx = np.repeat(np.arange(lb.n), rb.n)
            ridx = np.tile(np.arange(rb.n), lb.n)
            out = _join_output(plan.schema, lb, rb, lidx, ridx, None, None)
            if plan.residual is not None:
                v, nulls = self._eval_bool(plan.residual, out)
                out = out.mask(v & _valid(out.n, nulls))
            return out
        lk = [self._eval_col(e, lb, e.dtype) for e in plan.left_keys]
        rk = [self._eval_col(e, rb, e.dtype) for e in plan.right_keys]
        lkey, rkey, lval, rval = self._key_codes(lk, rk, lb.n, rb.n)
        cnt, lo, rpos = self._probe(lkey, rkey, lval, rval)
        total = int(cnt.sum())
        lidx = np.repeat(np.arange(lb.n), cnt)
        starts = np.repeat(lo, cnt)
        offs = np.arange(total) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        ridx = rpos[starts + offs]
        if plan.residual is not None:
            pairs = _join_output(plan.schema if jt is JoinType.INNER else None,
                                 lb, rb, lidx, ridx, None, None)
            v, nulls = self._eval_bool(plan.residual, pairs)
            keep = v & _valid(pairs.n, nulls)
            lidx, ridx = lidx[keep], ridx[keep]
        if jt in (JoinType.INNER,):
            return _join_output(plan.schema, lb, rb, lidx, ridx, None, None)
        lmatched = np.zeros(lb.n, dtype=bool)
        lmatched[lidx] = True
        if jt is JoinType.SEMI:
            return lb.take(np.nonzero(lmatched)[0])
        if jt is JoinType.ANTI:
            return lb.take(np.nonzero(~lmatched)[0])
        rmatched = np.zeros(rb.n, dtype=bool)
        rmatched[ridx] = True
        if jt in (JoinType.LEFT, JoinType.FULL):
            extra = np.nonzero(~lmatched)[0]
            lidx = np.concatenate([lidx, extra])
            ridx = np.concatenate([ridx, np.full(len(extra), -1)])
        if jt in (JoinType.RIGHT, JoinType.FULL):
            extra = np.nonzero(~rmatched)[0]
            lidx = np.concatenate([lidx, np.full(len(extra), -1)])
            ridx = np.concatenate([ridx, extra])
        return _join_output(plan.schema, lb, rb, lidx, ridx,
                            lidx < 0, ridx < 0)

    # ---- expressions -----------------------------------------------------

    def _eval_bool(self, e: E.Expr, b: HBatch):
        c = self._eval(e, b)
        v = c.values
        if v.dtype != np.bool_:
            v = v.astype(bool)
        return v, c.nulls

    def _eval_col(self, e: E.Expr, b: HBatch, dtype) -> HCol:
        c = self._eval(e, b)
        want = (dtype or c.dtype)
        if want is not None and not want.is_string and \
                c.values.dtype != want.device_dtype():
            c = replace(c, values=c.values.astype(want.device_dtype()),
                        dtype=want)
        return c

    def _eval(self, e: E.Expr, b: HBatch) -> HCol:
        m = getattr(self, "_e_" + type(e).__name__.lower(), None)
        if m is None:
            raise HostUnsupported(f"expr {type(e).__name__}")
        return m(e, b)

    def _e_alias(self, e: E.Alias, b):
        return self._eval(e.operand, b)

    def _e_column(self, e: E.Column, b: HBatch):
        if e.index is None:
            raise PlanError(f"unbound column {e.name}")
        return b.cols[e.index]

    def _e_literal(self, e: E.Literal, b: HBatch):
        dtype = e.dtype or e.literal_type
        v = e.value
        if v is None:
            dd = (dtype or T.INT64)
            lane = np.int32 if dd.is_string else dd.device_dtype()
            return HCol(dtype or T.INT64, np.zeros(b.n, dtype=lane),
                        np.ones(b.n, dtype=bool),
                        DictInfo.from_values([]) if dd.is_string else None)
        if dtype is not None and dtype.is_string:
            d = DictInfo.from_values([str(v)])
            return HCol(dtype, np.zeros(b.n, dtype=np.int32), None, d)
        if isinstance(v, bool):
            return HCol(T.BOOL, np.full(b.n, v, dtype=bool), None)
        lane = (dtype or (T.INT64 if isinstance(v, int) else T.FLOAT64)) \
            .device_dtype()
        return HCol(dtype or (T.INT64 if isinstance(v, int) else T.FLOAT64),
                    np.full(b.n, v, dtype=lane), None)

    def _e_scalarsubquery(self, e: E.ScalarSubquery, b: HBatch):
        memo = getattr(e, "_host_lit", None)
        if memo is None:
            if not isinstance(e.query, L.LogicalPlan):
                raise PlanError("unbound scalar subquery reached executor")
            t = self.execute_to_arrow(e.query)
            if t.num_rows > 1:
                raise ExecError("scalar subquery returned more than one row")
            dtype = e.query.schema.fields[0].dtype
            val = None if t.num_rows == 0 else t.column(0)[0].as_py()
            if dtype.id == T.TypeId.DATE32 and val is not None:
                import datetime as _dt
                val = val.toordinal() - _dt.date(1970, 1, 1).toordinal()
            elif dtype.id == T.TypeId.TIMESTAMP and val is not None:
                import datetime as _dt
                val = (val - _dt.datetime(1970, 1, 1)) \
                    // _dt.timedelta(microseconds=1)
            lit = E.Literal(value=val, literal_type=dtype)
            lit.dtype = e.dtype or dtype
            e._host_lit = lit
            memo = lit
        return self._e_literal(memo, b)

    def _e_binary(self, e: E.Binary, b: HBatch):
        op = e.op
        if op in (E.BinOp.AND, E.BinOp.OR):
            lv, ln = self._eval_bool(e.left, b)
            rv, rn = self._eval_bool(e.right, b)
            lN = ln if ln is not None else np.zeros(b.n, bool)
            rN = rn if rn is not None else np.zeros(b.n, bool)
            if op is E.BinOp.AND:  # Kleene: F dominates, T&T=T, else NULL
                known_true = (lv & ~lN) & (rv & ~rN)
                known_false = (~lv & ~lN) | (~rv & ~rN)
            else:                  # Kleene: T dominates, F|F=F, else NULL
                known_true = (lv & ~lN) | (rv & ~rN)
                known_false = (~lv & ~lN) & (~rv & ~rN)
            nulls = ~(known_true | known_false)
            return HCol(T.BOOL, known_true,
                        nulls if nulls.any() else None)
        lc = self._eval(e.left, b)
        rc = self._eval(e.right, b)
        if lc.dtype.is_string or rc.dtype.is_string:
            return self._string_compare(op, lc, rc, b)
        return self._numeric_binary(op, lc, rc, e.dtype, b)

    def _numeric_binary(self, op, lc: HCol, rc: HCol, out_dtype, b: HBatch):
        if op in E.COMPARISONS:
            res_dtype = T.BOOL
            wd = T.common_type(lc.dtype, rc.dtype).device_dtype()
        else:
            res_dtype = out_dtype or T.common_type(lc.dtype, rc.dtype)
            wd = res_dtype.device_dtype()
        lv, rv = lc.values, rc.values
        if lc.dtype.id == T.TypeId.DATE32 and rc.dtype.id == T.TypeId.TIMESTAMP:
            lv = lv.astype(np.int64) * np.int64(86_400_000_000)
        if rc.dtype.id == T.TypeId.DATE32 and lc.dtype.id == T.TypeId.TIMESTAMP:
            rv = rv.astype(np.int64) * np.int64(86_400_000_000)
        lv = lv.astype(wd) if lv.dtype != wd else lv
        rv = rv.astype(wd) if rv.dtype != wd else rv
        nulls = _or_nulls(lc.nulls, rc.nulls)
        B = E.BinOp
        if op is B.ADD:
            out = lv + rv
        elif op is B.SUB:
            out = lv - rv
        elif op is B.MUL:
            out = lv * rv
        elif op is B.DIV:
            zero = rv == 0
            safe = np.where(zero, 1, rv)
            if res_dtype.is_integer:
                out = np.trunc(lv.astype(np.float64) /
                               safe.astype(np.float64)).astype(wd)
            else:
                out = lv / safe
            out = np.where(zero, 0, out)
            nulls = _or_nulls(nulls, zero if zero.any() else None)
        elif op is B.MOD:
            zero = rv == 0
            safe = np.where(zero, 1, rv)
            out = lv - np.trunc(lv.astype(np.float64) /
                                safe.astype(np.float64)).astype(wd) * safe
            nulls = _or_nulls(nulls, zero if zero.any() else None)
        elif op is B.EQ:
            out = lv == rv
        elif op is B.NEQ:
            out = lv != rv
        elif op is B.LT:
            out = lv < rv
        elif op is B.LTE:
            out = lv <= rv
        elif op is B.GT:
            out = lv > rv
        else:
            out = lv >= rv
        return HCol(res_dtype, out, nulls)

    def _string_compare(self, op, lc: HCol, rc: HCol, b: HBatch):
        if op not in E.COMPARISONS:
            raise HostUnsupported(f"string {op}")
        ls = _materialize_str(lc)
        rs = _materialize_str(rc)
        B = E.BinOp
        out = {B.EQ: ls == rs, B.NEQ: ls != rs, B.LT: ls < rs,
               B.LTE: ls <= rs, B.GT: ls > rs, B.GTE: ls >= rs}[op]
        return HCol(T.BOOL, out, _or_nulls(lc.nulls, rc.nulls))

    def _e_not(self, e: E.Not, b):
        v, nulls = self._eval_bool(e.operand, b)
        return HCol(T.BOOL, ~v, nulls)

    def _e_negate(self, e: E.Negate, b):
        c = self._eval(e.operand, b)
        return replace(c, values=-c.values)

    def _e_isnull(self, e: E.IsNull, b):
        c = self._eval(e.operand, b)
        isn = c.nulls if c.nulls is not None else np.zeros(b.n, dtype=bool)
        return HCol(T.BOOL, ~isn if e.negated else isn.copy(), None)

    _US_PER_DAY = 86_400_000_000

    def _e_cast(self, e: E.Cast, b):
        c = self._eval(e.operand, b)
        to = e.to
        if to.is_string or c.dtype.is_string:
            raise HostUnsupported("string cast")
        v = c.values
        # lane-unit rescale (device parity: expr_compile date<->timestamp)
        if c.dtype.id == T.TypeId.DATE32 and to.id == T.TypeId.TIMESTAMP:
            v = v.astype(np.int64) * np.int64(self._US_PER_DAY)
        elif c.dtype.id == T.TypeId.TIMESTAMP and to.id == T.TypeId.DATE32:
            v = np.floor_divide(v, np.int64(self._US_PER_DAY))
        return HCol(to, v.astype(to.device_dtype()), c.nulls)

    def _e_case(self, e: E.Case, b):
        out_dtype = e.dtype
        if out_dtype is not None and out_dtype.is_string:
            raise HostUnsupported("string case")
        lane = (out_dtype or T.FLOAT64).device_dtype()
        out = np.zeros(b.n, dtype=lane)
        nulls = np.ones(b.n, dtype=bool)  # unset lanes -> ELSE below
        decided = np.zeros(b.n, dtype=bool)
        for cond, val in e.whens:
            cv, cn = self._eval_bool(cond, b)
            hit = cv & _valid(b.n, cn) & ~decided
            vc = self._eval_col(val, b, out_dtype)
            out[hit] = vc.values[hit]
            nulls[hit] = vc.nulls[hit] if vc.nulls is not None else False
            decided |= hit
        rest = ~decided
        if e.else_ is not None and rest.any():
            vc = self._eval_col(e.else_, b, out_dtype)
            out[rest] = vc.values[rest]
            nulls[rest] = vc.nulls[rest] if vc.nulls is not None else False
        return HCol(out_dtype or T.FLOAT64, out,
                    nulls if nulls.any() else None)

    def _e_inlist(self, e: E.InList, b):
        c = self._eval(e.operand, b)
        items = []
        has_null = False
        for it in e.items:
            if not isinstance(it, E.Literal):
                raise HostUnsupported("non-literal IN list")
            if it.value is None:
                has_null = True  # NULL in the list: misses become UNKNOWN
            else:
                items.append(it.value)
        if c.dtype.is_string:
            if c.dict is not None:
                lut = np.isin(c.dict.values.astype(str),
                              np.asarray([str(i) for i in items], dtype=str)) \
                    if items and len(c.dict) else \
                    np.zeros(max(len(c.dict), 1), dtype=bool)
                out = lut[np.clip(c.values, 0, max(len(c.dict) - 1, 0))] \
                    if len(c.dict) else np.zeros(b.n, dtype=bool)
            else:
                sv = _materialize_str(c)
                out = np.isin(sv, np.asarray([str(i) for i in items],
                                             dtype=str)) \
                    if items else np.zeros(b.n, dtype=bool)
        else:
            out = np.isin(c.values,
                          np.asarray(items, dtype=c.values.dtype)) \
                if items else np.zeros(b.n, dtype=bool)
        nulls = c.nulls
        if has_null:
            # x IN (..., NULL): no match -> NULL, match -> TRUE (3VL);
            # negated NOT IN with a NULL never returns TRUE for non-matches
            miss_null = ~out
            nulls = _or_nulls(nulls, miss_null if miss_null.any() else None)
        if e.negated:
            out = ~out
        return HCol(T.BOOL, out, nulls)

    def _e_like(self, e: E.Like, b):
        c = self._eval(e.operand, b)
        if c.dict is not None:
            lut = like_lut(c.dict, e.pattern, e.case_insensitive)
            out = lut[np.clip(c.values, 0, max(len(c.dict) - 1, 0))] \
                if len(c.dict) else np.zeros(b.n, dtype=bool)
        else:
            out = like_match(_materialize_str(c), e.pattern,
                             e.case_insensitive)
        if e.negated:
            out = ~out
        return HCol(T.BOOL, out, c.nulls)

    def _e_func(self, e: E.Func, b):
        name = e.name.lower()
        if name in ("year", "month", "day",
                    "extract_year", "extract_month", "extract_day"):
            which = name.split("_")[-1]
            c = self._eval(e.args[0], b)
            days = c.values
            if c.dtype.id == T.TypeId.TIMESTAMP:
                days = np.floor_divide(days, np.int64(86_400_000_000)) \
                    .astype(np.int32)
            y, m, d = _civil_from_days(days)
            return HCol(T.INT32, {"year": y, "month": m, "day": d}[which],
                        c.nulls)
        if name in _HOST_STR_FUNCS:
            return self._string_func(name, e, b)
        unary = {"abs": np.abs, "floor": np.floor, "ceil": np.ceil,
                 "sqrt": np.sqrt, "exp": np.exp, "ln": np.log,
                 "log": np.log, "log10": np.log10, "sign": np.sign}
        if name in unary:
            c = self._eval(e.args[0], b)
            out_dtype = e.dtype
            return HCol(out_dtype,
                        unary[name](c.values.astype(out_dtype.device_dtype())),
                        c.nulls)
        if name == "round":
            c = self._eval(e.args[0], b)
            digits = 0
            if len(e.args) > 1:
                if not isinstance(e.args[1], E.Literal):
                    raise HostUnsupported("round with non-literal digits")
                digits = int(e.args[1].value)
            scale = 10.0 ** digits
            return HCol(T.FLOAT64,
                        np.round(c.values.astype(np.float64) * scale) / scale,
                        c.nulls)
        if name == "coalesce":
            out_dtype = e.dtype
            if out_dtype is not None and out_dtype.is_string:
                raise HostUnsupported("string coalesce")
            out = None
            nulls = None
            for a in e.args:
                c = self._eval_col(a, b, out_dtype)
                if out is None:
                    out = c.values.copy()
                    nulls = (c.nulls.copy() if c.nulls is not None
                             else np.zeros(b.n, dtype=bool))
                else:
                    take = nulls & _valid(b.n, c.nulls)
                    out[take] = c.values[take]
                    nulls &= ~take
            return HCol(out_dtype or T.FLOAT64, out,
                        nulls if nulls is not None and nulls.any() else None)
        raise HostUnsupported(f"function {name}")

    def _string_func(self, name: str, e: E.Func, b: HBatch):
        c = self._eval(e.args[0], b)
        if c.dict is None:
            raise HostUnsupported("string fn on non-dictionary value")
        d = c.dict

        def lit_int(i, default=None):
            if i >= len(e.args):
                if default is not None:
                    return default
                raise HostUnsupported(f"{name} missing arg")
            a = e.args[i]
            if not isinstance(a, E.Literal):
                raise HostUnsupported(f"{name} non-literal arg")
            return int(a.value)

        if name in ("length", "char_length", "character_length"):
            lut = np.fromiter((len(str(v)) for v in d.values),
                              dtype=np.int64, count=len(d))
            out = lut[np.clip(c.values, 0, max(len(d) - 1, 0))] \
                if len(d) else np.zeros(b.n, np.int64)
            return HCol(T.INT64, out, c.nulls)

        def transform(f: Callable[[str], str], memo_key=None) -> HCol:
            # per-entry transforms memoize on the (cached) DictInfo: the
            # same substring/upper over the same column costs one python
            # pass per PROCESS, not one per evaluation (q22 evaluates
            # substr(c_phone,1,2) three times over a 150k-entry dictionary)
            cache = getattr(d, "_xform_memo", None)
            if cache is None:
                cache = {}
                object.__setattr__(d, "_xform_memo", cache)
            hit = cache.get(memo_key) if memo_key is not None else None
            if hit is None:
                new = np.asarray([f(str(v)) for v in d.values], dtype=object)
                uniq, inverse = (np.unique(new.astype(str),
                                           return_inverse=True)
                                 if len(new) else (np.asarray([], dtype=str),
                                                   np.zeros(0, np.int64)))
                nd = DictInfo.from_values(uniq.astype(object))
                hit = (inverse.astype(np.int32), nd)
                if memo_key is not None:
                    cache[memo_key] = hit
            inverse32, nd = hit
            codes = inverse32[np.clip(c.values, 0, max(len(d) - 1, 0))] \
                if len(d) else np.zeros(b.n, np.int32)
            return HCol(T.STRING, codes, c.nulls, nd)

        if name == "upper":
            return transform(str.upper, memo_key=("upper",))
        if name == "lower":
            return transform(str.lower, memo_key=("lower",))
        if name == "capitalize":
            # reference parity: crates/engine/src/lib.rs:71-95
            return transform(lambda s: (s[:1].upper() + s[1:].lower())
                             if s else s, memo_key=("capitalize",))
        if name == "trim":
            return transform(str.strip, memo_key=("trim",))
        if name in ("substr", "substring"):
            start = lit_int(1)
            ln = lit_int(2, default=1 << 30)
            i0 = max(start - 1, 0)
            return transform(lambda s: s[i0: i0 + ln],
                             memo_key=("substr", i0, ln))
        if name == "left":
            ln = lit_int(1)
            return transform(lambda s: s[:ln], memo_key=("left", ln))
        if name == "right":
            ln = lit_int(1)
            return transform(lambda s: s[-ln:] if ln else "",
                             memo_key=("right", ln))
        if name == "concat":
            parts = [self._eval(a, b) for a in e.args]
            svals = [_materialize_str(p) if p.dtype.is_string
                     else p.values.astype(str) for p in parts]
            joined = svals[0]
            for s in svals[1:]:
                joined = np.char.add(joined, s)
            uniq, inverse = np.unique(joined, return_inverse=True)
            nd = DictInfo.from_values(uniq.astype(object))
            nulls = None
            for p in parts:
                nulls = _or_nulls(nulls, p.nulls)
            return HCol(T.STRING, inverse.astype(np.int32), nulls, nd)
        raise HostUnsupported(f"string function {name}")


_HOST_STR_FUNCS = {"upper", "lower", "capitalize", "trim", "substr",
                   "substring", "length", "char_length", "character_length",
                   "concat", "left", "right"}


def _serve_by_name(stored: HBatch, want: T.Schema) -> Optional[HBatch]:
    """Project a memoized batch down to a narrower requested schema by column
    NAME; None when names are missing or ambiguous (duplicate names)."""
    names = [f.name for f in stored.schema.fields]
    idx = {}
    for i, nm in enumerate(names):
        if nm in idx:
            idx[nm] = None  # ambiguous
        else:
            idx[nm] = i
    cols = []
    for f in want.fields:
        i = idx.get(f.name)
        if i is None:
            return None
        c = stored.cols[i]
        if c.dtype != f.dtype:
            return None
        cols.append(c)
    return HBatch(want, cols, stored.n)


def _hash_str(sv: np.ndarray, seed: int = 0) -> np.ndarray:
    from igloo_tpu.exec.batch import hash64_bytes
    return hash64_bytes(np.asarray(sv, dtype=object), seed=seed) \
        .view(np.int64)


def _str_hash_lane(c: HCol, n: int, attr: str) -> np.ndarray:
    """Per-row 64-bit hash lane of a string column (gathered through the
    dictionary when present)."""
    if c.dict is not None:
        if not len(c.dict):
            return np.zeros(n, dtype=np.int64)
        h = getattr(c.dict, attr)
        return h[np.clip(c.values, 0, len(c.dict) - 1)].view(np.int64)
    return _hash_str(_materialize_str(c), seed=0 if attr == "hashes" else 1)


def _join_output(schema, lb: HBatch, rb: HBatch, lidx, ridx,
                 lnull, rnull) -> HBatch:
    """Concatenate gathered left+right columns; negative idx lanes (outer-join
    unmatched) become null."""
    cols = []
    for b_, idx, pad in ((lb, lidx, lnull), (rb, ridx, rnull)):
        safe = np.where(idx < 0, 0, idx)
        for c in b_.cols:
            vals = c.values[safe] if b_.n else np.zeros(
                len(idx), dtype=c.values.dtype)
            nulls = c.nulls[safe] if (c.nulls is not None and b_.n) else None
            if pad is not None and pad.any():
                nulls = (nulls.copy() if nulls is not None
                         else np.zeros(len(idx), dtype=bool))
                nulls[pad] = True
            cols.append(HCol(c.dtype, vals, nulls, c.dict))
    out_schema = schema
    if out_schema is None:
        out_schema = T.Schema(list(lb.schema.fields) + list(rb.schema.fields))
    return HBatch(out_schema, cols, len(lidx))


def _pa_for(dtype: T.DataType) -> pa.DataType:
    from igloo_tpu.exec.batch import dtype_to_arrow
    return dtype_to_arrow(dtype)


def to_arrow(b: HBatch) -> pa.Table:
    arrays, fields = [], []
    for f, c in zip(b.schema, b.cols):
        nulls = c.nulls
        if f.dtype.is_string:
            if c.dict is not None and len(c.dict):
                py = c.dict.values[np.clip(c.values, 0, len(c.dict) - 1)]
            else:
                py = np.full(b.n, "", dtype=object)
            if nulls is not None:
                py = py.copy()
                py[nulls] = None
            arrays.append(pa.array(py, type=pa.string()))
        elif f.dtype.id == T.TypeId.DATE32:
            a = pa.array(c.values.astype("int32"),
                         type=pa.int32()).cast(pa.date32())
            if nulls is not None:
                a = pa.compute.if_else(pa.array(~nulls), a,
                                       pa.scalar(None, type=pa.date32()))
            arrays.append(a)
        elif f.dtype.id == T.TypeId.TIMESTAMP:
            a = pa.array(c.values.astype("int64"),
                         type=pa.int64()).cast(pa.timestamp("us"))
            if nulls is not None:
                a = pa.compute.if_else(
                    pa.array(~nulls), a,
                    pa.scalar(None, type=pa.timestamp("us")))
            arrays.append(a)
        else:
            arrays.append(pa.array(c.values, mask=nulls))
        fields.append(pa.field(f.name, arrays[-1].type, f.nullable))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))
