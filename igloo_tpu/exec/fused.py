"""Whole-plan fusion: compile an entire logical plan into ONE jitted function.

The staged executor (exec/executor.py) dispatches one jit per plan node, and
most stages end in a device->host sync (live counts size the next stage), so
an 11-stage TPC-H Q3 stalls the device eleven times. This module realizes
SURVEY.md §7's design stance —
"each fragment lowers to ONE `jax.jit` computation" — end to end: the whole
query becomes a single XLA program: one dispatch, one small fetch.

The reference has no analog: its operators stream record batches through async
channels per node (crates/engine/src/physical_plan.rs:28-47), an architecture
that would serialize on the TPU's dispatch latency exactly like the staged path.

**Adaptive capacity hints.** Static shapes mean intermediate batches are padded
to their worst case (a filtered 6M-row lineitem keeps 8M lanes); carrying full
width through joins/aggregates/sorts costs ~0.1-1 s per 8M-lane gather/scatter.
Observed live counts from each run are recorded as per-node cardinality hints
(standard adaptive query execution, keyed by the node's structural fingerprint
— data changes change scan fingerprints and so invalidate hints naturally).
On later runs the program compacts intermediates down to the hinted power-of-two
capacity INSIDE the fused program; a deferred `n > capacity` flag triggers one
repair re-run with corrected hints, so results are always exact. Direct inner
joins go further: with a hint, build-side columns are gathered only AFTER the
probe-side compaction, at hinted width (lazy materialization).

The producer records the hint; the CONSUMER says whether adopting it pays,
because a compaction is itself lane-wide work (`K.compact_to`: a stable
(pred, u32) sort, then a gather per column). On one v5e at 2^26 lanes the sort
is 295 ms and each gathered column ~83 ms, while a whole TPC-H q1 (eight
float64 aggregates) at the full 2^26 lanes is 105-113 ms and q6's masked sum
there 9 ms (PERF.md, PRs 30 and 31). So an
aggregate without group expressions (one masked pass: `_global_aggregate`)
asks the Filter under it (`aggregate.uncompacted_filter`, which the staged
executor asks too) to keep its lanes: the filter still records its live count
but pushes no `acompact` fingerprint and raises no flag, so its program key is
the same before and after the hint exists and nothing is traced or compiled
twice (`fused.compact_declined` counts the hints left unadopted). Joins,
grouped aggregates, sorts, top-k, windows, distinct and limits read narrower
inputs cheaper by more than the compaction costs and keep it. A plan seen
for the first time whose unhinted candidates are wider than
`PROBE_CAPACITY` learns its counts from a probe (`FusedCompiler.probe`: the
counts alone, none of the rows) and compiles its hinted program at once,
instead of compiling and running a full-width program to throw away.

Correctness flags collected across the program (direct-join duplicate keys,
speculative join capacity overflow, compaction overflow) come back in the same
single fetch; only a raised flag or an oversized result costs extra round trips.

Raises FusionUnsupported for shapes that need host decisions (non-speculative
joins past the capacity budget, set ops, unions); the caller falls back to the
staged executor. A DISTINCT aggregate arrives split into two grouped
aggregates (plan/aggsplit.py) and fuses as they do.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Callable, Optional

import jax.numpy as jnp

from igloo_tpu import types as T
from igloo_tpu.exec import kernels as K
from igloo_tpu.exec.aggregate import (
    AggSpec, agg_out_bounds, aggregate_batch, distinct_batch,
    groups_in_place, minmax_order_arg, pair_sums_for, seg_dims_for,
    stage_scope, uncompacted_filter,
)
from igloo_tpu.exec.batch import (
    MIN_CAPACITY, DeviceBatch, DeviceColumn, round_capacity,
)
from igloo_tpu.exec.expr_compile import (
    ConstPool, Env, ExprCompiler, rank_lane,
)
from igloo_tpu.exec.join import (
    choose_direct_build, direct_bitmap_probe, direct_join_phase, direct_probe,
    expand_phase, make_key_hash_idxs, match_by_search, probe_phase,
)
from igloo_tpu.exec.sort_limit import (
    limit_batch, plan_topk, sort_batch, topk_batch,
)
from igloo_tpu.plan import expr as E
from igloo_tpu.plan import logical as L
from igloo_tpu.sql.ast import JoinType
from igloo_tpu.utils import tracing


class FusionUnsupported(Exception):
    """This plan needs host-side decisions between stages; use the staged path."""


@dataclass
class NodeMeta:
    """Host-side metadata mirror of a node's output batch: what expression
    compilation and join planning need, computed without running the device."""
    schema: T.Schema
    dicts: list
    bounds: list
    capacity: int


@dataclass
class Ctx:
    """Trace-time side channels: flag/stat ids are assigned at compile time,
    values filled during tracing (dict keys are static pytree aux, so the
    ordering of appends never matters)."""
    flags: dict = field(default_factory=dict)  # id -> device bool
    stats: dict = field(default_factory=dict)  # id -> device int64 live count


# NodeFn: (leaves, consts, ctx) -> DeviceBatch (jit-traceable)
NodeFn = Callable

# node outputs wider than this become adaptive-compaction candidates
ADAPTIVE_CAPACITY = 1 << 18
# only compact when the hinted capacity shrinks the batch at least this much
ADAPTIVE_SHRINK = 4
# a plan with an unhinted compaction candidate wider than this first runs a
# cardinality probe (FusedCompiler.probe) instead of its unhinted program:
# at TPC-H SF10 (2^26-lane lineitem) q3's unhinted program took 157 s to
# compile and 78 s to run on a v5e, only to learn the live counts its hinted
# successor is compiled from. Above a candidate this wide a grouping node
# records its count and never compacts by it: learning the count takes the
# hinted program's first run, and adopting it a third program of the plan
# (106 s to compile for q3 at SF10) for a narrower input to the top-k above.
# At SF1 (2^23) neither applies.
PROBE_CAPACITY = 1 << 24


class FusedCompiler:
    """One-shot compiler: plan -> (run, leaves, pool, cache_key, out_meta)."""

    # results at or under this capacity come back in the single fetch
    FETCH_CAPACITY = 1 << 12

    def __init__(self, executor):
        self.ex = executor
        self.pool = ConstPool()
        self.leaves: list[DeviceBatch] = []
        self.marks: list = []
        self.fps: list = []
        # hint-INDEPENDENT fingerprints: same node entries as fps but without
        # adopted-hint artifacts (acompact markers, lazy-join want sizes).
        # Hint keys derive from these, so adopting one node's hint never
        # changes another node's key — all hints adopt in ONE re-run instead
        # of cascading one plan level per run.
        self.hfps: list = []
        self.flag_tags: list = []   # flag id -> ("dup"|"overflow"|"compact", key)
        self.stat_keys: list = []   # stat id -> nhint cache key
        # Filter nodes whose consumer asked them not to compact
        # (aggregate.uncompacted_filter): hints recorded, never adopted
        self.uncompacted: list = []
        # stat ids of compaction candidates wider than PROBE_CAPACITY that
        # have no hint yet, and of grouping nodes (left out of the probe);
        # `wide`: a candidate wider than PROBE_CAPACITY was compiled
        self.unhinted_wide: list = []
        self.grouping_stats: set = set()
        self.wide = False

    # --- side-channel ids -------------------------------------------------

    def _push(self, fp, hint_fp="same") -> None:
        """Append a node fingerprint; hint_fp=None skips the hint list,
        any other value replaces the entry there."""
        self.fps.append(fp)
        if hint_fp == "same":
            self.hfps.append(fp)
        elif hint_fp is not None:
            self.hfps.append(hint_fp)

    def _new_flag(self, tag) -> int:
        self.flag_tags.append(tag)
        return len(self.flag_tags) - 1

    def _new_stat(self, key) -> int:
        self.stat_keys.append(key)
        return len(self.stat_keys) - 1

    def _wide_candidate(self, sid: int, hint) -> None:
        """A compaction candidate wider than PROBE_CAPACITY was compiled
        (stat `sid`; `hint` None where it has none yet)."""
        self.wide = True
        if hint is None:
            self.unhinted_wide.append(sid)

    def _hint(self, key) -> Optional[int]:
        v = self.ex._cache.get(("nhint", key))
        if v is None and self.ex._hints is not None:
            v = self.ex._hints.get(key)  # persistent store (fresh process)
            if v is not None:
                self.ex._cache[("nhint", key)] = v
        return int(v) if v is not None else None

    # --- public -----------------------------------------------------------

    def compile(self, plan: L.LogicalPlan):
        fn, meta = self._c(plan)
        # program-shape telemetry: how many plan nodes one dispatch covers
        # (the whole point of fusion) — system.metrics hist_max shows the
        # largest program this process compiled
        tracing.histogram("fused.nodes", len(self.fps))
        fetch_cap = self.FETCH_CAPACITY

        def run(leaves, consts):
            ctx = Ctx()
            out = fn(leaves, consts, ctx)
            n = jnp.sum(out.live.astype(jnp.int64))
            if out.capacity > fetch_cap:
                spec = K.compact_to(out, fetch_cap)
            else:
                spec = out
            return out, spec, n, ctx.flags, ctx.stats

        key = ("fused", tuple(self.fps), self.pool.signature(),
               tuple(self.marks), fetch_cap)
        self._fn = fn
        return run, key, meta

    def probe(self):
        """The compiled plan's cardinality probe, or None when it has no
        unhinted compaction candidate wider than PROBE_CAPACITY: a program
        that returns the live counts the plan's run records ({stat id:
        count}) and nothing else, so XLA drops every gather a count does not
        read and the sorts above the filters and joins. The counts of
        grouping nodes (aggregate, distinct) are left out — they would keep
        the grouping's sort — and are recorded by the hinted program's first
        run, never adopted (PROBE_CAPACITY). Call after compile()."""
        if not self.unhinted_wide:
            return None
        fn, grouping = self._fn, frozenset(self.grouping_stats)

        def run(leaves, consts):
            ctx = Ctx()
            fn(leaves, consts, ctx)
            return {sid: n for sid, n in ctx.stats.items()
                    if sid not in grouping}
        return run

    # --- dispatch ---------------------------------------------------------

    _ADAPTIVE_NODES = ("filter", "join", "aggregate", "distinct")

    def _c(self, plan: L.LogicalPlan):
        name = type(plan).__name__.lower()
        m = getattr(self, "_c_" + name, None)
        if m is None:
            raise FusionUnsupported(type(plan).__name__)
        fn, meta = m(plan)
        if meta.schema is not plan.schema and meta.schema != plan.schema:
            meta = NodeMeta(plan.schema, meta.dicts, meta.bounds, meta.capacity)

            def renamed(leaves, consts, ctx, _fn=fn, _s=plan.schema):
                b = _fn(leaves, consts, ctx)
                return DeviceBatch(_s, b.columns, b.live)
            fn = renamed
        if name in self._ADAPTIVE_NODES and meta.capacity > ADAPTIVE_CAPACITY:
            fn, meta = self._adaptive(
                fn, meta, name,
                compact=not any(plan is f for f in self.uncompacted))
        return fn, meta

    def _adaptive(self, fn: NodeFn, meta: NodeMeta, kind: str,
                  compact: bool = True):
        """Record this node's live count as a cardinality hint; when a prior
        run's hint shows a strong shrink, compact to the hinted capacity inside
        the program, flagging overflow (exact repair re-run with fresh hints).

        `compact=False` is the consumer declining (module docstring: a global
        aggregate reads the rows where they lie, and at 2^26 lanes the
        compaction is 295 ms + ~83 ms a column in front of a pass that costs
        9 ms, where a whole q1 is 105-113 ms). The count is still recorded,
        so the hint and what AdaptiveStats learns do not change; the
        fingerprints and flags are those of a node that has no hint yet."""
        hkey = (kind, tuple(self.hfps))
        sid = self._new_stat(hkey)
        hint = self._hint(hkey)
        if kind in ("aggregate", "distinct"):
            self.grouping_stats.add(sid)
            if self.wide:           # see PROBE_CAPACITY
                hint = None
        elif compact and meta.capacity > PROBE_CAPACITY:
            self._wide_candidate(sid, hint)
        want = round_capacity(max(hint, 1)) if hint is not None else None
        shrinks = want is not None \
            and want * ADAPTIVE_SHRINK <= meta.capacity
        if shrinks and not compact:
            tracing.counter("fused.compact_declined")
        elif shrinks:
            fid = self._new_flag(("compact", hkey))
            self._push(("acompact", want), hint_fp=None)

            def cfn(leaves, consts, ctx):
                out = fn(leaves, consts, ctx)
                n = jnp.sum(out.live.astype(jnp.int64))
                ctx.stats[sid] = n
                ctx.flags[fid] = n > want
                return K.compact_to(out, want)
            return cfn, NodeMeta(meta.schema, meta.dicts, meta.bounds, want)

        def sfn(leaves, consts, ctx):
            out = fn(leaves, consts, ctx)
            ctx.stats[sid] = jnp.sum(out.live.astype(jnp.int64))
            return out
        return sfn, meta

    def _compiler_for(self, meta: NodeMeta) -> ExprCompiler:
        return ExprCompiler(meta.dicts, self.pool, bounds=meta.bounds)

    def _compile_exprs(self, exprs, comp: ExprCompiler):
        """Resolve scalar subqueries (recursively executing them NOW, host
        side), then compile. Returns (resolved, compiled)."""
        resolved = [self.ex._resolve_subqueries(e) for e in exprs]
        out = [comp.compile(e) for e in resolved]
        return resolved, out

    # --- leaves -----------------------------------------------------------

    def _c_scan(self, plan: L.Scan):
        batch = self.ex._exec_scan(plan)
        idx = len(self.leaves)
        self.leaves.append(batch)
        meta = NodeMeta(plan.schema, [c.dictionary for c in batch.columns],
                        [c.bounds for c in batch.columns], batch.capacity)
        # NOTE: deliberately content-light — dictionary content feeds compiled
        # code through ConstPool args (pool.signature() keys sizes). Bounds
        # join the key only in CANONICAL form (quantized grid, see
        # exec/capacity.py): every bounds-derived static decision that shapes
        # the program (direct-join base/size, seg_dims offsets, pack radices)
        # is pushed into the key by its own node, so coarsening here is sound
        # and lets near scale factors share one fused program.
        from igloo_tpu.exec.capacity import canonical_direct_table
        # a provider that lives for ONE execution (a fragment's dependency
        # result: its table name is a per-query id) says so; its scan is
        # keyed by its position among the program's leaves, so the program
        # and its hints are found again by the next query. Its data never
        # enters here: the leaf is an argument, and the BatchCache keeps the
        # real name.
        ident = idx if getattr(plan.provider, "ephemeral", False) \
            else plan.table
        self._push(("scan", ident, tuple(plan.projection or ()),
                    E.shape(plan.pushed_filters), plan.partition,
                    plan.schema, batch.capacity,
                    tuple(c.nulls is not None for c in batch.columns),
                    tuple(canonical_direct_table(b[0], b[1])
                          if b is not None else None
                          for b in meta.bounds),
                    # carrier form shapes the traced program (widen ops +
                    # carrier dtypes): wide vs int8-offset vs scaled columns
                    # must key distinct fused executables
                    tuple((str(c.values.dtype), c.carrier.key())
                          if c.carrier is not None else None
                          for c in batch.columns)))

        def fn(leaves, consts, ctx, _i=idx):
            return leaves[_i]
        return fn, meta

    # --- row-wise ---------------------------------------------------------

    def _c_filter(self, plan: L.Filter):
        cfn, meta = self._c(plan.input)
        comp = self._compiler_for(meta)
        res, [c] = self._compile_exprs([plan.predicate], comp)
        self.marks.extend(comp.marks)
        self._push(("filter", E.shape(res[0])))

        def fn(leaves, consts, ctx):
            b = cfn(leaves, consts, ctx)
            env = Env.from_batch(b, consts)
            v, nl = c.fn(env)
            keep = b.live & v
            if nl is not None:
                keep = keep & ~nl
            return DeviceBatch(b.schema, b.columns, keep)
        return fn, meta

    def _c_project(self, plan: L.Project):
        cfn, meta = self._c(plan.input)
        comp = self._compiler_for(meta)
        res, comps = self._compile_exprs(plan.exprs, comp)
        self.marks.extend(comp.marks)
        self._push(("project", E.shape(res), plan.schema))
        out_schema = plan.schema

        def fn(leaves, consts, ctx):
            b = cfn(leaves, consts, ctx)
            env = Env.from_batch(b, consts)
            cols = []
            for cc, f in zip(comps, out_schema.fields):
                v, nl = cc.fn(env)
                want = f.dtype.device_dtype()
                if v.dtype != want:
                    v = v.astype(want)
                cols.append(DeviceColumn(f.dtype, v, nl, None))
            return DeviceBatch(out_schema, cols, b.live)
        out_meta = NodeMeta(out_schema, [cc.out_dict for cc in comps],
                            [cc.out_bounds for cc in comps], meta.capacity)
        return fn, out_meta

    # --- joins ------------------------------------------------------------

    def _c_join(self, plan: L.Join):
        lfn, lmeta = self._c(plan.left)
        rfn, rmeta = self._c(plan.right)
        jt = plan.join_type
        compL = self._compiler_for(lmeta)
        lres, lk = self._compile_exprs(plan.left_keys, compL)
        compR = self._compiler_for(rmeta)
        rres, rk = self._compile_exprs(plan.right_keys, compR)
        self.marks.extend(compL.marks)
        self.marks.extend(compR.marks)
        use_lk, use_rk = ([], []) if jt is JoinType.CROSS else (lk, rk)
        residual = None
        rres2 = []
        if plan.residual is not None:
            compB = ExprCompiler(lmeta.dicts + rmeta.dicts, self.pool,
                                 bounds=lmeta.bounds + rmeta.bounds)
            r = self.ex._resolve_subqueries(plan.residual)
            rres2 = [r]
            residual = compB.compile(r)
            self.marks.extend(compB.marks)

        if jt in (JoinType.SEMI, JoinType.ANTI):
            out_dicts = list(lmeta.dicts)
            out_bounds = list(lmeta.bounds)
        else:
            out_dicts = list(lmeta.dicts) + list(rmeta.dicts)
            out_bounds = list(lmeta.bounds) + list(rmeta.bounds)
        out_dicts = out_dicts[: len(plan.schema)]
        out_bounds = out_bounds[: len(plan.schema)]

        # jfp_core is capacity-free: hint keys derive from it so that child
        # hint adoption (which shrinks child capacities) never changes this
        # join's hint key. The full jfp (with caps) keys programs/negatives.
        jfp_core = ("join", E.shape(lres), E.shape(rres), E.shape(rres2), jt)
        jfp = jfp_core + (lmeta.capacity, rmeta.capacity)

        pick = None
        if use_lk:
            banned = frozenset(
                s for s in ("left", "right")
                if self.ex._cache.get(("nodirect", jfp_core, s)))
            pick = choose_direct_build(use_lk, use_rk, lmeta.capacity,
                                       rmeta.capacity, jt, banned=banned)
        if pick is not None:
            return self._c_join_direct(plan, jfp, jfp_core, pick, lfn, lmeta,
                                       rfn, rmeta, use_lk, use_rk, residual,
                                       out_dicts, out_bounds)

        # speculative sorted-probe join: static match capacity, deferred
        # overflow flag. Past the budget a host sync would be required.
        spec_cap = round_capacity(max(lmeta.capacity, rmeta.capacity))
        if jt is JoinType.CROSS or spec_cap > self.ex._SPECULATIVE_JOIN_BUDGET:
            raise FusionUnsupported("join needs a host capacity sync")
        lhx = make_key_hash_idxs(use_lk, self.pool)
        rhx = make_key_hash_idxs(use_rk, self.pool)
        if jt in (JoinType.SEMI, JoinType.ANTI):
            out_cap = lmeta.capacity
        else:
            out_cap = spec_cap
            if jt in (JoinType.LEFT, JoinType.FULL):
                out_cap += lmeta.capacity
            if jt in (JoinType.RIGHT, JoinType.FULL):
                out_cap += rmeta.capacity
        self._push(("join_sorted",) + jfp[1:] + (spec_cap, plan.schema),
                   hint_fp=("join_sorted",) + jfp_core[1:] + (plan.schema,))
        fid = self._new_flag(("overflow", jfp))
        search = match_by_search()

        def fn(leaves, consts, ctx):
            lb = lfn(leaves, consts, ctx)
            rb = rfn(leaves, consts, ctx)
            p = probe_phase(lb, rb, use_lk, use_rk, lhx, rhx, consts)
            ctx.flags[fid] = p.total > spec_cap
            return expand_phase(lb, rb, p, spec_cap, jt, residual,
                                plan.schema, consts, match_search=search)
        return fn, NodeMeta(plan.schema, out_dicts, out_bounds, out_cap)

    def _c_join_direct(self, plan, jfp, jfp_core, pick, lfn, lmeta, rfn,
                       rmeta, use_lk, use_rk, residual, out_dicts, out_bounds):
        jt = plan.join_type
        # canonical positional table (see choose_direct_build): blo/tsize are
        # family-quantized shape-class constants, safe in the fused cache key
        side, (blo, tsize), ki = pick
        swapped = side == "left"
        pks = use_rk if swapped else use_lk
        bks = use_lk if swapped else use_rk
        pkey, bkey = pks[ki], bks[ki]
        extra = [(pks[i], bks[i]) for i in range(len(pks)) if i != ki]
        probe_cap = rmeta.capacity if swapped else lmeta.capacity
        probe_is_left = not swapped
        fid = self._new_flag(("dup", (jfp_core, side)))

        # lazy inner join under a cardinality hint: run the probe at full
        # width, compact (probe cols + match index) down to the hinted
        # capacity, and only then gather build-side columns — narrow-width
        # materialization instead of N full-width gathers
        hkey = ("joinout", jfp_core, tuple(self.hfps))
        hint = self._hint(hkey) if jt is JoinType.INNER else None
        if hint is None and jt is JoinType.INNER:
            # fall back to the STAGED path's observed live count for this
            # same join (same jfp_core + capacities): plans that start life
            # on the staged executor (fusion rejected while capacities were
            # unhinted) seed the fused lazy join on their first fused
            # compile instead of needing one more adoption round
            hint = self.ex._staged_hint(("sjoin_live", jfp_core))
        want = round_capacity(max(hint, 1)) if hint is not None else None
        if want is not None and want * ADAPTIVE_SHRINK <= probe_cap:
            sid = self._new_stat(hkey)
            if probe_cap > PROBE_CAPACITY:
                self._wide_candidate(sid, hint)
            ofid = self._new_flag(("compact", hkey))
            self._push(("join_lazy",) + jfp[1:] +
                       (side, blo, tsize, ki, want, plan.schema),
                       hint_fp=("join_direct",) + jfp_core[1:] +
                       (plan.schema,))
            # the full-width pass needs only whether a row matches: with
            # one key and no residual it reads the table's occupancy bits
            # (direct_bitmap_probe), and the row ids only after the
            # compaction, at the hinted width
            bitmap = not extra and residual is None
            if bitmap:
                tracing.counter("join.bitmap_probes")

            def fn(leaves, consts, ctx):
                lb = lfn(leaves, consts, ctx)
                rb = rfn(leaves, consts, ctx)
                pb, bb = (rb, lb) if swapped else (lb, rb)
                if bitmap:
                    ok, table, pslot, dup = direct_bitmap_probe(
                        pb, bb, pkey, bkey, blo, tsize, consts)
                else:
                    ok, bidx, dup = direct_probe(pb, bb, pkey, bkey, blo,
                                                 tsize, swapped, residual,
                                                 consts, extra)
                ctx.flags[fid] = dup
                n = jnp.sum(ok.astype(jnp.int64))
                ctx.stats[sid] = n
                ctx.flags[ofid] = n > want
                perm = K.compact_perm(ok)[:want]
                live = jnp.take(ok, perm)
                p_cols = [replace(c.map_rows(lambda a: jnp.take(a, perm)),
                                  dictionary=None) for c in pb.columns]
                if bitmap:
                    bidx = jnp.take(table, jnp.take(pslot, perm))
                else:
                    bidx = jnp.take(bidx, perm)
                nbidx = jnp.clip(bidx, 0, bb.capacity - 1)
                b_cols = K.gather_batch(bb, nbidx)
                l_cols, r_cols = (b_cols, p_cols) if swapped \
                    else (p_cols, b_cols)
                return DeviceBatch(plan.schema, l_cols + r_cols, live)
            return fn, NodeMeta(plan.schema, out_dicts, out_bounds, want)

        if jt is JoinType.INNER:
            sid = self._new_stat(hkey)
            if probe_cap > PROBE_CAPACITY:
                self._wide_candidate(sid, hint)
        else:
            sid = None
        if jt in (JoinType.SEMI, JoinType.ANTI):
            out_cap = lmeta.capacity
        else:
            build_cap = lmeta.capacity if swapped else rmeta.capacity
            build_preserved = (
                jt is JoinType.FULL
                or (jt is JoinType.LEFT and not probe_is_left)
                or (jt is JoinType.RIGHT and probe_is_left))
            out_cap = probe_cap + (build_cap if build_preserved else 0)
        self._push(("join_direct",) + jfp[1:] +
                   (side, blo, tsize, ki, plan.schema),
                   hint_fp=("join_direct",) + jfp_core[1:] + (plan.schema,))

        def fn(leaves, consts, ctx):
            lb = lfn(leaves, consts, ctx)
            rb = rfn(leaves, consts, ctx)
            pb, bb = (rb, lb) if swapped else (lb, rb)
            out, dup = direct_join_phase(pb, bb, pkey, bkey, blo, tsize,
                                         swapped, jt, residual,
                                         plan.schema, consts,
                                         extra_keys=extra)
            ctx.flags[fid] = dup
            if sid is not None:
                ctx.stats[sid] = jnp.sum(out.live.astype(jnp.int64))
            return out
        return fn, NodeMeta(plan.schema, out_dicts, out_bounds, out_cap)

    # --- aggregates -------------------------------------------------------

    def _c_aggregate(self, plan: L.Aggregate):
        keep = uncompacted_filter(plan)
        if keep is not None:
            self.uncompacted.append(keep)
        cfn, meta = self._c(plan.input)
        comp = self._compiler_for(meta)
        gres, groups = self._compile_exprs(plan.group_exprs, comp)
        specs = []
        ares = []
        for a in plan.aggs:
            if a.arg is not None:
                [r], [arg] = self._compile_exprs([a.arg], comp)
                ares.append(r)
            else:
                arg = None
            out_dict = arg.out_dict if (arg is not None and a.dtype.is_string) \
                else None
            specs.append(AggSpec(a.func, arg, a.dtype, out_dict,
                                 order_arg=minmax_order_arg(a.func, arg, comp)))
        self.marks.extend(comp.marks)
        from igloo_tpu.plan.expr import AggFunc as _AF
        n_scatters = sum(
            2 if a.func is _AF.AVG else 1 for a in plan.aggs)
        seg_dims = seg_dims_for(groups, n_aggs=n_scatters,
                                input_capacity=meta.capacity)
        # packed-key single-sort path when the scatter path doesn't apply;
        # a host decision (bounds / dictionary sizes) -> part of the fused key
        pack_spec = None
        if seg_dims is None and groups:
            pack_spec = K.plan_group_packing(groups, self.pool)
            if pack_spec is not None:
                tracing.counter("pack.agg")
        pair_sums = pair_sums_for(seg_dims, specs)
        if groups_in_place(seg_dims):
            tracing.counter("agg.groups_in_place")
        if plan.distinct_stage == 1:
            tracing.counter("agg.distinct")
        fp = ("agg", E.shape(gres + ares),
              tuple((a.func, a.dtype) for a in plan.aggs),
              plan.schema, seg_dims, pack_spec) + \
            (("pair_sums",) if pair_sums else ())  # keys without it stay put
        self._push(fp)
        out_schema = plan.schema

        def fn(leaves, consts, ctx):
            b = cfn(leaves, consts, ctx)
            with stage_scope(plan):
                return aggregate_batch(b, groups, specs, out_schema, consts,
                                       seg_dims=seg_dims, pack_spec=pack_spec,
                                       pair_sums=pair_sums)
        if not groups:
            cap = MIN_CAPACITY
        elif seg_dims is not None:
            prod = 1
            for d, _off in seg_dims:
                prod *= d
            cap = round_capacity(prod + 1)
        else:
            cap = meta.capacity
        out_meta = NodeMeta(out_schema,
                            [g.out_dict for g in groups] +
                            [s.out_dict for s in specs],
                            [g.out_bounds for g in groups] +
                            agg_out_bounds(plan.aggs, meta.capacity), cap)
        return fn, out_meta

    def _c_distinct(self, plan: L.Distinct):
        cfn, meta = self._c(plan.input)
        self._push(("distinct",))

        def fn(leaves, consts, ctx):
            return distinct_batch(cfn(leaves, consts, ctx))
        return fn, meta

    def _c_window(self, plan: L.Window):
        from igloo_tpu.exec.window import compile_window, window_batch
        cfn, meta = self._c(plan.input)
        comp = self._compiler_for(meta)
        wfp, pk, okeys, specs, wdicts, wbounds = compile_window(
            plan, comp, self.ex._resolve_subqueries)
        self.marks.extend(comp.marks)
        self._push(("window", wfp, plan.schema))
        asc, nf = list(plan.ascending), list(plan.nulls_first)
        out_schema = plan.schema

        def fn(leaves, consts, ctx):
            return window_batch(cfn(leaves, consts, ctx), pk, okeys, asc, nf,
                                specs, out_schema, consts)
        return fn, NodeMeta(out_schema, list(meta.dicts) + wdicts,
                            list(meta.bounds) + wbounds, meta.capacity)

    # --- ordering ---------------------------------------------------------

    def _c_sort(self, plan: L.Sort):
        cfn, meta = self._c(plan.input)
        comp = self._compiler_for(meta)
        res, keys = self._compile_exprs(plan.keys, comp)
        keys = [rank_lane(k, comp) if k.dtype.is_string else k for k in keys]
        self.marks.extend(comp.marks)
        # pack the longest integer-family key prefix into one sort lane
        pack = K.plan_prefix_packing(keys, plan.ascending, plan.nulls_first,
                                     self.pool)
        if pack is not None:
            tracing.counter("pack.sort")
        self._push(("sort", E.shape(res),
                    tuple(plan.ascending), tuple(plan.nulls_first), pack))
        asc, nf = list(plan.ascending), list(plan.nulls_first)

        def fn(leaves, consts, ctx):
            return sort_batch(cfn(leaves, consts, ctx), keys, asc, nf, consts,
                              pack=pack)
        return fn, meta

    def _c_limit(self, plan: L.Limit):
        if isinstance(plan.input, L.Sort) and plan.limit is not None:
            return self._c_limit_sort(plan, plan.input)
        cfn, meta = self._c(plan.input)
        self._push_limit(plan)

        def fn(leaves, consts, ctx):
            return limit_batch(cfn(leaves, consts, ctx), plan.limit,
                               plan.offset)
        return fn, meta

    def _push_limit(self, plan: L.Limit) -> None:
        # LIMIT's bounds stay in the key: they mask by position, and a
        # top-k's k sizes its output
        tracing.counter("program.literal_keyed")
        self._push(("limit", plan.limit, plan.offset))

    def _c_limit_sort(self, plan: L.Limit, sp: L.Sort):
        """ORDER BY + LIMIT fusion: where sort_limit.plan_topk says so, a
        partial top-k replaces the full argsort.
        The decline path pushes fingerprints BYTE-IDENTICAL to the unfused
        sort + limit pair, so program keys and hint keys never move when the
        plan says no."""
        cfn, meta = self._c(sp.input)
        comp = self._compiler_for(meta)
        res, keys = self._compile_exprs(sp.keys, comp)
        keys = [rank_lane(k, comp) if k.dtype.is_string else k for k in keys]
        self.marks.extend(comp.marks)
        pack = K.plan_prefix_packing(keys, sp.ascending, sp.nulls_first,
                                     self.pool)
        if pack is not None:
            tracing.counter("pack.sort")
        asc, nf = list(sp.ascending), list(sp.nulls_first)
        k_total = plan.limit + plan.offset
        if not plan_topk(meta.capacity, k_total, pack, len(keys)):
            self._push(("sort", E.shape(res),
                        tuple(sp.ascending), tuple(sp.nulls_first), pack))
            self._push_limit(plan)

            def fn(leaves, consts, ctx):
                b = sort_batch(cfn(leaves, consts, ctx), keys, asc, nf,
                               consts, pack=pack)
                return limit_batch(b, plan.limit, plan.offset)
            return fn, meta
        out_cap = round_capacity(k_total)
        tracing.counter("program.literal_keyed")  # k sizes the output
        self._push(("topk", E.shape(res),
                    tuple(sp.ascending), tuple(sp.nulls_first), pack,
                    plan.limit, plan.offset, out_cap))

        def fn(leaves, consts, ctx):
            return topk_batch(cfn(leaves, consts, ctx), keys, consts, pack,
                              plan.limit, plan.offset, out_cap)
        return fn, NodeMeta(meta.schema, meta.dicts, meta.bounds, out_cap)
