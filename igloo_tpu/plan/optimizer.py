"""Logical optimizer.

The reference leans on DataFusion's optimizer on its working path
(`into_optimized_plan`, crates/igloo/src/main.rs:109) and does nothing on its custom
path. We implement the passes that matter for the TPU execution model:

- constant folding (shrinks jit graphs, enables literal-only pruning)
- filter merge + predicate pushdown (through Project/Aggregate/Join/Union down to
  Scan.pushed_filters, so connectors can prune files/row-groups host-side before
  bytes ever move toward HBM)
- projection pruning (Scan.projection — decode only needed Parquet columns; on
  device this is the difference between shipping 16 lanes and 4)
- DISTINCT aggregates split into two grouped aggregates (plan/aggsplit.py), so
  every tier below runs them as plain ones

All passes preserve bound-ness: Column.index stays consistent with each node's
input schema (pruning rewrites indices via child mappings).
"""
from __future__ import annotations

import copy
from typing import Optional

from igloo_tpu import types as T
from igloo_tpu.plan import expr as E
from igloo_tpu.plan import logical as L
from igloo_tpu.plan.aggsplit import split_distinct
from igloo_tpu.plan.binder import (
    _and_all, _extract_equi_key, _split_conjuncts, coerce_key_pair,
)
from igloo_tpu.sql.ast import JoinType


def optimize(plan: L.LogicalPlan) -> L.LogicalPlan:
    _optimize_subqueries(plan)
    plan = fold_constants_pass(plan)
    plan = reorder_cross_joins(plan)
    plan = pushdown_filters(plan)
    plan = semi_join_reduction(plan)
    plan = reorder_adaptive_joins(plan)
    plan = prune_projections(plan)
    return split_distinct_aggregates(plan)


def split_distinct_aggregates(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Every aggregate with DISTINCT arguments as the two grouped aggregates
    of plan/aggsplit.py split_distinct, so that no tier below the optimizer
    meets a DISTINCT aggregate. Last: the split keeps the aggregate's output
    schema, and the passes above have pruned and pushed into its input."""
    for name in ("input", "left", "right"):
        child = getattr(plan, name, None)
        if isinstance(child, L.LogicalPlan):
            setattr(plan, name, split_distinct_aggregates(child))
    if isinstance(plan, L.Union):
        plan.inputs = [split_distinct_aggregates(c) for c in plan.inputs]
    if isinstance(plan, L.Aggregate) and any(a.distinct for a in plan.aggs):
        return split_distinct(plan)
    return plan


# --- magic-set / semi-join reduction ---------------------------------------

# a key-source subtree must scan at most this much to be cloned as the
# semi-join build side; the aggregate input must scan at least this much for
# the rewrite to pay off
_SEMI_BUILD_MAX_BYTES = 64 << 20
_SEMI_INPUT_MIN_BYTES = 64 << 20


def _est_scan_bytes(p: L.LogicalPlan) -> Optional[int]:
    """Total estimated source bytes under `p`; None when any scan is
    unsized."""
    from igloo_tpu.exec.chunked import estimated_bytes
    total = 0
    for n in L.walk_plan(p):
        if isinstance(n, L.Scan):
            if n.provider is None:
                continue
            nb = estimated_bytes(n.provider)
            if nb is None:
                return None
            total += nb
    return total


def _key_source(p: L.LogicalPlan, idx: int):
    """Trace output column `idx` of `p` to an UNDER-filtered source subtree:
    the subtree's values for that column are a SUPERSET of the values `p` can
    produce (filters/joins above only drop rows), which is exactly what a
    semi-join build side needs. Returns (subtree, col idx) or (None, 0)."""
    if isinstance(p, L.Filter):
        sub, si = _key_source(p.input, idx)
        if sub is p.input and si == idx:
            return p, idx  # nothing was cut below: keep the filter (tighter)
        return sub, si
    if isinstance(p, L.Project):
        e = p.exprs[idx]
        if isinstance(e, E.Alias):
            e = e.operand
        if not isinstance(e, E.Column):
            return None, 0
        sub, si = _key_source(p.input, e.index)
        if sub is p.input and si == e.index:
            return p, idx  # keep the projection node (schema stays aligned)
        return sub, si
    if isinstance(p, L.Join):
        lw = len(p.left.schema)
        if idx < lw and p.join_type in (JoinType.INNER, JoinType.CROSS,
                                        JoinType.LEFT, JoinType.SEMI,
                                        JoinType.ANTI):
            return _key_source(p.left, idx)
        if idx >= lw and p.join_type in (JoinType.INNER, JoinType.CROSS,
                                        JoinType.LEFT):
            # right side of a LEFT join adds NULL padding only; null keys
            # never equi-match, so the unpadded source is still a superset
            # of the matchable values
            return _key_source(p.right, idx - lw)
        return None, 0
    return p, idx


def semi_join_reduction(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Magic-set rewrite: Join(OUTER-SIDE, Aggregate-by-k) where the join key
    on the outer side traces to a SMALL subtree -> filter the aggregate's
    input with a semi join against that subtree's distinct keys.

    TPC-H q17 is the canonical case: the decorrelated per-part average
    aggregates ALL 6M lineitem rows into 200k groups, but the outer query
    joins the result against ~200 filtered parts — aggregating the other
    199,800 groups is pure waste (and on the static-shape device path, the
    full-width aggregate dominates the query). The reference has no analog
    (DataFusion's optimizer lacks magic sets too); the rewrite matters here
    because TPU aggregation cost scales with padded input lanes.

    Correctness: the semi join drops whole groups whose key is outside the
    source's key SUPERSET — groups that could never equi-match the outer
    side (null group keys included: null never equi-matches). Rows within
    retained groups are untouched, so aggregate values are identical."""
    for name in ("input", "left", "right"):
        ch = getattr(plan, name, None)
        if isinstance(ch, L.LogicalPlan):
            setattr(plan, name, semi_join_reduction(ch))
    if isinstance(plan, L.Union):
        plan.inputs = [semi_join_reduction(c) for c in plan.inputs]
    if not (isinstance(plan, L.Join) and
            plan.join_type in (JoinType.INNER, JoinType.LEFT, JoinType.SEMI)
            and len(plan.left_keys) == 1 and
            isinstance(plan.left_keys[0], E.Column) and
            isinstance(plan.right_keys[0], E.Column)):
        return plan
    # locate an Aggregate under identity projections on the right, with the
    # join key landing on one of its GROUP columns
    node, idx = plan.right, plan.right_keys[0].index
    while isinstance(node, L.Project):
        e = node.exprs[idx]
        if isinstance(e, E.Alias):
            e = e.operand
        if not isinstance(e, E.Column):
            return plan
        node, idx = node.input, e.index
    if not isinstance(node, L.Aggregate) or idx >= len(node.group_exprs):
        return plan
    if isinstance(node.input, L.Join) and \
            node.input.join_type is JoinType.SEMI:
        return plan  # already reduced
    in_bytes = _est_scan_bytes(node.input)
    if in_bytes is None or in_bytes < _SEMI_INPUT_MIN_BYTES:
        return plan
    src, src_idx = _key_source(plan.left, plan.left_keys[0].index)
    if src is None:
        return plan
    # the source must be SELECTIVE: an unfiltered base table as the build
    # side filters nothing (FK integrity makes every group survive) and its
    # distinct-keys subplan is pure cost — e.g. q18's o_orderkey IN (...)
    # traces to the bare orders scan and must NOT rewrite
    if not any(isinstance(n, L.Filter) or
               (isinstance(n, L.Scan) and n.pushed_filters)
               for n in L.walk_plan(src)):
        return plan
    sb = _est_scan_bytes(src)
    if sb is None or sb > _SEMI_BUILD_MAX_BYTES:
        return plan
    gk = node.group_exprs[idx]
    f = src.schema.fields[src_idx]
    if gk.dtype != f.dtype:
        return plan
    col = E.Column(f.name, index=src_idx)
    col.dtype = f.dtype
    proj = L.Project(input=L.copy_plan(src), exprs=[col], names=[f.name])
    proj.schema = T.Schema([f])
    dist = L.Distinct(input=proj)
    dist.schema = proj.schema
    bcol = E.Column(f.name, index=0)
    bcol.dtype = f.dtype
    semi = L.Join(left=node.input, right=dist, join_type=JoinType.SEMI,
                  left_keys=[copy.deepcopy(gk)], right_keys=[bcol])
    semi.schema = node.input.schema
    node.input = semi
    return plan


def _node_exprs(node: L.LogicalPlan) -> list:
    if isinstance(node, L.Filter):
        return [node.predicate]
    if isinstance(node, L.Project):
        return list(node.exprs)
    if isinstance(node, L.Aggregate):
        return list(node.group_exprs) + [a.arg for a in node.aggs
                                         if a.arg is not None]
    if isinstance(node, L.Join):
        out = list(node.left_keys) + list(node.right_keys)
        if node.residual is not None:
            out.append(node.residual)
        return out
    if isinstance(node, L.Sort):
        return list(node.keys)
    if isinstance(node, L.Window):
        return (list(node.partition_exprs) + list(node.order_exprs)
                + list(node.funcs))
    if isinstance(node, L.Scan):
        return list(node.pushed_filters)
    return []


def _optimize_subqueries(plan: L.LogicalPlan) -> None:
    """Run the FULL pass pipeline over every bound scalar-subquery plan.
    Without this, subquery joins stay in their raw bound shape — Filters over
    CROSS joins — which the executor expands as a full cross product (TPC-H
    Q11's HAVING subquery: |partsupp| x |supplier| = 8e9 candidate slots at
    SF1). Recursion through optimize() also covers nested subqueries."""
    for node in L.walk_plan(plan):
        for e in _node_exprs(node):
            for n in E.walk(e):
                if isinstance(n, E.ScalarSubquery) and \
                        isinstance(n.query, L.LogicalPlan):
                    n.query = optimize(n.query)


# --- join reorder (cross-product avoidance) ---------------------------------------


def reorder_cross_joins(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Reorder a comma-FROM cross-join chain by WHERE-predicate connectivity.

    The binder builds comma FROM lists as a left-deep CROSS chain in written
    order; pushdown turns spanning equalities into join keys PAIRWISE, so a
    prefix with no predicate edge stays a true cross join — TPC-H Q2's
    `FROM part, supplier, partsupp, ...` becomes part x supplier, an |P|x|S|
    candidate expansion whose static-shape program is catastrophic (the
    expand at 8M lanes compiles for tens of minutes on TPU).

    This pass flattens Filter-over-pure-CROSS chains and checks prefix
    connectivity under the filter's conjuncts. Chains already connected in
    written order are returned UNCHANGED (plans stay bit-identical); otherwise
    relations greedily reorder so every join has at least one predicate edge
    when one exists, and a Project on top restores the original column order
    so everything above is untouched."""
    for name in ("input", "left", "right"):
        ch = getattr(plan, name, None)
        if isinstance(ch, L.LogicalPlan):
            setattr(plan, name, reorder_cross_joins(ch))
    if isinstance(plan, L.Union):
        plan.inputs = [reorder_cross_joins(c) for c in plan.inputs]
    if not isinstance(plan, L.Filter):
        return plan
    # walk from the filter stack down to the cross chain through structures
    # that preserve the chain's column indexes as a PREFIX: further Filters
    # (conjuncts collected — the binder stacks one Filter per conjunct),
    # identity-prefix Projects, and Join left spines (e.g. the decorrelation
    # LEFT join wrapping the FROM chain)
    conjuncts: list[E.Expr] = []
    parent, pattr = None, None
    node: L.LogicalPlan = plan
    rels: list = []
    while True:
        if isinstance(node, L.Filter):
            conjuncts += _split_conjuncts(node.predicate)
            parent, pattr, node = node, "input", node.input
        elif isinstance(node, L.Project) and _is_identity_prefix(node):
            parent, pattr, node = node, "input", node.input
        elif isinstance(node, L.Join):
            rels = _flatten_cross(node)
            if len(rels) >= 3:
                break
            parent, pattr, node = node, "left", node.left
        else:
            return plan
    if len(rels) < 3:
        return plan

    offsets = []
    off = 0
    for r in rels:
        offsets.append(off)
        off += len(r.schema)

    def rel_of(col_idx: int) -> int:
        for i in range(len(rels) - 1, -1, -1):
            if col_idx >= offsets[i]:
                return i
        return 0

    width = off
    edges: set[tuple[int, int]] = set()
    for c in conjuncts:
        cols = _cols_of(c)
        if not cols or any(i >= width for i in cols):
            continue  # references columns outside the chain
        touched = {rel_of(i) for i in cols}
        if len(touched) == 2:
            a, b = sorted(touched)
            edges.add((a, b))

    def connected(i: int, placed: set[int]) -> bool:
        return any((min(i, p), max(i, p)) in edges for p in placed)

    order = [0]
    remaining = list(range(1, len(rels)))
    while remaining:
        nxt = next((i for i in remaining if connected(i, set(order))),
                   remaining[0])
        order.append(nxt)
        remaining.remove(nxt)
    # written order already avoids cross products (or nothing improves):
    # leave the plan bit-identical
    if order == list(range(len(rels))):
        return plan

    chain = rels[order[0]]
    for i in order[1:]:
        j = L.Join(left=chain, right=rels[i], join_type=JoinType.CROSS)
        j.schema = T.Schema(list(chain.schema.fields) +
                            list(rels[i].schema.fields))
        chain = j
    # restore the ORIGINAL column order above the reordered chain
    new_offsets = {}
    off = 0
    for i in order:
        new_offsets[i] = off
        off += len(rels[i].schema)
    exprs, names = [], []
    orig_schema = node.schema
    for i, r in enumerate(rels):
        for k, f in enumerate(r.schema.fields):
            c = E.Column(f.name, index=new_offsets[i] + k)
            c.dtype = f.dtype
            exprs.append(c)
            names.append(orig_schema.fields[offsets[i] + k].name)
    proj = L.Project(input=chain, exprs=exprs, names=names)
    proj.schema = orig_schema
    setattr(parent, pattr, proj)
    return plan


def _flatten_cross(j: L.LogicalPlan) -> list[L.LogicalPlan]:
    if isinstance(j, L.Join) and j.join_type is JoinType.CROSS \
            and not j.left_keys and j.residual is None:
        return _flatten_cross(j.left) + [j.right]
    return [j]


def _is_identity_prefix(p: L.Project) -> bool:
    """Every projected expr is Column(index == position): the project only
    drops trailing columns, so lower column indexes pass through unchanged."""
    return all(isinstance(e, E.Column) and e.index == i
               for i, e in enumerate(p.exprs))


# --- adaptive join reorder (observed cardinalities) -------------------------------


import threading

_adaptive_tls = threading.local()


def last_adaptive_decisions() -> list:
    """Reorder decisions from the most recent optimize() on this thread —
    the engine appends them to EXPLAIN output and the coordinator merges
    them into last_metrics["adaptive"] (docs/adaptive.md). Cleared at the
    start of every reorder pass, so a query that reorders nothing (or runs
    with IGLOO_ADAPTIVE=0) reports nothing."""
    return list(getattr(_adaptive_tls, "decisions", ()))


def reorder_adaptive_joins(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Reorder commutable INNER equi-join spines greedily by effective build
    size: smallest relation first, then smallest CONNECTED relation at each
    step, so the cheapest effective build side sorts/probes first and join
    intermediates stay narrow (q9's six-table chain, q18's chain above the
    semi join are the targets).

    Effective size is OBSERVED output cardinality x estimated row width when
    the AdaptiveStats store (exec/hints.py) holds an observation for the
    subtree's structural fingerprint — post-filter cardinality bakes the
    filter's real selectivity in — and `table_lane_bytes` of the
    subtree's scans otherwise. First run: estimates; later runs: observed
    (one recompile ever, thanks to the canonical shape families of
    docs/compile_cache.md).

    Only provably commutable spines rewrite: INNER nodes, all keys plain
    Columns, no residuals. Spines whose greedy order equals written order
    are returned UNCHANGED (the IGLOO_ADAPTIVE=0 kill switch then reproduces
    the same plans bit-identically); otherwise a Project on top restores the
    original column order so everything above is untouched."""
    from igloo_tpu.exec.hints import adaptive_enabled
    _adaptive_tls.decisions = []
    if not adaptive_enabled():
        return plan
    return _adaptive_visit(plan)


def _adaptive_visit(plan: L.LogicalPlan) -> L.LogicalPlan:
    if isinstance(plan, L.Join):
        flat = _flatten_inner_spine(plan)
        if flat is not None:
            rels, edges = flat
            order, source = _spine_order(rels, edges)
            if order is not None and order != list(range(len(rels))):
                rels = [_adaptive_visit(r) for r in rels]
                rebuilt = _rebuild_spine(plan, rels, edges, order)
                if rebuilt is not None:
                    from igloo_tpu.utils import tracing
                    tracing.counter("adaptive.reorder")
                    tracing.counter("adaptive.reorder_observed"
                                    if source == "observed"
                                    else "adaptive.reorder_estimated")
                    _adaptive_tls.decisions.append({
                        "strategy": "reorder",
                        "join_order": list(order),
                        "adaptive_source": source})
                    return rebuilt
    for name in ("input", "left", "right"):
        ch = getattr(plan, name, None)
        if isinstance(ch, L.LogicalPlan):
            setattr(plan, name, _adaptive_visit(ch))
    if isinstance(plan, L.Union):
        plan.inputs = [_adaptive_visit(c) for c in plan.inputs]
    return plan


def _flatten_inner_spine(plan: L.Join):
    """Flatten a left-deep spine of residual-free INNER equi-joins whose keys
    are all plain Columns -> (rels, edges) with edge endpoints as GLOBAL
    column indexes over the written-order concat schema; None when the shape
    doesn't commute or is under 3 relations."""
    rels: list = []
    edges: list = []

    def rec(node) -> None:
        if isinstance(node, L.Join) and node.join_type is JoinType.INNER \
                and node.left_keys and node.residual is None and \
                all(type(k) is E.Column
                    for k in node.left_keys + node.right_keys):
            rec(node.left)
            lw = len(node.left.schema)
            rels.append(node.right)
            for lk, rk in zip(node.left_keys, node.right_keys):
                edges.append((lk.index, lw + rk.index))
            return
        rels.append(node)

    rec(plan)
    if len(rels) < 3 or len(plan.schema) != sum(len(r.schema) for r in rels):
        return None
    return rels, edges


def _est_subtree_lane_bytes(p: L.LogicalPlan) -> Optional[int]:
    """Estimated decoded device-lane bytes of the scans under `p`; None when
    any scan is unsized (then written order stands — no guess is better than
    a wrong one)."""
    from igloo_tpu.exec.chunked import table_lane_bytes
    total = 0
    for n in L.walk_plan(p):
        if isinstance(n, L.Scan):
            if n.provider is None:
                return None
            nb = table_lane_bytes(n.provider)
            if nb is None:
                return None
            total += nb
    return total


def _spine_order(rels: list, edges: list):
    """Greedy smallest-connected-first order over the relation graph, or
    (None, ...) when any relation is unsized or the graph would force a
    cross join the written order avoided."""
    from igloo_tpu.exec.hints import adaptive_store, plan_fp, row_width_bytes
    store = adaptive_store()
    sizes: list = []
    observed = 0
    for r in rels:
        fp = plan_fp(r)
        rows = store.observed_rows(fp) if fp is not None else None
        if rows is not None:
            sizes.append(rows * row_width_bytes(r.schema))
            observed += 1
        else:
            est = _est_subtree_lane_bytes(r)
            if est is None:
                return None, None
            sizes.append(est)
    offsets, off = [], 0
    for r in rels:
        offsets.append(off)
        off += len(r.schema)

    def rel_of(g: int) -> int:
        for i in range(len(rels) - 1, -1, -1):
            if g >= offsets[i]:
                return i
        return 0

    rel_edges = {(rel_of(a), rel_of(b)) for a, b in edges}
    order = [min(range(len(rels)), key=lambda i: sizes[i])]
    remaining = [i for i in range(len(rels)) if i != order[0]]
    while remaining:
        conn = [i for i in remaining
                if any((p, i) in rel_edges or (i, p) in rel_edges
                       for p in order)]
        if not conn:
            return None, None  # disconnected: would introduce a cross join
        nxt = min(conn, key=lambda i: sizes[i])
        order.append(nxt)
        remaining.remove(nxt)
    return order, ("observed" if observed == len(rels) else
                   "estimated" if observed == 0 else "mixed")


def _rebuild_spine(spine: L.Join, rels: list, edges: list,
                   order: list) -> Optional[L.LogicalPlan]:
    """Left-deep INNER chain in `order` + a Project restoring the original
    column order. Every edge is consumed as a join key the moment its
    later-placed relation joins the chain; a cyclic edge whose endpoints are
    already co-resident becomes an equality filter above the chain."""
    offsets, off = [], 0
    for r in rels:
        offsets.append(off)
        off += len(r.schema)

    def rel_of(g: int) -> int:
        for i in range(len(rels) - 1, -1, -1):
            if g >= offsets[i]:
                return i
        return 0

    def gfield(g: int) -> T.Field:
        i = rel_of(g)
        return rels[i].schema.fields[g - offsets[i]]

    def col(name: str, idx: int, dtype) -> E.Column:
        c = E.Column(name, index=idx)
        c.dtype = dtype
        return c

    placed = {order[0]}
    chain: L.LogicalPlan = rels[order[0]]
    pos = {offsets[order[0]] + k: k
           for k in range(len(rels[order[0]].schema))}
    consumed = [False] * len(edges)
    for i in order[1:]:
        lkeys, rkeys = [], []
        for ei, (a, b) in enumerate(edges):
            if consumed[ei]:
                continue
            if rel_of(a) in placed and rel_of(b) == i:
                gl, gr = a, b
            elif rel_of(b) in placed and rel_of(a) == i:
                gl, gr = b, a
            else:
                continue
            consumed[ei] = True
            lf, rf = gfield(gl), gfield(gr)
            lkeys.append(col(lf.name, pos[gl], lf.dtype))
            rkeys.append(col(rf.name, gr - offsets[i], rf.dtype))
        if not lkeys:
            return None  # pragma: no cover - connectivity guaranteed above
        j = L.Join(left=chain, right=rels[i], join_type=JoinType.INNER,
                   left_keys=lkeys, right_keys=rkeys)
        j.schema = T.Schema(list(chain.schema.fields) +
                            list(rels[i].schema.fields))
        base = len(pos)
        for k in range(len(rels[i].schema)):
            pos[offsets[i] + k] = base + k
        placed.add(i)
        chain = j
    # restore the ORIGINAL column order (and names) above the new chain
    orig = spine.schema
    exprs = []
    for g in range(off):
        f = gfield(g)
        exprs.append(col(f.name, pos[g], f.dtype))
    proj = L.Project(input=chain, exprs=exprs, names=list(orig.names))
    proj.schema = orig
    # cyclic edges with both endpoints placed before consumption cannot
    # occur (each edge is consumed when its later relation is placed), but
    # guard anyway: any leftover becomes an equality filter above the
    # restoring projection, where the original global indexes are valid
    preds = []
    for ei, (a, b) in enumerate(edges):
        if not consumed[ei]:
            fa, fb = gfield(a), gfield(b)
            eq = E.Binary(op=E.BinOp.EQ, left=col(fa.name, a, fa.dtype),
                          right=col(fb.name, b, fb.dtype))
            eq.dtype = T.BOOL
            preds.append(eq)
    return _wrap_filter(proj, preds) if preds else proj


# --- constant folding -------------------------------------------------------------


def fold_constants_pass(plan: L.LogicalPlan) -> L.LogicalPlan:
    for node in L.walk_plan(plan):
        if isinstance(node, L.Filter):
            node.predicate = fold_expr(node.predicate)
        elif isinstance(node, L.Project):
            node.exprs = [fold_expr(e) for e in node.exprs]
        elif isinstance(node, L.Aggregate):
            node.group_exprs = [fold_expr(e) for e in node.group_exprs]
            for a in node.aggs:
                if a.arg is not None:
                    a.arg = fold_expr(a.arg)
        elif isinstance(node, L.Join):
            node.left_keys = [fold_expr(e) for e in node.left_keys]
            node.right_keys = [fold_expr(e) for e in node.right_keys]
            if node.residual is not None:
                node.residual = fold_expr(node.residual)
        elif isinstance(node, L.Sort):
            node.keys = [fold_expr(e) for e in node.keys]
    return plan


def _lit(value, dtype: T.DataType) -> E.Literal:
    lt = E.Literal(value=value, literal_type=dtype)
    lt.dtype = dtype
    return lt


def fold_expr(e: E.Expr) -> E.Expr:
    def fold(n: E.Expr) -> E.Expr:
        if isinstance(n, E.Binary):
            l, r = n.left, n.right
            # boolean short-circuits with one literal side
            if n.op is E.BinOp.AND:
                if isinstance(l, E.Literal) and l.value is True:
                    return r
                if isinstance(r, E.Literal) and r.value is True:
                    return l
                if (isinstance(l, E.Literal) and l.value is False) or \
                        (isinstance(r, E.Literal) and r.value is False):
                    return _lit(False, T.BOOL)
            if n.op is E.BinOp.OR:
                if isinstance(l, E.Literal) and l.value is False:
                    return r
                if isinstance(r, E.Literal) and r.value is False:
                    return l
                if (isinstance(l, E.Literal) and l.value is True) or \
                        (isinstance(r, E.Literal) and r.value is True):
                    return _lit(True, T.BOOL)
            if isinstance(l, E.Literal) and isinstance(r, E.Literal):
                folded = _fold_binary(n.op, l, r, n.dtype)
                if folded is not None:
                    return folded
        elif isinstance(n, E.Not):
            if isinstance(n.operand, E.Literal):
                v = n.operand.value
                return _lit(None if v is None else (not v), T.BOOL)
            if isinstance(n.operand, E.Not):
                return n.operand.operand
        elif isinstance(n, E.Negate) and isinstance(n.operand, E.Literal):
            v = n.operand.value
            return _lit(None if v is None else -v, n.dtype)
        elif isinstance(n, E.Cast) and isinstance(n.operand, E.Literal):
            folded = _fold_cast(n.operand, n.to)
            if folded is not None:
                return folded
        elif isinstance(n, E.IsNull) and isinstance(n.operand, E.Literal):
            isn = n.operand.value is None
            return _lit((not isn) if n.negated else isn, T.BOOL)
        return n
    return E.transform(e, fold)


def _fold_binary(op: E.BinOp, l: E.Literal, r: E.Literal,
                 out_dtype) -> Optional[E.Expr]:
    if l.value is None or r.value is None:
        if op in (E.BinOp.AND, E.BinOp.OR):
            return None  # Kleene logic handled at runtime
        return _lit(None, out_dtype or T.NULL)
    a, b = l.value, r.value
    try:
        if op is E.BinOp.ADD:
            v = a + b
        elif op is E.BinOp.SUB:
            v = a - b
        elif op is E.BinOp.MUL:
            v = a * b
        elif op is E.BinOp.DIV:
            if b == 0:
                return _lit(None, out_dtype or T.NULL)
            # SQL integer division TRUNCATES (matches the runtime kernel,
            # expr_compile _compile_numeric_binary) — Python // floors
            if out_dtype is not None and out_dtype.is_integer:
                v = _trunc_div(a, b)
            else:
                v = a / b
        elif op is E.BinOp.MOD:
            if b == 0:
                return _lit(None, out_dtype or T.NULL)
            v = a - _trunc_div(a, b) * b  # truncating remainder, sign of a
        elif op is E.BinOp.EQ:
            return _lit(a == b, T.BOOL)
        elif op is E.BinOp.NEQ:
            return _lit(a != b, T.BOOL)
        elif op is E.BinOp.LT:
            return _lit(a < b, T.BOOL)
        elif op is E.BinOp.LTE:
            return _lit(a <= b, T.BOOL)
        elif op is E.BinOp.GT:
            return _lit(a > b, T.BOOL)
        elif op is E.BinOp.GTE:
            return _lit(a >= b, T.BOOL)
        else:
            return None
    except TypeError:
        return None
    return _lit(v, out_dtype or l.dtype)


def _trunc_div(a, b):
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b >= 0) else -q


def _fold_cast(lit: E.Literal, to: T.DataType) -> Optional[E.Expr]:
    v = lit.value
    if v is None:
        return _lit(None, to)
    try:
        if to.is_integer:
            return _lit(int(v), to)
        if to.is_float:
            return _lit(float(v), to)
        if to.id == T.TypeId.BOOL:
            return _lit(bool(v), to)
    except (TypeError, ValueError):
        return None
    return None  # string/date casts handled at runtime


# --- predicate pushdown -----------------------------------------------------------


def pushdown_filters(plan: L.LogicalPlan) -> L.LogicalPlan:
    """Push filter conjuncts as deep as legal. Returns a rewritten tree."""
    plan = _pushdown(plan, [])
    return plan


def _cols_of(e: E.Expr) -> set[int]:
    return {n.index for n in E.walk(e) if isinstance(n, E.Column)}


def _has_scalar_subquery(e: E.Expr) -> bool:
    return any(isinstance(n, E.ScalarSubquery) for n in E.walk(e))


def _remap_cols(e: E.Expr, mapping: dict[int, int]) -> E.Expr:
    e = copy.deepcopy(e)
    for n in E.walk(e):
        if isinstance(n, E.Column):
            n.index = mapping[n.index]
    return e


def _wrap_filter(plan: L.LogicalPlan, preds: list[E.Expr]) -> L.LogicalPlan:
    pred = _and_all([p for p in preds if not _is_true_lit(p)])
    if pred is None:
        return plan
    f = L.Filter(input=plan, predicate=pred)
    f.schema = plan.schema
    return f


def _is_true_lit(p: E.Expr) -> bool:
    return isinstance(p, E.Literal) and p.value is True


def _pushdown(plan: L.LogicalPlan, preds: list[E.Expr]) -> L.LogicalPlan:
    """`preds` are conjuncts bound against `plan`'s OUTPUT schema, to be applied
    above it unless they can sink further."""
    if isinstance(plan, L.Filter):
        inner = _split_conjuncts(plan.predicate)
        return _pushdown(plan.input, preds + inner)

    if isinstance(plan, L.Project):
        sinkable, stuck = [], []
        for p in preds:
            if _has_scalar_subquery(p):
                stuck.append(p)
                continue
            # substitute projected exprs into the predicate
            def sub(n):
                if isinstance(n, E.Column):
                    return copy.deepcopy(plan.exprs[n.index])
                return n
            sinkable.append(E.transform(copy.deepcopy(p), sub))
        plan.input = _pushdown(plan.input, sinkable)
        plan.schema = plan.schema  # unchanged
        return _wrap_filter(plan, stuck)

    if isinstance(plan, L.Aggregate):
        k = len(plan.group_exprs)
        sinkable, stuck = [], []
        for p in preds:
            cols = _cols_of(p)
            # k == 0 (global aggregate) must keep filters above: it emits one
            # row even over empty input, so sinking flips "no rows" to "one row"
            if k > 0 and all(i < k for i in cols) and not _has_scalar_subquery(p):
                def sub(n):
                    if isinstance(n, E.Column):
                        return copy.deepcopy(plan.group_exprs[n.index])
                    return n
                sinkable.append(E.transform(copy.deepcopy(p), sub))
            else:
                stuck.append(p)
        plan.input = _pushdown(plan.input, sinkable)
        return _wrap_filter(plan, stuck)

    if isinstance(plan, L.Join):
        n_left = len(plan.left.schema)
        jt = plan.join_type
        # Comma-list FROM items bind as CROSS joins with the WHERE equalities
        # left as predicates. Materializing the cross product (|L|x|R| candidate
        # slots) before filtering is catastrophic for the static-shape executor,
        # so equality conjuncts spanning exactly both sides become join keys
        # here, and any other both-sided conjunct becomes a residual (evaluated
        # during candidate expansion, before the output batch is sized).
        if jt in (JoinType.INNER, JoinType.CROSS):
            remaining = []
            for p in preds:
                pair = None if _has_scalar_subquery(p) else \
                    _extract_equi_key(p, n_left)
                if pair is not None:
                    lk, rk = coerce_key_pair(*pair)
                    plan.left_keys.append(lk)
                    plan.right_keys.append(rk)
                    jt = plan.join_type = JoinType.INNER
                else:
                    remaining.append(p)
            preds, remaining = remaining, []
            for p in preds:
                cols = _cols_of(p)
                if cols and not _has_scalar_subquery(p) and \
                        any(i < n_left for i in cols) and \
                        any(i >= n_left for i in cols):
                    plan.residual = _and_all(
                        ([plan.residual] if plan.residual is not None else [])
                        + [p])
                else:
                    remaining.append(p)
            preds = remaining
        semi = jt in (JoinType.SEMI, JoinType.ANTI)
        n_out_left = n_left
        left_preds, right_preds, stuck = [], [], []
        can_left = jt in (JoinType.INNER, JoinType.LEFT, JoinType.CROSS,
                          JoinType.SEMI, JoinType.ANTI)
        can_right = jt in (JoinType.INNER, JoinType.RIGHT, JoinType.CROSS)
        for p in preds:
            cols = _cols_of(p)
            if _has_scalar_subquery(p):
                stuck.append(p)
            elif cols and all(i < n_out_left for i in cols) and can_left:
                left_preds.append(p)
            elif not semi and cols and all(i >= n_out_left for i in cols) and can_right:
                right_preds.append(_remap_cols(p, {i: i - n_left
                                                   for i in range(n_left, n_left + len(plan.right.schema))}))
            else:
                stuck.append(p)
        # residual of an inner join can also sink if one-sided
        if plan.residual is not None and jt in (JoinType.INNER,):
            keep = []
            for c in _split_conjuncts(plan.residual):
                cols = _cols_of(c)
                if cols and all(i < n_left for i in cols):
                    left_preds.append(c)
                elif cols and all(i >= n_left for i in cols):
                    right_preds.append(_remap_cols(
                        c, {i: i - n_left for i in cols}))
                else:
                    keep.append(c)
            plan.residual = _and_all(keep)
        plan.left = _pushdown(plan.left, left_preds)
        plan.right = _pushdown(plan.right, right_preds)
        return _wrap_filter(plan, stuck)

    if isinstance(plan, L.Union):
        plan.inputs = [_pushdown(ch, [copy.deepcopy(p) for p in preds])
                       for ch in plan.inputs]
        return plan

    if isinstance(plan, (L.Distinct,)):
        plan.input = _pushdown(plan.input, preds)
        return plan

    if isinstance(plan, L.Scan):
        pushable = [p for p in preds if not _has_scalar_subquery(p)]
        plan.pushed_filters = list(pushable)
        # exact filters still applied above the scan (providers prune best-effort)
        return _wrap_filter(plan, preds)

    if isinstance(plan, (L.Sort, L.Limit)):
        # pushing below Sort is fine (stable), below Limit is NOT
        if isinstance(plan, L.Sort):
            plan.input = _pushdown(plan.input, preds)
            return plan
        plan.input = _pushdown(plan.input, [])
        return _wrap_filter(plan, preds)

    # SetOpJoin, Values, anything else: stop sinking
    for i, ch in enumerate(plan.children()):
        new = _pushdown(ch, [])
        _replace_child(plan, i, new)
    return _wrap_filter(plan, preds)


def _replace_child(plan, i, new):
    from igloo_tpu.plan.binder import _replace_child as rc
    rc(plan, i, new)


# --- projection pruning -----------------------------------------------------------


def prune_projections(plan: L.LogicalPlan) -> L.LogicalPlan:
    new_plan, mapping = _prune(plan, set(range(len(plan.schema))))
    assert len(mapping) == len(plan.schema), "root schema must be preserved"
    return new_plan


def _prune(plan: L.LogicalPlan, required: set[int]):
    """Prune `plan` so only `required` output columns (by index) are produced.
    Returns (new_plan, mapping old_index -> new_index). A node may keep more than
    required (mapping then covers all kept columns)."""
    if isinstance(plan, L.Scan):
        names = plan.schema.names
        keep = sorted(required) if required else [0] if names else []
        if not keep and names:
            keep = [0]  # always keep at least one column to carry row count
        if len(keep) == len(names):
            return plan, {i: i for i in range(len(names))}
        plan.projection = [names[i] for i in keep]
        plan.schema = T.Schema([plan.schema.fields[i] for i in keep])
        return plan, {old: new for new, old in enumerate(keep)}

    if isinstance(plan, L.Project):
        keep = sorted(required)
        child_req = set()
        for i in keep:
            child_req |= _cols_of(plan.exprs[i])
        for e in plan.exprs:
            if _has_scalar_subquery(e):
                for n in E.walk(e):
                    if isinstance(n, E.ScalarSubquery):
                        n.query = prune_projections(n.query)
        plan.input, cmap = _prune(plan.input, child_req)
        plan.exprs = [_remap_cols(plan.exprs[i], cmap) for i in keep]
        plan.names = [plan.names[i] for i in keep]
        plan.schema = T.Schema([plan.schema.fields[i] for i in keep])
        return plan, {old: new for new, old in enumerate(keep)}

    if isinstance(plan, L.Filter):
        child_req = set(required) | _cols_of(plan.predicate)
        for n in E.walk(plan.predicate):
            if isinstance(n, E.ScalarSubquery):
                n.query = prune_projections(n.query)
        plan.input, cmap = _prune(plan.input, child_req)
        plan.predicate = _remap_cols(plan.predicate, cmap)
        plan.schema = plan.input.schema
        return plan, cmap

    if isinstance(plan, L.Aggregate):
        child_req = set()
        for g in plan.group_exprs:
            child_req |= _cols_of(g)
        for a in plan.aggs:
            if a.arg is not None:
                child_req |= _cols_of(a.arg)
        plan.input, cmap = _prune(plan.input, child_req)
        plan.group_exprs = [_remap_cols(g, cmap) for g in plan.group_exprs]
        for a in plan.aggs:
            if a.arg is not None:
                a.arg = _remap_cols(a.arg, cmap)
        return plan, {i: i for i in range(len(plan.schema))}

    if isinstance(plan, L.Join):
        n_left = len(plan.left.schema)
        semi = plan.join_type in (JoinType.SEMI, JoinType.ANTI)
        lreq, rreq = set(), set()
        for i in required:
            if i < n_left:
                lreq.add(i)
            else:
                rreq.add(i - n_left)
        for k in plan.left_keys:
            lreq |= _cols_of(k)
        for k in plan.right_keys:
            rreq |= _cols_of(k)
        if plan.residual is not None:
            for i in _cols_of(plan.residual):
                if i < n_left:
                    lreq.add(i)
                else:
                    rreq.add(i - n_left)
        plan.left, lmap = _prune(plan.left, lreq)
        plan.right, rmap = _prune(plan.right, rreq)
        plan.left_keys = [_remap_cols(k, lmap) for k in plan.left_keys]
        plan.right_keys = [_remap_cols(k, rmap) for k in plan.right_keys]
        new_n_left = len(plan.left.schema)
        # combined mapping always covers both sides: the residual may reference
        # right-side columns even in semi/anti joins (NOT IN rewrite)
        comb = {}
        for old, new in lmap.items():
            comb[old] = new
        for old, new in rmap.items():
            comb[old + n_left] = new + new_n_left
        if plan.residual is not None:
            plan.residual = _remap_cols(plan.residual, comb)
        if semi:
            plan.schema = plan.left.schema
            return plan, lmap
        old_fields = plan.schema.fields
        kept_old = sorted(comb)
        from igloo_tpu.plan.binder import _dedup_fields
        plan.schema = T.Schema(_dedup_fields(
            [T.Field(old_fields[i].name if i < len(old_fields) else "c",
                     (list(plan.left.schema) + list(plan.right.schema))[comb[i]].dtype,
                     True) for i in kept_old]))
        return plan, {old: k for k, old in enumerate(kept_old)}

    if isinstance(plan, L.Sort):
        child_req = set(required)
        for k in plan.keys:
            child_req |= _cols_of(k)
        plan.input, cmap = _prune(plan.input, child_req)
        plan.keys = [_remap_cols(k, cmap) for k in plan.keys]
        plan.schema = plan.input.schema
        return plan, cmap

    if isinstance(plan, L.Limit):
        plan.input, cmap = _prune(plan.input, required)
        plan.schema = plan.input.schema
        return plan, cmap

    if isinstance(plan, (L.Distinct, L.Union, L.SetOpJoin, L.Values)):
        # positional semantics: all columns required
        all_req_children = []
        for i, ch in enumerate(plan.children()):
            new, cmap = _prune(ch, set(range(len(ch.schema))))
            assert len(cmap) == len(ch.schema)
            all_req_children.append(new)
            _replace_child(plan, i, new)
        return plan, {i: i for i in range(len(plan.schema))}

    # unknown node: require everything below
    for i, ch in enumerate(plan.children()):
        new, _ = _prune(ch, set(range(len(ch.schema))))
        _replace_child(plan, i, new)
    return plan, {i: i for i in range(len(plan.schema))}
