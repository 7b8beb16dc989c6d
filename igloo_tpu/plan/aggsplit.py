"""An aggregate split into two grouped aggregates: per-piece partials and
their merge.

The distributed planner (cluster/fragment.py), the chunked executor
(exec/chunked.py) and the out-of-core grace join (exec/grace.py) run an
aggregate's partials on pieces of its input and merge them by its keys
(`decompose_aggregate`, `partial_aggregate_node`, `final_merge_plan`).

`split_distinct` is the same split along a value instead of along pieces:
an aggregate with DISTINCT arguments becomes stage 1, grouped by its keys and
the distinct argument and carrying the partials of its plain aggregates, and
stage 2, grouped by its keys over stage 1's rows, which applies the distinct
aggregates without DISTINCT (each value is one row by then) and merges the
partials. The optimizer applies it to every plan (`optimize`), so no
executor sees a DISTINCT aggregate: each runs the two stages as the grouped
aggregates they are, the fused compiler inside one program. The stages are
marked (`L.Aggregate.distinct_stage`) so that the executors name their ops
(`jax.named_scope("distinct_stage1")`, `("distinct_stage2")`) and count the
split once per plan walk (`agg.distinct`).
"""
from __future__ import annotations

from igloo_tpu import types as T
from igloo_tpu.errors import NotSupportedError
from igloo_tpu.plan import expr as E
from igloo_tpu.plan import logical as L

DECOMPOSABLE = {E.AggFunc.SUM, E.AggFunc.MIN, E.AggFunc.MAX, E.AggFunc.COUNT,
                E.AggFunc.COUNT_STAR, E.AggFunc.AVG}


def col(i: int, dtype: T.DataType, name: str = "") -> E.Expr:
    c = E.Column(name=name or f"c{i}", index=i)
    c.dtype = dtype
    return c


def decompose_aggregate(agg: L.Aggregate):
    """Decompose a DECOMPOSABLE aggregate into per-chunk partials: returns
    (partial_schema, partial_aggs, partial_names, final_plan) where
    final_plan records how final_merge_plan recombines partial columns.
    Shared by the distributed planner, the chunked executor, the
    out-of-core grace join (exec/grace.py) and split_distinct."""
    k = len(agg.group_exprs)
    partial_aggs: list[E.Aggregate] = []
    partial_names: list[str] = []
    final_plan: list[tuple] = []  # (kind, partial col index, orig agg)
    pi = k
    for a in agg.aggs:
        if a.func in (E.AggFunc.COUNT, E.AggFunc.COUNT_STAR):
            partial_aggs.append(a)
            partial_names.append(f"p{pi}")
            final_plan.append(("sum0", pi, a))
            pi += 1
        elif a.func is E.AggFunc.AVG:
            s = E.Aggregate(func=E.AggFunc.SUM, arg=a.arg)
            s.dtype = T.FLOAT64
            c = E.Aggregate(func=E.AggFunc.COUNT, arg=a.arg)
            c.dtype = T.INT64
            partial_aggs.extend([s, c])
            partial_names.extend([f"p{pi}", f"p{pi + 1}"])
            final_plan.append(("avg", pi, a))
            pi += 2
        else:  # SUM / MIN / MAX: associative
            partial_aggs.append(a)
            partial_names.append(f"p{pi}")
            final_plan.append(("assoc", pi, a))
            pi += 1

    partial_fields = [T.Field(n, g.dtype, True)
                      for n, g in zip(agg.group_names, agg.group_exprs)]
    partial_fields += [T.Field(n, a.dtype, True)
                       for n, a in zip(partial_names, partial_aggs)]
    return T.Schema(partial_fields), partial_aggs, partial_names, final_plan


def partial_aggregate_node(agg: L.Aggregate, inp: L.LogicalPlan,
                           partial_schema, partial_aggs,
                           partial_names) -> L.Aggregate:
    node = L.Aggregate(input=inp,
                       group_exprs=[g for g in agg.group_exprs],
                       group_names=list(agg.group_names),
                       aggs=list(partial_aggs),
                       agg_names=list(partial_names))
    node.schema = partial_schema
    return node


def final_merge_plan(agg: L.Aggregate, merged: L.LogicalPlan,
                     final_plan: list[tuple]) -> L.LogicalPlan:
    """Final re-aggregation of partial rows + projection back to the
    aggregate's declared output schema. A ("distinct", i, a) entry of
    `final_plan` applies `a`'s function, without DISTINCT, to column i of
    `merged`, whose rows are already one per distinct value
    (split_distinct)."""
    k = len(agg.group_exprs)
    # final merge: re-aggregate partials by the group columns
    final_groups = [col(i, g.dtype, agg.group_names[i])
                    for i, g in enumerate(agg.group_exprs)]
    final_aggs: list[E.Aggregate] = []
    final_names: list[str] = []
    for kind, pi_, a in final_plan:
        if kind == "avg":
            for j, dt in ((pi_, T.FLOAT64), (pi_ + 1, T.INT64)):
                fa = E.Aggregate(func=E.AggFunc.SUM, arg=col(j, dt))
                fa.dtype = dt
                final_aggs.append(fa)
                final_names.append(f"f{j}")
        else:
            fn = E.AggFunc.SUM if kind == "sum0" else a.func
            arg_dtype = a.arg.dtype if kind == "distinct" else a.dtype
            fa = E.Aggregate(func=fn, arg=col(pi_, arg_dtype))
            fa.dtype = a.dtype
            final_aggs.append(fa)
            final_names.append(f"f{pi_}")
    merge = L.Aggregate(input=merged, group_exprs=final_groups,
                        group_names=list(agg.group_names),
                        aggs=final_aggs, agg_names=final_names)
    merge.schema = T.Schema(
        [T.Field(n, g.dtype, True)
         for n, g in zip(agg.group_names, final_groups)] +
        [T.Field(n, a.dtype, True)
         for n, a in zip(final_names, final_aggs)])

    # project back to the aggregate's declared output (AVG division,
    # COUNT null->0 on empty-side sums)
    out_exprs: list[E.Expr] = [
        col(i, g.dtype, agg.group_names[i])
        for i, g in enumerate(agg.group_exprs)]
    fi = k
    for kind, _pi, a in final_plan:
        if kind == "avg":
            s = col(fi, T.FLOAT64)
            c = col(fi + 1, T.INT64)
            zero = E.Literal(value=0)
            zero.dtype = T.INT64
            cast = E.Cast(operand=c, to=T.FLOAT64)
            cast.dtype = T.FLOAT64
            div = E.Binary(op=E.BinOp.DIV, left=s, right=cast)
            div.dtype = T.FLOAT64
            isz = E.Binary(op=E.BinOp.EQ, left=c, right=zero)
            isz.dtype = T.BOOL
            nul = E.Literal(value=None, literal_type=T.FLOAT64)
            nul.dtype = T.FLOAT64
            case = E.Case(whens=[(isz, nul)], else_=div)
            case.dtype = T.FLOAT64
            out_exprs.append(case)
            fi += 2
        elif kind == "sum0":
            s = col(fi, T.INT64)
            zero = E.Literal(value=0)
            zero.dtype = T.INT64
            isn = E.IsNull(operand=s)
            isn.dtype = T.BOOL
            case = E.Case(whens=[(isn, zero)], else_=s)
            case.dtype = T.INT64
            out_exprs.append(case)
            fi += 1
        else:
            out_exprs.append(col(fi, a.dtype))
            fi += 1
    proj = L.Project(input=merge, exprs=out_exprs,
                     names=list(agg.schema.names))
    proj.schema = agg.schema
    return proj


def split_distinct(agg: L.Aggregate) -> L.LogicalPlan:
    """agg(DISTINCT x), alone or beside plain aggregates, as two grouped
    aggregates and the projection back to `agg`'s schema: stage 1 groups by
    (keys..., x) and carries the plain aggregates' partials (COUNT and
    COUNT(*) as counts, SUM, MIN and MAX as themselves, AVG as a sum and a
    count); stage 2 groups by the keys over stage 1's rows, applies each
    distinct aggregate's function to x (a NULL x is a group of stage 1 and
    is skipped by every function, as DISTINCT skips it) and merges the
    partials. So COUNT(DISTINCT) of an empty group reads 0 and SUM / AVG
    read NULL, a global aggregate still yields its one row, and SUM / AVG
    (DISTINCT) add each value once. More than one distinct argument is not
    supported."""
    args = {E.fingerprint(a.arg) for a in agg.aggs if a.distinct}
    if len(args) > 1:
        raise NotSupportedError(
            "multiple distinct aggregate arguments are not supported yet")
    arg = next(a.arg for a in agg.aggs if a.distinct)
    plain = [(a, n) for a, n in zip(agg.aggs, agg.agg_names)
             if not a.distinct]
    by_value = L.Aggregate(input=agg.input,
                           group_exprs=list(agg.group_exprs) + [arg],
                           group_names=list(agg.group_names) + ["__distinct"],
                           aggs=[a for a, _ in plain],
                           agg_names=[n for _, n in plain])
    partial_schema, partial_aggs, partial_names, plain_plan = \
        decompose_aggregate(by_value)
    stage1 = partial_aggregate_node(by_value, agg.input, partial_schema,
                                    partial_aggs, partial_names)
    stage1.distinct_stage = 1
    k = len(agg.group_exprs)
    plain_steps = iter(plain_plan)
    final_plan = [("distinct", k, a) if a.distinct else next(plain_steps)
                  for a in agg.aggs]
    out = final_merge_plan(agg, stage1, final_plan)
    out.input.distinct_stage = 2
    return out
