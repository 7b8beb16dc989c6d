"""Logical plan IR.

The reference uses DataFusion's `LogicalPlan` and lowers a 4-node subset to its custom
operators (crates/engine/src/physical_planner.rs:23-140: TableScan/Projection/Filter/
Join). We own the logical plan — it is the unit the optimizer rewrites, the
distributed planner fragments, and the executor lowers to fused jit computations.

Every node carries its output `Schema`; expressions inside nodes are *bound*
(Column.index resolved against the node's input schema, dtypes inferred).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Optional

from igloo_tpu.types import Schema
from igloo_tpu.plan import expr as E
from igloo_tpu.sql.ast import JoinType

if TYPE_CHECKING:  # pragma: no cover
    from igloo_tpu.catalog import TableProvider


@dataclass
class LogicalPlan:
    schema: Schema = field(default=None, init=False)  # type: ignore[assignment]

    def children(self) -> list["LogicalPlan"]:
        return []

    def node_name(self) -> str:
        return type(self).__name__


@dataclass
class Scan(LogicalPlan):
    """Table scan. `projection` (column names) is filled by projection pruning;
    `pushed_filters` by predicate pushdown (connector may evaluate them early —
    unlike the reference, which ignores the provider and hardcodes a path,
    physical_planner.rs:37-39 / gap G5, the provider here is authoritative)."""
    table: str = ""
    provider: object = None  # TableProvider
    projection: Optional[list[str]] = None
    pushed_filters: list[E.Expr] = field(default_factory=list)
    # restrict the scan to these provider partition indices (distributed /
    # chunked execution); None = whole table
    partition: Optional[tuple[int, ...]] = None
    # fingerprint of the provider's partition index captured at planning time;
    # verified before partitioned reads (a re-globbed index of the same length
    # must not silently remap partition ids)
    partition_token: Optional[str] = None
    # fragment-tier bucket scan: read only hash bucket `bucket` of `buckets`
    # from a dependency fragment's Exchange-partitioned result (the worker's
    # dep fetch resolves these into bucketed do_get tickets); None = whole
    # result. Only meaningful on `__frag_*` scans.
    bucket: Optional[int] = None
    buckets: Optional[int] = None

    def node_name(self):
        cols = f" cols={self.projection}" if self.projection is not None else ""
        part = f" part={list(self.partition)}" if self.partition is not None else ""
        bk = f" bucket={self.bucket}/{self.buckets}" if self.bucket is not None else ""
        return f"Scan({self.table}{cols}{part}{bk})"


@dataclass
class Filter(LogicalPlan):
    input: LogicalPlan = None  # type: ignore[assignment]
    predicate: E.Expr = None   # type: ignore[assignment]

    def children(self):
        return [self.input]

    def node_name(self):
        return f"Filter({self.predicate!r})"


@dataclass
class Project(LogicalPlan):
    input: LogicalPlan = None          # type: ignore[assignment]
    exprs: list[E.Expr] = field(default_factory=list)
    names: list[str] = field(default_factory=list)

    def children(self):
        return [self.input]

    def node_name(self):
        return f"Project({', '.join(self.names)})"


@dataclass
class Aggregate(LogicalPlan):
    """Group-by + aggregate. Output schema = group columns then aggregate columns."""
    input: LogicalPlan = None  # type: ignore[assignment]
    group_exprs: list[E.Expr] = field(default_factory=list)
    group_names: list[str] = field(default_factory=list)
    aggs: list[E.Aggregate] = field(default_factory=list)
    agg_names: list[str] = field(default_factory=list)
    # 1 or 2: a stage of a DISTINCT aggregate split by plan/aggsplit.py
    # split_distinct (it names the stage's ops and counts the split); 0 else
    distinct_stage: int = 0

    def children(self):
        return [self.input]

    def node_name(self):
        stage = f", distinct stage {self.distinct_stage}" \
            if self.distinct_stage else ""
        return (f"Aggregate(by=[{', '.join(self.group_names)}], "
                f"aggs=[{', '.join(self.agg_names)}]{stage})")


@dataclass
class Join(LogicalPlan):
    """Equi-join with optional residual filter (bound against concat(left, right)
    schema). CROSS join = empty key lists. Completes the reference's partial
    HashJoinExec (G4: right/full outer unmatched rows are emitted here)."""
    left: LogicalPlan = None   # type: ignore[assignment]
    right: LogicalPlan = None  # type: ignore[assignment]
    join_type: JoinType = JoinType.INNER
    left_keys: list[E.Expr] = field(default_factory=list)
    right_keys: list[E.Expr] = field(default_factory=list)
    residual: Optional[E.Expr] = None  # non-equi part of ON

    def children(self):
        return [self.left, self.right]

    def node_name(self):
        return f"Join({self.join_type.value}, on={len(self.left_keys)} keys{', residual' if self.residual else ''})"


@dataclass
class Window(LogicalPlan):
    """Window-function evaluation: output = input columns + one column per
    entry in `funcs` (bound E.Window exprs sharing this node's single
    PARTITION BY / ORDER BY spec; the binder stacks one node per distinct
    spec). Row ORDER of the output batch is unspecified (like every
    non-Sort node); only the VALUES are window-ordered."""
    input: LogicalPlan = None  # type: ignore[assignment]
    partition_exprs: list[E.Expr] = field(default_factory=list)
    order_exprs: list[E.Expr] = field(default_factory=list)
    ascending: list[bool] = field(default_factory=list)
    nulls_first: list[bool] = field(default_factory=list)
    funcs: list[E.Expr] = field(default_factory=list)   # bound E.Window nodes
    names: list[str] = field(default_factory=list)

    def children(self):
        return [self.input]

    def node_name(self):
        return (f"Window({', '.join(self.names)} part="
                f"{len(self.partition_exprs)} order={len(self.order_exprs)})")


@dataclass
class Sort(LogicalPlan):
    input: LogicalPlan = None  # type: ignore[assignment]
    keys: list[E.Expr] = field(default_factory=list)  # bound against input schema
    ascending: list[bool] = field(default_factory=list)
    nulls_first: list[bool] = field(default_factory=list)

    def children(self):
        return [self.input]

    def node_name(self):
        return f"Sort({len(self.keys)} keys)"


@dataclass
class Limit(LogicalPlan):
    input: LogicalPlan = None  # type: ignore[assignment]
    limit: Optional[int] = None
    offset: int = 0

    def children(self):
        return [self.input]

    def node_name(self):
        return f"Limit({self.limit}, offset={self.offset})"


@dataclass
class Distinct(LogicalPlan):
    input: LogicalPlan = None  # type: ignore[assignment]

    def children(self):
        return [self.input]


@dataclass
class Union(LogicalPlan):
    """UNION ALL (bag union). Set-union is Distinct(Union)."""
    inputs: list[LogicalPlan] = field(default_factory=list)

    def children(self):
        return list(self.inputs)


@dataclass
class SetOpJoin(LogicalPlan):
    """INTERSECT / EXCEPT as distinct + semi/anti join on all columns."""
    left: LogicalPlan = None   # type: ignore[assignment]
    right: LogicalPlan = None  # type: ignore[assignment]
    anti: bool = False         # False=INTERSECT, True=EXCEPT

    def children(self):
        return [self.left, self.right]


@dataclass
class Values(LogicalPlan):
    """Inline literal rows (VALUES ... / SELECT-without-FROM one-row source)."""
    rows: list[list[object]] = field(default_factory=list)  # python values


@dataclass
class Exchange(LogicalPlan):
    """Hash-partition marker at a FRAGMENT root (distributed planner only —
    the reference's never-built FragmentType::Shuffle, fragment.rs:12): the
    worker executes `input`, then hash-partitions the result by the key
    columns (indices into the input schema) into `buckets` bucket slices
    served via bucketed do_get tickets. Never reaches a local executor.

    Hot-key salting (docs/adaptive.md): when the adaptive skew sketch flags
    bucket `salt_bucket` as pathologically hot, the exchange grows `salt - 1`
    extra buckets. The PROBE side spreads its hot-bucket rows round-robin
    across {salt_bucket} + the extra buckets; the BUILD side keeps its
    hot-bucket rows in place AND replicates them into every extra bucket, so
    each salted join fragment still sees every build row that could match."""
    input: LogicalPlan = None  # type: ignore[assignment]
    keys: list[int] = field(default_factory=list)
    buckets: int = 1
    salt_bucket: Optional[int] = None
    salt: int = 1                      # salted bucket count S (1 = no salting)
    salt_role: Optional[str] = None    # "probe" | "build"

    def children(self):
        return [self.input]

    def node_name(self):
        s = (f", salt={self.salt}@{self.salt_bucket}/{self.salt_role}"
             if self.salt_role else "")
        return f"Exchange(keys={self.keys}, buckets={self.buckets}{s})"


def copy_plan(plan: LogicalPlan) -> LogicalPlan:
    """Structural copy of a plan tree: nodes and expressions are fresh objects
    (safe for in-place optimizer rewrites), table providers are shared. Needed
    when one subtree is referenced twice (CTE used in two FROM positions)."""
    import copy as _copy
    n = _copy.copy(plan)
    if isinstance(n, Scan):
        n.pushed_filters = [_copy.deepcopy(e) for e in n.pushed_filters]
        n.projection = list(n.projection) if n.projection is not None else None
    elif isinstance(n, Filter):
        n.input = copy_plan(n.input)
        n.predicate = _copy.deepcopy(n.predicate)
    elif isinstance(n, Project):
        n.input = copy_plan(n.input)
        n.exprs = [_copy.deepcopy(e) for e in n.exprs]
        n.names = list(n.names)
    elif isinstance(n, Aggregate):
        n.input = copy_plan(n.input)
        n.group_exprs = [_copy.deepcopy(e) for e in n.group_exprs]
        n.group_names = list(n.group_names)
        n.aggs = [_copy.deepcopy(a) for a in n.aggs]
        n.agg_names = list(n.agg_names)
    elif isinstance(n, Join):
        n.left = copy_plan(n.left)
        n.right = copy_plan(n.right)
        n.left_keys = [_copy.deepcopy(e) for e in n.left_keys]
        n.right_keys = [_copy.deepcopy(e) for e in n.right_keys]
        n.residual = _copy.deepcopy(n.residual) if n.residual is not None else None
    elif isinstance(n, Window):
        n.input = copy_plan(n.input)
        n.partition_exprs = [_copy.deepcopy(e) for e in n.partition_exprs]
        n.order_exprs = [_copy.deepcopy(e) for e in n.order_exprs]
        n.ascending = list(n.ascending)
        n.nulls_first = list(n.nulls_first)
        n.funcs = [_copy.deepcopy(e) for e in n.funcs]
        n.names = list(n.names)
    elif isinstance(n, Sort):
        n.input = copy_plan(n.input)
        n.keys = [_copy.deepcopy(e) for e in n.keys]
        n.ascending = list(n.ascending)
        n.nulls_first = list(n.nulls_first)
    elif isinstance(n, (Limit, Distinct)):
        n.input = copy_plan(n.input)
    elif isinstance(n, Exchange):
        n.input = copy_plan(n.input)
        n.keys = list(n.keys)
    elif isinstance(n, Union):
        n.inputs = [copy_plan(c) for c in n.inputs]
    elif isinstance(n, SetOpJoin):
        n.left = copy_plan(n.left)
        n.right = copy_plan(n.right)
    elif isinstance(n, Values):
        n.rows = [list(r) for r in n.rows]
    return n


def plan_tree_str(plan: LogicalPlan, indent: int = 0) -> str:
    lines = ["  " * indent + plan.node_name()]
    for c in plan.children():
        lines.append(plan_tree_str(c, indent + 1))
    return "\n".join(lines)


def walk_plan(plan: LogicalPlan):
    yield plan
    for c in plan.children():
        yield from walk_plan(c)
