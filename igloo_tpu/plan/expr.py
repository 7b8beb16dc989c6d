"""Expression IR.

The reference delegates expression representation to DataFusion's `PhysicalExpr`
(crates/engine/src/operators/projection.rs:12-16, filter.rs:13-16 hold
`Arc<dyn PhysicalExpr>`); we own the IR because it must lower to jnp element-wise
graphs fused into each fragment's jit function (SURVEY.md §2 #7 "expression compiler").

Expressions are built untyped by the SQL parser, then *bound* (names resolved, types
inferred) by the planner. `dtype` is filled in during binding.
"""
from __future__ import annotations

import enum
import itertools
from dataclasses import dataclass, field as dc_field, fields as dc_fields
from typing import Optional

from igloo_tpu import types as T


class BinOp(enum.Enum):
    ADD = "+"
    SUB = "-"
    MUL = "*"
    DIV = "/"
    MOD = "%"
    EQ = "="
    NEQ = "<>"
    LT = "<"
    LTE = "<="
    GT = ">"
    GTE = ">="
    AND = "and"
    OR = "or"


COMPARISONS = {BinOp.EQ, BinOp.NEQ, BinOp.LT, BinOp.LTE, BinOp.GT, BinOp.GTE}
ARITHMETIC = {BinOp.ADD, BinOp.SUB, BinOp.MUL, BinOp.DIV, BinOp.MOD}


@dataclass
class Expr:
    """Base expression node. `dtype` is None until bound."""
    dtype: Optional[T.DataType] = dc_field(default=None, init=False, compare=False)

    def name_hint(self) -> str:
        return "expr"

    def children(self) -> list["Expr"]:
        return []


@dataclass
class Column(Expr):
    name: str
    # Resolved during binding: index into the input schema.
    index: Optional[int] = dc_field(default=None, compare=False)

    def name_hint(self) -> str:
        return self.name.split(".")[-1]

    def __repr__(self) -> str:
        return f"col({self.name})"


@dataclass
class Literal(Expr):
    value: object  # python int/float/str/bool/None; dates as int days, ts as int us
    literal_type: Optional[T.DataType] = None

    def name_hint(self) -> str:
        return str(self.value)

    def __repr__(self) -> str:
        return f"lit({self.value!r})"


@dataclass
class Interval(Expr):
    """INTERVAL literal; exists only pre-folding (date arithmetic constant-folds)."""
    days: int = 0
    months: int = 0

    def __repr__(self) -> str:
        return f"interval(days={self.days}, months={self.months})"


@dataclass
class Binary(Expr):
    op: BinOp
    left: Expr
    right: Expr

    def children(self):
        return [self.left, self.right]

    def name_hint(self) -> str:
        return f"{self.left.name_hint()} {self.op.value} {self.right.name_hint()}"

    def __repr__(self) -> str:
        return f"({self.left!r} {self.op.value} {self.right!r})"


@dataclass
class Not(Expr):
    operand: Expr

    def children(self):
        return [self.operand]

    def __repr__(self) -> str:
        return f"not({self.operand!r})"


@dataclass
class Negate(Expr):
    operand: Expr

    def children(self):
        return [self.operand]

    def __repr__(self) -> str:
        return f"(-{self.operand!r})"


@dataclass
class IsNull(Expr):
    operand: Expr
    negated: bool = False

    def children(self):
        return [self.operand]

    def __repr__(self) -> str:
        return f"is_{'not_' if self.negated else ''}null({self.operand!r})"


@dataclass
class Cast(Expr):
    operand: Expr
    to: T.DataType = None  # type: ignore[assignment]

    def children(self):
        return [self.operand]

    def name_hint(self) -> str:
        return self.operand.name_hint()

    def __repr__(self) -> str:
        return f"cast({self.operand!r} as {self.to})"


@dataclass
class Case(Expr):
    """CASE WHEN c THEN v ... ELSE e END (searched form; simple form is desugared)."""
    whens: list[tuple[Expr, Expr]] = dc_field(default_factory=list)
    else_: Optional[Expr] = None

    def children(self):
        out = []
        for c, v in self.whens:
            out += [c, v]
        if self.else_ is not None:
            out.append(self.else_)
        return out

    def __repr__(self) -> str:
        return f"case({self.whens!r}, else={self.else_!r})"


@dataclass
class InList(Expr):
    operand: Expr
    items: list[Expr] = dc_field(default_factory=list)
    negated: bool = False

    def children(self):
        return [self.operand] + self.items

    def __repr__(self) -> str:
        return f"in({self.operand!r}, {self.items!r}, neg={self.negated})"


@dataclass
class Like(Expr):
    operand: Expr
    pattern: str = ""
    negated: bool = False
    case_insensitive: bool = False

    def children(self):
        return [self.operand]

    def __repr__(self) -> str:
        return (f"like({self.operand!r}, {self.pattern!r}, "
                f"neg={self.negated}, ci={self.case_insensitive})")


@dataclass
class Func(Expr):
    """Scalar function call: abs, upper, lower, capitalize, length, substr, concat,
    extract_year/month/day, coalesce, round, floor, ceil, sqrt, ..."""
    name: str = ""
    args: list[Expr] = dc_field(default_factory=list)

    def children(self):
        return self.args

    def name_hint(self) -> str:
        return self.name

    def __repr__(self) -> str:
        return f"{self.name}({self.args!r})"


class AggFunc(enum.Enum):
    SUM = "sum"
    COUNT = "count"
    MIN = "min"
    MAX = "max"
    AVG = "avg"
    COUNT_STAR = "count_star"


@dataclass
class Aggregate(Expr):
    """Aggregate function reference inside a SELECT/HAVING. The planner hoists these
    into the Aggregate plan node; they never reach the expression compiler directly."""
    func: AggFunc = AggFunc.COUNT_STAR
    arg: Optional[Expr] = None
    distinct: bool = False

    def children(self):
        return [self.arg] if self.arg is not None else []

    def name_hint(self) -> str:
        if self.func is AggFunc.COUNT_STAR:
            return "count(*)"
        return f"{self.func.value}({self.arg.name_hint()})"

    def __repr__(self) -> str:
        return f"{self.func.value}({self.arg!r}{', distinct' if self.distinct else ''})"


@dataclass
class Window(Expr):
    """Window function: fn(args) OVER (PARTITION BY ... ORDER BY ...).

    `func` is "row_number" | "rank" | "dense_rank" | "lag" | "lead", or an
    aggregate applied over the window (`agg` set, func == "agg"). With an
    ORDER BY, aggregates use the SQL default frame (RANGE UNBOUNDED PRECEDING
    .. CURRENT ROW — running totals over peer groups); without one they span
    the whole partition. The reference executes these through DataFusion
    (crates/engine/src/lib.rs:54-57); the TPU design is a segmented-scan
    kernel (exec/window.py)."""
    func: str = ""
    agg: Optional["Aggregate"] = None
    args: list[Expr] = dc_field(default_factory=list)   # lag/lead: value[, offset]
    partition_by: list[Expr] = dc_field(default_factory=list)
    order_by: list[Expr] = dc_field(default_factory=list)
    ascending: list[bool] = dc_field(default_factory=list)
    nulls_first: list[bool] = dc_field(default_factory=list)

    def children(self):
        out = list(self.args) + list(self.partition_by) + list(self.order_by)
        if self.agg is not None and self.agg.arg is not None:
            out.append(self.agg.arg)
        return out

    def name_hint(self) -> str:
        return self.agg.name_hint() if self.agg is not None else self.func

    def __repr__(self) -> str:
        inner = repr(self.agg) if self.agg is not None else \
            f"{self.func}({self.args!r})"
        return (f"window({inner} part={self.partition_by!r} "
                f"order={self.order_by!r} asc={self.ascending} "
                f"nf={self.nulls_first})")


@dataclass
class Alias(Expr):
    operand: Expr = None  # type: ignore[assignment]
    alias: str = ""

    def children(self):
        return [self.operand]

    def name_hint(self) -> str:
        return self.alias

    def __repr__(self) -> str:
        return f"({self.operand!r} as {self.alias})"


@dataclass
class Star(Expr):
    """SELECT * placeholder; expanded by the planner."""
    qualifier: Optional[str] = None

    def __repr__(self) -> str:
        return f"{self.qualifier + '.' if self.qualifier else ''}*"


@dataclass
class ScalarSubquery(Expr):
    """(SELECT single value); the planner evaluates uncorrelated ones eagerly."""
    query: object = None  # ast.SelectStmt (avoid circular import)

    def __repr__(self) -> str:
        return "scalar_subquery(...)"


@dataclass
class InSubquery(Expr):
    operand: Expr = None  # type: ignore[assignment]
    query: object = None
    negated: bool = False

    def children(self):
        return [self.operand]

    def __repr__(self) -> str:
        return f"in_subquery({self.operand!r}, neg={self.negated})"


@dataclass
class Exists(Expr):
    query: object = None
    negated: bool = False

    def __repr__(self) -> str:
        return f"exists(neg={self.negated})"


def walk(e: Expr):
    yield e
    for c in e.children():
        yield from walk(c)


def transform(e: Expr, fn) -> Expr:
    """Bottom-up rewrite: fn applied to each node after its children are rewritten."""
    import copy
    n = copy.copy(e)
    if isinstance(n, Binary):
        n.left = transform(n.left, fn)
        n.right = transform(n.right, fn)
    elif isinstance(n, (Not, Negate, IsNull, Cast)):
        n.operand = transform(n.operand, fn)
    elif isinstance(n, Case):
        n.whens = [(transform(c, fn), transform(v, fn)) for c, v in n.whens]
        n.else_ = transform(n.else_, fn) if n.else_ is not None else None
    elif isinstance(n, InList):
        n.operand = transform(n.operand, fn)
        n.items = [transform(i, fn) for i in n.items]
    elif isinstance(n, Like):
        n.operand = transform(n.operand, fn)
    elif isinstance(n, Func):
        n.args = [transform(a, fn) for a in n.args]
    elif isinstance(n, Aggregate):
        n.arg = transform(n.arg, fn) if n.arg is not None else None
    elif isinstance(n, Alias):
        n.operand = transform(n.operand, fn)
    elif isinstance(n, InSubquery):
        n.operand = transform(n.operand, fn)
    elif isinstance(n, Window):
        n.args = [transform(a, fn) for a in n.args]
        n.partition_by = [transform(p, fn) for p in n.partition_by]
        n.order_by = [transform(o, fn) for o in n.order_by]
        if n.agg is not None:
            n.agg = transform(n.agg, fn)
    return fn(n)


def fingerprint(e, by_name: bool = False) -> str:
    """What a bound expression computes, as a hashable value: over one input
    schema, equal fingerprints mean equal results. A `repr` is a label, not
    that (a Column prints no index, a Literal no type, a subquery nothing of
    its query): this spells EVERY dataclass field of every node, so that a
    node which gains a field cannot make two expressions collide; what it
    cannot read field by field (a subquery's AST) equals nothing, not even
    itself on a second call. Keys a RESULT (which aggregate lanes are one,
    the staged tier's live-count hints): the value of every literal is in
    it. `by_name` as in `shape`."""
    out: list = []
    _spell(e, False, by_name, False, out)
    return "".join(out)


def shape(e, by_name: bool = False) -> str:
    """`fingerprint` without the VALUE of the literals that are arguments of
    a program (`runtime_literal`): their type and position stay. THE key form
    of programs, hints, flags, negatives and AdaptiveStats: two parameter
    sets of one query (TPC-H's substitution parameters, a dashboard's date
    picker) are one shape, so one trace, one compile, one set of hints. The
    value of a literal that sizes or selects code stays in the shape: a
    string, NULL, and every literal that is a direct argument of a function
    call (round's digits, substr's bounds: read on the host when the call is
    compiled, `ExprCompiler.compile_arg`).
    `by_name` leaves a Column's index out: the projection-insensitive form
    of `exec/hints.py plan_fp`."""
    out: list = []
    _spell(e, True, by_name, False, out)
    return "".join(out)


def runtime_literal(e: "Literal") -> bool:
    """Is this literal's value an argument of the program (a ConstPool
    scalar bound at dispatch) and no part of its key? Every typed scalar
    that lives in a numeric, date, timestamp or bool lane; not a string (a
    dictionary is built from it), not NULL (it has no lane of its own)."""
    dt = e.dtype or e.literal_type
    return (dt is not None and not dt.is_string and dt.id is not T.TypeId.NULL
            and isinstance(e.value, (bool, int, float)))


# every node class of this module -> its dataclass field names (a class
# defined elsewhere is read as it comes)
_FIELD_NAMES = {c: tuple(f.name for f in dc_fields(c))
                for c in list(globals().values())
                if isinstance(c, type) and issubclass(c, Expr)}
_OPAQUE = itertools.count()


def _spell(e, mask: bool, by_name: bool, static: bool, out: list) -> None:
    """Append the spelling of `e` to `out`: `fingerprint` (mask off) and
    `shape` (mask on). A string, not nested tuples: it is hashed at every
    lookup of a program (a str caches its hash, a tuple of tuples is walked
    again) and digested for every hint, on the host path of every query.
    Column, Literal and Binary — nine nodes in ten — are spelled in one
    format each, letter for letter what `_spell_fields` spells from their
    dataclass fields (tests/test_expr.py holds them to it)."""
    cls = type(e)
    if cls is Column:
        out.append("Column(%s,%r,%s,)" % (
            "~" if e.dtype is None else e.dtype, e.name,
            "" if by_name else "~" if e.index is None else "i%d" % e.index))
    elif cls is Binary:
        out.append("Binary(%s,%s," % ("~" if e.dtype is None else e.dtype,
                                      e.op))
        _spell(e.left, mask, by_name, False, out)
        out.append(",")
        _spell(e.right, mask, by_name, False, out)
        out.append(",)")
    elif cls is Literal and mask and not static and runtime_literal(e):
        out.append("Literal(%s,?,%s,)" % (
            "~" if e.dtype is None else e.dtype,
            "~" if e.literal_type is None else e.literal_type))
    else:
        _spell_fields(e, mask, by_name, static, out)


def _spell_fields(e, mask: bool, by_name: bool, static: bool,
                  out: list) -> None:
    """`_spell` for every node: each dataclass field, in order."""
    if isinstance(e, Expr):
        cls = type(e)
        names = _FIELD_NAMES.get(cls) or \
            tuple(f.name for f in dc_fields(cls))
        hide = skip = None
        if cls is Literal:
            hide = "value" if mask and not static and runtime_literal(e) \
                else None
        elif cls is Column and by_name:
            skip = "index"
        inner = mask and cls is Func
        out.append(cls.__name__)
        out.append("(")
        for n in names:
            if n == hide:
                out.append("?")
            elif n != skip:
                _spell(getattr(e, n), mask, by_name, inner, out)
            out.append(",")
        out.append(")")
    elif e is None:
        out.append("~")
    elif isinstance(e, str):
        out.append(repr(e))
    elif isinstance(e, (enum.Enum, T.DataType)):
        out.append(str(e))
    elif isinstance(e, (list, tuple)):
        out.append("[")
        for x in e:
            _spell(x, mask, by_name, static, out)
            out.append(",")
        out.append("]")
    elif isinstance(e, (bool, int, float)):
        # by type and spelling: 1 == 1.0 == True and 0.0 == -0.0 in Python
        out.append(type(e).__name__[0])
        out.append(repr(e))
    else:
        out.append(f"<opaque {next(_OPAQUE)}>")


def columns_in(e: Expr) -> set[str]:
    return {n.name for n in walk(e) if isinstance(n, Column)}
