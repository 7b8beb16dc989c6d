"""Expression compiler: bound Expr IR -> jnp element-wise graph.

Plays the role of DataFusion's `create_physical_expr` in the reference
(crates/engine/src/physical_planner.rs:60-64), but targets XLA: each expression
compiles to a pure function over device column lanes, returning `(values, nulls)`.
These functions compose into ONE `jax.jit` computation per fragment, so scan→filter→
project fuse with no intermediate materialization (SURVEY.md §7 design stance).

SQL three-valued logic: every compiled node yields `(vals, nulls)` with `nulls` an
optional bool lane (True = NULL). Kleene AND/OR; comparisons/arithmetic propagate NULL.

Strings: device lanes hold sorted-dictionary ids (see exec/batch.py). The compiler
turns string predicates into id comparisons / lookup-table gathers, and string
functions into host-side dictionary transforms + id remaps. String-producing
expressions therefore carry their output `DictInfo` statically (`Compiled.out_dict`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from igloo_tpu import types as T
from igloo_tpu.exec.batch import DeviceBatch, DictInfo, wide_values
from igloo_tpu.plan import expr as E
from igloo_tpu.utils import tracing


class Env:
    """Column environment a compiled expression reads from: device lanes of the input
    batch, indexed the same way the binder resolved Column.index, plus the const
    pool arrays (dictionary-derived LUTs) for this execution."""

    def __init__(self, values: list, nulls: list, consts: tuple = (),
                 columns: Optional[list] = None):
        self.values = values
        self.nulls = nulls
        self.consts = consts
        # the batch's columns as they are resident (carriers and all), for a
        # consumer that can read a carrier's own form (batch.f32_halves);
        # None on an Env built from bare lanes
        self.columns = columns

    @staticmethod
    def from_batch(batch: DeviceBatch, consts: tuple = ()) -> "Env":
        # wide_values is THE carrier decode point for operators: columns are
        # carrier-resident in HBM (exec/codec.py), and every compiled
        # expression — filters, projections, join/group/sort keys, DISTINCT —
        # reads lanes through this Env inside a jitted program, so the widen
        # fuses into the consumer and no wide lane ever materializes in HBM.
        return Env([wide_values(c) for c in batch.columns],
                   [c.nulls for c in batch.columns], consts, batch.columns)


class ConstPool:
    """Host-computed arrays (dictionary LUTs, per-entry hash lanes, parsed-cast
    tables) that compiled expressions read as runtime ARGUMENTS instead of
    trace-time constants. This is what keeps dictionary CONTENT out of the jit
    compile-cache key: two executions whose dictionaries differ only in content
    (same length bucket) reuse one compiled executable and just pass different
    const arrays (fixes round-1 verdict: DictInfo in static aux forced a full
    recompile per new dictionary).

    Arrays are padded to power-of-two lengths so the (shape, dtype) signature —
    which IS part of the cache key — buckets well.

    Scalar literals ride here too (`add_scalar`): one vector per lane dtype,
    one position per literal of the expression, in compile order. Their
    number and dtypes are in `signature()`; their values are arguments, so
    two parameter sets of one query are one program (`plan.expr.shape`)."""

    def __init__(self):
        # np.ndarray, or a _Scalars vector still being filled
        self.arrays: list = []
        self._scalars: dict = {}  # lane dtype name -> slot of its vector
        self.n_scalars = 0        # literals bound so far, over all vectors

    # pad memo keyed on the SOURCE array's id (e.g. DictInfo.hashes, which is
    # stable for a table's lifetime): repeated queries re-adding the same host
    # array get the identical padded array object back, which is what makes the
    # device-upload memo below actually hit across executions.
    _PAD_MEMO: dict = {}
    _PAD_MEMO_MAX = 512

    @classmethod
    def _padded(cls, arr: np.ndarray) -> np.ndarray:
        # id() key is SAFE here: the entry pins `arr` (ent[0]) and every hit
        # validates `ent[0] is arr`, so a recycled id can never match
        key = id(arr)  # lint: allow(cache-key)
        ent = cls._PAD_MEMO.get(key)
        if ent is not None and ent[0] is arr:
            return ent[1]
        out = np.ascontiguousarray(arr)
        from igloo_tpu.exec.batch import round_capacity
        if out.ndim == 1:
            cap = round_capacity(max(out.shape[0], 1))
            if cap != out.shape[0]:
                padded = np.zeros((cap,), dtype=out.dtype)
                padded[: out.shape[0]] = out
                out = padded
        elif out.ndim == 2:
            c0 = round_capacity(max(out.shape[0], 1))
            c1 = round_capacity(max(out.shape[1], 1))
            if (c0, c1) != out.shape:
                padded = np.zeros((c0, c1), dtype=out.dtype)
                padded[: out.shape[0], : out.shape[1]] = out
                out = padded
        _memo_put(cls._PAD_MEMO, key, (arr, out), cls._PAD_MEMO_MAX)
        return out

    def add(self, arr: np.ndarray) -> int:
        self.arrays.append(self._padded(arr))
        return len(self.arrays) - 1

    def add_scalar(self, value, np_dtype: np.dtype) -> tuple:
        """A literal's value as a runtime argument -> (slot, position): the
        compiled expression reads `env.consts[slot][position]`. A position
        per literal, never merged by value: `a > 5 AND b > 5` and
        `a > 5 AND b > 6` are one program."""
        name = np.dtype(np_dtype).name
        slot = self._scalars.get(name)
        if slot is None:
            slot = self._scalars[name] = len(self.arrays)
            self.arrays.append(_Scalars(np.dtype(np_dtype)))
        vec = self.arrays[slot]
        vec.values.append(vec.dtype.type(value))
        self.n_scalars += 1
        return slot, len(vec.values) - 1

    def literal_values(self) -> tuple:
        """Every scalar bound so far, by lane dtype, in compile order."""
        return tuple((n, tuple(self.arrays[s].values))
                     for n, s in self._scalars.items())

    def signature(self) -> tuple:
        return tuple((a.shape, str(a.dtype)) for a in self.arrays)

    # process-wide host-array -> device-array memo: repeated executions reuse
    # HBM-resident const buffers (dictionary hash lanes, LUTs) instead of
    # re-uploading per query (round-2 advisor finding). Keyed on id() with the
    # host array kept alive by the value tuple, so an id can't be recycled
    # while its entry is live; bounded FIFO eviction keeps it from growing
    # without bound when dictionaries churn.
    _DEVICE_MEMO: dict = {}
    _DEVICE_MEMO_MAX = 512

    @classmethod
    def _to_device(cls, a: np.ndarray):
        # id() key is SAFE here: the value tuple pins `a` and hits validate
        # `ent[0] is a` (see the memo comment above)
        ent = cls._DEVICE_MEMO.get(id(a))  # lint: allow(cache-key)
        if ent is not None and ent[0] is a:
            return ent[1]
        dev = jnp.asarray(a)
        _memo_put(cls._DEVICE_MEMO, id(a), (a, dev),  # lint: allow(cache-key)
                  cls._DEVICE_MEMO_MAX)
        return dev

    # scalar vectors by VALUE: the compiler builds a new vector per
    # execution, and a dashboard's handful of parameter sets comes again
    _SCALAR_MEMO: dict = {}

    @classmethod
    def _scalars_to_device(cls, vec: "_Scalars"):
        host = np.asarray(vec.values, dtype=vec.dtype)
        key = (vec.dtype.name, host.tobytes())
        dev = cls._SCALAR_MEMO.get(key)
        if dev is None:
            dev = jnp.asarray(host)
            _memo_put(cls._SCALAR_MEMO, key, dev, cls._DEVICE_MEMO_MAX)
        return dev

    def device_args(self) -> tuple:
        return tuple(self._scalars_to_device(a) if isinstance(a, _Scalars)
                     else self._to_device(a) for a in self.arrays)


def _memo_put(memo: dict, key, value, limit: int) -> None:
    """Bounded FIFO insert: at `limit` entries the older half goes."""
    if len(memo) >= limit:
        for k in list(memo)[: limit // 2]:
            del memo[k]
    memo[key] = value


class _Scalars:
    """One lane dtype's literal values of a ConstPool, as the (n,) vector
    they are passed as."""
    __slots__ = ("dtype", "values")

    def __init__(self, dtype: np.dtype):
        self.dtype = dtype
        self.values: list = []

    @property
    def shape(self) -> tuple:
        return (len(self.values),)


@dataclass
class Compiled:
    fn: Callable[[Env], tuple]  # Env -> (vals, nulls|None)
    dtype: T.DataType
    out_dict: Optional[DictInfo] = None  # set iff dtype is STRING
    # (lo, hi) host-known value bounds for integer-family outputs (bare column
    # refs / int literals); feeds the direct-join strategy choice. None = unknown.
    out_bounds: Optional[tuple] = None
    # the bound expression this was compiled from (ExprCompiler.compile sets
    # it; None on a Compiled built by hand), and how many of its literals
    # became arguments of the program. Two Compileds of one compiler whose
    # `E.fingerprint(expr)` are equal and that bind no literal compute the
    # same lanes: the direct aggregate reduces such arguments once. One that
    # binds a literal shares with nothing: its value is no part of the
    # program's key, so the NEXT execution of the program may bring two
    # values where this one brought one twice.
    expr: Optional[E.Expr] = None
    literals: int = 0


class ExprCompileError(Exception):
    pass


def _or_nulls(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a | b


def _unify_dicts(da: Optional[DictInfo], db: Optional[DictInfo]):
    """Merge two sorted dictionaries; returns (union, lut_a, lut_b) where lut_x maps
    old ids -> union ids. Host-side; dictionaries are small relative to data."""
    va = da.values if da is not None else np.asarray([], dtype=object)
    vb = db.values if db is not None else np.asarray([], dtype=object)
    union = np.asarray(sorted(set(va.tolist()) | set(vb.tolist())), dtype=object)
    uinfo = DictInfo.from_values(union)
    ustr = union.astype(str)
    lut_a = np.searchsorted(ustr, va.astype(str)).astype(np.int32) if len(va) else np.zeros(0, np.int32)
    lut_b = np.searchsorted(ustr, vb.astype(str)).astype(np.int32) if len(vb) else np.zeros(0, np.int32)
    return uinfo, lut_a, lut_b


def rank_lane(c: Compiled, comp: "ExprCompiler") -> Compiled:
    """Order-comparable lane for a string expression: the id lane itself when
    the dictionary is sorted (ids are ranks), else a gather through the
    lazily-computed rank LUT. Appends a mark — sortedness is dictionary
    CONTENT, so it must influence the caller's compile-cache key."""
    needs = c.out_dict is not None and not c.out_dict.is_sorted
    comp.marks.append(("rank_lane", needs))
    if not needs:
        return c
    ri = comp.pool.add(c.out_dict.ranks())

    def fn(env):
        v, nl = c.fn(env)
        return _gather_const(v, env.consts[ri]), nl
    return Compiled(fn, c.dtype, None)


def _remap_ids(ids, lut: np.ndarray):
    if len(lut) == 0:
        return jnp.zeros_like(ids)
    return jnp.take(jnp.asarray(lut), jnp.clip(ids, 0, len(lut) - 1))


def _gather_const(ids, lut):
    """Gather through a (padded) const-pool array passed at runtime. Live-row
    ids are always < the true dictionary length, so clipping to the padded
    length is safe; dead lanes gather padding, which nothing reads."""
    return jnp.take(lut, jnp.clip(ids, 0, lut.shape[0] - 1))


def _like_to_re2(pattern: str, case_insensitive: bool) -> str:
    """A LIKE pattern as an RE2 expression over the whole string: `%` any
    run of characters (newlines too), `_` one character, every other
    character itself (`\\x{..}`, whatever RE2 would make of it); ILIKE
    folds case."""
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(ch if ch.isascii() and ch.isalnum()
                       else f"\\x{{{ord(ch):x}}}")
    return ("(?si)" if case_insensitive else "(?s)") + "\\A" + \
        "".join(out) + "\\z"


def like_match(values, pattern: str, case_insensitive: bool) -> np.ndarray:
    """bool[len(values)]: each string's SQL LIKE verdict, matched by Arrow's
    RE2 over the whole array at once; a value that is not a string is
    matched as its text."""
    import pyarrow as pa
    import pyarrow.compute as pc
    try:
        arr = pa.array(values, type=pa.string())
    except (pa.ArrowInvalid, pa.ArrowTypeError):
        arr = pa.array([str(v) for v in values], type=pa.string())
    return pc.match_substring_regex(
        arr, _like_to_re2(pattern, case_insensitive)).fill_null(
            False).to_numpy(zero_copy_only=False)


def like_lut(d: DictInfo, pattern: str, case_insensitive: bool) -> np.ndarray:
    """bool[len(d)]: each dictionary entry's LIKE verdict, memoized on the
    DictInfo: a resident column's dictionary is the same object on every
    plan walk, so the table is matched once and, being the same array,
    padded and uploaded once by ConstPool's memos. A dictionary can hold an
    entry a row (TPC-H's O_COMMENT: 15 M at SF10), so matching it again on
    each walk would cost seconds a query."""
    memo = getattr(d, "_like_verdicts", None)
    if memo is None:
        memo = {}
        object.__setattr__(d, "_like_verdicts", memo)
    key = (pattern, case_insensitive)
    lut = memo.get(key)
    if lut is None:
        lut = memo[key] = like_match(d.values, pattern, case_insensitive)
    return lut


# --- date math (civil calendar <-> days since 1970-01-01; vectorized, int ops only,
#     after Howard Hinnant's algorithms — jit/TPU friendly) -----------------------

def civil_from_days(z):
    z = z.astype(jnp.int32) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(doe - doe // 1460 + doe // 36524 - doe // 146096, 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def days_from_civil_py(y: int, m: int, d: int) -> int:
    y -= m <= 2
    era = (y if y >= 0 else y - 399) // 400
    yoe = y - era * 400
    doy = (153 * (m + (-3 if m > 2 else 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


# ---------------------------------------------------------------------------------

class ExprCompiler:
    """Compiles bound expressions against a fixed input batch *prototype* (schema +
    per-column dictionaries). The produced callables are jit-traceable.

    Dictionary-derived values feed the callables through `pool` (see ConstPool);
    every structural decision that depends on dictionary content (not just its
    shape) is appended to `marks`, and (pool.signature(), marks) joins the
    executor's compile-cache key — so a cached executable is only reused when
    the new compile would have traced the identical program."""

    def __init__(self, dicts: list, pool: Optional[ConstPool] = None,
                 bounds: Optional[list] = None):
        self.dicts = dicts  # per input-column Optional[DictInfo]
        self.bounds = bounds  # per input-column Optional[(lo, hi)]; None = all unknown
        self.pool = pool if pool is not None else ConstPool()
        self.marks: list = []

    @staticmethod
    def for_batch(batch: DeviceBatch, pool: Optional[ConstPool] = None) -> "ExprCompiler":
        return ExprCompiler([c.dictionary for c in batch.columns], pool,
                            bounds=[c.bounds for c in batch.columns])

    def compile(self, e: E.Expr) -> Compiled:
        m = getattr(self, "_c_" + type(e).__name__.lower(), None)
        if m is None:
            raise ExprCompileError(f"cannot compile {type(e).__name__}: {e!r}")
        n0 = self.pool.n_scalars
        c = m(e)
        c.expr, c.literals = e, self.pool.n_scalars - n0
        return c

    def compile_arg(self, e: E.Expr) -> Compiled:
        """A direct argument of a function call: a literal there is read on
        the host when the call is compiled (round's digits, substr's bounds),
        so `E.shape` keeps its value in the program's key and it is traced
        as a constant."""
        if not isinstance(e, E.Literal):
            return self.compile(e)
        c = self._c_literal(e, static=True)
        c.expr = e
        return c

    # --- leaves ---

    def _c_column(self, e: E.Column) -> Compiled:
        idx = e.index
        if idx is None:
            raise ExprCompileError(f"unbound column {e.name}")
        d = self.dicts[idx] if idx < len(self.dicts) else None
        b = self.bounds[idx] if self.bounds and idx < len(self.bounds) else None
        return Compiled(lambda env: (env.values[idx], env.nulls[idx]), e.dtype,
                        d, out_bounds=b)

    def _c_literal(self, e: E.Literal, static: bool = False) -> Compiled:
        dt = e.dtype or e.literal_type
        if not static and E.runtime_literal(e):
            # the value is an ARGUMENT: a scalar of the pool, bound at
            # dispatch; its lane dtype is in the pool's signature
            np_dtype = dt.device_dtype()
            slot, k = self.pool.add_scalar(e.value, np_dtype)
            return Compiled(
                lambda env: (jnp.broadcast_to(env.consts[slot][k],
                                              (_cap(env),)), None), dt, None)
        # the value is part of the program's shape, and of its key; NULL is
        # not counted: it has no value that a parameter set could change
        # (the planner writes one per AVG into every merge fragment)
        if e.value is None:
            return Compiled(
                lambda env: (jnp.zeros_like(env.values[0] if env.values else jnp.zeros(1), dtype=jnp.int32),
                             jnp.ones(env.values[0].shape if env.values else (1,), dtype=bool)),
                T.NULL, None)
        tracing.counter("program.literal_keyed")
        if dt is not None and dt.is_string:
            dinfo = DictInfo.from_values([e.value])
            return Compiled(lambda env: (jnp.zeros(_cap(env), dtype=jnp.int32), None), dt, dinfo)
        np_dtype = dt.device_dtype() if dt else np.dtype("float64")
        val = np_dtype.type(e.value)
        return Compiled(lambda env: (jnp.full(_cap(env), val, dtype=np_dtype), None), dt, None)

    def _c_alias(self, e: E.Alias) -> Compiled:
        return self.compile(e.operand)

    def _c_cast(self, e: E.Cast) -> Compiled:
        c = self.compile(e.operand)
        to = e.to
        if c.dtype.id == T.TypeId.DATE32 and to.id == T.TypeId.TIMESTAMP:
            def fn(env):
                vals, nulls = c.fn(env)
                return vals.astype(jnp.int64) * np.int64(86_400_000_000), nulls
            return Compiled(fn, to, None)
        if c.dtype.id == T.TypeId.TIMESTAMP and to.id == T.TypeId.DATE32:
            def fn(env):
                vals, nulls = c.fn(env)
                return jnp.floor_divide(vals, np.int64(86_400_000_000)).astype(jnp.int32), nulls
            return Compiled(fn, to, None)
        if c.dtype.is_string and not to.is_string:
            # cast string -> numeric/temporal: parse the dictionary host-side
            d = c.out_dict
            dlen = len(d) if d is not None else 0
            parsed = np.zeros(max(dlen, 1), dtype=to.device_dtype())
            bad = np.zeros(max(dlen, 1), dtype=bool)
            for i, v in enumerate(d.values if d else []):
                if to.is_temporal:
                    # ISO date/timestamp strings. Unparseable entries become
                    # NULL (bad-flag), matching the numeric branch below: the
                    # dictionary covers the WHOLE column as scanned, so entries
                    # excluded by filters must not poison the query.
                    import datetime as _dt
                    try:
                        if to.id == T.TypeId.DATE32:
                            dd = _dt.date.fromisoformat(str(v).strip())
                            parsed[i] = dd.toordinal() - _dt.date(1970, 1, 1).toordinal()
                        else:
                            ts = _dt.datetime.fromisoformat(str(v).strip())
                            if ts.tzinfo is not None:
                                ts = ts.astimezone(_dt.timezone.utc) \
                                    .replace(tzinfo=None)
                            parsed[i] = (ts - _dt.datetime(1970, 1, 1)) \
                                // _dt.timedelta(microseconds=1)
                    except (ValueError, TypeError):
                        bad[i] = True
                    continue
                try:
                    parsed[i] = to.device_dtype().type(float(v) if to.is_float else int(float(v)))
                except (ValueError, TypeError):
                    bad[i] = True
            pi, bi = self.pool.add(parsed), self.pool.add(bad)

            def fn(env):
                vals, nulls = c.fn(env)
                return (_gather_const(vals, env.consts[pi]),
                        _or_nulls(nulls, _gather_const(vals, env.consts[bi])))
            return Compiled(fn, to, None)
        if not c.dtype.is_string and to.is_string:
            raise ExprCompileError("cast to string is evaluated host-side only")
        np_dtype = to.device_dtype()

        def fn(env):
            vals, nulls = c.fn(env)
            return vals.astype(np_dtype), nulls
        return Compiled(fn, to, c.out_dict if to.is_string else None)

    # --- boolean / null ---

    def _c_not(self, e: E.Not) -> Compiled:
        c = self.compile(e.operand)

        def fn(env):
            vals, nulls = c.fn(env)
            return ~vals, nulls
        return Compiled(fn, T.BOOL, None)

    def _c_negate(self, e: E.Negate) -> Compiled:
        c = self.compile(e.operand)

        def fn(env):
            vals, nulls = c.fn(env)
            return -vals, nulls
        return Compiled(fn, c.dtype, None)

    def _c_isnull(self, e: E.IsNull) -> Compiled:
        c = self.compile(e.operand)
        neg = e.negated

        def fn(env):
            vals, nulls = c.fn(env)
            isn = nulls if nulls is not None else jnp.zeros(vals.shape, dtype=bool)
            return (~isn if neg else isn), None
        return Compiled(fn, T.BOOL, None)

    # --- binary ---

    def _c_binary(self, e: E.Binary) -> Compiled:
        lc, rc = self.compile(e.left), self.compile(e.right)
        op = e.op
        if op in (E.BinOp.AND, E.BinOp.OR):
            return self._compile_kleene(op, lc, rc)
        if lc.dtype.is_string and rc.dtype.is_string:
            return self._compile_string_compare(op, lc, rc)
        if lc.dtype.is_string or rc.dtype.is_string:
            raise ExprCompileError(f"type mismatch in {e!r}")
        return self._compile_numeric_binary(op, lc, rc, e.dtype)

    def _compile_kleene(self, op, lc: Compiled, rc: Compiled) -> Compiled:
        if op is E.BinOp.AND:
            def fn(env):
                lv, ln = lc.fn(env)
                rv, rn = rc.fn(env)
                val = lv & rv
                if ln is None and rn is None:
                    return val, None
                lt = lv | (ln if ln is not None else False)
                rt = rv | (rn if rn is not None else False)
                ln_ = ln if ln is not None else jnp.zeros(lv.shape, bool)
                rn_ = rn if rn is not None else jnp.zeros(rv.shape, bool)
                # NULL unless one side is definitively FALSE
                nulls = (ln_ | rn_) & lt & rt
                return val & ~nulls, nulls
        else:
            def fn(env):
                lv, ln = lc.fn(env)
                rv, rn = rc.fn(env)
                val = lv | rv
                if ln is None and rn is None:
                    return val, None
                lf = ~lv | (ln if ln is not None else False)
                rf = ~rv | (rn if rn is not None else False)
                ln_ = ln if ln is not None else jnp.zeros(lv.shape, bool)
                rn_ = rn if rn is not None else jnp.zeros(rv.shape, bool)
                nulls = (ln_ | rn_) & lf & rf
                return val & ~nulls, nulls
        return Compiled(fn, T.BOOL, None)

    def _compile_numeric_binary(self, op, lc: Compiled, rc: Compiled, out_dtype) -> Compiled:
        if op in E.COMPARISONS:
            res_dtype = T.BOOL
            wd = T.common_type(lc.dtype, rc.dtype).device_dtype()
        else:
            res_dtype = out_dtype or T.common_type(lc.dtype, rc.dtype)
            wd = res_dtype.device_dtype()
        integer_div = op is E.BinOp.DIV and res_dtype.is_integer
        # DATE32 lanes are days, TIMESTAMP lanes are microseconds: when the two mix,
        # scale the date side up so comparisons/arithmetic share one unit.
        scale_l = (lc.dtype.id == T.TypeId.DATE32 and rc.dtype.id == T.TypeId.TIMESTAMP)
        scale_r = (rc.dtype.id == T.TypeId.DATE32 and lc.dtype.id == T.TypeId.TIMESTAMP)

        def fn(env):
            lv, ln = lc.fn(env)
            rv, rn = rc.fn(env)
            if scale_l:
                lv = lv.astype(jnp.int64) * np.int64(86_400_000_000)
            if scale_r:
                rv = rv.astype(jnp.int64) * np.int64(86_400_000_000)
            lvw = lv.astype(wd) if lv.dtype != wd else lv
            rvw = rv.astype(wd) if rv.dtype != wd else rv
            nulls = _or_nulls(ln, rn)
            if op is E.BinOp.ADD:
                out = lvw + rvw
            elif op is E.BinOp.SUB:
                out = lvw - rvw
            elif op is E.BinOp.MUL:
                out = lvw * rvw
            elif op is E.BinOp.DIV:
                if integer_div:  # SQL truncating integer division; x/0 -> NULL
                    zero = rvw == 0
                    safe = jnp.where(zero, 1, rvw)
                    q = jnp.trunc(lvw.astype(jnp.float64) / safe.astype(jnp.float64)).astype(wd)
                    out = jnp.where(zero, 0, q)
                    nulls = _or_nulls(nulls, zero)
                else:
                    zero = rvw == 0
                    out = jnp.where(zero, 0, lvw / jnp.where(zero, 1, rvw))
                    nulls = _or_nulls(nulls, zero)
            elif op is E.BinOp.MOD:
                zero = rvw == 0
                safe = jnp.where(zero, 1, rvw)
                out = lvw - jnp.trunc(lvw.astype(jnp.float64) / safe.astype(jnp.float64)).astype(wd) * safe
                nulls = _or_nulls(nulls, zero)
            elif op is E.BinOp.EQ:
                out = lvw == rvw
            elif op is E.BinOp.NEQ:
                out = lvw != rvw
            elif op is E.BinOp.LT:
                out = lvw < rvw
            elif op is E.BinOp.LTE:
                out = lvw <= rvw
            elif op is E.BinOp.GT:
                out = lvw > rvw
            else:
                out = lvw >= rvw
            return out, nulls
        return Compiled(fn, res_dtype, None)

    def _compile_string_compare(self, op, lc: Compiled, rc: Compiled) -> Compiled:
        """Compare two string expressions. Same-dictionary columns compare by id
        (sorted dictionary => ids are lexicographic ranks; unsorted => order
        comparisons go through the rank LUT); otherwise remap both through the
        union dictionary host-side, then compare ids."""
        same = lc.out_dict is rc.out_dict and lc.out_dict is not None
        self.marks.append(("strcmp_same", same))
        if same:
            li = ri = None
            if op not in (E.BinOp.EQ, E.BinOp.NEQ):
                lc = rank_lane(lc, self)
                rc = rank_lane(rc, self)
        else:
            _, lut_l, lut_r = _unify_dicts(lc.out_dict, rc.out_dict)
            li, ri = self.pool.add(lut_l), self.pool.add(lut_r)

        def fn(env):
            lv, ln = lc.fn(env)
            rv, rn = rc.fn(env)
            if li is not None:
                lv = _gather_const(lv, env.consts[li])
                rv = _gather_const(rv, env.consts[ri])
            nulls = _or_nulls(ln, rn)
            if op is E.BinOp.EQ:
                out = lv == rv
            elif op is E.BinOp.NEQ:
                out = lv != rv
            elif op is E.BinOp.LT:
                out = lv < rv
            elif op is E.BinOp.LTE:
                out = lv <= rv
            elif op is E.BinOp.GT:
                out = lv > rv
            elif op is E.BinOp.GTE:
                out = lv >= rv
            else:
                raise ExprCompileError(f"string op {op}")
            return out, nulls
        return Compiled(fn, T.BOOL, None)

    # --- CASE / IN / LIKE ---

    def _c_case(self, e: E.Case) -> Compiled:
        whens = [(self.compile(c), self.compile(v)) for c, v in e.whens]
        else_c = self.compile(e.else_) if e.else_ is not None else None
        out_dtype = e.dtype
        if out_dtype.is_string:
            branches = [v for _, v in whens] + ([else_c] if else_c else [])
            all_vals = sorted({str(v) for b in branches if b.out_dict is not None
                               for v in b.out_dict.values})
            out_dict = DictInfo.from_values(np.asarray(all_vals, dtype=object))
            ustr = out_dict.values.astype(str) if len(out_dict) else np.asarray([], dtype=str)
            luts = []
            for b in branches:
                bv = b.out_dict.values if b.out_dict is not None else np.asarray([], dtype=object)
                luts.append(self.pool.add(
                    np.searchsorted(ustr, bv.astype(str)).astype(np.int32)
                    if len(bv) else np.zeros(0, np.int32)))
        else:
            luts = None
            out_dict = None
        wd = out_dtype.device_dtype()

        def fn(env):
            vals = [v.fn(env) for _, v in whens]
            conds = [c.fn(env) for c, _ in whens]
            if else_c is not None:
                ev, en = else_c.fn(env)
            else:
                ev = jnp.zeros(_cap(env), dtype=wd)
                en = jnp.ones(_cap(env), dtype=bool)
            if luts is not None:
                vals = [(_gather_const(v, env.consts[luts[i]]), nn)
                        for i, (v, nn) in enumerate(vals)]
                if else_c is not None:
                    ev = _gather_const(ev, env.consts[luts[-1]])
            out = ev.astype(wd)
            out_null = en if en is not None else jnp.zeros(_cap(env), bool)
            # fold from last WHEN to first so earlier WHENs win
            for (cv, cn), (vv, vn) in zip(reversed(conds), reversed(vals)):
                take = cv & (~cn if cn is not None else True)
                out = jnp.where(take, vv.astype(wd), out)
                vn_ = vn if vn is not None else jnp.zeros(_cap(env), bool)
                out_null = jnp.where(take, vn_, out_null)
            return out, out_null
        return Compiled(fn, out_dtype, out_dict)

    def _c_inlist(self, e: E.InList) -> Compiled:
        c = self.compile(e.operand)
        neg = e.negated
        tracing.counter("program.literal_keyed")  # the list's length
        has_null_item = any(isinstance(i, E.Literal) and i.value is None for i in e.items)
        items = [i for i in e.items if not (isinstance(i, E.Literal) and i.value is None)]
        if c.dtype.is_string:
            # membership over the dictionary host-side -> id lookup table
            for i in items:
                if not isinstance(i, E.Literal):
                    raise ExprCompileError("string IN list items must be literals")
            item_vals = {i.value for i in items}
            d = c.out_dict
            dlen = len(d) if d is not None else 0
            lut = np.zeros(max(dlen, 1), dtype=bool)
            for i, v in enumerate(d.values if d is not None else []):
                lut[i] = v in item_vals
            lj = self.pool.add(lut)

            def fn(env):
                vals, nulls = c.fn(env)
                out = _gather_const(vals, env.consts[lj])
                if has_null_item:
                    # x IN (..., NULL): NULL unless a real match; NOT IN never TRUE
                    nulls = _or_nulls(nulls, ~out)
                return (~out if neg else out), nulls
            return Compiled(fn, T.BOOL, None)
        item_cs = [self.compile(i) for i in items]
        # SQL compares in the common type: widen both sides (a=1 IN (1.5) is FALSE,
        # not a truncated match)
        wide = c.dtype
        for ic in item_cs:
            wide = T.common_type(wide, ic.dtype)
        wd = wide.device_dtype()

        def fn(env):
            vals, nulls = c.fn(env)
            vw = vals.astype(wd)
            out = jnp.zeros(vals.shape, dtype=bool)
            for ic in item_cs:
                iv, _ = ic.fn(env)
                out = out | (vw == iv.astype(wd))
            if has_null_item:
                nulls = _or_nulls(nulls, ~out)
            return (~out if neg else out), nulls
        return Compiled(fn, T.BOOL, None)

    def _c_like(self, e: E.Like) -> Compiled:
        c = self.compile(e.operand)
        if not c.dtype.is_string:
            raise ExprCompileError("LIKE on non-string")
        tracing.counter("program.literal_keyed")  # the pattern
        d = c.out_dict
        lut = like_lut(d, e.pattern, e.case_insensitive) if d \
            else np.zeros(1, dtype=bool)
        neg = e.negated
        lj = self.pool.add(lut)

        def fn(env):
            vals, nulls = c.fn(env)
            out = _gather_const(vals, env.consts[lj])
            return (~out if neg else out), nulls
        return Compiled(fn, T.BOOL, None)

    # --- scalar functions ---

    def _c_func(self, e: E.Func) -> Compiled:
        name = e.name.lower()
        args = [self.compile_arg(a) for a in e.args]
        if name in _STRING_FUNCS:
            return self._compile_string_func(name, e, args)
        if name in ("year", "month", "day", "extract_year", "extract_month", "extract_day"):
            which = name.split("_")[-1]
            c = args[0]

            def fn(env, _which=which):
                vals, nulls = c.fn(env)
                if c.dtype.id == T.TypeId.TIMESTAMP:
                    vals = jnp.floor_divide(vals, np.int64(86_400_000_000)).astype(jnp.int32)
                y, m, d = civil_from_days(vals)
                return {"year": y, "month": m, "day": d}[_which].astype(jnp.int32), nulls
            return Compiled(fn, T.INT32, None)
        if name == "coalesce":
            out_dtype = e.dtype
            if out_dtype.is_string:
                # unify all argument dictionaries so every branch's ids decode
                # against one output dictionary
                all_vals = sorted({str(v) for a in args if a.out_dict is not None
                                   for v in a.out_dict.values})
                od = DictInfo.from_values(np.asarray(all_vals, dtype=object))
                ustr = od.values.astype(str) if len(od) else np.asarray([], dtype=str)
                luts = []
                for a in args:
                    av = a.out_dict.values if a.out_dict is not None else np.asarray([], dtype=object)
                    luts.append(self.pool.add(
                        np.searchsorted(ustr, av.astype(str)).astype(np.int32)
                        if len(av) else np.zeros(0, np.int32)))
            else:
                od, luts = None, None

            def fn(env):
                out_v = None
                out_n = None
                for i, c in enumerate(args):
                    v, nn = c.fn(env)
                    if luts is not None:
                        v = _gather_const(v, env.consts[luts[i]])
                    v = v.astype(out_dtype.device_dtype())
                    if out_v is None:
                        out_v, out_n = v, (nn if nn is not None else jnp.zeros(v.shape, bool))
                    else:
                        take = out_n
                        out_v = jnp.where(take, v, out_v)
                        nn_ = nn if nn is not None else jnp.zeros(v.shape, bool)
                        out_n = out_n & nn_
                return out_v, out_n
            return Compiled(fn, out_dtype, od)
        if name == "nullif":
            a, b = args
            unify = a.dtype.is_string and b.dtype.is_string and \
                a.out_dict is not b.out_dict
            self.marks.append(("nullif_unify", unify))
            if unify:
                _, lut_a, lut_b = _unify_dicts(a.out_dict, b.out_dict)
                ai, bi = self.pool.add(lut_a), self.pool.add(lut_b)
            else:
                ai = bi = None

            def fn(env):
                av, an = a.fn(env)
                bv, bn = b.fn(env)
                acmp = _gather_const(av, env.consts[ai]) if ai is not None else av
                bcmp = _gather_const(bv, env.consts[bi]) if bi is not None else bv
                eq = (acmp == bcmp) & (~bn if bn is not None else True)
                return av, _or_nulls(an, eq)
            return Compiled(fn, a.dtype, a.out_dict)
        unary = {
            "abs": jnp.abs, "floor": jnp.floor, "ceil": jnp.ceil, "sqrt": jnp.sqrt,
            "exp": jnp.exp, "ln": jnp.log, "log": jnp.log, "log10": jnp.log10,
            "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan, "sign": jnp.sign,
        }
        if name in unary:
            c = args[0]
            f = unary[name]
            out_dtype = e.dtype

            def fn(env):
                vals, nulls = c.fn(env)
                return f(vals.astype(out_dtype.device_dtype())), nulls
            return Compiled(fn, out_dtype, None)
        if name == "round":
            c = args[0]
            digits = _literal_int_arg(name, e.args, 1) if len(e.args) > 1 else 0
            scale = 10.0 ** digits

            def fn(env):
                vals, nulls = c.fn(env)
                return jnp.round(vals.astype(jnp.float64) * scale) / scale, nulls
            return Compiled(fn, T.FLOAT64, None)
        if name in ("power", "pow"):
            a, b = args

            def fn(env):
                av, an = a.fn(env)
                bv, bn = b.fn(env)
                return jnp.power(av.astype(jnp.float64), bv.astype(jnp.float64)), _or_nulls(an, bn)
            return Compiled(fn, T.FLOAT64, None)
        raise ExprCompileError(f"unknown function {name}")

    def _compile_string_func(self, name: str, e: E.Func, args: list) -> Compiled:
        """String functions evaluate over the dictionary on host; device ids remap."""
        c = args[0]
        d = c.out_dict or DictInfo.from_values([])

        def str_transform(f):
            new_vals = [f(str(v)) for v in d.values]
            uniq, inverse = np.unique(np.asarray(new_vals, dtype=object).astype(str), return_inverse=True)
            new_dict = DictInfo.from_values(uniq.astype(object))
            li = self.pool.add(inverse.astype(np.int32)
                               if len(new_vals) else np.zeros(0, np.int32))

            def fn(env):
                vals, nulls = c.fn(env)
                return _gather_const(vals, env.consts[li]), nulls
            return Compiled(fn, T.STRING, new_dict)

        if name == "upper":
            return str_transform(lambda s: s.upper())
        if name == "lower":
            return str_transform(lambda s: s.lower())
        if name == "capitalize":
            # parity with the reference's capitalize UDF (crates/engine/src/lib.rs:71-95):
            # first char upper, rest lower
            return str_transform(lambda s: (s[:1].upper() + s[1:].lower()) if s else s)
        if name == "trim":
            return str_transform(lambda s: s.strip())
        if name in ("left", "right"):
            n_chars = _literal_int_arg(name, e.args, 1)
            if name == "left":
                return str_transform(lambda s: s[:n_chars])
            return str_transform(lambda s: s[-n_chars:] if n_chars else "")
        if name in ("substr", "substring"):
            start = _literal_int_arg(name, e.args, 1)
            length = _literal_int_arg(name, e.args, 2) if len(e.args) > 2 else None
            i0 = max(start - 1, 0)

            def sub(s):
                return s[i0: i0 + length] if length is not None else s[i0:]
            return str_transform(sub)
        if name in ("length", "char_length", "character_length"):
            lens = np.asarray([len(str(v)) for v in d.values], dtype=np.int32)
            lj = self.pool.add(lens)

            def fn(env):
                vals, nulls = c.fn(env)
                return _gather_const(vals, env.consts[lj]), nulls
            return Compiled(fn, T.INT32, None)
        if name == "concat":
            # concat of string exprs: only dictionary-expressible when arity small;
            # compile as pairwise host product — practical for low-cardinality dims
            if len(args) == 1:
                return args[0]
            left = args[0]
            for right in args[1:]:
                left = self._concat2(left, right)
            return left
        raise ExprCompileError(f"unknown string function {name}")

    def _concat2(self, lc: Compiled, rc: Compiled) -> Compiled:
        dl = lc.out_dict or DictInfo.from_values([])
        dr = rc.out_dict or DictInfo.from_values([])
        nl, nr = max(len(dl), 1), max(len(dr), 1)
        if nl * nr > 1_000_000:
            raise ExprCompileError("concat dictionary product too large")
        prod = np.asarray([str(a) + str(b) for a in (dl.values if len(dl) else [""])
                           for b in (dr.values if len(dr) else [""])], dtype=object)
        uniq, inverse = np.unique(prod.astype(str), return_inverse=True)
        new_dict = DictInfo.from_values(uniq.astype(object))
        lj = self.pool.add(inverse.astype(np.int32).reshape(nl, nr))

        def fn(env):
            lv, ln = lc.fn(env)
            rv, rn = rc.fn(env)
            lut = env.consts[lj]
            li = jnp.clip(lv, 0, lut.shape[0] - 1)
            ri = jnp.clip(rv, 0, lut.shape[1] - 1)
            return lut[li, ri], _or_nulls(ln, rn)
        return Compiled(fn, T.STRING, new_dict)


_STRING_FUNCS = {"upper", "lower", "capitalize", "trim", "substr", "substring",
                 "length", "char_length", "character_length", "concat", "left", "right"}


def _literal_int_arg(fname: str, args: list, i: int) -> int:
    """Dictionary-level string transforms need static (literal) count arguments."""
    if i >= len(args):
        raise ExprCompileError(f"{fname} expects an argument at position {i + 1}")
    a = args[i]
    if not isinstance(a, E.Literal) or isinstance(a.value, bool) or \
            not isinstance(a.value, (int, float)):
        raise ExprCompileError(f"{fname} argument {i + 1} must be an integer literal")
    return int(a.value)


def _cap(env: Env) -> int:
    return env.values[0].shape[0] if env.values else 1
