"""Expression compiler tests: IR -> jnp, SQL null semantics, string dictionary tricks."""
import numpy as np
import pyarrow as pa
import pytest

from igloo_tpu import types as T
from igloo_tpu.exec import batch as B
from igloo_tpu.exec.expr_compile import Env, ExprCompiler
from igloo_tpu.plan import expr as E


def make_batch():
    t = pa.table({
        "a": pa.array([1, 2, 3, 4], type=pa.int64()),
        "b": pa.array([10.0, None, 30.0, 40.0], type=pa.float64()),
        "s": pa.array(["foo", "bar", "FOO", None]),
        "d": pa.array([8766, 9131, 10000, 10592], type=pa.int32()).cast(pa.date32()),
    })
    return B.from_arrow(t)


def col(name, batch, dtype):
    c = E.Column(name)
    c.index = batch.schema.index_of(name)
    c.dtype = dtype
    return c


def lit(v, dtype):
    l = E.Literal(v, dtype)
    l.dtype = dtype
    return l


def run(expr, batch):
    compiler = ExprCompiler.for_batch(batch)
    comp = compiler.compile(expr)
    vals, nulls = comp.fn(Env.from_batch(batch, compiler.pool.device_args()))
    live = np.asarray(batch.live)
    v = np.asarray(vals)[live]
    n = np.asarray(nulls)[live] if nulls is not None else np.zeros(len(v), bool)
    return v, n, comp


def test_arithmetic_with_nulls():
    b = make_batch()
    e = E.Binary(E.BinOp.ADD, col("a", b, T.INT64), col("b", b, T.FLOAT64))
    e.dtype = T.FLOAT64
    v, n, _ = run(e, b)
    assert v[0] == 11.0 and v[2] == 33.0
    assert list(n) == [False, True, False, False]


def test_comparison_and_kleene_and():
    b = make_batch()
    cmp1 = E.Binary(E.BinOp.GT, col("a", b, T.INT64), lit(1, T.INT64))
    cmp1.dtype = T.BOOL
    cmp2 = E.Binary(E.BinOp.LT, col("b", b, T.FLOAT64), lit(35.0, T.FLOAT64))
    cmp2.dtype = T.BOOL
    e = E.Binary(E.BinOp.AND, cmp1, cmp2)
    e.dtype = T.BOOL
    v, n, _ = run(e, b)
    # row0: a>1 F -> F (definite); row1: T AND NULL -> NULL; row2: T&T; row3: T&F
    assert list(v & ~n) == [False, False, True, False]
    assert list(n) == [False, True, False, False]


def test_div_by_zero_is_null():
    b = make_batch()
    e = E.Binary(E.BinOp.DIV, col("a", b, T.INT64), lit(0, T.INT64))
    e.dtype = T.INT64
    v, n, _ = run(e, b)
    assert all(n)


def test_string_eq_literal():
    b = make_batch()
    e = E.Binary(E.BinOp.EQ, col("s", b, T.STRING), lit("foo", T.STRING))
    e.dtype = T.BOOL
    v, n, _ = run(e, b)
    assert list(v[:3]) == [True, False, False]
    assert list(n) == [False, False, False, True]


def test_like():
    b = make_batch()
    e = E.Like(col("s", b, T.STRING), "%o")
    e.dtype = T.BOOL
    v, n, _ = run(e, b)
    assert list(v[:3]) == [True, False, False]  # FOO ends in O not o


def test_upper_then_eq():
    b = make_batch()
    up = E.Func("upper", [col("s", b, T.STRING)])
    up.dtype = T.STRING
    e = E.Binary(E.BinOp.EQ, up, lit("FOO", T.STRING))
    e.dtype = T.BOOL
    v, n, _ = run(e, b)
    assert list(v[:3]) == [True, False, True]


def test_capitalize_matches_reference_udf():
    # parity: reference capitalize UDF (crates/engine/src/lib.rs:71-95)
    t = pa.table({"s": pa.array(["hello", "wORLD", ""])})
    b = B.from_arrow(t)
    e = E.Func("capitalize", [col("s", b, T.STRING)])
    e.dtype = T.STRING
    compiler = ExprCompiler.for_batch(b)
    comp = compiler.compile(e)
    vals, _ = comp.fn(Env.from_batch(b, compiler.pool.device_args()))
    ids = np.asarray(vals)[:3]
    out = [comp.out_dict.values[i] for i in ids]
    assert out == ["Hello", "World", ""]


def test_case_expr():
    b = make_batch()
    cond = E.Binary(E.BinOp.GTE, col("a", b, T.INT64), lit(3, T.INT64))
    cond.dtype = T.BOOL
    e = E.Case([(cond, lit(1, T.INT64))], lit(0, T.INT64))
    e.dtype = T.INT64
    v, n, _ = run(e, b)
    assert list(v) == [0, 0, 1, 1]


def test_extract_year_month():
    b = make_batch()
    e = E.Func("year", [col("d", b, T.DATE32)])
    e.dtype = T.INT32
    v, n, _ = run(e, b)
    # days 8766=1994-01-01, 9131=1995-01-01, 10000=1997-05-19, 10592=1999-01-01
    assert list(v) == [1994, 1995, 1997, 1999]
    e2 = E.Func("month", [col("d", b, T.DATE32)])
    e2.dtype = T.INT32
    v2, _, _ = run(e2, b)
    assert list(v2) == [1, 1, 5, 1]


def test_in_list_string():
    b = make_batch()
    e = E.InList(col("s", b, T.STRING), [lit("foo", T.STRING), lit("FOO", T.STRING)])
    e.dtype = T.BOOL
    v, n, _ = run(e, b)
    assert list(v[:3]) == [True, False, True]


def test_is_null():
    b = make_batch()
    e = E.IsNull(col("b", b, T.FLOAT64))
    e.dtype = T.BOOL
    v, n, _ = run(e, b)
    assert list(v) == [False, True, False, False]
    assert not any(n)


def test_substr_and_length():
    t = pa.table({"s": pa.array(["hello", "hi"])})
    b = B.from_arrow(t)
    e = E.Func("substr", [col("s", b, T.STRING), lit(1, T.INT64), lit(2, T.INT64)])
    e.dtype = T.STRING
    compiler = ExprCompiler.for_batch(b)
    comp = compiler.compile(e)
    vals, _ = comp.fn(Env.from_batch(b, compiler.pool.device_args()))
    ids = np.asarray(vals)[:2]
    assert [comp.out_dict.values[i] for i in ids] == ["he", "hi"]
    e2 = E.Func("length", [col("s", b, T.STRING)])
    e2.dtype = T.INT32
    v, _, _ = run(e2, b)
    assert list(v) == [5, 2]


def test_in_list_no_fractional_truncation():
    b = make_batch()
    e = E.InList(col("a", b, T.INT64), [lit(1.5, T.FLOAT64), lit(3.0, T.FLOAT64)])
    e.dtype = T.BOOL
    v, n, _ = run(e, b)
    assert list(v) == [False, False, True, False]  # 1 must NOT match 1.5


def test_in_list_null_item_semantics():
    b = make_batch()
    nl = E.Literal(None, None)
    e = E.InList(col("a", b, T.INT64), [lit(2, T.INT64), nl])
    e.dtype = T.BOOL
    v, n, _ = run(e, b)
    assert (v[1], n[1]) == (True, False)      # match -> TRUE
    assert n[0] and n[2] and n[3]             # non-match with NULL item -> NULL


def test_date_vs_timestamp_comparison_scales():
    b = make_batch()
    # d row0 = day 8766 (1994-01-01); timestamp literal 1994-06-01 in us
    ts_us = 8917 * 86_400_000_000
    e = E.Binary(E.BinOp.LT, col("d", b, T.DATE32), lit(ts_us, T.TIMESTAMP))
    e.dtype = T.BOOL
    v, n, _ = run(e, b)
    assert list(v) == [True, False, False, False]


def test_coalesce_cross_dictionary_strings():
    t = pa.table({
        "x": pa.array(["aa", None]),
        "y": pa.array(["zz", "zz"]),
    })
    b = B.from_arrow(t)
    e = E.Func("coalesce", [col("x", b, T.STRING), col("y", b, T.STRING)])
    e.dtype = T.STRING
    compiler = ExprCompiler.for_batch(b)
    comp = compiler.compile(e)
    vals, nulls = comp.fn(Env.from_batch(b, compiler.pool.device_args()))
    ids = np.asarray(vals)[:2]
    assert [comp.out_dict.values[i] for i in ids] == ["aa", "zz"]


def test_cast_date_to_timestamp():
    b = make_batch()
    e = E.Cast(col("d", b, T.DATE32))
    e.to = T.TIMESTAMP
    e.dtype = T.TIMESTAMP
    v, n, _ = run(e, b)
    assert v[0] == 8766 * 86_400_000_000


# --- the two spellings of an expression (ISSUE 34) ---

def _sample_exprs():
    from igloo_tpu.plan import expr as E

    def typed(e, dt):
        e.dtype = dt
        return e
    col = typed(E.Column("t.x", index=3), T.FLOAT64)
    unbound = E.Column("y")
    lits = [typed(E.Literal(5, T.INT64), T.INT64), E.Literal(5.0, T.FLOAT64),
            E.Literal(True, T.BOOL), E.Literal(None), E.Literal("a'b", T.STRING),
            E.Literal(9000, T.DATE32), E.Literal(7)]
    out = [col, unbound] + lits
    out += [typed(E.Binary(E.BinOp.GT, col, lit), T.BOOL) for lit in lits]
    out.append(E.Binary(E.BinOp.AND, out[-1], E.Not(out[-2])))
    out.append(E.Func("round", [col, E.Literal(2, T.INT64)]))
    out.append(E.Func("coalesce", [E.Binary(E.BinOp.ADD, col, lits[0]),
                                   E.Literal(0, T.INT64)]))
    out.append(E.InList(col, [lits[0], lits[3]], negated=True))
    out.append(E.Case([(out[-4], lits[1])], else_=None))
    out.append(E.Aggregate(E.AggFunc.SUM, E.Binary(E.BinOp.MUL, col, lits[1])))
    return out


def test_fast_spellings_are_the_fields_spellings(monkeypatch):
    """Column, Literal and Binary are spelled by one format each; letter for
    letter what the generic walk over their dataclass fields spells. A field
    added to one of them shows here."""
    from dataclasses import fields
    from igloo_tpu.plan import expr as E
    assert [f.name for f in fields(E.Column)] == ["dtype", "name", "index"]
    assert [f.name for f in fields(E.Literal)] == ["dtype", "value",
                                                   "literal_type"]
    assert [f.name for f in fields(E.Binary)] == ["dtype", "op", "left",
                                                  "right"]
    exprs = _sample_exprs()
    fast = [(E.shape(e), E.shape(e, by_name=True), E.fingerprint(e),
             E.fingerprint(e, by_name=True)) for e in exprs]
    monkeypatch.setattr(E, "_spell", E._spell_fields)
    slow = [(E.shape(e), E.shape(e, by_name=True), E.fingerprint(e),
             E.fingerprint(e, by_name=True)) for e in exprs]
    assert fast == slow
    assert len({f[2] for f in fast}) == len(exprs)      # all differ by value


def test_shape_masks_values_and_keeps_what_sizes_code():
    from igloo_tpu.plan import expr as E
    col = E.Column("x", index=0)

    def gt(v, dt):
        return E.Binary(E.BinOp.GT, col, E.Literal(v, dt))
    assert E.shape(gt(5, T.INT64)) == E.shape(gt(6, T.INT64))
    assert E.fingerprint(gt(5, T.INT64)) != E.fingerprint(gt(6, T.INT64))
    assert E.shape(gt(5, T.INT64)) != E.shape(gt(5.0, T.FLOAT64))   # dtype
    assert E.shape(gt(5, T.DATE32)) != E.shape(gt(5, T.INT32))
    assert E.shape(gt(True, T.BOOL)) == E.shape(gt(False, T.BOOL))
    # strings, NULL, untyped literals and a function's literal arguments
    # keep their values
    assert E.shape(gt("a", T.STRING)) != E.shape(gt("b", T.STRING))
    assert E.shape(gt(None, T.INT64)) != E.shape(gt(5, T.INT64))
    assert E.shape(gt(5, None)) != E.shape(gt(6, None))
    r1, r2 = (E.Func("round", [col, E.Literal(d, T.INT64)]) for d in (1, 2))
    assert E.shape(r1) != E.shape(r2)
    deep = [E.Func("abs", [gt(v, T.INT64)]) for v in (1, 2)]
    assert E.shape(deep[0]) == E.shape(deep[1])     # not a DIRECT argument
    # position: the same values the other way round are another shape
    a = E.Binary(E.BinOp.AND, gt(5, T.INT64), gt(5.0, T.FLOAT64))
    b = E.Binary(E.BinOp.AND, gt(5.0, T.FLOAT64), gt(5, T.INT64))
    assert E.shape(a) != E.shape(b)
    # by_name: the projection-insensitive form of hints.plan_fp
    moved = E.Binary(E.BinOp.GT, E.Column("x", index=4), E.Literal(5, T.INT64))
    assert E.shape(moved) != E.shape(gt(5, T.INT64))
    assert E.shape(moved, by_name=True) == E.shape(gt(5, T.INT64), by_name=True)
    # a subquery equals nothing, not even itself
    sub = E.ScalarSubquery(query=object())
    assert E.shape(sub) != E.shape(sub)
