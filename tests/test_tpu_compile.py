"""Ask the chip's compiler, without the chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). These tests compile
two plain XLA stages of the main path at the shapes TPC-H SF1 runs them at,
and q1's small-domain aggregate (also over f32-pair leaves, with a q6-shaped
masked sum: ISSUE 37), for one chip of a `v5e:2x2` host. A kernel written for Mosaic (ROADMAP A8)
is held to the compiler here, the same way: lower it with `one_chip`
shardings and look for `tpu_custom_call` in `compiled.as_text()`. The six
hand-written kernels this file used to hold to the compiler were all refused
by it (int64 rank-1 blocks tile to zero) and went in PR 32.

Nothing runs: a compile that passes is not a chip run and says nothing about
results or time. The topology is described inside a module-scoped fixture
(never at import: only one process may load the TPU library, and every xdist
worker imports this file), and all compiles happen in this process.

Left out because its compile is slow, not because it fails: the packed-key
stable argsort of an int64 lane at 2^20 lanes takes 65-100 s to compile here
(35 s on the chip's host), `lax.top_k` of the same lane 59 s, the int64
cumsum 77 s and the float64 cumsum 350 s (PERF.md, "compile time of plain
stages").
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from igloo_tpu.exec.aggregate import DIRECT_SEG_SMALL_LIMIT

LANES = 1 << 20  # the capacity family member SF1 joins and group-bys run at


@pytest.fixture(scope="module")
def one_chip():
    old = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        if old is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without a chip; keep it off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _lower_and_compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


# --- two plain stages of the main path (each compiles in ~1-2 s) ------------

def test_segment_sum_stage_compiles(one_chip):
    """The segment-reduce under every GROUP BY: float64 values scattered
    into 2^16 segments (aggregate.py's direct-scatter bound)."""
    c = _lower_and_compile(
        lambda v, s: jax.ops.segment_sum(
            v, s, num_segments=DIRECT_SEG_SMALL_LIMIT),
        [((LANES,), jnp.float64), ((LANES,), jnp.int32)], one_chip)
    assert c.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_gather_stage_compiles(one_chip):
    """The join's materialization: int64 and float64 lanes gathered by an
    int32 position lane (`kernels.gather_batch`: one `jnp.take` per lane)."""
    c = _lower_and_compile(
        lambda a, b, i: (jnp.take(a, i), jnp.take(b, i)),
        [((LANES,), jnp.int64), ((LANES,), jnp.float64),
         ((LANES,), jnp.int32)], one_chip)
    assert c.memory_analysis().temp_size_in_bytes < (1 << 30)


# --- q1's aggregate: the one-pass small-domain reduce (ISSUE 33; ~10-20 s) --

def _q1_shaped_aggregate(pair: bool = False, pair_sums: bool = False):
    """`aggregate_batch` as q1's scan fragment calls it, as a function of
    plain lanes: two dictionary keys without null lanes (3 x 2 values), four
    float64 columns, the eight aggregates of q1, each argument compiled from
    its bound expression as the compilers do (five distinct ones: the AVGs
    repeat three of the SUMs'), `seg_dims` ((4, 0), (3, 0)). With `pair` the
    three columns that ride as raw float64 on the chip (price, disc, tax;
    qty is an int8 carrier there) are f32-pair carriers instead (ISSUE 37):
    `fn` then takes their low halves after `live`. With `pair_sums` the five
    float64 sums fold as f32 pairs (ISSUE 39), as where the chip's canary
    says its float64 is that pair."""
    from igloo_tpu import types as T
    from igloo_tpu.exec.codec import WidenSpec
    from igloo_tpu.exec.aggregate import AggSpec, aggregate_batch
    from igloo_tpu.exec.batch import DeviceBatch, DeviceColumn, DictInfo
    from igloo_tpu.exec.expr_compile import ExprCompiler
    from igloo_tpu.plan.expr import AggFunc, Binary, BinOp, Column, Literal

    dicts = [DictInfo.from_values("ANR"), DictInfo.from_values("FO")] + \
        [None] * 4
    names = ["flag", "status", "qty", "price", "disc", "tax"]
    dtypes = [T.STRING] * 2 + [T.FLOAT64] * 4
    in_schema = T.Schema([T.Field(n, d, False) for n, d in zip(names, dtypes)])
    out_schema = T.Schema(
        [T.Field("flag", T.STRING, True), T.Field("status", T.STRING, True)] +
        [T.Field(f"a{i}", T.FLOAT64, True) for i in range(7)] +
        [T.Field("n", T.INT64, False)])

    def bound(e, dtype=T.FLOAT64):
        e.dtype = dtype
        return e

    def col(name):
        i = names.index(name)
        return bound(Column(name, index=i), dtypes[i])

    def one(op, name):  # 1 <op> column
        return bound(Binary(op, bound(Literal(1.0, T.FLOAT64)), col(name)))

    def disc_price():
        return bound(Binary(BinOp.MUL, col("price"), one(BinOp.SUB, "disc")))

    args = {"qty": lambda: col("qty"), "price": lambda: col("price"),
            "disc": lambda: col("disc"), "disc_price": disc_price,
            "charge": lambda: bound(Binary(BinOp.MUL, disc_price(),
                                           one(BinOp.ADD, "tax")))}
    comp = ExprCompiler(dicts)
    specs = [AggSpec(f, comp.compile(args[a]()), T.FLOAT64, None) for f, a in [
        (AggFunc.SUM, "qty"), (AggFunc.SUM, "price"),
        (AggFunc.SUM, "disc_price"), (AggFunc.SUM, "charge"),
        (AggFunc.AVG, "qty"), (AggFunc.AVG, "price"), (AggFunc.AVG, "disc")]]
    specs.append(AggSpec(AggFunc.COUNT_STAR, None, T.INT64, None))
    groups = [comp.compile(col("flag")), comp.compile(col("status"))]

    def fn(flag_ids, status_ids, qty, price, disc, tax, live, *consts):
        lanes = [flag_ids, status_ids, qty, price, disc, tax]
        cols = [DeviceColumn(d, v, None, dic)
                for d, v, dic in zip(dtypes, lanes, dicts)]
        if pair:
            lows, consts = consts[:3], consts[3:]
            cols[3:] = [DeviceColumn(T.FLOAT64, c.values, None, None, None,
                                     WidenSpec("float64", pair=True), lo)
                        for c, lo in zip(cols[3:], lows)]
        out = aggregate_batch(DeviceBatch(in_schema, cols, live), groups,
                              specs, out_schema, consts,
                              seg_dims=((4, 0), (3, 0)), pair_sums=pair_sums)
        return [(c.values, c.nulls) for c in out.columns], out.live
    # the constants pool as PARAMETERS, as a dispatch passes it: q1's three
    # literals (the 1 of `1 - l_discount`, twice, and of `1 + l_tax`) are
    # scalars of one float64 vector (ISSUE 34), no constants of the trace
    return fn, [(a.shape, a.dtype) for a in comp.pool.device_args()]


def _q1_shapes(pair, consts):
    """The argument shapes of `_q1_shaped_aggregate(pair)`'s `fn`."""
    head = [((LANES,), jnp.int32)] * 2 + [((LANES,), jnp.float64)]
    if pair:
        return head + [((LANES,), jnp.float32)] * 3 + \
            [((LANES,), jnp.bool_)] + [((LANES,), jnp.float32)] * 3 + consts
    return head + [((LANES,), jnp.float64)] * 3 + [((LANES,), jnp.bool_)] + \
        consts


# distinct lanes q1 hands to the one-pass reduce: five float64 sums (the AVGs
# repeat three of them) and the live count (COUNT(*) and every valid-count)
Q1_LANES = 6
# segment ids that can hold a row: (4 - 1) x (3 - 1) of the padded 16
Q1_SEGMENTS = 6
# `bytes accessed` of this aggregate on the parent of PR 33 (f3009b3: one
# select-reduce per aggregate lane and padded segment, 81 reduce fusions, one
# fusion writing the 16 `pred[LANES]` masks), compiled once on a copy of it
# for the described chip at LANES = 2^20. The change reads 5.6 % of it.
Q1_PARENT_BYTES = 946_671_616


def test_q1_aggregate_is_one_pass_under_the_chips_compiler(one_chip):
    """ISSUE 33's guard that the one-pass reduce engages under the TPU
    compiler: no fusion returns a `pred[LANES]` array (no segment mask
    reaches HBM), the lanes are reduced by at most (distinct lanes + 2)
    fusions and not by one per lane and segment, and the program reads under
    a tenth of the bytes the per-segment loop read."""
    fn, consts = _q1_shaped_aggregate()
    assert consts == [((3,), jnp.float64)]
    c = _lower_and_compile(fn, _q1_shapes(False, consts), one_chip)
    text = c.as_text()
    entry = text[text.index("ENTRY"):]
    fusions = re.findall(r"^\s*(?:ROOT )?%?(\S*fusion\S*) = (.*?) fusion\(",
                         entry, re.M)
    assert fusions, "no fusion found in the entry computation"
    assert not [n for n, shape in fusions if f"pred[{LANES}]" in shape]
    reduces = [n for n, _ in fusions if "reduce" in n]
    assert 1 <= len(reduces) <= Q1_LANES + 2, reduces
    assert c.cost_analysis()["bytes accessed"] < Q1_PARENT_BYTES / 10


# --- q1's float64 sums folded as f32 pairs (ISSUE 39; ~25 s a half) ---------

# folded / float64 `flops` of the whole aggregate (products, decodes, reduce)
# compiled for the described chip at LANES = 2^20: 832.7 M / 1,089.6 M = 0.764
# over float64 leaves, 837.9 M / 1,143.0 M = 0.733 over pair leaves (PR 39).
# ISSUE 39 sized 0.70 from a fold of 13 operations a pair and segment; the
# guard that keeps a NaN error term out of `hi` (kernels.pair_add) makes it 15
Q1_FOLD_FLOPS_RATIO = 0.80


def _reduce_arities(compiled) -> list:
    """Accumulators of every `reduce(` instruction of the program, largest
    first: q1's ONE variadic reduce over the lanes (6 s32 counts and 30
    float64 sums, each two f32 words to this compiler either way: 66) and
    the two-word count of live groups."""
    return sorted((len(re.findall(r"\w+\[\]", m)) for m in re.findall(
        r"^\s*(?:ROOT )?%\S+ = (.*?) reduce\(", compiled.as_text(), re.M)),
        reverse=True)


@pytest.mark.parametrize("pair", [False, True], ids=["f64_leaves", "pairs"])
def test_q1_pair_fold_is_one_cheaper_reduce_under_the_chips_compiler(
        one_chip, pair):
    """ISSUE 39's guard, for "the compiler stopped fusing" and for the ratio
    the change was sized by: with the fold engaged q1's aggregate is still
    ONE reduce fusion over the lanes (66 f32 operands), no `pred[LANES]` or
    `f32[LANES]` operand of it reaches HBM (temporaries stay what the
    float64 reduce's are: kilobytes), and XLA's own operation count is under
    Q1_FOLD_FLOPS_RATIO of the float64 reduce's, compiled here beside it."""
    compiled = {}
    for fold in (False, True):
        fn, consts = _q1_shaped_aggregate(pair=pair, pair_sums=fold)
        compiled[fold] = _lower_and_compile(fn, _q1_shapes(pair, consts),
                                            one_chip)
    wide, folded = compiled[False], compiled[True]
    lanes = Q1_SEGMENTS * (1 + 2 * (Q1_LANES - 1))
    assert _reduce_arities(folded) == _reduce_arities(wide) == [lanes, 2]
    entry = folded.as_text()
    entry = entry[entry.index("ENTRY"):]
    assert not re.findall(r"= (?:pred|f32)\[%d\]\S* fusion\(" % LANES, entry)
    assert folded.memory_analysis().temp_size_in_bytes <= \
        wide.memory_analysis().temp_size_in_bytes + (1 << 20)
    ratio = folded.cost_analysis()["flops"] / wide.cost_analysis()["flops"]
    assert ratio <= Q1_FOLD_FLOPS_RATIO, ratio


# --- a resident float64 column as its two f32 halves (ISSUE 37) -------------

def _lane_splits(compiled) -> int:
    """X64SplitHigh / X64SplitLow custom-calls that write a whole lane (the
    scalars of the constants pool are split too, on both sides: not
    counted)."""
    return len(re.findall(
        r"= f32\[%d\]\S* custom-call\(.*custom_call_target=\"X64Split" % LANES,
        compiled.as_text()))


def test_q1_aggregate_over_pair_leaves_opens_with_no_split(one_chip):
    """The chip has no float64: a program splits every `f64[N]` parameter
    into its f32 halves (two custom-calls, each a pass over the lane) before
    anything reads it. Over f32-pair leaves q1's aggregate holds no split;
    over float64 leaves it holds two per column — if a later jax stops
    splitting, that half fails and the carrier has become dead weight."""
    fn, consts = _q1_shaped_aggregate()
    wide = _lower_and_compile(fn, _q1_shapes(False, consts), one_chip)
    assert _lane_splits(wide) == 2 * 4  # qty, price, disc, tax
    fn, consts = _q1_shaped_aggregate(pair=True)
    pair = _lower_and_compile(fn, _q1_shapes(True, consts), one_chip)
    assert _lane_splits(pair) == 2  # qty alone, a float64 leaf in this harness


def test_q6_masked_sum_over_pair_leaves_opens_with_no_split(one_chip):
    """q6's shape: a predicate over one float64 column and a masked sum of
    the product of two, decoded through `wide_values` as every operator
    decodes."""
    from igloo_tpu import types as T
    from igloo_tpu.exec.batch import DeviceColumn, wide_values
    from igloo_tpu.exec.codec import WidenSpec

    def q6(live, lo, hi, *lanes):
        if len(lanes) == 2:
            cols = [DeviceColumn(T.FLOAT64, v, None) for v in lanes]
        else:
            cols = [DeviceColumn(T.FLOAT64, h, None, None, None,
                                 WidenSpec("float64", pair=True), l)
                    for h, l in zip(lanes[:2], lanes[2:])]
        price, disc = (wide_values(c) for c in cols)
        keep = live & (disc >= lo) & (disc <= hi)
        return jnp.sum(jnp.where(keep, price * disc, 0.0))

    head = [((LANES,), jnp.bool_), ((), jnp.float64), ((), jnp.float64)]
    wide = _lower_and_compile(q6, head + [((LANES,), jnp.float64)] * 2,
                              one_chip)
    pair = _lower_and_compile(q6, head + [((LANES,), jnp.float32)] * 4,
                              one_chip)
    assert _lane_splits(wide) == 2 * 2
    assert _lane_splits(pair) == 0


# --- a literal is an argument: one lowering per shape (ISSUE 34; ~1 s each) -

def _lowered_for_the_chip(engine, sql: str, sharding):
    """What the engine's next execution of `sql` would hand the chip's
    compiler: (program key, text of the fused program lowered for the
    described chip, with the leaves' and the constants' shapes as the
    engine holds them here)."""
    from igloo_tpu.exec.executor import Executor, strip_dicts
    from igloo_tpu.exec.fused import FusedCompiler
    comp = FusedCompiler(Executor(engine._jit_cache,
                                  batch_cache=engine.batch_cache))
    run, key, _meta = comp.compile(engine.plan(sql))
    args = ([strip_dicts(b) for b in comp.leaves], comp.pool.device_args())
    spec = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), args)
    return key, jax.jit(run).lower(*spec).as_text()


@pytest.mark.parametrize("q", ["q1", "q6"])
def test_two_parameter_sets_lower_to_one_program(one_chip, q):
    """TPC-H's substitution parameters (clauses 2.4.1.3, 2.4.6.3) reach the
    chip's compiler as arguments: under two sets, q1's and q6's programs —
    scan, filter, aggregate: what a worker's scan fragment runs — have one
    key and lower to byte-identical text for the described chip, and the
    text holds neither set's values."""
    from test_literal_args import Q1_SETS, Q6_SETS, _sql
    from igloo_tpu.bench.tpch import gen_tables
    from igloo_tpu.engine import QueryEngine
    engine = QueryEngine()
    engine.register_table("lineitem", gen_tables(sf=0.002)["lineitem"])
    sets = {"q1": Q1_SETS, "q6": Q6_SETS}[q]
    (k0, t0), (k1, t1) = (_lowered_for_the_chip(engine, _sql(q, p), one_chip)
                          for p in sets[:2])
    assert k0 == k1
    assert t0 == t1
    # the dates, as the days since the epoch a constant would hold
    for days in {"q1": ("10471", "10493"), "q6": ("8766", "9496")}[q]:
        assert days not in t0


# --- the lazy join's full-width probe reads a bit table (~20 s) ------------

def test_bit_table_probe_at_sf10_widths_gathers_from_the_core(one_chip):
    """The orders join of TPC-H q3 at SF10 over the spec's keys: 2^26 probe
    lanes against a 2^27-slot positional table built from 2^24 build rows.
    `direct_bitmap_probe` packs the table into 2^22 u32 words in one fusion
    that the compiler places in the core's own memory (`S(1)`), and the
    2^26-lane gather reads those words: no fusion gathers s32 lanes at
    2^26 from the table, which is what cost 25.7 ns a lane against 8.7 from
    a 16 MiB table on a v5e (PERF.md §6, step 0). `direct_probe`, compiled
    beside it, writes those row ids: 256 MiB more of temporaries."""
    from igloo_tpu import types as T
    from igloo_tpu.exec.batch import DeviceBatch, DeviceColumn
    from igloo_tpu.exec.expr_compile import Compiled
    from igloo_tpu.exec.join import direct_bitmap_probe, direct_probe

    probe_lanes, build_lanes, tsize = 1 << 26, 1 << 24, 1 << 27
    schema = T.Schema([T.Field("k", T.INT64, False)])
    key = Compiled(fn=lambda env: (env.values[0], None), dtype=T.INT64)

    def batches(bkeys, blive, pkeys, plive):
        return (DeviceBatch(schema, [DeviceColumn(T.INT64, pkeys, None)],
                            plive),
                DeviceBatch(schema, [DeviceColumn(T.INT64, bkeys, None)],
                            blive))

    def bits(*lanes):
        ok, _table, _slot, dup = direct_bitmap_probe(*batches(*lanes), key,
                                                     key, 0, tsize, ())
        return ok, dup

    def table(*lanes):
        ok, _bidx, dup = direct_probe(*batches(*lanes), key, key, 0, tsize,
                                      False, None, ())
        return ok, dup

    shapes = [((build_lanes,), jnp.int64), ((build_lanes,), jnp.bool_),
              ((probe_lanes,), jnp.int64), ((probe_lanes,), jnp.bool_)]
    c = _lower_and_compile(bits, shapes, one_chip)
    entry = c.as_text()
    entry = entry[entry.index("ENTRY"):]
    gathers = re.findall(r"= (\w+)\[%d\]\S* fusion\(.*kind=kCustom"
                         % probe_lanes, entry)
    assert gathers == ["u32"], gathers
    packed = re.findall(r"= u32\[%d\]\{[^}]*\} fusion\(" % (tsize // 32),
                        entry)
    assert packed and all("S(1)" in p for p in packed), packed
    parent = _lower_and_compile(table, shapes, one_chip)
    assert c.memory_analysis().temp_size_in_bytes <= \
        parent.memory_analysis().temp_size_in_bytes - probe_lanes * 4
