"""Ask the chip's compiler, without the chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). These tests compile
the six Pallas kernels at the shapes `exec/dispatch.py` plans for TPC-H SF1
and two plain XLA stages of the main path, for one chip of a `v5e:2x2`
host, and hold `dispatch.TPU_COMPILED_KERNELS` to the compiler BOTH ways:
a kernel in the table must compile to a `tpu_custom_call`; a kernel outside
it must still be refused — so a PR that repairs a kernel has to move it into
the table, and one that breaks a kernel cannot leave it there.

Nothing runs: a compile that passes is not a chip run and says nothing about
results or time. The topology is described inside a module-scoped fixture
(never at import: only one process may load the TPU library, and every xdist
worker imports this file), and all compiles happen in this process.

Left out because its compile is slow, not because it fails: the packed-key
stable argsort of an int64 lane at 2^20 lanes takes 65-100 s to compile here
(35 s on the chip's host), the int64 cumsum 77 s and the float64 cumsum
350 s (PERF.md, "compile time of plain stages").
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from igloo_tpu.exec import dispatch

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


@pytest.fixture
def forced_compiled(monkeypatch):
    """Steer the planners as `IGLOO_TPU_PALLAS=1` on a TPU would: every
    kernel planned, compiled (interpret=False), whatever the table says."""
    monkeypatch.setenv("IGLOO_TPU_PALLAS", "1")
    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    assert dispatch.kernel_state() == (True, False)


def _probe():
    plan = dispatch.plan_probe(LANES, LANES)
    return (lambda sh, ph: dispatch.probe_bounds(plan, sh, ph),
            [((LANES,), jnp.int64), ((LANES,), jnp.int64)])


def _segagg():
    plan = dispatch.plan_segagg((None, (0,)), 1, LANES)
    assert dispatch.segagg_table_rows(plan) == dispatch.AGG_TABLE_ROWS_COMPILED
    return (lambda p, lv, ok, v: dispatch.segagg(plan, p, lv, ("sum",),
                                                 [ok, v]),
            [((LANES,), jnp.int64), ((LANES,), jnp.bool_),
             ((LANES,), jnp.bool_), ((LANES,), jnp.float64)])


def _gather():
    # two 8-byte build lanes that just fit GATHER_MAX_BYTES_COMPILED
    m = dispatch.GATHER_MAX_BYTES_COMPILED // 16
    return (lambda a, b, i: dispatch.gather_columns([a, b], i),
            [((m,), jnp.int64), ((m,), jnp.float64), ((LANES,), jnp.int32)])


def _match():
    plan = dispatch.plan_match(LANES, LANES)
    assert plan[1] == "kernel"
    return (lambda p, c: dispatch.match_table(plan, p, c, LANES),
            [((LANES,), jnp.int64), ((LANES,), jnp.int32)])


def _topk():
    plan = dispatch.plan_topk(LANES, 10, True)  # q3's LIMIT 10
    assert plan[1] == "pallas"
    return (lambda k: dispatch.topk_perm(plan, k), [((LANES,), jnp.int64)])


def _scatter():
    from igloo_tpu.exec import pallas_kernels
    _, npad, nbuckets, block, interp = dispatch.plan_scatter(LANES, 1, 16)
    return (lambda v, lv: pallas_kernels.hash_scatter([v], lv, nbuckets,
                                                      block, interp),
            [((npad,), jnp.uint64), ((npad,), jnp.bool_)])


KERNELS = {"probe": _probe, "segagg": _segagg, "gather": _gather,
           "match": _match, "topk": _topk, "scatter": _scatter}


def _lower_and_compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_table_names_only_known_kernels():
    assert dispatch.TPU_COMPILED_KERNELS <= set(KERNELS)


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_table_matches_the_chips_compiler(kernel, one_chip,
                                                 forced_compiled):
    fn, shapes = KERNELS[kernel]()
    if kernel in dispatch.TPU_COMPILED_KERNELS:
        text = _lower_and_compile(fn, shapes, one_chip).as_text()
        assert "tpu_custom_call" in text, \
            f"{kernel} compiled but no Pallas kernel is in the program"
    else:
        with pytest.raises(Exception):
            _lower_and_compile(fn, shapes, one_chip)


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_auto_on_a_tpu_plans_only_what_compiles(kernel, monkeypatch):
    """Under `auto` on a TPU backend a planner hands out a kernel only if
    the table has it; otherwise the sort path, counted."""
    from igloo_tpu.utils import tracing
    monkeypatch.delenv("IGLOO_TPU_PALLAS", raising=False)
    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    a = jnp.zeros((dispatch.GATHER_BLOCK,), jnp.int64)
    i = jnp.zeros((dispatch.GATHER_BLOCK,), jnp.int32)
    # each: did the planner hand out the KERNEL (match and topk have
    # non-Pallas routes of their own)
    plans_kernel = {
        "probe": lambda: dispatch.plan_probe(LANES, LANES) is not None,
        "segagg": lambda: dispatch.plan_segagg(
            (None, (0,)), 1, LANES) is not None,
        "gather": lambda: dispatch._plan_gather([a, a], i) is not None,
        "match": lambda: (dispatch.plan_match(LANES, LANES)
                          or (None, None))[1] == "kernel",
        "topk": lambda: dispatch.plan_topk(LANES, 10, True)[1] == "pallas",
        "scatter": lambda: dispatch.plan_scatter(LANES, 1, 16) is not None,
    }
    with tracing.counter_delta() as d:
        planned = plans_kernel[kernel]()
    assert planned == (kernel in dispatch.TPU_COMPILED_KERNELS)
    assert d.get("pallas.fallback.not_compiled") == (0 if planned else 1)
    assert d.get(f"pallas.{kernel}") == (1 if planned else 0)


def test_forced_kernels_raise_on_a_tpu(monkeypatch):
    """IGLOO_TPU_PALLAS=1 on a TPU does not give way to the sort path."""
    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    for mode, want in (("1", True), ("auto", False), ("interpret", False),
                       ("0", False)):
        monkeypatch.setenv("IGLOO_TPU_PALLAS", mode)
        assert dispatch.compile_failure_raises() is want
    monkeypatch.setattr(dispatch, "_backend", lambda: "cpu")
    monkeypatch.setenv("IGLOO_TPU_PALLAS", "1")
    assert dispatch.compile_failure_raises() is False


# --- two plain stages of the main path (each compiles in ~1-2 s) ------------

def test_segment_sum_stage_compiles(one_chip):
    """The segment-reduce under every GROUP BY: float64 values scattered
    into 2^16 segments (aggregate.py's direct-scatter bound)."""
    c = _lower_and_compile(
        lambda v, s: jax.ops.segment_sum(
            v, s, num_segments=dispatch.DIRECT_SEG_SMALL_LIMIT),
        [((LANES,), jnp.float64), ((LANES,), jnp.int32)], one_chip)
    assert c.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_gather_stage_compiles(one_chip):
    """The join's materialization: int64 and float64 lanes gathered by an
    int32 position lane (the XLA path `gather_columns` takes on the chip)."""
    c = _lower_and_compile(
        lambda a, b, i: (jnp.take(a, i), jnp.take(b, i)),
        [((LANES,), jnp.int64), ((LANES,), jnp.float64),
         ((LANES,), jnp.int32)], one_chip)
    assert c.memory_analysis().temp_size_in_bytes < (1 << 30)
