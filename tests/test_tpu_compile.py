"""Ask the chip's compiler, without the chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). These tests compile
two plain XLA stages of the main path at the shapes TPC-H SF1 runs them at,
for one chip of a `v5e:2x2` host. A kernel written for Mosaic (ROADMAP A8)
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
