"""Test configuration: run the whole suite on a virtual 8-device CPU mesh so
sharding/shuffle paths execute in CI without TPUs (SURVEY.md §4 test strategy (b);
the reference has no distributed tests at all — we invent the strategy here).

`JAX_PLATFORMS=cpu` in the environment is honoured, and the driver sets it;
the jax.config.update below makes the suite a CPU suite even when it is
started without the variable on a machine that has a chip."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# keep igloo_tpu's import-time cache config off too (see update below)
os.environ["IGLOO_TPU_COMPILE_CACHE"] = "0"
# the coordinator's front-door result cache (docs/serving.md) would make a
# REPEATED identical query skip execution entirely — module-scoped cluster
# fixtures re-run the same SQL and assert what execution DID (fragments per
# worker, recoveries, salting), so the suite pins it off; serving tests opt
# back in with monkeypatch
os.environ["IGLOO_SERVING_RESULT_CACHE"] = "0"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# no persistent compile cache for the CPU suite: reloading CPU AOT entries
# across host-feature detection contexts risks SIGILL (cache is for TPU)
jax.config.update("jax_compilation_cache_dir", None)

assert jax.default_backend() == "cpu", (
    "test suite must run on the virtual CPU mesh, got "
    f"{jax.default_backend()}")

# single-device execution by default: the 8 virtual devices exist for the
# sharding tests (test_parallel.py, test_engine_mesh.py), which opt in with an
# explicit mesh — without this pin, QueryEngine's "auto" mesh would flip the
# whole suite to sharded execution and single-device paths would lose coverage
import igloo_tpu.engine  # noqa: E402

igloo_tpu.engine.DEFAULT_MESH = None

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_adaptive_store():
    """The AdaptiveStats store (exec/hints.py) is process-global on purpose —
    the coordinator, planner, and engines share one feedback loop — but
    across TESTS that persistence would make plan shapes depend on which
    tests ran before (a shuffle-shape assertion flips to broadcast once an
    earlier test observed the same join side). Each test starts with a fresh
    in-memory store; tests of the feedback loop exercise persistence by
    pointing IGLOO_ADAPTIVE_STATS at their own tmp file."""
    from igloo_tpu.exec import hints
    hints.reset_adaptive_store()
    yield
    hints.reset_adaptive_store()


@pytest.fixture(autouse=True)
def _fresh_watchtower():
    """Watchtower state (utils/watch.py baselines + escalations,
    cluster/events.py journal) is process-global like the adaptive store,
    and for the same reason must not leak across tests — an escalation
    threshold warmed by one test would change what another escalates.
    The SAMPLER singleton (utils/timeseries.py) is deliberately left
    alone: module-scoped cluster fixtures own it for their lifetime."""
    from igloo_tpu.cluster import events
    from igloo_tpu.exec import hints
    from igloo_tpu.utils import watch
    hints.reset_watch_store()
    watch.clear()
    events.clear()
    yield
    hints.reset_watch_store()
    watch.clear()
    events.clear()


# NOTE (round 4): a session-shared jit compile cache was tried here to cut
# CPU compile time and REVERTED: keeping every compiled XLA:CPU executable
# alive for the whole session reproducibly segfaulted the process in
# libgcc's unwinder (dmesg: "segfault ... in libgcc_s.so.1") near the end of
# the suite — and saved no wall-clock. Per-engine caches let executables be
# garbage-collected between tests, which round 3 ran stably with.
