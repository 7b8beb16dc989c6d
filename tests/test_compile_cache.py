"""Cold-start kill chain: canonical shape families + persistent compile cache.

Three layers, all cheap (tiny tables, CPU backend):
- the capacity policy itself (family membership, hysteresis, the canonical
  direct-join table, boundary round-trips through from_arrow/to_arrow);
- jit-cache equivalence: the SAME query shape at two scale factors that
  quantize to one family member produces ZERO new `_jitted` entries on the
  second run — the tentpole property;
- the persistent tier: a fresh subprocess re-running a query serves its
  compiles from the on-disk cache (`compile_cache.hit` > 0), plus the
  entry-transfer helpers and the coordinator's Flight action pair;
- satellite regressions: ResultCache entry-capacity eviction, HintStore
  thread safety.
"""
import json
import os
import subprocess
import sys
import threading
import time

import pyarrow as pa
import pytest

from igloo_tpu.exec import capacity as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- capacity policy ---------------------------------------------------------

def test_family_small_band_is_exact_pow2():
    assert C.canonical_capacity(0) == 8
    assert C.canonical_capacity(8) == 8
    assert C.canonical_capacity(9) == 16
    assert C.canonical_capacity(1000) == 1024
    assert C.canonical_capacity(C.COARSE_FLOOR) == C.COARSE_FLOOR


def test_quantization_lands_on_family_members():
    members = set(C.capacity_family(1 << 26))
    prev = 0
    for n in (5, 100, 70_000, 130_000, 300_000, 600_000, 2_000_000,
              6_000_000, 20_000_000):
        cap = C.canonical_capacity(n)
        assert cap >= n
        assert cap in members, (n, cap)
        assert cap >= prev  # monotonic in n
        prev = cap


def test_canonical_capacity_is_idempotent():
    # call sites re-round existing capacities (spec_cap, GRACE partition
    # caps): hysteresis must never inflate a value that is already a member,
    # or every re-round climbs a family step (and 2^22 inputs would blow the
    # speculative-join budget)
    for m in C.capacity_family(1 << 25):
        assert C.canonical_capacity(m) == m, m


def test_neighboring_scale_factors_share_a_member():
    # the tentpole property: ~2x apart cardinalities above the coarse floor
    # quantize to ONE member, so their programs share compile-cache entries
    assert C.canonical_capacity(70_000) == C.canonical_capacity(130_000)


def test_hysteresis_rounds_near_boundary_up():
    member = C.COARSE_FLOOR << C.COARSE_STEP  # 262144
    # just under the member (within the ~3% headroom): rounds UP so drift
    # across the boundary cannot flip-flop the program shape
    assert C.canonical_capacity(member - 1000) > member
    # comfortably under: stays
    assert C.canonical_capacity(int(member * 0.9)) == member


def test_pow2_mode_knob(monkeypatch):
    monkeypatch.setenv("IGLOO_TPU_SHAPE_FAMILY", "pow2")
    assert C.canonical_capacity(70_000) == 131072
    assert C.capacity_family(1 << 20)[-1] == 1 << 20


def test_canonical_direct_table_invariants():
    for lo, hi in ((1, 60_000), (1, 120_000), (5_000, 9_000), (0, 6),
                   (-500, 2_000), (10957, 13514)):
        base, tsize = C.canonical_direct_table(lo, hi)
        assert base <= lo
        assert base + tsize > hi
    # neighboring scale factors share one positional table
    assert C.canonical_direct_table(1, 60_000) == \
        C.canonical_direct_table(1, 120_000)


def test_round_trip_at_family_boundaries():
    from igloo_tpu.exec.batch import from_arrow, to_arrow
    for n in (C.COARSE_FLOOR - 1, C.COARSE_FLOOR, C.COARSE_FLOOR + 1):
        t = pa.table({"a": pa.array(range(n), type=pa.int64())})
        batch = from_arrow(t)
        assert batch.capacity == C.canonical_capacity(n)
        back = to_arrow(batch)
        assert back.num_rows == n
        assert back.column("a")[0].as_py() == 0
        assert back.column("a")[n - 1].as_py() == n - 1


def test_direct_join_eligibility_survives_hysteresis_padding():
    # a dense PK side whose live count sits just under a family boundary
    # pads past the range's own member (hysteresis); eligibility compares
    # against the canonical TABLE size, so the fast path must survive
    from igloo_tpu import types as T
    from igloo_tpu.exec.expr_compile import Compiled
    from igloo_tpu.exec.join import choose_direct_build
    from igloo_tpu.sql.ast import JoinType
    rng_hi = (C.COARSE_FLOOR << C.COARSE_STEP) - 1  # range = 2^18 exactly
    build_cap = C.canonical_capacity(260_000)       # 2^20: two steps up
    lk = Compiled(fn=None, dtype=T.INT64, out_bounds=None)
    rk = Compiled(fn=None, dtype=T.INT64, out_bounds=(0, rng_hi))
    pick = choose_direct_build([lk], [rk], left_cap=1 << 21,
                               right_cap=build_cap, join_type=JoinType.INNER)
    assert pick is not None
    side, (base, tsize), _ = pick
    assert side == "right"
    assert base <= 0 and base + tsize > rng_hi
    assert build_cap <= tsize


# --- jit-cache equivalence across scale factors ------------------------------

def _scaled_table(n: int) -> pa.Table:
    return pa.table({"a": pa.array(range(n), type=pa.int64()),
                     "g": pa.array([i % 7 for i in range(n)],
                                   type=pa.int64())})


def test_same_jit_cache_entries_at_two_scale_factors():
    from igloo_tpu.engine import QueryEngine
    from igloo_tpu.utils import tracing
    sql = "SELECT g, SUM(a) AS s FROM t WHERE a >= 10 GROUP BY g ORDER BY g"
    eng = QueryEngine()
    eng.register_table("t", _scaled_table(70_000))
    first = eng.execute(sql)
    keys_after_first = set(eng._jit_cache)
    # "scale factor" 2x: same schema/exprs, ~2x the rows — same family member
    eng.register_table("t", _scaled_table(130_000))
    with tracing.counter_delta() as delta:
        second = eng.execute(sql)
    assert delta.get("jit.miss") == 0, dict(delta.values())
    assert set(eng._jit_cache) == keys_after_first
    # and the answers are the right ones for each dataset
    assert first.column("g").to_pylist() == list(range(7))
    assert second.column("g").to_pylist() == list(range(7))
    n = 130_000
    assert sum(second.column("s").to_pylist()) == \
        sum(a for a in range(n) if a >= 10)


# --- persistent tier ---------------------------------------------------------

_SUBPROC_SCRIPT = """
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import igloo_tpu  # configures the persistent cache from the env
from igloo_tpu.engine import QueryEngine
import igloo_tpu.engine as E
E.DEFAULT_MESH = None
import pyarrow as pa
eng = QueryEngine()
n = 2048
eng.register_table("t", pa.table({
    "a": pa.array(range(n), type=pa.int64()),
    "g": pa.array([i % 5 for i in range(n)], type=pa.int64())}))
eng.execute("SELECT g, SUM(a) AS s FROM t WHERE a >= 3 GROUP BY g ORDER BY g")
from igloo_tpu.utils import tracing
c = tracing.counters()
print(json.dumps({"hit": c.get("compile_cache.hit", 0),
                  "miss": c.get("compile_cache.miss", 0),
                  "dir": jax.config.jax_compilation_cache_dir}))
"""


def _run_cache_subprocess(cache_dir: str, placed_by: str = "igloo") -> dict:
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(JAX_PLATFORMS="cpu",
               IGLOO_TPU_COMPILE_CACHE=cache_dir,
               IGLOO_TPU_COMPILE_CACHE_MIN_SECS="0")
    if placed_by == "jax":
        # whoever launches the process places the cache: JAX's own variable
        # wins over a directory in the igloo setting
        env.update(JAX_COMPILATION_CACHE_DIR=cache_dir,
                   IGLOO_TPU_COMPILE_CACHE=cache_dir + "-not-this-one")
    out = subprocess.run([sys.executable, "-c", _SUBPROC_SCRIPT], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("placed_by", ["igloo", "jax"])
def test_second_process_hits_persistent_cache(tmp_path, placed_by):
    from igloo_tpu import compile_cache
    d = str(tmp_path / "xla")
    cold = _run_cache_subprocess(d, placed_by)
    assert cold["dir"] == d
    assert cold["miss"] > 0
    assert compile_cache.entry_names(d), "no persistent entries written"
    assert not os.path.exists(d + "-not-this-one")
    warm = _run_cache_subprocess(d, placed_by)
    assert warm["hit"] > 0, warm


@pytest.mark.parametrize("jax_dir,setting,want", [
    (None, None, "default"),        # nothing set: <checkout>/.xla_cache
    (None, "1", "default"),
    (None, "/some/dir", "/some/dir"),
    ("/placed", None, "/placed"),   # JAX's variable IS the cache
    ("/placed", "/some/dir", "/placed"),
    ("/placed", "0", None),         # off still wins
    (None, "off", None),
])
def test_cache_directory_resolution(monkeypatch, jax_dir, setting, want):
    from igloo_tpu import compile_cache as cc
    for name, val in (("JAX_COMPILATION_CACHE_DIR", jax_dir),
                      ("IGLOO_TPU_COMPILE_CACHE", setting)):
        if val is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, val)
    if want == "default":
        want = os.path.join(REPO, ".xla_cache")
    assert cc.resolve_setting() == want
    assert cc.default_dir() == os.path.join(REPO, ".xla_cache")


def test_entry_helpers_sanitize_and_round_trip(tmp_path):
    from igloo_tpu import compile_cache as cc
    d = str(tmp_path)
    assert cc.write_entry("prog-abc123-cache", b"\x00xla\x01", cache_dir=d)
    assert cc.read_entry("prog-abc123-cache", cache_dir=d) == b"\x00xla\x01"
    assert cc.entry_names(d) == ["prog-abc123-cache"]
    # path traversal / hidden / excluded names are rejected outright
    assert not cc.write_entry("../evil", b"x", cache_dir=d)
    assert not cc.write_entry(".hidden", b"x", cache_dir=d)
    assert not cc.write_entry("a/b", b"x", cache_dir=d)
    assert not cc.write_entry("nhints.json", b"{}", cache_dir=d)
    assert cc.read_entry("../../etc/passwd", cache_dir=d) is None
    assert cc.entry_names(d) == ["prog-abc123-cache"]
    # b64 round trip (the wire encoding of compile_cache_put)
    blob = bytes(range(256))
    assert cc.decode_entry(cc.encode_entry(blob)) == blob


def test_write_entry_repairs_abandoned_partial_writes(tmp_path):
    from igloo_tpu import compile_cache as cc
    d = str(tmp_path)
    # a zero-byte entry is never valid: rejected on write, invisible on
    # read/list (it can only be the stub of a killed process's write)
    assert not cc.write_entry("prog-empty-cache", b"", cache_dir=d)
    (tmp_path / "prog-stub-cache").write_bytes(b"")
    assert cc.read_entry("prog-stub-cache", cache_dir=d) is None
    assert "prog-stub-cache" not in cc.entry_names(d)
    # a truncated blob left by a killed process must NOT pin itself: a
    # later write of the full content (different size) replaces it
    (tmp_path / "prog-torn-cache").write_bytes(b"par")
    full = b"partial-write-now-complete"
    assert cc.write_entry("prog-torn-cache", full, cache_dir=d)
    assert cc.read_entry("prog-torn-cache", cache_dir=d) == full
    # same size ⇒ same content by contract: the existing file is kept
    assert cc.write_entry("prog-torn-cache", b"X" * len(full), cache_dir=d)
    assert cc.read_entry("prog-torn-cache", cache_dir=d) == full


def test_heartbeat_push_checks_stored_and_gives_up(tmp_path, monkeypatch):
    import json as _json

    from igloo_tpu import compile_cache as cc
    from igloo_tpu.cluster import rpc
    from igloo_tpu.cluster.worker import Worker
    d = str(tmp_path)
    monkeypatch.setattr(cc, "active_dir", lambda: d)
    for name in ("prog-aa-cache", "prog-bb-cache", "prog-cc-cache"):
        assert cc.write_entry(name, b"blob-" + name.encode(), cache_dir=d)
    old = time.time() - 2 * cc.TRANSFER_MIN_AGE_S
    for p in tmp_path.iterdir():
        os.utime(p, (old, old))

    w = Worker.__new__(Worker)  # push logic only; no server, no threads
    w.coordinator = "grpc+tcp://127.0.0.1:1"
    w._cache_known = set()
    w._push_failures = {}

    pushed = []

    def fake_actions(addr, actions):
        for name, payload in actions:
            assert name == "compile_cache_put"
            pushed.append(payload["name"])
            # coordinator refuses bb ({"stored": false} — e.g. disk error):
            # the worker must NOT count it as replicated
            stored = payload["name"] != "prog-bb-cache"
            yield _json.dumps({"stored": stored}).encode()

    monkeypatch.setattr(rpc, "flight_actions_raw", fake_actions)
    w._push_compile_cache()
    # one batched connection saw all three; aa/cc replicated, bb retried
    assert pushed == ["prog-aa-cache", "prog-bb-cache", "prog-cc-cache"]
    assert "prog-bb-cache" not in w._cache_known
    assert w._push_failures == {"prog-bb-cache": 1}
    for _ in range(2):  # 3-strike give-up: bb stops starving later beats
        w._push_compile_cache()
    assert w._push_failures["prog-bb-cache"] == 3
    assert "prog-bb-cache" in w._cache_known
    pushed.clear()
    w._push_compile_cache()
    assert pushed == []  # everything known: idle beat pushes nothing


def test_coordinator_compile_cache_actions(tmp_path, monkeypatch):
    from igloo_tpu import compile_cache as cc
    from igloo_tpu.cluster.coordinator import CoordinatorServer
    from igloo_tpu.cluster.rpc import flight_action, flight_action_raw
    monkeypatch.setattr(cc, "active_dir", lambda: str(tmp_path))
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0")
    try:
        addr = f"127.0.0.1:{coord.port}"
        blob = b"compiled-program-bytes"
        resp = flight_action(addr, "compile_cache_put", {
            "name": "jit_q3-deadbeef-cache",
            "data": cc.encode_entry(blob)})
        assert resp["stored"] is True
        assert cc.read_entry("jit_q3-deadbeef-cache") == blob
        got = flight_action_raw(addr, "compile_cache_get",
                                {"name": "jit_q3-deadbeef-cache"})
        assert got == blob
        # unknown / unsafe names come back empty, never error
        assert flight_action_raw(addr, "compile_cache_get",
                                 {"name": "no-such-entry"}) == b""
        assert flight_action_raw(addr, "compile_cache_get",
                                 {"name": "../evil"}) == b""
    finally:
        coord.shutdown()


# --- satellites --------------------------------------------------------------

def test_result_cache_entry_capacity_eviction():
    from igloo_tpu.exec.result_cache import ResultCache
    from igloo_tpu.utils import tracing
    rc = ResultCache(budget_bytes=1 << 30, capacity=2)
    t = pa.table({"x": [1, 2, 3]})
    with tracing.counter_delta() as delta:
        for i in range(3):
            rc.put((f"digest{i}", ("t",), ()), t)
    assert len(rc) == 2
    assert delta.get("result_cache.evicted") == 1
    # LRU order: digest0 went first
    assert rc.get(("digest0", ("t",), ())) is None
    assert rc.get(("digest2", ("t",), ())) is not None


def test_result_cache_capacity_default_is_bounded():
    from igloo_tpu.exec.result_cache import ResultCache
    assert ResultCache().capacity == ResultCache.DEFAULT_CAPACITY


def test_hint_store_concurrent_put_flush(tmp_path):
    from igloo_tpu.exec.hints import HintStore
    path = str(tmp_path / "nhints.json")
    store = HintStore(path)
    errors = []

    def worker(base):
        try:
            for i in range(200):
                store.put(("k", base, i % 10), i)
                if i % 20 == 0:
                    store.flush()
                store.get(("k", base, i % 10))
        except Exception as ex:  # pragma: no cover - the assertion target
            errors.append(ex)

    threads = [threading.Thread(target=worker, args=(b,)) for b in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    store.put(("final",), 42)
    store.flush()
    assert HintStore(path).get(("final",)) == 42


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
