"""Two-level parallelism (docs/distributed.md "Two-level topology"): the mesh
tier running INSIDE fragment-tier workers. Cheap tier-1 coverage — tiny
tables, a 2-device mesh, no subprocesses (the full 2-workers x 2-devices
cluster is scripts/twolevel_smoke.py in validate.sh)."""
import numpy as np
import pyarrow as pa
import jax.numpy as jnp

from igloo_tpu.catalog import MemTable
from igloo_tpu.engine import QueryEngine
from igloo_tpu.parallel.mesh import make_mesh, mesh_device_count
from igloo_tpu.utils import tracing


def _tables():
    rng = np.random.default_rng(5)
    n = 512
    orders = pa.table({"o_id": np.arange(n, dtype=np.int64),
                       "o_cust": rng.integers(0, 8, n),
                       "o_total": np.round(rng.random(n) * 100, 2)})
    cust = pa.table({"c_id": np.arange(8, dtype=np.int64),
                     "c_name": pa.array([f"c{i}" for i in range(8)])})
    return orders, cust


def _engines(mesh_n=2):
    orders, cust = _tables()
    sharded = QueryEngine(mesh=make_mesh(mesh_n))
    single = QueryEngine(mesh=None)
    for e in (sharded, single):
        e.register_table("orders", MemTable(orders))
        e.register_table("cust", MemTable(cust))
    return sharded, single


def _assert_rows_equal(got: pa.Table, want: pa.Table):
    g, w = got.to_pydict(), want.to_pydict()
    assert list(g) == list(w)
    for k in g:
        if str(got.column(k).type) == "double":
            # sharded reductions sum in a different order; row identity, not
            # bit identity, is the contract for float aggregates
            np.testing.assert_allclose(np.array(g[k], dtype=float),
                                       np.array(w[k], dtype=float),
                                       rtol=1e-9, err_msg=k)
        else:
            assert g[k] == w[k], k


# --- jax.shard_map as ShardedExecutor._jitted_shard_map calls it ---

def test_jax_shard_map_runs_on_row_mesh():
    from igloo_tpu.parallel.mesh import ROWS
    from jax.sharding import PartitionSpec as P
    import jax
    mesh = make_mesh(2)

    def f(x):
        return jax.lax.psum(jnp.sum(x), ROWS)

    out = jax.shard_map(f, mesh=mesh, in_specs=(P(ROWS),), out_specs=P(),
                        check_vma=False)(jnp.arange(8, dtype=jnp.int32))
    assert int(out) == 28


# --- sharded execution equivalence + chip-level broadcast composition ---

def test_sharded_join_agg_matches_single_device():
    """Row-sharded upload (the H2D IS the repartition) + mesh join/agg return
    rows identical to the single-device path; the tiny build side takes the
    mesh broadcast rule — composing with (not duplicating) the fragment
    tier's host-level broadcast decision, which is a planner concern."""
    sharded, single = _engines()
    sql = ("SELECT c.c_name, COUNT(*) AS n, SUM(o.o_total) AS s "
           "FROM orders o JOIN cust c ON o.o_cust = c.c_id "
           "GROUP BY c.c_name ORDER BY c.c_name")
    with tracing.counter_delta() as delta:
        got = sharded.execute(sql)
    _assert_rows_equal(got, single.execute(sql))
    # the mesh tier really ran: row-sharded uploads happened, and the small
    # build side (8 rows vs 512) replicated chip-side exactly once per join
    # — no duplicated output rows (asserted by row equality above)
    assert delta.get("mesh.shard_uploads") > 0
    assert delta.get("join.broadcast") >= 1


def test_explain_analyze_mesh_annotation():
    sharded, _ = _engines()
    out = sharded.execute(
        "EXPLAIN ANALYZE SELECT o_cust, COUNT(*) AS n FROM orders "
        "GROUP BY o_cust ORDER BY o_cust")
    text = "\n".join(out.column("plan").to_pylist())
    assert "-- mesh: devices=2" in text, text
    assert "lanes_per_device=" in text


# --- topology-derived planning ---

def _join_plan():
    eng = QueryEngine()
    orders, cust = _tables()
    eng.register_table("orders", MemTable(orders, partitions=2))
    eng.register_table("cust", MemTable(cust, partitions=2))
    return eng.plan("SELECT o.o_id, c.c_name FROM orders o "
                    "JOIN cust c ON o.o_cust = c.c_id")


def test_bucket_placement_homogeneous_unchanged():
    from igloo_tpu.cluster.fragment import DistributedPlanner
    planner = DistributedPlanner(["a", "b"], shuffle_buckets=4,
                                 topology={"a": 2, "b": 2})
    assert planner.total_shards == 4
    assert planner._bucket_placement(4) == ["a", "b", "a", "b"]


def test_bucket_placement_weighted_by_devices():
    from igloo_tpu.cluster.fragment import DistributedPlanner
    planner = DistributedPlanner(["a", "b"], shuffle_buckets=4,
                                 topology={"a": 3, "b": 1})
    placement = planner._bucket_placement(4)
    # largest-remainder proportional: the 3-chip worker takes 3 of 4 buckets
    assert placement.count("a") == 3 and placement.count("b") == 1
    # interleaved, not front-loaded: worker b appears before the last slot
    assert "b" in placement[:2]


def test_planner_assigns_join_buckets_by_topology():
    from igloo_tpu.cluster.fragment import DistributedPlanner
    plan = _join_plan()
    planner = DistributedPlanner(["a", "b"], shuffle_buckets=4,
                                 topology={"a": 3, "b": 1})
    frags = planner.plan(plan)
    joins = [f for f in frags if f.kind == "join"]
    assert len(joins) == 4
    workers = [f.worker for f in joins]
    assert workers.count("a") == 3 and workers.count("b") == 1


def test_salted_extras_avoid_hot_buckets_placed_worker():
    """Heterogeneous placement can put the hot bucket anywhere; the salted
    extra buckets must rotate AFTER the worker the hot bucket was PLACED on
    (not after workers[hot % W]), or the split re-serializes on one host."""
    from igloo_tpu.cluster.fragment import DistributedPlanner
    from igloo_tpu.exec import hints
    plan = _join_plan()
    # force the salted path: flag the probe (left/orders) side's sketch as
    # pathologically skewed at this bucket count
    store = hints.adaptive_store()
    from igloo_tpu.plan import logical as L
    join = next(n for n in L.walk_plan(plan) if isinstance(n, L.Join))
    # only the PROBE side carries a sketch (an unobserved build side keeps
    # the broadcast switch out of play — it needs both sides observed)
    fp = hints.plan_fp(join.left)
    assert fp is not None
    store.observe_by_digest(hints.digest_key(fp), max_share=0.99,
                            hot_bucket=3, nbuckets=4, rows=512)
    planner = DistributedPlanner(["a", "b"], shuffle_buckets=4,
                                 topology={"a": 3, "b": 1})
    frags = planner.plan(plan)
    salted = [d for d in planner.adaptive_info
              if d.get("strategy") == "salted"]
    assert salted, planner.adaptive_info
    joins = {f.bucket: f.worker for f in frags if f.kind == "join"}
    # weighted placement puts hot bucket 3 on 'a' (placement a,b,a,a);
    # every salted extra bucket (>= 4) must land on the OTHER worker
    assert joins[3] == "a", joins
    extras = [w for b, w in joins.items() if b >= 4]
    assert extras and all(w == "b" for w in extras), joins


def test_worker_info_serde_roundtrip_and_legacy():
    from igloo_tpu.cluster import serde
    d = serde.worker_info_to_json("w1", "grpc+tcp://h:1", devices=4, slots=2)
    info = serde.worker_info_from_json(d)
    assert info == {"id": "w1", "addr": "grpc+tcp://h:1", "devices": 4,
                    "slots": 2, "events": []}
    # the retired wall-clock `ts` field must be GONE from the payload (no
    # consumer ever read it — wire-contract true positive, PR 14) but a
    # legacy payload still carrying it must parse untouched
    assert "ts" not in d
    old = serde.worker_info_from_json({"id": "w1", "addr": "a", "ts": 1.0})
    assert old["id"] == "w1" and old["devices"] == 1
    # a pre-topology worker's payload registers as single-device
    legacy = serde.worker_info_from_json({"id": "w0", "addr": "x"})
    assert legacy["devices"] == 1 and legacy["slots"] == 0


def test_membership_tracks_topology():
    from igloo_tpu.cluster.coordinator import Membership
    m = Membership(timeout_s=60)
    m.register("w1", "addr1", devices=4, slots=2)
    m.register("w2", "addr2")
    assert m.topology() == {"addr1": 4, "addr2": 1}
    # heartbeat refreshes a changed device count (restart behind same id)
    assert m.heartbeat("w1", devices=2)
    assert m.topology()["addr1"] == 2
    # absent devices field leaves the recorded topology alone
    assert m.heartbeat("w1")
    assert m.topology()["addr1"] == 2


# --- worker-side routing + slots ---

def test_worker_slot_default_accounts_for_mesh():
    from igloo_tpu.cluster.worker import _default_slots
    import jax
    local = jax.local_device_count()  # 8 on the virtual CPU mesh
    assert _default_slots(1) == max(2, 2 * local)
    # a mesh fragment occupies every chip of the mesh: one independent
    # execution unit -> 2 slots, so HBM predictions stay per-host honest
    assert _default_slots(local) == 2
    assert _default_slots(local // 2) == 4


def test_mesh_device_count_follows_setting():
    assert mesh_device_count(None) == 1
    assert mesh_device_count(make_mesh(2)) == 2
    # "default" resolves through engine.DEFAULT_MESH, pinned to None in
    # conftest -> single-device
    assert mesh_device_count("default") == 1


def test_plan_wants_mesh_routing():
    from igloo_tpu.cluster.worker import _plan_wants_mesh
    eng = QueryEngine()
    orders, cust = _tables()
    eng.register_table("orders", MemTable(orders))
    eng.register_table("cust", MemTable(cust))
    assert not _plan_wants_mesh(
        eng.plan("SELECT o_id FROM orders WHERE o_total > 50"))
    assert _plan_wants_mesh(
        eng.plan("SELECT o.o_id FROM orders o JOIN cust c "
                 "ON o.o_cust = c.c_id"))
    assert _plan_wants_mesh(
        eng.plan("SELECT o_cust, COUNT(*) AS n FROM orders GROUP BY o_cust"))
