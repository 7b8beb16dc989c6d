"""Device mesh + batch sharding utilities.

The reference scales rows across workers only in declaration (FragmentType::
Shuffle is never constructed, crates/coordinator/src/fragment.rs:12; the
worker-side shuffle fetch returns empty bytes, crates/worker/src/service.rs:26-32).
Here the row axis is a real `jax.sharding.Mesh` axis: DeviceBatch lanes are
row-sharded with `NamedSharding(mesh, P(ROWS))`, repartition is
`shard_map` + `lax.all_to_all` over ICI (shuffle.py), and partial->final
aggregation rides the same mesh (parallel/executor.py).

One mesh axis is enough for a SQL engine: there is no tensor/model axis to
shard (SURVEY.md §5.7) — the row axis is the scaling dimension, and ICI
collectives replace the reference's dead worker<->worker gRPC path.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from igloo_tpu.exec.batch import DeviceBatch, DeviceColumn, MIN_CAPACITY
from igloo_tpu.utils import tracing

ROWS = "rows"  # the one mesh axis: row-partitioned data parallelism


def resolve_mesh(setting) -> Optional[Mesh]:
    """Shared mesh-resolution rule (QueryEngine, worker daemon): None =
    single-device; "auto" = row-shard across all local devices when more than
    one is visible; "default" = the process default (engine.DEFAULT_MESH,
    which the test suite pins to None so single-device paths keep coverage on
    the virtual 8-device CPU mesh); a Mesh passes through."""
    if setting == "default":
        from igloo_tpu.engine import DEFAULT_MESH
        setting = DEFAULT_MESH
    if setting is None:
        return None
    if setting == "auto":
        return make_mesh() if len(jax.devices()) > 1 else None
    return setting


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """A 1-D mesh over `n_devices` (default: all local devices). Row capacity
    bucketing is power-of-two, so meshes of non-power-of-two size are rounded
    down to the largest power of two that divides evenly into capacities."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    pow2 = 1
    while pow2 * 2 <= n:
        pow2 *= 2
    return Mesh(np.asarray(devices[:pow2]), (ROWS,))


def row_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(ROWS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _put_batch(batch: DeviceBatch, sharding: NamedSharding,
               min_capacity: int) -> DeviceBatch:
    # mesh boundary: widen carrier-resident columns eagerly. A 0-d
    # carrier_arg cannot take a row-partitioned spec, and shard_map programs
    # take batch leaves under a uniform P(ROWS) — the compressed form stops
    # at the mesh edge (exchange between WORKERS stays encoded; see
    # cluster/exchange.py).
    from igloo_tpu.exec.batch import materialize_batch
    batch = materialize_batch(batch)
    if batch.capacity < min_capacity:
        from igloo_tpu.exec import kernels as K
        batch = K.resize_batch(batch, min_capacity)
    cols = [DeviceColumn(c.dtype, jax.device_put(c.values, sharding),
                         jax.device_put(c.nulls, sharding)
                         if c.nulls is not None else None,
                         c.dictionary) for c in batch.columns]
    return DeviceBatch(batch.schema, cols, jax.device_put(batch.live, sharding))


def shard_rows(batch: DeviceBatch, mesh: Mesh) -> DeviceBatch:
    """Reshard a batch so its lanes are row-partitioned across the mesh.
    Capacity is padded up so every device gets at least MIN_CAPACITY lanes.
    The H2D upload IS the repartition: each device receives only its row
    slice, so no separate redistribution collective runs. `mesh.shard_uploads`
    / `mesh.sharded_lanes` attribute the uploads per query/fragment (lanes =
    padded capacity, known host-side without a device sync; divide by the
    mesh size for lanes-per-device)."""
    n = int(mesh.devices.size)
    tracing.counter("mesh.shard_uploads")
    # the PADDED capacity (what _put_batch actually uploads), not the
    # incoming one — small batches resize up to n * MIN_CAPACITY first
    tracing.counter("mesh.sharded_lanes",
                    max(batch.capacity, n * MIN_CAPACITY))
    return _put_batch(batch, row_sharding(mesh), n * MIN_CAPACITY)


def mesh_device_count(setting) -> int:
    """Devices a resolved mesh setting WOULD span (1 = single-device): the
    topology number a worker reports at registration/heartbeat and the basis
    of its execution-slot default — a mesh fragment occupies every device of
    the mesh at once (cluster/worker.py)."""
    try:
        m = resolve_mesh(setting)
    except Exception:
        return 1
    return int(m.devices.size) if m is not None else 1


def replicate(batch: DeviceBatch, mesh: Mesh) -> DeviceBatch:
    """Reshard a batch so every device holds a full copy (an eager all-gather
    when the input was row-sharded)."""
    return _put_batch(batch, replicated_sharding(mesh), MIN_CAPACITY)


def is_row_sharded(batch: DeviceBatch) -> bool:
    sh = batch.live.sharding
    return isinstance(sh, NamedSharding) and sh.spec and sh.spec[0] == ROWS
