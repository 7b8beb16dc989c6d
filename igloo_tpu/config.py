"""Configuration.

The reference accepts `--config <path>` and ignores it (crates/igloo/src/main.rs:
36-40, gap in §5.6); ours is real: TOML with tables to register, device/mesh
settings, cache budget, and cluster addresses (the hardcoded 127.0.0.1:5005x pair
in the reference's daemons becomes configuration here).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional

import tomllib

from igloo_tpu.errors import IglooError


@dataclass
class TableConfig:
    name: str
    path: str
    format: str = "parquet"        # parquet | csv | iceberg
    options: dict = field(default_factory=dict)


@dataclass
class ClusterConfig:
    coordinator_host: str = "127.0.0.1"
    coordinator_port: int = 50051
    worker_host: str = "127.0.0.1"
    worker_port: int = 50052
    flight_port: int = 50055
    heartbeat_interval_s: float = 5.0
    # liveness: evict workers silent for this long (reference records last_seen
    # but never acts on it — gap G6)
    worker_timeout_s: float = 15.0


@dataclass
class RpcConfig:
    """Cluster failure budget ([rpc] TOML section; every field is also
    overridable per-process via the matching IGLOO_RPC_* env var, and
    `query_deadline_s` via IGLOO_QUERY_DEADLINE_S — env wins). See
    docs/distributed.md#failure-model for the semantics.

    Every field defaults to None = "not set in the TOML": `rpc_policy()`
    passes only the set fields through, so the numeric defaults live in ONE
    place — cluster/rpc.py's RpcPolicy — instead of a hand-maintained copy
    here that would silently shadow a tuned default."""
    connect_timeout_s: Optional[float] = None
    call_timeout_s: Optional[float] = None
    stream_timeout_s: Optional[float] = None
    retries: Optional[int] = None
    backoff_base_s: Optional[float] = None
    backoff_max_s: Optional[float] = None
    backoff_jitter: Optional[float] = None
    # default per-query deadline for distributed execution; None = unbounded
    query_deadline_s: Optional[float] = None


@dataclass
class ServingConfig:
    """Multi-tenant front-door knobs ([serving] TOML section; each field is
    also overridable per-process via the matching IGLOO_SERVING_* env var —
    env wins, like [rpc]). See docs/serving.md for semantics.

    None = "not set in the TOML": the numeric defaults live in ONE place —
    cluster/serving.py's AdmissionController — so a tuned default is never
    silently shadowed by a stale copy here."""
    queue_depth: Optional[int] = None          # 0 = serialize (kill switch)
    max_concurrency: Optional[int] = None
    session_inflight: Optional[int] = None
    hbm_budget_bytes: Optional[int] = None
    weights: Optional[list[int]] = None        # per-priority-tier dequeue


@dataclass
class StorageConfig:
    """Object-store failure budget + prefetch ([storage] TOML section; every
    field is also overridable per-process via the matching IGLOO_STORAGE_*
    env var — env wins, like [rpc]). See docs/storage.md for semantics.

    None = "not set in the TOML": the numeric defaults live in ONE place —
    storage/policy.py's StoragePolicy and storage/prefetch.py — so a tuned
    default is never silently shadowed by a stale copy here."""
    connect_timeout_s: Optional[float] = None
    read_timeout_s: Optional[float] = None
    retries: Optional[int] = None
    backoff_base_s: Optional[float] = None
    backoff_max_s: Optional[float] = None
    backoff_jitter: Optional[float] = None
    prefetch: Optional[bool] = None          # False = kill switch
    prefetch_bytes: Optional[int] = None


@dataclass
class DistributedConfig:
    """Multi-host JAX runtime (SURVEY #20 "jax distributed init").

    When `enabled`, `init_distributed()` brings this process into a
    pod-spanning JAX runtime via `jax.distributed.initialize`: all hosts'
    chips join ONE global device set, and QueryEngine's mesh then spans hosts
    — XLA routes intra-host collectives over ICI and cross-host legs over
    DCN. This is the scale-UP tier; the Flight coordinator/worker fragment
    tier (cluster/) is the scale-OUT tier for independent engines. The two
    compose: each fragment worker may itself be a multi-host mesh process
    group (docs/distributed.md)."""
    enabled: bool = False
    coordinator_address: Optional[str] = None  # host:port of process 0
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    local_device_ids: Optional[list[int]] = None


@dataclass
class Config:
    tables: list[TableConfig] = field(default_factory=list)
    device: str = "auto"           # auto | tpu | cpu
    mesh_shape: Optional[list[int]] = None
    mesh_axes: list[str] = field(default_factory=lambda: ["data"])
    # None: the resident share of the device's memory (exec/cache.py
    # hbm_budgets); a number overrides it
    cache_budget_bytes: Optional[int] = None
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    rpc: RpcConfig = field(default_factory=RpcConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    storage: StorageConfig = field(default_factory=StorageConfig)
    distributed: DistributedConfig = field(default_factory=DistributedConfig)
    use_jit: bool = True

    @staticmethod
    def load(path: str) -> "Config":
        if not os.path.exists(path):
            raise IglooError(f"config file not found: {path}")
        with open(path, "rb") as fh:
            raw = tomllib.load(fh)
        cfg = Config()
        for t in raw.get("tables", []):
            if "name" not in t or "path" not in t:
                raise IglooError("each [[tables]] entry needs name and path")
            cfg.tables.append(TableConfig(
                name=t["name"], path=t["path"],
                format=t.get("format", "parquet"),
                options={k: v for k, v in t.items()
                         if k not in ("name", "path", "format")}))
        eng = raw.get("engine", {})
        cfg.device = eng.get("device", cfg.device)
        cfg.mesh_shape = eng.get("mesh_shape", cfg.mesh_shape)
        cfg.mesh_axes = eng.get("mesh_axes", cfg.mesh_axes)
        cfg.cache_budget_bytes = eng.get("cache_budget_bytes",
                                         cfg.cache_budget_bytes)
        cfg.use_jit = eng.get("use_jit", cfg.use_jit)
        cl = raw.get("cluster", {})
        for k in ("coordinator_host", "coordinator_port", "worker_host",
                  "worker_port", "flight_port", "heartbeat_interval_s",
                  "worker_timeout_s"):
            if k in cl:
                setattr(cfg.cluster, k, cl[k])
        rp = raw.get("rpc", {})
        for k in ("connect_timeout_s", "call_timeout_s", "stream_timeout_s",
                  "retries", "backoff_base_s", "backoff_max_s",
                  "backoff_jitter", "query_deadline_s"):
            if k in rp:
                setattr(cfg.rpc, k, rp[k])
        sv = raw.get("serving", {})
        for k in ("queue_depth", "max_concurrency", "session_inflight",
                  "hbm_budget_bytes", "weights"):
            if k in sv:
                setattr(cfg.serving, k, sv[k])
        st = raw.get("storage", {})
        for k in ("connect_timeout_s", "read_timeout_s", "retries",
                  "backoff_base_s", "backoff_max_s", "backoff_jitter",
                  "prefetch", "prefetch_bytes"):
            if k in st:
                setattr(cfg.storage, k, st[k])
        ds = raw.get("distributed", {})
        for k in ("enabled", "coordinator_address", "num_processes",
                  "process_id", "local_device_ids"):
            if k in ds:
                setattr(cfg.distributed, k, ds[k])
        return cfg


def init_distributed(cfg: "Config") -> bool:
    """Join the pod-spanning JAX runtime described by [distributed]; returns
    True when initialization ran. Safe to call unconditionally — a disabled
    section is a no-op, and TPU pod slices can omit every field
    (jax.distributed auto-detects coordinator/process ids from the TPU
    metadata server). After this, `jax.devices()` is GLOBAL and
    `QueryEngine(mesh=...)` meshes span hosts (docs/distributed.md)."""
    d = cfg.distributed
    if not d.enabled:
        return False
    import jax
    kw = {}
    if d.coordinator_address is not None:
        kw["coordinator_address"] = d.coordinator_address
    if d.num_processes is not None:
        kw["num_processes"] = d.num_processes
    if d.process_id is not None:
        kw["process_id"] = d.process_id
    if d.local_device_ids is not None:
        kw["local_device_ids"] = d.local_device_ids
    jax.distributed.initialize(**kw)
    return True


def rpc_policy(cfg: "Config"):
    """[rpc] section -> cluster RpcPolicy (imported lazily: config loading
    must not pull pyarrow.flight into processes that never talk Flight).
    Only fields actually set in the TOML are passed — unset ones keep the
    RpcPolicy defaults."""
    from igloo_tpu.cluster.rpc import RpcPolicy
    kw = {f: getattr(cfg.rpc, f)
          for f in ("connect_timeout_s", "call_timeout_s", "stream_timeout_s",
                    "retries", "backoff_base_s", "backoff_max_s",
                    "backoff_jitter")
          if getattr(cfg.rpc, f) is not None}
    return RpcPolicy(**kw)


def storage_policy(cfg: "Config"):
    """[storage] section -> storage StoragePolicy (only fields actually set
    in the TOML are passed — unset ones keep the StoragePolicy defaults)."""
    from igloo_tpu.storage.policy import StoragePolicy
    kw = {f: getattr(cfg.storage, f)
          for f in ("connect_timeout_s", "read_timeout_s", "retries",
                    "backoff_base_s", "backoff_max_s", "backoff_jitter")
          if getattr(cfg.storage, f) is not None}
    return StoragePolicy(**kw)


def apply_storage(cfg: "Config") -> None:
    """Install the [storage] section process-wide: the policy as the
    default every ObjectStore uses (env still wins per field —
    policy_from_env layers on top) and the prefetch twins."""
    from igloo_tpu.storage import policy as sp
    from igloo_tpu.storage import prefetch as spf
    sp.set_default_policy(sp.policy_from_env(storage_policy(cfg)))
    spf.configure(cfg.storage.prefetch, cfg.storage.prefetch_bytes)


def make_provider(t: TableConfig):
    if t.format == "parquet":
        from igloo_tpu.connectors.parquet import ParquetTable
        return ParquetTable(t.path)
    if t.format == "csv":
        from igloo_tpu.connectors.csv import CsvTable
        return CsvTable(t.path, **t.options)
    if t.format == "iceberg":
        from igloo_tpu.connectors.iceberg import IcebergTable
        return IcebergTable(t.path)
    raise IglooError(f"unknown table format {t.format!r}")
