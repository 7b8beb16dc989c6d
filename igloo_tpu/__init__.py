"""igloo-tpu: a TPU-native distributed SQL query engine.

Brand-new design with the capabilities of the reference engine (igloo-io/igloo, a
Rust/DataFusion/Arrow-Flight coordinator–worker SQL engine — see SURVEY.md): federated
SQL over Parquet/CSV/Iceberg/Postgres/MySQL, an Arrow Flight SQL front door, a
coordinator/worker control plane — with the execution tier designed for TPUs: query
fragments lower to `jax.jit`-compiled XLA computations over HBM-resident columnar
batches, shuffles run as ICI `all_to_all` collectives, hot batches pin in HBM.

Public API (replaces the reference's stub pyigloo, pyigloo/src/lib.rs):

    import igloo_tpu
    sess = igloo_tpu.connect()                  # in-process session
    sess.register_parquet("t", "data/t.parquet")
    table = sess.sql("SELECT a, b FROM t WHERE a > 10")   # -> pyarrow.Table
"""
import jax

# The engine's device lanes are int64/float64 (SQL semantics, TPC-H decimals). The
# TPU emulates both: TPC-H sums agree with a float64 oracle to ~1e-14 on the v5e,
# but its f64 divide is not bit-exact (exec/codec.py's canary decides per backend).
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: join-heavy TPC-H stages cost minutes of
# cold compile on TPU; caching them on disk makes every process after the
# first start warm, and the cluster tier replicates entries so a shape
# compiles once per CLUSTER (igloo_tpu/compile_cache.py has the policy,
# telemetry hooks, and the cluster transfer; docs/compile_cache.md the
# knobs). A setup failure warns once and bumps `compile_cache.disabled`
# instead of dying silently.
from igloo_tpu import compile_cache as _compile_cache  # noqa: E402

_compile_cache.configure()
_compile_cache.install_metrics()

from igloo_tpu import types  # noqa: E402,F401
from igloo_tpu.version import __version__  # noqa: E402,F401


def connect(config=None):
    """Open an in-process session (the reference's `QueryEngine::new`,
    crates/engine/src/lib.rs:39-44)."""
    from igloo_tpu.runtime.session import Session
    return Session(config=config)
