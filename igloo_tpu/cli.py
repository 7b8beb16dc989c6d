"""igloo CLI.

Parity with the reference binary (crates/igloo/src/main.rs:9-20: --sql, --config,
--distributed) plus --device/--explain/--timing, an interactive REPL when no --sql
is given, and the same demo UX: with no tables configured, a sample `users` table
is registered (main.rs:59-77). Unlike the reference (gap G3: --distributed
silently falls back to local, main.rs:97-100), --distributed here really connects
to a coordinator and errors loudly when it cannot.
"""
from __future__ import annotations

import argparse
import os
import sys

import pyarrow as pa


def sample_users_table() -> pa.Table:
    # mirrors the reference CLI's in-memory demo table (main.rs:59-77)
    return pa.table({
        "id": pa.array([1, 2, 3, 4, 5], type=pa.int64()),
        "name": ["alice", "bob", "carol", "dave", "eve"],
        "age": pa.array([30, 25, 35, 28, 40], type=pa.int64()),
    })


def build_engine(cfg, use_jit: bool = True):
    from igloo_tpu.config import apply_storage, init_distributed, \
        make_provider
    from igloo_tpu.engine import QueryEngine
    kw = {}
    if cfg is not None:
        # multi-host runtime first: jax.distributed.initialize must run
        # before the first device query or the process stays single-host
        init_distributed(cfg)
        # [storage] policy + prefetch twins (env wins per-field)
        apply_storage(cfg)
        if cfg.cache_budget_bytes is not None:
            kw["cache_budget_bytes"] = cfg.cache_budget_bytes
        if cfg.mesh_shape:
            import math
            from igloo_tpu.parallel.mesh import make_mesh
            kw["mesh"] = make_mesh(math.prod(cfg.mesh_shape))
    # no explicit mesh config -> engine "default" sentinel (DEFAULT_MESH),
    # keeping the process-level knob authoritative
    engine = QueryEngine(use_jit=use_jit, **kw)
    registered = False
    if cfg is not None:
        for t in cfg.tables:
            engine.register_table(t.name, make_provider(t))
            registered = True
    if not registered:
        engine.register_table("users", sample_users_table())
    return engine


def _print_table(t: pa.Table, limit: int = 100) -> None:
    if t.num_rows > limit:
        shown = t.slice(0, limit)
        print(shown.to_pandas().to_string(index=False))
        print(f"... ({t.num_rows} rows total, showing {limit})")
    else:
        print(t.to_pandas().to_string(index=False))


def warm_cache(sf: float) -> int:
    """Compile every TPC-H query's fused program (twice: unhinted + hinted)
    into the persistent XLA cache and record cardinality hints, so any later
    process — including a fresh bench run — skips all cold compiles."""
    import time
    from igloo_tpu.bench.tpch import QUERIES, gen_tables, register_all
    from igloo_tpu.engine import QueryEngine
    t0 = time.perf_counter()
    tables = gen_tables(sf=sf)
    print(f"generated TPC-H sf={sf} ({time.perf_counter() - t0:.1f}s)",
          file=sys.stderr)
    engine = build_engine(None)
    register_all(engine, tables)
    for q, sql in QUERIES.items():
        t0 = time.perf_counter()
        try:
            engine.execute(sql)            # compile v1, record hints
            engine.result_cache.clear()
            engine.execute(sql)            # compile hinted program
        except Exception as ex:
            print(f"{q}: FAILED {type(ex).__name__}: {ex}", file=sys.stderr)
            continue
        print(f"{q}: warmed ({time.perf_counter() - t0:.1f}s)",
              file=sys.stderr)
    return 0


def render_top(status: dict, coordinator: str = "") -> str:
    """Render one `watch_status` snapshot (cluster/protocol.py
    WATCH_STATUS) as the `igloo top` screen. Pure — testable without a
    cluster (docs/observability.md#watchtower)."""
    import time
    out = []
    hdr = "igloo top"
    if coordinator:
        hdr += f" — {coordinator}"
    out.append(hdr)
    out.append(f"queries   qps {status.get('qps') or 0.0:g}   "
               f"p50 {status.get('p50_ms') or 0.0:g} ms   "
               f"p99 {status.get('p99_ms') or 0.0:g} ms   "
               f"(window {status.get('window_s') or 0.0:g}s)")
    serving = status.get("serving") or {}
    if serving:
        out.append("serving   " + "   ".join(
            f"{k} {serving[k]}" for k in sorted(serving)))
    workers = status.get("workers") or []
    out.append(f"workers ({len(workers)})")
    for w in workers:
        out.append(f"  {str(w.get('id', '?')):<14} "
                   f"{str(w.get('addr', '')):<24} "
                   f"devices {w.get('devices', 1):<3} "
                   f"slots {w.get('slots', 0):<3} "
                   f"age {w.get('age_s') or 0.0:g}s")
    samples = status.get("samples") or []
    if samples:
        # memory pressure from the newest sampler row's byte-sized gauges
        gauges = samples[-1].get("gauges") or {}
        mem = [(k, v) for k, v in sorted(gauges.items())
               if "hbm" in k or "bytes" in k]
        if mem:
            out.append("gauges    " + "   ".join(
                f"{k} {v:g}" for k, v in mem[:6]))
    active = status.get("active") or []
    out.append(f"active queries ({len(active)})"
               + (": " + ", ".join(str(q) for q in active)
                  if active else ""))
    out.append("recent events")
    evs = status.get("events") or []
    if not evs:
        out.append("  (none)")
    for ev in evs[-10:]:
        ts = time.strftime("%H:%M:%S", time.localtime(ev.get("ts") or 0.0))
        tags = [f"{k}={ev[k]}" for k in ("worker", "qid") if ev.get(k)]
        tags += [f"{k}={v}" for k, v in sorted(
            (ev.get("attrs") or {}).items())]
        out.append(f"  {ts}  {str(ev.get('severity', 'info')).upper():<5} "
                   f"{str(ev.get('kind', '?')):<22} " + " ".join(tags))
    return "\n".join(out)


def top_main(argv=None) -> int:
    """`igloo top`: live cluster dashboard off the coordinator's one-call
    `watch_status` action — qps/latency quantiles, admission state,
    per-worker topology, active queries, the journal tail."""
    ap = argparse.ArgumentParser(
        prog="igloo top",
        description="live cluster dashboard (watchtower snapshot)")
    ap.add_argument("--coordinator", default="127.0.0.1:50051",
                    help="coordinator address host:port")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds")
    ap.add_argument("--once", action="store_true",
                    help="print one snapshot and exit (no screen clear)")
    args = ap.parse_args(argv)
    import time
    from igloo_tpu.cluster.client import DistributedClient
    try:
        client = DistributedClient(args.coordinator)
        while True:
            status = client.watch_status()
            text = render_top(status, coordinator=args.coordinator)
            if not args.once:
                print("\x1b[2J\x1b[H", end="")
            print(text, flush=True)
            if args.once:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0
    except Exception as ex:
        print(f"error: cannot reach coordinator at {args.coordinator}: {ex}",
              file=sys.stderr)
        return 2


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "top":
        # subcommand, dispatched before the flag parser (the main surface
        # stays flag-based for reference parity)
        return top_main(argv[1:])
    ap = argparse.ArgumentParser(
        prog="igloo",
        description="igloo-tpu: TPU-native distributed SQL engine")
    ap.add_argument("--sql", help="SQL to execute (omit for a REPL)")
    ap.add_argument("--config", help="TOML config file")
    ap.add_argument("--distributed", action="store_true",
                    help="execute through a coordinator (requires a running "
                         "cluster; see igloo-coordinator / igloo-worker)")
    ap.add_argument("--coordinator", default=None,
                    help="coordinator address host:port for --distributed")
    ap.add_argument("--device", choices=["auto", "tpu", "cpu"], default="auto")
    ap.add_argument("--no-jit", action="store_true",
                    help="run kernels eagerly (debugging)")
    ap.add_argument("--timing", action="store_true",
                    help="print the per-operator stats tree (rows, wall, "
                         "compile/execute split, transfer bytes) after each "
                         "query, plus the raw timing spans")
    ap.add_argument("--warm-cache", nargs="?", const="1", default=None,
                    metavar="SF",
                    help="precompile the TPC-H stage set at the given scale "
                         "factor (default 1) into the persistent XLA cache + "
                         "cardinality-hint store, then exit. XLA programs are "
                         "shape-bucketed, so warm at the scale you will run")
    args = ap.parse_args(argv)

    if args.device != "auto":
        # importing the package already imported jax, which read
        # JAX_PLATFORMS then: set the config (the env var too, for children)
        os.environ["JAX_PLATFORMS"] = args.device
        import jax
        jax.config.update("jax_platforms", args.device)
        if args.device == "tpu":
            # asked for the chip: fail rather than run somewhere else
            try:
                found = jax.default_backend()
            except RuntimeError as ex:
                found = f"none ({str(ex).splitlines()[0]})"
            if found != "tpu":
                print(f"error: --device tpu but JAX found {found}",
                      file=sys.stderr)
                return 2

    from igloo_tpu.config import Config
    from igloo_tpu.errors import IglooError
    from igloo_tpu.utils import tracing

    cfg = Config.load(args.config) if args.config else None

    if args.warm_cache is not None:
        return warm_cache(float(args.warm_cache))

    if args.distributed:
        # no silent local fallback (reference gap G3): distributed means
        # distributed, and failure to reach the cluster is an error
        from igloo_tpu.cluster.client import DistributedClient
        addr = args.coordinator
        if addr is None and cfg is not None:
            addr = f"{cfg.cluster.coordinator_host}:{cfg.cluster.coordinator_port}"
        if addr is None:
            addr = "127.0.0.1:50051"
        try:
            client = DistributedClient(addr)
            client.ping()
        except Exception as ex:
            print(f"error: cannot reach coordinator at {addr}: {ex}",
                  file=sys.stderr)
            return 2
        runner = client.execute
    else:
        engine = build_engine(cfg, use_jit=not args.no_jit)
        # engine.query keeps the per-query stats (operator tree) beside the
        # table, so --timing can print what actually executed
        runner = lambda sql: engine.query(sql)  # noqa: E731

    def run_one(sql: str) -> int:
        from igloo_tpu.engine import QueryResult
        from igloo_tpu.utils import stats
        try:
            tracing.reset()
            result = runner(sql)
            qstats = None
            if isinstance(result, QueryResult):
                qstats = result.stats
                result = result.table
            _print_table(result)
            if args.timing:
                if qstats is not None:
                    print(stats.render_tree(qstats), file=sys.stderr)
                print(tracing.last_trace(), file=sys.stderr)
            return 0
        except IglooError as ex:
            print(f"error: {ex}", file=sys.stderr)
            return 1

    if args.sql:
        return run_one(args.sql)

    # REPL
    print("igloo-tpu SQL shell — \\q to quit")
    buf = []
    while True:
        try:
            prompt = "igloo> " if not buf else "   ... "
            line = input(prompt)
        except (EOFError, KeyboardInterrupt):
            print()
            return 0
        if line.strip() in ("\\q", "quit", "exit"):
            return 0
        buf.append(line)
        if line.rstrip().endswith(";") or (len(buf) == 1 and line.strip() and
                                           not line.rstrip().endswith(",")):
            sql = "\n".join(buf).rstrip().rstrip(";")
            buf = []
            if sql.strip():
                run_one(sql)


if __name__ == "__main__":
    sys.exit(main())
