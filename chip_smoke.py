#!/usr/bin/env python
"""chip_smoke.py: prove that igloo-tpu's main path runs on the TPU.

ONE process (it holds the chip) drives TPC-H at --sf (default 1), generated
from --seed and staged as Parquet inside the run, through both ways users
reach the engine:

  session  QueryEngine over Parquet tables (bench/runner.py:make_engine),
           every query of --queries, each run twice (cold, second);
  served   a CoordinatorServer + one Worker in this process on loopback,
           queried by a DistributedClient over Arrow Flight (--served).

With a session phase goes one sorted probe join over made-up tables (float
keys take no direct table; the default queries' joins are all direct): on a
TPU its expand phase is the scatter + cummax scan, which no CPU run of the
compilers takes.

Every result is compared with the independent pandas oracle
(bench/tpch_pandas.py) on the same tables: same rows in the query's order,
strings/ints/dates equal, floats within REL_TOL. One JSON line per query
says where it ran (tier, jit misses, compile-cache traffic, transfer bytes,
peak HBM). The run FAILS if a session query ran off the device tier, a
`nofuse` sentinel was found armed, a served fragment did not execute on the
worker, or JAX's first device is not a TPU — whatever else passed. Run it
on the CPU at a small --sf as a rehearsal: every phase runs, and it ends
`"ok": false`.

`--chips 4` runs ONLY the mesh tier: q1 and q3 row-sharded over a
four-device mesh, and the same two with mesh=None on one device, both
against the oracle, with the per-device placement of `lineitem` and the
collectives of the compiled programs.

Last line of stdout, always:
  {"ok": <bool>, "device": {"platform": ..., "kind": ..., "count": N}}
Exit code 0 only with "ok": true.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

#: relative tolerance on float aggregates against the float64 pandas oracle.
#: The engine sums in float64 too (emulated on the TPU), but in another
#: order: a sum over ~6M SF1 rows reorders ~1e7 additions of relative
#: error 2^-53 each, so agreement to 1e-9 is what float64 allows and what
#: tests/test_tpch.py holds the CPU backend to.
REL_TOL = 1e-9

TABLES = ("region", "nation", "supplier", "part", "partsupp", "customer",
          "orders", "lineitem")

_failures: list = []


def emit(**rec) -> None:
    print(json.dumps(rec, sort_keys=True, default=str), flush=True)


def check(ok: bool, what: str) -> bool:
    """Record a failed check; every one reaches the exit code."""
    if not ok:
        _failures.append(what)
        emit(check="FAILED", what=what)
    return bool(ok)


# --- set-up ------------------------------------------------------------------

def rebuild_native() -> bool:
    """What runs is built from committed files: drop a stale (git-ignored)
    _native.so and let the loader rebuild it from hash64.c."""
    from igloo_tpu import native
    if os.path.exists(native._SO):
        os.remove(native._SO)
    return native.available()


def cache_state() -> dict:
    """The compile cache and the sidecar stores beside it, which change
    which programs get compiled (exec/hints.py)."""
    from igloo_tpu import compile_cache
    d = compile_cache.active_dir()
    state = {"dir": d, "placed_by_JAX_COMPILATION_CACHE_DIR":
             bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
             "xla_entries": 0, "sidecars": {}}
    if d and os.path.isdir(d):
        names = os.listdir(d)
        state["sidecars"] = {
            n: os.path.getsize(os.path.join(d, n))
            for n in names if n.endswith(".json")}
        state["xla_entries"] = len(names) - len(state["sidecars"])
    state["sidecars_empty"] = not state["sidecars"]
    return state


def stage(sf: float, seed: int, root: str, only: list) -> tuple:
    """Write the eight tables as Parquet under `root`, or with --tables just
    those, by the benchmark's vectorised generator; -> (pandas frames for the
    oracle, row counts)."""
    import pyarrow.parquet as pq
    if only:
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmark"))
        from datagen import gen_tables
        tables = gen_tables(sf=sf, seed=seed, tables=only)
    else:
        from igloo_tpu.bench.tpch import gen_tables
        tables = gen_tables(sf=sf, seed=seed)
    frames = {}
    for name, tbl in tables.items():
        pq.write_table(tbl, os.path.join(root, f"{name}.parquet"))
        frames[name] = frame(tbl)
    return frames, {n: t.num_rows for n, t in tables.items()}


# --- the oracle comparison ---------------------------------------------------

def frame(table):
    """Arrow table -> DataFrame with date columns as int days (the oracle's
    convention, bench/tpch_pandas.py)."""
    import pandas as pd
    import pyarrow as pa
    return pd.DataFrame({
        f.name: (col.cast(pa.int32()).to_numpy()
                 if pa.types.is_date32(f.type) else col.to_pandas())
        for f, col in zip(table.schema, table.columns)})


def compare(got, want) -> tuple:
    """(largest relative error over float columns, [problems]). `got` is the
    engine's Arrow table, `want` the oracle's DataFrame (or a scalar, or
    another Arrow table). Columns pair by name, the rest in order (q18 names
    its sum differently); rows compare in order — every multi-row query
    here has an ORDER BY."""
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    got = frame(got)
    if isinstance(want, pa.Table):
        want = frame(want)
    elif not isinstance(want, pd.DataFrame):
        want = pd.DataFrame({got.columns[0]: [want]})
    if len(got) != len(want):
        return 0.0, [f"rows: got {len(got)}, oracle {len(want)}"]
    if got.shape[1] != want.shape[1]:
        return 0.0, [f"columns: got {list(got.columns)}, "
                     f"oracle {list(want.columns)}"]
    rest = [c for c in want.columns if c not in got.columns]
    worst, problems = 0.0, []
    for name in got.columns:
        g = got[name].to_numpy()
        w = want[name if name in want.columns else rest.pop(0)].to_numpy()
        if g.dtype.kind == "f" or w.dtype.kind == "f":
            g, w = g.astype(np.float64), w.astype(np.float64)
            if not np.all(np.isfinite(g)):
                problems.append(f"{name}: non-finite values")
                continue
            rel = np.where(g == w, 0.0,
                           np.abs(g - w) / np.maximum(np.abs(w), 1e-300))
            err = float(rel.max(initial=0.0))
            worst = max(worst, err)
            if err > REL_TOL:
                problems.append(f"{name}: relative error {err:.3e} "
                                f"> {REL_TOL:g}")
        else:
            differ = np.flatnonzero(g.astype(object) != w.astype(object))
            if len(differ):
                i = int(differ[0])
                problems.append(f"{name}: row {i}: got {g[i]!r}, "
                                f"oracle {w[i]!r}")
    return worst, problems


def run_twice(engine, sql: str) -> list:
    """[(seconds, QueryResult, counter delta)] for a cold run and a second
    one (hint adoption may recompile); neither may come from the result
    cache."""
    from igloo_tpu.utils import tracing
    runs = []
    for _ in range(2):
        engine.result_cache.clear()
        with tracing.counter_delta() as delta:
            t0 = time.perf_counter()
            res = engine.query(sql)
            dt = time.perf_counter() - t0
        runs.append((dt, res, delta.values()))
    return runs


def compile_counts(c0: dict, c1: dict) -> dict:
    """[cold, second] pairs of the counters that say what was compiled."""
    return {name: [c0.get(key, 0), c1.get(key, 0)] for name, key in (
        ("jit_miss", "jit.miss"), ("compile_cache_hit", "compile_cache.hit"),
        ("compile_cache_miss", "compile_cache.miss"),
        ("fused_execute", "fused.execute"))}


def summed(c0: dict, c1: dict) -> dict:
    return {k: c0.get(k, 0) + c1.get(k, 0) for k in set(c0) | set(c1)}


def path_counters(delta: dict) -> dict:
    """Which executors and repair paths a query took (a fused program whose
    deferred flag fired re-runs staged, quietly — this is where it shows)."""
    return {k: v for k, v in sorted(delta.items()) if v and k.startswith(
        ("fused.", "join.", "engine.", "codec.decimal_canary", "codec.f32pair",
         "codec.f64_wide", "topk.",
         "grace.", "serving.demoted", "coordinator."))}


def device_checks(q: str, phase: str, counters: dict) -> None:
    """The run must have been on the device, on the path it claims."""
    check(not counters.get("fused.nofuse_sentinel", 0)
          and not counters.get("fused.nofuse_armed", 0),
          f"{phase} {q}: a `nofuse` sentinel was found armed in nhints.json "
          "(an earlier process died inside this program's compile)")


# --- phase 1: the in-process session -----------------------------------------

def run_session(stage_dir: str, frames: dict, queries: list) -> None:
    from igloo_tpu.bench.runner import make_engine
    from igloo_tpu.bench.tpch import QUERIES
    from igloo_tpu.bench.tpch_pandas import PANDAS_QUERIES
    from igloo_tpu.utils import stats
    engine = make_engine(stage_dir)
    for q in queries:
        (cold_s, res, c0), (second_s, res2, c1) = run_twice(engine, QUERIES[q])
        want = PANDAS_QUERIES[q](frames)
        err, problems = compare(res.table, want)
        err2, problems2 = compare(res2.table, want)
        both = summed(c0, c1)
        emit(phase="session", query=q, tier=[res.stats.tier, res2.stats.tier],
             rows=res.table.num_rows, cold_s=cold_s, second_s=second_s,
             h2d_bytes=[res.stats.h2d_bytes, res2.stats.h2d_bytes],
             d2h_bytes=[res.stats.d2h_bytes, res2.stats.d2h_bytes],
             path=path_counters(both),
             peak_hbm_bytes=stats.device_peak_hbm_bytes(),
             max_rel_err=max(err, err2),
             matches_oracle=not (problems or problems2),
             **compile_counts(c0, c1))
        for p in problems + problems2:
            check(False, f"session {q}: {p}")
        for st in (res.stats, res2.stats):
            check(st.tier == "device",
                  f"session {q}: tier {st.tier!r}, expected 'device'")
        device_checks(q, "session", both)


def run_sorted_join(seed: int) -> None:
    """One `join_sorted` program through the session, against pandas."""
    import jax
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    from igloo_tpu.engine import QueryEngine
    from igloo_tpu.exec import fused
    rng = np.random.default_rng(seed)
    n = 1 << 17
    # half of the probe keys match nothing, 256 build keys come twice, both
    # sides hold NULL keys
    a = pa.table({"fk": pa.array(rng.integers(0, 4096, n) * 0.5,
                                 mask=np.arange(n) % 97 == 0),
                  "x": pa.array(np.arange(n), type=pa.int64())})
    k = np.concatenate([np.arange(2048), np.arange(256)]) * 0.5
    b = pa.table({"k": pa.array(k, mask=np.arange(len(k)) % 13 == 0),
                  "v": pa.array(np.arange(len(k)) % 64, type=pa.int64())})
    engine = QueryEngine()
    engine.register_table("a", a)
    engine.register_table("b", b)
    sql = ("SELECT v, COUNT(*) AS c, SUM(x) AS sx FROM a JOIN b "
           "ON a.fk = b.k GROUP BY v ORDER BY v")
    (cold_s, res, c0), (second_s, res2, c1) = run_twice(engine, sql)
    j = a.to_pandas().merge(b.to_pandas().dropna(subset=["k"]),
                            left_on="fk", right_on="k")
    want = j.groupby("v").agg(c=("x", "size"), sx=("x", "sum")) \
        .reset_index().sort_values("v")
    want = pd.DataFrame({"v": want.v, "c": want.c, "sx": want.sx})
    _err, problems = compare(res.table, want)
    _err2, problems2 = compare(res2.table, want)
    comp = fused.FusedCompiler(engine._executor())
    comp.compile(engine.plan(sql))
    both = summed(c0, c1)
    searched = bool(both.get("join.match_search", 0))
    emit(phase="sorted_join", rows=res.table.num_rows, joined=len(j),
         tier=[res.stats.tier, res2.stats.tier], cold_s=cold_s,
         second_s=second_s, path=path_counters(both),
         match_route="search" if searched else "scan",
         matches_oracle=not (problems or problems2), **compile_counts(c0, c1))
    for p in problems + problems2:
        check(False, f"sorted_join: {p}")
    check(any(fp[0] == "join_sorted" for fp in comp.fps),
          "sorted_join: the plan holds no join_sorted node")
    check(bool(both.get("fused.execute", 0))
          and not both.get("join.speculation_overflow", 0),
          "sorted_join: did not run as the fused program alone")
    check(searched == (jax.default_backend() != "tpu"),
          "sorted_join: the searchsorted route was planned on a TPU, or "
          "the scan off it")
    device_checks("sorted_join", "session", both)


# --- phase 2: the served path ------------------------------------------------

def run_served(stage_dir: str, frames: dict, queries: list) -> None:
    from igloo_tpu.bench.tpch import QUERIES
    from igloo_tpu.bench.tpch_pandas import PANDAS_QUERIES
    from igloo_tpu.cluster.client import DistributedClient
    from igloo_tpu.cluster.coordinator import CoordinatorServer
    from igloo_tpu.cluster.worker import Worker
    from igloo_tpu.connectors.parquet import ParquetTable
    from igloo_tpu.utils import stats, tracing
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=600.0)
    caddr = f"127.0.0.1:{coord.port}"
    worker = Worker(caddr, port=0, heartbeat_interval_s=1.0)
    try:
        worker.start()
        deadline = time.time() + 30
        while not coord.membership.live() and time.time() < deadline:
            time.sleep(0.05)
        if not coord.membership.live():
            raise RuntimeError("worker never registered with the coordinator")
        for name in frames:
            coord.register_table(name, ParquetTable(
                os.path.join(stage_dir, f"{name}.parquet")))
        wid = worker.server.worker_id
        with DistributedClient(caddr) as client:
            for q in queries:
                runs = []
                for _ in range(2):
                    # a repeat must EXECUTE, not come from the front door's
                    # result cache
                    coord.engine.result_cache.clear()
                    # process-wide counters, not counter_delta(): that one
                    # sees this thread only, and fragments run on Flight's
                    before = dict(tracing.counters())
                    t0 = time.perf_counter()
                    got = client.execute(QUERIES[q])
                    dt = time.perf_counter() - t0
                    after = tracing.counters()
                    delta = {k: after[k] - before.get(k, 0) for k in after
                             if after[k] != before.get(k, 0)}
                    runs.append((dt, got, client.last_metrics(), delta))
                want = PANDAS_QUERIES[q](frames)
                worst, problems, frag_lines = 0.0, [], []
                for dt, got, m, delta in runs:
                    err, probs = compare(got, want)
                    worst = max(worst, err)
                    problems += probs
                    frags = m.get("fragments") or []
                    check(bool(frags), f"served {q}: no fragment stats in "
                          "last_metrics() — the query did not run distributed")
                    check(all(f.get("worker") == wid for f in frags),
                          f"served {q}: fragments not executed on the worker "
                          f"{wid}: {[f.get('worker') for f in frags]}")
                    check(delta.get("coordinator.distributed_queries", 0) == 1
                          and not delta.get("serving.demoted", 0),
                          f"served {q}: not run as one distributed query: "
                          f"{path_counters(delta)}")
                    device_checks(q, "served", delta)
                    frag_lines.append([
                        {k: f[k] for k in
                         ("kind", "rows", "input_rows", "elapsed_s",
                          "h2d_bytes", "d2h_bytes", "jit_misses") if k in f}
                        for f in frags])
                c0, c1 = runs[0][3], runs[1][3]
                both = summed(c0, c1)
                emit(phase="served", query=q, rows=runs[0][1].num_rows,
                     cold_s=runs[0][0], second_s=runs[1][0],
                     fragments=frag_lines, worker=wid,
                     path=path_counters(both),
                     peak_hbm_bytes=stats.device_peak_hbm_bytes(),
                     max_rel_err=worst, matches_oracle=not problems,
                     **compile_counts(c0, c1))
                for p in problems:
                    check(False, f"served {q}: {p}")
    finally:
        worker.shutdown()
        coord.shutdown()


# --- --chips 4: the mesh tier ------------------------------------------------

def run_mesh(stage_dir: str, frames: dict, nrows: dict, chips: int,
             dump_dir: str) -> None:
    import jax

    from igloo_tpu.bench.tpch import QUERIES
    from igloo_tpu.bench.tpch_pandas import PANDAS_QUERIES
    from igloo_tpu.connectors.parquet import ParquetTable
    from igloo_tpu.engine import QueryEngine
    from igloo_tpu.parallel.mesh import make_mesh
    if not check(jax.device_count() == chips,
                 f"--chips {chips}: JAX sees {jax.device_count()} devices"):
        return
    results = {}
    for label, mesh in (("sharded", make_mesh(chips)), ("one_device", None)):
        engine = QueryEngine(mesh=mesh)
        for name in TABLES:
            engine.register_table(name, ParquetTable(
                os.path.join(stage_dir, f"{name}.parquet")))
        for q in ("q1", "q3"):
            (cold_s, res, c0), (second_s, res2, c1) = run_twice(
                engine, QUERIES[q])
            err, problems = compare(res2.table, PANDAS_QUERIES[q](frames))
            results[(label, q)] = res2.table
            want_tier = "sharded" if mesh is not None else "device"
            check(res.stats.tier == want_tier and res2.stats.tier == want_tier,
                  f"mesh {label} {q}: tier {res.stats.tier!r}/"
                  f"{res2.stats.tier!r}, expected {want_tier!r}")
            device_checks(q, f"mesh {label}", summed(c0, c1))
            for p in problems:
                check(False, f"mesh {label} {q}: {p}")
            emit(phase="mesh", layout=label, query=q,
                 tier=[res.stats.tier, res2.stats.tier], cold_s=cold_s,
                 second_s=second_s,
                 shard_uploads=c0.get("mesh.shard_uploads", 0),
                 sharded_lanes=c0.get("mesh.sharded_lanes", 0),
                 max_rel_err=err, matches_oracle=not problems,
                 **compile_counts(c0, c1))
        if mesh is not None:
            placement_checks(nrows["lineitem"], chips)
            found = compiled_collectives(dump_dir)
            emit(phase="mesh", collectives_in_compiled_text=found)
            check("all-to-all" in found, "mesh: no all-to-all in the "
                  f"compiled text of the sharded programs ({found})")
    for q in ("q1", "q3"):
        err, problems = compare(results[("sharded", q)],
                                results[("one_device", q)])
        emit(phase="mesh", query=q, sharded_vs_one_device_max_rel_err=err,
             equal=not problems)
        for p in problems:
            check(False, f"mesh {q}: sharded != one device: {p}")


def placement_checks(lineitem_rows: int, chips: int) -> None:
    """Every device holds its share of `lineitem`, not only the first: sum
    the `addressable_shards` of every live array that is lineitem-sized
    (the scan cache keeps the uploaded lanes alive), and read each device's
    memory_stats()."""
    import jax
    per_dev = {d.id: 0 for d in jax.local_devices()}
    for arr in jax.live_arrays():
        if arr.ndim == 1 and arr.shape[0] >= lineitem_rows:
            for sh in arr.addressable_shards:
                per_dev[sh.device.id] += int(sh.data.nbytes)
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    emit(phase="mesh", lineitem_rows=lineitem_rows,
         lineitem_sized_bytes_per_device=per_dev,
         bytes_in_use_per_device=[m.get("bytes_in_use") for m in stats],
         peak_bytes_per_device=[m.get("peak_bytes_in_use") for m in stats])
    share = sum(per_dev.values()) / chips
    check(share > 0 and all(0.9 * share <= b <= 1.1 * share
                            for b in per_dev.values()),
          f"mesh: lineitem is not spread evenly over {chips} devices: "
          f"{per_dev}")


def compiled_collectives(dump_dir: str) -> dict:
    """Collective ops in the optimized HLO of every program compiled so far
    (XLA dumps it under --xla_dump_to, set in main() before jax starts)."""
    import re
    found: dict = {}
    for name in sorted(os.listdir(dump_dir)):
        if not name.endswith("after_optimizations.txt"):
            continue
        with open(os.path.join(dump_dir, name)) as f:
            for op in re.findall(r" (all-to-all|all-gather|all-reduce|"
                                 r"reduce-scatter|collective-permute)"
                                 r"(?:-start)?\(", f.read()):
                found[op] = found.get(op, 0) + 1
    return found


# --- main --------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=19980401)
    ap.add_argument("--queries", default="q1,q6,q3",
                    help="in-process session queries, in this order")
    ap.add_argument("--served", default="q1,q3",
                    help="queries through coordinator + worker ('' skips)")
    ap.add_argument("--tables", default="",
                    help="stage only these, by benchmark/datagen.py (--sf 10 "
                    "in minutes, not in Python lists): for a served probe at "
                    "a large --sf, with --queries '' (the session registers "
                    "all eight)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh tier against one device")
    args = ap.parse_args(argv)

    t_start = time.time()
    tmp = tempfile.mkdtemp(prefix="igloo_chip_smoke_")
    dump_dir = os.path.join(tmp, "hlo")
    if args.chips > 1:
        # before jax starts: the mesh phase reads the collectives out of
        # the optimized HLO that XLA dumps
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_dump_to={dump_dir} --xla_dump_hlo_as_text").strip()
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    emit(device=device)
    try:
        emit(native_available=rebuild_native(), compile_cache=cache_state(),
             igloo_env={k: v for k, v in os.environ.items()
                        if k.startswith(("IGLOO_", "JAX_", "XLA_"))})
        t0 = time.perf_counter()
        frames, nrows = stage(args.sf, args.seed, tmp,
                              [t for t in args.tables.split(",") if t])
        emit(phase="stage", sf=args.sf, seed=args.seed, rows=nrows,
             seconds=time.perf_counter() - t0)
        if args.chips > 1:
            run_mesh(tmp, frames, nrows, args.chips, dump_dir)
        else:
            session = [q for q in args.queries.split(",") if q]
            if session:
                run_session(tmp, frames, session)
                run_sorted_join(args.seed)
            served = [q for q in args.served.split(",") if q]
            if served:
                run_served(tmp, frames, served)
        check(device["platform"] == "tpu",
              f"JAX's first device is {device['platform']!r}, not a TPU")
        check(device["count"] == args.chips,
              f"{device['count']} devices visible, --chips {args.chips}")
    except BaseException:
        # the outermost boundary: report, then fail through the exit code
        traceback.print_exc()
        _failures.append("exception")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    emit(seconds=time.time() - t_start, failures=_failures,
         compile_cache_after=cache_state())
    print(json.dumps({"ok": not _failures, "device": device}), flush=True)
    return 1 if _failures else 0


if __name__ == "__main__":
    sys.exit(main())
