"""Single-process TPC-H sweep worker: ALL queries in one engine/process.

A subprocess per query makes every query decode and upload its input tables
again, and books that under "cold". This worker amortizes the upload: ONE
process, one engine, the column-granular HBM scan cache (exec/executor.py
_exec_scan) uploads each column at most once, so a query's cold cost is
trace + lower + compile (or a compile-cache read).

Protocol (consumed by bench.py, which adds the watchdog):
  stdout: exactly one JSON line per finished query
          {"q": .., "cold_s": .., "warm_trials": [..], "cached_s": ..}
  stderr: "SWEEP-START <q>" before each query (stall attribution: when the
          orchestrator kills a hung worker it knows which query to poison),
          plus human-readable progress.

A poison list (queries that hung a previous worker) is passed via
--skip; a deadline (unix epoch seconds) via --deadline makes the worker skip
remaining queries cleanly rather than being killed mid-fetch.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


from igloo_tpu.bench.runner import make_engine  # shared staging helper


_CONVERGENCE_COUNTERS = ("jit.miss", "fused.compact_repair",
                         "join.speculation_overflow",
                         "join.direct_dup_fallback",
                         "pallas.probe_overflow", "pallas.agg_overflow",
                         "pallas.match_overflow")

# packed-key fast-path adoption counters (exec/kernels.py planners via the
# executor/fused compilers): any delta across a query's runs means the
# single-sort packed path was active for it, recorded per query so BENCH
# rounds can attribute wins to that path
_PACK_COUNTERS = ("pack.agg", "pack.sort", "pack.semi")

# per-query counter-delta prefixes recorded into the sweep JSON (cold run):
# compile cache, packed-key planners, out-of-core tiers, transfer bytes,
# cross-worker exchange — the trajectory data that lets a BENCH_*.json
# regression be EXPLAINED (route flip? cache miss? partition-count change?),
# not just detected
_DELTA_PREFIXES = ("jit.", "pack.", "grace.", "chunked.", "xfer.",
                   "cache.", "result_cache.", "engine.", "fused.", "join.",
                   "exchange.", "compile_cache.", "adaptive.", "pallas.",
                   "mesh.", "codec.", "autotune.", "topk.")

# Pallas kernel names whose dispatch counters feed the per-query `pallas`
# block (docs/kernels.md); fallback/overflow counters are summed beside
# them so an A/B against IGLOO_TPU_PALLAS=0 is attributable per query
_PALLAS_KERNELS = ("probe", "segagg", "gather", "scatter", "match", "topk")
_PALLAS_FALLBACKS = ("pallas.probe_overflow", "pallas.agg_overflow",
                     "pallas.match_overflow")


def _pallas_enabled() -> bool:
    from igloo_tpu.exec import dispatch
    return dispatch.enabled()


def _peak_hbm_bytes() -> int:
    """Peak device-memory watermark across local devices; 0 when the backend
    does not report memory stats (CPU)."""
    try:
        import jax
        peaks = []
        for d in jax.local_devices():
            ms = getattr(d, "memory_stats", None)
            ms = ms() if callable(ms) else None
            if ms:
                peaks.append(ms.get("peak_bytes_in_use",
                                    ms.get("bytes_in_use", 0)))
        return int(max(peaks)) if peaks else 0
    except Exception:
        return 0


def run_query(engine, sql: str, trials: int, hbm_budget: int = 0) -> dict:
    """cold -> hint-adoption re-runs -> warm trials -> result-cached run.
    With `hbm_budget` every execution runs under `engine.demoted(budget)` —
    the memory-scaled bench mode (`bench.py --hbm-budget`) that forces the
    out-of-core tiers and records the per-query `oversized` block
    (docs/out_of_core.md)."""
    from igloo_tpu.utils import tracing
    budget_cm = engine.demoted(budget_bytes=hbm_budget) if hbm_budget \
        else contextlib.nullcontext()
    with budget_cm, tracing.counter_delta() as query_delta:
        with tracing.counter_delta() as cold_delta:
            t0 = time.perf_counter()
            engine.execute(sql)
            cold = time.perf_counter() - t0
        # adopt cardinality hints until the EXECUTION converges: no fresh
        # compiles and no repair/fallback re-runs. Judging by run TIME
        # plateaus (the old loop) breaks too early on queries whose adoption
        # cascades a few rounds at similar cost (q7: three ~10 s adoption
        # rounds before the 0.5 s steady state — the plateau heuristic
        # bailed after one and the repairs then fired inside the timed warm
        # trials as a 20x flap)
        for _ in range(8):
            with tracing.counter_delta() as adopt_delta:
                engine.result_cache.clear()
                engine.execute(sql)
            if all(adopt_delta.get(k) == 0 for k in _CONVERGENCE_COUNTERS):
                break
        warm = []
        with tracing.counter_delta() as warm_delta:
            for _ in range(trials):
                engine.result_cache.clear()
                t0 = time.perf_counter()
                engine.execute(sql)
                warm.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        engine.execute(sql)
        cached = time.perf_counter() - t0
    rec = {"cold_s": round(cold, 4),
           "warm_trials": [round(w, 4) for w in warm],
           "cached_s": round(cached, 4),
           # persistent-XLA-cache traffic on the COLD run: hits > 0 with a
           # small cold_s means the "cold" compile was served from disk —
           # the number that makes the cold-run trajectory across BENCH
           # rounds interpretable (cleared vs pre-warmed cache dir)
           "compile_cache_hits": cold_delta.get("compile_cache.hit"),
           "compile_cache_misses": cold_delta.get("compile_cache.miss"),
           "packed": any(query_delta.get(k) > 0 for k in _PACK_COUNTERS),
           # cold-run counter deltas (trajectory explanations) + the per-warm
           # transfer numbers that prove the scan cache amortized uploads
           "counters": {k: v for k, v in cold_delta.values().items()
                        if k.startswith(_DELTA_PREFIXES)},
           "warm_h2d_bytes": warm_delta.get("xfer.h2d_bytes") //
           max(trials, 1),
           "peak_hbm_bytes": _peak_hbm_bytes()}
    # fragment-tier shuffle adoption (0 on a single-node sweep; populated
    # when the engine under test routes through the distributed exchange):
    # bucket partition ops and bytes moved worker<->worker per query, so the
    # perf trajectory captures the shuffle tier once bench gains a
    # distributed mode
    rec["shuffle_buckets"] = query_delta.get("exchange.partitions")
    rec["exchange_bytes"] = query_delta.get("exchange.fetch_bytes")
    # adaptive-execution decisions for this query (docs/adaptive.md): did
    # the optimizer reorder a join spine, was the order driven by observed
    # stats or estimates, and did the fragment tier broadcast/salt — the
    # record that makes an A/B against IGLOO_ADAPTIVE=0 attributable
    from igloo_tpu.exec.hints import adaptive_enabled
    reorder = query_delta.get("adaptive.reorder") > 0
    rec["adaptive"] = {
        "enabled": adaptive_enabled(),
        "reorder": reorder,
        "adaptive_source": (
            "observed" if query_delta.get("adaptive.reorder_observed")
            else "estimated") if reorder else None,
        "broadcast": query_delta.get("adaptive.broadcast"),
        "salted": query_delta.get("adaptive.salted"),
        "observed": query_delta.get("adaptive.observed"),
    }
    # Pallas kernel dispatch for this query (docs/kernels.md): which
    # kernels ran, and how often the runtime overflow or eligibility
    # ladder sent an op back to the sort path — the per-query record for
    # the IGLOO_TPU_PALLAS=0 A/B (dispatch decisions land in
    # BENCH_DETAIL.json via bench.py's passthrough)
    fallbacks = sum(query_delta.get(k) for k in _PALLAS_FALLBACKS)
    fallbacks += sum(v for k, v in query_delta.values().items()
                     if k.startswith("pallas.fallback."))
    rec["pallas"] = {
        "enabled": _pallas_enabled(),
        "kernels_used": [k for k in _PALLAS_KERNELS
                         if query_delta.get(f"pallas.{k}") > 0],
        "fallbacks": fallbacks,
    }
    # per-shape autotuner record (docs/kernels.md#autotuner): which table
    # version the dispatch planners consulted and whether tuned winners —
    # not module defaults — shaped this query's kernels; the record that
    # makes a tuned-vs-default A/B (IGLOO_TPU_AUTOTUNE=0) attributable
    from igloo_tpu.exec import autotune
    rec["autotune"] = {
        "mode": autotune.mode(),
        "table_version": autotune.table_version(),
        "hits": query_delta.get("autotune.hit"),
        "misses": query_delta.get("autotune.miss"),
        "swept": query_delta.get("autotune.sweep"),
        "tuned": query_delta.get("autotune.hit") > 0,
    }
    # two-level topology block (docs/distributed.md): which level(s) of
    # parallelism this query's execution actually used. A sweep worker is one
    # process (one "host"); mesh_devices counts its chip-level shards, and
    # `sharded` says the sharded tier ran: the mesh resolved AND no other
    # tier (host / chunked / GRACE) took the query instead. NOT keyed on the
    # upload counters — a warm query serves row-sharded batches from the
    # scan cache with zero uploads in its delta.
    mesh = engine._resolve_mesh() if hasattr(engine, "_resolve_mesh") else None
    routed_elsewhere = any(
        query_delta.get(k) > 0 for k in
        ("engine.host_route", "engine.chunked_route", "engine.grace_route"))
    rec["topology"] = {
        "workers": 1,
        "mesh_devices": int(mesh.devices.size) if mesh is not None else 1,
        "sharded": mesh is not None and not routed_elsewhere,
    }
    if hbm_budget:
        # the per-query out-of-core record for the memory-scaled mode: what
        # budget it ran under, which tier took it, how many partitions, and
        # how many bytes actually spilled — the rows/s-under-budget curve
        # (bench.py adds rows_per_s_under_budget) rides into BENCH_DETAIL
        # and the bench_gate WATCH list so the SF10 cliff cannot return
        rec["oversized"] = {
            "budget_bytes": int(hbm_budget),
            "completed": True,
            "grace": query_delta.get("engine.grace_route") > 0,
            "chunked": query_delta.get("engine.chunked_route") > 0,
            "grace_partitions": query_delta.get("grace.partitions"),
            "spill_bytes": query_delta.get("exchange.spill_bytes"),
        }
    joins = query_delta.get("grace.join")
    rec["grace"] = query_delta.get("engine.grace_route") > 0
    if rec["grace"]:
        # per-execution partition count (the query ran several times above)
        rec["grace_partitions"] = query_delta.get("grace.partitions") // \
            max(joins, 1)
        # whether the double-buffered loop actually RAN (the counter), not
        # just whether the env flag allowed it — recursive-mode and
        # single-partition executions fall back to the serial loop
        rec["grace_pipeline"] = query_delta.get("grace.pipeline") > 0
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stage", required=True)
    ap.add_argument("--queries", required=True, help="csv of query ids")
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--skip", default="", help="csv of poisoned query ids")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="unix epoch seconds; skip queries past this")
    ap.add_argument("--hbm-budget", type=int, default=0,
                    help="bytes: run every query under "
                         "engine.demoted(budget) — the memory-scaled mode")
    args = ap.parse_args(argv)

    from igloo_tpu.bench.tpch import QUERIES
    engine = make_engine(args.stage)
    skip = set(q for q in args.skip.split(",") if q)
    queries = [q for q in args.queries.split(",") if q]

    per_q = []  # completed query durations, for the deadline margin
    for q in queries:
        if q in skip:
            print(json.dumps({"q": q, "error": "poisoned (hung a previous "
                              "worker)"}), flush=True)
            continue
        if args.deadline:
            # leave room for one more query of typical observed cost
            margin = max(per_q[-3:]) if per_q else 60.0
            if time.time() + margin > args.deadline:
                log(f"SWEEP-DEADLINE before {q} "
                    f"(margin {margin:.0f}s); stopping cleanly")
                break
        log(f"SWEEP-START {q}")
        t0 = time.perf_counter()
        try:
            rec = run_query(engine, QUERIES[q], args.trials,
                            hbm_budget=args.hbm_budget)
        except Exception as e:  # record, keep sweeping
            log(f"{q}: FAILED {type(e).__name__}: {e}")
            print(json.dumps({"q": q,
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
            continue
        took = time.perf_counter() - t0
        per_q.append(took)
        rec["q"] = q
        print(json.dumps(rec), flush=True)
        gc.collect()
    log("SWEEP-DONE")
    return 0


if __name__ == "__main__":
    sys.exit(main())
