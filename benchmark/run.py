#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process (it holds the chip).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of BENCHMARK.json's `workloads`: a configuration
(configs/<config>.json: the deployment, its scale, its guarantees) under a
traffic mix (traffic/<traffic>.json: which queries, in which order, from how
many clients). Everything that belongs to one configuration, traffic mix,
query, deployment or metric is a file found by its name; nothing here names
one (README.md).

Phases:
  set-up   import, stage the tables the traffic reads from --seed (Parquet
           under a temporary directory), build the deployment, run the
           traffic's queries until a whole pass compiles nothing and traces
           no more than the pass before it. Timed as
           `setup_s`, from the start of this file to the window's start.
  window   --seconds of the traffic: closed loop, the queries round-robin,
           the result cache cleared before each so that each executes. Each
           query is timed on the host clock from the call until the client
           holds the whole Arrow table. The round of queries in flight when
           the time is up is finished and counted; the window ends with it. With
           --trace 1 the window is the configuration's `trace_seconds` at
           most, and runs under jax.profiler.
  check    read the device's memory peak, free the deployment, compute the
           plain reference's answers (oracle/) on the same data, compare
           every table the window returned, print each number compared
           beside its limit and the result as the last line of stdout.

The run refuses to measure (exit 2, no result line) unless JAX's first
device is a TPU and the device count is the cell's `chips`. `--rehearse-sf`
drives every phase on whatever JAX has, at a small scale factor, for
tests/: such a run ends `"correct": false` and exit 1, whatever it compared.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: set-up repeats the traffic's queries until a pass is steady; hint adoption
#: recompiles on the second execution (PERF.md), and from an empty cache the
#: served cells needed three passes plus the one that shows steadiness
MAX_WARM_PASSES = 6
#: counters of the program that must not move in the window: each says that
#: a query left the device path the cell claims to measure
FALLBACK_COUNTERS = ("engine.host_route", "serving.demoted",
                     "pallas.compile_fallback")


class Refused(Exception):
    """The run cannot be a measurement; no result line is printed."""


def log(**rec) -> None:
    print(json.dumps(rec, sort_keys=True, default=str), flush=True)


def load_json(path: str) -> dict:
    """A JSON file by its path from the root of the checkout."""
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, by name (a later PR adds files)."""
    path = os.path.normpath(os.path.join(HERE, kind, f"{name}.py"))
    if not os.path.isfile(path):
        raise Refused(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind or 'top'}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str) -> dict:
    """Cell name -> its entry, configuration, traffic and metrics."""
    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(entry["file"])
    traffic = load_json(os.path.join(os.path.basename(HERE), "traffic",
                                     f"{cell['traffic']}.json"))
    for q in traffic["queries"]:
        with open(os.path.join(HERE, q["sql"])) as f:
            q["text"] = f.read()

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def device_info() -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def memory_peak_bytes() -> int:
    """Peak on the fullest chip, as the backend reports it (0: it does not)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def counters() -> dict:
    """The program's process-wide counters (fragments run on Flight's
    threads, so a per-thread delta would miss them)."""
    from igloo_tpu.utils import tracing
    return dict(tracing.counters())


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


# --- set-up ------------------------------------------------------------------

def stage(config: dict, traffic: dict, sf: float, seed: int, root: str):
    """Generate the tables the traffic's queries read and write them as
    Parquet under `root`. -> ({table: Arrow table of the columns the
    reference needs}, {table: rows})."""
    import pyarrow.parquet as pq
    datagen = load_module("", config["datagen"])
    reads: dict = {}
    for q in traffic["queries"]:
        for table, cols in q["reads"].items():
            reads.setdefault(table, set()).update(cols)
    tables = datagen.gen_tables(sf=sf, seed=seed, tables=sorted(reads))
    kept, rows = {}, {}
    for name, tbl in tables.items():
        pq.write_table(tbl, os.path.join(root, f"{name}.parquet"))
        kept[name] = tbl.select(sorted(reads[name]))
        rows[name] = tbl.num_rows
    return kept, rows


def one_query(dep, q: dict) -> dict:
    """Clear the result cache, run `q`, time it, ask where it ran."""
    import jax
    rec = {"name": q["name"], "table": None, "error": None, "info": {}}
    with jax.profiler.TraceAnnotation(f"bench:{q['name']}:clear_cache"):
        dep.clear_result_cache()
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"bench:{q['name']}:execute"):
            rec["table"] = dep.execute(q["text"])
        rec["latency_s"] = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation(f"bench:{q['name']}:last_info"):
            rec["info"] = dep.last_info()
    except Exception as ex:  # the loop must go on: the query counts as failed
        rec["latency_s"] = time.perf_counter() - t0
        rec["error"] = f"{type(ex).__name__}: {ex}"
        traceback.print_exc()
    return rec


def warm_up(dep, traffic: dict) -> list:
    """Run the traffic's queries until a whole pass is steady: it compiles
    nothing (`compile_cache.miss`), and traces no more than the pass before
    it (`jit.miss`: a program met for the second or third time may still be
    re-traced with adopted hints and loaded from the persistent cache, which
    costs tenths of a second and would otherwise land in the window's first
    queries). -> one record per pass."""
    passes = []
    for n in range(MAX_WARM_PASSES):
        before = counters()
        t0 = time.perf_counter()
        for q in traffic["queries"]:
            rec = one_query(dep, q)
            if rec["error"]:
                raise RuntimeError(f"warm-up {q['name']}: {rec['error']}")
        d = delta(counters(), before)
        passes.append({"pass": n, "seconds": time.perf_counter() - t0,
                       "compile_cache_miss": d.get("compile_cache.miss", 0),
                       "compile_cache_hit": d.get("compile_cache.hit", 0),
                       "jit_miss": d.get("jit.miss", 0)})
        log(phase="warm_up", **passes[-1])
        if (n and not passes[-1]["compile_cache_miss"]
                and passes[-1]["jit_miss"] >= passes[-2]["jit_miss"]):
            return passes
    raise RuntimeError(f"not steady after {MAX_WARM_PASSES} passes of the "
                       f"traffic: {passes}")


# --- the window --------------------------------------------------------------

def window(dep, traffic: dict, seconds: float) -> tuple:
    """Closed loop, one client, whole rounds of the traffic's queries: the
    round in flight when the time is up is finished and counted, so that
    every window holds the same mix whatever a query costs (q1 takes 13
    times q6: a window cut between them would read 2-3 % off). -> ([query
    records], seconds to the end of the last query)."""
    done = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        done += [one_query(dep, q) for q in traffic["queries"]]
    return done, time.perf_counter() - t0


def traced_window(dep, traffic: dict, seconds: float, keep: str) -> tuple:
    """The window under jax.profiler. -> (records, seconds, reduction)."""
    import glob

    import jax

    import trace_reduce
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the Python tracer slows the host
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                done, length = window(dep, traffic, seconds)
        finally:
            jax.profiler.stop_trace()
        t0 = time.perf_counter()
        files = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        size = os.path.getsize(files[0])
        planes = trace_reduce.load(files[0])
        reduction = trace_reduce.reduce(planes)
        log(phase="trace", xplane_bytes=size,
            reduce_seconds=time.perf_counter() - t0,
            planes=[[p["name"], [[ln["name"], len(ln["events"])]
                                 for ln in p["lines"]][:12]]
                    for p in planes])
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(files[0], os.path.join(keep, "trace.xplane.pb"))
        return done, length, reduction
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


# --- the check ---------------------------------------------------------------

def reference_answers(traffic: dict, kept: dict) -> dict:
    """{query: the plain reference's answer} on the staged data."""
    from compare import frame
    frames = {name: frame(tbl) for name, tbl in kept.items()}
    return answers_from(frames, traffic)


def answers_from(frames: dict, traffic: dict) -> dict:
    out = {}
    for q in traffic["queries"]:
        module, func = q["oracle"].split(":")
        out[q["name"]] = getattr(load_module("oracle", module), func)(frames)
    return out


def check(done: list, want: dict, moved: dict, config: dict,
          device: dict, chips: int, rehearsal: bool) -> tuple:
    """-> (checks: name -> {value, limit}, failed queries). Every number
    compared, beside its limit; `correct` is all of them within."""
    from compare import compare
    worst, wrong, notes = 0.0, 0, []
    failed = 0
    for rec in done:
        if rec["error"] or not rec["info"].get("executed_on_device"):
            failed += 1
            notes.append(f"{rec['name']}: " + (
                rec["error"] or rec["info"].get("where", "no info")))
            continue
        err, bad, why = compare(rec["table"], want[rec["name"]])
        worst, wrong = max(worst, err), wrong + bad
        notes += [f"{rec['name']}: {w}" for w in why]
    fallbacks = sum(moved.get(k, 0) for k in FALLBACK_COUNTERS)
    checks = {
        "max_rel_err": {"value": worst,
                        "limit": config["guarantees"]["float_rel_tol"]},
        "wrong_cells": {"value": wrong, "limit": 0},
        "failed_queries": {"value": failed, "limit": 0},
        "fallback_counters": {"value": fallbacks, "limit": 0},
        "empty_window": {"value": int(not done), "limit": 0},
        "not_a_tpu_run": {"value": int(
            rehearsal or device["platform"] != "tpu"
            or device["count"] != chips), "limit": 0},
    }
    for note in notes[:20]:
        log(check="differs", what=note)
    return checks, failed


def run_control(name: str, spec: dict, kept: dict, device: dict,
                rehearsal: bool) -> int:
    """controls/<name>.py's answers in the program's place, one per query of
    the traffic, through the same check: no deployment, no window."""
    traffic = spec["traffic"]
    want = reference_answers(traffic, kept)
    got = load_module("controls", name).answers(kept, traffic, answers_from)
    done = [{"name": q["name"], "table": got[q["name"]], "error": None,
             "latency_s": 0.0, "info": {"executed_on_device": True}}
            for q in traffic["queries"]]
    run = {"queries": done, "window_s": 0.0, "counters": {}, "setup": {},
           "trace": None, "memory_peak_bytes": 0}
    checks, failed = check(done, want, {}, spec["config"], device,
                           spec["cell"]["chips"], rehearsal)
    return finish(run, spec, checks, failed, device, None, rehearsal)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-sf", type=float, default=None,
                    help="for tests/: run every phase at this scale factor "
                         "on whatever JAX has; never a passing result")
    ap.add_argument("--control", default="",
                    help="put controls/<name>.py's answers in the program's "
                         "place (no deployment, no window): `correct` has "
                         "to come out false")
    ap.add_argument("--keep-trace", default="",
                    help="copy the .xplane.pb of a traced run here")
    args = ap.parse_args(argv)

    sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]
    spec = resolve(args.workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    rehearsal = args.rehearse_sf is not None
    device = device_info()
    if not rehearsal and (device["platform"] != "tpu"
                          or device["count"] != cell["chips"]):
        raise Refused(f"{args.workload} needs {cell['chips']} TPU chip(s); "
                      f"JAX has {device}")
    import igloo_tpu  # noqa: F401  (the system under test)
    from igloo_tpu import compile_cache
    t_imported = time.perf_counter()
    log(phase="start", workload=args.workload, seed=args.seed,
        device=device, compile_cache_dir=compile_cache.active_dir(),
        import_seconds=t_imported - T_START)

    sf = args.rehearse_sf if rehearsal else config["scale_factor"]
    tmp = tempfile.mkdtemp(prefix="bench_stage_")
    dep = None
    try:
        kept, rows = stage(config, traffic, sf, args.seed, tmp)
        t_staged = time.perf_counter()
        log(phase="stage", sf=sf, rows=rows, seconds=t_staged - t_imported)
        if args.control:
            return run_control(args.control, spec, kept, device, rehearsal)
        builder = load_module("deployments", config["deployment"])
        dep = builder.build(tmp, sorted(kept))
        t_built = time.perf_counter()
        passes = warm_up(dep, traffic)
        t_window = time.perf_counter()
        setup = {"setup_s": t_window - T_START,
                 "import_s": t_imported - T_START,
                 "stage_s": t_staged - t_imported,
                 "build_s": t_built - t_staged,
                 "warm_s": t_window - t_built, "passes": passes}
        log(phase="set_up", **setup)

        before = counters()
        if args.trace:
            seconds = min(args.seconds, config["trace_seconds"])
            done, length, reduction = traced_window(
                dep, traffic, seconds, args.keep_trace)
        else:
            done, length = window(dep, traffic, args.seconds)
            reduction = None
        moved = delta(counters(), before)
        peak = memory_peak_bytes()
        for q in traffic["queries"]:
            mine = [r for r in done if r["name"] == q["name"]]
            log(phase="window", query=q["name"], n=len(mine),
                latency_s=[round(r["latency_s"], 4) for r in mine],
                last_info=mine[-1]["info"] if mine else None)
        log(phase="window", queries=len(done), seconds=length,
            counters={k: v for k, v in sorted(moved.items())})
        dep.close()
        dep = None

        want = reference_answers(traffic, kept)
        checks, failed = check(done, want, moved, config, device,
                               cell["chips"], rehearsal)
        run = {"queries": done, "window_s": length, "counters": moved,
               "setup": setup, "trace": reduction, "traffic": traffic,
               "rows": rows, "device": device, "memory_peak_bytes": peak}
        return finish(run, spec, checks, failed, device,
                      "layer_metrics" if args.trace else "end_to_end",
                      rehearsal)
    finally:
        if dep is not None:
            dep.close()
        shutil.rmtree(tmp, ignore_errors=True)


def finish(run: dict, spec: dict, checks: dict, failed: int, device: dict,
           kind, rehearsal: bool) -> int:
    """Read the metrics of `kind` (end_to_end | layer_metrics: the directory
    of their readers), print the checks and the result line."""
    metrics = {"end_to_end": spec["end_to_end"],
               "layer_metrics": spec["per_layer"], None: []}[kind]
    values = {}
    for m in metrics:
        value = load_module(kind, m["name"]).read(run)
        if value is not None:       # nothing to read: the metric is left out
            values[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": len(run["queries"]), "failed": failed,
              "metrics": values, "device": dev}
    trace = run.get("trace")
    if trace:
        dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = checks
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 1 if rehearsal else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as ex:
        print(f"refused: {ex}", file=sys.stderr)
        sys.exit(2)
