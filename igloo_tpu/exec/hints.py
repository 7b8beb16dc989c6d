"""Persistent per-plan-fingerprint stores for adaptive execution.

Two stores share one digest-keyed JSON-file idiom:

- `HintStore` (PR1/round-4 era): flat int live-count hints for the fused /
  staged compilers' in-program compactions, keyed by *compiler-internal*
  fingerprints (exec/fused.py hfps, exec/executor.py slive keys).
- `AdaptiveStats` (the telemetry->planner feedback loop, docs/adaptive.md):
  per-*logical-subtree* observed execution statistics — output cardinality,
  join input rows (selectivity), exchange result bytes, and a top-bucket skew
  sketch — keyed by `plan_fp` structural fingerprints. Planners consume them:
  join reordering (plan/optimizer.py), broadcast-vs-shuffle switching
  (cluster/fragment.py), hot-key salting (cluster/exchange.py), and the mesh
  tier's broadcast rule (parallel/executor.py).

Safety contract (both stores): keys are structural fingerprints stored under
a stable content hash of their repr. A hash collision or stale entry can only
mis-SIZE or mis-ROUTE a plan choice — pick a worse join order, broadcast or
salt when it no longer pays — never corrupt a result: every consumer's
output is semantics-preserving for any stats value, and in-program
compactions keep their overflow-flag exact-repair path
(FusedCompiler._adaptive). Note that scan fingerprints key by table NAME +
pushed filters + partition, not content: re-registering different data
under the same name keeps old entries, which — by the same contract — can
only mis-route plans until fresh observations overwrite them.

Persisting beside the XLA compilation cache means a new process plans from
the cluster's observed history directly — and hits the persistent XLA cache
for the programs those plans compile to."""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import threading
from typing import Optional

# lock discipline (checked by igloo-lint lock-discipline): one store instance
# is shared by every executor the engine builds, and `put`/`observe`/`flush`
# run both on the query thread and on worker threads (GRACE prefetch, Flight
# RPC handlers); `_data`/`_dirty` read-modify-writes must hold the store lock
_GUARDED_BY = {"_lock": ("_data", "_dirty")}

#: kill switch for the whole telemetry->planner loop: IGLOO_ADAPTIVE=0
#: reproduces pre-adaptive plans (join order, exchange shape) exactly
ADAPTIVE_ENV = "IGLOO_ADAPTIVE"


def adaptive_enabled() -> bool:
    return os.environ.get(ADAPTIVE_ENV, "1") != "0"


def _digest(key) -> str:
    return hashlib.sha1(repr(key).encode()).hexdigest()


def digest_key(key) -> str:
    """Public stable digest of a fingerprint key — what rides the wire when a
    planner tags fragments for the coordinator's end-of-query recording."""
    return _digest(key)


class _JsonStore:
    """Digest-keyed JSON-file store base: atomic flush, never fails a query."""

    def __init__(self, path: Optional[str]):
        self._path = path
        self._lock = threading.Lock()
        self._data: dict = {}
        self._dirty = False
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    self._data = self._coerce(json.load(f))
            except Exception:
                self._data = {}

    def _coerce(self, raw: dict) -> dict:  # subclass value validation
        return dict(raw)

    def flush(self) -> None:
        # the file write stays INSIDE the lock: two racing flushes (query
        # thread + GRACE prefetch thread) could otherwise os.replace an older
        # snapshot over a newer one, silently dropping a just-adopted entry
        with self._lock:
            if not self._dirty or not self._path:
                return
            self._dirty = False
            try:
                os.makedirs(os.path.dirname(self._path), exist_ok=True)
                fd, tmp = tempfile.mkstemp(dir=os.path.dirname(self._path))
                with os.fdopen(fd, "w") as f:
                    json.dump(self._data, f)
                os.replace(tmp, self._path)
            except Exception:
                pass  # stats are an optimization; never fail a query on them


class HintStore(_JsonStore):
    """Flat int hints (live counts / sentinels) for the fused/staged tiers."""

    def _coerce(self, raw: dict) -> dict:
        return {k: int(v) for k, v in raw.items()}

    def get(self, key) -> Optional[int]:
        with self._lock:
            return self._data.get(_digest(key))

    def put(self, key, n: int) -> None:
        d = _digest(key)
        with self._lock:
            if self._data.get(d) != n:
                self._data[d] = int(n)
                self._dirty = True

    def remove(self, key) -> None:
        with self._lock:
            if self._data.pop(_digest(key), None) is not None:
                self._dirty = True


class AdaptiveStats(_JsonStore):
    """Observed execution statistics per logical-subtree fingerprint.

    Record fields (all optional, merged per observation):
      rows       observed output cardinality of the subtree
      in_rows    sum of join input cardinalities (rows/in_rows = selectivity)
      bytes      observed Arrow result bytes (exchange fragments)
      max_share  top-bucket share of the subtree's hash exchange (skew sketch,
                 from the fragment store's existing per-bucket rows metadata)
      hot_bucket index of that top bucket
      nbuckets   bucket count the sketch was taken at (a sketch only guides
                 salting when the current plan uses the same bucket count —
                 the hash is deterministic per count, not across counts)
      peak_hbm_bytes  observed device-memory watermark after running the
                 subtree (an UPPER bound — the watermark is process-
                 cumulative); the serving admission gate's footprint
                 prediction (cluster/serving.py, docs/serving.md)
    """

    _FIELDS = ("rows", "in_rows", "bytes", "max_share", "hot_bucket",
               "nbuckets", "peak_hbm_bytes")

    def _coerce(self, raw: dict) -> dict:
        out = {}
        for k, v in raw.items():
            if isinstance(v, dict):
                out[k] = {f: v[f] for f in self._FIELDS if f in v}
        return out

    # NOTE: `observed`/`observed_rows` return raw data-dependent values —
    # they are taint SOURCES for the igloo-lint jit-key checker: their
    # results must never reach a _jitted fingerprint unquantized (they drive
    # plan-structure and routing choices, not program shapes).
    def observed(self, key) -> Optional[dict]:
        with self._lock:
            rec = self._data.get(_digest(key))
            return dict(rec) if rec is not None else None

    def observed_rows(self, key) -> Optional[int]:
        rec = self.observed(key)
        v = rec.get("rows") if rec else None
        return int(v) if v is not None else None

    def selectivity(self, key) -> Optional[float]:
        """Observed rows-out / rows-in, when both were recorded."""
        rec = self.observed(key)
        if not rec or not rec.get("in_rows") or rec.get("rows") is None:
            return None
        return rec["rows"] / rec["in_rows"]

    def observe(self, key, **fields) -> None:
        self.observe_by_digest(_digest(key), **fields)

    def observe_by_digest(self, digest: str, **fields) -> None:
        """Merge non-None fields into the record (last observation wins —
        stale values can only mis-route, see module docstring)."""
        clean = {k: v for k, v in fields.items()
                 if k in self._FIELDS and v is not None}
        if not clean:
            return
        with self._lock:
            rec = self._data.get(digest)
            if rec is None:
                rec = {}
                self._data[digest] = rec
            for k, v in clean.items():
                if rec.get(k) != v:
                    rec[k] = v
                    self._dirty = True

    def remove(self, key) -> None:
        with self._lock:
            if self._data.pop(_digest(key), None) is not None:
                self._dirty = True


class BaselineStats(_JsonStore):
    """Rolling per-fingerprint performance baselines for the watchtower's
    anomaly detector (docs/observability.md#watchtower): bounded windows of
    observed wall seconds, peak-HBM bytes, and exchange bytes per `plan_fp`
    key. Quantiles are computed from the window at read time — a WINDOW of
    64 keeps every digest a few hundred bytes in the JSON file while P99
    still reflects the recent regime, and a plan whose cost legitimately
    shifts (data grew) re-baselines itself within one window.

    Same safety contract as AdaptiveStats: a stale or collided baseline can
    only mis-CLASSIFY a query as slow/normal — escalation captures extra
    telemetry, it never changes a plan or a result."""

    _FIELDS = ("wall_s", "hbm_bytes", "exchange_bytes")
    WINDOW = 64

    def _coerce(self, raw: dict) -> dict:
        out = {}
        for k, v in raw.items():
            if not isinstance(v, dict):
                continue
            rec: dict = {"count": int(v.get("count", 0))}
            for f in self._FIELDS:
                vals = v.get(f)
                if isinstance(vals, list):
                    rec[f] = [float(x) for x in vals][-self.WINDOW:]
            out[k] = rec
        return out

    def observe(self, key, wall_s: Optional[float] = None,
                hbm_bytes: Optional[float] = None,
                exchange_bytes: Optional[float] = None) -> None:
        fields = {"wall_s": wall_s, "hbm_bytes": hbm_bytes,
                  "exchange_bytes": exchange_bytes}
        clean = {k: float(v) for k, v in fields.items() if v is not None}
        if not clean:
            return
        d = _digest(key)
        with self._lock:
            rec = self._data.setdefault(d, {"count": 0})
            rec["count"] = int(rec.get("count", 0)) + 1
            for f, v in clean.items():
                window = rec.setdefault(f, [])
                window.append(v)
                del window[:-self.WINDOW]
            self._dirty = True

    @staticmethod
    def _quantile(vals: list, q: float) -> float:
        if not vals:
            return 0.0
        s = sorted(vals)
        idx = min(max(int(q * len(s) + 0.999999) - 1, 0), len(s) - 1)
        return s[idx]

    def baseline(self, key) -> dict:
        """Digest summary: observation count plus P50/P99 of each window
        (0.0 where nothing was observed)."""
        with self._lock:
            rec = self._data.get(_digest(key))
            rec = {k: (list(v) if isinstance(v, list) else v)
                   for k, v in rec.items()} if rec else {}
        out = {"count": int(rec.get("count", 0))}
        for f in self._FIELDS:
            vals = rec.get(f) or []
            out[f"{f}_p50"] = self._quantile(vals, 0.50)
            out[f"{f}_p99"] = self._quantile(vals, 0.99)
        return out


def row_width_bytes(schema) -> int:
    """Estimated bytes per row for observed-rows -> bytes conversion. The
    join reorder (plan/optimizer.py) and the broadcast switch
    (cluster/fragment.py) must agree on what a row weighs, so the heuristic
    lives here, next to the store both read."""
    return max(8, sum(16 if f.dtype.is_string else 8 for f in schema))


# --- structural plan fingerprints -------------------------------------------


def plan_fp(plan, exact: bool = False):
    """Projection-INSENSITIVE structural fingerprint of a logical subtree:
    expressions by column NAME (not index), scans by (table, filters,
    partition). The same logical work keys the same entry whether observed
    pre- or post-pruning, on the device tier or a cluster fragment. Returns
    None for shapes with no stable key (subqueries, windows, unions...).

    Expressions enter in their SHAPE form (`plan.expr.shape`: a literal's
    type and position, not its value), as in every program and hint key:
    what AdaptiveStats, the watchtower's baselines and the staged tier's
    `slive` hints learnt under one parameter set of a query is found under
    the next. `exact=True` keeps the values: it keys the staged tier's
    live-count hint for a join input (exec/executor.py), whose rows depend
    on them."""
    from igloo_tpu.plan import expr as E
    from igloo_tpu.plan import logical as L

    def xr(x):
        # a nested subquery is spelled as what equals nothing (two different
        # subqueries must not collide) -> poison the whole key
        r = E.fingerprint(x, by_name=True) if exact \
            else E.shape(x, by_name=True)
        return None if "Subquery(" in r or "Exists(" in r else r

    def node(tag, exprs, *rest):
        er = xr(exprs)
        subs = [plan_fp(c, exact) for c in plan.children()]
        if er is None or any(s is None for s in subs):
            return None
        return (tag, er) + rest + tuple(subs)

    t = type(plan)
    if t is L.Scan:
        return node("scan", plan.pushed_filters, plan.table, plan.partition)
    if t is L.Filter:
        return node("filter", plan.predicate)
    if t is L.Project:
        return node("proj", plan.exprs, tuple(plan.names))
    if t is L.Join:
        return node("join", (plan.left_keys, plan.right_keys, plan.residual),
                    plan.join_type.value)
    if t is L.Aggregate:
        return node("agg", (plan.group_exprs, plan.aggs),
                    tuple(plan.agg_names))
    if t is L.Distinct:
        return node("distinct", ())
    if t is L.Sort:
        # ORDER BY must not poison the key: production queries near-always
        # sort their output, and an unkeyed plan gets no latency baseline
        # (docs/observability.md#watchtower)
        return node("sort", plan.keys, tuple(plan.ascending),
                    tuple(plan.nulls_first))
    if t is L.Limit:
        return node("limit", (), plan.limit, plan.offset)
    return None  # unbounded/unhandled shapes: no stable key


# --- default instances -------------------------------------------------------


def default_store() -> HintStore:
    """Store beside the persistent XLA cache (same enable/disable knob)."""
    from igloo_tpu import compile_cache
    cache_dir = compile_cache.active_dir()
    return HintStore(os.path.join(cache_dir, "nhints.json")
                     if cache_dir else None)


_adaptive_singleton_lock = threading.Lock()
_adaptive_singleton: Optional[AdaptiveStats] = None

ADAPTIVE_PATH_ENV = "IGLOO_ADAPTIVE_STATS"


def adaptive_store() -> AdaptiveStats:
    """Process-wide AdaptiveStats: engine, coordinator planner, and mesh tier
    all feed and read ONE store. Path precedence: IGLOO_ADAPTIVE_STATS env >
    beside the persistent XLA cache > in-memory only (still adaptive within
    the process; nothing persists)."""
    global _adaptive_singleton
    with _adaptive_singleton_lock:
        if _adaptive_singleton is None:
            path = os.environ.get(ADAPTIVE_PATH_ENV)
            if path is None:
                from igloo_tpu import compile_cache
                cache_dir = compile_cache.active_dir()
                if cache_dir:
                    path = os.path.join(cache_dir, "adaptive_stats.json")
            _adaptive_singleton = AdaptiveStats(path or None)
        return _adaptive_singleton


def reset_adaptive_store() -> None:
    """Drop the process singleton (tests re-point IGLOO_ADAPTIVE_STATS)."""
    global _adaptive_singleton
    with _adaptive_singleton_lock:
        _adaptive_singleton = None


_watch_singleton_lock = threading.Lock()
_watch_singleton: Optional[BaselineStats] = None

WATCH_PATH_ENV = "IGLOO_WATCH_STATS"


def watch_store() -> BaselineStats:
    """Process-wide BaselineStats for the watchtower detector
    (utils/watch.py). Path precedence mirrors adaptive_store():
    IGLOO_WATCH_STATS env > beside the persistent XLA cache > in-memory
    only (baselines still build within the process; nothing persists)."""
    global _watch_singleton
    with _watch_singleton_lock:
        if _watch_singleton is None:
            path = os.environ.get(WATCH_PATH_ENV)
            if path is None:
                from igloo_tpu import compile_cache
                cache_dir = compile_cache.active_dir()
                if cache_dir:
                    path = os.path.join(cache_dir, "watch_baselines.json")
            _watch_singleton = BaselineStats(path or None)
        return _watch_singleton


def reset_watch_store() -> None:
    """Drop the process singleton (tests re-point IGLOO_WATCH_STATS)."""
    global _watch_singleton
    with _watch_singleton_lock:
        _watch_singleton = None
