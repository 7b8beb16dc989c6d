"""Query fragments + the distributed planner.

Parity: the reference's `QueryFragment` (crates/coordinator/src/fragment.rs:
7-56 — id / FragmentType / plan / worker / dependencies) and
`DistributedPlanner` (distributed_planner.rs:25-150). Two reference flaws are
fixed by design:

- fragments no longer re-plan whole subtrees (gap G10: each reference fragment
  calls create_physical_plan on the FULL node, duplicating work) — a fragment's
  plan references its dependencies' results as `__frag_<id>` tables;
- aggregation is decomposed into per-worker partial fragments + one final
  merge fragment (the reference ships the whole aggregate to one place), so
  scan+reduce parallelizes across workers the way partial->shuffle->final
  aggregation parallelizes across chips in parallel/executor.py.

Placement: scan fragments stride provider partitions across workers (data
partition parallelism — the latent axis the reference never exploits, SURVEY
§2 parallelism table); non-leaf fragments round-robin across workers instead
of always running on the coordinator (distributed_planner.rs:65-92 pins every
join to "coordinator").

Shuffle joins (the reference's declared-but-dead FragmentType::Shuffle,
fragment.rs:12): an equi-join whose sides are both local subtrees becomes a
HASH-PARTITIONED EXCHANGE instead of a union onto one worker. Each side's
scan fragments get an `Exchange` root (the worker hash-partitions the result
by the join keys into B buckets at store time), and B per-bucket join
fragments — spread across workers — each read only bucket b of EVERY input
fragment via bucketed do_get tickets. Join compute and network traffic both
scale with worker count; the consumer unions the B join-fragment results.
"""
from __future__ import annotations

import itertools
import os
import uuid
from dataclasses import dataclass, field
from typing import Optional

from igloo_tpu import types as T
from igloo_tpu.cluster import serde
from igloo_tpu.plan import expr as E
from igloo_tpu.plan import logical as L
from igloo_tpu.plan.aggsplit import (
    DECOMPOSABLE, decompose_aggregate, final_merge_plan, partial_aggregate_node,
)
from igloo_tpu.sql.ast import JoinType
from igloo_tpu.utils import tracing

FRAG_PREFIX = "__frag_"

# join types a hash-partitioned exchange preserves: every row routes to
# exactly one bucket and matching keys co-locate, so inner/outer/semi/anti
# semantics are all per-bucket local. CROSS has no keys to partition by.
_SHUFFLE_JOIN_TYPES = {JoinType.INNER, JoinType.LEFT, JoinType.RIGHT,
                       JoinType.FULL, JoinType.SEMI, JoinType.ANTI}


@dataclass
class QueryFragment:
    """One unit of distributed work: a serialized plan whose `__frag_*` scans
    name the results of `deps`, placed on `worker` (an address)."""
    id: str
    plan: dict                       # serde.plan_to_json output
    worker: str = ""
    deps: list[str] = field(default_factory=list)
    schema: Optional[T.Schema] = None
    kind: str = ""                   # "scan" | "exchange" | "join" | "root"
    bucket: Optional[int] = None     # per-bucket join fragment's bucket id
    # AdaptiveStats digest of the join SIDE this fragment materializes: the
    # coordinator sums rows/bytes/bucket counts across fragments sharing a
    # key at query end and records them for the next plan (docs/adaptive.md)
    stats_key: Optional[str] = None

    def is_ready(self, completed: set[str]) -> bool:
        return all(d in completed for d in self.deps)


def _frag_scan(frag: "QueryFragment") -> L.LogicalPlan:
    """A plan node reading a dependency fragment's result."""
    s = L.Scan(table=FRAG_PREFIX + frag.id, provider=None)
    s.schema = frag.schema
    return s


def _bucket_scan(frag: "QueryFragment", bucket: int, buckets: int
                 ) -> L.LogicalPlan:
    """A plan node reading ONE hash bucket of a dependency fragment's
    Exchange-partitioned result."""
    s = L.Scan(table=FRAG_PREFIX + frag.id, provider=None,
               bucket=bucket, buckets=buckets)
    s.schema = frag.schema
    return s


def _bucket_union(side_frags: list, bucket: int, buckets: int,
                  schema: T.Schema) -> L.LogicalPlan:
    children = [_bucket_scan(f, bucket, buckets) for f in side_frags]
    if len(children) == 1:
        return children[0]
    u = L.Union(inputs=children)
    u.schema = schema
    return u


def _whole_union(side_frags: list, schema: T.Schema) -> L.LogicalPlan:
    """Union of WHOLE fragment results (the broadcast build side)."""
    children: list[L.LogicalPlan] = [_frag_scan(f) for f in side_frags]
    if len(children) == 1:
        return children[0]
    u = L.Union(inputs=children)
    u.schema = schema
    return u


def _plain_key_indices(keys: list, schema: T.Schema) -> Optional[list[int]]:
    """Join keys as plain column indices into the side's output schema, or
    None when any key is a computed expression (then the two sides' raw
    column bytes need not agree and hash co-partitioning is unsound)."""
    idxs = []
    for k in keys:
        if type(k) is not E.Column or k.index is None or \
                not 0 <= k.index < len(schema.fields):
            return None
        idxs.append(k.index)
    return idxs


def _copy_expr(e):
    import copy
    return copy.deepcopy(e) if e is not None else None


def _rewrap(nodes: list, inner: L.LogicalPlan) -> L.LogicalPlan:
    """Re-apply upper-path nodes (root-first, as find_grace_join peeled them)
    over `inner`: shallow node copies with the input swapped — expressions
    stay shared, which is safe because _make_fragment serializes each
    fragment's plan to JSON at creation time."""
    import copy
    for nd in reversed(nodes):
        c = copy.copy(nd)
        c.input = inner
        inner = c
    return inner


def _is_local(p: L.LogicalPlan) -> bool:
    """True if the subtree is scan/filter/project/values only — safe to ship
    whole to a worker and, for scans, to stride by partition."""
    if isinstance(p, (L.Scan, L.Values)):
        return True
    if isinstance(p, (L.Filter, L.Project)):
        return _is_local(p.input)
    return False


def _subtree_scan(p: L.LogicalPlan) -> Optional[L.Scan]:
    if isinstance(p, L.Scan):
        return p
    if isinstance(p, (L.Filter, L.Project)):
        return _subtree_scan(p.input)
    return None


def _with_partition(p: L.LogicalPlan, part: tuple[int, ...]) -> L.LogicalPlan:
    """Copy of the subtree with its scan restricted to `part`, capturing the
    provider's partition-index fingerprint so reads fail loudly if the index
    is rebuilt (re-glob) between planning and execution."""
    n = L.copy_plan(p)
    sc = _subtree_scan(n)
    assert sc is not None
    sc.partition = part
    tok = getattr(sc.provider, "partition_token", None)
    if tok is not None:
        try:
            sc.partition_token = tok()
        except Exception:
            sc.partition_token = None
    return n


class DistributedPlanner:
    """Fragments an optimized plan across `workers` (list of addresses).

    Adaptive decisions (docs/adaptive.md, behind IGLOO_ADAPTIVE=0): when the
    process-wide AdaptiveStats store holds OBSERVED statistics for a join
    side (recorded by the coordinator from a previous run of the same side
    fingerprint), the planner may replace the hash exchange with a
    BROADCAST plan (replicating the small build side ships fewer bytes than
    exchanging both sides — the mesh tier's `should_broadcast` rule promoted
    to the fragment tier) or SALT a pathologically skewed exchange (split
    the hot bucket's probe rows across extra buckets, replicate the matching
    build bucket — the escape hatch docs/distributed.md used to document as
    unwinnable). First runs carry no observations and keep the plain
    exchange shape, so behavior only changes once telemetry justifies it."""

    def __init__(self, workers: list[str], partitions_per_worker: int = 1,
                 shuffle_buckets: Optional[int] = None,
                 topology: Optional[dict] = None,
                 budget_bytes: Optional[int] = None):
        if not workers:
            raise ValueError("no workers")
        self.workers = list(workers)
        self.ppw = partitions_per_worker
        # addr -> local mesh device count, from registration/heartbeat
        # reports (cluster/serde.py worker_info_*). Two-level sizing rule:
        # BUCKET COUNT scales with hosts (workers x ppw below — a bucket is
        # a unit of cross-worker exchange), SHARD COUNT scales with chips
        # (each bucket fragment row-shards across its worker's mesh), so a
        # B-bucket join on W workers x D devices runs W x D-way without the
        # planner over-bucketing to W x D fragments (which would multiply
        # exchange slices and per-fragment overhead, not parallelism).
        self.topology = {a: max(int(d), 1)
                         for a, d in (topology or {}).items()}
        self.total_shards = sum(self.topology.get(a, 1)
                                for a in self.workers)
        self._rr = itertools.cycle(range(len(workers)))
        if shuffle_buckets is None:
            env = os.environ.get("IGLOO_SHUFFLE_BUCKETS")
            shuffle_buckets = int(env) if env else \
                len(self.workers) * self.ppw
        self.shuffle_buckets = max(1, shuffle_buckets)
        # kill switch for A/B against the union-onto-one-worker plan shape
        self.shuffle_enabled = \
            os.environ.get("IGLOO_SHUFFLE_JOIN", "1") != "0"
        from igloo_tpu.exec.hints import adaptive_enabled
        self.adaptive_enabled = adaptive_enabled()
        # per-join decision records, published into last_metrics["adaptive"]
        # so every plan choice is attributable
        self.adaptive_info: list[dict] = []
        # distributed out-of-core (docs/out_of_core.md): with a per-host
        # budget, an over-budget join tree fragments into per-GRACE-partition
        # bucket joins spread across the fleet instead of demoting to the
        # single-node ladder. IGLOO_GRACE_DISTRIBUTED=0 preserves today's
        # plans bit-identically (the coordinator never passes a budget).
        self.budget_bytes = budget_bytes
        self.grace_enabled = \
            os.environ.get("IGLOO_GRACE_DISTRIBUTED", "1") != "0"
        # set when plan() took the grace path: {"buckets", "partitioned_
        # leaves", "replicated_leaves", "budget_bytes"} — the coordinator
        # publishes it as the query's `oversized` metrics block
        self.grace_info: Optional[dict] = None

    def plan(self, plan: L.LogicalPlan) -> list[QueryFragment]:
        """-> fragments in dependency-safe order; the LAST one is the root."""
        frags: list[QueryFragment] = []
        if self.budget_bytes and self.grace_enabled and \
                len(self.workers) >= 2:
            root_plan = self._try_grace_distributed(plan, frags)
            if root_plan is not None:
                self._make_fragment(root_plan, frags_out=frags)
                return frags
            frags.clear()
        root_plan = self._split(plan, frags)
        self._make_fragment(root_plan, frags_out=frags)  # appends the root
        return frags

    # --- internals ---

    def _next_worker(self) -> str:
        return self.workers[next(self._rr)]

    def _bucket_placement(self, n_buckets: int) -> list[str]:
        """Bucket -> worker assignment. Homogeneous topologies keep the
        round-robin stride; a heterogeneous cluster (workers with unequal
        mesh sizes) gets largest-remainder proportional shares — a 4-chip
        worker takes 4x the buckets of a 1-chip worker, since each of its
        buckets runs 4-way inside the mesh — interleaved so consecutive
        buckets still spread across workers."""
        W = len(self.workers)
        devs = [self.topology.get(a, 1) for a in self.workers]
        if len(set(devs)) <= 1:
            return [self.workers[b % W] for b in range(n_buckets)]
        total = sum(devs)
        quota = [n_buckets * d / total for d in devs]
        counts = [int(q) for q in quota]
        for i in sorted(range(W), key=lambda i: quota[i] - counts[i],
                        reverse=True)[:n_buckets - sum(counts)]:
            counts[i] += 1
        out: list[str] = []
        while len(out) < n_buckets:
            for i in range(W):
                if counts[i]:
                    counts[i] -= 1
                    out.append(self.workers[i])
        return out

    def _make_fragment(self, plan: L.LogicalPlan,
                       frags_out: list[QueryFragment],
                       deps: Optional[list[str]] = None,
                       worker: Optional[str] = None,
                       kind: str = "",
                       bucket: Optional[int] = None,
                       stats_key: Optional[str] = None) -> QueryFragment:
        plan_json = serde.plan_to_json(plan)
        if deps is None:
            # dedupe, preserving order: a per-bucket join fragment references
            # the same dependency once per side scan
            seen: dict[str, None] = {}
            for d in _frag_refs(plan_json):
                seen.setdefault(d["table"][len(FRAG_PREFIX):])
            deps = list(seen)
        f = QueryFragment(id=uuid.uuid4().hex[:12], plan=plan_json,
                          worker=worker or self._next_worker(),
                          deps=deps, schema=plan.schema, kind=kind,
                          bucket=bucket, stats_key=stats_key)
        frags_out.append(f)
        return f

    def _split(self, p: L.LogicalPlan,
               frags: list[QueryFragment]) -> L.LogicalPlan:
        """Post-order: replace distributable subtrees with fragment scans;
        return the plan the root fragment executes."""
        if isinstance(p, L.Aggregate) and _is_local(p.input) and \
                all(a.func in DECOMPOSABLE for a in p.aggs):
            return self._split_aggregate(p, frags)
        # recurse into children; large local subtrees under joins become
        # their own (partitioned) fragments
        for name in ("input", "left", "right"):
            ch = getattr(p, name, None)
            if isinstance(ch, L.LogicalPlan):
                setattr(p, name, self._split(ch, frags))
        if isinstance(p, L.Union):
            p.inputs = [self._split(c, frags) for c in p.inputs]
        if isinstance(p, L.Join):
            shuffled = self._try_shuffle_join(p, frags)
            if shuffled is not None:
                return shuffled
            for name in ("left", "right"):
                ch = getattr(p, name)
                if _is_local(ch) and not isinstance(ch, L.Values):
                    setattr(p, name, self._scan_fragments(ch, frags))
        return p

    # --- hash-partitioned shuffle joins ---

    def _try_shuffle_join(self, p: L.Join,
                          frags: list[QueryFragment]
                          ) -> Optional[L.LogicalPlan]:
        """Join over two local subtrees -> per-bucket join fragments reading
        bucket slices of Exchange-partitioned side fragments; returns the
        Union the consumer executes, or None when ineligible (the caller
        falls back to the union-of-scan-fragments shape)."""
        if not self.shuffle_enabled or len(self.workers) < 2 \
                or self.shuffle_buckets < 2:
            return None
        if p.join_type not in _SHUFFLE_JOIN_TYPES or not p.left_keys:
            return None
        for side in (p.left, p.right):
            if not _is_local(side) or isinstance(side, L.Values) \
                    or side.schema is None:
                return None
        lkeys = _plain_key_indices(p.left_keys, p.left.schema)
        rkeys = _plain_key_indices(p.right_keys, p.right.schema)
        if lkeys is None or rkeys is None:
            return None
        # both sides must hash the same value domain: binder coercion casts
        # (non-Column keys) are already rejected above, this guards direct
        # Column pairs of unequal dtype
        for lk, rk in zip(p.left_keys, p.right_keys):
            if lk.dtype is None or rk.dtype is None or \
                    lk.dtype.id is not rk.dtype.id:
                return None
        B = self.shuffle_buckets
        lkey, rkey, lobs, robs = self._side_observations(p)
        # --- broadcast-vs-shuffle switch (observed stats only) ---
        bcast = self._choose_broadcast(p, lobs, robs)
        if bcast is not None:
            return self._broadcast_join(p, frags, bcast, lkey, rkey)
        # --- hot-key salting of a pathologically skewed exchange ---
        salt = self._choose_salt(p, B, lobs, robs)
        lsalt = rsalt = None
        if salt is not None:
            hot, S, probe_left = salt
            lsalt = (hot, S, "probe" if probe_left else "build")
            rsalt = (hot, S, "build" if probe_left else "probe")
            B_total = B + S - 1
        else:
            B_total = B
        left_frags = self._exchange_fragments(p.left, lkeys, B, frags,
                                              stats_key=lkey, salt=lsalt)
        right_frags = self._exchange_fragments(p.right, rkeys, B, frags,
                                               stats_key=rkey, salt=rsalt)
        join_scans: list[L.LogicalPlan] = []
        W = len(self.workers)
        placement = self._bucket_placement(B)
        for b in range(B_total):
            jb = L.Join(left=_bucket_union(left_frags, b, B_total,
                                           p.left.schema),
                        right=_bucket_union(right_frags, b, B_total,
                                            p.right.schema),
                        join_type=p.join_type,
                        left_keys=[_copy_expr(k) for k in p.left_keys],
                        right_keys=[_copy_expr(k) for k in p.right_keys],
                        residual=_copy_expr(p.residual))
            jb.schema = p.schema
            if salt is not None and b >= B:
                # salted extra buckets hold slices of the HOT bucket's work:
                # rotate them onto workers AFTER the one the hot bucket was
                # PLACED on (the weighted placement, not the bucket index —
                # a heterogeneous placement can put bucket `hot` anywhere),
                # or the split re-serializes on one worker (host-rotated,
                # not device-weighted: they are slices of ONE bucket, and
                # spreading across hosts is the whole point)
                hot_i = self.workers.index(placement[salt[0]])
                worker = self.workers[(hot_i + 1 + (b - B)) % W]
            else:
                worker = placement[b]
            jf = self._make_fragment(jb, frags, worker=worker,
                                     kind="join", bucket=b)
            join_scans.append(_frag_scan(jf))
        if salt is None and self.adaptive_enabled:
            self.adaptive_info.append({
                "strategy": "shuffle", "buckets": B,
                "total_shards": self.total_shards,
                "adaptive_source": "observed" if (lobs or robs)
                else "estimated"})
        if len(join_scans) == 1:
            return join_scans[0]
        u = L.Union(inputs=join_scans)
        u.schema = p.schema
        return u

    # --- distributed out-of-core GRACE (docs/out_of_core.md) ---

    def _try_grace_distributed(self, plan: L.LogicalPlan,
                               frags: list[QueryFragment]
                               ) -> Optional[L.LogicalPlan]:
        """Over-budget join tree -> per-bucket join fragments whose buckets
        ARE the GRACE partitions: exec/grace.py's partition scheme (key
        equivalence classes + anchor-analysis validity + budget-derived
        partition count) lifted to the fleet. Every partitioned leaf becomes
        Exchange fragments hash-routing into B buckets (streamed +
        spill-backed on the worker, cluster/exchange.py StreamingPut);
        replicated leaves ship whole; bucket b's join fragment unions bucket
        b of every partitioned side and runs wherever the device-weighted
        placement puts it. Returns the root plan, or None when the plan does
        not qualify — the caller falls back to the normal split (and the
        coordinator to the single-node demote ladder)."""
        from igloo_tpu.exec import grace
        gp = grace.find_grace_join(plan, self.budget_bytes)
        if gp is None:
            return None
        part = [lf for lf in gp.leaves if lf.key_col is not None]
        rep = [lf for lf in gp.leaves if lf.key_col is None]
        if not part:
            return None
        if any(lf.node.schema is None for lf in gp.leaves):
            return None
        for lf in part:
            # partitioned leaves must be shippable scan chains (the Exchange
            # fragment re-executes them partition-at-a-time on the worker)
            if not _is_local(lf.node) or isinstance(lf.node, L.Values):
                return None
        B = min(max(gp.n_parts, len(self.workers) * self.ppw),
                grace.MAX_GRACE_PARTITIONS)
        with tracing.span("grace.distributed", buckets=B,
                          partitioned=len(part), replicated=len(rep),
                          budget=int(self.budget_bytes)):
            leaf_sub: dict[int, tuple] = {}
            for lf in rep:
                f = self._make_fragment(L.copy_plan(lf.node), frags,
                                        deps=[], kind="scan")
                leaf_sub[id(lf.node)] = (False, [f])
            for lf in part:
                lfr = self._exchange_fragments(lf.node, [lf.key_col], B,
                                               frags)
                leaf_sub[id(lf.node)] = (True, lfr)

            def rebuild(n: L.LogicalPlan, b: int) -> L.LogicalPlan:
                if id(n) in leaf_sub:
                    bucketed, lfr = leaf_sub[id(n)]
                    if bucketed:
                        return _bucket_union(lfr, b, B, n.schema)
                    return _whole_union(lfr, n.schema)
                if isinstance(n, L.Filter):
                    f = L.Filter(input=rebuild(n.input, b),
                                 predicate=_copy_expr(n.predicate))
                    f.schema = n.schema
                    return f
                j = L.Join(left=rebuild(n.left, b),
                           right=rebuild(n.right, b),
                           join_type=n.join_type,
                           left_keys=[_copy_expr(k) for k in n.left_keys],
                           right_keys=[_copy_expr(k) for k in n.right_keys],
                           residual=_copy_expr(n.residual))
                j.schema = n.schema
                return j

            # the upper path splits at the aggregate: nodes BELOW it run
            # inside every bucket fragment (ahead of the partial aggregate),
            # nodes ABOVE it wrap the final merge in the root fragment
            above, below = gp.path, []
            partial_schema = partial_aggs = partial_names = final_plan = None
            if gp.agg is not None:
                ai = gp.path.index(gp.agg)
                above, below = gp.path[:ai], gp.path[ai + 1:]
                partial_schema, partial_aggs, partial_names, final_plan = \
                    decompose_aggregate(gp.agg)
            placement = self._bucket_placement(B)
            bucket_scans: list[L.LogicalPlan] = []
            for b in range(B):
                body = _rewrap(below, rebuild(gp.root, b))
                if gp.agg is not None:
                    body = partial_aggregate_node(
                        gp.agg, body, partial_schema, partial_aggs,
                        partial_names)
                bf = self._make_fragment(body, frags, worker=placement[b],
                                         kind="join", bucket=b)
                bucket_scans.append(_frag_scan(bf))
            if len(bucket_scans) == 1:
                merged: L.LogicalPlan = bucket_scans[0]
            else:
                merged = L.Union(inputs=bucket_scans)
                merged.schema = partial_schema if gp.agg is not None \
                    else gp.root.schema
            root = final_merge_plan(gp.agg, merged, final_plan) \
                if gp.agg is not None else merged
            root = _rewrap(above, root)
        tracing.counter("grace.remote_partitions", B)
        self.grace_info = {
            "buckets": B, "partitioned_leaves": len(part),
            "replicated_leaves": len(rep),
            "budget_bytes": int(self.budget_bytes)}
        if self.adaptive_enabled:
            self.adaptive_info.append({
                "strategy": "grace_distributed", "buckets": B,
                "partitioned_leaves": len(part),
                "adaptive_source": "estimated"})
        return root

    # --- adaptive decisions (docs/adaptive.md) ---

    def _side_observations(self, p: L.Join):
        """(left digest, right digest, left obs, right obs) for the join's
        side fingerprints; digests tag this query's fragments so the
        coordinator records what actually happened under the same keys the
        NEXT planning reads."""
        if not self.adaptive_enabled:
            return None, None, None, None
        from igloo_tpu.exec.hints import adaptive_store, digest_key, plan_fp
        store = adaptive_store()
        out = []
        for side in (p.left, p.right):
            fp = plan_fp(side)
            if fp is None:
                out.extend([None, None])
            else:
                out.extend([digest_key(fp), store.observed(fp)])
        if out[0] is not None and out[0] == out[2]:
            # self-join: both sides share one fingerprint, so per-side
            # recording would SUM the two sides into one record (2x rows,
            # merged sketches) — a systematic bias, not tolerable staleness.
            # Skip observation and recording for this join entirely.
            return None, None, None, None
        return out[0], out[2], out[1], out[3]

    @staticmethod
    def _replicable(jt: JoinType, build_left: bool) -> bool:
        """True when replicating the build side cannot duplicate output:
        build-side unmatched rows are never emitted for these types, and
        probe rows still appear exactly once (same validity rule as the mesh
        tier's broadcast join, parallel/shuffle.py)."""
        if jt is JoinType.INNER:
            return True
        if jt is JoinType.LEFT:
            return not build_left
        if jt is JoinType.RIGHT:
            return build_left
        if jt in (JoinType.SEMI, JoinType.ANTI):
            return not build_left   # build is always the right side
        return False                # FULL: both sides preserved

    @staticmethod
    def _obs_bytes(side: L.LogicalPlan, obs: Optional[dict]) -> Optional[int]:
        """Observed side size in bytes: exchange result bytes when recorded,
        else observed rows x estimated row width."""
        if not obs:
            return None
        if obs.get("bytes"):
            return int(obs["bytes"])
        if obs.get("rows") is not None:
            from igloo_tpu.exec.hints import row_width_bytes
            return int(obs["rows"]) * row_width_bytes(side.schema.fields)
        return None

    def _choose_broadcast(self, p: L.Join, lobs, robs) -> Optional[str]:
        """"left"/"right" build side to replicate, or None. Fires only on
        OBSERVED sizes: replicating on a bad estimate ships build x W bytes,
        while a missed broadcast merely keeps the exchange — asymmetric risk,
        so the first run always observes.

        Two-level composition: this rule decides HOST-level replication
        (W - 1 extra network copies), independently of the mesh tier's
        `should_broadcast` (parallel/shuffle.py), which decides CHIP-level
        distribution of whatever one worker holds. They cannot
        double-broadcast: a side replicated here arrives on each worker
        once, and the worker's mesh then either all-gathers that one copy
        across its chips (chip broadcast) or hash-shuffles it (chip
        exchange) — each level moves only its own minimum, and salting
        stays a fragment-level concern (the mesh tier's escape hatch is
        broadcast, see PATHOLOGICAL SKEW RULE)."""
        if not self.adaptive_enabled:
            return None
        lb = self._obs_bytes(p.left, lobs)
        rb = self._obs_bytes(p.right, robs)
        if lb is None or rb is None:
            return None
        W = len(self.workers)
        floor = 64 * 1024 * W  # tiny build sides always broadcast
        cand = []
        if self._replicable(p.join_type, True) and \
                lb * (W - 1) <= max(rb, floor):
            cand.append(("left", lb))
        if self._replicable(p.join_type, False) and \
                rb * (W - 1) <= max(lb, floor):
            cand.append(("right", rb))
        if not cand:
            return None
        return min(cand, key=lambda c: c[1])[0]

    def _choose_salt(self, p: L.Join, B: int, lobs, robs):
        """(hot_bucket, S, probe_is_left) when one side's skew sketch crossed
        the pathological bound at THIS bucket count and the other side may
        replicate, else None."""
        if not self.adaptive_enabled or B < 2:
            return None
        from igloo_tpu.parallel.shuffle import pathological_share
        bound = pathological_share(B)
        env = os.environ.get("IGLOO_SALT_BUCKETS")
        S = int(env) if env else max(2, len(self.workers))
        for obs, probe_left in ((lobs, True), (robs, False)):
            if not obs or obs.get("max_share") is None or \
                    obs.get("hot_bucket") is None:
                continue
            if obs.get("nbuckets") != B:
                continue  # sketch taken at another bucket count: not mappable
            if obs["max_share"] <= bound:
                continue
            if not self._replicable(p.join_type, build_left=not probe_left):
                continue
            self.adaptive_info.append({
                "strategy": "salted", "buckets": B, "salt": S,
                "hot_bucket": int(obs["hot_bucket"]),
                "probe": "left" if probe_left else "right",
                "max_share": round(float(obs["max_share"]), 4),
                "adaptive_source": "observed"})
            tracing.counter("adaptive.salted")
            from igloo_tpu.cluster import events
            events.emit("exchange_salted", hot_bucket=int(obs["hot_bucket"]),
                        salt=S, max_share=round(float(obs["max_share"]), 4))
            return int(obs["hot_bucket"]), S, probe_left
        return None

    def _broadcast_join(self, p: L.Join, frags: list[QueryFragment],
                        build_side: str, lkey, rkey) -> L.LogicalPlan:
        """Replicate the build side instead of exchanging both: probe scan
        fragments keep their data in place, one join fragment per probe
        fragment runs CO-LOCATED with it and fetches the (small) build
        result — the only bytes that move."""
        build_left = build_side == "left"
        build = p.left if build_left else p.right
        probe = p.right if build_left else p.left
        build_frags = self._side_fragments(
            build, frags, stats_key=lkey if build_left else rkey)
        probe_frags = self._side_fragments(
            probe, frags, stats_key=rkey if build_left else lkey)
        tracing.counter("adaptive.broadcast")
        from igloo_tpu.cluster import events
        events.emit("broadcast_join", build=build_side,
                    probe_fragments=len(probe_frags))
        self.adaptive_info.append({
            "strategy": "broadcast", "build": build_side,
            "probe_fragments": len(probe_frags),
            "adaptive_source": "observed"})
        join_scans: list[L.LogicalPlan] = []
        for pf in probe_frags:
            bunion = _whole_union(build_frags, build.schema)
            pscan = _frag_scan(pf)
            left, right = (bunion, pscan) if build_left else (pscan, bunion)
            jb = L.Join(left=left, right=right, join_type=p.join_type,
                        left_keys=[_copy_expr(k) for k in p.left_keys],
                        right_keys=[_copy_expr(k) for k in p.right_keys],
                        residual=_copy_expr(p.residual))
            jb.schema = p.schema
            jf = self._make_fragment(jb, frags, worker=pf.worker, kind="join")
            join_scans.append(_frag_scan(jf))
        if len(join_scans) == 1:
            return join_scans[0]
        u = L.Union(inputs=join_scans)
        u.schema = p.schema
        return u

    def _side_fragments(self, side: L.LogicalPlan,
                        frags: list[QueryFragment],
                        stats_key: Optional[str] = None
                        ) -> list[QueryFragment]:
        """Plain (un-exchanged) fragments for a join side, one per scan
        partition set."""
        out = []
        for part in self._partition_sets(side):
            sub = _with_partition(side, part) if part else L.copy_plan(side)
            out.append(self._make_fragment(sub, frags, deps=[], kind="scan",
                                           stats_key=stats_key))
        return out

    def _exchange_fragments(self, side: L.LogicalPlan, keys: list[int],
                            buckets: int,
                            frags: list[QueryFragment],
                            stats_key: Optional[str] = None,
                            salt: Optional[tuple] = None
                            ) -> list[QueryFragment]:
        """One Exchange-rooted fragment per scan partition set of `side`.
        `salt` = (hot_bucket, S, role) adds the salted-bucket spread/
        replication at the worker's partition step (cluster/exchange.py)."""
        out = []
        for part in self._partition_sets(side):
            sub = _with_partition(side, part) if part else L.copy_plan(side)
            ex = L.Exchange(input=sub, keys=list(keys), buckets=buckets)
            if salt is not None:
                ex.salt_bucket, ex.salt, ex.salt_role = salt
            ex.schema = sub.schema
            out.append(self._make_fragment(ex, frags, deps=[],
                                           kind="exchange",
                                           stats_key=stats_key))
        return out

    def _scan_fragments(self, subtree: L.LogicalPlan,
                        frags: list[QueryFragment]) -> L.LogicalPlan:
        """Partition a local subtree across workers; consumer unions results."""
        parts = self._partition_sets(subtree)
        if len(parts) <= 1:
            f = self._make_fragment(subtree, frags, deps=[])
            return _frag_scan(f)
        children = []
        for part in parts:
            f = self._make_fragment(_with_partition(subtree, part), frags,
                                    deps=[])
            children.append(_frag_scan(f))
        u = L.Union(inputs=children)
        u.schema = subtree.schema
        return u

    def _partition_sets(self, subtree: L.LogicalPlan) -> list[tuple[int, ...]]:
        sc = _subtree_scan(subtree)
        if sc is None or sc.provider is None:
            return [()]
        try:
            n_parts = sc.provider.num_partitions()
        except Exception:
            n_parts = 1
        n_frag = min(len(self.workers) * self.ppw, max(n_parts, 1))
        if n_parts <= 1 or n_frag <= 1:
            return [()]
        return [tuple(range(i, n_parts, n_frag)) for i in range(n_frag)]

    def _split_aggregate(self, agg: L.Aggregate,
                         frags: list[QueryFragment]) -> L.LogicalPlan:
        """agg over a local subtree -> per-partition partial fragments +
        final merge plan (returned for the parent fragment to execute)."""
        parts = self._partition_sets(agg.input)
        partial_schema, partial_aggs, partial_names, final_plan = \
            decompose_aggregate(agg)

        children = []
        for part in parts:
            sub = _with_partition(agg.input, part) if part else \
                L.copy_plan(agg.input)
            node = partial_aggregate_node(agg, sub, partial_schema,
                                          partial_aggs, partial_names)
            f = self._make_fragment(node, frags, deps=[])
            children.append(_frag_scan(f))
        if len(children) == 1:
            merged: L.LogicalPlan = children[0]
        else:
            merged = L.Union(inputs=children)
            merged.schema = partial_schema
        return final_merge_plan(agg, merged, final_plan)


def _frag_refs(plan_json: dict) -> list[dict]:
    """All Scan nodes referencing fragment results, by tree walk."""
    out = []

    def walk(d):
        if isinstance(d, dict):
            if d.get("t") == "Scan" and str(d.get("table", "")).startswith(
                    FRAG_PREFIX):
                out.append(d)
            for v in d.values():
                walk(v)
        elif isinstance(d, list):
            for v in d:
                walk(v)
    walk(plan_json)
    return out
