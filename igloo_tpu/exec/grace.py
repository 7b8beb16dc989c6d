"""Out-of-core GRACE execution v2: multi-join partition pipelines on one device.

v1 (round-4) lifted the chunked executor's ceiling for exactly one shape — a
single bottom-level INNER equi-join under a decomposable aggregate.  SF10
Q3/Q5 stalled because their plans are *trees* of joins; anything past one join
fell back to monolithic execution.  v2 generalizes the planner and overlaps
host partitioning with device execution:

  plan analysis (find_grace_join): the plan below the usual upper path
      ([Limit] [Sort] [Project/Filter]* [Aggregate(decomposable)]) may be an
      arbitrary tree of INNER/SEMI/ANTI equi-joins.  Join keys that are bare
      columns trace down to (leaf, column) pairs; a union-find over the
      predicates yields KEY EQUIVALENCE CLASSES ("chains of shared key
      columns").  The partition scheme picks the best-scoring class (most
      over-budget bytes covered) whose assignment passes the anchor-analysis
      VALIDITY check (_scheme_valid): every leaf with a column in the class is
      CO-PARTITIONED by a shared hash of that column (equal values land in the
      same bucket on every side, so the union over buckets IS the join); the
      remaining leaves are REPLICATED (present in full in every partition).

  phase 1 (partition): each partitioned leaf is read provider-partition at a
      time through the device executor (filters/projections applied on
      device), and the surviving host Arrow rows split into P buckets by the
      key hash.  Integer/date/timestamp keys hash on their int64 lanes;
      dictionary-encoded STRING keys hash their dictionary bytes host-side
      (native/hash64.c via batch.hash64_bytes) and gather per row — equal
      strings hash equal across tables regardless of dictionary alignment.
      Replicated leaves execute once (streamed host-side when they are plain
      scan chains; routed through the chunked tier / recursive GRACE when
      they are complex subtrees).

  phase 2 (join, double-buffered): for p in 0..P the whole join tree runs on
      device with partitioned leaves replaced by bucket tables.  A background
      thread prepares partition p+1 — dictionary-encodes, codec-narrows and
      `device_put`s its buckets into prebuilt DeviceBatches — while partition
      p's jitted program runs, so HBM holds at most TWO partition pairs and
      the device never waits on host hashing/upload (IGLOO_GRACE_PIPELINE=0
      forces the serial loop for A/B).  All partitions of a leaf share one
      capacity (max bucket, pow2-rounded), one union dictionary per string
      column, union value bounds and union null-lane presence, so every
      partition keys the SAME compiled program per stage.

  recursion: when a partition's plan is still over budget (a replicated leaf
      bigger than the budget — its key was not in the chosen class), GRACE
      re-applies itself inside the partition on the next-best class, up to
      MAX_GRACE_DEPTH levels.

  merge: as v1 — decomposable aggregates run as per-partition partials merged
      once at the end; plain join trees concatenate host-side and the upper
      plan runs over the union.

The partition count is DERIVED from the budget (ceil(partitionable bytes /
budget)) and only clamped at MAX_GRACE_PARTITIONS, with a tracing counter
(`grace.partitions_clamped`) when the clamp re-opens a memory-bound gap.
Per-phase wall-clock rides the `grace.partition_ms` / `grace.join_ms` /
`grace.merge_ms` counters (surfaced by EXPLAIN ANALYZE).

The reference has no out-of-core story at all (its operators materialize
build sides in RAM HashMaps, crates/engine/src/operators/hash_join.rs:100-128).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np
import pyarrow as pa

from igloo_tpu import types as T
from igloo_tpu.exec import encoded
from igloo_tpu.plan import expr as E
from igloo_tpu.plan import logical as L
from igloo_tpu.sql.ast import JoinType
from igloo_tpu.utils import stats, tracing

# sanity clamp only — the real partition count is derived from the budget;
# past this the host-side bucket bookkeeping dominates and the clamp is
# reported via the grace.partitions_clamped counter instead of silently
# un-bounding memory (the old hard cap of 64 did exactly that)
MAX_GRACE_PARTITIONS = 1024
# recursive re-partitioning levels (level 0 = the outer GRACE execution)
MAX_GRACE_DEPTH = 3
# EXPLAIN ANALYZE records full operator subtrees for this many partitions;
# the rest contribute to the per-partition ROLLUP only (a 1024-partition
# query must not materialize 1024 stats subtrees)
DETAIL_PARTITIONS = 4

#: partitions that land as flight-recorder timeline spans (grace.partition /
#: grace.prefetch): enough to SEE the double-buffer overlap in Perfetto,
#: bounded so a 1024-partition query doesn't bloat its trace
_SPAN_PARTITIONS = 64

_INTERIOR_JOINS = (JoinType.INNER, JoinType.SEMI, JoinType.ANTI)

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


@dataclass
class GraceLeaf:
    """One leaf of the join tree: a subtree executable on its own."""
    node: L.LogicalPlan
    index: int
    nbytes: int                    # estimated lane bytes of its scans (0=unknown)
    over: bool                     # any single scan exceeds the budget
    kills: bool                    # empty leaf/bucket => empty partition result
    key_col: Optional[int] = None  # partition column in the leaf schema; None
    #                                => replicated into every partition


@dataclass
class GracePlan:
    """find_grace_join output: everything execute_to_arrow needs."""
    path: list                     # root chain down to (excluding) the join tree
    agg: Optional[L.Aggregate]
    root: L.LogicalPlan            # join-tree root
    leaves: list = field(default_factory=list)   # list[GraceLeaf]
    n_parts: int = 2


def _is_interior(node: L.LogicalPlan) -> bool:
    return isinstance(node, L.Join) and node.join_type in _INTERIOR_JOINS \
        and bool(node.left_keys)


def _key_eligible(key: E.Expr) -> bool:
    """Partition keys must be bare bound columns hashable host-side: the
    integer family (ints/dates/timestamps hash their int64 lanes) or strings
    (dictionary bytes hash through native hash64)."""
    if not isinstance(key, E.Column) or key.index is None or key.dtype is None:
        return False
    d = key.dtype
    return d.is_integer or d.is_temporal or d.is_string


def _collect_tree(root: L.LogicalPlan):
    """-> (joins, leaves) of the interior INNER/SEMI/ANTI equi-join tree.
    Filters above an interior join are transparent (kept in place by the
    per-partition rebuild); everything else is a leaf.  `kills` is False only
    for leaves under the right side of an ANTI join (an empty anti build side
    passes the probe side through, so such partitions must still run)."""
    joins: list[L.Join] = []
    leaves: list[GraceLeaf] = []

    def peel(n):
        while isinstance(n, L.Filter):
            n = n.input
        return n

    def walk(n, anti_right):
        j = peel(n)
        if _is_interior(j):
            joins.append(j)
            walk(j.left, anti_right)
            walk(j.right, anti_right or j.join_type is JoinType.ANTI)
        else:
            leaves.append(GraceLeaf(node=n, index=len(leaves), nbytes=0,
                                    over=False, kills=not anti_right))

    walk(root, False)
    return joins, leaves


def _trace_leaf_col(node: L.LogicalPlan, idx: int, leaf_ids: dict):
    """Resolve a bound column index against `node`'s output down to a
    (leaf id, leaf column index) pair; None when the column crosses a
    non-transparent node (e.g. a Project between joins)."""
    while True:
        if id(node) in leaf_ids:
            return (id(node), idx)
        if isinstance(node, L.Filter):
            node = node.input
            continue
        if isinstance(node, L.Join):
            if node.join_type in (JoinType.SEMI, JoinType.ANTI):
                node = node.left   # output schema = left side
                continue
            nl = len(node.left.schema)
            if idx < nl:
                node = node.left
            else:
                idx -= nl
                node = node.right
            continue
        return None


def find_grace_join(plan: L.LogicalPlan, budget_bytes: int):
    """Locate a GRACE-v2-eligible over-budget join tree. Returns a GracePlan
    or None when the plan does not qualify (caller takes the normal path)."""
    from igloo_tpu.plan.aggsplit import DECOMPOSABLE
    from igloo_tpu.exec.chunked import table_lane_bytes
    path: list[L.LogicalPlan] = []
    node = plan
    agg: Optional[L.Aggregate] = None
    while True:
        if isinstance(node, (L.Limit, L.Sort, L.Project, L.Filter)):
            path.append(node)
            node = node.input
        elif isinstance(node, L.Aggregate) and agg is None and \
                all(a.func in DECOMPOSABLE for a in node.aggs):
            agg = node
            path.append(node)
            node = node.input
        else:
            break
    if not _is_interior(node):
        return None
    joins, leaves = _collect_tree(node)

    over_any = False
    for leaf in leaves:
        total = 0
        for sc in L.walk_plan(leaf.node):
            if isinstance(sc, L.Scan) and sc.provider is not None:
                b = table_lane_bytes(sc.provider)
                if b is not None:
                    total += b
                    if b > budget_bytes:
                        leaf.over = True
                        over_any = True
        leaf.nbytes = total
    if not over_any:
        return None

    # key equivalence classes over (leaf, column) via union-find
    leaf_ids = {id(leaf.node): leaf for leaf in leaves}
    parent: dict = {}

    def find(x):
        while parent.setdefault(x, x) != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    def union(a, b):
        parent[find(a)] = find(b)

    for j in joins:
        for lk, rk in zip(j.left_keys, j.right_keys):
            if not (_key_eligible(lk) and _key_eligible(rk)):
                continue
            a = _trace_leaf_col(j.left, lk.index, leaf_ids)
            b = _trace_leaf_col(j.right, rk.index, leaf_ids)
            if a is not None and b is not None:
                union(a, b)
    classes: dict = {}
    for x in list(parent):
        classes.setdefault(find(x), []).append(x)

    # partition-scheme selection: classes ranked by over-budget bytes covered
    # (ties: partitionable bytes overall); the best class whose assignment
    # passes the co-location VALIDITY check (anchor analysis below) wins
    cands = []
    for members in classes.values():
        cols: dict[int, int] = {}   # leaf id -> first class column
        for lid, col in sorted(members, key=lambda m: m[1]):
            cols.setdefault(lid, col)
        over_b = sum(leaf_ids[lid].nbytes for lid in cols
                     if leaf_ids[lid].over)
        part_b = sum(leaf_ids[lid].nbytes for lid in cols)
        if over_b > 0:
            cands.append(((over_b, part_b), cols))
    cands.sort(key=lambda c: c[0], reverse=True)
    best = next(((score, cols) for score, cols in cands
                 if _scheme_valid(node, leaf_ids, cols)), None)
    if best is None:
        return None
    (_, part_bytes), cols = best
    for lid, col in cols.items():
        leaf_ids[lid].key_col = col

    need = max(2, -(-part_bytes // max(budget_bytes, 1)))
    if need > MAX_GRACE_PARTITIONS:
        tracing.counter("grace.partitions_clamped")
        tracing.log.warning(
            "grace: %d partitions needed to bound memory, clamped to %d "
            "(per-partition working set will exceed the %d-byte budget)",
            need, MAX_GRACE_PARTITIONS, budget_bytes)
        need = MAX_GRACE_PARTITIONS
    return GracePlan(path=path, agg=agg, root=node, leaves=leaves,
                     n_parts=int(need))


def _scheme_valid(root: L.LogicalPlan, leaf_ids: dict,
                  part_cols: dict) -> bool:
    """Compositional co-location check for a candidate partition assignment.

    Per subtree we compute (valid, free, anchors): `free` = the subtree has no
    partitioned leaf (its tuples appear in EVERY partition); otherwise
    `anchors` = output columns whose value v satisfies "tuple t of this
    subtree exists in partition p iff p == hash(v) % P".  Leaves partitioned
    by k anchor {k}; inner joins propagate anchors and close them over their
    equi pairs, requiring a linking pair when BOTH sides are anchored (else
    joined rows could land in different buckets and the per-partition union
    would lose tuples).  SEMI/ANTI scope the analysis: witnesses live only in
    the bucket of the join key, so a partitioned build side demands a key
    pair whose probe column is anchored (ANTI additionally forbids a free
    probe side — a replicated probe row would spuriously survive in every
    bucket its witnesses are NOT in).  A False here rejects the class; the
    planner falls back to the next-best class or the normal path."""
    def pairs_of(j: L.Join):
        out = []
        for lk, rk in zip(j.left_keys, j.right_keys):
            if isinstance(lk, E.Column) and lk.index is not None and \
                    isinstance(rk, E.Column) and rk.index is not None:
                out.append((lk.index, rk.index))
        return out

    def rec(nd):
        if id(nd) in leaf_ids:
            col = part_cols.get(id(nd))
            if col is None:
                return True, True, set()
            return True, False, {col}
        if isinstance(nd, L.Filter):
            return rec(nd.input)
        j = nd
        vl, fl, al = rec(j.left)
        vr, fr, ar = rec(j.right)
        if not (vl and vr):
            return False, True, set()
        pairs = pairs_of(j)
        if j.join_type is JoinType.INNER:
            if not fl and not fr and \
                    not any(li in al and ri in ar for li, ri in pairs):
                return False, True, set()
            nl = len(j.left.schema)
            comb = set(al if not fl else ()) | \
                {nl + c for c in (ar if not fr else ())}
            changed = True
            while changed:
                changed = False
                for li, ri in pairs:
                    if li in comb and nl + ri not in comb:
                        comb.add(nl + ri)
                        changed = True
                    if nl + ri in comb and li not in comb:
                        comb.add(li)
                        changed = True
            return True, fl and fr, comb
        # SEMI / ANTI: output = probe (left) side only
        if fr:
            return True, fl, al
        links = {li for li, ri in pairs if ri in ar}
        if not links:
            return False, True, set()
        if not fl:
            if not (links & al):
                return False, True, set()
            return True, False, al
        if j.join_type is JoinType.ANTI:
            # free probe + partitioned build: a probe row would survive in
            # every bucket except its witnesses' — unsound
            return False, True, set()
        # SEMI with free probe: a probe row's witnesses all live in
        # hash(link key), so it is emitted exactly once, anchored by that key
        return True, False, set(links)

    valid, _, _ = rec(root)
    return valid


# --- host-side partition hashing -------------------------------------------


def _hash_rows(tbl: pa.Table, name: str) -> np.ndarray:
    """uint64 hash lane of one key column, host-side. Strings hash their
    dictionary bytes once (native hash64.c fast path) and gather per row, so
    the per-row cost is one int32 take regardless of string length."""
    import pyarrow.compute as pc
    from igloo_tpu.exec.batch import hash64_bytes
    col = tbl.column(name)
    col = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    typ = col.type
    if pa.types.is_dictionary(typ) or pa.types.is_string(typ) or \
            pa.types.is_large_string(typ):
        if not pa.types.is_dictionary(typ):
            col = col.dictionary_encode()
        dvals = np.asarray(col.dictionary.to_numpy(zero_copy_only=False),
                           dtype=object)
        ids = np.asarray(pc.fill_null(col.indices, 0)).astype(np.int64)
        if len(dvals) == 0:
            vals = np.zeros(len(col), dtype=np.uint64)
        else:
            vals = hash64_bytes(dvals, seed=0)[ids]
    else:
        if pa.types.is_date32(typ):
            col = col.cast(pa.int32())  # date32 -> int64 is not a supported
            # arrow cast; go through the day count
        vals = np.asarray(col.cast(pa.int64()).fill_null(0)).astype(np.uint64)
    h = vals * _GOLDEN
    return h ^ (h >> np.uint64(29))


def _split_by_hash(tbl: pa.Table, name: str, n_parts: int,
                   buckets: list) -> None:
    """Append `tbl`'s rows to `buckets` by key hash: ONE stable argsort of the
    partition ids + boundary slices instead of P full-table filters."""
    pid = (_hash_rows(tbl, name) % np.uint64(n_parts)).astype(np.int64)
    order = np.argsort(pid, kind="stable")
    sorted_tbl = tbl.take(order)
    counts = np.bincount(pid, minlength=n_parts)
    off = 0
    for p in range(n_parts):
        c = int(counts[p])
        if c:
            buckets[p].append(sorted_tbl.slice(off, c))
        off += c


# unique snapshot tokens for grace-created providers: the scan cache's
# fallback snapshot used to be a bare id(provider), and the partition loop
# allocates/frees one provider per partition — CPython happily REUSES a
# freed provider's id, which made the cache serve partition p-1's columns as
# partition p's. Tokens come from a monotonic counter; the PREFETCH thread
# builds _PartitionTables (each drawing a token) concurrently with
# main-thread provider stamping, so the counter bump is lock-guarded instead
# of leaning on itertools.count()'s accidental GIL atomicity.
#
# lock discipline (checked by igloo-lint lock-discipline):
_GUARDED_BY = {"_snap_lock": ("_snap_ids",)}
_snap_lock = threading.Lock()
_snap_ids = 0


def _fresh_snapshot() -> str:
    global _snap_ids
    with _snap_lock:
        _snap_ids += 1
        return f"__grace_snap_{_snap_ids}"


def _stamp_snapshot(provider) -> object:
    tok = _fresh_snapshot()
    provider.snapshot = lambda _tok=tok: _tok
    return provider


class _PartitionTable:
    """Bucket provider: a MemTable that may carry a prebuilt DeviceBatch
    (uploaded by the prefetch thread; Executor._scan_batch returns it
    directly) and union value bounds pinned across all partitions."""

    stable_row_order = True

    def __init__(self, table: pa.Table):
        from igloo_tpu.exec.batch import schema_from_arrow
        self._table = table
        self._schema = schema_from_arrow(table.schema)
        self.prebuilt_batch = None
        self.fixed_bounds: Optional[dict] = None
        self._snap = _fresh_snapshot()

    def snapshot(self) -> str:
        return self._snap

    def __deepcopy__(self, memo):
        return self

    def schema(self):
        return self._schema

    def read(self, projection=None, filters=None) -> pa.Table:
        t = self._table
        if projection is not None:
            t = t.select(projection)
        return t

    def num_partitions(self) -> int:
        return 1

    def read_partition(self, index, projection=None, filters=None):
        return self.read(projection=projection, filters=filters)

    def estimated_bytes(self) -> int:
        return self._table.nbytes


class GraceJoinExecutor:
    """Executes a qualifying plan partition at a time (see module doc)."""

    def __init__(self, catalog, jit_cache=None, use_jit: bool = True,
                 batch_cache=None, hints=None,
                 budget_bytes: int = 2 << 30):
        self.catalog = catalog
        self._jit_cache = jit_cache if jit_cache is not None else {}
        self._use_jit = use_jit
        self._batch_cache = batch_cache
        self._hints = hints
        self.budget_bytes = budget_bytes
        self._exec = None  # ONE Executor reused across partitions and phases

    def _executor(self):
        if self._exec is None:
            from igloo_tpu.exec.executor import Executor
            self._exec = Executor(self._jit_cache, use_jit=self._use_jit,
                                  batch_cache=self._batch_cache,
                                  hints=self._hints)
        return self._exec

    # --- entry --------------------------------------------------------------

    def execute_to_arrow(self, plan: L.LogicalPlan, found: GracePlan,
                         depth: int = 0) -> pa.Table:
        with stats.op("GraceJoin", partitions=found.n_parts,
                      depth=depth) as gnode:
            return self._execute(plan, found, depth, gnode)

    def _execute(self, plan: L.LogicalPlan, found: GracePlan,
                 depth: int, gnode) -> pa.Table:
        from igloo_tpu.catalog import MemTable
        from igloo_tpu.plan.aggsplit import (
            decompose_aggregate, final_merge_plan, partial_aggregate_node,
        )
        gp = found
        tracing.counter("grace.join")
        tracing.counter("grace.partitions", gp.n_parts)
        if depth:
            tracing.counter("grace.recursive")
        used_names: list[str] = []
        try:
            # --- phase 1: partition / replicate the leaves -------------------
            t0 = time.perf_counter()
            parted: dict[int, list[pa.Table]] = {}
            rep_prov: dict[int, object] = {}
            with stats.op("GracePhase(partition)"):
                for leaf in gp.leaves:
                    if leaf.key_col is not None:
                        parted[leaf.index] = self._partition_leaf(
                            leaf, gp.n_parts, depth)
                        used_names.append(f"__grace_p{leaf.index}")
                    else:
                        tbl = self._leaf_to_arrow(leaf.node, depth)
                        # sliceable provider partitions so a RECURSIVE grace
                        # level can stream this table instead of
                        # device-reading it whole
                        parts = max(
                            1, -(-tbl.nbytes // max(self.budget_bytes, 1)))
                        rep_prov[leaf.index] = _stamp_snapshot(
                            MemTable(tbl, partitions=parts))
                        used_names.append(f"__grace_rep{leaf.index}")
            tracing.counter("grace.partition_ms",
                            int(1000 * (time.perf_counter() - t0)))

            # a replicated over-budget leaf means this level cannot bound its
            # memory — partitions re-enter GRACE (recursion), so skip the
            # prebuilt device uploads their plans would never use
            recursive_mode = depth + 1 < MAX_GRACE_DEPTH and any(
                leaf.key_col is None and leaf.over for leaf in gp.leaves)

            # recursive mode skips the prebuilt uploads, so only the union
            # bounds (consumed via fixed_bounds) are worth computing — the
            # union dictionaries / null scans / shared capacity would be
            # discarded by prepare()
            if recursive_mode:
                meta = {i: (self._union_bounds(
                            self._leaf_of(gp, i).node.schema, parted[i]),
                            {}, 0, set())
                        for i in parted}
            else:
                meta = {i: self._leaf_meta(self._leaf_of(gp, i), parted[i])
                        for i in parted}

            # partitions that cannot produce rows (an empty co-partitioned
            # bucket on any inner/semi-reachable leaf) are skipped outright
            killing = [leaf.index for leaf in gp.leaves
                       if leaf.key_col is not None and leaf.kills]
            run_ps = [p for p in range(gp.n_parts)
                      if all(parted[i][p].num_rows > 0 for i in killing)]
            if any(leaf.key_col is None and leaf.kills and
                   rep_prov[leaf.index].read().num_rows == 0
                   for leaf in gp.leaves):
                run_ps = []

            below: list[L.LogicalPlan] = []
            if gp.agg is not None:
                i = gp.path.index(gp.agg)
                below = gp.path[i + 1:]
                partial_schema, partial_aggs, partial_names, final_spec = \
                    decompose_aggregate(gp.agg)

            def prepare(p: int) -> dict:
                provs = {}
                for i in parted:
                    # widen THIS bucket only (the others stay in carrier
                    # form); from_arrow then re-narrows at the device edge
                    tbl = encoded.decode_table(parted[i][p])
                    prov = _PartitionTable(tbl)
                    bounds, udicts, cap, nullf = meta[i]
                    prov.fixed_bounds = bounds
                    if not recursive_mode:
                        from igloo_tpu.exec.batch import from_arrow
                        prov.prebuilt_batch = from_arrow(
                            tbl,
                            schema=self._leaf_of(gp, i).node.schema,
                            capacity=cap, dictionaries=udicts or None,
                            null_fields=nullf or None)
                    provs[i] = prov
                return provs

            def build_sub(provs: dict) -> L.LogicalPlan:
                repl = {}
                for leaf in gp.leaves:
                    prov = provs[leaf.index] if leaf.key_col is not None \
                        else rep_prov[leaf.index]
                    name = (f"__grace_p{leaf.index}"
                            if leaf.key_col is not None
                            else f"__grace_rep{leaf.index}")
                    repl[id(leaf.node)] = _mem_scan(name, prov,
                                                    leaf.node.schema)
                sub = _replace_leaves(gp.root, repl)
                for nd in reversed(below):
                    sub = _rewire(nd, sub)
                if gp.agg is not None:
                    sub = partial_aggregate_node(gp.agg, sub, partial_schema,
                                                 partial_aggs, partial_names)
                return sub

            # --- phase 2: the (double-buffered) partition loop ---------------
            t0 = time.perf_counter()
            pipeline = os.environ.get("IGLOO_GRACE_PIPELINE", "1") != "0" \
                and not recursive_mode and len(run_ps) > 1
            partials: list[pa.Table] = []
            part_rows: list[int] = []
            part_wall: list[float] = []

            def run_partition(k: int, p: int, provs: dict) -> None:
                """One partition's plan on device; rows (host Arrow — free)
                and wall feed the per-partition rollup. The first few
                partitions keep full operator subtrees under EXPLAIN
                ANALYZE; the rest are recorded quiet (rollup only). The
                first _SPAN_PARTITIONS land as `grace.partition` timeline
                spans — on the Perfetto view they visibly overlap the
                prefetch thread's `grace.prefetch` spans, which is the
                double-buffer's win made observable."""
                tp = time.perf_counter()
                keep = stats.detail_active() and k < DETAIL_PARTITIONS
                cm = stats.op(f"Partition[{p}]") if keep else stats.quiet()
                span_cm = tracing.span("grace.partition", partition=p) \
                    if k < _SPAN_PARTITIONS else contextlib.nullcontext()
                with span_cm, cm:
                    tbl = self._leaf_routed(build_sub(provs), depth)
                    if keep:
                        stats.set_rows(tbl.num_rows)
                partials.append(tbl)
                part_rows.append(tbl.num_rows)
                part_wall.append(time.perf_counter() - tp)

            with stats.op("GracePhase(join)"):
                if pipeline:
                    tracing.counter("grace.pipeline")
                    from concurrent.futures import ThreadPoolExecutor
                    # the prefetch thread adopts this query's stats context
                    # so its uploads/counters land in the right deltas
                    sctx = stats.capture()

                    def prepare_traced(k: int, p: int) -> dict:
                        # the adopted trace context puts the prefetch span
                        # in the SAME query trace as the compute spans it
                        # overlaps. Gated on the execution ORDINAL k, same
                        # as grace.partition — skipped-empty-partition runs
                        # have sparse partition IDs, and gating the two
                        # halves differently would trace compute without
                        # its overlapping prefetch
                        with stats.adopt(sctx):
                            span_cm = tracing.span("grace.prefetch",
                                                   partition=p) \
                                if k < _SPAN_PARTITIONS \
                                else contextlib.nullcontext()
                            with span_cm:
                                return prepare(p)

                    with ThreadPoolExecutor(max_workers=1) as pool:
                        fut = pool.submit(prepare_traced, 0, run_ps[0])
                        for k, p in enumerate(run_ps):
                            provs = fut.result()
                            if k + 1 < len(run_ps):
                                fut = pool.submit(prepare_traced, k + 1,
                                                  run_ps[k + 1])
                            run_partition(k, p, provs)
                else:
                    for k, p in enumerate(run_ps):
                        run_partition(k, p, prepare(p))
            tracing.counter("grace.join_ms",
                            int(1000 * (time.perf_counter() - t0)))
            if gnode is not None:
                gnode.attrs.update(
                    partitions_run=len(run_ps),
                    partitions_skipped=gp.n_parts - len(run_ps),
                    pipeline=bool(pipeline))
                if part_rows:
                    gnode.attrs["partition_rows"] = (
                        f"min={min(part_rows)}/"
                        f"avg={sum(part_rows) // len(part_rows)}/"
                        f"max={max(part_rows)}")
                    gnode.attrs["partition_ms"] = (
                        f"min={1e3 * min(part_wall):.1f}/"
                        f"avg={1e3 * sum(part_wall) / len(part_wall):.1f}/"
                        f"max={1e3 * max(part_wall):.1f}")

            # --- merge -------------------------------------------------------
            t0 = time.perf_counter()
            with stats.op("GracePhase(merge)"):
                if gp.agg is not None:
                    merged_tbl = pa.concat_tables(partials) if partials else \
                        partial_schema_empty(partial_schema)
                    merged_scan = _mem_scan(
                        "__grace_partials",
                        _stamp_snapshot(MemTable(merged_tbl)),
                        partial_schema)
                    top = final_merge_plan(gp.agg, merged_scan, final_spec)
                    upper = gp.path[: gp.path.index(gp.agg)]
                    used_names.append("__grace_partials")
                else:
                    out_tbl = pa.concat_tables(partials) if partials else \
                        tbl_empty_like(gp.root.schema)
                    top = _mem_scan("__grace_joined",
                                    _stamp_snapshot(MemTable(out_tbl)),
                                    gp.root.schema)
                    upper = gp.path
                    used_names.append("__grace_joined")
                for nd in reversed(upper):
                    top = _rewire(nd, top)
                out = self._executor().execute_to_arrow(top)
                stats.set_rows(out.num_rows)
            tracing.counter("grace.merge_ms",
                            int(1000 * (time.perf_counter() - t0)))
            return out
        finally:
            # free the HBM the loop's same-name scan-cache slots still pin
            if self._batch_cache is not None:
                for name in used_names:
                    self._batch_cache.invalidate_table(name.lower())

    @staticmethod
    def _leaf_of(gp: GracePlan, index: int) -> GraceLeaf:
        return gp.leaves[index]

    # --- phase 1 -------------------------------------------------------------

    def _partition_leaf(self, leaf: GraceLeaf, n_parts: int,
                        depth: int) -> list[pa.Table]:
        """Stream the leaf through the device executor and split its output
        rows into co-partition buckets by the class-key hash."""
        key_name = leaf.node.schema.fields[leaf.key_col].name
        buckets: list[list[pa.Table]] = [[] for _ in range(n_parts)]
        for tbl in self._leaf_chunks(leaf.node, depth):
            if tbl.num_rows:
                _split_by_hash(tbl, key_name, n_parts, buckets)
        # partition buffers are the long-lived host state of the whole loop:
        # hold them in carrier form (exec/encoded.py; numerics only — string
        # buckets must stay plain so _union_dicts sees the raw values).
        # prepare() widens one bucket at a time, right before upload.
        # Per-bucket specs are safe here: buckets are never co-hashed again
        out = [encoded.encode_table(
                   pa.concat_tables(b) if b else
                   tbl_empty_like(leaf.node.schema))
               for b in buckets]
        tracing.counter("grace.partition_bytes", sum(t.nbytes for t in out))
        return out

    def _leaf_chunks(self, node: L.LogicalPlan, depth: int):
        """Yield the leaf's output host-side without ever materializing more
        than one provider partition on device: plain scan chains stride the
        provider's partitions; complex subtrees route through the chunked
        tier / recursive GRACE / plain executor."""
        from igloo_tpu.cluster.fragment import _subtree_scan
        sc = _subtree_scan(node)
        np_ = 1
        if sc is not None and sc.provider is not None and sc.partition is None:
            try:
                np_ = sc.provider.num_partitions()
            except Exception:
                np_ = 1
        if sc is not None and sc.provider is not None and \
                sc.partition is None and np_ > 1:
            from igloo_tpu.cluster.fragment import _with_partition
            from igloo_tpu.storage import prefetch as _prefetch
            # feed the partition stride through the storage prefetcher: the
            # reader thread decodes row group i+1 while partition i's plan
            # runs on device (docs/storage.md#prefetch) — the cold-scan half
            # of the double-buffer this loop feeds
            items = [(sc.provider, i, sc.projection, sc.pushed_filters)
                     for i in range(np_)]
            with _prefetch.scan_prefetch(items):
                for i in range(np_):
                    yield self._executor().execute_to_arrow(
                        _with_partition(node, (i,)))
            return
        yield self._leaf_routed(node, depth)

    def _leaf_routed(self, node: L.LogicalPlan, depth: int) -> pa.Table:
        """Execute a whole subtree (a complex leaf, or one partition's plan)
        with the engine's memory ladder: chunked tier for decomposable
        aggregates, recursive GRACE when the subtree is still over budget
        (e.g. a replicated leaf bigger than the budget), plain executor
        otherwise."""
        from igloo_tpu.exec.chunked import LocalChunkExecutor, chunk_count
        chunks = chunk_count(node, self.budget_bytes)
        if chunks:
            return LocalChunkExecutor(
                self.catalog, self._jit_cache, use_jit=self._use_jit,
                batch_cache=self._batch_cache,
                chunks=chunks).execute_to_arrow(node)
        if depth + 1 < MAX_GRACE_DEPTH:
            found = find_grace_join(node, self.budget_bytes)
            if found is not None:
                return self.execute_to_arrow(node, found, depth + 1)
        return self._executor().execute_to_arrow(node)

    def _leaf_to_arrow(self, node: L.LogicalPlan, depth: int) -> pa.Table:
        ts = list(self._leaf_chunks(node, depth))
        return ts[0] if len(ts) == 1 else pa.concat_tables(ts)

    # --- shared per-leaf metadata (one compiled program per stage) -----------

    def _leaf_meta(self, leaf: GraceLeaf, tables: list):
        """(union bounds, union dictionaries, shared capacity, union null
        columns) over ALL buckets of one leaf: every partition presents
        IDENTICAL static metadata to the executor, keeping ONE compiled
        program per stage (per-bucket exact values would fork the jit/fused
        caches P ways — bounds feed join-strategy constants and packed-key
        radices, dictionary/capacity/null-lane shapes feed the pool and batch
        prototypes)."""
        schema = leaf.node.schema
        bounds = self._union_bounds(schema, tables)
        udicts = _union_dicts(schema, tables)
        from igloo_tpu.exec.batch import round_capacity
        cap = round_capacity(max((t.num_rows for t in tables), default=1) or 1)
        nullf = {f.name for f in schema
                 if any(t.num_rows and t.column(f.name).null_count
                        for t in tables)}
        return bounds, udicts, cap, nullf

    @staticmethod
    def _union_bounds(schema: T.Schema, tables: list) -> dict:
        """Per-column (lo, hi) over ALL partitions of one leaf, for integer-
        family columns (a superset range is always safe for the consumers:
        direct-join table sizing, packed-key radices — and hash partitioning
        spreads each key over its full global range anyway)."""
        out: dict = {}
        for f in schema:
            if not (f.dtype.is_integer or f.dtype.is_temporal):
                continue
            lo = hi = None
            for t in tables:
                # min_max consumes the ChunkedArray directly — no
                # combine_chunks/cast copies in the path that exists because
                # host memory is already tight. column_min_max reads LOGICAL
                # bounds off encoded buckets without widening them (the
                # carrier min/max plus the field's recorded offset) and
                # yields temporal lane integers (days / microseconds)
                mm = encoded.column_min_max(t, f.name)
                if mm is None:
                    continue
                mn, mx = mm
                lo = mn if lo is None else min(lo, mn)
                hi = mx if hi is None else max(hi, mx)
            if lo is not None:
                out[f.name] = (int(lo), int(hi))
        return out


def _union_dicts(schema: T.Schema, tables: list) -> dict:
    """One shared (sorted) dictionary per string column across ALL buckets of
    a leaf, so every partition's ids gather through identically-shaped hash
    lanes and the compile caches see one dictionary fingerprint."""
    from igloo_tpu.exec.batch import DictInfo
    out: dict = {}
    for f in schema:
        if not f.dtype.is_string:
            continue
        vals: set = set()
        for t in tables:
            if t.num_rows == 0:
                continue
            c = t.column(f.name)
            c = c.combine_chunks() if isinstance(c, pa.ChunkedArray) else c
            if not pa.types.is_dictionary(c.type):
                c = c.dictionary_encode()
            dv = c.dictionary.to_numpy(zero_copy_only=False)
            vals.update(v for v in dv if v is not None)
        out[f.name] = DictInfo.from_values(
            np.asarray(sorted(vals), dtype=object))
    return out


# --- plan surgery -----------------------------------------------------------


def _replace_leaves(node: L.LogicalPlan, repl: dict) -> L.LogicalPlan:
    """Shallow-rebuild the join tree with leaves swapped for bucket scans.
    Interior joins and transparent filters are copy.copy'd (keys/predicates
    stay SHARED across partitions, so scalar-subquery memos resolve once)."""
    import copy as _copy
    r = repl.get(id(node))
    if r is not None:
        return r
    n = _copy.copy(node)
    if isinstance(n, L.Filter):
        n.input = _replace_leaves(node.input, repl)
        return n
    assert isinstance(n, L.Join)
    n.left = _replace_leaves(node.left, repl)
    n.right = _replace_leaves(node.right, repl)
    return n


def _mem_scan(name: str, provider, schema: T.Schema) -> L.Scan:
    s = L.Scan(table=name, provider=provider)
    s.schema = schema
    return s


def _rewire(node: L.LogicalPlan, new_input: L.LogicalPlan) -> L.LogicalPlan:
    n = L.copy_plan(node)
    n.input = new_input
    return n


def tbl_empty_like(schema: T.Schema) -> pa.Table:
    from igloo_tpu.exec.batch import dtype_to_arrow
    arrays = [pa.array([], type=dtype_to_arrow(f.dtype)) for f in schema]
    return pa.Table.from_arrays(arrays, names=schema.names)


def partial_schema_empty(schema: T.Schema) -> pa.Table:
    return tbl_empty_like(schema)
