"""Pallas kernel dispatch: the ONE gateway to ``exec/pallas_kernels.py``.

Every adoption site (join probe, sort-tier aggregation, batch gathers)
consults this module at PLAN/TRACE time — a host-side static decision that
callers fold into their jit cache keys (``cache_token()`` rides every
``Executor._jitted`` key and the fused program key, per-kernel plans ride
the per-op fingerprints) — and routes through the ``probe_bounds`` /
``segagg`` / ``gather_columns`` wrappers below, which are the only legal
callers of ``pallas_kernels`` (igloo-lint ``pallas-dispatch`` rule: the
flag and the fallback ladder must not be bypassable).

Knob: ``IGLOO_TPU_PALLAS``
  - ``auto`` (default)  on TPU backends only, and there only the kernels
                        the chip's compiler accepts
                        (``TPU_COMPILED_KERNELS`` — empty today, so ``auto``
                        on a TPU plans exactly what ``0`` plans);
  - ``0``               kernels off everywhere — reproduces the sort-path
                        plans and results bit-identically;
  - ``1``               every kernel on; on non-TPU backends this implies
                        the Pallas interpreter (a compiled Pallas call needs
                        Mosaic/TPU). On a TPU the kernels are compiled and a
                        compile failure RAISES — forcing them is how a
                        kernel outside the table is worked on;
  - ``interpret``       kernels on through the Pallas interpreter on any
                        backend — the CPU equivalence mode tier-1 uses.

Fallback ladder (each rung attributable): mode off / non-TPU auto -> sort
path silently; ``auto`` on a TPU and the kernel is not in
``TPU_COMPILED_KERNELS`` -> sort path + ``pallas.fallback.not_compiled``;
eligibility miss or an earlier failure's negative cache ->
sort path + ``pallas.fallback.<reason>``; COMPILE failure (a program the
backend cannot lower) -> caught at the executor's call sites, negative
cache + sort-path re-run (``pallas.compile_fallback``; never under
``IGLOO_TPU_PALLAS=1`` on a TPU, see ``compile_failure_raises``); runtime overflow
(probe window / agg table) -> deferred flag -> sort-path re-run +
negative cache (``pallas.probe_overflow`` / ``pallas.agg_overflow``).

Block shapes and table sizes derive from the canonical capacity families
(exec/capacity.py): lane capacities are family members (powers of two), so
``pow2_block`` blocks always divide them and kernel programs are keyed by
the same small shape family as the rest of the engine.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

from igloo_tpu.exec.capacity import canonical_capacity, pow2_block
from igloo_tpu.utils import tracing

#: empty-slot sentinel in the hash-agg key table (canonical definition —
#: the kernels module imports it from here); packed key lanes are
#: mixed-radix digit strings and therefore always >= 0
EMPTY_KEY = np.int64(-1)

# --- kernel-eligibility bounds --------------------------------------------

#: per-probe-row bucket scan window (bounded ragged emission); a build-side
#: duplicate-key run longer than this overflows to the sort path
PROBE_WINDOW = 16
#: probe rows per grid block
PROBE_BLOCK = 1024
#: expected bucket occupancy target: buckets = build_capacity >> this
PROBE_BUCKET_SHIFT = 3
#: widest build side the probe kernel accepts in INTERPRET mode (the sorted
#: hash lane must be kernel-resident); matches the speculative-join budget
PROBE_MAX_BUILD = 1 << 22
#: compiled-mode clamp: the resident int64 hash lane must fit VMEM
#: (~16 MB/core) beside the bucket-starts lane and the probe blocks —
#: 2^20 lanes = 8 MB. A compile failure IS caught (the executor's
#: compile-failure rung), but it costs a wasted compile and permanently
#: bans the op, so the compiled bounds stay conservative.
PROBE_MAX_BUILD_COMPILED = 1 << 20
#: bucket-count clamp (the starts lane is a kernel input)
PROBE_MAX_BUCKETS = 1 << 19

#: the direct-scatter aggregate's "small segment space" bound: at or under
#: this many segments exec/aggregate.py scatters unconditionally; above it
#: the scatter path needs a tight aggregate budget and the Pallas hash-agg
#: table is capped at this many rows — ONE shared constant so the two
#: eligibility checks cannot drift (see aggregate.seg_dims_for)
DIRECT_SEG_SMALL_LIMIT = 1 << 16

#: hash-agg bucket ways (bounded collision resolution, the probe-window twin)
AGG_WAYS = 8
#: input rows per grid block
AGG_BLOCK = 1024
#: compiled-mode table clamp: the key/count/accumulator tables are all
#: VMEM-resident across grid steps — 2^14 rows keeps a many-aggregate
#: table set under ~2 MB (see PROBE_MAX_BUILD_COMPILED's rationale)
AGG_TABLE_ROWS_COMPILED = 1 << 14

#: fused gather: total source bytes the kernel may keep resident
#: (interpret mode; the compiled clamp keeps the residency under VMEM)
GATHER_MAX_BYTES = 1 << 25
GATHER_MAX_BYTES_COMPILED = 1 << 22
GATHER_BLOCK = 1024
#: fusing fewer lanes than this is not worth a kernel launch
GATHER_MIN_COLS = 2

#: match materialization: per-probe-row output window (the probe window's
#: twin — a row's match count is bounded by its probe run, so the same
#: default never overflows when the probe kernel didn't)
MATCH_WINDOW = 16
MATCH_BLOCK = 1024
#: the owner table is match-capacity-resident (int32): interpret / compiled
#: VMEM clamps, PROBE_MAX_BUILD's rationale
MATCH_MAX_CAP = 1 << 22
MATCH_MAX_CAP_COMPILED = 1 << 20

#: blocked partial top-k: per-block selection is k static min/mask rounds,
#: so k stays small (LIMIT + OFFSET; every TPC-H LIMIT is <= 100)
TOPK_MAX_K = 128
TOPK_BLOCK = 1024
TOPK_MAX_ROWS = 1 << 22
TOPK_MAX_ROWS_COMPILED = 1 << 20

#: exchange hash + partition scatter: padded row clamp (lanes are padded to
#: the canonical capacity family so kernel programs stay family-keyed),
#: bucket histogram residency, and the key-column fan-in
SCATTER_BLOCK = 1024
SCATTER_MAX_ROWS = 1 << 22
SCATTER_MAX_ROWS_COMPILED = 1 << 20
SCATTER_MAX_BUCKETS = 1 << 16
SCATTER_MAX_COLS = 8

#: Kernels the TPU's compiler accepts at the shapes planned here — the ONLY
#: kernels ``auto`` plans on a TPU backend. tests/test_tpu_compile.py holds
#: the table to the compiler both ways (a member must compile to a
#: ``tpu_custom_call``, a non-member must still be refused), so a PR that
#: repairs a kernel has to move it in here. Empty: with jax 0.9.0 every one
#: of probe / segagg / gather / scatter / topk is refused while lowering
#: (`_check_block_mappings`: "integer modulo by zero" — the tiling of a 1-D
#: block is 128 * (32 // bitwidth) and the lanes are 64-bit), and match
#: (int32 lanes) dies in a RecursionError lowering its in-kernel int32
#: scatter under x64 (docs/kernels.md "Compiled mode on the chip").
TPU_COMPILED_KERNELS: frozenset = frozenset()


def mode() -> str:
    """Normalized ``IGLOO_TPU_PALLAS``: auto | 0 | 1 | interpret."""
    raw = os.environ.get("IGLOO_TPU_PALLAS", "auto").strip().lower()
    return raw if raw in ("0", "1", "interpret") else "auto"


def _backend() -> str:
    import jax
    return jax.default_backend()


def kernel_state() -> tuple:
    """(enabled, interpret) for the current mode + backend + x64 config.
    The kernels work on int64 hash/key lanes, so a 32-bit-only process
    never enables them."""
    m = mode()
    if m == "0":
        return False, False
    import jax
    if not jax.config.jax_enable_x64:
        return False, False
    if m == "interpret":
        return True, True
    if m == "1":
        return True, _backend() != "tpu"
    return (_backend() == "tpu"), False


def enabled() -> bool:
    return kernel_state()[0]


def _compiles(kernel: str, interp: bool) -> bool:
    """False (and ``pallas.fallback.not_compiled``) when `kernel` would be
    planned COMPILED under ``auto`` but the chip's compiler refuses it. The
    interpreter and a forced ``IGLOO_TPU_PALLAS=1`` are not held to the
    table."""
    if interp or mode() != "auto" or kernel in TPU_COMPILED_KERNELS:
        return True
    _fallback(kernel, "not_compiled")
    return False


def compile_failure_raises() -> bool:
    """True under ``IGLOO_TPU_PALLAS=1`` on a TPU: the user forced compiled
    kernels, so the executor's compile-failure rungs re-raise instead of
    quietly re-running the sort path."""
    return mode() == "1" and _backend() == "tpu"


def cache_token() -> tuple:
    """Rides every jit cache key (Executor._jitted, the fused program key)
    so flipping IGLOO_TPU_PALLAS mid-process can never serve a program
    traced under the other mode. The autotune table version rides along for
    the same reason: adopting new tuned shapes (locally or via cluster
    replication) must re-trace every kernel-bearing program, never serve a
    trace planned under the old shapes."""
    from igloo_tpu.exec import autotune
    return ("pallas",) + kernel_state() + (autotune.table_version(),)


def _tuned(kernel: str, cap: int) -> dict:
    """Autotuned shape overrides for (kernel, canonical capacity) — {} when
    autotuning is off or no winner is persisted (module defaults apply)."""
    from igloo_tpu.exec import autotune
    return autotune.shapes(kernel, cap)


def _fallback(kernel: str, reason: str) -> None:
    tracing.counter(f"pallas.fallback.{reason}")
    return None


# --- per-kernel planners (host-side; results are hashable cache-key parts) -

def plan_probe(build_cap: int, probe_cap: int,
               banned: bool = False) -> Optional[tuple]:
    """Plan the hash-probe kernel for a sorted-probe join, or None for the
    sort path. `build_cap`/`probe_cap` are canonical lane capacities."""
    on, interp = kernel_state()
    if not on:
        return None
    if banned:
        return _fallback("probe", "banned")
    if build_cap > (PROBE_MAX_BUILD if interp else PROBE_MAX_BUILD_COMPILED):
        return _fallback("probe", "too_big")
    if not _compiles("probe", interp):
        return None
    tuned = _tuned("probe", canonical_capacity(build_cap))
    shift = int(tuned.get("bucket_shift", PROBE_BUCKET_SHIFT))
    nbuckets = min(max(canonical_capacity(build_cap) >> shift, 8),
                   PROBE_MAX_BUCKETS)
    block = pow2_block(probe_cap, int(tuned.get("block", PROBE_BLOCK)))
    tracing.counter("pallas.probe")
    return ("probe", nbuckets, int(tuned.get("window", PROBE_WINDOW)),
            block, interp)


def plan_segagg(pack_spec, n_keys: int, input_cap: int,
                banned: bool = False) -> Optional[tuple]:
    """Plan the one-pass hash aggregation for a sort-tier GROUP BY, or None.
    Requires a pack_spec covering EVERY key: the packed lane is then an
    exact (injective) group id, so table-key equality is group equality
    with no verify pass. All AggFunc members are supported."""
    on, interp = kernel_state()
    if not on:
        return None
    if banned:
        return _fallback("segagg", "banned")
    if pack_spec is None or len(pack_spec[1]) != n_keys:
        return _fallback("segagg", "unpackable")
    if not _compiles("segagg", interp):
        return None
    # 8x headroom over the input capacity keeps the per-bucket occupancy
    # low enough that `ways` slots rarely exhaust (overflow falls back)
    tuned = _tuned("segagg", canonical_capacity(input_cap))
    ways = int(tuned.get("ways", AGG_WAYS))
    table = min(canonical_capacity(input_cap) * ways,
                DIRECT_SEG_SMALL_LIMIT if interp
                else AGG_TABLE_ROWS_COMPILED)
    nbuckets = max(table // ways, 8)
    block = pow2_block(input_cap, int(tuned.get("block", AGG_BLOCK)))
    tracing.counter("pallas.segagg")
    return ("segagg", nbuckets, ways, block, interp)


def segagg_table_rows(plan: tuple) -> int:
    """Output capacity of a planned hash aggregation (a family member)."""
    return plan[1] * plan[2]


def _plan_gather(arrays: list, idx) -> Optional[tuple]:
    """Trace-time static decision for a batch gather; silent fallback (no
    counters for ineligibility — gathers are everywhere and most are too
    small or too wide to fuse)."""
    on, interp = kernel_state()
    if not on or len(arrays) < GATHER_MIN_COLS:
        return None
    if idx.ndim != 1 or any(a.ndim != 1 for a in arrays):
        return None
    m = arrays[0].shape[0]
    if any(a.shape[0] != m for a in arrays):
        return None
    n = idx.shape[0]
    block = pow2_block(n, GATHER_BLOCK)
    if n % block:
        return None
    budget = GATHER_MAX_BYTES if interp else GATHER_MAX_BYTES_COMPILED
    if sum(a.size * a.dtype.itemsize for a in arrays) > budget:
        return None
    if not _compiles("gather", interp):
        return None
    tracing.counter("pallas.gather")
    return ("gather", block, interp)


# --- kernel wrappers (jit-traceable; the only pallas_kernels call sites) ---

def probe_bounds(plan: tuple, sorted_hash, probe_hash):
    """(lower, upper, overflow) — ``join._probe_bounds``'s contract over the
    ascending-sorted build hash multiset, plus the deferred overflow flag."""
    from igloo_tpu.exec import pallas_kernels
    _, nbuckets, window, block, interp = plan
    return pallas_kernels.hash_probe_bounds(sorted_hash, probe_hash,
                                            nbuckets, window, block, interp)


def segagg(plan: tuple, packed, live, ops: tuple, op_inputs: list):
    """(key_table, live_counts, per-op tables, overflow) — see
    ``pallas_kernels.hash_segagg``."""
    from igloo_tpu.exec import pallas_kernels
    _, nbuckets, ways, block, interp = plan
    return pallas_kernels.hash_segagg(packed, live, ops, op_inputs,
                                      nbuckets, ways, block, interp)


def gather_columns(arrays: list, idx) -> list:
    """Gather every lane in `arrays` by `idx`: the fused Pallas kernel when
    the mode and shapes allow, one ``jnp.take`` per lane otherwise."""
    plan = _plan_gather(arrays, idx)
    if plan is None:
        import jax.numpy as jnp
        return [jnp.take(a, idx) for a in arrays]
    from igloo_tpu.exec import pallas_kernels
    _, block, interp = plan
    return pallas_kernels.fused_gather(list(arrays), idx, block, interp)


def plan_match(probe_cap: int, match_cap: int,
               banned: bool = False) -> Optional[tuple]:
    """Plan match materialization for ``join.expand_phase``: route "kernel"
    (one blocked Pallas pass, bounded window, deferred overflow) when the
    kernels are on and the shapes fit; route "search" (an exact searchsorted
    inversion of the prefix lane — the algorithmic fast path the non-Pallas
    tier keeps) otherwise. A ban (earlier overflow/compile failure) demotes
    the kernel route to "search", never all the way to the scan."""
    on, interp = kernel_state()
    if on and not banned:
        if match_cap > (MATCH_MAX_CAP if interp else MATCH_MAX_CAP_COMPILED):
            _fallback("match", "too_big")
        elif _compiles("match", interp):
            tuned = _tuned("match", canonical_capacity(match_cap))
            block = pow2_block(probe_cap,
                               int(tuned.get("block", MATCH_BLOCK)))
            tracing.counter("pallas.match")
            return ("match", "kernel",
                    int(tuned.get("window", MATCH_WINDOW)), block, interp)
    elif on and banned:
        _fallback("match", "banned")
    if _backend() == "tpu" and not interp:
        # on real TPU hardware the scatter+cummax scan beats a searchsorted
        # over the match lane (a ~23-pass gather loop — see expand_phase)
        return None
    tracing.counter("join.match_search")
    return ("match", "search")


def plan_topk(cap: int, k: int, full_pack: bool,
              banned: bool = False) -> Optional[tuple]:
    """Plan a partial top-k for LIMIT-over-ORDER-BY, or None for the full
    sort path. Mode-independent: route "alg" (``lax.top_k`` over the packed
    sort key — ties are lowest-index-first, the stable argsort's first-k
    order) is pure XLA and wins on every tier; route "pallas" is the blocked
    kernel. `k` is LIMIT + OFFSET; `full_pack` means the prefix packing
    covers EVERY sort key (a single totally-ordered lane — partial packs
    still need the lexicographic tiebreak sort)."""
    if k <= 0 or cap <= 0:
        return None
    if not full_pack:
        return _fallback("topk", "unpackable")
    if 2 * k > cap:
        # LIMIT covers most of the batch: a partial top-k (and the packed
        # prefix it rides on) buys nothing — take the direct sort path
        return _fallback("topk", "large_limit")
    on, interp = kernel_state()
    if on and not banned and k <= TOPK_MAX_K and \
            cap <= (TOPK_MAX_ROWS if interp else TOPK_MAX_ROWS_COMPILED) \
            and _compiles("topk", interp):
        tuned = _tuned("topk", canonical_capacity(cap))
        block = pow2_block(cap, int(tuned.get("block", TOPK_BLOCK)))
        if k <= block:
            tracing.counter("pallas.topk")
            return ("topk", "pallas", k, block, interp)
    tracing.counter("topk.alg")
    return ("topk", "alg", k)


def plan_scatter(nrows: int, ncols: int, nbuckets: int,
                 banned: bool = False) -> Optional[tuple]:
    """Plan the fused exchange hash + partition scatter, or None for the
    numpy path. `nrows` is the raw table length (lanes are padded to its
    canonical capacity so programs stay family-keyed), `ncols` the key
    fan-in, `nbuckets` the exchange bucket count."""
    on, interp = kernel_state()
    if not on or ncols == 0 or nrows == 0:
        return None
    if banned:
        return _fallback("scatter", "banned")
    npad = canonical_capacity(nrows)
    if ncols > SCATTER_MAX_COLS or nbuckets > SCATTER_MAX_BUCKETS or \
            npad > (SCATTER_MAX_ROWS if interp else SCATTER_MAX_ROWS_COMPILED):
        return _fallback("scatter", "too_big")
    if not _compiles("scatter", interp):
        return None
    tuned = _tuned("scatter", npad)
    block = pow2_block(npad, int(tuned.get("block", SCATTER_BLOCK)))
    tracing.counter("pallas.scatter")
    return ("scatter", npad, nbuckets, block, interp)


def match_table(plan: tuple, prefix, counts, match_cap: int):
    """(owner, overflow) — the slot-ownership table ``join.expand_phase``
    derives by owner-scatter + associative scan, via the Pallas match
    kernel (route "kernel" plans only)."""
    from igloo_tpu.exec import pallas_kernels
    _, _, window, block, interp = plan
    return pallas_kernels.match_owner_table(prefix, counts, match_cap,
                                            window, block, interp)


def topk_perm(plan: tuple, sort_key):
    """Positions of the k smallest packed sort keys, in the full stable
    ascending order's first-k sequence (ties lowest-position-first)."""
    import jax
    import jax.numpy as jnp
    if plan[1] == "alg":
        k = plan[2]
        return jax.lax.top_k(-sort_key, k)[1].astype(jnp.int32)
    from igloo_tpu.exec import pallas_kernels
    _, _, k, block, interp = plan
    ckeys, cpos = pallas_kernels.blocked_topk(sort_key, k, block, interp)
    # candidates are block-major with position-ascending ties inside AND
    # across blocks, so a stable argsort reproduces the global stable order
    order = jnp.argsort(ckeys, stable=True)
    return jnp.take(cpos, order[:k])


def exchange_scatter(plan: tuple, val_lanes: list):
    """(bucket_ids, order, counts) for an exchange partition — numpy arrays
    bit-identical to ``exchange.bucket_ids`` + stable argsort + bincount.
    `val_lanes` are the host-side canonical pre-mix uint64 lanes
    (``exchange._column_vals``); padding to the canonical capacity and the
    final stable sort of the bucket lane happen device-side."""
    import jax.numpy as jnp
    from igloo_tpu.exec import pallas_kernels
    _, npad, nbuckets, block, interp = plan
    n = int(val_lanes[0].shape[0])
    pad = npad - n
    lanes = [jnp.asarray(np.pad(v, (0, pad))) for v in val_lanes]
    live = jnp.arange(npad) < n
    pid_full, counts = pallas_kernels.hash_scatter(lanes, live, nbuckets,
                                                   block, interp)
    pid = pid_full[:n]
    order = jnp.argsort(pid, stable=True)
    return (np.asarray(pid).astype(np.int64), np.asarray(order),
            np.asarray(counts))
