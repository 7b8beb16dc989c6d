"""Canonical shape families: the engine-wide capacity policy.

Every static-shaped buffer in the engine (scan batches, intermediate
compactions, aggregate output segments, exchange buckets, direct-join
positional tables) is padded to a *canonical capacity* so that XLA programs
are keyed by a SMALL family of shapes instead of one shape per cardinality.
A process without its compile cache answers its first q3 after four minutes
and every later one in a fraction of a second (PERF_LEDGER.jsonl,
`first_setup_s` against `setup_s`) — for ad-hoc traffic, compilation IS the
latency, so the shape family is sized for program reuse first and padding
waste second:

- **small band** (n <= 2^16): exact power-of-two buckets. Programs here
  compile in well under a second, and tight padding matters more than
  sharing (an 8-row dimension table must not become a 32-row one).
- **coarse band** (2^16 < n <= 2^22): members every OTHER power of two
  (2^18, 2^20, 2^22 — geometric ratio 4). This is the ad-hoc sweet spot:
  a query shape at scale factor s and at 2s quantizes to the SAME member,
  so e.g. TPC-H q3 at SF0.02 and SF0.04 lower to one XLA program. Padding
  cost is bounded 4x on buffers of at most 32 MB/lane-column.
- **large band** (n > 2^22): power-of-two again. At HBM scale a 4x pad is
  an OOM, not a tax — and the out-of-core tiers (GRACE/chunked) already
  pin their partition capacities to shared program shapes.

**Hysteresis.** Above the small band, the row count is padded by 1/32
(~3%) before quantizing: a cardinality sitting just under a family boundary
rounds UP, so day-to-day drift across the boundary (inserts, scale-factor
nudges) cannot flip-flop a table between two members and double-compile
every downstream program.

`IGLOO_TPU_SHAPE_FAMILY=pow2` restores plain power-of-two bucketing
everywhere (A/B knob; `coarse` — or unset — selects the family above).

`exec/batch.round_capacity` delegates here, so every existing call site
(scans, compacts, match capacities, segment counts, shuffle buckets)
inherits the policy without local changes.
"""
from __future__ import annotations

import os

MIN_CAPACITY = 8

# upper edge of the exact-pow2 small band
COARSE_FLOOR = 1 << 16
# coarse members every STEP powers of two up to COARSE_CEIL, pow2 above
COARSE_STEP = 2
COARSE_CEIL = 1 << 22

# hysteresis headroom above the small band: n is padded by n >> 3%-ish
# (1/32) before quantizing, so near-boundary cardinalities round up once
# instead of flip-flopping across the boundary as data drifts
_HEADROOM_SHIFT = 5


def family_mode() -> str:
    """'coarse' (default) or 'pow2' (IGLOO_TPU_SHAPE_FAMILY knob)."""
    raw = os.environ.get("IGLOO_TPU_SHAPE_FAMILY", "coarse").strip().lower()
    return "pow2" if raw == "pow2" else "coarse"


def _pow2(n: int) -> int:
    c = MIN_CAPACITY
    while c < n:
        c <<= 1
    return c


def _is_member(n: int) -> bool:
    """True when n is already a family member (coarse mode)."""
    if n < MIN_CAPACITY or n & (n - 1):
        return False
    if n <= COARSE_FLOOR or n > COARSE_CEIL:
        return True
    # coarse band: every COARSE_STEP-th power of two above the floor
    return (n.bit_length() - COARSE_FLOOR.bit_length()) % COARSE_STEP == 0


def canonical_capacity(n: int) -> int:
    """Smallest family member >= n (with hysteresis headroom above the
    small band). This is THE quantization every padded lane goes through.
    IDEMPOTENT: a value that is already a member maps to itself — call
    sites routinely re-round existing capacities (spec_cap, GRACE partition
    caps), and headroom there would inflate a full family step per pass."""
    if n <= COARSE_FLOOR or family_mode() == "pow2":
        return _pow2(n)
    if _is_member(n):
        return n
    n_eff = n + (n >> _HEADROOM_SHIFT)
    if n_eff > COARSE_CEIL:
        return _pow2(n_eff)
    c = COARSE_FLOOR
    step = COARSE_STEP
    while c < n_eff:
        c <<= step
    return c


def capacity_family(limit: int) -> list:
    """The family members up to `limit` (docs/tests; not a hot path).
    Mirrors canonical_capacity: pow2 through COARSE_FLOOR, then
    COARSE_FLOOR << 2k coarse members through COARSE_CEIL, pow2 above."""
    out = []
    c = MIN_CAPACITY
    while c <= min(limit, COARSE_FLOOR):
        out.append(c)
        c <<= 1
    if family_mode() == "pow2":
        while c <= limit:
            out.append(c)
            c <<= 1
        return out
    c = COARSE_FLOOR << COARSE_STEP
    while c <= min(limit, COARSE_CEIL):
        out.append(c)
        c <<= COARSE_STEP
    c = COARSE_CEIL << 1
    while c <= limit:
        out.append(c)
        c <<= 1
    return out


def canonical_direct_table(lo: int, hi: int) -> tuple:
    """Canonical (base, table_size) for a direct-join positional table over
    key bounds [lo, hi]. The raw bounds are data-dependent constants; baking
    them into a compiled program (and its cache key) would give every scale
    factor its own join program. Instead the table size is quantized to the
    capacity family (with a 4/3 margin so the base can grid-align) and the
    base is floor-aligned to a quarter-table grid: nearby bounds — e.g. TPC-H
    orderkey ranges at neighboring scale factors — share one (base, size)
    pair and therefore one compiled join. Guarantees base <= lo and
    base + table_size > hi, so every key in [lo, hi] still lands in-table;
    the extra slots stay empty (-1) and can never match a probe."""
    rng = int(hi) - int(lo) + 1
    tcap = canonical_capacity((rng * 4 + 2) // 3)
    grid = max(tcap // 4, 1)
    base = (int(lo) // grid) * grid
    return base, tcap
