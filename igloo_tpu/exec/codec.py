"""Lossless narrow-transfer codec for the host->HBM boundary.

The engine computes on int64/float64 lanes (SQL semantics), but shipping those
lanes verbatim spends host<->device bytes and HBM on zeros: a 6M-row float64
column is 48 MB uploaded and resident even when every value is a whole number
under 50. (What the upload costs on the current chip: PERF.md.)

This codec picks, per column and on the host, the smallest *provably lossless*
carrier representation and uploads that. Since PR 16 the carrier is also the
RESIDENT representation: the narrow array stays in HBM as
`DeviceColumn.values` with its `WidenSpec` attached, and operators widen
in-jit at the point of use (`batch.wide_values`; XLA fuses the cast/divide
into the consumer) — so HBM footprint, exchange, and spill all pay carrier
bytes, and full lanes exist only transiently inside fused programs and at the
Arrow output boundary (docs/compressed_execution.md). Carriers, tried
narrowest-first:

- integer family (int64/int32/date32/timestamp lanes): offset shrink —
  ``carrier = v - off`` cast to int8/int16/int32 when the value RANGE fits;
  widen = ``carrier.astype(lane) + off``. Exact by construction.
- float lanes: scaled-decimal shrink — ``c = rint(v * scale)`` for scale in
  {1, 100, 10000} when c fits int32 AND ``c / scale == v`` elementwise on the
  host (float64 division, verified value by value); widen =
  ``c.astype(f64) / scale``. TPC-H prices/discounts/taxes are decimals with
  <= 4 fractional digits, so they ride int8/int16/int32 carriers. IEEE-754
  division is deterministic, so the host check guarantees the device result
  bit-for-bit — on a backend whose f64 divide is IEEE-correct, which the
  one-time on-device canary below decides. On the TPU v5e it is NOT (f64 is
  emulated there): the canary fails (`codec.decimal_canary_fail`), the
  divided scales are skipped, and decimal columns ride as f32-round-trip or
  raw f64 lanes (PERF.md, PR 22).
- float64 -> float32 round-trip: when ``v == f32(v)`` exactly (NaN-aware).
- float64 -> f32 pair (PR 37), for float64 lanes no narrower carrier takes:
  ``hi = f32(v)``, ``lo = f32(v - f64(hi))``, split once on the host and
  resident as TWO rank-1 f32 lanes (`DeviceColumn.values` the high half,
  `carrier_arg` the low one); widen = ``hi.astype(f64) + lo.astype(f64)``.
  No byte is saved (8 B a lane either way). What is saved is a pass: a chip
  without float64 (the v5e) computes every f64 value as such a pair, so a
  program that takes an ``f64[N]`` parameter opens with two X64Split
  custom-calls that read the whole lane and write its halves before anything
  else runs, on every execution of a column that never changes; over f32
  parameters the consumer fusion reads the halves directly. It engages only
  where the device's float64 IS that pair, which the second canary below
  decides: yes on the v5e, no on XLA:CPU (real float64: the pair would lose
  five bits), where columns ship as they always did.
- everything else ships as the lane dtype unchanged.

The reference engine has no analog (it streams Arrow RecordBatches in-process,
reference crates/engine/src/operators/parquet_scan.rs:40-85); this boundary
exists only because the TPU sits across an interconnect.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def encoded_enabled() -> bool:
    """Master kill switch for compressed execution (docs/compressed_execution.md).

    `IGLOO_TPU_ENCODED=0` disables EVERY narrowing layer — uploads ship full
    engine lanes, columns are never carrier-resident in HBM, exchange/GRACE
    buffers stay decoded — which is what makes it the bit-identical A/B
    baseline for the byte counters (`codec.*`, `exchange.bytes`, `grace.*`).
    Read per call so tests/smokes can flip it between queries."""
    return os.environ.get("IGLOO_TPU_ENCODED", "1") != "0"


def rle_enabled() -> bool:
    """Run-length transfer carrier for sorted/clustered columns
    (`IGLOO_TPU_RLE=0` to disable; subordinate to IGLOO_TPU_ENCODED)."""
    return encoded_enabled() and os.environ.get("IGLOO_TPU_RLE", "1") != "0"


_I8 = (-(1 << 7), (1 << 7) - 1)
_I16 = (-(1 << 15), (1 << 15) - 1)
_I32 = (-(1 << 31), (1 << 31) - 1)
_INT_STEPS = ((np.int8, _I8), (np.int16, _I16), (np.int32, _I32))


@dataclass(frozen=True)
class WidenSpec:
    """How to reconstruct the engine lane from the carrier, on device.

    lane:   target numpy dtype name ('int64', 'float64', ...)
    offset: integer added after the cast (int paths; 0 for float paths)
    scale:  divisor applied after the cast (float paths; 1 = none)
    pair:   the carrier is the HIGH f32 half of a float64 lane and its low
            half rides beside it, row for row (`lo_arg`)
    """
    lane: str
    offset: int = 0
    scale: float = 1.0
    pair: bool = False

    def widen(self, a: jax.Array, scale_arg=None, offset_arg=None,
              lo_arg=None) -> jax.Array:
        """`scale_arg`/`offset_arg`, when given, must be RUNTIME 0-d arrays
        holding self.scale/self.offset. Scale: baking the divisor in as a
        constant lets XLA rewrite the divide into a multiply by the (inexact)
        reciprocal, which breaks the host-verified exactness for ~13% of
        scaled-decimal values. Offset: it is data-dependent (the column min),
        so baking it in would compile a fresh widen program per distinct min
        (one per chunk in the chunked executor)."""
        lane = jnp.dtype(self.lane)
        if self.pair:
            # on a chip that emulates float64 these two casts ARE the wide
            # value's halves: no X64Split pass in front of the consumer
            return a.astype(lane) + lo_arg.astype(lane)
        if self.scale != 1.0:
            s = (scale_arg.astype(lane) if scale_arg is not None
                 else lane.type(self.scale))
            return a.astype(lane) / s
        if self.offset:
            off = (offset_arg.astype(lane) if offset_arg is not None
                   else lane.type(self.offset))
            return a.astype(lane) + off
        if a.dtype != lane:
            return a.astype(lane)
        return a

    def key(self) -> tuple:
        """Static jit-cache key: everything EXCEPT the data-dependent payload
        values (offset rides in at runtime; only its presence is static). A
        pair keys apart from the f32 round-trip carrier, whose lane dtype it
        shares; every other form keeps the key it had."""
        if self.pair:
            return (self.lane, "f32pair")
        return (self.lane, self.scale != 1.0, self.scale, bool(self.offset))


def narrow_int_dtype(lo: int, hi: int, lane: np.dtype):
    """The carrier `_shrink_int` gives integers of `lane` that span
    [lo, hi]: the narrowest step their RANGE fits, None when none is
    narrower than the lane. What a column's bounds say of its resident
    width before its values are read (exec/chunked.py estimated_lane_bytes)."""
    for nd, (nlo, nhi) in _INT_STEPS:
        nd_ = np.dtype(nd)
        if nd_.itemsize >= lane.itemsize:
            return None
        if hi - lo <= nhi - nlo:
            return nd_
    return None


def _shrink_int(v: np.ndarray, lane: np.dtype):
    """Offset-shrink an integer array; None when it cannot shrink."""
    if v.size == 0:
        return v.astype(np.int8), WidenSpec(lane.name)
    lo, hi = int(v.min()), int(v.max())
    nd = narrow_int_dtype(lo, hi, lane)
    if nd is None:
        return None
    nlo, nhi = np.iinfo(nd).min, np.iinfo(nd).max
    # center the carrier range when an offset is needed at all
    off = 0 if (nlo <= lo and hi <= nhi) else lo - nlo
    return (v - off).astype(nd), WidenSpec(lane.name, offset=off)


_FLOAT_SCALES = (1.0, 100.0, 10000.0)

# one-time on-device canary for the scaled-decimal path: None = not yet run.
# The host verifies ``c / scale == v`` in IEEE f64, but the device replays the
# divide under (possibly emulated) f64 — on a backend whose emulation is not
# IEEE-correct the host check would promise an exactness the device cannot
# deliver. The canary replays representative carriers for EVERY scale through
# the same jitted divide (runtime scale argument, exactly like
# WidenSpec.widen) at first upload; any mismatch disables scaled-decimal
# shrinking process-wide and those columns fall back to wide lanes
# (f32 round-trip or raw f64). Round-5 advisor item.
_decimal_canary_ok: Optional[bool] = None
# two first-uploads on different threads (serving tier) must not both run the
# canary and race the verdict write; compute-once under a lock. Tests may
# still poke `codec._decimal_canary_ok` directly (the read below is lock-free
# once the verdict exists).
_canary_lock = threading.Lock()


def reset_decimal_canary() -> None:
    """Test-visible reset hook: forget the canary verdict so test order (or a
    backend flip under the same process) cannot leak a stale verdict."""
    global _decimal_canary_ok
    with _canary_lock:
        _decimal_canary_ok = None


def _scaled_decimal_ok() -> bool:
    if _decimal_canary_ok is not None:
        return _decimal_canary_ok
    with _canary_lock:
        return _scaled_decimal_ok_locked()


def _scaled_decimal_ok_locked() -> bool:
    global _decimal_canary_ok
    if _decimal_canary_ok is None:
        import jax
        import jax.numpy as jnp
        ok = True
        # carriers spanning the int32 range incl. values whose quotient is
        # inexact in binary (odd cents / odd hundredths of cents)
        c = np.concatenate([
            np.arange(-999, 1000, 7, dtype=np.int64),
            np.asarray([_I32[0], _I32[1], 1, -1, 3, 99, 12345679,
                        987654321, -123456789], dtype=np.int64)])
        try:
            div = jax.jit(lambda a, s: a.astype(jnp.float64) / s)
            for scale in _FLOAT_SCALES:
                host = c.astype(np.float64) / np.float64(scale)
                dev = np.asarray(div(jnp.asarray(c.astype(np.int32)),
                                     jnp.asarray(np.float64(scale))))
                if not np.array_equal(dev, host):
                    ok = False
                    break
        except Exception:
            ok = False
        _decimal_canary_ok = ok
        from igloo_tpu.utils import tracing
        tracing.counter("codec.decimal_canary_ok" if ok
                        else "codec.decimal_canary_fail")
        if not ok:
            tracing.log.warning(
                "codec: on-device scaled-decimal canary FAILED; decimal "
                "columns will ship as wide lanes (f32/f64) instead")
    return _decimal_canary_ok


def _shrink_float(v: np.ndarray, lane: np.dtype):
    """Scaled-decimal or f32 round-trip shrink for a float array."""
    if v.size == 0:
        return v.astype(np.int8), WidenSpec(lane.name)
    finite = np.isfinite(v)
    if finite.all():
        for scale in _FLOAT_SCALES:
            # scale 1.0 widens by pure int->float CAST (no division), so it
            # needs no canary; the divided scales are gated on the device
            # replaying the host-verified divide bit-for-bit
            if scale != 1.0 and not _scaled_decimal_ok():
                continue
            c = np.rint(v * scale)
            if not ((c >= _I32[0]).all() and (c <= _I32[1]).all()):
                continue
            ci = c.astype(np.int64)
            # exact host verification: the device replays this same divide
            if not np.array_equal(ci.astype(lane) / lane.type(scale), v):
                continue
            shrunk = _shrink_int(ci, np.dtype(np.int64))
            if shrunk is not None and shrunk[0].dtype.itemsize < lane.itemsize:
                nv, _ = shrunk
                if shrunk[1].offset == 0:
                    return nv, WidenSpec(lane.name, scale=scale)
            if lane.itemsize > 4:
                return ci.astype(np.int32), WidenSpec(lane.name, scale=scale)
            break
    if lane == np.float64:
        f32 = v.astype(np.float32)
        if np.array_equal(f32.astype(np.float64), v, equal_nan=True):
            return f32, WidenSpec(lane.name)
    return None


def shrink(np_vals: np.ndarray, lane: np.dtype):
    """-> (carrier ndarray, WidenSpec) | None when no narrowing applies.

    `np_vals` must already be in the engine lane dtype (nulls pre-filled with
    0/False so sentinel values cannot break range analysis)."""
    if lane.kind in ("i", "u") and np_vals.dtype == lane:
        return _shrink_int(np_vals, lane)
    if lane.kind == "f" and np_vals.dtype == lane:
        return _shrink_float(np_vals, lane)
    return None


# --- f32-pair carrier --------------------------------------------------------
# one-time on-device canary, in the decimal canary's idiom: None = not yet
# run. A pair is lossless only where the device's float64 already IS the pair
# (hi, lo) of f32 — where every f64 parameter is split into exactly these two
# halves before the first operation reads it. The canary uploads probe values
# once as float64 and once as host-split pairs, runs both through one emulated
# operation with a RUNTIME operand (`x * one`: a constant would be folded and
# the f64 buffer copied out untouched) and compares the results bit for bit.
# On XLA:CPU (IEEE float64) the 53-bit probes differ and the verdict is no.
_f32pair_canary_ok: Optional[bool] = None

#: engage the pair only when the lane is long enough for a split pass to
#: matter (as RLE_MIN_ROWS): under it the chip's two custom-calls move a few
#: kilobytes, while a second array is one more transfer per upload — and a
#: served merge fragment uploads its eight-row dependency table every query
PAIR_MIN_ROWS = 1024

_F32_TINY = np.finfo(np.float32).tiny
_F32_MAX = np.finfo(np.float32).max


def reset_f32pair_canary() -> None:
    """Test-visible reset hook, as `reset_decimal_canary`."""
    global _f32pair_canary_ok
    with _canary_lock:
        _f32pair_canary_ok = None


#: rows a `split_pair` block holds: its five temporaries stay in the host's
#: cache (the split of a 60 M-row column is then ~0.5 s, not ~4)
_SPLIT_BLOCK = 1 << 16


def _split_block(v: np.ndarray, hi: np.ndarray, lo: np.ndarray) -> bool:
    np.copyto(hi, v, casting="same_kind")   # round to nearest even
    rest = hi.astype(np.float64)
    np.subtract(v, rest, out=rest)          # exact: at most 29 significant bits
    np.copyto(lo, rest, casting="same_kind")
    for half in (hi, lo):
        a = np.abs(half)
        # NaN and inf fail the first test, a subnormal the second
        if not a.max() <= _F32_MAX or ((a < _F32_TINY) & (a > 0)).any():
            return False
    return not (hi.view(np.uint32) == 0x80000000).any()


def split_pair(v: np.ndarray):
    """float64 -> (hi, lo) float32 halves with ``f64(hi) + f64(lo)`` the
    value a float64-emulating chip computes on, or None when some value has
    no such pair: non-finite, past the f32 exponent range, a half that is a
    subnormal f32 (the chip flushes those to zero), or a negative zero (its
    halves sum to +0.0)."""
    hi = np.empty(v.shape, dtype=np.float32)
    lo = np.empty(v.shape, dtype=np.float32)
    with np.errstate(over="ignore", invalid="ignore"):
        for s in range(0, len(v), _SPLIT_BLOCK):
            e = s + _SPLIT_BLOCK
            if not _split_block(v[s:e], hi[s:e], lo[s:e]):
                return None
    return hi, lo


def _f32pair_probes() -> np.ndarray:
    rng = np.random.default_rng(37)
    return np.concatenate([
        # two-digit decimals over TPC-H's ranges (prices, discounts, taxes)
        np.round(rng.uniform(900.0, 105000.0, 512), 2),
        np.arange(0, 11) / 100.0,
        -np.round(rng.uniform(0.0, 1000.0, 64), 2),
        # 53 significant bits: what a pair (48) cannot hold, so a backend
        # with a real float64 says no
        1.0 + np.ldexp(1.0, -np.arange(1, 53)),
        rng.uniform(-1e6, 1e6, 512),
        np.asarray([0.0, 1 / 3, -1 / 3, np.pi, -np.e,
                    # ties and near-ties of the high half's rounding
                    1.0 + 2.0 ** -24, 1.0 + 2.0 ** -24 - 2.0 ** -52,
                    1.0 + 3 * 2.0 ** -24, 1.0 + 3 * 2.0 ** -24 - 2.0 ** -50,
                    # near the f32 exponent limits, both halves still normal
                    3e38, -3e38, 1.2345678901234567e-30, 1e30]),
    ])


def _f32pair_ok() -> bool:
    if _f32pair_canary_ok is not None:
        return _f32pair_canary_ok
    with _canary_lock:
        return _f32pair_ok_locked()


def _f32pair_ok_locked() -> bool:
    global _f32pair_canary_ok
    if _f32pair_canary_ok is None:
        try:
            v = _f32pair_probes()
            hi, lo = split_pair(v)
            one = jnp.asarray(np.float64(1.0))
            wide = jax.jit(lambda x, m: x * m)(jnp.asarray(v), one)
            pair = jax.jit(
                lambda h, l, m: WidenSpec("float64", pair=True).widen(
                    h, lo_arg=l) * m)(jnp.asarray(hi), jnp.asarray(lo), one)
            ok = np.array_equal(np.asarray(wide).view(np.uint64),
                                np.asarray(pair).view(np.uint64))
        except Exception:
            ok = False
        _f32pair_canary_ok = ok
        from igloo_tpu.utils import tracing
        tracing.counter("codec.f32pair_canary_ok" if ok
                        else "codec.f32pair_canary_fail")
    return _f32pair_canary_ok


def _pad_to(a: np.ndarray, cap: int) -> np.ndarray:
    if len(a) == cap:
        return a
    out = np.zeros((cap,), dtype=a.dtype)
    out[: len(a)] = a
    return out


# --- run-length transfer carrier --------------------------------------------
# Sorted/clustered columns (l_shipdate-shaped after a clustered read) collapse
# to a handful of runs; shipping (run values, run starts) instead of the full
# carrier lane cuts H2D a further order of magnitude. RLE exists only on the
# wire: the device expands it back to the SCALAR narrow carrier in one jit, so
# downstream filters/segment ops run on the per-row carrier lane and nothing
# else in the engine needs run awareness.

#: engage RLE only when the column is long enough to matter and the run count
#: is a small fraction of the rows (the two shipped arrays must clearly win)
RLE_MIN_ROWS = 1024
RLE_MAX_RUN_FRACTION = 8  # runs <= n // 8


def rle_encode(arr: np.ndarray):
    """-> (run_values, run_starts int32) | None when RLE does not pay.
    `run_starts[0]` is always 0; run k covers rows
    [run_starts[k], run_starts[k+1])."""
    n = len(arr)
    if n < RLE_MIN_ROWS or arr.dtype.kind not in ("i", "u"):
        return None
    change = np.nonzero(arr[1:] != arr[:-1])[0]
    if len(change) + 1 > n // RLE_MAX_RUN_FRACTION:
        return None
    starts = np.concatenate([[0], change + 1]).astype(np.int32)
    return arr[starts], starts


def rle_decode(run_values: np.ndarray, run_starts: np.ndarray,
               n: int) -> np.ndarray:
    """Host-side inverse of `rle_encode`."""
    idx = np.searchsorted(run_starts, np.arange(n), side="right") - 1
    return run_values[idx]


@functools.lru_cache(maxsize=256)
def _rle_expand_jit(runs_cap: int, cap: int, dtype_name: str):
    def fn(rv, starts):
        idx = jnp.searchsorted(starts, jnp.arange(cap, dtype=jnp.int32),
                               side="right") - 1
        return jnp.take(rv, jnp.clip(idx, 0, runs_cap - 1))
    return jax.jit(fn)


def upload_columns(plans: list, device=None, clock=None) -> list:
    """Upload a batch of columns, keeping carriers RESIDENT on device.
    `clock`, where given, gets the seconds spent handing arrays to the
    device added to its `put_s` (a scan's miss path tells its H2D from its
    codec by it: exec/executor.py `_ScanLoadClock`).

    `plans` is a list of (np_array, lane_dtype | None, capacity); lane None
    means the array ships as-is after padding (bool masks). Narrowing is
    decided over the UNPADDED values (so pad zeros cannot drag the value
    range) and the carrier is zero-padded — a dead lane therefore widens to
    the spec's offset, which is 0 on every path except offset-shrink.

    Returns one (device_array, spec, carrier_arg) triple per plan, order
    preserved. `spec` is the CANONICAL WidenSpec (offset presence only — the
    real offset rides in `carrier_arg`, a 0-d device array, so distinct column
    minima share compiled programs); spec None means the lane shipped wide.
    For an f32 pair `device_array` is the high half and `carrier_arg` the low
    one, a `[capacity]` lane like it (8 B a lane between them, counted so).
    The narrow array is what stays in HBM: operators widen in-jit through
    `batch.wide_values` (XLA fuses the cast/divide into the consumer), so HBM
    residency and every downstream byte cost scale with carrier width.

    With IGLOO_TPU_ENCODED=0 every column ships and resides WIDE (the
    bit-identical kill switch; also the `codec.*` counter A/B baseline).
    Sorted/clustered integer carriers additionally ship run-length encoded
    (IGLOO_TPU_RLE) and expand to the scalar carrier in one device jit."""
    raw_put = (jnp.asarray if device is None
               else functools.partial(jax.device_put, device=device))
    h2d = 0

    def put(a):
        nonlocal h2d
        h2d += getattr(a, "nbytes", 0)
        if clock is None:
            return raw_put(a)
        t0 = time.perf_counter()
        out = raw_put(a)
        clock.put_s += time.perf_counter() - t0
        return out

    from igloo_tpu.utils import tracing
    enc = encoded_enabled()
    rle = rle_enabled()
    out: list = [None] * len(plans)
    carrier_bytes = 0
    decoded_bytes = 0
    for i, (arr, lane, cap) in enumerate(plans):
        shrunk = shrink(arr, np.dtype(lane)) \
            if (enc and lane is not None) else None
        if shrunk is None:
            # a float64 lane no narrower carrier took: the two f32 halves the
            # chip computes on, where the canary says they are the lane
            f64 = lane is not None and arr.dtype == np.float64
            pair = split_pair(arr) \
                if (enc and f64 and arr.size >= PAIR_MIN_ROWS
                    and _f32pair_ok()) else None
            if pair is not None:
                hi, lo = pair
                out[i] = (put(_pad_to(hi, cap)),
                          WidenSpec("float64", pair=True),
                          put(_pad_to(lo, cap)))
                tracing.counter("codec.f32pair_columns")
            else:
                out[i] = (put(_pad_to(arr, cap)), None, None)
                if f64:
                    tracing.counter("codec.f64_wide_columns")
            if lane is not None:
                decoded_bytes += cap * np.dtype(lane).itemsize
                carrier_bytes += cap * arr.dtype.itemsize
            continue
        carrier, spec = shrunk
        decoded_bytes += cap * np.dtype(lane).itemsize
        runs = rle_encode(carrier) if rle else None
        if runs is not None:
            rv, starts = runs
            runs_cap = round_capacity_for_runs(len(rv))
            dev_rv = put(_pad_to(rv, runs_cap))
            # pad starts with `cap` (past every real row) so the expand's
            # searchsorted maps dead run slots past the data
            pstarts = np.full((runs_cap,), cap, dtype=np.int32)
            pstarts[: len(starts)] = starts
            dev_starts = put(pstarts)
            vals = _rle_expand_jit(runs_cap, cap, rv.dtype.name)(
                dev_rv, dev_starts)
            tracing.counter("codec.rle_columns")
            carrier_bytes += int(dev_rv.nbytes + dev_starts.nbytes)
        else:
            vals = put(_pad_to(carrier, cap))
            carrier_bytes += cap * carrier.dtype.itemsize
        # canonical spec + runtime 0-d payload: the offset is data-dependent
        # (column min), the scale divisor must stay a runtime operand so XLA
        # cannot rewrite the divide into an inexact reciprocal multiply
        if spec.offset:
            carg = put(np.int64(spec.offset))
            cspec = WidenSpec(spec.lane, offset=1)
        elif spec.scale != 1.0:
            carg = put(np.float64(spec.scale))
            cspec = WidenSpec(spec.lane, scale=spec.scale)
        else:
            carg = None
            cspec = WidenSpec(spec.lane)
        out[i] = (vals, cspec, carg)
    if carrier_bytes:
        tracing.counter("codec.carrier_bytes", carrier_bytes)
    if decoded_bytes:
        tracing.counter("codec.decoded_bytes", decoded_bytes)
    from igloo_tpu.utils.stats import record_upload
    record_upload(h2d)  # actual shipped bytes: narrowed carriers, padded
    return out


def round_capacity_for_runs(nruns: int) -> int:
    """Shape-bucket the RLE run arrays like every other lane so the expand
    jit cache stays small."""
    from igloo_tpu.exec.capacity import canonical_capacity
    return canonical_capacity(max(nruns, 1))


def host_widen(spec: WidenSpec, vals: np.ndarray, carg=None) -> np.ndarray:
    """Decode a fetched carrier lane back to the engine lane ON HOST, at the
    output boundary (batch.arrow_from_host). Bit-identical to the device
    widen: the offset path is exact integer addition, the scale path replays
    the very IEEE-f64 divide `_shrink_float` verified elementwise, and the
    cast paths (f32->f64, int8->int64, an f32 pair's two halves: their sum
    has at most 48 significant bits) are exact by construction."""
    lane = np.dtype(spec.lane)
    if spec.pair:
        # `carg` is the low half, row for row with `vals`: the sum is the
        # double the chip's own X64Combine hands back for these halves
        return vals.astype(lane) + carg.astype(lane)
    if spec.scale != 1.0:
        return vals.astype(lane) / lane.type(spec.scale)
    if spec.offset:
        off = int(carg) if carg is not None else spec.offset
        return vals.astype(lane) + lane.type(off)
    return vals.astype(lane, copy=False)


# --- measured carrier ratio: whole tables in carrier bytes -------------------
# The GRACE trigger and the optimizer's join order estimate a WHOLE table in
# wide lane bytes (chunked.table_lane_bytes). Once a provider's columns have
# actually shipped, the observed narrow/wide ratio is remembered PER PROVIDER
# INSTANCE — ONE number, written by the last scan of it, whatever columns
# that scan read — and those estimators scale by it, so effective partitions
# grow per HBM budget. The price of a SCAN (chunked.estimated_lane_bytes: the
# chunked tier, serving's predict_hbm_bytes) does not read it: a routing
# decision must not depend on which query ran last. Keyed weakly so a dropped
# provider cannot pin its entry; unmeasured providers price at 1.0 (estimates
# never shrink on faith).

import weakref

_RATIO_LOCK = threading.Lock()
_CARRIER_RATIOS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def record_carrier_ratio(provider, narrow_bytes: int,
                         wide_bytes: int) -> None:
    if provider is None or wide_bytes <= 0 or not encoded_enabled():
        return
    ratio = min(max(narrow_bytes / wide_bytes, 0.0), 1.0)
    try:
        with _RATIO_LOCK:
            _CARRIER_RATIOS[provider] = ratio
    except TypeError:
        pass  # non-weakref-able provider: price wide, never crash


def reset_carrier_ratios() -> None:
    """Forget every measured ratio — restores the price-wide-until-measured
    cold state. For tests and A/B bench runs that need whole-table sizes (and
    so GRACE routing and join order) independent of which queries ran earlier
    in the process."""
    with _RATIO_LOCK:
        _CARRIER_RATIOS.clear()


def carrier_ratio(provider) -> float:
    """Measured carrier/wide byte ratio for this provider instance, or 1.0
    when unmeasured (or the kill switch is off)."""
    if provider is None or not encoded_enabled():
        return 1.0
    try:
        with _RATIO_LOCK:
            return _CARRIER_RATIOS.get(provider, 1.0)
    except TypeError:
        return 1.0


@functools.lru_cache(maxsize=64)
def _live_jit(cap: int):
    return jax.jit(lambda n: jnp.arange(cap, dtype=jnp.int32) < n)


def live_lane(cap: int, n: int, device=None):
    """Selection mask with the first `n` lanes set, built ON DEVICE from a
    4-byte scalar instead of uploading `cap` bool bytes."""
    nn = np.int32(n)
    nd = jnp.asarray(nn) if device is None else jax.device_put(nn, device)
    return _live_jit(int(cap))(nd)
