"""Narrow-transfer codec (exec/codec.py): losslessness of every carrier path.

The codec may pick any carrier it proves exact on the host; these tests assert
the device round-trip reproduces the original lanes bit-for-bit, and that the
expected carrier families actually engage (so a regression to "ship wide"
would be caught by the dtype assertions, not just silently slow)."""
import numpy as np
import pyarrow as pa
import pytest

from igloo_tpu.exec import codec
from igloo_tpu.exec.batch import from_arrow, to_arrow
from igloo_tpu.types import Schema


def roundtrip(table: pa.Table) -> pa.Table:
    return to_arrow(from_arrow(table))


def test_decimal_cents_exact():
    v = np.round(np.random.default_rng(0).uniform(900.0, 105000.0, 4096) * 100) / 100
    t = pa.table({"price": v})
    got = roundtrip(t)
    assert got.column("price").to_pylist() == v.tolist()
    shrunk = codec.shrink(v, np.dtype(np.float64))
    assert shrunk is not None and shrunk[1].scale == 100.0
    assert shrunk[0].dtype == np.int32


def test_small_decimals_ride_int8():
    v = np.random.default_rng(1).integers(0, 11, 4096) / 100.0  # discounts
    shrunk = codec.shrink(v, np.dtype(np.float64))
    assert shrunk is not None and shrunk[0].dtype == np.int8
    t = pa.table({"d": v})
    assert roundtrip(t).column("d").to_pylist() == v.tolist()


def test_integral_floats_scale_one():
    v = np.random.default_rng(2).integers(1, 51, 4096).astype(np.float64)
    shrunk = codec.shrink(v, np.dtype(np.float64))
    assert shrunk is not None and shrunk[1].scale == 1.0
    assert shrunk[0].dtype == np.int8
    assert roundtrip(pa.table({"q": v})).column("q").to_pylist() == v.tolist()


def test_irregular_floats_ship_wide():
    v = np.random.default_rng(3).standard_normal(1024)
    assert codec.shrink(v, np.dtype(np.float64)) is None
    assert roundtrip(pa.table({"x": v})).column("x").to_pylist() == v.tolist()


def test_f32_roundtrip_carrier():
    v = (np.random.default_rng(4).standard_normal(1024) * 1e9) \
        .astype(np.float32).astype(np.float64)  # f32-exact, not scaled-decimal
    shrunk = codec.shrink(v, np.dtype(np.float64))
    assert shrunk is not None and shrunk[0].dtype == np.float32
    assert roundtrip(pa.table({"x": v})).column("x").to_pylist() == v.tolist()


def test_nan_inf_ship_exact():
    v = np.array([1.5, np.nan, np.inf, -np.inf, 0.0])
    got = roundtrip(pa.table({"x": pa.array(v, type=pa.float64())}))
    out = got.column("x").to_pylist()
    assert out[0] == 1.5 and np.isnan(out[1]) and out[2] == np.inf


def test_int_offset_shrink_timestamps():
    base = 1_700_000_000_000_000
    v = base + np.random.default_rng(5).integers(0, 3_600_000_000, 2048)
    shrunk = codec.shrink(v, np.dtype(np.int64))
    assert shrunk is not None and shrunk[1].offset != 0
    assert shrunk[0].dtype.itemsize <= 8
    lane = np.dtype(np.int64)
    widened = np.asarray(shrunk[1].widen(np.asarray(shrunk[0])))
    assert np.array_equal(widened.astype(lane), v)


def test_date_range_rides_i16():
    v = np.random.default_rng(6).integers(8035, 10592, 4096).astype(np.int32)
    shrunk = codec.shrink(v, np.dtype(np.int32))
    assert shrunk is not None and shrunk[0].dtype == np.int16


def test_nulls_preserved():
    t = pa.table({"x": pa.array([1.25, None, 3.75, None], type=pa.float64()),
                  "s": pa.array(["a", None, "b", "a"])})
    got = roundtrip(t)
    assert got.column("x").to_pylist() == [1.25, None, 3.75, None]
    assert got.column("s").to_pylist() == ["a", None, "b", "a"]


def test_big_int64_keys_unshrunk_exact():
    v = np.random.default_rng(7).integers(-2**62, 2**62, 1024)
    assert roundtrip(pa.table({"k": v})).column("k").to_pylist() == v.tolist()


def test_live_lane():
    live = np.asarray(codec.live_lane(16, 5))
    assert live.tolist() == [True] * 5 + [False] * 11


def test_decimal_canary_passes_on_cpu():
    """The one-time on-device canary replays every scale's divide and, on an
    IEEE-correct backend (the CPU suite), keeps the scaled-decimal path on."""
    codec._decimal_canary_ok = None
    try:
        assert codec._scaled_decimal_ok() is True
        v = np.round(np.random.default_rng(8).uniform(0, 1000, 512) * 100) / 100
        shrunk = codec.shrink(v, np.dtype(np.float64))
        assert shrunk is not None and shrunk[1].scale == 100.0
    finally:
        codec._decimal_canary_ok = None


def test_decimal_canary_failure_falls_back_to_wide_lanes():
    """A device whose emulated-f64 divide is not bit-exact must NOT use the
    scaled-decimal carrier: shrink falls back to the f32 round-trip (when
    exact) or raw f64 — never a representation the device would corrupt."""
    codec._decimal_canary_ok = False
    try:
        # six-digit prices in cents: scaled-decimal would engage (c < 2^31)
        # but f32 cannot carry them exactly -> must ship as raw float64 (None)
        v = np.round(np.random.default_rng(9).uniform(1e5, 1e6, 512) * 100) / 100
        assert codec.shrink(v, np.dtype(np.float64)) is None
        # dyadic decimals remain f32-exact and take the round-trip carrier
        s = np.random.default_rng(10).integers(1, 11, 512) / 2.0
        shrunk = codec.shrink(s, np.dtype(np.float64))
        assert shrunk is not None and shrunk[0].dtype == np.float32
        assert shrunk[1].scale == 1.0  # cast path, not a device divide
        # integral floats keep the cast-only scale-1 carrier (no divide)
        q = np.random.default_rng(11).integers(1, 51, 512).astype(np.float64)
        shrunk = codec.shrink(q, np.dtype(np.float64))
        assert shrunk is not None and shrunk[1].scale == 1.0
        t = pa.table({"d": s, "q": q})
        got = roundtrip(t)
        assert got.column("d").to_pylist() == s.tolist()
        assert got.column("q").to_pylist() == q.tolist()
    finally:
        codec._decimal_canary_ok = None


# --- f32-pair carrier (ISSUE 37): a float64 lane as the chip's two halves ----


@pytest.fixture
def chip_codec(monkeypatch):
    """The codec as the v5e leaves it: the decimal canary failed, the pair
    canary passed (set by the test, as the decimal canary's verdict is)."""
    monkeypatch.setattr(codec, "_decimal_canary_ok", False)
    monkeypatch.setattr(codec, "_f32pair_canary_ok", True)


def _upload(v, cap=None):
    cap = cap or len(v)
    (vals, spec, carg), = codec.upload_columns([(v, np.float64, cap)])
    return vals, spec, carg


def test_pair_halves_sum_within_two_to_the_minus_47(chip_codec):
    from igloo_tpu.exec.batch import DeviceColumn, wide_values
    from igloo_tpu.types import FLOAT64
    rng = np.random.default_rng(37)
    v = np.concatenate([np.round(rng.uniform(900.0, 105000.0, 2000), 2),
                        rng.uniform(-1e6, 1e6, 2000), [0.0, 1 / 3, -np.pi]])
    vals, spec, lo = _upload(v, cap=8192)
    assert spec == codec.WidenSpec("float64", pair=True)
    assert vals.dtype == np.float32 and lo.dtype == np.float32
    assert vals.shape == lo.shape == (8192,)  # two rank-1 lanes
    wide = np.asarray(wide_values(DeviceColumn(FLOAT64, vals, None, None,
                                               None, spec, lo)))
    assert np.all(np.abs(wide[:len(v)] - v) <= np.abs(v) * 2.0 ** -47)
    assert not wide[len(v):].any()  # pad lanes widen to 0
    host = codec.host_widen(spec, np.asarray(vals), np.asarray(lo))
    assert np.array_equal(host, wide)  # the output boundary: f64(hi)+f64(lo)


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf, 1e39, -1e39,
                                 1e-40, 1.2345678901234567e-31, -0.0])
def test_a_column_with_a_value_that_has_no_pair_ships_wide(chip_codec, bad):
    """Non-finite, past the f32 exponent range, a subnormal half (1e-40 as a
    high half, 1.23e-31 for its low one) or a negative zero: the whole lane rides
    as float64, as it did."""
    v = np.random.default_rng(1).standard_normal(2048)
    v[17] = bad
    from igloo_tpu.utils import tracing
    with tracing.counter_delta() as d:
        vals, spec, carg = _upload(v)
    assert spec is None and carg is None and vals.dtype == np.float64
    assert np.array_equal(np.asarray(vals), v, equal_nan=True)
    assert d.get("codec.f64_wide_columns") == 1
    assert not d.get("codec.f32pair_columns")
    assert codec.split_pair(v) is None


def test_a_column_a_narrower_carrier_takes_still_takes_it(chip_codec):
    q = np.random.default_rng(2).integers(1, 51, 512).astype(np.float64)
    vals, spec, _ = _upload(q)
    assert vals.dtype == np.int8 and not spec.pair
    f = np.random.default_rng(3).standard_normal(512) \
        .astype(np.float32).astype(np.float64)
    vals, spec, _ = _upload(f)
    assert vals.dtype == np.float32 and not spec.pair
    # ... and tells itself from a pair of the same lane dtype in program keys
    assert spec.key() != codec.WidenSpec("float64", pair=True).key()
    assert codec.WidenSpec("float64").key() == ("float64", False, 1.0, False)


def test_kill_switch_keeps_a_pair_off(chip_codec, monkeypatch):
    monkeypatch.setenv("IGLOO_TPU_ENCODED", "0")
    v = np.random.default_rng(4).standard_normal(2048)
    vals, spec, _ = _upload(v)
    assert spec is None and vals.dtype == np.float64


def test_a_short_lane_stays_wide(chip_codec):
    """Under PAIR_MIN_ROWS a split pass costs nothing and a second array is
    one more transfer: a merge fragment's eight-row dependency table ships
    as it did."""
    v = np.random.default_rng(6).standard_normal(codec.PAIR_MIN_ROWS)
    assert _upload(v)[1] == codec.WidenSpec("float64", pair=True)
    vals, spec, carg = _upload(v[:-1], cap=len(v))
    assert spec is None and carg is None and vals.dtype == np.float64


def test_pair_canary_says_no_on_xla_cpu(monkeypatch):
    """XLA:CPU's float64 is IEEE: a 53-bit probe through `x * one` differs
    from its 48-bit pair, the verdict is no, and `upload_columns` returns
    what it returned before, array for array."""
    from igloo_tpu.utils import tracing
    monkeypatch.setattr(codec, "_decimal_canary_ok", False)
    codec.reset_f32pair_canary()
    try:
        with tracing.counter_delta() as d:
            assert codec._f32pair_ok() is False
            assert codec._f32pair_ok() is False  # computed once
        assert d.get("codec.f32pair_canary_fail") == 1
        assert not d.get("codec.f32pair_canary_ok")
        rng = np.random.default_rng(5)
        price = np.round(rng.uniform(900.0, 105000.0, 1500), 2)
        plans = [(price, np.float64, 2048),
                 (rng.integers(1, 51, 1500).astype(np.float64), np.float64, 2048),
                 (rng.integers(0, 9, 1500), np.int64, 2048),
                 (rng.random(1500) < 0.5, None, 2048)]
        got = codec.upload_columns(plans)
        monkeypatch.setattr(codec, "split_pair", None)  # never reached
        monkeypatch.setattr(codec, "_f32pair_ok", lambda: False)
        want = codec.upload_columns(plans)
        for (gv, gs, ga), (wv, ws, wa) in zip(got, want):
            assert gs == ws and (ga is None) == (wa is None)
            assert gv.dtype == wv.dtype and np.array_equal(gv, wv)
        assert got[0][1] is None and got[0][0].dtype == np.float64
    finally:
        codec.reset_f32pair_canary()


def test_pair_canary_reset_hook_and_one_verdict_across_threads():
    import threading
    codec.reset_f32pair_canary()
    assert codec._f32pair_canary_ok is None
    seen = []
    ts = [threading.Thread(target=lambda: seen.append(codec._f32pair_ok()))
          for _ in range(6)]
    from igloo_tpu.utils import tracing
    with tracing.counter_delta():
        before = tracing.counters().get("codec.f32pair_canary_fail", 0)
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert seen == [False] * 6
    assert tracing.counters().get("codec.f32pair_canary_fail", 0) - before == 1
    codec.reset_f32pair_canary()
    assert codec._f32pair_canary_ok is None
