"""The trace reduction (bench/trace.py) on small synthetic traces."""

import pytest

from bench import trace
from bench.trace import Event

MS = 1e6  # nanoseconds


def _host(name, s, e):
    return Event("/host:CPU", "python3", name, s * MS, e * MS)


def _op(dev, name, s, e, line=trace.OPS_LINE):
    return Event(f"/device:TPU:{dev}", line, name, s * MS, e * MS)


def _events():
    """A 100 ms window; device 0 busy 10-30 (two overlapping ops) and
    60-70; device 1 busy 0-50. Host spans cover 0-40 and 40-100."""
    return [
        _host("bench_window", 0, 100),
        _host("fwd_wave_0", 0, 40),
        _host("bwd_and_update", 40, 100),
        _op(0, "fusion.1", 10, 25),
        _op(0, "fusion.2", 20, 30),
        _op(0, "dot.3", 60, 70),
        _op(1, "dot.4", -10, 50),  # starts before the window: clipped
        _op(1, "dot.5", 0, 0.0),  # empty
        _op(0, "module", 0, 100, line="XLA Modules"),  # other line: ignored
        _op(2, "dot.6", 0, 100),  # a chip the cell does not use
    ]


def test_union_merges_overlaps():
    assert trace.union([(20, 30), (10, 25), (60, 70), (70, 75)]) == [
        (10, 30), (60, 75)]
    assert trace.gaps([(10, 30), (60, 75)], 0, 100) == [
        (0, 10), (30, 60), (75, 100)]


def test_busy_and_idle_share():
    red = trace.reduce(_events(), chips=2, window="bench_window")
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s_per_chip"] == pytest.approx([0.030, 0.050])
    assert red["busy_s"] == pytest.approx(0.040)
    assert red["idle_pct"] == pytest.approx(60.0)
    ops = dict(red["device_ops"])
    # per chip: fusion 25 ms on chip 0; dot 10 + 50 ms over two chips
    assert ops["fusion"] == pytest.approx(0.0125)
    assert ops["dot"] == pytest.approx(0.030)


def test_every_op_time_kept():
    """``op_s`` has every operation's seconds per chip, beyond the top ones
    that ``device_ops`` lists."""
    extra = [_op(0, f"op{i}.1", 70 + i, 70.5 + i) for i in range(15)]
    red = trace.reduce(_events() + extra, chips=2, window="bench_window")
    assert len(red["op_s"]) == 17 and len(red["device_ops"]) == trace.TOP
    assert red["device_ops"] == trace._top(red["op_s"])
    assert red["op_s"]["op3"] == pytest.approx(0.00025)
    # chip 0: 15 + 10 + 10 + 15 x 0.5 ms; chip 1: 50 ms (clipped)
    assert sum(red["op_s"].values()) == pytest.approx((42.5 + 50) / 2 * 1e-3)


def test_gaps_attributed_to_host_spans():
    red = trace.reduce(_events(), chips=2, window="bench_window")
    gaps = dict(red["idle_gaps"])
    # chip 0 idle 0-10, 30-60, 70-100; chip 1 idle 50-100; per chip
    assert gaps["fwd_wave_0"] == pytest.approx((0.010 + 0.010) / 2)
    assert gaps["bwd_and_update"] == pytest.approx((0.020 + 0.030 + 0.050) / 2)
    assert "outside_spans" not in gaps
    idle = sum(gaps.values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])


def test_uncovered_gap_and_bounds():
    ev = [_host("bench_window", 0, 100), _host("fwd_wave_1", 0, 20),
          _op(0, "a", 0, 100), _op(0, "b", 0, 100)]
    red = trace.reduce(ev, chips=1, window="bench_window")
    assert red["idle_pct"] == pytest.approx(0.0)  # stacked ops count once
    ev = [_host("bench_window", 0, 100), _host("fwd_wave_1", 0, 20),
          _op(0, "a", 90, 95)]
    red = trace.reduce(ev, chips=1, window="bench_window")
    gaps = dict(red["idle_gaps"])
    assert gaps["fwd_wave_1"] == pytest.approx(0.020)
    assert gaps["outside_spans"] == pytest.approx(0.075)
    for r in (red,):
        assert 0.0 <= r["idle_pct"] <= 100.0
        assert r["busy_s"] <= r["window_s"]


@pytest.mark.parametrize("name,folded", [
    ("%mul.1 = f32[8,257,4096]{2,0,1:T(8,128)} multiply(f32[8,257,4096]{2,0,1} "
     "%Arg_0.1, f32[8,257,4096]{2,0,1} %Arg_1.1)", "mul f32[8,257,4096]"),
    ("%broadcast_multiply_fusion = f32[1024,4096]{1,0:T(8,128)} fusion(%x.1), "
     "kind=kLoop", "broadcast_multiply_fusion f32[1024,4096]"),
    ("%copy-done = f32[32]{0:T(128)S(1)} copy-done((f32[32]{0}) %copy-start)",
     "copy-done f32[32]"),
    ("fusion.12", "fusion"),
])
def test_op_names_fold(name, folded):
    assert trace._fold(name) == folded


def test_missing_window_or_chips_raise():
    with pytest.raises(ValueError, match="no host span"):
        trace.reduce([_op(0, "a", 0, 1)], chips=1, window="bench_window")
    with pytest.raises(ValueError, match="device planes"):
        trace.reduce(_events(), chips=4, window="bench_window")
