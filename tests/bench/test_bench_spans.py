"""The program's own spans: where the program opens them, and their
reduction (bench/spans.py) on small synthetic traces."""

import jax
import pytest

from bench import harness, spans, trace
from bench.trace import Event
from test_bench_trace import _events

MS = 1e6  # nanoseconds
STEP_CHILDREN = [  # (name, start, end) in ms from the start of a step
    ("spindle.fwd:vision", 2, 12), ("spindle.fwd:text", 12, 20),
    ("spindle.grad_init", 20, 22), ("spindle.bwd:text", 22, 30),
    ("spindle.grad_acc:text", 30, 32), ("spindle.bwd:vision", 32, 38),
    ("spindle.grad_acc:vision", 38, 40), ("spindle.optim", 40, 46),
    ("spindle.optim.clip", 41, 43), ("spindle.loss_read", 46, 48),
]
READERS = {
    "engine.bwd_pull_ms": 14.0,  # 8 + 6 ms of pulls per step
    "engine.grad_acc_ms": 6.0,  # 2 + 2 + 2
    "optim.update_ms": 6.0,  # the clip's 2 ms inside
    "session.loss_wait_ms": 2.0,
}


def _host(name, s, e):
    return Event("/host:CPU", "python3", name, s * MS, e * MS)


def _op(dev, s, e):
    return Event(f"/device:TPU:{dev}", trace.OPS_LINE, "fusion.1", s * MS, e * MS)


def _bench_events():
    """Two steps of 48 ms in a 100 ms window, the program's spans nested in
    each; the benchmark's own spans overlap them out of order; a step before
    the window. Chip 0 is busy 5-10, 25-28 and 60-70, chip 1 0-50."""
    ev = [_host("bench_window", 0, 100), _host("spindle.step", -10, -1),
          _host("fwd_wave_0", 0, 15), _host("bwd_and_update", 15, 50),
          _host("fwd_wave_0", 50, 65), _host("bwd_and_update", 65, 100)]
    for o in (0, 50):
        ev.append(_host("spindle.step", o, o + 48))
        ev += [_host(n, o + s, o + e) for n, s, e in STEP_CHILDREN]
    ev += [_op(0, 5, 10), _op(0, 25, 28), _op(0, 60, 70), _op(1, 0, 50)]
    return ev


def _brute(events, chips=2, q=0.25):
    """Per ``q`` ms of the window: the owner (the spindle span over it that
    started latest, the shorter of two) and each idle chip."""
    spans_ = [(e.start_ns / MS, e.end_ns / MS, e.name) for e in events
              if e.name.startswith("spindle.")]
    ops = [(int(e.plane[-1]), e.start_ns / MS, e.end_ns / MS) for e in events
           if e.plane.startswith(trace.DEVICE_PREFIX)]
    self_ms, idle = {}, {}
    for k in range(int(100 / q)):
        t = (k + 0.5) * q
        cover = [sp for sp in spans_ if sp[0] <= t < sp[1]]
        owner = max(cover, key=lambda sp: (sp[0], -sp[1]))[2] if cover else None
        if owner:
            self_ms[owner] = self_ms.get(owner, 0.0) + q
        for c in range(chips):
            if not any(d == c and s <= t < e for d, s, e in ops):
                idle[owner] = idle.get(owner, 0.0) + q / chips
    return self_ms, idle


def _reduce(events=None):
    return spans.program_spans(_bench_events() if events is None else events,
                               window="bench_window", chips=2, steps=2)


def test_self_time_and_counts_per_phase():
    red = _reduce()
    ph = red["phases"]
    assert ph["spindle.step"]["ms"] == pytest.approx(2.0)  # 48 - 46 covered
    assert ph["spindle.optim"]["ms"] == pytest.approx(4.0)  # 6 - the clip's 2
    assert ph["spindle.optim.clip"]["ms"] == pytest.approx(2.0)
    # grouped on the part before ':'; the step before the window left out
    assert ph["spindle.bwd"] == pytest.approx(
        {"ms": 14.0, "count": 2.0, "idle_s": ph["spindle.bwd"]["idle_s"]})
    assert ph["spindle.step"]["count"] == 1.0
    assert set(ph) == {"spindle.step", "spindle.fwd", "spindle.grad_init",
                       "spindle.bwd", "spindle.grad_acc", "spindle.optim",
                       "spindle.optim.clip", "spindle.loss_read"}
    self_ms, _ = _brute(_bench_events())
    for p, v in ph.items():
        want = sum(ms for n, ms in self_ms.items() if spans.phase(n) == p) / 2
        assert v["ms"] == pytest.approx(want), p


def test_idle_goes_to_the_innermost_span():
    red = _reduce()
    by_span = dict(red["idle_by_span"])
    _, idle = _brute(_bench_events())
    # fwd:vision: chip 0 idle 2-5, 10-12 and 52-60, chip 1 idle 52-62
    assert by_span["spindle.fwd:vision"] == pytest.approx(
        (3 + 2 + 8 + 10) / 2 * 1e-3)
    for name, ms in idle.items():
        if name is not None and name in by_span:
            assert by_span[name] == pytest.approx(ms * 1e-3), name
    top = {n for n, _ in red["idle_by_span"]}
    assert "fwd_wave_0" not in top and "bwd_and_update" not in top
    assert len(red["idle_by_span"]) == trace.TOP
    assert red["phases"]["spindle.optim"]["idle_s"] == pytest.approx(
        sum(v for n, v in idle.items() if n == "spindle.optim") * 1e-3)


def test_share_of_idle_covered():
    red = _reduce()
    # idle per chip: 82 and 50 ms; uncovered 48-50 and 98-100 on chip 0,
    # 98-100 on chip 1
    assert red["idle_s"] == pytest.approx((82 + 50) / 2 * 1e-3)
    assert red["covered_pct"] == pytest.approx(100.0 * 126 / 132)
    base = trace.reduce(_bench_events(), chips=2, window="bench_window")
    assert red["idle_s"] == pytest.approx(base["window_s"] - base["busy_s"])


def test_reduce_ignores_program_spans():
    """``reduce``'s outputs are the same with the program's spans in the
    trace as without them."""
    extra = [_host(n, s, e) for n, s, e in STEP_CHILDREN] + [
        _host("spindle.step", 0, 90)]
    plain = trace.reduce(_events(), chips=2, window="bench_window")
    assert trace.reduce(_events() + extra, chips=2,
                        window="bench_window") == plain
    no_program = [e for e in _bench_events() if not e.name.startswith("spindle.")]
    assert trace.reduce(no_program, chips=2, window="bench_window") == \
        trace.reduce(_bench_events(), chips=2, window="bench_window")


def _ctx(events):
    return harness.trace_context(events, chips=2, steps=2)


@pytest.mark.parametrize("name,want", READERS.items())
def test_readers_read_the_run_s_trace(name, want):
    read = harness.load_reader(name)
    ctx = _ctx(_bench_events())
    assert read(ctx) == pytest.approx(want)
    # reduced once by the harness, from the events the trace's reduction read
    assert ctx["spans"] == _reduce()
    assert ctx["trace"] == trace.reduce(_bench_events(), chips=2,
                                        window="bench_window")
    # a program without the spans: nothing to read
    bare = [e for e in _bench_events() if not e.name.startswith("spindle.")]
    assert _ctx(bare)["spans"] is None
    assert read(_ctx(bare)) is None
    # an untraced run: nothing to read
    assert read({"steps": 2, "chips": 2}) is None


def test_missing_window_or_chips_raise():
    with pytest.raises(ValueError, match="no host span"):
        spans.program_spans([_op(0, 0, 1)], window="bench_window", chips=1,
                            steps=1)
    with pytest.raises(ValueError, match="device planes"):
        spans.program_spans(_bench_events(), window="bench_window", chips=3,
                            steps=1)


# ---------------------------------------------------------------- program
def _session():
    from repro.core import ClusterSpec
    from repro.runtime import tiny_multitask_clip
    from repro.session import SessionConfig, SpindleSession

    model, batches = tiny_multitask_clip(n_tasks=2, layers=(1, 1))
    return SpindleSession(
        SessionConfig(cluster=ClusterSpec(n_devices=8, island_size=4,
                                          mem_bytes=96e9)),
        model=model, batches=batches, tasks=("img_text", "audio_text")).bind()


def test_program_opens_its_spans_where_the_work_happens(tmp_path):
    steps = 2
    plain = _session()  # compiles untraced: the Python tracer slows compiles
    want = [plain.step() for _ in range(steps)]
    session = _session()
    with jax.profiler.trace(str(tmp_path)):
        got = [session.step() for _ in range(steps)]
    assert got == want  # the spans change no arithmetic

    events = trace.load(tmp_path)
    names = [e.name for e in events if e.name.startswith(spans.PREFIX)
             and not e.plane.startswith(trace.DEVICE_PREFIX)]
    # the readers' loader keeps these spans, as trace.load has them
    assert [e for e in spans.load(tmp_path) if e.name in names] == [
        e for e in events if e.name in names]
    count = {}
    for n in names:
        count[spans.phase(n)] = count.get(spans.phase(n), 0) + 1
    plan_steps = session.engine.plan.steps
    insts = {session.engine.meta_info[s.meta_id][0] for s in plan_steps}
    assert set(count) == {"spindle.step", "spindle.fwd", "spindle.grad_init",
                          "spindle.bwd", "spindle.grad_acc", "spindle.optim",
                          "spindle.optim.clip", "spindle.loss_read"}
    for once in ("spindle.step", "spindle.grad_init", "spindle.optim",
                 "spindle.optim.clip", "spindle.loss_read"):
        assert count[once] == steps, once
    for per_plan_step in ("spindle.fwd", "spindle.bwd", "spindle.grad_acc"):
        assert count[per_plan_step] == steps * len(plan_steps), per_plan_step
    assert {n.split(":", 1)[1] for n in names if ":" in n} == insts
