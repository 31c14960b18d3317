"""Reduction of a profiler trace to busy, idle, top device ops and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into flat
events. ``reduce`` takes those events and:

- finds the measured window, the host span named ``window`` (the harness's
  ``WINDOW``);
- takes each device's operations (the ``ops_line`` line of its
  ``/device:TPU:<n>`` plane), clipped to the window, and their union as the
  time that device was busy; ``busy_s`` is the mean over the cell's chips and
  ``idle_pct`` is one minus busy over the window;
- sums device time per operation, as seconds per chip (``op_s``, every
  operation; ``device_ops``, the top ones): an event named by its HLO text (``%mul.1 = f32[8,257,4096]{...}
  multiply(...)``) counts under its instruction name and result shape
  (``mul f32[8,257,4096]``), any other under its name; ``.<n>`` suffixes
  are folded;
- splits every idle stretch of every device among the host spans that cover
  it (the benchmark's ``fwd_wave_<i>`` and ``bwd_and_update``), by overlap,
  with what no span covers under ``outside_spans``, as seconds per chip.

No share exceeds 100%: busy time is a union of intervals inside the window.
"""

from __future__ import annotations

import bisect
import pathlib
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: the host span around the measured window
WINDOW = "bench_window"
SPAN_RE = re.compile(r"^(fwd_wave_\d+|bwd_and_update)$")
TOP = 10


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def load(trace_dir: pathlib.Path) -> List[Event]:
    """All events of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                s = float(e.start_ns)
                out.append(Event(plane.name, line.name, e.name, s,
                                 s + float(e.duration_ns)))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Tuple[float, float]:
    return max(s, lo), min(e, hi)


HLO_RE = re.compile(r"^%([\w-]+?)(?:\.\d+)? = ([a-z0-9]+\[[0-9,]*\])")


def _fold(name: str) -> str:
    m = HLO_RE.match(name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return re.sub(r"\.\d+$", "", name)


def _top(d: Dict[str, float]) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]


def window_bounds(events: Sequence[Event], window: str) -> Tuple[float, float]:
    """Start and end, in ns, of the first host span named ``window``."""
    wins = [e for e in events if e.name == window
            and not e.plane.startswith(DEVICE_PREFIX)]
    if not wins:
        raise ValueError(f"no host span {window!r} in the trace")
    return wins[0].start_ns, wins[0].end_ns


def device_planes(events: Sequence[Event], chips: int) -> List[str]:
    """The planes of the first ``chips`` devices, in device order."""
    planes = sorted({e.plane for e in events if e.plane.startswith(DEVICE_PREFIX)},
                    key=lambda p: int(p[len(DEVICE_PREFIX):].split()[0]))[:chips]
    if len(planes) < chips:
        raise ValueError(f"the trace has {len(planes)} device planes, the cell "
                         f"uses {chips}")
    return planes


def reduce(events: Sequence[Event], *, chips: int, window: str,
           ops_line: str = OPS_LINE) -> Dict:
    lo, hi = window_bounds(events, window)
    planes = device_planes(events, chips)
    spans = sorted((e.start_ns, e.end_ns, e.name) for e in events
                   if not e.plane.startswith(DEVICE_PREFIX)
                   and SPAN_RE.match(e.name))
    span_starts = [s[0] for s in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)

    busy_ns, op_ns, gap_ns = [], {}, {}
    for plane in planes:
        ops = [(e.start_ns, e.end_ns, e.name) for e in events
               if e.plane == plane and e.line == ops_line]
        clipped = []
        for s, e, name in ops:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                clipped.append((s, e))
                op_ns[_fold(name)] = op_ns.get(_fold(name), 0.0) + (e - s)
        merged = union(clipped)
        busy_ns.append(sum(e - s for s, e in merged))
        for gs, ge in gaps(merged, lo, hi):
            covered = 0.0
            i = bisect.bisect_left(span_starts, gs - longest)
            while i < len(spans) and spans[i][0] < ge:
                s, e, name = spans[i]
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    gap_ns[name] = gap_ns.get(name, 0.0) + ov
                    covered += ov
                i += 1
            rest = (ge - gs) - covered
            if rest > 0:
                gap_ns["outside_spans"] = gap_ns.get("outside_spans", 0.0) + rest

    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / len(planes) * 1e-9
    per_chip = 1e-9 / len(planes)
    op_s = {k: v * per_chip for k, v in op_ns.items()}
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_per_chip": [b * 1e-9 for b in busy_ns],
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "op_s": op_s,
        "device_ops": _top(op_s),
        "idle_gaps": _top({k: v * per_chip for k, v in gap_ns.items()}),
        "n_device_ops": sum(1 for e in events if e.plane in planes
                            and e.line == ops_line),
    }
