"""Reduction of the program's own profiler spans to phase times and idle.

The program opens ``jax.profiler.TraceAnnotation``s named ``spindle.<phase>``
or ``spindle.<phase>:<instance>`` around the phases of a training step
(``repro/session.py``, ``repro/runtime/engine.py``, ``repro/optim/adamw.py``).
``program_spans`` takes flat events (``bench/trace.load``'s or ``load``'s)
and, inside the measured window (the host span ``window``), with each span
clipped to it:

- cuts the time the spans cover into stretches, each owned by the innermost
  span over it, the covering span that started latest: a span's self time
  is what it owns, its time less what its ``spindle.*`` children cover;
- per phase (the name before ``:``): self time in ms and the number of
  spans, per traced step, and the device idle time owned by its spans, in
  seconds per chip;
- ``idle_by_span``: the ten full span names that own the most idle time,
  seconds per chip, as ``reduce``'s ``idle_gaps`` gives them;
- ``covered_pct``: the share of the devices' idle time in the window that
  some ``spindle.*`` span covers.

Idle time is what ``bench/trace.reduce`` counts: the stretches of the window
in which no op of the chip's ``XLA Ops`` line runs. Host spans of other
names, the benchmark's own among them, are ignored here, even where they
overlap the program's out of order.

The harness reduces a traced run's spans once, from the events it loaded
for ``bench/trace.reduce``, and hands them to the per-layer readers as
``ctx["spans"]``; the readers call ``phase_ms``.
``python3 -m bench.spans --steps <n> [--chips <n>] [<trace dir>]`` prints
the reduction of a trace as JSON, by default of the one ``bench/run.py``
leaves under ``.bench_out/trace``, read by ``load``.
"""

from __future__ import annotations

import argparse
import heapq
import json
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

from . import spec as specmod
from . import trace
from .trace import DEVICE_PREFIX, OPS_LINE, WINDOW, Event

PREFIX = "spindle."
#: where ``bench/run.py`` has the harness write a traced run's trace
TRACE_DIR = specmod.ROOT / ".bench_out" / "trace"


def phase(name: str) -> str:
    """``spindle.bwd:vision`` -> ``spindle.bwd``."""
    return name.split(":", 1)[0]


def owned_stretches(spans: Sequence[Tuple[float, float, str]]
                    ) -> List[Tuple[float, float, str]]:
    """``(start, end, name)`` stretches, in order, each owned by the covering
    span that started latest (of two that started together, the one that
    ends first). Time no span covers is left out."""
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    heap: List[Tuple[float, float, int]] = []
    out: List[Tuple[float, float, str]] = []
    k = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while k < len(order) and spans[order[k]][0] <= t0:
            i = order[k]
            heapq.heappush(heap, (-spans[i][0], spans[i][1], i))
            k += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if not heap:
            continue
        name = spans[heap[0][2]][2]
        if out and out[-1][2] == name and out[-1][1] == t0:
            out[-1] = (out[-1][0], t1, name)
        else:
            out.append((t0, t1, name))
    return out


def load(trace_dir: pathlib.Path) -> List[Event]:
    """The events of the newest ``.xplane.pb`` under ``trace_dir`` that
    ``program_spans`` reads: host spans named ``WINDOW`` or ``spindle.*`` and
    the device planes' ``OPS_LINE``. With the Python tracer on, a traced
    window holds millions of other host events; skipping them before an
    ``Event`` is made takes a small part of ``trace.load``'s time."""
    import jax

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    out = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                name = e.name
                if device or name == WINDOW or name.startswith(PREFIX):
                    s = float(e.start_ns)
                    out.append(Event(plane.name, line.name, name, s,
                                     s + float(e.duration_ns)))
    return out


def program_spans(events: Sequence[Event], *, window: str, chips: int,
                  steps: int, ops_line: str = OPS_LINE) -> Dict:
    lo, hi = trace.window_bounds(events, window)
    spans = []
    for e in events:
        if e.name.startswith(PREFIX) and not e.plane.startswith(DEVICE_PREFIX):
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                spans.append((s, t, e.name))
    stretches = owned_stretches(spans)
    planes = trace.device_planes(events, chips)

    self_ns: Dict[str, float] = {}
    for s, t, name in stretches:
        self_ns[phase(name)] = self_ns.get(phase(name), 0.0) + (t - s)
    count: Dict[str, int] = {}
    for _, _, name in spans:
        count[phase(name)] = count.get(phase(name), 0) + 1

    idle_ns: Dict[str, float] = {}
    idle_total = 0.0
    for plane in planes:
        busy = trace.union((max(e.start_ns, lo), min(e.end_ns, hi))
                           for e in events
                           if e.plane == plane and e.line == ops_line)
        j = 0
        for gs, ge in trace.gaps(busy, lo, hi):
            idle_total += ge - gs
            while j < len(stretches) and stretches[j][1] <= gs:
                j += 1
            i = j
            while i < len(stretches) and stretches[i][0] < ge:
                s, t, name = stretches[i]
                ov = min(t, ge) - max(s, gs)
                if ov > 0:
                    idle_ns[name] = idle_ns.get(name, 0.0) + ov
                i += 1

    per_chip = 1e-9 / len(planes)
    idle_phase: Dict[str, float] = {}
    for name, v in idle_ns.items():
        idle_phase[phase(name)] = idle_phase.get(phase(name), 0.0) + v * per_chip
    covered = sum(idle_ns.values())
    return {
        "phases": {p: {"ms": self_ns.get(p, 0.0) * 1e-6 / steps,
                       "count": count[p] / steps,
                       "idle_s": idle_phase.get(p, 0.0)}
                   for p in sorted(count)},
        "idle_by_span": trace._top({k: v * per_chip for k, v in idle_ns.items()}),
        "idle_s": idle_total * per_chip,
        "covered_pct": 100.0 * covered / idle_total if idle_total > 0 else None,
    }


def phase_ms(ctx: Dict, *phases: str) -> Optional[float]:
    """Self ms per traced step summed over ``phases``; ``None`` where the
    run was not traced or has no span of any of them."""
    red = ctx.get("spans")
    if red is None or not any(p in red["phases"] for p in phases):
        return None
    return sum(red["phases"][p]["ms"] for p in phases if p in red["phases"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", nargs="?", default=str(TRACE_DIR))
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    red = program_spans(load(pathlib.Path(args.trace_dir)),
                        window=WINDOW, chips=args.chips, steps=args.steps)
    print(json.dumps(red))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
