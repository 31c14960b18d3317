"""One run of one cell: set-up, the first steps, the warm-up, the measured
window, the comparison of every step with the reference, and the result line.

``run_cell`` is what ``bench/run.py`` calls on the chip; the tests call it on
the CPU at a tiny size. Everything specific to a cell is found by name:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/limits/<cell>.json``, ``bench/metrics/<metric>.py``, and the code of
the configuration's architecture, ``bench/arch/<arch>/`` (``spec.arch``).

A metric's reader gets one dict, ``ctx``: the cell's ``spec``, ``chips``,
``setup_s``, ``step_s``, ``steps`` and ``window_s`` of the window and its
``flops_per_step``; in a traced run also the host spans' ``plan_ms``,
``fwd_ms`` and ``bwd_ms``, ``peak_flops``, ``trace`` (``bench/trace.reduce``:
busy and idle, and ``op_s``, the seconds per chip of every device operation
by its folded name) and ``spans`` (``bench/spans.program_spans``: the
program's own spans, reduced once; ``None`` where the program opened none).
"""

from __future__ import annotations

import gc
import math
import pathlib
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import jax

from . import compare, flops, reference, spans as spansmod, spec as specmod
from . import trace as tracemod

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: steps before the warm-up (the first gradient is read after the first)
CHECK_STEPS = 3
#: further steps, at most, until one compiles nothing
WARM_STEPS = 3


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) in this
    process from the moment it is made."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1


def load_reader(name: str) -> Callable[[Dict], Optional[float]]:
    return specmod.load_file(specmod.BENCH / "metrics" / f"{name}.py",
                             f"bench_metric_{name}").read


def metrics_for(bench: Dict, cell: str, traced: bool) -> List[Dict]:
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def peak_flops(kind: str) -> float:
    peaks = specmod.read_json(specmod.BENCH / "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return float(peaks[kind]["bf16_flops_per_s"])


# ------------------------------------------------------------------ phases
def prepare(workload: str):
    """What an entry point on the chip does first: the program on the path,
    JAX's persistent compilation cache in the checkout (every compile kept,
    the eager primitives' too: by default only those over 1 s), the cell by
    name, and the look for its chips. Returns ``(benchmark, cell)``; exits
    with code 2, having printed no result, where JAX finds no TPU or fewer
    chips than the cell asks for."""
    src = str(specmod.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from . import program

    program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = specmod.load_benchmark()
    cell = specmod.find_cell(bench, workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {workload} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        raise SystemExit(2)
    return bench, cell


def program_readings(spec: Dict, seed: int, chips: int, spans=None):
    """Bind the session and run the checked first steps through its own
    ``step()`` and data cursor. Returns the live session (handed on to the
    warm-up and the window) and its losses and first gradient so far."""
    from . import program

    pool = specmod.make_batches(spec, seed)
    session = program.open_session(spec, specmod.make_weights(spec, seed),
                                   pool, chips, spans)
    losses = []
    for i in range(CHECK_STEPS):
        losses.append(session.step())
        if i == 0:
            grad = program.first_grad_norms(session, spec["optimizer"]["b1"])
    return session, {"losses": losses, "grad": grad}


def change_readings(session, spec: Dict, seed: int):
    """Per-leaf norms of the session's parameter change since the seed's."""
    from . import program

    return program.change_norms(session, specmod.make_weights(spec, seed))


def warm(session, compiles: CompileCounter) -> List[float]:
    """Step until a step compiles nothing; returns the steps' losses."""
    losses = []
    for _ in range(WARM_STEPS):
        n0 = compiles.n
        losses.append(session.step())
        if compiles.n == n0:
            break
    return losses


def measure(session, seconds: float, compiles: CompileCounter, spans=None
            ) -> Dict[str, Any]:
    """Step for ``seconds``; the window ends when parameters and optimizer
    state are ready."""
    from . import program

    jax.block_until_ready(program.state(session))
    n0 = compiles.n
    losses = []
    with jax.profiler.TraceAnnotation(tracemod.WINDOW):
        t0 = time.perf_counter()
        while True:
            if spans is not None:
                spans.begin_step()
            losses.append(session.step())
            if spans is not None:
                spans.end_step()
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(program.state(session))
        t1 = time.perf_counter()
    return {"window_s": t1 - t0, "steps": len(losses), "losses": losses,
            "compiles": compiles.n - n0}


def drive(spec: Dict, seed: int, chips: int, seconds: float,
          compiles: CompileCounter, *, t_start: float, spans=None,
          trace_dir: Optional[pathlib.Path] = None,
          log: Callable[[str], None] = print):
    """One session from the seed through the checked steps, the warm-up and
    the window; the session is freed on return. Returns the program's
    readings over every step it took (losses, first gradient, parameter
    change) and the window's record, with ``setup_s`` and the peak memory
    read before anything else is put on the chips. The program runs at the
    matmul precision the configuration states."""
    with jax.default_matmul_precision(spec["precision"]):
        return _drive(spec, seed, chips, seconds, compiles, t_start, spans,
                      trace_dir, log)


def _drive(spec, seed, chips, seconds, compiles, t_start, spans, trace_dir,
           log):
    session, prog = program_readings(spec, seed, chips, spans)
    prog["losses"] += warm(session, compiles)
    log(f"set-up: {len(prog['losses'])} steps before the window, "
        f"{compiles.n} compilations so far")
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        spans.recording = True
    setup_s = time.perf_counter() - t_start
    win = measure(session, seconds, compiles, spans)
    if trace_dir is not None:
        spans.recording = False
        jax.profiler.stop_trace()
    log(f"window: {win['steps']} steps in {win['window_s']:.3f} s, "
        f"{win['compiles']} compilations inside it")
    win["setup_s"] = setup_s
    win["memory_peak_bytes"] = memory_peak(jax.devices()[:chips])
    prog["losses"] += win["losses"]
    prog["delta"] = change_readings(session, spec, seed)
    del session
    gc.collect()
    return prog, win


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def reference_readings(spec: Dict, seed: int, steps: int = CHECK_STEPS,
                       control: bool = False) -> Dict:
    """The reference's steps (or the control's) from the same seed, weights
    and batches made anew, the batches cycled as the program's data cursor
    cycles them."""
    return reference.readings(spec, specmod.make_weights(spec, seed),
                              specmod.make_batches(spec, seed), steps, control)


def trace_context(events, chips: int, steps: int) -> Dict[str, Any]:
    """The readers' ``trace`` and ``spans`` from one traced window's
    events, each reduced once."""
    red = tracemod.reduce(events, chips=chips, window=tracemod.WINDOW)
    prog = spansmod.program_spans(events, window=tracemod.WINDOW, chips=chips,
                                  steps=steps)
    return {"trace": red, "spans": prog if prog["phases"] else None}


def check_lines(checks: Dict[str, Dict]) -> List[str]:
    return [f"check {k}: {c['value']!r} limit {c['limit']!r}"
            for k, c in checks.items()]


# --------------------------------------------------------------------- run
def run_cell(bench: Dict, cell: Dict, seed: int, seconds: float, traced: bool,
             *, t_start: float, compiles: CompileCounter,
             out_dir: pathlib.Path, log: Callable[[str], None] = print,
             spec: Optional[Dict] = None, limits: Optional[Dict] = None) -> Dict:
    """One run; returns the result object of the contract's last line.
    ``spec`` and ``limits`` default to the cell's own files."""
    from . import program

    if spec is None:
        spec = specmod.load_spec(cell["config"], cell["traffic"])
    if limits is None:
        limits = specmod.read_json(
            specmod.BENCH / "limits" / f"{cell['name']}.json")
    chips = int(cell["chips"])
    spans = program.HostSpans(annotate=True) if traced else None
    prog, win = drive(spec, seed, chips, seconds, compiles, t_start=t_start,
                      spans=spans, trace_dir=out_dir / "trace" if traced else None,
                      log=log)
    ref = reference_readings(spec, seed, len(prog["losses"]))
    verdict = compare.judge(compare.numbers(prog, ref), limits)
    failed = sum(1 for x in win["losses"] if not math.isfinite(x))

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": win["memory_peak_bytes"]}
    ctx: Dict[str, Any] = {
        "spec": spec,
        "setup_s": win["setup_s"],
        "step_s": win["window_s"] / win["steps"],
        "steps": win["steps"],
        "window_s": win["window_s"],
        "chips": chips,
        "flops_per_step": flops.step_flops(spec),
        "plan_ms": spans.plan_ms if traced else None,
        "fwd_ms": spans.fwd_ms if traced else [],
        "bwd_ms": spans.bwd_ms if traced else [],
    }
    result: Dict[str, Any] = {
        "correct": verdict["correct"] and failed == 0,
        "attempted": win["steps"],
        "failed": failed,
        "compared_steps": len(prog["losses"]),
    }
    if traced:
        ctx.update(trace_context(tracemod.load(out_dir / "trace"), chips,
                                 win["steps"]))
        red, prog_spans = ctx["trace"], ctx["spans"]
        ctx["peak_flops"] = peak_flops(dev0.device_kind)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"trace: {red['n_device_ops']} device ops, busy {red['busy_s']!r} s "
            f"of {red['window_s']!r} s per chip")
        if prog_spans is not None:
            log(f"trace: program spans cover {prog_spans['covered_pct']!r}% of "
                f"the idle time; idle by span {prog_spans['idle_by_span']!r}")
    metrics = {}
    for m in metrics_for(bench, cell["name"], traced):
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = verdict["checks"]
    return result
