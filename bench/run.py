"""Benchmark of Spindle's multi-task training step on TPU chips.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it starts on,
in this process: binds a ``SpindleSession`` over the cell's model at its
published widths, runs the first steps and the warm-up, times
``session.step()`` for ``--seconds``, follows every step the session took
with the plain reference in ``bench/reference.py`` and compares, and prints
one JSON object as its last line of standard output (the numbers compared
also go, one per line, to the end of standard error). ``--trace 1`` traces the window and reports the
per-layer metrics in place of the end-to-end ones.

It refuses to run, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is not in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / ".bench_out"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    from bench import harness

    bench, cell = harness.prepare(args.workload)
    compiles = harness.CompileCounter()
    result = harness.run_cell(
        bench, cell, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, compiles=compiles, out_dir=OUT,
        log=lambda s: print(s, flush=True))
    for line in harness.check_lines(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
