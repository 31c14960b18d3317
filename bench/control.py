"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --seed <first> --seeds 12 \
        --control-seeds 3 --fault-seeds 3 [--seconds <s>] [--out <file.json>]

In one process, on the cell's chips and at its own sizes, for each seed: a
whole run of the program (first steps, warm-up and a window of ``--seconds``,
by default the benchmark's ``run_seconds``) against the reference that
follows all of its steps (the sound readings); on the first
``--control-seeds`` seeds the control (the reference with its matrix
products in three bfloat16 passes, one precision below the configuration's)
over as many steps, against the same reference; and on the first
``--fault-seeds`` seeds a run with the half-batch fault of
``bench/faults.py`` planted. A state left unchanged reads 1 by construction
and is not run. ``--out`` keeps every row with the raw per-step losses and per-leaf
norms behind it. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(cell, seed0: int, n: int, n_control: int, n_fault: int,
             seconds: float, log=print):
    from bench import compare, faults, harness, spec as specmod

    spec = specmod.load_spec(cell["config"], cell["traffic"])
    chips = int(cell["chips"])
    compiles = harness.CompileCounter()
    quiet = lambda s: None  # noqa: E731

    def run(seed):
        prog, _ = harness.drive(spec, seed, chips, seconds, compiles,
                                t_start=time.perf_counter(), log=quiet)
        return prog, len(prog["losses"])

    def raw(r):
        return {k: [float(x) for x in r[k]]
                for k in ("losses", "grad", "delta", "grad_raw") if k in r}

    rows = []
    for i in range(n):
        seed = seed0 + i
        prog, steps = run(seed)
        ref = harness.reference_readings(spec, seed, steps)
        row = {"seed": seed, "steps": steps,
               "program": compare.numbers(prog, ref)}
        raws = {"program": raw(prog), "reference": raw(ref)}
        if i < n_control:
            low = harness.reference_readings(spec, seed, steps, control=True)
            row["control"] = compare.numbers(low, ref)
            raws["control"] = raw(low)
        if i < n_fault:
            with faults.planted("half_batch"):
                bad, bad_steps = run(seed)
            row["half_batch"] = compare.numbers(
                bad, harness.reference_readings(spec, seed, bad_steps))
        log(json.dumps(row))
        rows.append(dict(row, raw=raws))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--out", type=pathlib.Path)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness

    bench, cell = harness.prepare(args.workload)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    rows = readings(cell, args.seed, args.seeds, args.control_seeds,
                    args.fault_seeds, seconds, log=lambda s: print(s, flush=True))
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
