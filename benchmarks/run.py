"""Benchmark driver: one module per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--json out.json]
    PYTHONPATH=src python -m benchmarks.run --smoke --check-keys   # CI job

Besides the aggregate ``--json`` dump, every bench writes a
machine-readable ``BENCH_<name>.json`` at the repo root
(schema: ``{"bench": ..., "rows": [...], "seconds": ...}``) so the perf
trajectory is tracked across PRs.  ``--smoke`` runs the quick subset
(dynamicity + planner_cost + serving) on reduced grids, writing its BENCH
files to a temp dir — or ``--out-dir`` (the CI artifact path) — so the
committed trajectories are never clobbered; ``--check-keys`` diffs the
regenerated rows' metric keys against the committed trajectory files and
fails if any committed metric went missing.
"""

from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import sys
import tempfile
import time

from repro.launch.compile_cache import enable_compile_cache

from . import (
    bench_breakdown,
    bench_case_study,
    bench_colocation,
    bench_dynamicity,
    bench_end_to_end,
    bench_estimator,
    bench_faults,
    bench_fleet,
    bench_kernels,
    bench_optimality,
    bench_planner_cost,
    bench_serving,
    roofline,
)

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

BENCHES = {
    "end_to_end": bench_end_to_end,       # Fig. 8
    "case_study": bench_case_study,       # Fig. 9
    "breakdown": bench_breakdown,         # Fig. 10
    "optimality": bench_optimality,       # Fig. 11
    "planner_cost": bench_planner_cost,   # Fig. 12
    "estimator": bench_estimator,         # Fig. 4
    "dynamicity": bench_dynamicity,       # Appendix D analogue
    "serving": bench_serving,             # continuous batching + replan
    "fleet": bench_fleet,                 # multi-tenant scheduling policies
    "colocation": bench_colocation,       # decode in training idle windows
    "faults": bench_faults,               # snapshots + crash recovery
    "kernels": bench_kernels,             # substrate
}


#: quick subset exercised by the CI benchmark smoke job
SMOKE_BENCHES = ("dynamicity", "planner_cost", "serving", "fleet",
                 "colocation", "faults")


def write_bench_json(name: str, rows, seconds: float,
                     out_dir: pathlib.Path = REPO_ROOT) -> pathlib.Path:
    """Write the per-bench perf-trajectory record (repo root by default)."""
    out = out_dir / f"BENCH_{name}.json"
    with open(out, "w") as f:
        json.dump(
            {"bench": name, "rows": rows, "seconds": seconds},
            f, indent=1, default=str,
        )
    return out


def metric_keys(rows) -> set:
    """Union of row metric keys, with one level of dotted nesting
    (``cache.hit_rate``) so nested stat dicts are diffable too."""
    keys = set()
    for r in rows:
        if not isinstance(r, dict):
            continue
        for k, v in r.items():
            keys.add(k)
            if isinstance(v, dict):
                keys.update(f"{k}.{kk}" for kk in v)
    return keys


def committed_keys(name: str) -> set:
    """Metric keys of the committed BENCH_<name>.json (empty if absent)."""
    path = REPO_ROOT / f"BENCH_{name}.json"
    if not path.exists():
        return set()
    try:
        with open(path) as f:
            return metric_keys(json.load(f).get("rows", []))
    except (json.JSONDecodeError, OSError):
        return set()


def _row_ident(r: dict, idx: int) -> str:
    """Stable identity of one bench row (the non-metric descriptor fields).

    ``requests`` is part of the identity so a smoke-grid row can never be
    diffed against a full-grid row of the same policy — smoke runs compare
    against the committed ``BENCH_<name>.smoke.json`` baselines instead."""
    parts = [
        f"{k}={r[k]}"
        for k in ("bench", "workload", "policy", "devices", "slots",
                  "requests")
        if k in r
    ]
    return "|".join(parts) or f"row{idx}"


def throughput_metrics(rows) -> dict:
    """Machine-portable throughput metrics of one bench's rows.

    Absolute timings and tok/s move with the machine, so the regression
    gate compares *relative* metrics only: explicit ``speedup_*`` keys,
    top-level ``*hit_rate*`` keys, ``kv_compression`` (logical/physical
    KV page ratio — a pure dedup measure), ``goodput`` and
    ``token_exact`` (fault-tolerance fractions: useful/executed steps and
    lossless-recovery, both exact counting identities), and each row's
    ``throughput_tok_s`` normalized to the first throughput-carrying row
    of the same run (e.g. continuous batching's gain over the static
    baseline).  All are higher-is-better.  Nested cache-stat dicts are
    deliberately excluded — per-replan cache composition varies run to
    run; the speedups it feeds are the stable signal.
    """
    out: dict = {}
    base_tp = None
    for i, r in enumerate(rows):
        if not isinstance(r, dict):
            continue
        ident = _row_ident(r, i)
        for k, v in r.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            if ("speedup" in k or "hit_rate" in k
                    or k in ("kv_compression", "goodput", "token_exact")):
                out[f"{ident}.{k}"] = float(v)
            elif k == "throughput_tok_s" and v > 0:
                if base_tp is None:
                    base_tp = float(v)
                out[f"{ident}.throughput_rel"] = float(v) / base_tp
    return out


def regression_check(name: str, rows, baseline_dir: pathlib.Path,
                     tolerance: float, *, suffix: str = "") -> list:
    """Regressions of ``rows`` vs ``<baseline_dir>/BENCH_<name><suffix>.json``.

    A metric regresses when the regenerated value drops below
    ``baseline * (1 - tolerance)`` — or when it is MISSING from the
    regenerated rows (baselines are same-grid by construction: smoke runs
    pass ``suffix=".smoke"`` to diff against the committed smoke-grid
    baselines, full runs diff against the full-grid trajectory files — so
    a vanished metric is a collapse, not a grid difference; regenerate +
    recommit the baselines when the grid itself changes deliberately).
    A missing baseline FILE is a visible skip (new benches legitimately
    have none yet); an unreadable one fails the gate."""
    path = baseline_dir / f"BENCH_{name}{suffix}.json"
    if not path.exists():
        print(f"[benchmarks] WARNING: no baseline {path.name} — "
              f"regression gate skipped for {name}", file=sys.stderr)
        return []
    try:
        with open(path) as f:
            base = throughput_metrics(json.load(f).get("rows", []))
    except (json.JSONDecodeError, OSError) as e:
        # an unreadable baseline must FAIL the gate, not vacuously pass it
        return [f"baseline {path.name} unreadable: {e}"]
    new = throughput_metrics(rows)
    bad = []
    for key, ref in sorted(base.items()):
        if ref <= 0:
            continue
        got = new.get(key)
        if got is None:
            bad.append(f"{key}: missing from regenerated rows "
                       f"(baseline {ref:.4f})")
        elif got < ref * (1.0 - tolerance):
            bad.append(f"{key}: {got:.4f} < {ref:.4f} * (1 - {tolerance:g})")
    return bad


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    ap.add_argument("--json", default="bench_results.json")
    ap.add_argument("--dryrun-records", default="dryrun_records.json")
    ap.add_argument("--smoke", action="store_true",
                    help="quick subset (dynamicity + planner_cost + "
                         "serving) on reduced grids")
    ap.add_argument("--check-keys", action="store_true",
                    help="fail when regenerated rows drop metric keys "
                         "present in the committed BENCH_<name>.json")
    ap.add_argument("--baseline", nargs="?", const=str(REPO_ROOT),
                    default=None, metavar="DIR",
                    help="fail on throughput REGRESSION vs the committed "
                         "BENCH_<name>.json files in DIR (default: repo "
                         "root) — relative metrics only (speedups, hit "
                         "rates, normalized throughput), see --tolerance")
    ap.add_argument("--tolerance", type=float, default=0.3,
                    help="--baseline slack: a metric regresses when it "
                         "drops below baseline * (1 - tolerance)")
    ap.add_argument("--out-dir", default=None,
                    help="directory for the BENCH_<name>.json files "
                         "(default: repo root; --smoke: a temp dir) — CI "
                         "points this at its artifact upload path")
    args = ap.parse_args()
    enable_compile_cache()

    all_rows = []
    if args.only:
        names = [args.only]
    elif args.smoke:
        names = list(SMOKE_BENCHES)
    else:
        names = list(BENCHES)
    # smoke rows are reduced-grid: never clobber the committed trajectory
    # files — the key diff still runs against the committed baselines
    if args.out_dir:
        out_dir = pathlib.Path(args.out_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
    elif args.smoke:
        out_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench_smoke_"))
    else:
        out_dir = REPO_ROOT
    missing: dict = {}
    regressions: dict = {}
    for name in names:
        mod = BENCHES[name]
        baseline = committed_keys(name) if args.check_keys else set()
        print(f"\n=== {name} " + "=" * (60 - len(name)))
        t0 = time.perf_counter()
        kwargs = (
            {"smoke": True}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters
            else {}
        )
        rows = mod.run(**kwargs)  # single execution; main() renders the rows
        seconds = time.perf_counter() - t0
        mod.main(rows)
        for r in rows:
            all_rows.append(r)
        out = write_bench_json(name, rows, seconds, out_dir)
        print(f"--- {name} done in {seconds:.1f}s -> {out}")
        if args.check_keys:
            lost = baseline - metric_keys(rows)
            if lost:
                missing[name] = sorted(lost)
        if args.baseline:
            bad = regression_check(
                name, rows, pathlib.Path(args.baseline), args.tolerance,
                suffix=".smoke" if args.smoke else "",
            )
            if bad:
                regressions[name] = bad

    if args.check_keys:
        if missing:
            for name, lost in missing.items():
                print(f"[benchmarks] BENCH_{name}.json lost metrics: {lost}",
                      file=sys.stderr)
            raise SystemExit(1)
        print(f"[benchmarks] key check OK for {', '.join(names)}")
    if args.baseline:
        if regressions:
            for name, bad in regressions.items():
                for line in bad:
                    print(f"[benchmarks] BENCH_{name}.json regression: {line}",
                          file=sys.stderr)
            raise SystemExit(1)
        print(f"[benchmarks] regression check OK for {', '.join(names)} "
              f"(tolerance {args.tolerance:g})")

    if not args.only and not args.smoke:
        print("\n=== roofline " + "=" * 52)
        rrows = roofline.run(args.dryrun_records)
        if rrows:
            print(roofline.format_table(rrows, mesh="16x16"))
            all_rows.extend({k: v for k, v in r.items()} for r in rrows)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(all_rows, f, indent=1, default=str)
        print(f"\n[benchmarks] wrote {len(all_rows)} rows to {args.json}")


if __name__ == "__main__":
    main()
