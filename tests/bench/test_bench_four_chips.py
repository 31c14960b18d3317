"""A run across four chips, on four virtual CPU devices: the whole of a run
after the harness's look for a chip (``harness.run_cell``) of the
``multitask_clip`` one-chip cell moved onto four chips, at a tiny size and
with that cell's limits, places the plan's steps on device groups; a sound
run is correct, and neither the exchange between chips left out nor half of
every batch left out is; nor is the control, put in the program's place.

The device count is forced before JAX starts, so the runs are made in one
subprocess and the tests read its result."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import spec as specmod

ROOT = pathlib.Path(__file__).resolve().parents[2]
#: the one-chip cell moved onto four chips
CELL = next(c["name"] for c in specmod.load_benchmark(ROOT)["workloads"]
            if c["config"] == "multitask_clip")
SEED = 2**31 + 77

_SCRIPT = """
import contextlib, json, pathlib, sys
sys.path[:0] = [{tests!r}, {root!r}, {src!r}]
import jax
from conftest import tiny_spec
from bench import compare, faults, harness, spec as specmod
bench = specmod.load_benchmark()
cell = dict(specmod.find_cell(bench, {cell!r}), chips=4)
spec = tiny_spec(cell["config"], cell["traffic"])
compiles = harness.CompileCounter()
out = {{"devices": jax.device_count()}}
for kind in ("sound", "no_exchange", "half_batch"):
    kw = dict(t_start=0.0, compiles=compiles, log=lambda s: None, spec=spec,
              out_dir=pathlib.Path({tmp!r}))
    with faults.planted(kind) if kind != "sound" else contextlib.nullcontext():
        r = harness.run_cell(bench, cell, {seed}, 0.01, False, **kw)
    out[kind] = {{"correct": r["correct"], "checks": r["checks"],
                 "count": r["device"]["count"], "metrics": sorted(r["metrics"])}}
    if kind == "sound":
        out["steps"] = r["compared_steps"]
ref = harness.reference_readings(spec, {seed}, out["steps"])
low = harness.reference_readings(spec, {seed}, out["steps"], control=True)
limits = specmod.read_json(specmod.BENCH / "limits" / f"{{cell['name']}}.json")
out["control"] = compare.judge(compare.numbers(low, ref), limits)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    code = _SCRIPT.format(tests=str(ROOT / "tests" / "bench"), root=str(ROOT),
                          src=str(ROOT / "src"), cell=CELL,
                          seed=SEED, tmp=str(tmp_path_factory.mktemp("out")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_on_four_devices_is_correct(runs):
    assert runs["devices"] == 4
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert r["count"] == 4 and r["metrics"] == ["setup_s", "step_s"]


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch"])
def test_fault_on_four_devices_is_not_correct(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]


def test_control_on_four_devices_is_not_correct(runs):
    assert not runs["control"]["correct"], runs["control"]["checks"]
