"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of a run after the harness's look for a chip
(``harness.run_cell``) at a tiny size on the CPU, with the cell's own
limits: a sound run is correct; a step that returns its state unchanged, or
one that leaves half of every batch out, is not; nor is the control, put in
the program's place."""

import pathlib

import pytest

from bench import compare, faults, harness, spec as specmod
from conftest import tiny_spec

BENCH = specmod.load_benchmark()
ONE_CHIP = [c for c in BENCH["workloads"] if c["chips"] == 1]
SEED = 2**31 + 99


@pytest.fixture(scope="module")
def compiles():
    return harness.CompileCounter()


def _run(cell, compiles, tmp, fault=None):
    spec = tiny_spec(cell["config"], cell["traffic"])
    kw = dict(t_start=0.0, compiles=compiles, out_dir=pathlib.Path(tmp),
              log=lambda s: None, spec=spec)
    if fault is None:
        return harness.run_cell(BENCH, cell, SEED, 0.01, False, **kw)
    with faults.planted(fault):
        return harness.run_cell(BENCH, cell, SEED, 0.01, False, **kw)


@pytest.mark.parametrize("cell", ONE_CHIP, ids=lambda c: c["name"])
def test_sound_run_is_correct(cell, compiles, tmp_path):
    r = _run(cell, compiles, tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"step_s", "setup_s"}


@pytest.mark.parametrize("fault", faults.kinds(1))
@pytest.mark.parametrize("cell", ONE_CHIP, ids=lambda c: c["name"])
def test_planted_fault_is_not_correct(cell, fault, compiles, tmp_path):
    r = _run(cell, compiles, tmp_path, fault)
    assert not r["correct"], r["checks"]


def test_no_exchange_has_no_effect_on_one_chip(compiles, tmp_path):
    """One chip moves nothing between device groups, so the exchange left
    out changes no number: a one-chip cell cannot have that fault."""
    assert faults.kinds(1) == ("unchanged", "half_batch")
    assert "no_exchange" in faults.kinds(4)
    cell = ONE_CHIP[0]
    assert _run(cell, compiles, tmp_path, "no_exchange")["checks"] == \
        _run(cell, compiles, tmp_path)["checks"]


@pytest.mark.parametrize("cell", ONE_CHIP, ids=lambda c: c["name"])
def test_control_is_not_correct(cell, compiles):
    """The control (the reference with its matrix products in three
    bfloat16 passes), put in the program's place over the steps of a run,
    fails the cell's committed limits, and reads far above the sound
    program, which on the CPU is float32-exact."""
    spec = tiny_spec(cell["config"], cell["traffic"])
    limits = specmod.read_json(specmod.BENCH / "limits" / f"{cell['name']}.json")
    prog, _ = harness.drive(spec, SEED, 1, 0.01, compiles, t_start=0.0,
                            log=lambda s: None)
    steps = len(prog["losses"])
    ref = harness.reference_readings(spec, SEED, steps)
    control = compare.numbers(
        harness.reference_readings(spec, SEED, steps, control=True), ref)
    assert not compare.judge(control, limits)["correct"], control
    sound = compare.numbers(prog, ref)
    assert compare.judge(sound, limits)["correct"], sound
    assert max(control[k] / max(sound[k], 1e-12) for k in sound) >= 100, (
        sound, control)
