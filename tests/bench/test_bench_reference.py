"""bench/reference.py tied to the program as it stands, at a tiny size on
the CPU, and the seeded inputs that both sides read."""

import hashlib
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import compare, harness, reference, spec as specmod

SEED = 2**33 + 12345  # past 32 bits, as the benchmark's seeds may be


def test_reference_loss_matches_the_programs(tiny):
    from bench.program import build_model

    weights = specmod.make_weights(tiny, SEED)
    batch = specmod.make_batches(tiny, SEED)[0]
    ours = float(specmod.arch(tiny).loss(tiny, weights, batch, reference.dot))
    model = build_model(tiny, None)
    theirs = float(model.reference_loss(weights, batch))
    assert ours == pytest.approx(theirs, rel=1e-5)


def test_weights_have_the_programs_layout(tiny):
    from bench.program import build_model

    from repro.runtime.mtmodel import MTModel

    model = build_model(tiny, None)
    ours = jax.eval_shape(lambda: specmod.make_weights(tiny, 1))
    theirs = jax.eval_shape(lambda k: MTModel.init(model, k),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(ours)] == [
        a.shape for a in jax.tree.leaves(theirs)]


def test_reference_steps_match_the_sessions(tiny):
    """Three SpindleSession.step() calls against three reference steps:
    losses, first gradient and parameter change agree to float32 rounding."""
    session, prog = harness.program_readings(tiny, SEED, chips=1)
    assert len(session.history) == harness.CHECK_STEPS
    prog["delta"] = harness.change_readings(session, tiny, SEED)
    ref = harness.reference_readings(tiny, SEED)
    nums = compare.numbers(prog, ref)
    assert nums["loss_gap"] < 1e-5
    assert nums["grad_gap"] < 1e-4
    assert nums["update_gap"] < 1e-3
    assert ref["losses"][2] != ref["losses"][0]  # the steps see new rows


_DIGEST = """
import hashlib, sys
sys.path[:0] = {paths!r}
import jax, numpy as np
from conftest import tiny_spec
from bench import spec
s = tiny_spec("ofasys", "4task_b2")
h = hashlib.sha256()
for x in jax.tree.leaves((spec.make_weights(s, {seed}), spec.make_batches(s, {seed}))):
    h.update(np.asarray(x).tobytes())
print(h.hexdigest())
"""


def test_inputs_from_the_seed_alone():
    """Two processes (each with its own hash seed) make the same bytes."""
    here = os.path.dirname(__file__)
    code = _DIGEST.format(paths=[here] + sys.path, seed=SEED)
    outs = []
    for hashseed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=hashseed, JAX_PLATFORMS="cpu")
        outs.append(subprocess.run([sys.executable, "-c", code], env=env,
                                   capture_output=True, text=True, check=True,
                                   timeout=300).stdout.split()[-1])
    assert outs[0] == outs[1]
    from conftest import tiny_spec

    s = tiny_spec("ofasys", "4task_b2")
    h = hashlib.sha256()
    for x in jax.tree.leaves((specmod.make_weights(s, SEED),
                              specmod.make_batches(s, SEED))):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == outs[0]
    other = specmod.make_batches(s, SEED + 1)[0]["caption"]["tokens"]
    assert not np.array_equal(other, specmod.make_batches(s, SEED)[0]["caption"]["tokens"])
