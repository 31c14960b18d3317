"""The architectures' FLOPs (``forward_flops``, and ``bench/flops.py``'s step)
against what XLA and the jaxpr count for the program's own forward pass, at
a tiny size on the CPU, for every configuration of BENCHMARK.json."""

import math

import jax
import pytest

from bench import flops, spec as specmod
from conftest import CONFIG_CELLS, CONFIG_IDS, tiny_spec


def _dot_flops(jaxpr) -> float:
    """2 x (batch x free x contracted) summed over every dot_general,
    sub-jaxprs included."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, rc), (lb, _) = eqn.params["dimension_numbers"]
            a, b = (v.aval.shape for v in eqn.invars)
            contracted = math.prod(a[i] for i in lc)
            out = math.prod(eqn.outvars[0].aval.shape)
            total += 2.0 * out * contracted
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _dot_flops(sub)
    return total


@pytest.fixture(scope="module", params=CONFIG_CELLS, ids=CONFIG_IDS)
def forward(request):
    """The tiny spec and the program's whole-model forward pass on it."""
    from bench.program import build_model

    spec = tiny_spec(*request.param)
    model = build_model(spec, None)
    shapes = jax.eval_shape(lambda: (specmod.make_weights(spec, 7),
                                     specmod.make_batches(spec, 7)[0]))
    return spec, (lambda p, b: model.reference_loss(p, b)), shapes


def test_forward_flops_match_the_programs_matmuls(forward):
    spec, fn, (weights, batch) = forward
    counted = _dot_flops(jax.make_jaxpr(fn)(weights, batch).jaxpr)
    assert specmod.arch(spec).forward_flops(spec) == pytest.approx(
        counted, rel=1e-12)


def test_forward_flops_against_xla_cost_analysis(forward):
    spec, fn, (weights, batch) = forward
    xla = float(jax.jit(fn).lower(weights, batch).cost_analysis()["flops"])
    ours = specmod.arch(spec).forward_flops(spec)
    # XLA also counts norms, softmax, RoPE and SiLU, which the model FLOPs
    # leave out: 3-4% at these widths (measured), never less than ours
    assert ours <= xla <= 1.15 * ours


def test_step_is_three_forwards(tiny):
    assert flops.step_flops(tiny) == 3 * specmod.arch(tiny).forward_flops(tiny)


def test_published_cells_per_step():
    clip = specmod.load_spec("multitask_clip", "4task_b4")
    ofa = specmod.load_spec("ofasys", "4task_b2")
    # both one-chip cells are sized to about 2.5 TFLOP a step
    assert 2.3e12 < flops.step_flops(clip) < 2.5e12
    assert 2.5e12 < flops.step_flops(ofa) < 2.7e12
