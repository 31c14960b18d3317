"""The architectures' FLOPs (``forward_flops`` and ``routed_flops``, and
``bench/flops.py``'s step) against what XLA and the jaxpr count for the
program's own forward pass, at a tiny size on the CPU, for every
configuration of BENCHMARK.json; and the same two checks on a synthetic
routed layer that holds a share of its experts."""

import math
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import pytest

from bench import flops, spec as specmod
from conftest import CONFIG_CELLS, CONFIG_IDS, tiny_spec

#: Pallas kernels whose rows depend on the routing: the repo's grouped
#: matmul (``repro/kernels/moe_gmm.py``)
ROUTED_KERNELS = ("_gmm_kernel",)


def _kernel_name(eqn) -> str:
    info = eqn.params["jaxpr"].debug_info
    return (info.func_src_info or "").split(" ")[0] if info else ""


def _is_routed(eqn) -> bool:
    name = eqn.primitive.name
    return name == "ragged_dot_general" or (
        name == "pallas_call" and _kernel_name(eqn) in ROUTED_KERNELS)


def _eqns(jaxpr, times: int = 1) -> Iterator[Tuple[object, int]]:
    """Every equation with the number of times it runs, sub-jaxprs included
    (a scan's body as many times as its length). A Pallas kernel's body is
    not entered: its products are per block."""
    for eqn in jaxpr.eqns:
        yield eqn, times
        if eqn.primitive.name == "pallas_call":
            continue
        n = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, n)


def _product_flops(eqn) -> float:
    """2 x out x contracted of one product at its static shapes: a
    ``dot_general``, a ``ragged_dot_general`` at all of its rows, or the
    grouped-matmul kernel, ``(sizes, x (E, C, d), w (E, d, f))``, at all
    ``E x C`` of its rows."""
    name = eqn.primitive.name
    if name == "pallas_call":
        contracted = eqn.invars[1].aval.shape[-1]
    else:
        dims = (eqn.params["dimension_numbers"] if name == "dot_general" else
                eqn.params["ragged_dot_dimension_numbers"].dot_dimension_numbers)
        (lc, _), _ = dims
        a = eqn.invars[0].aval.shape
        contracted = math.prod(a[i] for i in lc)
    return 2.0 * math.prod(eqn.outvars[0].aval.shape) * contracted


def _dot_flops(jaxpr) -> Tuple[float, float]:
    """``(dense, routed)``: the FLOPs of every ``dot_general``, and apart
    from them those of the routed products at their static rows."""
    dense = routed = 0.0
    for eqn, n in _eqns(jaxpr):
        if eqn.primitive.name == "dot_general":
            dense += n * _product_flops(eqn)
        elif _is_routed(eqn):
            routed += n * _product_flops(eqn)
    return dense, routed


def _routed_xla_flops(jaxpr) -> float:
    """XLA's count of the routed products: each lowered alone at its shapes
    and costed by ``cost_analysis``. It is not their useful work (a ragged
    product of 960 FLOPs reads 4038 on the CPU), so the bound below takes it
    out of the whole program's count."""
    total = 0.0
    for eqn, n in _eqns(jaxpr):
        if _is_routed(eqn):
            args = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                    for v in eqn.invars]
            lowered = jax.jit(lambda *a, e=eqn: e.primitive.bind(*a, **e.params)
                              ).lower(*args)
            total += n * float(lowered.cost_analysis()["flops"])
    return total


def expert_share(cfg) -> float:
    """Published routed experts over those held here (the configuration's
    ``share``); 1 where it holds them all or has none."""
    keys = cfg.get("share", {}).get("experts", [])
    return math.prod(cfg[f"published_{k}"] / cfg[k] for k in keys)


def assert_counts_match(forward: float, routed: float, share: float, fn, args):
    """The model FLOPs against the program's products: ``forward -
    routed`` is its ``dot_general`` count, and ``routed`` over the share
    held here its routed products' count at their static rows (one row per
    token and chosen expert)."""
    dense, static = _dot_flops(jax.make_jaxpr(fn)(*args).jaxpr)
    assert forward - routed == pytest.approx(dense, rel=1e-12)
    assert routed * share == pytest.approx(static, rel=1e-12)


def assert_within_xla(forward: float, routed: float, fn, args):
    """XLA's count of the program, its routed products taken out, against
    the dense model FLOPs. XLA also counts norms, softmax, RoPE, SiLU and
    the routing's arithmetic, which the model FLOPs leave out: 3-4% at the
    tiny widths of the configurations (measured), never less than ours."""
    xla = float(jax.jit(fn).lower(*args).cost_analysis()["flops"])
    xla -= _routed_xla_flops(jax.make_jaxpr(fn)(*args).jaxpr)
    dense = forward - routed
    assert dense <= xla <= 1.15 * dense


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
    spec, fn, args = forward
    assert_counts_match(specmod.arch(spec).forward_flops(spec),
                        flops.routed_flops(spec),
                        expert_share(specmod.load_config(spec["name"])), fn, args)


def test_forward_flops_against_xla_cost_analysis(forward):
    spec, fn, args = forward
    assert_within_xla(specmod.arch(spec).forward_flops(spec),
                      flops.routed_flops(spec), fn, args)


def test_step_is_three_forwards(tiny):
    assert flops.step_flops(tiny) == 3 * specmod.arch(tiny).forward_flops(tiny)


def test_dense_architecture_has_no_routed_work(tiny):
    assert not hasattr(specmod.arch(tiny), "routed_flops")
    assert flops.routed_flops(tiny) == 0.0


def test_published_cells_per_step():
    clip = specmod.load_spec("multitask_clip", "4task_b4")
    ofa = specmod.load_spec("ofasys", "4task_b2")
    # both one-chip cells are sized to about 2.5 TFLOP a step
    assert 2.3e12 < flops.step_flops(clip) < 2.5e12
    assert 2.5e12 < flops.step_flops(ofa) < 2.7e12


def test_published_cells_step_flops_to_the_bit():
    """Routed work reads 0 for a dense architecture and moves no cell's
    FLOPs: ``step_mfu`` and ``busy_roofline_pct`` read as they did."""
    clip = specmod.load_spec("multitask_clip", "4task_b4")
    ofa = specmod.load_spec("ofasys", "4task_b2")
    assert flops.step_flops(clip).hex() == "0x1.17d3e84000000p+41"
    assert flops.step_flops(ofa).hex() == "0x1.2e22f42000000p+41"


# ------------------------------------------------------- routed products
def test_ragged_product_counted_apart():
    lhs, rhs = jnp.ones((12, 8)), jnp.ones((3, 8, 5))
    sizes = jnp.array([4, 4, 4], jnp.int32)
    jaxpr = jax.make_jaxpr(jax.lax.ragged_dot)(lhs, rhs, sizes).jaxpr
    # 12 static rows, each against one group's 8 x 5 matrix
    assert _dot_flops(jaxpr) == (0.0, 2.0 * 12 * 8 * 5)
    # beside a dot_general, and inside a scan's body twice
    def fn(a, b, w):
        h = jax.lax.ragged_dot(a, b, sizes)
        c, _ = jax.lax.scan(
            lambda c, _: (jax.lax.ragged_dot(c, b[:, :5, :], sizes), None),
            h, None, length=2)
        return (c @ w).sum()
    got = _dot_flops(jax.make_jaxpr(fn)(lhs, rhs, jnp.ones((5, 3))).jaxpr)
    assert got == (2.0 * 12 * 5 * 3, 2.0 * 12 * 8 * 5 + 2 * (2.0 * 12 * 5 * 5))


def test_grouped_matmul_kernel_counted_as_routed():
    from repro.kernels.moe_gmm import grouped_matmul

    x, w = jnp.ones((2, 128, 512)), jnp.ones((2, 512, 128))
    jaxpr = jax.make_jaxpr(
        lambda x, w: grouped_matmul(x, w, interpret=True))(x, w).jaxpr
    # every row of the capacity layout, none of the kernel's per-block dots
    assert _dot_flops(jaxpr) == (0.0, 2.0 * 2 * 128 * 512 * 128)


#: a routed layer that holds 2 of 8 experts, 2 chosen per token, and the
#: share its configuration would state
MOE = {"tokens": 16, "d": 64, "ff": 32, "experts": 8, "held": 2, "top_k": 2}
MOE_CFG = {"n_routed_experts": 2, "published_n_routed_experts": 8,
           "share": {"chips": 4, "holds": "2 of the 8 routed experts",
                     "experts": ["n_routed_experts"]}}


def moe_forward_flops(s) -> float:
    """The input projection and the router's scores over all experts, and
    the routed part below."""
    t, d = s["tokens"], s["d"]
    return 2.0 * t * d * d + 2.0 * t * d * s["experts"] + moe_routed_flops(s)


def moe_routed_flops(s) -> float:
    """The expert's two products on the rows routed to the experts held
    here, as expected: tokens x top_k x held / experts of them."""
    rows = s["tokens"] * s["top_k"] * s["held"] / s["experts"]
    return 2 * (2.0 * rows * s["d"] * s["ff"])


def moe_loss(p, x):
    """Scores over every expert, the top two per token; rows sorted by
    expert, those of the held experts first, and the held experts' two
    products on them (the rest lie past the group sizes and read 0)."""
    s = MOE
    h = x @ p["w_in"]
    scores = jax.nn.sigmoid(h @ p["router"])
    weight, expert = jax.lax.top_k(scores, s["top_k"])
    expert, weight = expert.reshape(-1), weight.reshape(-1)
    token = jnp.repeat(jnp.arange(s["tokens"]), s["top_k"])
    order = jnp.argsort(jnp.where(expert < s["held"], expert, s["experts"]))
    sizes = jnp.bincount(expert, length=s["experts"])[: s["held"]].astype(jnp.int32)
    rows = h[token[order]]
    up = jax.nn.silu(jax.lax.ragged_dot(rows, p["w_up"], sizes))
    down = jax.lax.ragged_dot(up, p["w_down"], sizes) * weight[order][:, None]
    out = jnp.zeros_like(h).at[token[order]].add(down)
    return jnp.mean(jnp.square(out))


@pytest.fixture(scope="module")
def moe():
    s = MOE
    shapes = {"w_in": (s["d"], s["d"]), "router": (s["d"], s["experts"]),
              "w_up": (s["held"], s["d"], s["ff"]),
              "w_down": (s["held"], s["ff"], s["d"])}
    keys = jax.random.split(jax.random.key(3), len(shapes) + 1)
    p = {k: jax.random.normal(kk, v) for (k, v), kk in zip(shapes.items(), keys)}
    return p, jax.random.normal(keys[-1], (s["tokens"], s["d"]))


def test_routed_layer_counts_match(moe):
    assert_counts_match(moe_forward_flops(MOE), moe_routed_flops(MOE),
                        expert_share(MOE_CFG), moe_loss, moe)
    # counted among the dot_generals, the routed products would fail it
    with pytest.raises(AssertionError):
        assert_counts_match(moe_forward_flops(MOE), 0.0, 1.0, moe_loss, moe)


def test_routed_layer_within_xla(moe):
    assert_within_xla(moe_forward_flops(MOE), moe_routed_flops(MOE),
                      moe_loss, moe)
