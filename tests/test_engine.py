"""WaveEngine ≡ reference execution (the §3.6 numerical contract)."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import ClusterSpec, plan
from repro.optim import AdamW
from repro.runtime import WaveEngine, tiny_multitask_clip, tiny_ofasys


@pytest.mark.parametrize("maker", [tiny_multitask_clip, tiny_ofasys],
                         ids=["clip", "ofasys"])
@pytest.mark.parametrize("n_devices,island", [(4, 4), (8, 4), (16, 8)])
def test_engine_matches_reference(maker, n_devices, island):
    model, batches = maker()
    params = model.init(jax.random.PRNGKey(0))
    ref_loss, ref_grads = jax.value_and_grad(model.reference_loss)(
        params, batches
    )
    p = plan(model.graph, ClusterSpec(n_devices=n_devices, island_size=island))
    eng = WaveEngine(model, p)
    loss, grads = eng.loss_and_grads(params, batches)
    assert float(jnp.abs(loss - ref_loss)) < 1e-5
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_engine_shared_param_group_sync():
    """Shared components: engine grads = Σ task contributions (the
    parameter device-group pool semantics, §3.6 step 3)."""
    model, batches = tiny_multitask_clip(n_tasks=3)
    params = model.init(jax.random.PRNGKey(1))
    p = plan(model.graph, ClusterSpec(n_devices=8, island_size=4))
    eng = WaveEngine(model, p)
    groups = eng.param_device_groups()
    # every shared tower must have a device group registered
    for comp in ("vision", "text", "audio"):
        assert comp in groups
    _, grads = eng.loss_and_grads(params, batches)
    # the shared text tower receives gradient from >1 task: nonzero
    g = jax.tree.leaves(grads["text"])
    assert any(bool(jnp.any(x != 0)) for x in g)


def test_engine_train_step_descends():
    model, batches = tiny_ofasys()
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-2, weight_decay=0.0)
    state = opt.init(params)
    p = plan(model.graph, ClusterSpec(n_devices=8, island_size=4))
    eng = WaveEngine(model, p)
    losses = []
    for _ in range(8):
        params, state, loss = eng.train_step(params, state, batches, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0], f"no descent: {losses}"


def test_engine_wave_structure_respects_plan():
    model, batches = tiny_multitask_clip()
    p = plan(model.graph, ClusterSpec(n_devices=8, island_size=4))
    WaveEngine(model, p)  # binding validates plan ↔ model consistency
    waves = p.waves()
    assert len(waves) >= 1
    # each wave's steps sit on disjoint devices (one concurrent execution)
    for widx, steps in waves.items():
        devs = [d for s in steps for d in s.devices]
        assert len(devs) == len(set(devs))


# --------------------------------------------------------------------------
# Compiled step roles
# --------------------------------------------------------------------------

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _assert_matches(loss, grads, ref_loss, ref_grads):
    assert float(jnp.abs(loss - ref_loss)) < 1e-5
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_engine_repeat_calls_build_and_compile_nothing():
    """After the first call every plan step runs a cached role program:
    no role is built and XLA compiles nothing."""
    model, batches = tiny_multitask_clip()
    params = model.init(jax.random.PRNGKey(0))
    p = plan(model.graph, ClusterSpec(n_devices=8, island_size=4))
    eng = WaveEngine(model, p)
    jax.block_until_ready(eng.loss_and_grads(params, batches))
    built = eng.role_stats["built"]
    assert built == len(eng._fn_cache) > 0

    compiles = []

    def on_event(event, duration, **_):
        if event == BACKEND_COMPILE:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for _ in range(2):
            before = dict(eng.role_stats)
            jax.block_until_ready(eng.loss_and_grads(params, batches))
            assert eng.role_stats["built"] == built
            assert eng.role_stats["fwd_hits"] - before["fwd_hits"] == len(p.steps)
            assert eng.role_stats["pull_hits"] - before["pull_hits"] == len(p.steps)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []


@pytest.mark.parametrize("field,value", [("n_heads", 2), ("d_ff", 48)])
def test_engine_rebind_to_a_changed_component_runs_its_new_spec(field, value):
    """A model whose same-named component changed its spec gets programs of
    its own: no stale program runs after rebind(model=...)."""
    import dataclasses

    from repro.runtime import MTModel

    model1, batches = tiny_ofasys()
    cluster = ClusterSpec(n_devices=8, island_size=4)
    eng = WaveEngine(model1, plan(model1.graph, cluster))
    params1 = model1.init(jax.random.PRNGKey(0))
    eng.loss_and_grads(params1, batches)
    built = eng.role_stats["built"]

    comps = [dataclasses.replace(c, **{field: value}) if c.name == "lm" else c
             for c in model1.components.values()]
    model2 = MTModel(comps, model1.flows)
    # n_heads keeps every parameter's shape: only the spec tells them apart
    params2 = params1 if field == "n_heads" else model2.init(jax.random.PRNGKey(0))
    ref_loss, ref_grads = jax.value_and_grad(model2.reference_loss)(
        params2, batches
    )
    if field == "n_heads":  # the old spec gives another loss on these params
        old_loss = model1.reference_loss(params2, batches)
        assert float(jnp.abs(ref_loss - old_loss)) > 1e-4
    eng.rebind(plan(model2.graph, cluster), model=model2)
    loss, grads = eng.loss_and_grads(params2, batches)
    _assert_matches(loss, grads, ref_loss, ref_grads)
    assert eng.role_stats["built"] > built  # the decoder's roles are new


def test_engine_replan_keeps_the_unchanged_roles_programs():
    """A replan that slices one tower differently builds only the roles it
    changed; every other step reuses its compiled programs."""
    model, batches = tiny_ofasys()
    params = model.init(jax.random.PRNGKey(0))
    ref_loss, ref_grads = jax.value_and_grad(model.reference_loss)(
        params, batches
    )
    eng = WaveEngine(model, plan(model.graph,
                                 ClusterSpec(n_devices=8, island_size=4)))
    eng.loss_and_grads(params, batches)
    before = dict(eng._fn_cache)
    stats = dict(eng.role_stats)

    p2 = plan(model.graph, ClusterSpec(n_devices=2, island_size=2))
    eng.rebind(p2)
    loss, grads = eng.loss_and_grads(params, batches)
    _assert_matches(loss, grads, ref_loss, ref_grads)

    new = set(eng._fn_cache) - set(before)
    built = eng.role_stats["built"] - stats["built"]
    reused = eng.role_stats["fwd_hits"] - stats["fwd_hits"]
    assert built == len(new) > 0
    assert reused > 0 and built + reused == len(p2.steps)
    assert all(eng._fn_cache[k] is role for k, role in before.items())
