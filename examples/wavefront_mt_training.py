"""The paper's core scenario end-to-end: plan + execute MT MM training.

A thin demo shell over :class:`repro.session.SpindleSession` — the one
lifecycle API (plan → bind → execute → replan, DESIGN.md §10).  Builds a
small Multitask-CLIP-style model (3 tasks, shared towers); the session
plans it through the PlanCache (graph contraction → scaling curves → MPSP
allocation → wavefront schedule → device placement), binds a WaveEngine,
and trains wave-by-wave, with callbacks observing plans/steps.  Then
DYNAMICITY: a task completes mid-run via ``session.signal(TaskCompleted)``
— the §5.5 re-plan hook — the plan is regenerated incrementally through
the cache, the engine rebinds without rebuilding unchanged step closures,
and training continues.  The engine is verified against single-program
execution before AND after the shift.

    PYTHONPATH=src python examples/wavefront_mt_training.py
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.core import ClusterSpec, simulate_plan, simulate_sequential
from repro.launch.events import TaskCompleted
from repro.runtime import tiny_multitask_clip
from repro.session import SessionCallbacks, SessionConfig, SpindleSession

TASKS = ("img_text", "audio_text", "audio_vision")


def describe_plan(p) -> None:
    mg = p.meta_graph
    print(f"  MetaOps: {len(mg.meta_ops)}  levels: {len(mg.levels())}  "
          f"waves: {len(p.waves())}  makespan: {p.makespan*1e3:.2f} ms "
          f"(C̃* {p.c_star_total*1e3:.2f} ms)")
    for widx, steps in sorted(p.waves().items()):
        names = ", ".join(
            f"{mg.meta_ops[s.meta_id].name}[{len(s.op_ids)}]×{len(s.devices)}d"
            for s in steps
        )
        print(f"  wave {widx}: {names}")


class DemoObserver(SessionCallbacks):
    """Observe the lifecycle: new plans and replans print as they happen."""

    def on_plan(self, session, plan):
        describe_plan(plan)

    def on_replan(self, session, event, old_plan, new_plan, info):
        print(f"  re-plan on {event.kind}({event.task}): {info.mode} "
              f"({info.planning_seconds*1e3:.1f} ms planner, "
              f"{info.closures_cached} engine step roles kept)")


def verify_engine(session) -> None:
    """Numerical contract: engine ≡ jax.value_and_grad(reference_loss)."""
    ref_l, ref_g = jax.value_and_grad(session.model.reference_loss)(
        session.params, session.batches
    )
    loss, grads = session.engine.loss_and_grads(session.params, session.batches)
    err = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_g))
    )
    print(f"  engine == reference: loss Δ={float(abs(loss - ref_l)):.2e}, "
          f"max grad Δ={err:.2e}")


def main() -> None:
    cluster = ClusterSpec(n_devices=8, island_size=4, mem_bytes=96e9)
    session = SpindleSession(
        SessionConfig(cluster=cluster),
        model_factory=lambda tasks: tiny_multitask_clip(n_tasks=len(tasks)),
        tasks=TASKS,
        callbacks=[DemoObserver()],
    )

    print("== Spindle plan (3 tasks) ==")
    session.bind()
    p = session.current_plan

    seq = simulate_sequential(session.model.graph, cluster)
    sp = simulate_plan(p, cluster)
    print("  analytic speedup vs sequential: "
          f"{seq.makespan / sp.makespan:.2f}x  "
          f"(utilization {seq.avg_flops_utilization:.2f} → "
          f"{sp.avg_flops_utilization:.2f})")

    print("\n== WaveEngine training (session.run) ==")
    verify_engine(session)
    for step in range(6):
        loss = session.step()
        print(f"  step {step}: loss {loss:.4f}")

    print("\n== dynamicity: task 'audio_vision' completes → "
          "session.signal re-plans ==")
    session.signal(TaskCompleted("audio_vision"))
    # shared tower parameters carried over automatically (same instances)
    verify_engine(session)
    for step in range(3):
        loss = session.step()
        print(f"  step {step}: loss {loss:.4f}")
    print(f"  cache: {session.cache.stats.as_dict()}")
    print("wavefront MT training OK")


if __name__ == "__main__":
    main()
