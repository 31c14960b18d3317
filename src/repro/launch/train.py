"""End-to-end training driver — a thin CLI shell over the session layer.

Trains any registered arch (full or ``--reduced`` smoke size) on the
deterministic synthetic LM stream with AdamW, checkpoint/auto-resume,
straggler detection, and optional int8-compressed cross-pod gradient sync.
On this CPU container the practical path is ``--reduced`` (the quickstart
example trains a ~100M-class model for a few hundred steps); on a TPU pod
the same driver runs the full configs on the production mesh.

With ``--plan-workload`` the driver additionally stands up a plan-only
:class:`repro.session.SpindleSession` for the named MT workload: the plan
is built through the session's PlanCache, the training loop feeds its step
times into a :class:`repro.launch.events.StragglerEventSource`, and the
session polls it every step, so a detected straggler fires the §5.5
re-plan hook through the one production code path instead of
driver-inline logic.  The detector compares per-host medians, so it can
only flag when ONE instance sees timings from every host — the source
carries a :class:`repro.ckpt.straggler.TimingCollector` that allgathers
the local step time across processes (rank-0 pattern; in-process fallback
on single-process runtimes), feeding ``record_all``.

``--elastic-smoke`` runs the fault-injection scenario instead (the CI
gate): a scripted straggler mid-run must take the checkpoint → re-mesh →
restore path (``ReplanRecord(mode="restore")``) and keep training on the
surviving hosts' devices.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt \
        --plan-workload multitask_clip
"""

from __future__ import annotations

import argparse
import time
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import default_sharding, get_arch, reduced
from ..ckpt import CheckpointManager, StragglerDetector
from ..data import DataConfig, SyntheticLM, shard_batch
from ..models import build_model
from ..optim import AdamW, OptState, warmup_cosine
from ..parallel import ShardingRules, batch_axes, tree_param_shardings
from .compile_cache import enable_compile_cache


def plan_preview(
    workload: str,
    *,
    planner: str = "spindle",
    n_devices: int = 16,
    island_size: int = 8,
    verbose: bool = True,
    event_sources=(),
    callbacks=(),
):
    """Stand up a plan-only SpindleSession for a named MT workload.

    The training driver uses this to print the wavefront plan a multi-task
    run would execute on a real cluster — same registry/stages/cache as the
    bound sessions, the simulator, and the benchmarks (DESIGN.md §9/§10).
    Returns the session; its ``current_plan`` is the built plan, and later
    ``session.poll()``/``session.signal(...)`` replans through the cache.
    """
    from ..core.pipeline import available_planners
    from ..core.placement import ClusterSpec
    from ..core.workloads import WORKLOADS
    from ..session import SessionConfig, SpindleSession

    # validate names up front so the CLI error stays friendly without a
    # blanket except that would also swallow genuine planner failures
    if workload not in WORKLOADS:
        raise SystemExit(
            f"[train] unknown --plan-workload {workload!r}; "
            f"choose from {sorted(WORKLOADS)}"
        )
    if planner not in available_planners():
        raise SystemExit(
            f"[train] unknown --planner {planner!r}; "
            f"choose from {available_planners()}"
        )
    cfg = SessionConfig(
        workload=workload,
        planner=planner,
        cluster=ClusterSpec(n_devices=n_devices, island_size=island_size,
                            mem_bytes=96e9),
        # straggler replans must adapt, not vacuously re-hit the cache:
        # shrink the planning cluster by the flagged hosts (restored on
        # recovery) so the regenerated plan actually routes around them
        straggler_shrink=True,
    )
    session = SpindleSession(cfg, event_sources=list(event_sources),
                             callbacks=list(callbacks))
    p = session.plan()
    if verbose:
        print(f"[plan] {workload} via {planner!r}: "
              f"{len(p.waves())} waves / {len(p.steps)} steps, "
              f"makespan {p.makespan*1e3:.1f} ms/iter "
              f"(planned in {p.planning_seconds*1e3:.0f} ms)")
    return session


def elastic_smoke(
    *,
    steps: int = 10,
    straggler_at: int = 4,
    straggler_hosts: Tuple[int, ...] = (1,),
    ckpt_dir: Optional[str] = None,
    verbose: bool = True,
) -> Dict[str, Any]:
    """Fault-injection scenario: a bound distributed session survives a
    scripted straggler through the checkpoint → re-mesh → restore path.

    Runs a :class:`repro.session.SpindleSession` over every local device
    (CI forces 8 via ``XLA_FLAGS=--xla_force_host_platform_device_count``),
    two devices per host, with a :class:`CheckpointCallbacks`-threaded
    :class:`CheckpointManager` and a :class:`ScriptedEventSource` that
    flags ``straggler_hosts`` after step ``straggler_at``.  The run must
    produce a ``ReplanRecord(mode="restore")`` whose new placement excludes
    exactly the flagged hosts' devices, then keep training; any violation
    raises ``SystemExit`` (the CI job greps the transcript on top).
    """
    import tempfile

    from ..ckpt import CheckpointManager
    from ..config import MeshConfig
    from ..parallel import mesh_over_devices
    from ..runtime import tiny_multitask_clip
    from ..session import CheckpointCallbacks, SessionConfig, SpindleSession
    from .events import ScriptedEventSource, StragglerDetected

    n_dev = jax.device_count()
    if n_dev < 4:
        print(f"[elastic] WARNING: only {n_dev} devices visible — set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    per_host = 2 if n_dev >= 4 else 1
    cluster = MeshConfig(
        shape=(n_dev,), axes=("data",), devices_per_host=per_host
    ).cluster_spec(island_size=max(per_host * 2, 2), mem_bytes=1e13)
    bad = tuple(h for h in straggler_hosts if 0 <= h < cluster.n_hosts)
    if not bad or len(bad) >= cluster.n_hosts:
        raise SystemExit("[elastic] no valid straggler host to inject")
    mgr = CheckpointManager(ckpt_dir or tempfile.mkdtemp(prefix="elastic_"),
                            every=max(straggler_at, 1), keep=3)
    src = ScriptedEventSource(
        [StragglerDetected(bad)], fire_at=[straggler_at]
    )
    session = SpindleSession(
        SessionConfig(
            cluster=cluster,
            straggler_shrink=True,
            mesh=mesh_over_devices(range(n_dev)),
        ),
        model_factory=lambda tasks: tiny_multitask_clip(n_tasks=len(tasks)),
        tasks=("img_text", "audio_text", "audio_vision"),
        callbacks=[CheckpointCallbacks(mgr)],
        event_sources=[src],
    ).bind()

    announced = 0
    for k in range(steps):
        loss = session.step()
        phase = "post-restore" if any(
            r.mode == "restore" for r in session.replans
        ) else "healthy"
        if verbose:
            print(f"[elastic] step {k:3d}  loss {loss:.4f}  ({phase})")
        for r in session.replans[announced:]:
            if r.mode == "restore":
                print(f"[elastic] straggler {list(bad)} -> replan "
                      f"mode=restore plan_mode={r.plan_mode} "
                      f"restored_step={r.restored_step} healthy_devices="
                      f"{len(session.cluster.healthy_devices())}")
        announced = len(session.replans)

    restores = [r for r in session.replans if r.mode == "restore"]
    if not restores:
        raise SystemExit("[elastic] FAIL: no restore replan occurred")
    flagged_devs = {d for h in bad for d in cluster.devices_of(h)}
    plan_devs = {d for s in session.current_plan.steps for d in s.devices}
    if plan_devs & flagged_devs:
        raise SystemExit(
            f"[elastic] FAIL: flagged devices {sorted(plan_devs & flagged_devs)} "
            "still placed after the restore replan"
        )
    if session.step_count <= straggler_at + 1:
        raise SystemExit("[elastic] FAIL: no post-restore training step")
    print(f"[elastic] OK: {len(restores)} restore replan(s), "
          f"{session.step_count - straggler_at - 1} post-restore steps, "
          f"final loss {session.history[-1]:.4f}")
    return {
        "steps": session.step_count,
        "history": session.history,
        "replans": session.replans,
        "session": session,
    }


def crash_smoke(
    *,
    steps: int = 8,
    kill_at: int = 4,
    kill_hosts: Tuple[int, ...] = (1,),
    ckpt_every: int = 2,
    ckpt_dir: Optional[str] = None,
    verbose: bool = True,
) -> Dict[str, Any]:
    """Hard-failure scenario: a bound session survives a host KILL through
    the async-snapshot → rollback → re-mesh → replay path (CI gate).

    A :class:`repro.launch.faults.FaultInjector` hard-kills ``kill_hosts``
    after step ``kill_at`` while an
    :class:`repro.ckpt.AsyncCheckpointManager` snapshots every
    ``ckpt_every`` steps off the step turn.  The session must roll back to
    the last durable snapshot, evict the dead block, re-mesh over the
    survivors and deterministically replay the lost steps — the full loss
    history must EXACTLY match an uninterrupted reference run on the
    surviving topology, and the final plan must not place the dead
    devices.  Any violation raises ``SystemExit`` (CI greps the
    transcript on top).
    """
    import tempfile

    import numpy as np

    from ..ckpt import AsyncCheckpointManager, all_steps
    from ..config import MeshConfig
    from ..parallel import mesh_over_devices
    from ..runtime import tiny_multitask_clip
    from ..session import CheckpointCallbacks, SessionConfig, SpindleSession
    from .faults import FaultInjector, FaultScript

    n_dev = jax.device_count()
    if n_dev < 4:
        print(f"[crash] WARNING: only {n_dev} devices visible — set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    per_host = 2 if n_dev >= 4 else 1
    cluster = MeshConfig(
        shape=(n_dev,), axes=("data",), devices_per_host=per_host
    ).cluster_spec(island_size=max(per_host * 2, 2), mem_bytes=1e13)
    bad = tuple(h for h in kill_hosts if 0 <= h < cluster.n_hosts)
    if not bad or len(bad) >= cluster.n_hosts:
        raise SystemExit("[crash] no valid host to kill")
    if not 0 < kill_at < steps:
        raise SystemExit(f"[crash] --kill-at must be in 1..{steps - 1}")
    tasks = ("img_text", "audio_text", "audio_vision")
    factory = lambda ts: tiny_multitask_clip(n_tasks=len(ts))  # noqa: E731

    # uninterrupted reference on the surviving topology — the ground truth
    # the recovered run must reproduce loss-for-loss
    ref = SpindleSession(
        SessionConfig(cluster=cluster.shrink(bad)),
        model_factory=factory, tasks=tasks,
    ).bind()
    ref_hist = [ref.step() for _ in range(steps)]

    mgr = AsyncCheckpointManager(
        ckpt_dir or tempfile.mkdtemp(prefix="crash_"),
        every=max(ckpt_every, 1), keep=3,
    )
    inj = FaultInjector(cluster.n_hosts,
                        schedule=[FaultScript(step=kill_at, hosts=bad)])
    session = SpindleSession(
        SessionConfig(
            cluster=cluster,
            mesh=mesh_over_devices(range(n_dev)),
        ),
        model_factory=factory,
        tasks=tasks,
        callbacks=[CheckpointCallbacks(mgr)],
        event_sources=[inj],
    ).bind()

    announced = 0
    for k in range(steps):
        loss = session.step()
        if verbose:
            phase = "recovered" if any(
                r.mode == "restore" for r in session.replans
            ) else "healthy"
            print(f"[crash] step {k:3d}  loss {loss:.4f}  ({phase})")
        for r in session.replans[announced:]:
            if r.mode == "restore":
                print(f"[crash] host kill {list(bad)} -> rollback to "
                      f"step {r.restored_step}, replayed "
                      f"{r.rollback_steps} lost step(s), re-meshed on "
                      f"{len(session.cluster.healthy_devices())} devices")
        announced = len(session.replans)
    mgr.wait()

    restores = [r for r in session.replans if r.mode == "restore"]
    if not restores:
        raise SystemExit("[crash] FAIL: no rollback-restore replan occurred")
    dead_devs = {d for h in bad for d in cluster.devices_of(h)}
    plan_devs = {d for s in session.current_plan.steps for d in s.devices}
    if plan_devs & dead_devs:
        raise SystemExit(
            f"[crash] FAIL: dead devices {sorted(plan_devs & dead_devs)} "
            "still placed after recovery"
        )
    if len(session.history) != steps:
        raise SystemExit(
            f"[crash] FAIL: {len(session.history)} steps recorded, "
            f"expected {steps}"
        )
    err = float(np.max(np.abs(np.asarray(session.history)
                              - np.asarray(ref_hist))))
    if err > 1e-6:
        raise SystemExit(
            f"[crash] FAIL: recovered losses diverge from the "
            f"uninterrupted reference (max abs err {err:.2e})"
        )
    durable = all_steps(mgr.base)
    if not durable:
        raise SystemExit("[crash] FAIL: no restorable checkpoint on disk")
    print(f"[crash] OK: rollback_steps={restores[0].rollback_steps} "
          f"restored_step={restores[0].restored_step} "
          f"loss-exact vs reference (max err {err:.1e}), "
          f"{len(durable)} durable snapshot(s), async saves "
          f"{mgr.saves_written} written / {mgr.saves_dropped} dropped")
    return {
        "steps": session.step_count,
        "history": session.history,
        "ref_history": ref_hist,
        "replans": session.replans,
        "session": session,
    }


def make_train_state(model, optimizer, rng, rules=None):
    """Fresh ``(params, opt_state)``.  With ``rules``, parameters and both
    AdamW moments are placed by :func:`repro.parallel.tree_param_shardings`
    (FSDP over ``data`` when ``ShardingConfig.fsdp``); the step counter is
    replicated.  Without, everything stays on the default device."""
    params = model.init(rng)
    if rules is None:
        return params, optimizer.init(params)
    shardings = tree_param_shardings(rules, params)
    params = jax.device_put(params, shardings)
    opt_state = optimizer.init(params)
    opt_state = jax.device_put(
        opt_state,
        OptState(mu=shardings, nu=shardings,
                 count=rules.named(jax.sharding.PartitionSpec())),
    )
    return params, opt_state


def _make_compressed_dp_step(model, optimizer, mesh):
    """Pure-DP train step with int8-compressed gradient all-reduce.

    Params replicated; each "data" shard computes grads on its slice of
    the batch; the DP sync runs as :func:`repro.optim.compressed_mean`
    (int8 payload + shared max-scale) inside shard_map — 4× less gradient
    traffic than fp32 all-reduce, the cross-pod/DCN trick from DESIGN.md
    §8. The optimizer update runs on the synced grads (replicated math)."""

    from jax.sharding import PartitionSpec as P

    from ..optim.compress import compressed_mean

    def local_grads(params, b):
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss(p, b), has_aux=True
        )(params)
        synced = jax.tree.map(
            lambda g: compressed_mean(g, "data"), grads
        )
        loss = jax.lax.pmean(loss, "data")
        return synced, loss

    batch_spec = P("data")
    grads_fn = jax.shard_map(
        local_grads,
        mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0, 1))
    def step_fn(params, opt_state, b):
        grads, loss = grads_fn(params, b)
        new_params, new_state = optimizer.update(grads, opt_state, params)
        return new_params, new_state, loss

    return step_fn


def train(
    arch: str = "qwen3-0.6b",
    *,
    reduced_cfg: bool = True,
    steps: int = 100,
    batch: int = 8,
    seq: int = 128,
    lr: float = 3e-4,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    log_every: int = 10,
    seed: int = 0,
    stop_at_step: Optional[int] = None,  # simulate a crash/interrupt
    mesh=None,
    compress_grads: bool = False,
    verbose: bool = True,
    plan_workload: Optional[str] = None,
    planner: str = "spindle",
) -> Dict[str, Any]:
    from ..ckpt import TimingCollector
    from .events import StragglerEventSource

    n_hosts = max(jax.process_count(), 1)
    straggler_src = StragglerEventSource(
        StragglerDetector(n_hosts=n_hosts),
        collector=TimingCollector(n_hosts=n_hosts),
    )
    session = None
    if plan_workload:
        from ..session import SessionCallbacks

        class _ReplanLogger(SessionCallbacks):
            def on_replan(self, sess, event, old_plan, new_plan, info):
                if verbose:
                    print(f"[train] {event.kind} -> replanned "
                          f"({info.mode}, "
                          f"{info.planning_seconds*1e3:.1f} ms planner)")

        session = plan_preview(
            plan_workload, planner=planner, verbose=verbose,
            event_sources=[straggler_src], callbacks=[_ReplanLogger()],
        )
    cfg = get_arch(arch)
    if reduced_cfg:
        cfg = reduced(cfg)
    shcfg = default_sharding(cfg)
    model = build_model(cfg, shcfg)
    optimizer = AdamW(
        lr=partial(warmup_cosine, peak_lr=lr, warmup_steps=max(steps // 10, 1),
                   total_steps=steps),
        moment_dtype=jnp.dtype(cfg.opt_dtype),
    )
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                  global_batch=batch, seed=seed))

    # the compressed-DP step keeps parameters replicated (pure DP); every
    # other step under a mesh shards them by the arch's sharding rules
    rules = (ShardingRules(mesh, shcfg)
             if mesh is not None and not compress_grads else None)
    params, opt_state = make_train_state(
        model, optimizer, jax.random.PRNGKey(seed), rules=rules
    )

    start_step = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, every=ckpt_every, keep=3)
        restored, manifest = mgr.restore_latest({"params": params,
                                                 "opt": opt_state})
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            start_step = int(manifest["step"]) + 1
            if verbose:
                print(f"[train] resumed from step {manifest['step']}")

    if compress_grads and mesh is not None and "data" in mesh.axis_names:
        step_fn = _make_compressed_dp_step(model, optimizer, mesh)
    else:
        # params and optimizer state are donated: the update writes in
        # place, so one copy of the training state is live, not two
        @partial(jax.jit, donate_argnums=(0, 1))
        def step_fn(params, opt_state, b):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: model.loss(p, b, mesh=mesh), has_aux=True
            )(params)
            new_params, new_state = optimizer.update(grads, opt_state, params)
            return new_params, new_state, loss

    history = []
    t_start = time.perf_counter()
    for step in range(start_step, steps):
        if stop_at_step is not None and step >= stop_at_step:
            break  # simulated interruption (schedule still sized by `steps`)
        b = data.batch(step)
        if mesh is not None:
            b = shard_batch(b, mesh, batch_axes(mesh))
        t0 = time.perf_counter()
        params, opt_state, loss = step_fn(params, opt_state, b)
        loss = float(loss)
        dt = time.perf_counter() - t0
        # the collector behind record_step turns this process's time into
        # the aggregated per-host vector (allgather; rank 0 feeds the
        # detector) — the only feed under which the detector can flag
        straggler_src.record_step(dt)
        history.append(loss)
        if verbose and (step % log_every == 0 or step == steps - 1):
            tok_s = batch * seq / dt
            print(f"[train] step {step:5d}  loss {loss:.4f}  "
                  f"{dt*1e3:7.1f} ms  {tok_s:9.0f} tok/s")
        if mgr:
            mgr.maybe_save(step, {"params": params, "opt": opt_state},
                           extra={"loss": loss, "arch": arch})
        if session is not None:
            # the session drains the straggler source and replans the MT
            # workload through its cache (§5.5 hook, one production path)
            session.poll()
        else:
            for ev in straggler_src.poll():
                if verbose and ev.hosts:
                    print("[train] stragglers detected: "
                          f"{list(ev.hosts)} — re-plan trigger")
                elif verbose:
                    print("[train] stragglers recovered")
    wall = time.perf_counter() - t_start
    interrupted = stop_at_step is not None and stop_at_step < steps
    if mgr and history and not interrupted and (steps - 1) % ckpt_every != 0:
        # off-cadence final step of a COMPLETED schedule: save
        # unconditionally (maybe_save would no-op here by construction and
        # silently drop the last steps).  Interrupted runs must not stamp
        # steps-1 onto older state — a real crash saves nothing either,
        # and resume would otherwise skip the untrained tail.
        mgr.save(steps - 1, {"params": params, "opt": opt_state},
                 extra={"loss": history[-1]})

    return {
        "arch": arch,
        "steps": steps,
        "first_loss": history[0] if history else None,
        "final_loss": history[-1] if history else None,
        "wall_seconds": wall,
        "params": params,
        "history": history,
        "mt_plan": session.current_plan if session is not None else None,
        "mt_session": session,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan-workload", default=None,
                    help="also plan this MT workload via the PlannerPipeline")
    ap.add_argument("--planner", default="spindle",
                    help="planner strategy for --plan-workload")
    ap.add_argument("--elastic-smoke", action="store_true",
                    help="fault-injection scenario: scripted straggler -> "
                         "checkpointed re-mesh restore (CI gate); ignores "
                         "the plain-training flags except --steps/--ckpt-dir")
    ap.add_argument("--straggler-at", type=int, default=4,
                    help="elastic-smoke: inject the straggler after this step")
    ap.add_argument("--straggler-hosts", default="1",
                    help="elastic-smoke: comma-separated host ids to flag")
    ap.add_argument("--crash-smoke", action="store_true",
                    help="hard-failure scenario: scripted host kill -> "
                         "async-snapshot rollback + deterministic replay "
                         "(CI gate); uses --steps/--kill-at/--kill-hosts")
    ap.add_argument("--kill-at", type=int, default=4,
                    help="crash-smoke: hard-kill after this step")
    ap.add_argument("--kill-hosts", default="1",
                    help="crash-smoke: comma-separated host ids to kill")
    args = ap.parse_args()
    enable_compile_cache()
    if args.crash_smoke:
        crash_smoke(
            steps=args.steps,
            kill_at=args.kill_at,
            kill_hosts=tuple(
                int(h) for h in args.kill_hosts.split(",") if h != ""
            ),
            ckpt_every=max(args.ckpt_every, 1),
            ckpt_dir=args.ckpt_dir,
        )
        return
    if args.elastic_smoke:
        elastic_smoke(
            steps=args.steps,
            straggler_at=args.straggler_at,
            straggler_hosts=tuple(
                int(h) for h in args.straggler_hosts.split(",") if h != ""
            ),
            ckpt_dir=args.ckpt_dir,
        )
        return
    out = train(
        args.arch,
        reduced_cfg=args.reduced,
        steps=args.steps,
        batch=args.batch,
        seq=args.seq,
        lr=args.lr,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        seed=args.seed,
        plan_workload=args.plan_workload,
        planner=args.planner,
    )
    print(f"[train] done: loss {out['first_loss']:.4f} → {out['final_loss']:.4f} "
          f"in {out['wall_seconds']:.1f}s")


if __name__ == "__main__":
    main()
