"""WaveEngine — executes a Spindle ExecutionPlan on a real MTModel (§3.6).

The four runtime steps of the paper map onto JAX as follows:

  (1) **Localization** — every PlanStep (a sliced MetaOp on a fixed device
      group) becomes a pure segment function over the owning component
      instance's params, compiled once per step role into two programs: a
      forward that also returns ``jax.vjp``'s residuals, and the pull that
      consumes them.  On a multi-device runtime both run on the step's
      sub-mesh, following the shardings of their placed inputs (async
      dispatch ⇒ steps of one wave run concurrently on disjoint groups —
      the SPMD-engine analogue of per-group NCCL streams, DESIGN.md §3).
  (2) **Intra-task data dependency** — inter-wave data flow is the engine
      moving the producer's output activation to the consumer's device
      group (``device_put`` resharding = the paper's copy/shard/concat/
      send/recv transmission ops).
  (3) **Inter-task model dependency** — the **parameter device-group pool**
      ``{D_i → {W_j}}`` from the plan; gradients of a shared instance
      accumulate across all its per-task uses (realized as Σ over uses here,
      = the group all-reduce on hardware; optionally int8-compressed for
      island-crossing groups via repro.optim.compress).
  (4) **Training step** — forward wave-by-wave through the role programs
      (residuals kept per step), backward in reverse wave order by their
      pulls, group-wise gradient sync, optimizer update.

Numerical contract (tested): ``loss_and_grads`` ≡ ``jax.value_and_grad`` of
``MTModel.reference_loss`` for ANY planner-produced plan.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.plan import ExecutionPlan, PlanStep
from .mtmodel import ExecComponent, MTModel


@dataclass
class _StepRecord:
    step: PlanStep
    meta_id: int
    inst: str
    kind: str  # entry | mid | loss
    pred_order: List[int]  # meta_ids whose activations were inputs (entry)
    role: "_Role"
    args: Tuple  # the forward's arguments, handed to the pull again
    residuals: "_Residuals"
    is_loss: bool
    out_like: Any = None  # output array (placement template for cotangents)


class _Residuals:
    """The part of a ``jax.vjp`` VJP that a compiled forward returns.

    A VJP's leaves include the primal inputs it saved (parameters,
    activations, batch arrays); a compiled program would copy each one into
    a fresh output buffer.  ``own`` holds only the leaves the forward
    computed; ``layout`` (static: the VJP's tree and, per leaf, its source)
    lets the pull rebuild the VJP from ``own`` and the forward's arguments."""

    def __init__(self, own: List[Any], layout: Tuple):
        self.own = own
        self.layout = layout

    @classmethod
    def split(cls, vjp, args) -> "_Residuals":
        leaves, tree = jax.tree.flatten(vjp)
        arg_pos = {id(x): i for i, x in enumerate(jax.tree.leaves(args))}
        own, src = [], []
        for leaf in leaves:
            i = arg_pos.get(id(leaf))
            if i is None:
                src.append((False, len(own)))
                own.append(leaf)
            else:
                src.append((True, i))
        return cls(own, (tree, tuple(src)))

    def join(self, args):
        tree, src = self.layout
        arg_leaves = jax.tree.leaves(args)
        return jax.tree.unflatten(
            tree, [arg_leaves[i] if is_arg else self.own[i]
                   for is_arg, i in src]
        )


jax.tree_util.register_pytree_node(
    _Residuals, lambda r: (r.own, r.layout),
    lambda layout, own: _Residuals(list(own), layout),
)


@dataclass(frozen=True)
class _Role:
    """One step role's two compiled programs over its segment
    ``seg(batches, inst_params, *ins)``:

    - ``fwd(batches, inst_params, *ins) -> (out, residuals)``;
    - ``pull(residuals, (batches, inst_params, *ins), g_out)
      -> (d_inst_params, *d_ins)``."""

    fwd: Callable
    pull: Callable

    @classmethod
    def compile(cls, seg: Callable) -> "_Role":
        def fwd(batches, inst_params, *ins):
            out, vjp = jax.vjp(partial(seg, batches), inst_params, *ins)
            return out, _Residuals.split(vjp, (batches, inst_params, *ins))

        def pull(res, args, g_out):
            return res.join(args)(g_out)

        return cls(jax.jit(fwd), jax.jit(pull))


class WaveEngine:
    def __init__(self, model: MTModel, plan: ExecutionPlan, *,
                 distributed: bool = False):
        self.model = model
        #: plan device ids must be real devices (checked on init and rebind)
        self._check_devices = distributed
        self.distributed = distributed and jax.device_count() > 1
        # Step-role cache: the compiled (fwd, pull) pair per plan-id-
        # independent step identity (instance, component spec, task set,
        # predecessor roles, layer range, loss flag) — survives rebind() so
        # replanned plans reuse the programs of unchanged steps.
        self._fn_cache: Dict[Tuple, _Role] = {}
        #: role programs built, and forward and pull calls served by a
        #: program already in the cache
        self.role_stats: Dict[str, int] = {
            "built": 0, "fwd_hits": 0, "pull_hits": 0,
        }
        # Device-group mesh cache (distributed mode): one Mesh per distinct
        # device tuple, shared by activation and parameter placement.
        self._mesh_cache: Dict[Tuple[int, ...], jax.sharding.Mesh] = {}
        #: ids of the devices the last ``loss_and_grads`` call's forward
        #: step outputs landed on (where the plan actually ran)
        self.output_devices: frozenset = frozenset()
        self._validate_devices(plan)
        self._bind(plan)

    # ------------------------------------------------------------------
    def _validate_devices(self, plan: ExecutionPlan) -> None:
        """A distributed engine runs each step on the devices its plan
        names; a plan sized for a larger cluster is an error, never a
        silent run on fewer devices."""
        if not self._check_devices:
            return
        n = jax.device_count()
        missing = sorted(
            {d for s in plan.steps for d in s.devices if not 0 <= d < n}
        )
        if missing:
            raise ValueError(
                f"plan places steps on devices {missing}, but the runtime "
                f"has {n}: plan for the devices that exist"
            )

    def _bind(self, plan: ExecutionPlan) -> None:
        """Derive all plan-dependent lookup structures."""
        self.plan = plan
        self.mg = plan.meta_graph
        self._preds = self.mg.predecessors()
        self._succs = {m: set() for m in self.mg.meta_ops}
        for src, dsts in self.mg.edges.items():
            for d in dsts:
                self._succs[src].add(d)
        # meta → (instance, component, task string)
        self.meta_info: Dict[int, Tuple[str, str, str]] = {}
        for mid, m in self.mg.meta_ops.items():
            inst, comp, _, task = self.model.op_info[m.op_ids[0]]
            self.meta_info[mid] = (inst, comp, m.task)
        # flow-order task list (merged-batch concat order)
        self.flow_order = [f.task for f in self.model.flows]

    def rebind(self, plan: ExecutionPlan,
               model: Optional[MTModel] = None) -> Dict[str, int]:
        """Swap in a replanned/cached plan — and optionally a shifted model.

        Only the cheap plan-derived lookups are rebuilt; the compiled step
        roles in ``_fn_cache`` are keyed independently of MetaOp numbering,
        so steps whose (instance, layer range, inputs) identity is unchanged
        keep their programs even when the new plan slices or renumbers
        MetaOps differently.  Returns ``closures_cached`` — the number of
        roles retained for potential reuse; actual reuse happens on the next
        ``loss_and_grads`` call (steps whose identity changed build and
        compile their role then), observable as the cache size staying flat
        and in ``role_stats``.

        When ``model`` is given (a task arrived/completed mid-run and the
        MTModel was rebuilt for the new task set), the engine rebinds to it
        while KEEPING the role cache: a role's programs are pure in its key,
        which carries the component spec itself, and take params and batches
        as arguments, so steps shared between the old and new task sets reuse
        their programs, a same-named component whose spec changed builds new
        ones, and no program pins a retired model.
        """
        ref_model = model if model is not None else self.model
        self._validate_devices(plan)
        if plan.meta_graph is not self.mg or model is not None:
            # validate BEFORE mutating: a raise must leave the engine on
            # its previous (model, plan) pairing, still usable
            for m in plan.meta_graph.meta_ops.values():
                if m.op_ids[0] not in ref_model.op_info:
                    raise ValueError(
                        "rebind: plan references operators unknown to this "
                        "model — replan against the same task graph first"
                    )
        if model is not None:
            self.model = model
        cached = len(self._fn_cache)
        self._bind(plan)
        return {"closures_cached": cached}

    # ------------------------------------------------------------------
    def param_device_groups(self) -> Dict[str, Tuple[int, ...]]:
        return self.plan.param_device_groups()

    # ------------------------------------------------------------------
    def _layer_range(self, step: PlanStep) -> Tuple[int, int]:
        m = self.mg.meta_ops[step.meta_id]
        first = m.op_ids.index(step.op_ids[0])
        return first, first + len(step.op_ids)

    def _entry_preds(self, mid: int) -> Tuple[List[int], Tuple[Tuple[str, str], ...]]:
        """Ordered predecessor ids + their (task, component) roles.

        Ordering is by role (task, component) with id tiebreak, so the
        positional layout — and therefore the cached closure — is stable
        across replans that renumber MetaOps.
        """
        preds = sorted(
            self._preds[mid],
            key=lambda p: (self.meta_info[p][2], self.meta_info[p][1], p),
        )
        pred_info = tuple(
            (self.meta_info[p][2], self.meta_info[p][1]) for p in preds
        )
        return preds, pred_info

    def _group_mesh(self, devs: Tuple[int, ...]) -> jax.sharding.Mesh:
        mesh = self._mesh_cache.get(devs)
        if mesh is None:
            mesh = jax.sharding.Mesh(
                np.array([jax.devices()[d] for d in devs]), ("dp",)
            )
            self._mesh_cache[devs] = mesh
        return mesh

    def _put(self, x, step: PlanStep):
        """Move an activation onto the step's device group (flow transmission)."""
        if not self.distributed:
            return x
        devs = step.devices
        if len(devs) == 1:
            return jax.device_put(x, jax.devices()[devs[0]])
        spec = jax.sharding.PartitionSpec(
            "dp" if x.ndim and x.shape[0] % len(devs) == 0 else None
        )
        return jax.device_put(
            x, jax.sharding.NamedSharding(self._group_mesh(devs), spec)
        )

    def _put_params(self, p, step: PlanStep):
        """Replicate an instance's params onto the step's device group (the
        single-controller analogue of parameter broadcast).  Params that
        went through an optimizer update or an elastic restore are
        committed somewhere; step math must run on ONE consistent device
        set with the group-committed activations, so each step re-places
        its instance's params onto its own group.  Leaves already resident
        on the target sharding pass through untouched, and loss_and_grads
        memoizes the placed tree per (instance, group) for the call, so a
        k-entry instance pays one placement per group, not k."""
        if not self.distributed:
            return p
        devs = step.devices
        if len(devs) == 1:
            target = jax.sharding.SingleDeviceSharding(jax.devices()[devs[0]])
        else:
            target = jax.sharding.NamedSharding(
                self._group_mesh(devs), jax.sharding.PartitionSpec()
            )
        return jax.tree.map(
            lambda a: a if getattr(a, "sharding", None) == target
            else jax.device_put(a, target),
            p,
        )

    # ------------------------------------------------------------------
    def loss_and_grads(self, params, batches, *,
                       on_wave: Optional[Callable[[int, List[PlanStep]], None]] = None):
        """Wave-by-wave fwd + reverse-wave bwd. Returns (loss, grads).

        ``on_wave(wave_index, steps)`` fires after each forward wave is
        dispatched — the session's observer hook for per-wave metrics.
        """
        model = self.model
        acts: Dict[int, Any] = {}
        losses: Dict[int, Any] = {}
        records: List[_StepRecord] = []
        # Per-call placement memo: params are constant inside one
        # loss_and_grads, so each (instance, device group) pair pays for
        # its replication exactly once per call, not once per wave entry.
        placed: Dict[Tuple[str, Tuple[int, ...]], Any] = {}
        used: set = set()

        waves = self.plan.waves()
        for widx in sorted(waves):
            for step in waves[widx]:
                mid = step.meta_id
                inst, comp, task = self.meta_info[mid]
                with TraceAnnotation(f"spindle.fwd:{inst}"):
                    c = model.components[comp]
                    lo, hi = self._layer_range(step)
                    m = self.mg.meta_ops[mid]
                    terminal = not self._succs[mid]
                    is_loss_step = terminal and hi == m.L and c.kind in (
                        "contrastive", "decoder"
                    )

                    pkey = (inst, step.devices)
                    inst_p = placed.get(pkey)
                    if inst_p is None:
                        inst_p = self._put_params(params[inst], step)
                        placed[pkey] = inst_p
                    if lo == 0:
                        preds, pred_info = self._entry_preds(mid)
                        ins = [self._put(acts[p], step) for p in preds]
                        role = self._entry_role(
                            c, inst, pred_info, lo, hi, is_loss_step, task
                        )
                        kind = "entry"
                    else:
                        preds = []
                        ins = [self._put(acts[mid], step)]
                        role = self._mid_role(c, inst, lo, hi, is_loss_step, task)
                        kind = "mid"
                    args = (
                        {t: batches[t] for t in self._tasks_of(task)},
                        inst_p, *ins,
                    )
                    out, res = role.fwd(*args)
                    rec = _StepRecord(step, mid, inst, kind, preds, role, args,
                                      res, is_loss_step, out_like=out)
                    records.append(rec)
                    used.update(d.id for d in out.devices())
                    if is_loss_step:
                        losses[mid] = out
                    else:
                        acts[mid] = out
            if on_wave is not None:
                on_wave(widx, waves[widx])
        self.output_devices = frozenset(used)

        n_losses = len(losses)

        def _local(x):
            """Bring a cross-group value to the default device (transmission
            op for scalars/cotangents crossing device groups)."""
            if not self.distributed:
                return x
            return jax.tree.map(
                lambda a: jax.device_put(a, jax.devices()[0]), x
            )

        total = sum(_local(l) for l in losses.values()) / n_losses

        # ---------------- backward: reverse wave order ----------------
        with TraceAnnotation("spindle.grad_init"):
            grads = {k: jax.tree.map(jnp.zeros_like, v) for k, v in params.items()}
        cot: Dict[int, Any] = {}

        def _acc(a, b):
            return jax.tree.map(lambda x, y: x + _same_place(y, x), a, b)

        def _same_place(y, like):
            if not self.distributed:
                return y
            return jax.device_put(y, like.sharding)

        while records:
            rec = records.pop()  # reverse order; residuals freed once pulled
            mid = rec.meta_id
            if not rec.is_loss and mid not in cot:
                continue  # activation never used (defensive)
            with TraceAnnotation(f"spindle.bwd:{rec.inst}"):
                if rec.is_loss:
                    g_out = jnp.asarray(1.0 / n_losses, jnp.float32)
                else:
                    g_out = cot.pop(mid)
                if self.distributed:
                    g_out = jax.tree.map(
                        lambda g, o: _same_place(g, o), g_out, rec.out_like
                    ) if rec.out_like is not None else g_out
                pulls = rec.role.pull(rec.residuals, rec.args, g_out)
                self.role_stats["pull_hits"] += 1
            d_params, d_ins = pulls[0], pulls[1:]
            with TraceAnnotation(f"spindle.grad_acc:{rec.inst}"):
                grads[rec.inst] = _acc(grads[rec.inst], d_params)
                if rec.kind == "mid":
                    (d_h,) = d_ins
                    cot[mid] = _acc(cot[mid], d_h) if mid in cot else d_h
                else:
                    for p, d in zip(rec.pred_order, d_ins):
                        cot[p] = _acc(cot[p], d) if p in cot else d
        return total, grads

    # ------------------------------------------------------------------
    def _tasks_of(self, task_str: str) -> List[str]:
        ts = task_str.split("+")
        return sorted(ts, key=self.flow_order.index)

    def _role(self, key: Tuple, make_seg: Callable[[], Callable]) -> _Role:
        role = self._fn_cache.get(key)
        if role is not None:
            self.role_stats["fwd_hits"] += 1
            return role
        role = self._fn_cache[key] = _Role.compile(make_seg())
        self.role_stats["built"] += 1
        return role

    def _entry_role(self, c: ExecComponent, inst, pred_info, lo, hi,
                    is_loss, task_str) -> _Role:
        """Compiled entry-step role.

        The cache key carries no MetaOp ids — only roles (instance, the
        component spec, task set, predecessor (task, component) layout,
        layer range) — and params and ``batches`` are arguments, so the
        role survives rebind() across replans.
        """
        key = ("entry", inst, c, task_str, pred_info, lo, hi, is_loss)
        return self._role(key, lambda: self._entry_seg(
            c, pred_info, lo, hi, is_loss, self._tasks_of(task_str)))

    def _mid_role(self, c: ExecComponent, inst, lo, hi, is_loss,
                  task_str) -> _Role:
        key = ("mid", inst, c, task_str, lo, hi, is_loss)
        return self._role(key, lambda: self._mid_seg(
            c, lo, hi, is_loss, self._tasks_of(task_str)))

    def _entry_seg(self, c, pred_info, lo, hi, is_loss, tasks):
        # The segment reaches the model through the engine, and only while
        # it is traced: no program pins a model that rebind() retired.
        engine = self
        pos_by_task = {
            t: [i for i, (pt, _) in enumerate(pred_info) if pt == t]
            for t in tasks
        }

        def seg(batches, inst_params, *pred_acts):
            model = engine.model
            if c.kind == "contrastive":
                inputs = {pc: a for (_, pc), a in zip(pred_info, pred_acts)}
                return model.loss_op(inst_params, c, inputs, batches[tasks[0]])
            # entry per task (merged components concat the union batch)
            hs = []
            for t in tasks:
                inputs = {pred_info[i][1]: pred_acts[i] for i in pos_by_task[t]}
                hs.append(model.entry(inst_params, c, inputs, batches[t]))
            h = hs[0] if len(hs) == 1 else jnp.concatenate(hs, axis=0)
            return _layers_and_loss(model, c, inst_params, h, lo, hi,
                                    is_loss, batches, tasks)

        return seg

    def _mid_seg(self, c, lo, hi, is_loss, tasks):
        engine = self  # trace-time model lookup — see _entry_seg

        def seg(batches, inst_params, h):
            return _layers_and_loss(engine.model, c, inst_params, h, lo, hi,
                                    is_loss, batches, tasks)

        return seg

    # ------------------------------------------------------------------
    def train_step(self, params, opt_state, batches, optimizer, *,
                   on_wave=None):
        """One full §3.6 iteration: fwd+bwd wave-by-wave, group sync, update."""
        loss, grads = self.loss_and_grads(params, batches, on_wave=on_wave)
        with TraceAnnotation("spindle.optim"):
            new_params, new_state = optimizer.update(grads, opt_state, params)
        return new_params, new_state, loss


def _layers_and_loss(model: MTModel, c: ExecComponent, inst_params, h, lo, hi,
                     is_loss, batches, tasks):
    """Layers ``lo:hi`` of a component instance, then its loss where the
    segment ends the component's chain."""
    for lp in inst_params["layers"][lo:hi]:
        h = model.apply_layer(c, lp, h)
    if not is_loss:
        return h
    labels = jnp.concatenate(
        [batches[t]["labels"] for t in tasks], axis=0
    ) if len(tasks) > 1 else batches[tasks[0]]["labels"]
    return model.loss_op(inst_params, c, {}, {"labels": labels}, h=h)
