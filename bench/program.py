"""The system under test, as the benchmark drives it.

With ``bench/arch/<arch>/program.py``, the only module of the benchmark that
imports the program. What every architecture shares is here: the compile
cache of the checkout, the engine the faults patch, a ``SpindleSession``
(planner ``spindle``) bound over the cell's chips to the model that the
architecture's ``build_model`` makes from the plain spec, and what the
comparison reads from the session's state. Weights and batches come from
``bench/spec.py``; the session's own ``init`` and data cursor hand them to
the program.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core.placement import ClusterSpec
from repro.optim import AdamW
from repro.parallel import mesh_over_devices
from repro.session import SessionCallbacks, SessionConfig, SpindleSession

# for ``harness.prepare`` and ``bench/faults.py``, which import nothing of
# the program themselves
from repro.launch.compile_cache import enable_compile_cache  # noqa: F401
from repro.runtime.engine import WaveEngine  # noqa: F401

from . import reference, spec as specmod


def build_model(spec: Dict[str, Any], weights):
    """The program's model of the spec's architecture, holding ``weights``
    (``None`` where only its shapes and functions are wanted)."""
    return specmod.arch(spec, "program").build_model(spec, weights)


def _bytes_limit(dev) -> float:
    stats = dev.memory_stats() or {}
    return float(stats.get("bytes_limit", 16e9))


class HostSpans(SessionCallbacks):
    """Host-clock spans at the session's layer boundaries.

    ``plan_ms``: from ``bind()`` to the ``on_plan`` hook. Per step, the
    forward time from ``step()`` entry to the last forward wave's
    ``on_wave``, and the rest (reverse waves, gradient accumulation, AdamW,
    the loss read) to ``step()``'s return. With ``annotate`` each span is a
    ``jax.profiler.TraceAnnotation`` too (``fwd_wave_<i>``,
    ``bwd_and_update``), so the trace can say what the host did in a gap."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.bind_t0: Optional[float] = None
        self.plan_ms: Optional[float] = None
        self.fwd_ms: List[float] = []
        self.bwd_ms: List[float] = []
        self._waves: List[int] = []
        self._t0 = self._t_last = 0.0
        self._ann = None
        self.recording = False

    def on_plan(self, session, plan) -> None:
        if self.plan_ms is None and self.bind_t0 is not None:
            self.plan_ms = (time.perf_counter() - self.bind_t0) * 1e3
        self._waves = sorted(plan.waves())

    def _open(self, name: str) -> None:
        if self.annotate:
            self._ann = jax.profiler.TraceAnnotation(name)
            self._ann.__enter__()

    def _close(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def begin_step(self) -> None:
        if not self.recording:
            return
        self._t0 = self._t_last = time.perf_counter()
        self._open(f"fwd_wave_{self._waves[0]}")

    def on_wave(self, session, wave_index, steps) -> None:
        if not self.recording:
            return
        self._t_last = time.perf_counter()
        self._close()
        i = self._waves.index(wave_index)
        if i + 1 < len(self._waves):
            self._open(f"fwd_wave_{self._waves[i + 1]}")
        else:
            self._open("bwd_and_update")

    def end_step(self) -> None:
        if not self.recording:
            return
        t = time.perf_counter()
        self._close()
        self.fwd_ms.append((self._t_last - self._t0) * 1e3)
        self.bwd_ms.append((t - self._t_last) * 1e3)


def open_session(spec: Dict[str, Any], weights, pool: List[Dict], chips: int,
                 spans: Optional[HostSpans] = None) -> SpindleSession:
    """Bind a session over ``chips`` devices; its data cursor cycles ``pool``."""
    devs = jax.devices()[:chips]
    mesh = mesh_over_devices(range(chips)) if chips > 1 else None
    cluster = ClusterSpec(n_devices=chips, island_size=chips,
                          mem_bytes=_bytes_limit(devs[0]))
    opt = spec["optimizer"]
    session = SpindleSession(
        SessionConfig(cluster=cluster, mesh=mesh, planner="spindle",
                      lr=opt["lr"], weight_decay=opt["weight_decay"]),
        callbacks=[spans] if spans is not None else [],
        batch_fn=lambda step: pool[step % len(pool)],
    )
    session.optimizer = AdamW(
        lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"])
    if spans is not None:
        spans.bind_t0 = time.perf_counter()
    session.bind(build_model(spec, weights))
    return session


def state(session: SpindleSession):
    """The arrays a step leaves behind: wait on these to end a window."""
    return session.params, session.opt_state


def first_grad_norms(session: SpindleSession, b1: float):
    """Per-leaf norms of the first gradient as the optimizer took it, from
    AdamW's first moment after one step (it starts at zero)."""
    mu = jax.device_put(session.opt_state.mu, jax.devices()[0])
    return jax.device_get(jax.jit(reference.leaf_norms)(mu)) / (1.0 - b1)


def change_norms(session: SpindleSession, params0):
    """Per-leaf norms of the parameters' change since ``params0``."""
    dev = jax.devices()[0]
    return jax.device_get(jax.jit(lambda a, b: reference.leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(
            jax.device_put(session.params, dev), jax.device_put(params0, dev)))
