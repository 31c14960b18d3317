"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): ``(16, 16)`` over ``(data, model)`` for one v5e pod
(256 chips), ``(2, 16, 16)`` over ``(pod, data, model)`` for the two-pod
dry-run (512 chips).  The ``pod`` axis crosses DCN; ``data``/``model`` ride
ICI — the sharding rules put DP/FSDP on ``data`` (+ optionally ``pod``) and
TP/EP/SP on ``model`` accordingly.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 1, n_model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         (AxisType.Auto,) * 2)
