"""Mesh axis conventions.

Production meshes are ``(data, model)`` single-pod and ``(pod, data, model)``
multi-pod.  The batch dimension shards over ``("pod","data")`` (DP), model
parallel dims (TP heads / FFN, EP experts, sequence sharding) over
``"model"``.  FSDP parameter sharding rides the ``"data"`` axis (ICI) and
optionally extends over ``"pod"`` (DCN) — see ShardingConfig.
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence, Tuple

import jax
import numpy as np

POD = "pod"
DATA = "data"
MODEL = "model"

AxisNames = Tuple[str, ...]


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """Build a mesh over the available devices (CPU hosts or TPU chips).

    Axes are ``Auto``: the sharding rules place arrays and GSPMD propagates
    the rest (``jax.make_mesh`` would otherwise default to ``Explicit``).
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         (jax.sharding.AxisType.Auto,) * len(axes))


def mesh_over_devices(
    device_ids: Iterable[int],
    axes: Sequence[str] = (DATA,),
    shape: Optional[Sequence[int]] = None,
) -> jax.sharding.Mesh:
    """Build a mesh over an EXPLICIT device-id subset — the elastic re-mesh
    primitive: after a straggler eviction, the session rebuilds its mesh
    from ``ClusterSpec.healthy_devices()`` so restored arrays land only on
    surviving hosts' devices.  An id the runtime does not have raises: a
    plan sized for a larger cluster must not silently run on fewer devices.
    ``shape`` defaults to 1-D over the survivors.
    """
    pool = jax.devices()
    ids = list(device_ids)
    missing = [d for d in ids if not 0 <= d < len(pool)]
    if missing:
        raise ValueError(
            f"mesh_over_devices: device ids {missing} do not exist "
            f"(runtime has {len(pool)} devices)"
        )
    if not ids:
        raise ValueError("mesh_over_devices: empty device subset")
    devs = [pool[d] for d in ids]
    arr = np.array(devs)
    if shape is not None:
        arr = arr.reshape(tuple(shape))
    return jax.sharding.Mesh(arr, tuple(axes))


def batch_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Mesh axes the batch dimension shards over."""
    names = mesh.axis_names
    out = tuple(a for a in (POD, DATA) if a in names)
    return out or (names[0],)


def model_axis(mesh: jax.sharding.Mesh) -> Optional[str]:
    return MODEL if MODEL in mesh.axis_names else None


def axis_size(mesh: jax.sharding.Mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.devices.shape[mesh.axis_names.index(name)]
