"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16)."""
from __future__ import annotations

from typing import Sequence, Tuple

import jax


def auto_mesh(shape: Sequence[int],
              axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis explicitly ``AxisType.Auto``
    (compiler-chosen sharding propagation, as the launch stack expects)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod ('data' x 'model'); the multi-pod mesh adds
    a leading 'pod' axis (2 pods = 512 chips). Must be called in a process
    whose jax platform exposes enough devices (see launch/dryrun.py)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1) -> jax.sharding.Mesh:
    """Debug mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model_axis
    return auto_mesh((data, model_axis), ("data", "model"))
