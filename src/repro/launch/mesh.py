"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = 1
    for s in shape:
        ndev *= s
    return jax.make_mesh(shape, axes, devices=jax.devices()[:ndev],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
