"""Production mesh construction.

Never touches jax device state at import time — everything is a function.
Mesh shapes: single-pod (16, 16) = 256 chips ("data", "model"); multi-pod
(2, 16, 16) = 512 chips ("pod", "data", "model").  ``pod`` is the DCN-level
data-parallel axis (high startup cost — where gradient merging pays most).

Every axis is built Auto: the train step makes its data-parallel axes
Manual inside its ``shard_map`` and leaves the rest to GSPMD.  Install a
mesh as the ambient one with ``jax.set_mesh``.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis Auto (GSPMD partitioning)."""
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(devices: int, tp_enabled: bool):
    """("data", "model") mesh over ``devices`` chips.

    With tensor parallelism off every chip sits on ``data``, so none
    duplicates another's work; with it on, the model axis takes two."""
    model_parallel = 2 if (tp_enabled and devices % 2 == 0) else 1
    return make_mesh((devices // model_parallel, model_parallel),
                     ("data", "model"))
