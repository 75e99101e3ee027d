"""Training meshes for the cost-model trainer (DESIGN.md §13).

`repro.launch.mesh` builds the *production LM* meshes (dp × fsdp × tp over
512 devices, checked by the dryrun probes). The cost-model trainer needs
something much smaller: a dp mesh over however many local devices the
process actually has — 2 fake CPU devices under
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` in CI, real
accelerators in production. This module is that factory, kept in
`repro.sharding` so the trainer never imports launch code.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_train_mesh(dp: int) -> Mesh:
    """A ``(dp, 1)`` mesh with axes ``("data", "model")`` over the first
    `dp` local devices. Cost-model params are replicated; the size-1 model
    axis keeps the layout the chip benchmark builds for its train cells.

    Raises ValueError when the host doesn't have enough devices — the
    actionable fix on CPU hosts is in the message.
    """
    if dp < 1:
        raise ValueError(f"mesh dp must be >= 1, got dp={dp}")
    devices = jax.devices()
    if len(devices) < dp:
        raise ValueError(
            f"mesh dp={dp} needs {dp} devices but only {len(devices)} are "
            f"visible; on CPU hosts set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={dp}")
    grid = np.asarray(devices[:dp]).reshape(dp, 1)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))
