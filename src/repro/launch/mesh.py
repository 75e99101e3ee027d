"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips ("data","model").
    Multi-pod: 2×16×16 = 512 chips ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / CPU smoke runs)."""
    n = jax.device_count()
    if shape is None:
        shape = (n, 1)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def activation_mapping(mesh) -> dict:
    """The activation-sharding context used by all launchers."""
    dp = ("pod", "data") if "pod" in mesh.axis_names else "data"
    return {
        "dp": dp,
        "axis_sizes": {a: int(mesh.shape[a]) for a in mesh.axis_names},
        "act_btd": P(dp, None, None),
        "moe_ecd": P("model", dp, None),
    }
