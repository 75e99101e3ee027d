"""Activation-sharding context.

Model code stays mesh-agnostic: it calls `constrain(x, name)` at key points
(post-embedding, block outputs, MoE dispatch buffers, microbatch reshape).
When a launcher wraps tracing in `activation_sharding(mapping)`, those calls
become `with_sharding_constraint`s; otherwise they are identity. The mapping
values are either PartitionSpecs or rank-indexed spec factories.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import PartitionSpec as P

_CTX = threading.local()


@contextmanager
def activation_sharding(mapping: dict):
    """mapping: name -> PartitionSpec | callable(rank)->PartitionSpec.
    Special key 'dp': the data-parallel mesh axis (str or tuple) used for
    batch/microbatch constraints."""
    prev = getattr(_CTX, "map", None)
    _CTX.map = mapping
    try:
        yield
    finally:
        _CTX.map = prev


def _lookup(name: str):
    m = getattr(_CTX, "map", None)
    if not m:
        return None
    return m.get(name)


def dp_axes():
    """The data-parallel axis name(s), or None outside a context."""
    return _lookup("dp")


def _axis_size(axes) -> int:
    sizes = _lookup("axis_sizes") or {}
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n


def _divides(shape, spec) -> bool:
    for dim, axes in zip(shape, tuple(spec)):
        if axes is not None and dim % _axis_size(axes) != 0:
            return False
    return True


def constrain(x, name: str):
    spec = _lookup(name)
    if spec is None:
        return x
    if callable(spec):
        spec = spec(x.ndim)
    if not _divides(x.shape, spec):
        return x           # constraint would be invalid; let GSPMD decide
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_batch_tree(tree, leading: int = 1):
    """Constrain every array in a batch pytree: dims [0:leading] unsharded,
    dim `leading` over the dp axes, rest unsharded. Used for the microbatch
    reshape inside train_step (keeps GSPMD from resharding the scan input)."""
    dp = dp_axes()
    if dp is None:
        return tree

    def one(x):
        if x.ndim <= leading:
            return x
        spec = P(*([None] * leading + [dp] + [None] * (x.ndim - leading - 1)))
        if not _divides(x.shape, spec):
            return x
        return jax.lax.with_sharding_constraint(x, spec)

    return jax.tree_util.tree_map(one, tree)


def shard_map_nocheck(f, mesh, in_specs, out_specs):
    """`jax.shard_map` with varying-manual-axes (VMA) checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
