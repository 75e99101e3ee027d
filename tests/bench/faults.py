"""Faults planted underneath a train cell's timed path, for the tests that
see `correct` come out false: wrappers of the train step and of the batch
(`run.run_cell`'s `fault`), and patches of the program's code that return
(owner, attribute, replacement)."""
from __future__ import annotations

import numpy as np


def unchanged(step):
    """The step returns its state unchanged."""
    def faulty(params, opt, *args):
        import jax
        import jax.numpy as jnp
        _, _, stats = step(jax.tree_util.tree_map(jnp.copy, params), opt,
                           *args)
        return params, opt, stats
    return faulty


def altered_loss(step):
    """The step's loss moved by 5%."""
    def faulty(*args):
        params, opt, stats = step(*args)
        return params, opt, dict(stats, loss=stats["loss"] * 1.05)
    return faulty


def half_batch(b):
    """Half of every shard's graphs left out; the mean over the rest."""
    valid = np.array(b.valid, copy=True)
    valid[..., valid.shape[-1] // 2:] = 0.0
    b.valid = valid
    return b


def one_shard_batch(b):
    """The last device's runtimes doubled."""
    targets = np.array(b.targets, copy=True)
    targets[-1] *= 2.0
    b.targets = targets
    return b


def wrong_device_keys():
    """Device 0 draws its dropout masks from device 1's key."""
    import repro.training.trainer as trainer
    real = trainer.step_keys

    def keys(base, step, dp=0):
        k = real(base, step, dp)
        return k[np.array([1] + list(range(1, dp)))] if dp > 1 else k
    return trainer, "step_keys", keys


def one_shard_grads():
    """The gradient all-reduce keeps device 0's gradients, not the mean
    (the exchange between chips left out); the loss is still averaged."""
    import jax
    import jax.numpy as jnp
    real = jax.lax.pmean

    def pmean(x, axis_name, **kw):
        if not isinstance(x, dict):
            return real(x, axis_name, **kw)
        first = jax.lax.axis_index(axis_name) == 0
        return jax.lax.psum(jax.tree_util.tree_map(
            lambda g: jnp.where(first, g, 0.0), x), axis_name)
    return jax.lax, "pmean", pmean
