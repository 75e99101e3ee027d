"""Public wrapper for the fused GNN aggregation kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.graph_aggregate.kernel import graph_aggregate_bnd

LANES = 128


@partial(jax.jit, static_argnames=("act", "mean", "block_f", "interpret"))
def graph_aggregate(adj: jnp.ndarray, x: jnp.ndarray, w: jnp.ndarray, *,
                    act: str = "relu", mean: bool = True, block_f: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """`block_f` below the output width F must be a multiple of 128 (the
    TPU lane width); at or above F the kernel takes F in one block."""
    if block_f < w.shape[1] and block_f % LANES:
        raise ValueError(f"block_f={block_f} splits F={w.shape[1]} into "
                         f"blocks that are not a multiple of {LANES}")
    return graph_aggregate_bnd(adj, x, w, act=act, mean=mean,
                               block_f=block_f, interpret=interpret)


def block_candidates(hidden: int) -> list[int]:
    """block_f candidates for the tile-size autotuner: lane-aligned widths
    up to the hidden size."""
    return [b for b in (128, 256, 512, 1024) if b <= max(hidden, LANES)]
