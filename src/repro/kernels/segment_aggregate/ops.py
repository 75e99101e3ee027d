"""Public wrapper for the fused sparse-aggregation kernel.

Pads every operand up to tiling-friendly shapes (M → ×8, D/F → lane
multiples sized for the weight dtype — int8 needs (32, 128) tiles, f32
(8, 128) — E → ×128 and ×block_e), runs `kernel.segment_aggregate_mf`,
and strips the padding. Padding rows/edges carry zero masks, so they
contribute nothing; padded output channels are sliced off.

The kernel keeps whole node-axis blocks in VMEM, so a pack has a node
bound: `vmem_bytes` reckons a call's footprint and the wrapper raises a
ValueError naming `VMEM_LIMIT_BYTES` before the TPU compiler would fail
with RESOURCE_EXHAUSTED.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.segment_aggregate.kernel import segment_aggregate_mf

LANES = 128
# The TPU compiler's default scoped-VMEM limit for a kernel on a v5e
# (its RESOURCE_EXHAUSTED message: "limit 16.00M").
VMEM_LIMIT_BYTES = 16 << 20


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_bytes(M: int, D: int, F: int, block_e: int) -> int:
    """f32 bytes the kernel holds in VMEM for padded shapes: the whole
    [M, D] input, the [M, F] output, the [M, F] and [M, 1] scratch, and
    the two [block_e, M] one-hot selectors of an edge block.

    >>> vmem_bytes(2048, 256, 256, 256) <= VMEM_LIMIT_BYTES
    True
    >>> vmem_bytes(4096, 256, 256, 256) <= VMEM_LIMIT_BYTES
    False
    """
    return 4 * M * (D + 2 * F + 1 + 2 * block_e)


@partial(jax.jit, static_argnames=("act", "mean", "block_e", "interpret"))
def segment_aggregate(x: jnp.ndarray, w: jnp.ndarray, w_scale: jnp.ndarray,
                      gather: jnp.ndarray, scatter: jnp.ndarray,
                      edge_mask: jnp.ndarray, node_mask: jnp.ndarray, *,
                      act: str = "relu", mean: bool = True,
                      block_e: int = 256,
                      interpret: bool = False) -> jnp.ndarray:
    """Fused transform+segment-aggregate over a packed edge list.

    x: [M, D] f32; w: [D, F] int8 (with per-output-channel `w_scale`
    [1, F] or [F]) or f32 (pass ones); gather/scatter: [E] int32 flat
    node indices; edge_mask: [E]; node_mask: [M]. Returns [M, F] f32 =
    ``segment_aggregate(act((x·node_mask) @ (w·w_scale)), edges)``, the
    quantity `core.gnn._segment_aggregate` computes from a materialized
    message tensor — here the messages stay in VMEM (kernel.py).

    `block_e` is the edge-block width (the kernel's only tunable; see
    `block_candidates`, the `graph_aggregate.block_candidates` idiom): a
    multiple of 128, capped at the padded edge count.
    """
    if block_e < LANES or block_e % LANES:
        raise ValueError(f"block_e={block_e} must be a positive multiple "
                         f"of {LANES} (the TPU lane width)")
    M, D = x.shape
    F = w.shape[1]
    E = gather.shape[0]
    # int8 weights tile at (32, 128); f32 operands at (8, 128)
    d_mult = 32 if w.dtype == jnp.int8 else 8
    Mp, Dp, Fp = _pad_to(M, 8), _pad_to(D, d_mult), _pad_to(F, LANES)
    block_e = min(block_e, _pad_to(E, LANES))
    Ep = _pad_to(E, block_e)
    need = vmem_bytes(Mp, Dp, Fp, block_e)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"segment_aggregate over {M} nodes (D={D}, F={F}, "
            f"block_e={block_e}) needs ~{need / 2**20:.1f} MiB of VMEM, "
            f"over the {VMEM_LIMIT_BYTES / 2**20:.0f} MiB limit "
            f"(VMEM_LIMIT_BYTES); pack fewer nodes per batch")

    x = jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Dp - D)))
    w = jnp.pad(w, ((0, Dp - D), (0, Fp - F)))
    w_scale = jnp.pad(w_scale.reshape(1, -1).astype(jnp.float32),
                      ((0, 0), (0, Fp - F)), constant_values=1.0)
    nm = jnp.pad(node_mask.astype(jnp.float32), (0, Mp - M))[:, None]
    gat = jnp.pad(gather.astype(jnp.int32), (0, Ep - E))[None, :]
    sct = jnp.pad(scatter.astype(jnp.int32), (0, Ep - E))[None, :]
    em = jnp.pad(edge_mask.astype(jnp.float32), (0, Ep - E))[None, :]

    out = segment_aggregate_mf(x, w, w_scale, gat, sct, em, nm, act=act,
                               mean=mean, block_e=block_e,
                               interpret=interpret)
    return out[:M, :F]


def block_candidates(edge_capacity: int) -> list[int]:
    """block_e candidates for the tile-size autotuner (mirrors
    `kernels.graph_aggregate.block_candidates` for block_f): lane-aligned
    widths up to the edge capacity."""
    return [b for b in (128, 256, 512, 1024)
            if b <= max(edge_capacity, LANES)]
