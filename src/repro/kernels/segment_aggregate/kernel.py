"""Fused int8-weight GNN segment aggregation Pallas TPU kernel.

Computes, over one packed sparse batch (features.SparseGraphBatch layout):

    msg = act((x · node_mask) @ (w.f32 * w_scale))       # [M, F]
    out[d] = Σ_{e: scatter[e]=d} edge_mask[e] * msg[gather[e]]
    (mean: divide by Σ edge_mask per destination, floored at 1)

i.e. one GraphSAGE hop's transform+aggregate (`core/gnn.py
`_segment_aggregate``) in a single pass: the message tensor is computed
once into VMEM scratch — with the int8→f32 weight dequantization fused
into the matmul operand, so weights stream from HBM as int8 (¼ the
bytes) — and the packed edge list is walked in blocks of `block_e`
edges without the message tensor ever round-tripping to HBM.

Gather/scatter are phrased as one-hot matmuls (MXU-friendly — the same
trick the guide uses for TPU gathers): for an edge block,
``gsel[e, m] = (m == gather[e])`` picks message rows via ``gsel @ msg``
and ``sselᵀ @ rows`` scatter-adds them (ssel carries edge_mask), so the
whole aggregation runs on the MXU instead of serializing on dynamic
indexing.

Grid: (num_e_blocks,) — sequential on TPU, so `out` and the VMEM
scratch accumulators persist across steps. BlockSpecs:
  x       [M, D]        index (0, 0)    (full)
  w       [D, F]        index (0, 0)    (full; int8 or f32)
  w_scale [1, F]        index (0, 0)
  nmask   [M, 1]        index (0, 0)
  gather  [1, block_e]  index (0, e)
  scatter [1, block_e]  index (0, e)
  emask   [1, block_e]  index (0, e)
  out     [M, F]        index (0, 0)    (revisited every step)
Scratch: msg [M, F] f32 + deg [M, 1] f32 in VMEM. VMEM grows with M:
`ops.vmem_bytes` reckons M·(D + 2·F + 1 + 2·block_e) floats, and
`ops.segment_aggregate` refuses a pack over `ops.VMEM_LIMIT_BYTES`
(M=2048, D=F=256 fits; M=4096 does not — the v5e compiler refuses it
too). Bucketed capacities (data/batching.py) are pow2, so M/D/F/E arrive
tiling-friendly; `ops.segment_aggregate` pads the stragglers. `block_e`
must be a multiple of 128 (the edge blocks are lane-major); candidates
for the tile-size autotuner come from `ops.block_candidates` (the
`graph_aggregate.block_candidates` idiom).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, s_ref, nm_ref, g_ref, sc_ref, em_ref, o_ref,
            msg_ref, deg_ref, *, act: str, mean: bool, nsteps: int):
    e = pl.program_id(0)

    @pl.when(e == 0)
    def _init():
        x = x_ref[...].astype(jnp.float32) * nm_ref[...]
        w = w_ref[...].astype(jnp.float32) * s_ref[...]
        m = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if act == "relu":
            m = jnp.maximum(m, 0.0)
        msg_ref[...] = m
        o_ref[...] = jnp.zeros_like(o_ref)
        deg_ref[...] = jnp.zeros_like(deg_ref)

    gat = g_ref[0]                                    # [block_e] int32
    sct = sc_ref[0]
    em = em_ref[0].astype(jnp.float32)                # [block_e]
    M = msg_ref.shape[0]
    blk = gat.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (blk, M), 1)
    gsel = (cols == gat[:, None]).astype(jnp.float32)            # [blk, M]
    rows = jax.lax.dot_general(gsel, msg_ref[...],
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    # padding edges carry edge_mask 0, so ssel zeroes their contribution
    ssel = (cols == sct[:, None]).astype(jnp.float32) * em[:, None]
    o_ref[...] += jax.lax.dot_general(ssel, rows, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    deg_ref[...] += jnp.sum(ssel, axis=0)[:, None]

    @pl.when(e == nsteps - 1)
    def _finish():
        if mean:
            o_ref[...] = o_ref[...] / jnp.maximum(deg_ref[...], 1.0)


def segment_aggregate_mf(x: jnp.ndarray, w: jnp.ndarray,
                         w_scale: jnp.ndarray, gather: jnp.ndarray,
                         scatter: jnp.ndarray, edge_mask: jnp.ndarray,
                         node_mask: jnp.ndarray, *, act: str = "relu",
                         mean: bool = True, block_e: int = 256,
                         interpret: bool = False) -> jnp.ndarray:
    """x: [M, D]; w: [D, F] (int8 or f32); w_scale: [1, F]; gather/
    scatter/edge_mask: [1, E] with E a multiple of `block_e`; node_mask:
    [M, 1]. Returns [M, F] f32. Shapes must arrive tiling-aligned — use
    `ops.segment_aggregate`, which pads and strips."""
    M, D = x.shape
    F = w.shape[1]
    E = gather.shape[1]
    nsteps = E // block_e
    kernel = functools.partial(_kernel, act=act, mean=mean, nsteps=nsteps)
    return pl.pallas_call(
        kernel,
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((M, D), lambda e: (0, 0)),
            pl.BlockSpec((D, F), lambda e: (0, 0)),
            pl.BlockSpec((1, F), lambda e: (0, 0)),
            pl.BlockSpec((M, 1), lambda e: (0, 0)),
            pl.BlockSpec((1, block_e), lambda e: (0, e)),
            pl.BlockSpec((1, block_e), lambda e: (0, e)),
            pl.BlockSpec((1, block_e), lambda e: (0, e)),
        ],
        out_specs=pl.BlockSpec((M, F), lambda e: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, F), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((M, F), jnp.float32),
            pltpu.VMEM((M, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, w_scale, node_mask, gather, scatter, edge_mask)
