"""The learned performance model (paper §3).

Pipeline:
  opcode embedding ⊕ node scalar features [⊕ kernel features (option 1)]
    → f1 → GNN (GraphSAGE | GAT | none)
    → node-final MLP (3 layers, Table 5)
    → reduction (per-node | column-wise | LSTM | Transformer)
      [⊕ kernel features (option 2)]
    → linear head (no activation) → scalar prediction per kernel.

The scalar is a log-runtime estimate for the fusion task and an arbitrary
ranking score for the tile-size task (trained with pairwise rank loss).
"""
from __future__ import annotations

from dataclasses import dataclass, asdict

import jax
import jax.numpy as jnp

from repro.core import features as F
from repro.core import gnn as G
from repro.core import reductions as R
from repro.core.opset import NUM_OPCODES
from repro.nn.core import (
    dense_apply,
    dense_init,
    dropout,
    embedding_apply,
    embedding_init,
    mlp_apply,
    mlp_init,
)

# batches must be jit-traceable before any apply; features.py defers this
# so its numpy-only consumers never import jax
F.register_pytrees()


@dataclass
class CostModelConfig:
    gnn: str = "graphsage"               # graphsage | gat | none
    reduction: str = "transformer"       # per_node | column_wise | lstm | transformer
    hidden_dim: int = 192
    opcode_embed_dim: int = 64           # paper uses 256; scaled for CPU CI
    gnn_layers: int = 3                  # Table 5
    node_final_layers: int = 3           # Table 5
    aggregator: str = "mean"             # Table 5
    directed: bool = True                # 'vanilla'; False = ablation
    kernel_feat_mode: str = "node"       # 'node' (option 1) | 'kernel' (option 2)
    include_static_perf: bool = True
    include_tile: bool = True
    transformer_layers: int = 1
    transformer_heads: int = 4
    gat_heads: int = 2
    dropout: float = 0.1
    max_nodes: int = 64
    # fused Pallas aggregation: kernels/graph_aggregate on the dense
    # layout, kernels/segment_aggregate (inference-only) on the sparse one
    use_pallas_aggregate: bool = False
    # batched-graph representation the data path should produce for this
    # model: 'dense' ([B,N,N] padded adjacency, MXU matmul aggregation) or
    # 'sparse' (packed SparseGraphBatch + segment_sum). `cost_model_apply`
    # itself dispatches on the batch type; samplers/evaluators/autotuners
    # read this field to pick the encoder. See DESIGN.md §4.
    adjacency: str = "dense"             # dense | sparse | segmented
    # Store GNN layer params stacked ([L, ...] leaves) and run message
    # passing as one `lax.scan` over the layer axis: the layer body traces
    # once per bucket shape instead of `gnn_layers` times, so compile cost
    # is depth-independent (DESIGN.md §12). Either layout of an on-disk
    # checkpoint restores into either setting (training/checkpoint.py).
    scan_layers: bool = False
    # Numeric format of the parameter tree `cost_model_apply` receives:
    # 'f32' (plain arrays) or 'int8' (repro.quant — weights are
    # `QuantizedLeaf`s, dequantized inside jit; with use_pallas_aggregate
    # on the sparse layout the GNN f2 weights instead stay int8 all the
    # way into the fused segment_aggregate kernel). Inference-only: the
    # trainer always trains f32 and `repro.quant.quantize_params`
    # produces the int8 tree afterwards (DESIGN.md §14).
    precision: str = "f32"

    def __post_init__(self):
        if self.adjacency not in ("dense", "sparse", "segmented"):
            raise ValueError(f"unknown adjacency {self.adjacency!r} "
                             "(dense | sparse | segmented)")
        if self.precision not in ("f32", "int8"):
            raise ValueError(f"unknown precision {self.precision!r} "
                             "(f32 | int8)")
        if self.use_pallas_aggregate and self.gnn != "graphsage":
            raise ValueError(
                f"use_pallas_aggregate supports gnn='graphsage' only, got "
                f"gnn={self.gnn!r} (dense layout: kernels/graph_aggregate; "
                "sparse: kernels/segment_aggregate)")
        if self.use_pallas_aggregate and self.adjacency == "segmented":
            raise ValueError(
                "use_pallas_aggregate does not support adjacency="
                "'segmented': a whole-program segment pack has no node "
                "bound, and kernels/segment_aggregate keeps the whole pack "
                "in VMEM (ops.VMEM_LIMIT_BYTES)")

    def to_dict(self) -> dict:
        return asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "CostModelConfig":
        return CostModelConfig(**d)


def cost_model_init(rng, cfg: CostModelConfig, dtype=jnp.float32) -> dict:
    keys = jax.random.split(rng, 8)
    d = cfg.hidden_dim
    in_dim = cfg.opcode_embed_dim + F.NODE_FEATURE_DIM
    if cfg.kernel_feat_mode == "node":
        in_dim += F.KERNEL_FEATURE_DIM
    params = {
        "opcode_embed": embedding_init(keys[0], NUM_OPCODES,
                                       cfg.opcode_embed_dim, dtype=dtype),
        "f1": dense_init(keys[1], in_dim, d, bias=False, dtype=dtype),
        "node_final": mlp_init(keys[3], [d] * (cfg.node_final_layers + 1),
                               bias=False, dtype=dtype),
        "reduction": R.reduction_init(
            keys[4], cfg.reduction, d,
            transformer_layers=cfg.transformer_layers,
            transformer_heads=cfg.transformer_heads, dtype=dtype),
    }
    if cfg.gnn == "graphsage":
        params["gnn"] = G.sage_init(keys[2], d, cfg.gnn_layers,
                                    directed=cfg.directed, dtype=dtype)
    elif cfg.gnn == "gat":
        params["gnn"] = G.gat_init(keys[2], d, max(cfg.gnn_layers, 1),
                                   cfg.gat_heads, directed=cfg.directed,
                                   dtype=dtype)
    elif cfg.gnn != "none":
        raise ValueError(f"unknown gnn {cfg.gnn!r}")
    if cfg.scan_layers and "gnn" in params and params["gnn"]["layers"]:
        params["gnn"] = G.stack_params(params["gnn"])

    if cfg.reduction == "per_node":
        params["node_head"] = dense_init(keys[5], d, 1, bias=False, dtype=dtype)
        if cfg.kernel_feat_mode == "kernel":
            params["kernel_head"] = dense_init(
                keys[6], F.KERNEL_FEATURE_DIM, 1, bias=False, dtype=dtype)
    else:
        out_dim = R.reduction_out_dim(cfg.reduction, d)
        if cfg.kernel_feat_mode == "kernel":
            out_dim += F.KERNEL_FEATURE_DIM
        params["head"] = dense_init(keys[5], out_dim, 1, bias=False, dtype=dtype)
    return params


def cost_model_apply(params: dict, cfg: CostModelConfig, batch,
                     *, rng=None, deterministic: bool = True) -> jnp.ndarray:
    """batch: features.GraphBatch or features.SparseGraphBatch (pytrees).
    Returns predictions [B] (one per graph slot). Both representations share
    one parameter tree and agree numerically (DESIGN.md §4)."""
    if cfg.precision == "int8":
        from repro.quant.scale import dequantize_tree
        # sparse + Pallas: the GNN tree stays quantized — its f2
        # weights feed the segment_aggregate kernel as int8 and are
        # dequantized in-VMEM; everything else decodes here, inside jit
        keep_gnn = (cfg.use_pallas_aggregate and "gnn" in params
                    and not isinstance(batch, F.GraphBatch))
        gnn_q = params["gnn"] if keep_gnn else None
        params = dequantize_tree(params)
        if gnn_q is not None:
            params = dict(params, gnn=gnn_q)
    if isinstance(batch, F.SegmentedGraphBatch):
        return _cost_model_apply_segmented(params, cfg, batch, rng=rng,
                                           deterministic=deterministic)
    if isinstance(batch, F.SparseGraphBatch):
        return _cost_model_apply_sparse(params, cfg, batch, rng=rng,
                                        deterministic=deterministic)
    opcodes = batch.opcodes
    node_feats = batch.node_feats
    adj = batch.adj
    mask = batch.node_mask
    kfeats = batch.kernel_feats

    if not cfg.include_tile:
        kfeats = kfeats.at[:, F.TILE_SLICE].set(0.0)
    if not cfg.include_static_perf:
        kfeats = kfeats.at[:, F.STATIC_PERF_SLICE].set(0.0)

    with jax.named_scope("embed"):
        emb = embedding_apply(params["opcode_embed"], opcodes)  # [B,N,E]
        x = jnp.concatenate([emb, node_feats], axis=-1)
        if cfg.kernel_feat_mode == "node":
            B, N = opcodes.shape
            kf = jnp.broadcast_to(kfeats[:, None, :],
                                  (B, N, kfeats.shape[-1]))
            x = jnp.concatenate([x, kf], axis=-1)
        eps = jax.nn.relu(dense_apply(params["f1"], x)) * mask[..., None]

    with jax.named_scope("gnn"):
        if cfg.gnn == "graphsage":
            eps = G.sage_apply(params["gnn"], eps, adj, mask,
                               aggregator=cfg.aggregator,
                               directed=cfg.directed,
                               use_pallas=cfg.use_pallas_aggregate)
        elif cfg.gnn == "gat":
            eps = G.gat_apply(params["gnn"], eps, adj, mask,
                              num_heads=cfg.gat_heads, directed=cfg.directed)

    with jax.named_scope("node_final"):
        sub = None if rng is None else jax.random.fold_in(rng, 1)
        eps = dropout(sub, eps, cfg.dropout, deterministic)
        eps = mlp_apply(params["node_final"], eps, final_act=True)
        eps = eps * mask[..., None]

    if cfg.reduction == "per_node":
        with jax.named_scope("head"):
            per_node = dense_apply(params["node_head"], eps)[..., 0]
            y = jnp.sum(per_node * mask, axis=1)
            if cfg.kernel_feat_mode == "kernel":
                y = y + dense_apply(params["kernel_head"], kfeats)[..., 0]
        return y

    with jax.named_scope("reduction"):
        kappa = R.reduction_apply(params["reduction"], cfg.reduction, eps,
                                  mask,
                                  transformer_heads=cfg.transformer_heads,
                                  rng=rng, dropout_rate=cfg.dropout,
                                  deterministic=deterministic)
    with jax.named_scope("head"):
        if cfg.kernel_feat_mode == "kernel":
            kappa = jnp.concatenate([kappa, kfeats], axis=-1)
        return dense_apply(params["head"], kappa)[..., 0]


def _mask_kernel_feats(cfg: CostModelConfig, kfeats: jnp.ndarray):
    if not cfg.include_tile:
        kfeats = kfeats.at[:, F.TILE_SLICE].set(0.0)
    if not cfg.include_static_perf:
        kfeats = kfeats.at[:, F.STATIC_PERF_SLICE].set(0.0)
    return kfeats


def _embed_sparse(params: dict, cfg: CostModelConfig, batch) -> jnp.ndarray:
    """Embed + f1 + GNN over a flat sparse node buffer: the per-node half
    of the sparse forward pass, shared by the plain sparse path and the
    segmented path (which runs it on segment blocks before reassembly)."""
    mask = batch.node_mask                       # [M]
    kfeats = _mask_kernel_feats(cfg, batch.kernel_feats)

    with jax.named_scope("embed"):
        emb = embedding_apply(params["opcode_embed"], batch.opcodes)
        x = jnp.concatenate([emb, batch.node_feats], axis=-1)   # [M, ·]
        if cfg.kernel_feat_mode == "node":
            x = jnp.concatenate(
                [x, jnp.take(kfeats, batch.graph_ids, axis=0)], axis=-1)
        eps = jax.nn.relu(dense_apply(params["f1"], x)) * mask[:, None]
    with jax.named_scope("gnn"):
        if cfg.gnn == "graphsage" and cfg.use_pallas_aggregate:
            # fused kernels/segment_aggregate path (f32 or int8 f2 weights)
            eps = G.sage_apply_sparse_q(params["gnn"], eps, batch.edge_src,
                                        batch.edge_dst, batch.edge_mask,
                                        mask, aggregator=cfg.aggregator,
                                        directed=cfg.directed)
        elif cfg.gnn == "graphsage":
            eps = G.sage_apply_sparse(params["gnn"], eps, batch.edge_src,
                                      batch.edge_dst, batch.edge_mask, mask,
                                      aggregator=cfg.aggregator,
                                      directed=cfg.directed)
        elif cfg.gnn == "gat":
            eps = G.gat_apply_sparse(params["gnn"], eps, batch.edge_src,
                                     batch.edge_dst, batch.edge_mask, mask,
                                     num_heads=cfg.gat_heads,
                                     directed=cfg.directed)
    return eps


def _cost_model_apply_sparse(params: dict, cfg: CostModelConfig, batch,
                             *, rng=None,
                             deterministic: bool = True) -> jnp.ndarray:
    """Sparse/packed forward pass: flat [M, ·] node buffer, segment_sum
    aggregation, per-graph readout via segment ids (or a gather into a
    [G, R, D] layout for the sequence reductions)."""
    eps = _embed_sparse(params, cfg, batch)
    return _readout_sparse(params, cfg, eps, batch.node_mask,
                           batch.graph_ids, batch.kernel_feats,
                           batch.gather_idx, batch.gather_mask,
                           rng=rng, deterministic=deterministic)


def _cost_model_apply_segmented(params: dict, cfg: CostModelConfig, batch,
                                *, rng=None,
                                deterministic: bool = True) -> jnp.ndarray:
    """Whole-program forward pass (DESIGN.md §12): run the per-node half on
    the inner segment batch, scatter owned-node embeddings back into
    whole-graph node order, then read out per original graph. Graphs that
    fit one segment go through bit-identically to the sparse path."""
    eps_in = _embed_sparse(params, cfg, batch.inner)       # [M_inner, D]
    M = batch.num_nodes
    # halo + padding rows target the dummy slot M and are dropped; owned
    # slots are written exactly once (owned sets partition the graph)
    buf = jnp.zeros((M + 1, eps_in.shape[-1]), eps_in.dtype)
    eps = buf.at[batch.scatter_idx].set(eps_in)[:M]
    return _readout_sparse(params, cfg, eps, batch.node_mask,
                           batch.graph_ids, batch.kernel_feats,
                           batch.gather_idx, batch.gather_mask,
                           rng=rng, deterministic=deterministic)


def _readout_sparse(params: dict, cfg: CostModelConfig, eps: jnp.ndarray,
                    mask: jnp.ndarray, gids: jnp.ndarray,
                    kfeats: jnp.ndarray, gather_idx: jnp.ndarray,
                    gather_mask: jnp.ndarray, *, rng=None,
                    deterministic: bool = True) -> jnp.ndarray:
    """node-final MLP + reduction + head over a flat [M, D] embedding
    buffer with per-node graph ids — the per-graph half of the sparse
    forward pass (also the segmented path's outer readout)."""
    num_graphs = kfeats.shape[0]
    kfeats = _mask_kernel_feats(cfg, kfeats)

    with jax.named_scope("node_final"):
        sub = None if rng is None else jax.random.fold_in(rng, 1)
        eps = dropout(sub, eps, cfg.dropout, deterministic)
        eps = mlp_apply(params["node_final"], eps, final_act=True)
        eps = eps * mask[:, None]

    if cfg.reduction == "per_node":
        with jax.named_scope("head"):
            per_node = dense_apply(params["node_head"], eps)[..., 0]  # [M]
            y = jax.ops.segment_sum(per_node * mask, gids,
                                    num_segments=num_graphs)
            if cfg.kernel_feat_mode == "kernel":
                y = y + dense_apply(params["kernel_head"], kfeats)[..., 0]
        return y

    with jax.named_scope("reduction"):
        if cfg.reduction == "column_wise":
            s = jax.ops.segment_sum(eps * mask[:, None], gids,
                                    num_segments=num_graphs)
            cnt = jax.ops.segment_sum(mask, gids, num_segments=num_graphs)
            n = jnp.maximum(cnt, 1.0)
            neg = jnp.finfo(eps.dtype).min
            mx = jax.ops.segment_max(jnp.where(mask[:, None] > 0, eps, neg),
                                     gids, num_segments=num_graphs)
            # padding graph slots have no nodes; zero them instead of
            # -inf/min so the head stays finite (their predictions are
            # masked by `valid`)
            mx = jnp.where(cnt[:, None] > 0, mx, 0.0)
            kappa = jnp.concatenate([s / n[:, None], mx], axis=-1)
        else:
            # sequence reductions (LSTM/Transformer) need per-graph node
            # order; gather the flat buffer into [G, R, D] (R = packed
            # reduce capacity, typically ≪ the dense path's max_nodes ×
            # slot padding)
            eps_pad = jnp.concatenate(
                [eps, jnp.zeros((1, eps.shape[-1]), eps.dtype)], axis=0)
            seq = jnp.take(eps_pad, gather_idx, axis=0)        # [G, R, D]
            kappa = R.reduction_apply(
                params["reduction"], cfg.reduction, seq, gather_mask,
                transformer_heads=cfg.transformer_heads, rng=rng,
                dropout_rate=cfg.dropout, deterministic=deterministic)
    with jax.named_scope("head"):
        if cfg.kernel_feat_mode == "kernel":
            kappa = jnp.concatenate([kappa, kfeats], axis=-1)
        return dense_apply(params["head"], kappa)[..., 0]


def param_count(params) -> int:
    return int(sum(x.size for x in jax.tree_util.tree_leaves(params)))
