"""Graph neural networks over batched kernel graphs (paper §3.2).

GraphSAGE (the paper's choice) and GAT (the ablation alternative), both
direction-aware: incoming and outgoing edges aggregate through separate
feedforward modules ('Undirected' ablation shares them).

Two numerically equivalent aggregation backends share one parameter tree:

* dense — a masked-adjacency matmul `adj[b, d, s] @ h[b, s, :]`, the
  TPU-native formulation (MXU-friendly; see DESIGN.md §3).
  `repro.kernels.graph_aggregate` provides the fused Pallas version; the
  jnp path here is used for training on CPU and as the kernel oracle.
* sparse — `jax.ops.segment_sum` over a packed edge list
  (`*_apply_sparse`), linear in edge count instead of quadratic in the
  padded node count; used with `features.SparseGraphBatch` (DESIGN.md §4).

Two numerically equivalent *layer-stack* layouts share the same layer code
(DESIGN.md §12):

* unrolled — `{"layers": [layer_0, ..., layer_{L-1}]}`, a Python loop;
  each `jit` trace inlines every layer, so trace/compile cost grows with
  depth × number of batch shapes.
* stacked — `{"stacked": tree}` where each leaf carries a leading layer
  axis `[L, ...]`; every `*_apply` runs the layer body once under
  `jax.lax.scan`, so trace cost is depth-independent (the scan-over-layers
  idiom). `stack_params` / `unstack_params` convert between the layouts
  bit-exactly, and `training.checkpoint.restore_checkpoint` restores
  either layout from either on-disk layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.core import (
    dense_apply,
    dense_init,
    l2_normalize,
)


# ----------------------------------------------------------------------------
# Layer-stack layout converters + scan-over-layers driver (DESIGN.md §12)
# ----------------------------------------------------------------------------
def stack_params(params: dict) -> dict:
    """Convert an unrolled GNN parameter tree (``{"layers": [...]}``) to the
    stacked layout (``{"stacked": tree}``, leaves ``[L, ...]``).

    Stacking is exact (`jnp.stack` of the per-layer leaves), so predictions
    and gradients through the scan path match the unrolled path.

    >>> import jax, numpy as np
    >>> p = sage_init(jax.random.key(0), 8, 3, directed=True)
    >>> s = stack_params(p)
    >>> s["stacked"]["f2_in"]["w"].shape
    (3, 8, 8)
    >>> u = unstack_params(s)
    >>> bool(np.array_equal(u["layers"][1]["f3"]["w"],
    ...                     p["layers"][1]["f3"]["w"]))
    True
    """
    if "stacked" in params:
        return params
    layers = params["layers"]
    if not layers:
        raise ValueError("cannot stack an empty layer list")
    return {"stacked": jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *layers)}


def unstack_params(params: dict) -> dict:
    """Inverse of `stack_params`: split the leading layer axis back into a
    per-layer list. Exact (pure slicing)."""
    if "layers" in params:
        return params
    stacked = params["stacked"]
    num_layers = int(jax.tree_util.tree_leaves(stacked)[0].shape[0])
    return {"layers": [jax.tree_util.tree_map(lambda x: x[i], stacked)
                       for i in range(num_layers)]}


def num_layers(params: dict) -> int:
    """Depth of a GNN parameter tree in either layout."""
    if "stacked" in params:
        return int(jax.tree_util.tree_leaves(params["stacked"])[0].shape[0])
    return len(params["layers"])


def _apply_stack(params: dict, eps: jnp.ndarray, layer_fn) -> jnp.ndarray:
    """Run `layer_fn(layer_params, h) -> h` over every layer of `params`.

    Stacked layout → one `lax.scan` (the layer body traces once per
    enclosing jit trace, regardless of depth); unrolled layout → a Python
    loop (the body traces once per layer).
    """
    if "stacked" in params:
        def body(h, layer):
            return layer_fn(layer, h), None
        eps, _ = jax.lax.scan(body, eps, params["stacked"])
        return eps
    for layer in params["layers"]:
        eps = layer_fn(layer, eps)
    return eps


# Layer-body trace counters (benchmarks/bench_giant_graphs.py): every call
# of a `*_layer_apply*` body bumps one of these. Under jit that happens at
# *trace* time only, so the counters measure exactly the trace/compile
# blowup the scan path removes: unrolled traces the body depth× per batch
# shape, stacked traces it once per shape.
_TRACE_COUNTS = {"dense": 0, "sparse": 0}


def reset_layer_trace_counts() -> None:
    _TRACE_COUNTS["dense"] = 0
    _TRACE_COUNTS["sparse"] = 0


def layer_trace_counts() -> dict:
    return dict(_TRACE_COUNTS)


# ----------------------------------------------------------------------------
# GraphSAGE
# ----------------------------------------------------------------------------
def sage_layer_init(rng, dim: int, *, directed: bool, dtype=jnp.float32) -> dict:
    k_in, k_out, k3 = jax.random.split(rng, 3)
    params = {
        "f2_in": dense_init(k_in, dim, dim, bias=False, dtype=dtype),
        # concat(self, agg_in[, agg_out]) -> dim
        "f3": dense_init(k3, dim * (3 if directed else 2), dim, bias=False,
                         dtype=dtype),
    }
    if directed:
        params["f2_out"] = dense_init(k_out, dim, dim, bias=False, dtype=dtype)
    return params


def _aggregate(adj: jnp.ndarray, h: jnp.ndarray, node_mask: jnp.ndarray,
               aggregator: str) -> jnp.ndarray:
    """adj: [B,N,N] (adj[b,d,s]); h: [B,N,D]; returns [B,N,D] per-dst agg."""
    h = h * node_mask[..., None]
    agg = jnp.einsum("bds,bsh->bdh", adj, h)
    if aggregator == "mean":
        deg = jnp.sum(adj, axis=-1, keepdims=True)
        agg = agg / jnp.maximum(deg, 1.0)
    return agg


def sage_layer_apply(params: dict, eps: jnp.ndarray, adj: jnp.ndarray,
                     node_mask: jnp.ndarray, *, aggregator: str = "mean",
                     directed: bool = True,
                     use_pallas: bool = False) -> jnp.ndarray:
    """One GraphSAGE hop:
    eps_i^k = l2( f3( concat(eps_i, Σ_{j∈in(i)} f2_in(eps_j)
                              [, Σ_{j∈out(i)} f2_out(eps_j)]) ) )

    use_pallas=True routes the transform+aggregate through the fused
    repro.kernels.graph_aggregate kernel (beyond-paper optimization):
    interpreted off-TPU; it compiles for a v5e at B=32, N=64, D=F=192
    (tests/test_tpu_compile.py). Its speed is not measured.
    """
    _TRACE_COUNTS["dense"] += 1
    if use_pallas:
        from repro.kernels import interpret_mode
        from repro.kernels.graph_aggregate.ops import graph_aggregate
        interp = interpret_mode()
        mean = aggregator == "mean"
        agg_in = graph_aggregate(adj, eps, params["f2_in"]["w"],
                                 act="relu", mean=mean, interpret=interp)
        parts = [eps, agg_in]
        if directed:
            adj_t = jnp.swapaxes(adj, -1, -2)
            parts.append(graph_aggregate(adj_t, eps, params["f2_out"]["w"],
                                         act="relu", mean=mean,
                                         interpret=interp))
        else:
            adj_t = jnp.swapaxes(adj, -1, -2)
            agg_out = graph_aggregate(adj_t, eps, params["f2_in"]["w"],
                                      act="relu", mean=mean,
                                      interpret=interp)
            parts[1] = 0.5 * (agg_in + agg_out)
        h = dense_apply(params["f3"], jnp.concatenate(parts, axis=-1))
        h = jax.nn.relu(h)
        return l2_normalize(h, axis=-1) * node_mask[..., None]

    msg_in = jax.nn.relu(dense_apply(params["f2_in"], eps))
    agg_in = _aggregate(adj, msg_in, node_mask, aggregator)
    parts = [eps, agg_in]
    if directed:
        msg_out = jax.nn.relu(dense_apply(params["f2_out"], eps))
        # outgoing edges: transpose the adjacency
        agg_out = _aggregate(jnp.swapaxes(adj, -1, -2), msg_out, node_mask,
                             aggregator)
        parts.append(agg_out)
    else:
        # undirected ablation: same module, symmetrized adjacency
        agg_out = _aggregate(jnp.swapaxes(adj, -1, -2), msg_in, node_mask,
                             aggregator)
        parts[1] = 0.5 * (agg_in + agg_out)
    h = dense_apply(params["f3"], jnp.concatenate(parts, axis=-1))
    h = jax.nn.relu(h)
    return l2_normalize(h, axis=-1) * node_mask[..., None]


def sage_init(rng, dim: int, num_layers: int, *, directed: bool = True,
              dtype=jnp.float32) -> dict:
    keys = jax.random.split(rng, max(num_layers, 1))
    return {"layers": [sage_layer_init(keys[i], dim, directed=directed,
                                       dtype=dtype)
                       for i in range(num_layers)]}


def sage_apply(params: dict, eps: jnp.ndarray, adj: jnp.ndarray,
               node_mask: jnp.ndarray, *, aggregator: str = "mean",
               directed: bool = True, use_pallas: bool = False) -> jnp.ndarray:
    def layer_fn(layer, h):
        return sage_layer_apply(layer, h, adj, node_mask,
                                aggregator=aggregator, directed=directed,
                                use_pallas=use_pallas)
    return _apply_stack(params, eps, layer_fn)


# ----------------------------------------------------------------------------
# Sparse (segment-sum) backend — flat [M, D] node buffer + packed edge list
# ----------------------------------------------------------------------------
def _segment_aggregate(msg: jnp.ndarray, gather: jnp.ndarray,
                       scatter: jnp.ndarray, edge_mask: jnp.ndarray,
                       node_mask: jnp.ndarray, aggregator: str) -> jnp.ndarray:
    """Aggregate per-node messages along edges.

    msg: [M, D]; gather/scatter: [E] flat node indices (message taken at
    `gather`, summed into `scatter`); returns [M, D]. With gather=src,
    scatter=dst this is in-edge aggregation (== dense `adj @ h`); swapped,
    out-edge aggregation (== dense `adjᵀ @ h`).
    """
    m = msg * node_mask[:, None]
    w = edge_mask[:, None]
    agg = jax.ops.segment_sum(m[gather] * w, scatter,
                              num_segments=msg.shape[0])
    if aggregator == "mean":
        deg = jax.ops.segment_sum(edge_mask, scatter,
                                  num_segments=msg.shape[0])
        agg = agg / jnp.maximum(deg, 1.0)[:, None]
    return agg


def sage_layer_apply_sparse(params: dict, eps: jnp.ndarray,
                            edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
                            edge_mask: jnp.ndarray, node_mask: jnp.ndarray,
                            *, aggregator: str = "mean",
                            directed: bool = True) -> jnp.ndarray:
    """Sparse twin of `sage_layer_apply` over a flat node buffer.

    Takes the same parameter tree; numerically equivalent to the dense path
    on the same graphs (tests/test_sparse_batching.py pins this).
    """
    _TRACE_COUNTS["sparse"] += 1
    msg_in = jax.nn.relu(dense_apply(params["f2_in"], eps))
    agg_in = _segment_aggregate(msg_in, edge_src, edge_dst, edge_mask,
                                node_mask, aggregator)
    parts = [eps, agg_in]
    if directed:
        msg_out = jax.nn.relu(dense_apply(params["f2_out"], eps))
        agg_out = _segment_aggregate(msg_out, edge_dst, edge_src, edge_mask,
                                     node_mask, aggregator)
        parts.append(agg_out)
    else:
        agg_out = _segment_aggregate(msg_in, edge_dst, edge_src, edge_mask,
                                     node_mask, aggregator)
        parts[1] = 0.5 * (agg_in + agg_out)
    h = dense_apply(params["f3"], jnp.concatenate(parts, axis=-1))
    h = jax.nn.relu(h)
    return l2_normalize(h, axis=-1) * node_mask[:, None]


def sage_apply_sparse(params: dict, eps: jnp.ndarray, edge_src: jnp.ndarray,
                      edge_dst: jnp.ndarray, edge_mask: jnp.ndarray,
                      node_mask: jnp.ndarray, *, aggregator: str = "mean",
                      directed: bool = True) -> jnp.ndarray:
    def layer_fn(layer, h):
        return sage_layer_apply_sparse(layer, h, edge_src, edge_dst,
                                       edge_mask, node_mask,
                                       aggregator=aggregator,
                                       directed=directed)
    return _apply_stack(params, eps, layer_fn)


def _f2_qs(leaf: dict):
    """(weights, per-output-channel scale) of one f2 module for the fused
    kernel: int8 q + its scale for a `quant.scale.QuantizedLeaf`, the f32
    weight with unit scales otherwise (the kernel's dequant is then a
    no-op multiply, so the f32 sparse-Pallas path costs nothing extra)."""
    from repro.quant.scale import QuantizedLeaf
    w = leaf["w"]
    if isinstance(w, QuantizedLeaf):
        return w.q, w.scale.reshape(1, -1)
    return w, jnp.ones((1, w.shape[-1]), jnp.float32)


def sage_layer_apply_sparse_q(params: dict, eps: jnp.ndarray,
                              edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
                              edge_mask: jnp.ndarray, node_mask: jnp.ndarray,
                              *, aggregator: str = "mean",
                              directed: bool = True) -> jnp.ndarray:
    """`sage_layer_apply_sparse` with the transform+aggregate fused into
    the `repro.kernels.segment_aggregate` Pallas kernel (inference-only —
    the kernel has no VJP; the trainer stays on the jnp twin). The f2
    weights may be int8 `QuantizedLeaf`s (dequantized in-VMEM, DESIGN.md
    §14) or plain f32; f3 is dequantized outside the kernel either way.
    The kernel is interpreted off-TPU (`kernels.interpret_mode`)."""
    from repro.kernels import interpret_mode
    from repro.kernels.segment_aggregate.ops import segment_aggregate
    from repro.quant.scale import leaf_f32
    _TRACE_COUNTS["sparse"] += 1
    mean = aggregator == "mean"
    interpret = interpret_mode()

    def fused(leaf, gather, scatter):
        w, scale = _f2_qs(leaf)
        return segment_aggregate(eps, w, scale, gather, scatter, edge_mask,
                                 node_mask, act="relu", mean=mean,
                                 interpret=interpret)

    agg_in = fused(params["f2_in"], edge_src, edge_dst)
    parts = [eps, agg_in]
    if directed:
        parts.append(fused(params["f2_out"], edge_dst, edge_src))
    else:
        agg_out = fused(params["f2_in"], edge_dst, edge_src)
        parts[1] = 0.5 * (agg_in + agg_out)
    f3 = {"w": leaf_f32(params["f3"]["w"])}
    h = dense_apply(f3, jnp.concatenate(parts, axis=-1))
    h = jax.nn.relu(h)
    return l2_normalize(h, axis=-1) * node_mask[:, None]


def sage_apply_sparse_q(params: dict, eps: jnp.ndarray,
                        edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
                        edge_mask: jnp.ndarray, node_mask: jnp.ndarray, *,
                        aggregator: str = "mean",
                        directed: bool = True) -> jnp.ndarray:
    """Kernel-backed twin of `sage_apply_sparse` (f32 or int8 params)."""
    def layer_fn(layer, h):
        return sage_layer_apply_sparse_q(layer, h, edge_src, edge_dst,
                                         edge_mask, node_mask,
                                         aggregator=aggregator,
                                         directed=directed)
    return _apply_stack(params, eps, layer_fn)


# ----------------------------------------------------------------------------
# GAT
# ----------------------------------------------------------------------------
def gat_layer_init(rng, dim: int, num_heads: int, *, directed: bool,
                   dtype=jnp.float32) -> dict:
    assert dim % num_heads == 0
    hd = dim // num_heads
    ks = jax.random.split(rng, 6)
    params = {
        "w_in": dense_init(ks[0], dim, dim, bias=False, dtype=dtype),
        "a_src_in": jax.random.normal(ks[1], (num_heads, hd), dtype) * 0.1,
        "a_dst_in": jax.random.normal(ks[2], (num_heads, hd), dtype) * 0.1,
        "proj": dense_init(ks[3], dim * (2 if directed else 1), dim,
                           bias=False, dtype=dtype),
    }
    if directed:
        params["w_out"] = dense_init(ks[4], dim, dim, bias=False, dtype=dtype)
        params["a_src_out"] = jax.random.normal(ks[5], (num_heads, hd),
                                                dtype) * 0.1
        # independent copy — an aliased leaf would be donated twice
        params["a_dst_out"] = params["a_dst_in"] + 0.0
    return params


def _gat_attend(h: jnp.ndarray, adj: jnp.ndarray, a_src: jnp.ndarray,
                a_dst: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """Masked multi-head attention aggregation over in-edges of `adj`."""
    B, N, D = h.shape
    hd = D // num_heads
    hh = h.reshape(B, N, num_heads, hd)
    e_src = jnp.einsum("bnhd,hd->bnh", hh, a_src)   # score contribution of src
    e_dst = jnp.einsum("bnhd,hd->bnh", hh, a_dst)
    # logits[b, h, d, s] = leaky_relu(e_dst[d] + e_src[s])
    logits = jax.nn.leaky_relu(
        e_dst.transpose(0, 2, 1)[:, :, :, None] +
        e_src.transpose(0, 2, 1)[:, :, None, :], 0.2)
    neg = jnp.finfo(logits.dtype).min
    mask = adj[:, None, :, :] > 0
    logits = jnp.where(mask, logits, neg)
    alpha = jax.nn.softmax(logits, axis=-1)
    # rows with no in-edges get a uniform softmax over masked -inf -> nan-free
    alpha = jnp.where(jnp.any(mask, axis=-1, keepdims=True), alpha, 0.0)
    out = jnp.einsum("bhds,bshx->bdhx", alpha, hh)
    return out.reshape(B, N, D)


def gat_layer_apply(params: dict, eps: jnp.ndarray, adj: jnp.ndarray,
                    node_mask: jnp.ndarray, *, num_heads: int,
                    directed: bool = True) -> jnp.ndarray:
    _TRACE_COUNTS["dense"] += 1
    h_in = dense_apply(params["w_in"], eps)
    agg_in = _gat_attend(h_in, adj, params["a_src_in"], params["a_dst_in"],
                         num_heads)
    if directed:
        h_out = dense_apply(params["w_out"], eps)
        agg_out = _gat_attend(h_out, jnp.swapaxes(adj, -1, -2),
                              params["a_src_out"], params["a_dst_out"],
                              num_heads)
        agg = jnp.concatenate([agg_in, agg_out], axis=-1)
    else:
        sym = jnp.maximum(adj, jnp.swapaxes(adj, -1, -2))
        agg = _gat_attend(h_in, sym, params["a_src_in"], params["a_dst_in"],
                          num_heads)
    h = dense_apply(params["proj"], agg)
    h = jax.nn.elu(h) + eps          # residual keeps training stable
    return h * node_mask[..., None]


def gat_init(rng, dim: int, num_layers: int, num_heads: int, *,
             directed: bool = True, dtype=jnp.float32) -> dict:
    keys = jax.random.split(rng, max(num_layers, 1))
    return {"layers": [gat_layer_init(keys[i], dim, num_heads,
                                      directed=directed, dtype=dtype)
                       for i in range(num_layers)]}


def gat_apply(params: dict, eps: jnp.ndarray, adj: jnp.ndarray,
              node_mask: jnp.ndarray, *, num_heads: int,
              directed: bool = True) -> jnp.ndarray:
    def layer_fn(layer, h):
        return gat_layer_apply(layer, h, adj, node_mask, num_heads=num_heads,
                               directed=directed)
    return _apply_stack(params, eps, layer_fn)


def _gat_attend_sparse(h: jnp.ndarray, edge_src: jnp.ndarray,
                       edge_dst: jnp.ndarray, edge_mask: jnp.ndarray,
                       a_src: jnp.ndarray, a_dst: jnp.ndarray,
                       num_heads: int) -> jnp.ndarray:
    """Segment-softmax attention over in-edges: sparse twin of `_gat_attend`.

    h: [M, D]; edges are flat indices into the node buffer. The softmax per
    (dst, head) segment is max-shifted for stability; destinations with no
    in-edges get a zero output, matching the dense path's masked softmax.
    """
    M, D = h.shape
    hd = D // num_heads
    hh = h.reshape(M, num_heads, hd)
    e_src = jnp.einsum("mhd,hd->mh", hh, a_src)
    e_dst = jnp.einsum("mhd,hd->mh", hh, a_dst)
    logits = jax.nn.leaky_relu(e_dst[edge_dst] + e_src[edge_src], 0.2)
    neg = jnp.finfo(logits.dtype).min
    z = jnp.where(edge_mask[:, None] > 0, logits, neg)
    zmax = jax.ops.segment_max(z, edge_dst, num_segments=M)      # [M, H]
    zmax = jnp.maximum(zmax, neg)            # empty segments: -inf → finite
    num = jnp.exp(z - zmax[edge_dst]) * edge_mask[:, None]       # [E, H]
    den = jax.ops.segment_sum(num, edge_dst, num_segments=M)     # [M, H]
    alpha = num / jnp.maximum(den[edge_dst], 1e-30)
    out = jax.ops.segment_sum(alpha[:, :, None] * hh[edge_src], edge_dst,
                              num_segments=M)                    # [M, H, hd]
    return out.reshape(M, D)


def gat_layer_apply_sparse(params: dict, eps: jnp.ndarray,
                           edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
                           edge_mask: jnp.ndarray, node_mask: jnp.ndarray,
                           *, num_heads: int,
                           directed: bool = True) -> jnp.ndarray:
    if not directed:
        # the symmetrized (max(adj, adjᵀ)) edge set can't be deduplicated
        # under jit with static shapes; the ablation stays on the dense path
        raise NotImplementedError(
            "undirected GAT is dense-only; use adjacency='dense' "
            "(see DESIGN.md §4)")
    _TRACE_COUNTS["sparse"] += 1
    h_in = dense_apply(params["w_in"], eps)
    agg_in = _gat_attend_sparse(h_in, edge_src, edge_dst, edge_mask,
                                params["a_src_in"], params["a_dst_in"],
                                num_heads)
    h_out = dense_apply(params["w_out"], eps)
    agg_out = _gat_attend_sparse(h_out, edge_dst, edge_src, edge_mask,
                                 params["a_src_out"], params["a_dst_out"],
                                 num_heads)
    agg = jnp.concatenate([agg_in, agg_out], axis=-1)
    h = dense_apply(params["proj"], agg)
    h = jax.nn.elu(h) + eps
    return h * node_mask[:, None]


def gat_apply_sparse(params: dict, eps: jnp.ndarray, edge_src: jnp.ndarray,
                     edge_dst: jnp.ndarray, edge_mask: jnp.ndarray,
                     node_mask: jnp.ndarray, *, num_heads: int,
                     directed: bool = True) -> jnp.ndarray:
    def layer_fn(layer, h):
        return gat_layer_apply_sparse(layer, h, edge_src, edge_dst,
                                      edge_mask, node_mask,
                                      num_heads=num_heads, directed=directed)
    return _apply_stack(params, eps, layer_fn)
