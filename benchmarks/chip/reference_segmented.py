"""Plain reference of the cost model on whole programs above the node
budget: the segmented path (DESIGN.md §12) written out per graph.

It imports nothing of the `repro` package. A graph in its wire form is cut
here into contiguous topological blocks of at most `max_nodes` nodes,
owned nodes plus halo: copies of the out-of-block producers the block's
nodes read, with their inputs cleared and not outputs, so every edge lies
in the block that owns its destination. Each block is featurized as a
graph of its own, with the whole program's kernel features, and runs the
GraphSAGE layers densely (`reference.node_embeddings`, with no node-final
layers). The owned nodes' embeddings are put back in program order, and
the node-final MLP, the Transformer readout and the head run over the
whole program.

Precision, as `reference.score`: the configuration states float32 with
matmuls at JAX's default precision, which on a TPU rounds each matmul's
operands to bfloat16. So every weight matmul runs at the default
precision and the neighbour sums at the highest. The readout's attention
weighs the values in key blocks with an online softmax: past
`DENSE_MAX_NODES` nodes a program's [N, N] logits do not fit the chip, and
the block's unnormalized probabilities are the operands that the default
precision rounds. `jnp.bfloat16` is the control, weights and activations
one precision below the configuration's.
"""
from __future__ import annotations

import math

import numpy as np

import reference

# the longest readout that attends in one block, and the keys of a block
# beyond it: the program's sizes (`repro.nn.transformer`), at which the
# default precision rounds the same operands
DENSE_MAX_NODES = 8192
KEY_BLOCK = 1024


def segments(g: dict, max_nodes: int) -> list[tuple[int, int, list[int]]]:
    """(lo, hi, halo) of each block of `g`: nodes lo..hi-1 in order, and
    the sorted producers before lo that they read. A node joins the open
    block unless it, with the new halo it brings, would take the block past
    `max_nodes`; then the block closes and the node opens the next. A graph
    within `max_nodes` is one block with no halo."""
    nodes = g["nodes"]
    n = len(nodes)
    if n <= max_nodes:
        return [(0, n, [])]
    blocks = []
    lo, halo, i = 0, set(), 0
    while i < n:
        new = {j for j in nodes[i]["inputs"] if j < lo} - halo
        if (i - lo + 1) + len(halo) + len(new) > max_nodes:
            if i == lo:
                raise ValueError(f"node {i} reads {len(new)} nodes of "
                                 f"earlier blocks, over {max_nodes}")
            blocks.append((lo, i, sorted(halo)))
            lo, halo = i, set()
            continue
        halo |= new
        i += 1
    blocks.append((lo, n, sorted(halo)))
    return blocks


def block_graph(g: dict, lo: int, hi: int, halo: list[int]) -> dict:
    """The wire form of one block: its halo copies, then its own nodes with
    their inputs renumbered."""
    local, nodes = {}, []
    for j in halo:
        local[j] = len(nodes)
        nodes.append(dict(g["nodes"][j], inputs=[], is_output=False))
    for j in range(lo, hi):
        local[j] = len(nodes)
        nd = g["nodes"][j]
        nodes.append(dict(nd, inputs=[local[k] for k in nd["inputs"]]))
    return {"nodes": nodes, "tile_size": g["tile_size"]}


def _gnn(params, cfg, b):
    """f1 and the GraphSAGE layers of a dense batch of blocks: the
    reference's node embeddings with the node-final layers left for the
    whole program."""
    bare = dict(params, node_final={"layers": []})
    return reference.node_embeddings(bare, cfg, b)


def _attention(q, k, v, mask):
    """Softmax attention of q over k, v [B, N, H, hd], masked keys left
    out: over all keys at once up to `DENSE_MAX_NODES`, else key block by
    key block in turn (N a multiple of `KEY_BLOCK`), each query keeping
    its running maximum, normalizer and weighted sum of values."""
    import jax
    import jax.numpy as jnp
    n, hd = k.shape[1], q.shape[-1]
    neg = jnp.finfo(q.dtype).min
    if n <= DENSE_MAX_NODES:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        logits = jnp.where(mask[:, None, None, :] > 0, logits, neg)
        att = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)
    scale = 1.0 / math.sqrt(hd)

    def block(carry, kvm):
        m, total, acc = carry
        kb, vb, mb = kvm
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb) * scale
        s = jnp.where(mb[:, None, None, :] > 0, s, neg)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        kept = jnp.exp(m - m_new)
        return (m_new, total * kept + p.sum(-1),
                acc * kept[..., None]
                + jnp.einsum("bhqk,bkhd->bhqd", p, vb)), None

    def blocks(x):
        """[B, N, ...] -> [N / KEY_BLOCK, B, KEY_BLOCK, ...]"""
        x = x.reshape(x.shape[:1] + (n // KEY_BLOCK, KEY_BLOCK)
                      + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    m = jnp.full(q.shape[:1] + (q.shape[2], n), -jnp.inf, q.dtype)
    acc = jnp.zeros(q.shape[:1] + (q.shape[2], n, hd), q.dtype)
    (_, total, acc), _ = jax.lax.scan(
        block, (m, jnp.zeros_like(m), acc), (blocks(k), blocks(v),
                                              blocks(mask)))
    return jnp.swapaxes(acc / total[..., None], 1, 2)


def _readout(params, cfg, h, mask):
    """Node-final MLP, the pre-norm Transformer encoder, the masked sum and
    the head of one program's embeddings h [1, N, D], N a power of two,
    padding rows masked -> score [1]."""
    import jax
    relu = jax.nn.relu
    for layer in params["node_final"]["layers"]:
        h = relu(h @ layer["w"])
    x = h * mask[..., None]
    enc = params["reduction"]["encoder"]
    bsz, n, d = x.shape
    heads = cfg["transformer_heads"]
    hd = d // heads
    for blk in enc["blocks"]:
        y = reference._ln(blk["ln1"], x)
        q, k, v = ((y @ blk["attn"][name]["w"]).reshape(bsz, n, heads, hd)
                   for name in ("q", "k", "v"))
        y = _attention(q, k, v, mask).reshape(bsz, n, d)
        x = x + y @ blk["attn"]["o"]["w"]
        y = reference._gelu(reference._ln(blk["ln2"], x) @ blk["fc1"]["w"]
                            + blk["fc1"]["b"])
        x = x + y @ blk["fc2"]["w"] + blk["fc2"]["b"]
    x = reference._ln(enc["ln_f"], x)
    kappa = (x * mask[..., None]).sum(1)
    return (kappa @ params["head"]["w"])[:, 0]


def embed_program(params, cfg, g: dict, norm: dict, max_nodes: int,
                  dtype, gnn):
    """The program's node embeddings after the GNN, [n, D] in program
    order, each block computed on its own by `gnn` (the jitted `_gnn`)."""
    import jax.numpy as jnp
    whole = reference.featurize(g)
    out = None
    for lo, hi, halo in segments(g, max_nodes):
        f = dict(reference.featurize(block_graph(g, lo, hi, halo)),
                 kernel_feats=whole["kernel_feats"])
        b = reference.dense_batch([f], norm, max_nodes)
        b = reference.cast({k: jnp.asarray(v) for k, v in b.items()}, dtype)
        h = np.asarray(gnn(params, reference.frozen(cfg), b)
                       .astype(jnp.float32))[0]
        if out is None:
            out = np.zeros((len(g["nodes"]), h.shape[-1]), np.float32)
        out[lo:hi] = h[len(halo):len(halo) + hi - lo]
    return out


def score(params, cfg: dict, graphs: list[dict], norm: dict,
          max_nodes: int, dtype) -> np.ndarray:
    """Reference scores of whole programs in their wire form, computed on
    the default device in `dtype` (float32, or bfloat16 for the control).
    Each program's readout is padded to the next power of two of its
    nodes, as the program pads it, so a sample compiles few shapes."""
    import jax
    import jax.numpy as jnp
    gnn = jax.jit(_gnn, static_argnums=(1,))
    readout = jax.jit(_readout, static_argnums=(1,))
    p = reference.cast(params, dtype)
    static = reference.frozen(cfg)
    out = np.zeros((len(graphs),), np.float64)
    with jax.default_matmul_precision("default"):
        for i, g in enumerate(graphs):
            h = embed_program(p, cfg, g, norm, max_nodes, dtype, gnn)
            n = h.shape[0]
            rows = reference.pad_rows(n)
            hp = np.zeros((1, rows, h.shape[1]), np.float32)
            hp[0, :n] = h
            mask = np.zeros((1, rows), np.float32)
            mask[0, :n] = 1.0
            args = reference.cast([jnp.asarray(hp), jnp.asarray(mask)],
                                  dtype)
            out[i] = float(np.asarray(readout(p, static, *args)
                                      .astype(jnp.float32))[0])
    return out
