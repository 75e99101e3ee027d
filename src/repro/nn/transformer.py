"""Transformer encoder used as a kernel-embedding reduction (paper §3.2).

Pre-norm encoder blocks with masked multi-head self-attention over node
embeddings. This is the *cost-model* transformer; the LM zoo has its own
decoder implementation under repro.models (different enough — rotary, GQA,
KV caches — that sharing would hurt clarity).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.core import (
    dense_apply,
    dense_init,
    dropout,
    layernorm_apply,
    layernorm_init,
)


def mha_init(rng, dim: int, num_heads: int, dtype=jnp.float32) -> dict:
    assert dim % num_heads == 0, (dim, num_heads)
    kq, kk, kv, ko = jax.random.split(rng, 4)
    return {
        "q": dense_init(kq, dim, dim, bias=False, dtype=dtype),
        "k": dense_init(kk, dim, dim, bias=False, dtype=dtype),
        "v": dense_init(kv, dim, dim, bias=False, dtype=dtype),
        "o": dense_init(ko, dim, dim, bias=False, dtype=dtype),
    }


# Sequences up to this many nodes attend in one [B, H, N, N] block, as a
# whole-program pack of the sparse path does (its node budget is 8 192).
# Longer ones, the reassembled graphs of the segmented path, attend in key
# blocks with an online softmax: the dense logits of a 32 768-node graph
# (4 heads, f32) alone would take 17 GB.
DENSE_MAX_NODES = 8192
KEY_BLOCK = 1024


def mha_apply(params: dict, x: jnp.ndarray, mask: jnp.ndarray | None,
              num_heads: int) -> jnp.ndarray:
    """x: [B, N, D]; mask: [B, N] validity (1=real node)."""
    B, N, D = x.shape
    H = num_heads
    hd = D // H
    q = dense_apply(params["q"], x).reshape(B, N, H, hd)
    k = dense_apply(params["k"], x).reshape(B, N, H, hd)
    v = dense_apply(params["v"], x).reshape(B, N, H, hd)
    if N <= DENSE_MAX_NODES:
        out = dense_attention(q, k, v, mask)
    else:
        out = blocked_attention(q, k, v, mask, block=KEY_BLOCK)
    return dense_apply(params["o"], out.reshape(B, N, D))


def dense_attention(q, k, v, mask) -> jnp.ndarray:
    """q, k, v: [B, N, H, hd]; mask: [B, N] or None -> [B, N, H, hd]."""
    hd = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    if mask is not None:
        neg = jnp.finfo(logits.dtype).min
        logits = jnp.where(mask[:, None, None, :] > 0, logits, neg)
    attn = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", attn, v)


def blocked_attention(q, k, v, mask, *, block: int) -> jnp.ndarray:
    """`dense_attention` over key blocks of `block` nodes, keeping each
    query's running maximum, normalizer and weighted sum (the online
    softmax), so no [N, N] array is formed. Masked keys take the dense
    form's most negative logit, so once a query has met a real key they
    weigh exp(min - max) = 0, as in the dense softmax."""
    B, N, H, hd = q.shape
    nb = -(-N // block)
    pad = nb * block - N
    if mask is None:
        mask = jnp.ones((B, N), q.dtype)
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    kb = jnp.moveaxis(k.reshape(B, nb, block, H, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block, H, hd), 1, 0)
    mb = jnp.moveaxis(mask.reshape(B, nb, block), 1, 0)
    scale = 1.0 / jnp.sqrt(float(hd))
    neg = jnp.finfo(q.dtype).min

    def body(carry, blk):
        m, l, acc = carry
        kq, vq, mq = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kq) * scale
        s = jnp.where(mq[:, None, None, :] > 0, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd",
                                                      p, vq)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, N), -jnp.inf, q.dtype)
    l0 = jnp.zeros((B, H, N), q.dtype)
    a0 = jnp.zeros((B, H, N, hd), q.dtype)
    (_, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, mb))
    return jnp.moveaxis(acc / l[..., None], 1, 2)


def encoder_init(rng, dim: int, num_heads: int, num_layers: int,
                 mlp_factor: int = 4, dtype=jnp.float32) -> dict:
    blocks = []
    keys = jax.random.split(rng, max(num_layers, 1))
    for i in range(num_layers):
        ka, k1, k2 = jax.random.split(keys[i], 3)
        blocks.append({
            "ln1": layernorm_init(dim, dtype),
            "attn": mha_init(ka, dim, num_heads, dtype),
            "ln2": layernorm_init(dim, dtype),
            "fc1": dense_init(k1, dim, mlp_factor * dim, bias=True, dtype=dtype),
            "fc2": dense_init(k2, mlp_factor * dim, dim, bias=True, dtype=dtype),
        })
    return {"blocks": blocks, "ln_f": layernorm_init(dim, dtype)}


def encoder_apply(params: dict, x: jnp.ndarray, mask: jnp.ndarray | None,
                  num_heads: int, *, rng=None, dropout_rate: float = 0.0,
                  deterministic: bool = True) -> jnp.ndarray:
    """Returns per-node encodings [B, N, D] (reduction handled by caller)."""
    for i, blk in enumerate(params["blocks"]):
        sub = None if rng is None else jax.random.fold_in(rng, i)
        h = mha_apply(blk["attn"], layernorm_apply(blk["ln1"], x), mask,
                      num_heads)
        h = dropout(sub, h, dropout_rate, deterministic)
        x = x + h
        h = dense_apply(blk["fc1"], layernorm_apply(blk["ln2"], x))
        h = jax.nn.gelu(h)
        h = dense_apply(blk["fc2"], h)
        x = x + h
    return layernorm_apply(params["ln_f"], x)
