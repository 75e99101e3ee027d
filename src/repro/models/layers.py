"""Layer zoo shared by all 10 assigned architectures.

Mixers:
  * gqa_attention — rotary + GQA, full-causal or sliding-window, optional
    qk-norm (qwen3). Train/prefill use a chunked online-softmax scan over KV
    blocks (flash-attention structure; the Pallas kernel in
    repro.kernels.flash_attention mirrors it). Decode attends over a cache
    (ring buffer for SWA).
  * mla — DeepSeek-V3 multi-head latent attention. Decode uses the absorbed
    form over the compressed KV cache.
  * ssd — Mamba2 state-space duality mixer (chunked intra/inter algorithm;
    the Pallas ssd_scan kernel mirrors the inter-chunk recurrence).
  * rglru — RecurrentGemma's gated linear recurrence, trained with an
    associative scan (log-depth on TPU).

FFNs: SwiGLU MLP and token-choice MoE with sort-based expert-parallel
dispatch (capacity + drop, MaxText-style).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import MLAConfig, ModelConfig, MoEConfig, RGLRUConfig, SSMConfig
from repro.sharding.context import constrain

NEG_INF = -1e30


def _dt(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _norm_init(dim: int) -> dict:
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    return (y * params["scale"]).astype(x.dtype)


def _winit(rng, shape, dtype, scale: float = 0.02):
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# ----------------------------------------------------------------------------
# Rotary position embedding
# ----------------------------------------------------------------------------
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, hd] (hd even); positions: [S] absolute int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]   # [S, half]
    sin = jnp.sin(ang)[None, :, None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# Chunked online-softmax attention (train / prefill path)
# ----------------------------------------------------------------------------
def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, window: int | None = None,
                      q_offset: int | jnp.ndarray = 0,
                      block_kv: int = 512) -> jnp.ndarray:
    """q: [B,S,H,hd]; k,v: [B,T,KH,hd] with H % KH == 0. Returns [B,S,H,hd].

    Scans KV blocks with running (max, normalizer, accumulator) — bounded
    memory for 32k prefill; the jnp oracle for the Pallas flash kernel.
    """
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    rep = H // KH
    scale = 1.0 / math.sqrt(hd)
    qh = (q * scale).reshape(B, S, KH, rep, hd)

    blk = min(block_kv, T)
    nb = -(-T // blk)
    pad = nb * blk - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nb, blk, KH, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, blk, KH, hd).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(S)

    def body(carry, inp):
        m, l, acc = carry
        kq, vq, bi = inp
        s = jnp.einsum("bsgrd,btgd->bgrst", qh.astype(jnp.float32),
                       kq.astype(jnp.float32))
        k_pos = bi * blk + jnp.arange(blk)
        valid = (k_pos[None, :] < T)
        if causal:
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            valid = valid & (q_pos[:, None] - k_pos[None, :] < window)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bgrst,btgd->bgrsd", p, vq.astype(jnp.float32))
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KH, rep, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, rep, S), jnp.float32)
    a0 = jnp.zeros((B, KH, rep, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
    return out.astype(q.dtype)


def cache_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, k_pos: jnp.ndarray,
                    pos: jnp.ndarray, *,
                    window: int | None = None) -> jnp.ndarray:
    """Decode: q [B,1,H,hd] over cache [B,C,KH,hd]; k_pos [B,C] absolute
    positions of cached keys (-1 = empty slot)."""
    B, _, H, hd = q.shape
    C, KH = k_cache.shape[1], k_cache.shape[2]
    rep = H // KH
    scale = 1.0 / math.sqrt(hd)
    qh = (q * scale).reshape(B, KH, rep, hd)
    s = jnp.einsum("bgrd,btgd->bgrt", qh.astype(jnp.float32),
                   k_cache.astype(jnp.float32))
    valid = (k_pos >= 0) & (k_pos <= pos)
    if window is not None:
        valid = valid & (pos - k_pos < window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrt,btgd->bgrd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ----------------------------------------------------------------------------
# GQA attention block (mixers 'attn' and 'swa')
# ----------------------------------------------------------------------------
def attn_init(rng, cfg: ModelConfig) -> dict:
    D, H, KH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, \
        cfg.resolved_head_dim
    H_pad = max(cfg.attn_pad_heads, H) if cfg.attn_pad_heads else H
    assert H_pad % KH == 0, (H_pad, KH)
    dt = _dt(cfg)
    ks = jax.random.split(rng, 4)
    wq = _winit(ks[0], (D, H_pad, hd), dt)
    wo = _winit(ks[3], (H_pad, hd, D), dt,
                scale=0.02 / math.sqrt(2 * max(cfg.num_layers, 1)))
    if H_pad > H:
        # GQA maps head h -> kv group h // rep, so padding must be PER
        # GROUP (last rep_pad - rep slots of each group), and the padded
        # heads' wo rows are zero-init: the function is exactly the
        # unpadded model's at init.
        rep, rep_pad = H // KH, H_pad // KH
        mask = jnp.arange(H_pad) % rep_pad < rep     # real-head positions
        wo = wo * mask[:, None, None].astype(wo.dtype)
    p = {
        "wq": wq,
        "wk": _winit(ks[1], (D, KH, hd), dt),
        "wv": _winit(ks[2], (D, KH, hd), dt),
        "wo": wo,
    }
    if cfg.qk_norm:
        p["q_norm"] = _norm_init(hd)
        p["k_norm"] = _norm_init(hd)
    return p


def attn_qkv(params: dict, cfg: ModelConfig, x: jnp.ndarray,
             positions: jnp.ndarray):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply_train(params: dict, cfg: ModelConfig, x: jnp.ndarray, *,
                     window: int | None, q_offset=0) -> jnp.ndarray:
    B, S, D = x.shape
    positions = q_offset + jnp.arange(S)
    q, k, v = attn_qkv(params, cfg, x, positions)
    if cfg.use_pallas_attn:
        from repro.kernels import interpret_mode
        from repro.kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=True, window=window,
                              q_offset=int(q_offset) if not hasattr(
                                  q_offset, "dtype") else 0,
                              block_q=min(128, S), block_k=min(cfg.block_kv,
                                                               S),
                              interpret=interpret_mode())
    else:
        out = chunked_attention(q, k, v, causal=True, window=window,
                                q_offset=q_offset, block_kv=cfg.block_kv)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def attn_cache_init(cfg: ModelConfig, batch: int, capacity: int, *,
                    window: int | None) -> dict:
    KH, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    C = min(capacity, window) if window is not None else capacity
    dt = _dt(cfg)
    return {
        "k": jnp.zeros((batch, C, KH, hd), dt),
        "v": jnp.zeros((batch, C, KH, hd), dt),
        "k_pos": jnp.full((batch, C), -1, jnp.int32),
    }


def attn_apply_decode(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                      cache: dict, pos: jnp.ndarray, *,
                      window: int | None) -> tuple[jnp.ndarray, dict]:
    """x: [B,1,D]; pos: scalar int32 absolute position of this token."""
    positions = pos[None] if pos.ndim == 0 else pos
    q, k, v = attn_qkv(params, cfg, x, positions)
    C = cache["k"].shape[1]
    slot = (pos % C) if window is not None else pos
    k_c = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    v_c = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    kp = jax.lax.dynamic_update_slice(
        cache["k_pos"], jnp.broadcast_to(pos, (k.shape[0], 1)).astype(jnp.int32),
        (0, slot))
    out = cache_attention(q, k_c, v_c, kp, pos, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, {"k": k_c, "v": v_c, "k_pos": kp}


def attn_make_cache_from_prefill(cfg: ModelConfig, k, v, *, window,
                                 capacity: int) -> dict:
    """Build a decode cache from prefill-computed k/v [B,S,KH,hd]."""
    B, S = k.shape[0], k.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    if window is not None:
        C = min(capacity, window)
        # keep the last C positions, placed at slot pos % C (ring layout)
        keep_k, keep_v, keep_p = k[:, -C:], v[:, -C:], pos[-C:]
        slots = keep_p % C
        kc = jnp.zeros((B, C) + k.shape[2:], k.dtype).at[:, slots].set(keep_k)
        vc = jnp.zeros((B, C) + v.shape[2:], v.dtype).at[:, slots].set(keep_v)
        kp = jnp.full((B, C), -1, jnp.int32).at[:, slots].set(
            jnp.broadcast_to(keep_p, (B, C)))
        return {"k": kc, "v": vc, "k_pos": kp}
    C = capacity
    kc = jnp.zeros((B, C) + k.shape[2:], k.dtype).at[:, :S].set(k)
    vc = jnp.zeros((B, C) + v.shape[2:], v.dtype).at[:, :S].set(v)
    kp = jnp.full((B, C), -1, jnp.int32).at[:, :S].set(
        jnp.broadcast_to(pos, (B, S)))
    return {"k": kc, "v": vc, "k_pos": kp}


# ----------------------------------------------------------------------------
# SwiGLU MLP
# ----------------------------------------------------------------------------
def mlp_init(rng, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    dt = _dt(cfg)
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": _winit(ks[0], (D, F), dt),
        "w_up": _winit(ks[1], (D, F), dt),
        "w_down": _winit(ks[2], (F, D), dt,
                         scale=0.02 / math.sqrt(2 * max(cfg.num_layers, 1))),
    }


def mlp_apply(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    return h @ params["w_down"]


# ----------------------------------------------------------------------------
# Token-choice MoE with sort-based expert-parallel dispatch
# ----------------------------------------------------------------------------
def moe_init(rng, cfg: ModelConfig) -> dict:
    mc = cfg.moe
    D, E, F = cfg.d_model, mc.num_experts, mc.d_ff_expert
    dt = _dt(cfg)
    ks = jax.random.split(rng, 6)
    p = {
        "router": _winit(ks[0], (D, E), jnp.float32, scale=0.006),
        "w_gate": _winit(ks[1], (E, D, F), dt),
        "w_up": _winit(ks[2], (E, D, F), dt),
        "w_down": _winit(ks[3], (E, F, D), dt,
                         scale=0.02 / math.sqrt(2 * max(cfg.num_layers, 1))),
    }
    if mc.router_scale:                      # deepseek aux-free bias routing
        p["e_bias"] = jnp.zeros((E,), jnp.float32)
    if mc.num_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg,
                               mc.d_ff_shared * mc.num_shared_experts)
    return p


def _route(params: dict, mc: MoEConfig, xf: jnp.ndarray):
    """xf: [T, D] -> (gates [T,K], ids [T,K]).

    Softmax top-k, or with `router_scale` DeepSeek's sigmoid scores,
    selected with the load-balancing bias added and weighted without it;
    with `n_group` > 1 the selection is group-limited (noaux_tc)."""
    logits = (xf.astype(jnp.float32) @ params["router"])
    if mc.router_scale:
        scores = jax.nn.sigmoid(logits)
        sel = scores + params["e_bias"][None, :]
        if mc.n_group > 1:
            sel = _group_limited(sel, mc.n_group, mc.topk_group)
        _, ids = jax.lax.top_k(sel, mc.top_k)
        gates = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, mc.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    if mc.routed_scaling_factor != 1.0:
        gates = gates * mc.routed_scaling_factor
    return gates, ids


def _group_limited(sel: jnp.ndarray, n_group: int,
                   topk_group: int) -> jnp.ndarray:
    """sel [T, E] with every expert outside a token's `topk_group` best
    groups set to -inf; a group scores the sum of its two best experts."""
    T, E = sel.shape
    grouped = sel.reshape(T, n_group, E // n_group)
    group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, keep = jax.lax.top_k(group_scores, topk_group)          # [T, topk_g]
    kept = jnp.zeros((T, n_group), jnp.bool_).at[
        jnp.arange(T)[:, None], keep].set(True)
    return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)


def moe_apply(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """x: [B,S,D]. Sort-based dispatch with per-expert capacity + drop."""
    mc = cfg.moe
    B, S, D = x.shape
    T = B * S
    K, E = mc.top_k, mc.num_experts
    xf = x.reshape(T, D)
    gates, ids = _route(params, mc, xf)

    cap = int(math.ceil(T * K / E * mc.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)                    # lane-align capacity

    flat_ids = ids.reshape(-1)                        # [T*K]
    sort_idx = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[sort_idx]
    counts = jnp.zeros((E,), jnp.int32).at[flat_ids].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_ids]
    keep = pos_sorted < cap
    slot_sorted = jnp.where(keep, sorted_ids * cap + pos_sorted, E * cap)

    tok_sorted = (sort_idx // K).astype(jnp.int32)
    dispatch_tok = jnp.zeros((E * cap + 1,), jnp.int32) \
        .at[slot_sorted].set(tok_sorted)
    slot_used = jnp.zeros((E * cap + 1,), jnp.bool_) \
        .at[slot_sorted].set(keep)
    xe = xf[dispatch_tok[:E * cap]] * slot_used[:E * cap, None]
    xe = constrain(xe.reshape(E, cap, D), "moe_ecd")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    ye_flat = jnp.concatenate(
        [ye.reshape(E * cap, D), jnp.zeros((1, D), ye.dtype)], axis=0)

    # route outputs back to (token, k) order
    slot_of_flat = jnp.zeros((T * K,), jnp.int32).at[sort_idx].set(
        slot_sorted.astype(jnp.int32))
    yk = ye_flat[slot_of_flat].reshape(T, K, D)
    out = jnp.sum(yk * gates[..., None].astype(yk.dtype), axis=1)

    if mc.num_shared_experts:
        out = out + mlp_apply(params["shared"], xf)
    return out.reshape(B, S, D).astype(x.dtype)


# ----------------------------------------------------------------------------
# MLA — DeepSeek-V3 multi-head latent attention
# ----------------------------------------------------------------------------
def mla_init(rng, cfg: ModelConfig) -> dict:
    m: MLAConfig = cfg.mla
    D, H = cfg.d_model, cfg.num_heads
    dt = _dt(cfg)
    ks = jax.random.split(rng, 7)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wdq": _winit(ks[0], (D, m.q_lora_rank), dt),
        "q_norm": _norm_init(m.q_lora_rank),
        "wuq": _winit(ks[1], (m.q_lora_rank, H, qk), dt),
        "wdkv": _winit(ks[2], (D, m.kv_lora_rank + m.qk_rope_head_dim), dt),
        "kv_norm": _norm_init(m.kv_lora_rank),
        "wuk": _winit(ks[3], (m.kv_lora_rank, H, m.qk_nope_head_dim), dt),
        "wuv": _winit(ks[4], (m.kv_lora_rank, H, m.v_head_dim), dt),
        "wo": _winit(ks[5], (H, m.v_head_dim, D), dt,
                     scale=0.02 / math.sqrt(2 * max(cfg.num_layers, 1))),
    }


def _mla_q(params, cfg: ModelConfig, x, positions):
    m = cfg.mla
    cq = rmsnorm(params["q_norm"], x @ params["wdq"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, params["wuq"])
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(params, cfg: ModelConfig, x, positions):
    m = cfg.mla
    dkv = x @ params["wdkv"]
    ckv = rmsnorm(params["kv_norm"], dkv[..., :m.kv_lora_rank], cfg.norm_eps)
    k_rope = rope(dkv[..., None, m.kv_lora_rank:], positions, cfg.rope_theta)
    return ckv, k_rope[:, :, 0, :]


def mla_apply_train(params: dict, cfg: ModelConfig, x: jnp.ndarray, *,
                    q_offset=0) -> jnp.ndarray:
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.num_heads
    positions = q_offset + jnp.arange(S)
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    ckv, k_rope = _mla_kv_latent(params, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, params["wuk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, params["wuv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    # pad v head dim up to qk dim so the shared chunked kernel applies,
    # then slice back (v_head 128 vs qk 192)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk - m.v_head_dim)))
    out = chunked_attention(q, k, v_p, causal=True, q_offset=q_offset,
                            block_kv=cfg.block_kv)[..., :m.v_head_dim]
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def mla_cache_init(cfg: ModelConfig, batch: int, capacity: int) -> dict:
    m = cfg.mla
    dt = _dt(cfg)
    return {
        "ckv": jnp.zeros((batch, capacity, m.kv_lora_rank), dt),
        "krope": jnp.zeros((batch, capacity, m.qk_rope_head_dim), dt),
        "k_pos": jnp.full((batch, capacity), -1, jnp.int32),
    }


def mla_apply_decode(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                     cache: dict, pos: jnp.ndarray) -> tuple[jnp.ndarray, dict]:
    """Absorbed-form decode: attend in the compressed latent space."""
    m = cfg.mla
    B = x.shape[0]
    positions = pos[None]
    q_nope, q_rope = _mla_q(params, cfg, x, positions)       # [B,1,H,*]
    ckv_new, krope_new = _mla_kv_latent(params, cfg, x, positions)
    ckv = jax.lax.dynamic_update_slice(cache["ckv"], ckv_new, (0, pos, 0))
    krope = jax.lax.dynamic_update_slice(cache["krope"], krope_new,
                                         (0, pos, 0))
    kp = jax.lax.dynamic_update_slice(
        cache["k_pos"], jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32),
        (0, pos))
    # absorb wuk into the query: q_lat [B,H,r]
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["wuk"])[:, 0]
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = (jnp.einsum("bhr,btr->bht", q_lat.astype(jnp.float32),
                    ckv.astype(jnp.float32)) +
         jnp.einsum("bhk,btk->bht", q_rope[:, 0].astype(jnp.float32),
                    krope.astype(jnp.float32))) * scale
    valid = (kp >= 0) & (kp <= pos)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bht,btr->bhr", p, ckv.astype(jnp.float32))
    v = jnp.einsum("bhr,rhk->bhk", ctx_lat.astype(_dt(cfg)), params["wuv"])
    y = jnp.einsum("bhk,hkd->bd", v, params["wo"])[:, None, :]
    return y, {"ckv": ckv, "krope": krope, "k_pos": kp}


# ----------------------------------------------------------------------------
# SSD — Mamba2 mixer
# ----------------------------------------------------------------------------
def ssd_dims(cfg: ModelConfig):
    sc: SSMConfig = cfg.ssm
    d_inner = sc.expand * cfg.d_model
    H = d_inner // sc.head_dim
    return d_inner, H, sc.head_dim, sc.d_state


def ssd_init(rng, cfg: ModelConfig) -> dict:
    sc = cfg.ssm
    D = cfg.d_model
    d_inner, H, P, N = ssd_dims(cfg)
    conv_dim = d_inner + 2 * sc.ngroups * N
    dt = _dt(cfg)
    ks = jax.random.split(rng, 5)
    in_dim = 2 * d_inner + 2 * sc.ngroups * N + H
    return {
        "w_in": _winit(ks[0], (D, in_dim), dt),
        "conv_w": _winit(ks[1], (sc.conv_width, conv_dim), jnp.float32, 0.2),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),          # a = -exp(A_log)
        "dt_bias": jnp.full((H,), math.log(math.e - 1), jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "y_norm": _norm_init(d_inner),
        "w_out": _winit(ks[2], (d_inner, D), dt,
                        scale=0.02 / math.sqrt(2 * max(cfg.num_layers, 1))),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: jnp.ndarray | None = None):
    """Depthwise causal conv. x: [B,S,C]; w: [W,C]. Returns (y, new_state)
    where state is the last W-1 inputs (for decode)."""
    W = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
            for i in range(W))
    y = y + b[None, None, :]
    new_state = xp[:, -(W - 1):, :] if W > 1 else None
    return y.astype(x.dtype), new_state


def _ssd_split(cfg: ModelConfig, proj: jnp.ndarray):
    sc = cfg.ssm
    d_inner, H, P, N = ssd_dims(cfg)
    g = sc.ngroups
    z, xs, Bm, Cm, dt_raw = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + g * N,
               2 * d_inner + 2 * g * N], axis=-1)
    return z, xs, Bm, Cm, dt_raw


def ssd_mix_chunked(cfg: ModelConfig, X, Bm, Cm, dlog, h0=None):
    """The SSD chunked algorithm (jnp oracle for the Pallas ssd_scan kernel).

    X: [B,S,H,P] inputs (already dt-scaled); Bm/Cm: [B,S,N] (ngroups=1);
    dlog: [B,S,H] per-step log-decay (<= 0). Returns (Y [B,S,H,P],
    final_state [B,H,N,P]).
    """
    sc = cfg.ssm
    B_, S, H, P = X.shape
    N = Bm.shape[-1]
    L = min(sc.chunk, S)
    nc = S // L
    assert nc * L == S, (S, L)
    Xc = X.reshape(B_, nc, L, H, P)
    Bc = Bm.reshape(B_, nc, L, N)
    Cc = Cm.reshape(B_, nc, L, N)
    dc = dlog.reshape(B_, nc, L, H)
    cum = jnp.cumsum(dc, axis=2)                       # [B,nc,L,H]

    # intra-chunk (masked decay attention)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # [B,nc,L,L,H]
    causal = jnp.tril(jnp.ones((L, L), bool))
    dec = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    scores = jnp.einsum("bcln,bcsn->bcls", Cc.astype(jnp.float32),
                        Bc.astype(jnp.float32))
    att = scores[..., None] * dec                          # [B,nc,L,L,H]
    Y_intra = jnp.einsum("bclsh,bcshp->bclhp", att, Xc.astype(jnp.float32))

    # per-chunk input state contribution
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)        # [B,nc,L,H]
    S_state = jnp.einsum("bcln,bclh,bclhp->bchnp",
                         Bc.astype(jnp.float32), decay_to_end,
                         Xc.astype(jnp.float32))           # [B,nc,H,N,P]

    # inter-chunk recurrence
    chunk_decay = jnp.exp(cum[:, :, -1, :])                # [B,nc,H]

    def step(h, inp):
        s_c, d_c = inp                                     # [B,H,N,P],[B,H]
        h_new = h * d_c[..., None, None] + s_c
        return h_new, h                                    # emit state BEFORE

    h_init = jnp.zeros((B_, H, N, P), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    hT, h_before = jax.lax.scan(
        step, h_init, (S_state.transpose(1, 0, 2, 3, 4),
                       chunk_decay.transpose(1, 0, 2)))
    h_before = h_before.transpose(1, 0, 2, 3, 4)           # [B,nc,H,N,P]

    Y_inter = jnp.einsum("bcln,bclh,bchnp->bclhp",
                         Cc.astype(jnp.float32), jnp.exp(cum), h_before)
    Y = (Y_intra + Y_inter).reshape(B_, S, H, P)
    return Y, hT


def ssd_apply_train(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                    conv_state=None, h0=None, return_state: bool = False):
    sc = cfg.ssm
    B, S, D = x.shape
    d_inner, H, P, N = ssd_dims(cfg)
    proj = x @ params["w_in"]
    z, xs, Bm, Cm, dt_raw = _ssd_split(cfg, proj)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out, new_conv = _causal_conv(conv_in, params["conv_w"],
                                      params["conv_b"], conv_state)
    conv_out = jax.nn.silu(conv_out)
    xs = conv_out[..., :d_inner]
    Bm = conv_out[..., d_inner:d_inner + sc.ngroups * N]
    Cm = conv_out[..., d_inner + sc.ngroups * N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["A_log"])                          # [H], negative
    dlog = dt * a[None, None, :]                           # [B,S,H]
    X = xs.reshape(B, S, H, P)
    U = X.astype(jnp.float32) * dt[..., None]
    # pad S to a chunk multiple with state-neutral steps (B=0 ⇒ no input
    # contribution; dlog=0 ⇒ decay 1 ⇒ state unchanged)
    L = min(sc.chunk, S)
    pad = (-S) % L
    if pad:
        U_p = jnp.pad(U, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Bm_p = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm_p = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        dlog_p = jnp.pad(dlog, ((0, 0), (0, pad), (0, 0)))
        Y, hT = ssd_mix_chunked(cfg, U_p, Bm_p, Cm_p, dlog_p, h0)
        Y = Y[:, :S]
    else:
        Y, hT = ssd_mix_chunked(cfg, U, Bm, Cm, dlog, h0)
    Y = Y + params["D_skip"][None, None, :, None] * X.astype(jnp.float32)
    y = Y.reshape(B, S, d_inner).astype(x.dtype)
    y = rmsnorm(params["y_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ params["w_out"]
    if return_state:
        return out, {"state": hT.astype(jnp.float32), "conv": new_conv}
    return out


def ssd_cache_init(cfg: ModelConfig, batch: int) -> dict:
    sc = cfg.ssm
    d_inner, H, P, N = ssd_dims(cfg)
    conv_dim = d_inner + 2 * sc.ngroups * N
    return {
        "state": jnp.zeros((batch, H, N, P), jnp.float32),
        "conv": jnp.zeros((batch, sc.conv_width - 1, conv_dim), jnp.float32),
    }


def ssd_apply_decode(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                     cache: dict, pos) -> tuple[jnp.ndarray, dict]:
    """Single-token state update. x: [B,1,D]."""
    del pos
    sc = cfg.ssm
    B = x.shape[0]
    d_inner, H, P, N = ssd_dims(cfg)
    proj = x @ params["w_in"]
    z, xs, Bm, Cm, dt_raw = _ssd_split(cfg, proj)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out, new_conv = _causal_conv(conv_in, params["conv_w"],
                                      params["conv_b"], cache["conv"])
    conv_out = jax.nn.silu(conv_out)
    xs = conv_out[..., :d_inner]
    Bm = conv_out[..., d_inner:d_inner + sc.ngroups * N][:, 0]
    Cm = conv_out[..., d_inner + sc.ngroups * N:][:, 0]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])[:, 0]
    a = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt * a[None, :])                       # [B,H]
    X = xs.reshape(B, H, P).astype(jnp.float32)
    U = X * dt[..., None]
    state = cache["state"] * decay[..., None, None] + \
        jnp.einsum("bn,bhp->bhnp", Bm.astype(jnp.float32), U)
    Y = jnp.einsum("bn,bhnp->bhp", Cm.astype(jnp.float32), state)
    Y = Y + params["D_skip"][None, :, None] * X
    y = Y.reshape(B, 1, d_inner).astype(x.dtype)
    y = rmsnorm(params["y_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return y @ params["w_out"], {"state": state, "conv": new_conv}


# ----------------------------------------------------------------------------
# RG-LRU — RecurrentGemma recurrent mixer
# ----------------------------------------------------------------------------
def rglru_init(rng, cfg: ModelConfig) -> dict:
    rc: RGLRUConfig = cfg.rglru
    D = cfg.d_model
    W = rc.lru_width or D
    dt = _dt(cfg)
    ks = jax.random.split(rng, 6)
    return {
        "w_x": _winit(ks[0], (D, W), dt),
        "w_gate": _winit(ks[1], (D, W), dt),
        "conv_w": _winit(ks[2], (rc.conv_width, W), jnp.float32, 0.2),
        "conv_b": jnp.zeros((W,), jnp.float32),
        "w_rg": _winit(ks[3], (W, W), dt),                 # recurrence gate
        "w_ig": _winit(ks[4], (W, W), dt),                 # input gate
        "lam": jnp.full((W,), 2.2, jnp.float32),           # a≈0.9 at init
        "w_out": _winit(ks[5], (W, D), dt,
                        scale=0.02 / math.sqrt(2 * max(cfg.num_layers, 1))),
    }


def _rglru_scan(log_a: jnp.ndarray, b: jnp.ndarray, h0=None):
    """h_t = exp(log_a_t) * h_{t-1} + b_t via associative scan over S.
    log_a, b: [B,S,W]."""
    a = jnp.exp(log_a)
    if h0 is not None:
        # fold the carried state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def rglru_core(params: dict, cfg: ModelConfig, x: jnp.ndarray,
               conv_state=None, h0=None):
    rc = cfg.rglru
    u = x @ params["w_x"]
    gate = x @ params["w_gate"]
    conv_out, new_conv = _causal_conv(u, params["conv_w"], params["conv_b"],
                                      conv_state)
    uc = conv_out.astype(jnp.float32)
    r = jax.nn.sigmoid(uc @ params["w_rg"].astype(jnp.float32))
    i = jax.nn.sigmoid(uc @ params["w_ig"].astype(jnp.float32))
    log_a = -rc.c_exponent * jax.nn.softplus(params["lam"]) * r    # [B,S,W]
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6))
    b = beta * (i * uc)
    h = _rglru_scan(log_a, b, h0)
    y = (h.astype(x.dtype) * jax.nn.silu(gate))
    return y @ params["w_out"], new_conv, h[:, -1]


def rglru_apply_train(params: dict, cfg: ModelConfig, x: jnp.ndarray):
    out, _, _ = rglru_core(params, cfg, x)
    return out


def rglru_cache_init(cfg: ModelConfig, batch: int) -> dict:
    rc = cfg.rglru
    W = rc.lru_width or cfg.d_model
    return {
        "state": jnp.zeros((batch, W), jnp.float32),
        "conv": jnp.zeros((batch, rc.conv_width - 1, W), jnp.float32),
    }


def rglru_apply_decode(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                       cache: dict, pos) -> tuple[jnp.ndarray, dict]:
    del pos
    out, new_conv, h_last = rglru_core(params, cfg, x,
                                       conv_state=cache["conv"],
                                       h0=cache["state"])
    return out, {"state": h_last.astype(jnp.float32), "conv": new_conv}
