"""MoE routing: DeepSeek-V3's group-limited sigmoid routing (noaux_tc)
against a plain float32 reference of the whole MoE layer, the published
routing settings, and the routes that were there before kept bit for bit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models.config import ModelConfig, MoEConfig, Stack
from repro.models.registry import get_config

# huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json
HF_DSV3 = {
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "num_experts_per_tok": 8, "n_group": 8,
    "topk_group": 4, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "num_hidden_layers": 61,
    "first_k_dense_replace": 3, "num_attention_heads": 128,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "vocab_size": 129280,
    "rope_theta": 10000, "rms_norm_eps": 1e-06,
}


def test_deepseek_config_is_the_published_one():
    cfg = get_config("deepseek-v3-671b")
    mc, m = cfg.moe, cfg.mla
    hf = HF_DSV3
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_heads) == (
        hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"],
        hf["num_attention_heads"])
    assert cfg.num_layers == hf["num_hidden_layers"]
    assert cfg.layer_types()[:hf["first_k_dense_replace"] + 1] == [
        "mla+mlp"] * hf["first_k_dense_replace"] + ["mla+moe"]
    assert (mc.num_experts, mc.top_k, mc.d_ff_expert) == (
        hf["n_routed_experts"], hf["num_experts_per_tok"],
        hf["moe_intermediate_size"])
    assert (mc.num_shared_experts, mc.d_ff_shared) == (
        hf["n_shared_experts"], hf["moe_intermediate_size"])
    assert mc.router_scale and hf["scoring_func"] == "sigmoid"
    assert (mc.n_group, mc.topk_group, mc.routed_scaling_factor) == (
        hf["n_group"], hf["topk_group"], hf["routed_scaling_factor"])
    # `_route` always normalizes the chosen gates, which is HF's
    # norm_topk_prob true
    assert hf["norm_topk_prob"]
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (
        hf["q_lora_rank"], hf["kv_lora_rank"], hf["qk_nope_head_dim"],
        hf["qk_rope_head_dim"], hf["v_head_dim"])
    assert (cfg.rope_theta, cfg.norm_eps) == (hf["rope_theta"],
                                              hf["rms_norm_eps"])


def _small_dsv3(**moe) -> ModelConfig:
    """DeepSeek-V3's MoE layer at a small size: 16 routed experts in 4
    groups, the best 2 groups kept, top-4, scaled by 2.5, one shared
    expert, capacity wide enough that no token is dropped."""
    mc = dict(num_experts=16, top_k=4, d_ff_expert=8, num_shared_experts=1,
              d_ff_shared=8, capacity_factor=16.0, router_scale=True,
              n_group=4, topk_group=2, routed_scaling_factor=2.5)
    mc.update(moe)
    return ModelConfig(name="dsv3-moe-small", family="moe", d_model=32,
                       vocab_size=64, stacks=(Stack(("mla+moe",), 1),),
                       num_heads=4, moe=MoEConfig(**mc), dtype="float32")


def _reference_moe(p, mc: MoEConfig, x: np.ndarray) -> np.ndarray:
    """DeepSeek-V3's MoE layer token by token in float32 numpy: sigmoid
    scores; a group scores the sum of its two best biased scores; experts
    outside the best `topk_group` groups are out; the top_k biased scores
    of the rest choose the experts, their unbiased scores, normalized and
    scaled, weigh them; the shared expert adds to every token."""
    def swiglu(w_gate, w_up, w_down, t):
        g = t @ w_gate
        return ((g / (1.0 + np.exp(-g))) * (t @ w_up)) @ w_down

    f = {k: np.asarray(v, np.float32) for k, v in p.items()
         if k != "shared"}
    shared = {k: np.asarray(v, np.float32) for k, v in p["shared"].items()}
    E, G = mc.num_experts, mc.n_group
    out = np.zeros_like(x)
    for t, xt in enumerate(x):
        scores = 1.0 / (1.0 + np.exp(-(xt @ f["router"])))
        biased = scores + f["e_bias"]
        groups = biased.reshape(G, E // G)
        group_score = np.sort(groups, axis=-1)[:, -2:].sum(-1)
        kept = np.argsort(-group_score, kind="stable")[:mc.topk_group]
        allowed = np.full(E, -np.inf, np.float32)
        for gi in kept:
            lo = gi * (E // G)
            allowed[lo:lo + E // G] = biased[lo:lo + E // G]
        ids = np.argsort(-allowed, kind="stable")[:mc.top_k]
        w = scores[ids] / scores[ids].sum() * mc.routed_scaling_factor
        for wi, e in zip(w, ids):
            out[t] += wi * swiglu(f["w_gate"][e], f["w_up"][e],
                                  f["w_down"][e], xt)
        out[t] += swiglu(shared["w_gate"], shared["w_up"],
                         shared["w_down"], xt)
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_moe_matches_the_plain_reference(seed):
    cfg = _small_dsv3()
    k_init, k_bias, k_x = jax.random.split(jax.random.key(seed), 3)
    p = L.moe_init(k_init, cfg)
    # a load-balancing bias that moves the choice, as a trained one does
    p["e_bias"] = jax.random.normal(k_bias, (16,), jnp.float32) * 0.05
    p["router"] = p["router"] * 50.0
    x = jax.random.normal(k_x, (2, 24, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L.moe_apply(p, cfg, x)).reshape(48, 32)
    want = _reference_moe(p, cfg.moe, np.asarray(x).reshape(48, 32))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_group_limit_changes_the_route():
    """Kept groups decide: with all 4 groups kept the route is plain
    top-k, and differs on some token."""
    cfg = _small_dsv3()
    p = L.moe_init(jax.random.key(3), cfg)
    p["router"] = p["router"] * 50.0
    xf = jax.random.normal(jax.random.key(4), (64, 32), jnp.float32)
    _, ids = L._route(p, cfg.moe, xf)
    _, all_ids = L._route(p, dataclasses.replace(cfg.moe, topk_group=4),
                          xf)
    group = np.asarray(ids) // 4
    assert all(len(set(row)) <= 2 for row in group)
    assert not np.array_equal(np.sort(ids, -1), np.sort(all_ids, -1))


def _route_before(params, mc, xf):
    """`_route` as it was before group-limited routing."""
    logits = (xf.astype(jnp.float32) @ params["router"])
    if mc.router_scale:
        scores = jax.nn.sigmoid(logits)
        sel = scores + params["e_bias"][None, :]
        _, ids = jax.lax.top_k(sel, mc.top_k)
        gates = jnp.take_along_axis(scores, ids, axis=-1)
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, mc.top_k)
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, ids


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m",
                                  "deepseek-v3-671b"])
def test_a_route_without_groups_is_kept_bit_for_bit(arch):
    """The smoke configs' routes (softmax top-k; sigmoid with bias, one
    group): the same program and the same numbers as before."""
    from repro.models.registry import get_smoke_config
    cfg = get_smoke_config(arch)
    p = L.moe_init(jax.random.key(5), cfg)
    if "e_bias" in p:
        p["e_bias"] = jax.random.normal(jax.random.key(6), p["e_bias"].shape)
    xf = jax.random.normal(jax.random.key(7), (40, cfg.d_model))
    now = jax.jit(lambda p, x: L._route(p, cfg.moe, x))
    before = jax.jit(lambda p, x: _route_before(p, cfg.moe, x))
    assert str(jax.make_jaxpr(now)(p, xf)) == \
        str(jax.make_jaxpr(before)(p, xf))
    for a, b in zip(now(p, xf), before(p, xf)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
