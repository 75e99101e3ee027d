"""deepseek-v3-671b [moe]: 61L d=7168 128H d_ff(expert)=2048 vocab=129280.
MLA (q_lora 1536, kv_lora 512, nope 128 + rope 64, v 128), 1 shared + 256
routed experts top-8 with sigmoid+bias aux-free routing, group-limited to
the best 4 of 8 groups (noaux_tc), gates normalized and scaled by 2.5,
first 3 layers dense (d_ff 18432) [arXiv:2412.19437]. Every width is the
published config's (huggingface.co/deepseek-ai/DeepSeek-V3, config.json:
hidden_size, intermediate_size, moe_intermediate_size, n_routed_experts,
n_shared_experts, num_experts_per_tok, n_group, topk_group,
routed_scaling_factor, num_hidden_layers, first_k_dense_replace,
num_attention_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, vocab_size, rope_theta, rms_norm_eps);
tests/test_moe_routing.py holds them equal. Its norm_topk_prob true is
what every route does: the chosen gates are always normalized.

Assumed, left out of the model:
  * MTP (num_nextn_predict_layers 1): a training-objective add-on, one
    extra block beside the trunk, orthogonal to this paper's
    runtime-modeling study (noted in DESIGN.md).
  * YaRN rope_scaling (factor 40, original_max_position_embeddings 4096):
    it changes the rotary frequencies and the softmax scale by constants,
    so at the 4 096 positions the published import traces it leaves the
    program's structure unchanged.
The smoke config keeps plain top-2 of 8 experts, so its imported program
stays the graph earlier corpora hold.
Optimizer: Adafactor (factored 2nd moment) — Adam m+v at 671B does not fit
the 256-chip HBM budget; see EXPERIMENTS.md §Dry-run.
Full (latent) attention => long_500k skipped."""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, Stack


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-671b", family="moe",
        d_model=7168, vocab_size=129280,
        num_heads=128, d_ff=18432,
        stacks=(
            Stack(("mla+mlp",), 3),
            Stack(("mla+moe",), 58),
        ),
        mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        moe=MoEConfig(num_experts=256, top_k=8, d_ff_expert=2048,
                      num_shared_experts=1, d_ff_shared=2048,
                      router_scale=True, n_group=8, topk_group=4,
                      routed_scaling_factor=2.5),
        optimizer="adafactor",
        # microbatch must be a multiple of the dp axis (16) or the batch
        # replicates per microbatch — found by the §Perf roofline loop
        microbatch=16,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-671b-smoke", family="moe",
        d_model=64, vocab_size=256,
        num_heads=4, d_ff=128,
        stacks=(
            Stack(("mla+mlp",), 1),
            Stack(("mla+moe",), 1),
        ),
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                      num_shared_experts=1, d_ff_shared=32,
                      router_scale=True),
        optimizer="adafactor",
        microbatch=2, block_kv=16, dtype="float32",
    )
