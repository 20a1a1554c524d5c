"""Moonlight-16B-A3B (moonshotai; the DeepSeek-V3 architecture): latent
attention (MLA) in every layer, a leading dense SwiGLU layer, then expert
layers of 64 routed experts (6 per token, sigmoid router with a selection
bias, gates renormalised and scaled by 2.446) and 2 shared experts
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2405.04434 §2.1,
arXiv:2412.19437 §2.1.2].

The sequence-wise balance loss weight (alpha 0.0001) and the router's
bias step (gamma 0.001, `moe.BIAS_STEP`) are not in the published config:
DeepSeek-V3 §4.2's.
RoPE uses the program's rotate-half layout; the checkpoint interleaves its
rope dims, a permutation of the weights."""

from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=11264,
        vocab_size=163840,
        attention="mla",
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=50000.0,
        norm="rms",
        rms_norm_eps=1e-5,
        act="swiglu",
        num_experts=64,
        top_k_experts=6,
        moe_intermediate_size=1408,
        n_shared_experts=2,
        first_k_dense_replace=1,
        scoring_func="sigmoid",
        routed_scaling_factor=2.446,
        router_aux_weight=1e-4,
        max_seq=8192,
    )


def smoke_config() -> ModelConfig:
    """The same structure at CPU size: a dense layer, then expert layers
    with MLA, shared experts and 8 routed experts, half of them held."""
    return ModelConfig(
        name="moonlight-16b-a3b-smoke",
        family="moe",
        num_layers=4,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        d_ff=128,
        vocab_size=256,
        attention="mla",
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        rope_theta=50000.0,
        norm="rms",
        rms_norm_eps=1e-5,
        act="swiglu",
        num_experts=8,
        top_k_experts=3,
        experts_held=4,
        moe_intermediate_size=32,
        n_shared_experts=2,
        first_k_dense_replace=1,
        scoring_func="sigmoid",
        routed_scaling_factor=2.446,
        router_aux_weight=1e-4,
        remat=False,
    )
