"""deepseek-moe-16b [moe] — DeepSeekMoE 16B, arXiv:2401.06066;
hf:deepseek-ai/deepseek-moe-16b-base config.json.

28 layers, d 2048, MHA 16 heads x 128 (no attention bias), RoPE theta 10000
over the whole head, RMSNorm eps 1e-6, SiLU-gated MLPs, vocab 102400, untied
LM head, bf16. Layer 0 is dense (FFN 10944); layers 1-27 are expert layers:
a softmax router over 64 routed experts of width 1408 keeps the top 6 per
token, their weights not renormalised, plus 2 shared experts run as one MLP
of width 2816. The registered config is the whole model: it holds all 64
routed experts; a chip's share under expert parallelism sets
``experts_held`` and ``first_expert``. The pre-training balance loss
(``aux_loss_alpha`` 0.001) is not part of the fine-tune loss."""
from repro.configs.base import ModelConfig
from repro.configs.registry import register, register_policy
from repro.core.policy import ParamGroup, PrivacyPolicy


@register
def deepseek_moe_16b() -> ModelConfig:
    return ModelConfig(
        name="deepseek-moe-16b", family="moe", n_layers=28, d_model=2048,
        n_heads=16, n_kv_heads=16, head_dim=128, d_ff=10944, vocab=102400,
        n_experts=64, top_k=6, experts_held=64, n_shared=2, moe_d_ff=1408,
        first_k_dense=1, renorm_topk=False, rope_theta=10000.0,
        norm="rmsnorm", act="swiglu", dtype="bfloat16",
        param_dtype="bfloat16", remat=True, attn_chunk=512)


@register_policy("deepseek-moe-16b")
def deepseek_moe_16b_policy() -> PrivacyPolicy:
    """Group-wise clipping split along the model's natural axes: routed
    expert weights (each sample touches top-k of 64, so per-sample expert
    gradients are sparse and small-normed) get their own clipping unit and
    threshold, the router its own (tiny but gradient-sensitive), everything
    else (attention / shared-FFN / embeddings) forms the dense trunk unit.
    Sensitivity composes as sqrt(R_experts^2 + R_router^2 + R_dense^2)."""
    return PrivacyPolicy(groups=(
        ParamGroup("experts", r".*/experts/.*", R=0.5, scope="group"),
        ParamGroup("router", r".*/router/.*", R=0.25, scope="group"),
        ParamGroup("dense", ".*", R=1.0, scope="group"),
    ), mode="bk-mixopt")
