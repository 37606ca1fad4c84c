"""deepseek-v3-671b — MLA + 1 shared / 256 routed top-8 MoE + MTP.
[arXiv:2412.19437; huggingface.co/deepseek-ai/DeepSeek-V3 config.json]

The first ``first_k_dense`` = 3 layers have a dense SiLU MLP of width
18432; the other 58 an expert FFN.  Routing is ``noaux_tc``: sigmoid
scores, a selection-only bias, the 4 best of 8 expert groups (a group
scores the sum of its two best), the 8 best experts inside them, their
unbiased scores normalized and scaled by 2.5.  Rotary positions use
YaRN (factor 40 over 4096 original positions, β 32/1, mscale 1/1), so
the softmax scale is 192^-½ · (0.1 ln 40 + 1)².

The MLA decode path uses the absorbed (latent-space) form — the paper's
compile-time weight-layout trick (Eq. 3) in attention-algebra form.
"""

from .base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v3-671b", family="moe",
    num_layers=61, d_model=7168, n_heads=128, n_kv_heads=128,
    d_ff=18432,                      # the leading dense layers' width
    vocab=129280, head_dim=128,
    mla=True, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    n_experts=256, top_k=8, n_shared=1, moe_d_ff=2048,
    router_fn="sigmoid", moe_cf=1.25,
    n_group=8, topk_group=4, routed_scale=2.5,
    first_k_dense=3,
    yarn_factor=40.0, yarn_original_len=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=1.0, yarn_mscale_all_dim=1.0,
    mtp=True, rope_theta=1e4, mlp_act="silu", norm_eps=1e-6,
)

#: Two expert groups of four, one kept: routing by group is exercised on
#: the CPU.  One dense layer, then two expert layers.
SMOKE = ArchConfig(
    name="deepseek-v3-671b-smoke", family="moe",
    num_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=128, vocab=256, head_dim=16,
    mla=True, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    n_experts=8, top_k=2, n_shared=1, moe_d_ff=32,
    router_fn="sigmoid", moe_cf=2.0,
    n_group=2, topk_group=1, routed_scale=2.5,
    first_k_dense=1,
    yarn_factor=40.0, yarn_original_len=64, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=1.0, yarn_mscale_all_dim=1.0,
    mtp=True, rope_theta=1e4, mlp_act="silu",
    q_chunk=16, kv_chunk=32,
)
