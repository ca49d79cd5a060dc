"""DeepSeek-V2-Lite [arXiv:2405.04434, appendix B; the published
config.json of deepseek-ai/DeepSeek-V2-Lite] — 15.7B total / 2.4B active.

27 layers, h=2048, MLA with 16 heads and no query compression (W^Q maps
h to 16 x (128 + 64)), d_c=512, YaRN rope (factor 40 over 4096
positions, mscale = mscale_all_dim = 0.707); the first layer a dense
SwiGLU of 10944, then 26 MoE layers of 64 routed experts (softmax top-6,
gates not renormalised) + 2 shared, each 1408 wide; aux_loss_alpha
0.001; vocab 102400, untied head.
"""

from repro.core.notation import (AttentionKind, FamilyKind, MLASpec, MlpKind,
                                 MoESpec, ModelSpec, YaRNSpec)

YARN = YaRNSpec(factor=40.0, original_max_position=4096, beta_fast=32.0,
                beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)

SPEC = ModelSpec(
    name="deepseek-v2-lite",
    family=FamilyKind.MOE,
    n_layers=27,
    h=2048,
    n_h=16,
    n_kv=16,
    d_head=128,
    h_ff=10944,
    vocab=102400,
    attention=AttentionKind.MLA,
    mlp=MlpKind.SWIGLU,
    mla=MLASpec(d_cq=None, d_c=512, d_h=128, d_hr=64, d_v=128),
    moe=MoESpec(n_routed=64, n_active=6, n_shared=2, d_ff_expert=1408,
                first_k_dense=1, norm_topk=False, aux_coef=0.001),
    norm_eps=1e-6,
    rope_theta=10000.0,
    yarn=YARN,
    max_seq_len=163840,
)

# the same kinds of layer at smoke widths: no query LoRA, YaRN, one dense
# layer then three MoE layers, 2 shared experts, 4 of 8 routed experts held
SMOKE = ModelSpec(
    name="deepseek-v2-lite-smoke",
    family=FamilyKind.MOE,
    n_layers=4,
    h=128,
    n_h=4,
    n_kv=4,
    d_head=32,
    h_ff=256,
    vocab=512,
    attention=AttentionKind.MLA,
    mlp=MlpKind.SWIGLU,
    mla=MLASpec(d_cq=None, d_c=64, d_h=32, d_hr=16, d_v=32),
    moe=MoESpec(n_routed=8, n_active=2, n_shared=2, d_ff_expert=64,
                first_k_dense=1, n_held=4, norm_topk=False, aux_coef=0.001),
    norm_eps=1e-6,
    rope_theta=10000.0,
    yarn=YARN,
    max_seq_len=512,
)
