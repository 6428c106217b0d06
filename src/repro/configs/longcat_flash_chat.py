"""LongCat-Flash-Chat 560B [hf:meituan-longcat/LongCat-Flash-Chat].

28 double layers, d_model=6144: each holds two MLA sublayers (64 heads,
q_lora 1536, kv_lora 512, nope 128, rope 64, v 128, LoRA scales), two
gated-SiLU MLPs of 12288, and a shortcut MoE fed from the first sublayer:
512 routed experts of 2048 and 256 identity experts, top-12 of a softmax
router with a score-correction bias, weights times 6.  rope_theta 1e7,
RMSNorm eps 1e-5, vocab 131072, untied.  The full config holds every
routed expert (``held_experts`` 0); a one-chip cut sets ``held_experts``.
"""
from repro.models.config import MLACfg, ModelCfg, MoECfg
from .base import ArchSpec

CFG = ModelCfg(
    name="longcat-flash-chat", family="moe",
    n_layers=28, d_model=6144, n_heads=64, n_kv_heads=64,
    d_ff=12288, vocab=131072, head_dim=192,   # qk head dim (nope+rope)
    pattern=("longcat",), rope_theta=1e7,
    norm="rmsnorm", norm_eps=1e-5, mlp="gated_silu", tie_embeddings=False,
    mla=MLACfg(q_lora=1536, kv_lora=512, rope_dim=64, nope_dim=128,
               v_dim=128, lora_scale=True),
    moe=MoECfg(n_experts=512, top_k=12, d_expert=2048, n_zero=256,
               routed_scale=6.0),
)

SPEC = ArchSpec(
    cfg=CFG,
    published_params=560e9,
)
