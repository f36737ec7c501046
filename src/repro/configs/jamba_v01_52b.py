"""jamba-v0.1-52b [hybrid] — 32L d_model=4096 32H (GQA kv=8) d_ff=14336
vocab=65536, MoE 16e top-2.  Mamba+attn 1:7 interleave, MoE every other
layer [arXiv:2403.19887; hf].

Layer period = 8: position 4 is attention, the other 7 are Mamba; odd
positions carry the MoE FFN (16 experts, top-2), even carry dense FFN.
SSM-dominant => runs the ``long_500k`` cell.

Jamba's equations (``transformers``' ``modeling_jamba``): no positional
encoding in attention, RMSNorms over the Mamba mixer's dt, B and C, and
top-2 gates not renormalised.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="jamba-v0.1-52b",
    family="hybrid",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    vocab=65536,
    n_experts=16,
    top_k=2,
    moe_every=2,
    moe_offset=1,
    attn_every=8,
    attn_offset=4,
    mamba_d_state=16,
    mamba_expand=2,
    mamba_d_conv=4,
    mamba_inner_norms=True,
    pos_encoding="none",
    moe_renormalize=False,
)


def reduced() -> ModelConfig:
    return CONFIG.replace(n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
                          head_dim=16, d_ff=96, vocab=256, n_experts=4,
                          top_k=2, mamba_d_state=4, mamba_chunk=16,
                          attn_chunk=32)
