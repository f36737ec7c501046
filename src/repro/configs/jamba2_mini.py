"""AI21-Jamba2-Mini [hybrid] — 32L d_model=4096 32H (GQA kv=8, head_dim
128) d_ff=14336 vocab=65536, 16 experts top-2 on odd layers, Mamba-1
(d_state 16, d_conv 4, expand 2, dt_rank 256) with attention at layer 4
of every 8 [hf: ai21labs/AI21-Jamba2-Mini config.json].

The layer equations are Jamba's (``transformers``' ``modeling_jamba``):
attention without positional encoding, RMSNorms over the Mamba mixer's
dt, B and C after ``x_proj``, and top-2 gates taken straight from the
softmax over all 16 experts, not renormalised and never dropped.  The
expert layers run the dropless dispatch; ``experts_held`` /
``experts_offset`` give a chip's share of them (all 16 here).
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="AI21-Jamba2-Mini",
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
    mamba_dt_rank=256,
    mamba_inner_norms=True,
    pos_encoding="none",
    moe_renormalize=False,
    moe_dispatch="dropless",
    norm_eps=1e-6,
)


def reduced() -> ModelConfig:
    """One period of 8 layers at toy widths, 4 of 8 experts held."""
    return CONFIG.replace(n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
                          head_dim=16, d_ff=96, vocab=256, n_experts=8,
                          experts_held=4, top_k=2, mamba_d_state=4,
                          mamba_dt_rank=0, mamba_chunk=16, attn_chunk=32)
