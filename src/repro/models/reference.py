"""Plain float32 forward pass of the Jamba hybrid, preserved as an oracle.

The whole network over a token block in straightforward ``jax.numpy``:
no cache, no kernels, no chunked scan, no batching tricks, every matmul
at ``jax.default_matmul_precision("highest")``.  It reads the parameters
in the layout :func:`repro.models.transformer.init_lm` makes and follows
``transformers``' ``modeling_jamba`` layer by layer:

* a pre-norm residual block per layer: RMSNorm, mixer, residual; RMSNorm,
  feed-forward, residual; a final RMSNorm and the untied LM head;
* attention (``JambaAttention``): GQA, causal softmax, no positional
  encoding (RoPE only where the config asks for it);
* Mamba (``JambaMambaMixer.slow_forward``): in_proj, depthwise causal
  conv with bias, SiLU, x_proj, the RMSNorms over dt, B and C, dt_proj
  with its bias, softplus, then the textbook recurrence one token at a
  time, the D skip and the SiLU gate, out_proj;
* MoE (``JambaSparseMoeBlock``): softmax over all ``n_experts`` in
  float32, top-k, gates as the softmax gives them (renormalised only
  where the config asks), each expert a SwiGLU MLP over every token under
  a dense mask of its gates.

Departure from ``modeling_jamba``: only the experts this chip holds
(``experts_held`` from ``experts_offset``) add to an expert layer's
output, as in the program; with every expert held this is the whole
layer.  The router runs in float32 (``modeling_jamba`` runs it in the
activations' dtype, which here is float32 as well).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def layer_params(params: dict, cfg: ModelConfig, i: int) -> dict:
    """Layer ``i``'s parameters, unstacked from the period groups."""
    p = cfg.layer_period()
    r = 0 if cfg.unroll_layers else cfg.n_layers // p
    if i < r * p:
        group = params["group"][f"pos{i % p}"]
        return jax.tree.map(lambda a: a[i // p], group)
    return params[f"rem{i - r * p}"]


def rms(x, g, eps):
    """RMSNorm in float32."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, theta):
    """Rotary embedding over positions 0..T-1 (x [B,T,h,hd])."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p: dict, x, cfg: ModelConfig):
    """Causal GQA self-attention of the normalised input ``x``."""
    b, t, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, t, h, hd)
    k = (x @ p["wk"]).reshape(b, t, kv, hd)
    v = (x @ p["wv"]).reshape(b, t, kv, hd)
    if cfg.pos_encoding == "rope":
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, t, h * hd)
    return o @ p["wo"]


def mamba(p: dict, x, cfg: ModelConfig):
    """The Mamba-1 mixer of the normalised input ``x`` [B,T,d]."""
    t = x.shape[1]
    dt_rank, ds = cfg.mamba_dt_rank_actual, cfg.mamba_d_state
    xin, z = jnp.split(x @ p["in_proj"], 2, axis=-1)
    dc = p["conv_w"].shape[0]
    xp = jnp.pad(xin, ((0, 0), (dc - 1, 0), (0, 0)))
    xc = sum(xp[:, i:i + t] * p["conv_w"][i] for i in range(dc)) \
        + p["conv_b"]
    xc = jax.nn.silu(xc)
    dt, bm, cm = jnp.split(xc @ p["x_proj"], [dt_rank, dt_rank + ds], -1)
    if cfg.mamba_inner_norms:
        dt = rms(dt, p["dt_norm"], cfg.norm_eps)
        bm = rms(bm, p["b_norm"], cfg.norm_eps)
        cm = rms(cm, p["c_norm"], cfg.norm_eps)
    delta = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])   # [B,T,di]
    a = -jnp.exp(p["a_log"])                                    # [di,ds]

    def step(hs, inp):
        d_t, b_t, c_t, x_t = inp          # [B,di] [B,ds] [B,ds] [B,di]
        hs = jnp.exp(d_t[..., None] * a) * hs \
            + d_t[..., None] * b_t[:, None, :] * x_t[..., None]
        return hs, jnp.einsum("bis,bs->bi", hs, c_t)

    h0 = jnp.zeros((x.shape[0], xc.shape[-1], ds), jnp.float32)
    seq = tuple(jnp.swapaxes(a_, 0, 1) for a_ in (delta, bm, cm, xc))
    _, ys = jax.lax.scan(step, h0, seq)
    y = jnp.swapaxes(ys, 0, 1) + xc * p["d_skip"]
    return (y * jax.nn.silu(z)) @ p["out_proj"]


def mlp(p: dict, x):
    """SwiGLU feed-forward of the normalised input."""
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def route(p: dict, x, cfg: ModelConfig):
    """Top-k gates and expert ids [T,k] of the softmax over all experts."""
    vals, idx = jax.lax.top_k(jax.nn.softmax(x @ p["router"], axis=-1),
                              cfg.top_k)
    if cfg.moe_renormalize:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return vals, idx


def moe(p: dict, x, cfg: ModelConfig):
    """The held experts' part of the expert layer of the normalised input
    ``x`` [B,T,d], and the expert ids [B*T,k] it routed by."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    vals, idx = route(p, flat, cfg)
    out = jnp.zeros_like(flat)
    for j in range(cfg.n_experts_here):
        gate = jnp.sum(jnp.where(idx == cfg.experts_offset + j, vals, 0.0),
                       axis=-1)
        expert = {k: p[k][j] for k in ("w_gate", "w_up", "w_down")}
        out = out + gate[:, None] * mlp(expert, flat)
    return out.reshape(b, t, d), idx


def forward(params: dict, cfg: ModelConfig, tokens):
    """Float32 logits [B,T,vocab] of a causal pass over ``tokens`` [B,T],
    and the expert layers' choices [n_moe, B*T, k] (None without any)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        x = p["embed"][jnp.asarray(tokens)]
        eps, routes = cfg.norm_eps, []
        for i, (mixer, ffn) in enumerate(cfg.layer_plan()):
            lp = layer_params(p, cfg, i)
            h = rms(x, lp["mixer"]["norm"], eps)
            x = x + (attention(lp["mixer"], h, cfg) if mixer == "attn"
                     else mamba(lp["mixer"], h, cfg))
            h = rms(x, lp["ffn"]["norm"], eps)
            if ffn == "dense":
                x = x + mlp(lp["ffn"], h)
            else:
                o, idx = moe(lp["ffn"], h, cfg)
                x = x + o
                routes.append(idx)
        h = rms(x, p["final_norm"], eps)
        unembed = p["unembed"] if "unembed" in p else p["embed"].T
        return h @ unembed, (jnp.stack(routes) if routes else None)
