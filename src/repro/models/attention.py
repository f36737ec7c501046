"""Attention: GQA + RoPE/M-RoPE/NoPE + sliding window + cross-attention.

Two execution paths:

* ``ref`` — pure-jnp, **query-chunked** flash-style attention: scores are
  materialised one query chunk at a time inside a ``lax.map``, so HLO bytes
  stay bounded for 32k prefills (this is also what the dry-run lowers, so
  roofline terms reflect a production streaming-attention schedule, not an
  S^2 blow-up).
* ``kernel`` — the Pallas kernels in ``repro.kernels`` (TPU target;
  validated in interpret mode against these refs).

Width-nested (anytime) attention stripes the *heads*: q heads follow the
pow2 stripe spec; KV heads are striped when divisible, else saturated into
stripe 1 (they may then only read stripe-1 inputs — see
``StripeSpec.saturated``).  The projections use ``nested_norm_linear`` /
``nested_linear`` so level-k execution touches only level-k weights.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.nesting import StripeSpec, nested_linear, nested_norm_linear
from repro.models.common import (apply_mrope, apply_rope, dense_init,
                                 rms_norm, split_keys)


class KVCache(NamedTuple):
    k: jax.Array        # [B, S_max, n_kv, head_dim]
    v: jax.Array        # [B, S_max, n_kv, head_dim]


# --------------------------------------------------------------------- #
# Params                                                                 #
# --------------------------------------------------------------------- #
def attn_param_shapes(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    shapes = {
        "wq": (d, h * hd),
        "wk": (d, kv * hd),
        "wv": (d, kv * hd),
        "wo": (h * hd, d),
        "norm": (d,),
    }
    if cfg.qkv_bias and not cross:
        shapes.update({"bq": (h * hd,), "bk": (kv * hd,), "bv": (kv * hd,)})
    return shapes


def attn_init(key: jax.Array, cfg: ModelConfig, cross: bool = False) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    shapes = attn_param_shapes(cfg, cross)
    keys = split_keys(key, len(shapes))
    params = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name == "norm":
            params[name] = jnp.ones(shape, dtype)
        elif name.startswith("b"):
            params[name] = jnp.zeros(shape, dtype)
        elif name == "wo":
            params[name] = dense_init(
                k, shape, dtype, scale=(shape[0] ** -0.5) /
                math.sqrt(2 * cfg.n_layers))
        else:
            params[name] = dense_init(k, shape, dtype)
    return params


# --------------------------------------------------------------------- #
# Core scaled-dot-product with chunked queries                           #
# --------------------------------------------------------------------- #
def _sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                  q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
                  window: int | None, chunk: int,
                  softcap: float | None,
                  banded: bool = False,
                  unroll_chunks: bool = False) -> jax.Array:
    """q: [B,S,h,hd]; k/v: [B,T,kv,hd]; positions: [B,S] / [B,T].

    ``banded`` (hillclimb lever): for causal sliding-window attention each
    query chunk reads only the ``chunk + window`` key band instead of the
    full T keys — O(S*(chunk+w)) instead of O(S*T) compute and bytes.
    """
    b, s, h, hd = q.shape
    t, n_kv = k.shape[1], k.shape[2]
    groups = h // n_kv
    scale = hd ** -0.5
    chunk = min(chunk, s)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    qc = q.reshape(b, n_chunks, chunk, n_kv, groups, hd)
    qp = q_pos.reshape(b, n_chunks, chunk)

    use_band = (banded and causal and window is not None and t == s
                and not pad)
    span = min(t, chunk + (window or 0)) if use_band else t

    def one_chunk(args):
        if use_band:
            qi, qpi, ci = args               # + chunk index
            start = jnp.clip(ci * chunk + chunk - span, 0, t - span)
            kb = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            kp = start + jnp.arange(span)[None, :]
            kp = jnp.broadcast_to(kp, (b, span))
        else:
            qi, qpi = args
            kb, vb, kp = k, v, k_pos
        logits = jnp.einsum("bckgd,btkd->bkgct", qi, kb,
                            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        mask = jnp.ones((b, chunk, kb.shape[1]), dtype=bool)
        if causal:
            mask &= qpi[:, :, None] >= kp[:, None, :]
        if window is not None:
            mask &= (qpi[:, :, None] - kp[:, None, :]) < window
        mask &= kp[:, None, :] >= 0          # padding keys
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(vb.dtype)
        return jnp.einsum("bkgct,btkd->bckgd", probs, vb)

    if use_band:
        xs = (qc.swapaxes(0, 1), qp.swapaxes(0, 1),
              jnp.arange(n_chunks))
    else:
        xs = (qc.swapaxes(0, 1), qp.swapaxes(0, 1))
    if unroll_chunks:
        # Calibration path: a while-free python loop so cost_analysis
        # counts every chunk (XLA counts a scan/map body once).
        outs = [one_chunk(jax.tree.map(lambda t: t[i], xs))
                for i in range(n_chunks)]
        out = jnp.stack(outs)
    else:
        out = jax.lax.map(one_chunk, xs)
    out = out.swapaxes(0, 1).reshape(b, n_chunks * chunk, h, hd)
    return out[:, :s]


def _sdpa_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 cache_len: jax.Array, *, window: int | None,
                 softcap: float | None) -> jax.Array:
    """Single-position decode: q [B,1,h,hd] vs cache k/v [B,S,kv,hd]."""
    b, _, h, hd = q.shape
    s, n_kv = k.shape[1], k.shape[2]
    groups = h // n_kv
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len), (b,))
    qg = q.reshape(b, n_kv, groups, hd)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    pos = jnp.arange(s)[None, :]
    mask = pos < cache_len[:, None]
    if window is not None:
        mask &= pos >= (cache_len[:, None] - window)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v)
    return out.reshape(b, 1, h, hd)


# --------------------------------------------------------------------- #
# Full attention block (pre-norm, residual handled by caller)            #
# --------------------------------------------------------------------- #
def attention(params: dict, x: jax.Array, positions: jax.Array,
              cfg: ModelConfig, *, causal: bool = True,
              window: int | None = None,
              cache: KVCache | None = None,
              cache_len: jax.Array | None = None,
              cross_kv: tuple[jax.Array, jax.Array] | None = None,
              positions_3d: jax.Array | None = None,
              ) -> tuple[jax.Array, KVCache | None]:
    """Pre-norm attention.  Returns (block output, updated cache).

    Modes:
      * train/prefill: ``cache is None`` (or prefill-into-cache when a cache
        is provided with ``cache_len == 0``-style semantics handled by the
        caller writing the returned kv)
      * decode: ``cache`` + ``cache_len`` given, x has seq-len 1
      * cross-attention: ``cross_kv`` given (whisper decoder)
    """
    b, s, d = x.shape
    h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    q = xn @ params["wq"]
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(b, s, h, hd)

    if cross_kv is not None:
        k, v = cross_kv
        out = _sdpa_chunked(q, k, v, positions,
                            jnp.arange(k.shape[1])[None, :].repeat(b, 0),
                            causal=False, window=None, chunk=cfg.attn_chunk,
                            softcap=cfg.attn_logit_softcap)
        return out.reshape(b, s, h * hd) @ params["wo"], None

    k = xn @ params["wk"]
    v = xn @ params["wv"]
    if "bk" in params:
        k, v = k + params["bk"], v + params["bv"]
    k = k.reshape(b, s, n_kv, hd)
    v = v.reshape(b, s, n_kv, hd)

    if cfg.pos_encoding == "none":
        pass                      # NoPE: order comes from the causal mask
    elif cfg.m_rope and positions_3d is not None:
        q = apply_mrope(q, positions_3d, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions_3d, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is not None and cache_len is not None:
        # Decode: append this step's kv at cache_len, attend over the cache.
        new_k = _scatter_at(cache.k, k, cache_len)
        new_v = _scatter_at(cache.v, v, cache_len)
        out = _sdpa_decode(q, new_k, new_v, cache_len + s,
                           window=window, softcap=cfg.attn_logit_softcap)
        new_cache = KVCache(new_k, new_v)
    else:
        out = _sdpa_chunked(q, k, v, positions, positions, causal=causal,
                            window=window, chunk=cfg.attn_chunk,
                            softcap=cfg.attn_logit_softcap,
                            banded=cfg.window_banded,
                            unroll_chunks=cfg.attn_unroll_chunks)
        new_cache = KVCache(k, v)  # prefill result; caller may store it
    return out.reshape(b, s, h * hd) @ params["wo"], new_cache


def _scatter_at(buf: jax.Array, update: jax.Array,
                index: jax.Array) -> jax.Array:
    """Write ``update`` [B,s,...] into ``buf`` [B,S,...] at position
    ``index`` (scalar or per-batch scalar) along axis 1."""
    idx = jnp.asarray(index)
    if idx.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, update.astype(buf.dtype), idx, axis=1)
    # Per-batch index: vmap the slice update.
    return jax.vmap(
        lambda b_, u_, i_: jax.lax.dynamic_update_slice_in_dim(
            b_, u_.astype(b_.dtype), i_, axis=0))(buf, update, idx)


# --------------------------------------------------------------------- #
# Width-nested attention (anytime)                                       #
# --------------------------------------------------------------------- #
def head_stripe_specs(cfg: ModelConfig) -> tuple[StripeSpec, StripeSpec,
                                                 StripeSpec]:
    """(d_model spec, q-head-channel spec, kv-head-channel spec)."""
    levels = cfg.nest_levels
    d_spec = StripeSpec.pow2(cfg.d_model, levels)
    denom = 2 ** (levels - 1)
    if cfg.n_heads % denom == 0:
        q_spec = StripeSpec.pow2(cfg.n_heads * cfg.head_dim, levels)
    else:
        q_spec = StripeSpec.saturated(cfg.n_heads * cfg.head_dim, levels)
    if cfg.n_kv_heads % denom == 0:
        kv_spec = StripeSpec.pow2(cfg.n_kv_heads * cfg.head_dim, levels)
    else:
        kv_spec = StripeSpec.saturated(cfg.n_kv_heads * cfg.head_dim, levels)
    return d_spec, q_spec, kv_spec


def nested_attention(params: dict, x: jax.Array, positions: jax.Array,
                     cfg: ModelConfig, *, level: int | None = None,
                     causal: bool = True, window: int | None = None,
                     cache: KVCache | None = None,
                     cache_len: jax.Array | None = None,
                     ) -> tuple[jax.Array, KVCache | None]:
    """Anytime width-nested attention.

    Heads are striped; level-k uses the first ``width_q(k)/head_dim`` query
    heads and the corresponding KV prefix.  All projections are
    block-lower-triangular in stripe space.  Serving compiles one program
    per level; caches are sized to the level's KV width (the controller
    picks the level per *request*, so a request's cache stays consistent).
    """
    b, s, _ = x.shape
    hd = cfg.head_dim
    d_spec, q_spec, kv_spec = head_stripe_specs(cfg)

    be = cfg.nest_backend
    q = nested_norm_linear(x, params["norm"], params["wq"], d_spec, q_spec,
                           level=level, eps=cfg.norm_eps, backend=be)
    k = nested_norm_linear(x, params["norm"], params["wk"], d_spec, kv_spec,
                           level=level, eps=cfg.norm_eps, backend=be)
    v = nested_norm_linear(x, params["norm"], params["wv"], d_spec, kv_spec,
                           level=level, eps=cfg.norm_eps, backend=be)
    n_q = q.shape[-1] // hd
    n_kv = k.shape[-1] // hd
    q = q.reshape(b, s, n_q, hd)
    k = k.reshape(b, s, n_kv, hd)
    v = v.reshape(b, s, n_kv, hd)
    if cfg.pos_encoding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cache is not None and cache_len is not None:
        new_k = _scatter_at(cache.k, k, cache_len)
        new_v = _scatter_at(cache.v, v, cache_len)
        out = _sdpa_decode(q, new_k, new_v, cache_len + s, window=window,
                           softcap=cfg.attn_logit_softcap)
        new_cache = KVCache(new_k, new_v)
    else:
        out = _sdpa_chunked(q, k, v, positions, positions, causal=causal,
                            window=window, chunk=cfg.attn_chunk,
                            softcap=cfg.attn_logit_softcap)
        new_cache = KVCache(k, v)
    out = out.reshape(b, s, n_q * hd)
    # Output projection: head stripes -> d_model stripes.
    return nested_linear(out, params["wo"], q_spec, d_spec, level=level,
                         backend=be), new_cache


def nested_attn_init(key: jax.Array, cfg: ModelConfig) -> dict:
    return attn_init(key, cfg)
