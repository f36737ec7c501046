"""Pallas TPU kernel: single-query decode attention over a long KV cache.

The decode_32k / long_500k hot spot: one new query position per sequence
attends over S cached KV positions.  Memory-bound (the whole KV cache is
read once per step), so the kernel's job is a clean streaming pipeline:

Grid: (batch, kv_heads, S/bk); the kv-block dim is innermost/sequential
with streaming-softmax state in VMEM scratch.  All ``g = h/kv`` grouped
q heads ride along in one [g, hd] tile so each KV block is read exactly
once.  ``cache_len`` arrives via scalar prefetch; tiles beyond it are
skipped (so a 500k-slot buffer with a 100k-token cache reads only 100k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bk: int, window: int | None, scale: float, n_kv: int):
    bh, ki = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1
    cache_len = len_ref[bh // n_kv]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = ki * bk
    live = k_start < cache_len
    if window is not None:
        live &= (k_start + bk) > (cache_len - window)

    @pl.when(live)
    def _block():
        q = q_ref[0, 0, :, :]                       # [g, hd]
        k = k_ref[0, :, 0, :]                       # [bk, hd]
        v = v_ref[0, :, 0, :]
        logits = jnp.dot(q, k.T,
                         preferred_element_type=jnp.float32) * scale
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        mask = k_pos < cache_len
        if window is not None:
            mask &= k_pos >= cache_len - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == last)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     cache_len: jax.Array, *, window: int | None = None,
                     bk: int = 512, interpret: bool = False) -> jax.Array:
    """q: [B,h,hd]; k/v: [B,S,kv,hd]; cache_len scalar or [B] -> [B,h,hd]."""
    b, h, hd = q.shape
    s, n_kv = k.shape[1], k.shape[2]
    g = h // n_kv
    bk = min(bk, s)
    if s % bk:
        raise ValueError(f"cache {s} not divisible by block {bk}")
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    qg = q.reshape(b, n_kv, g, hd)
    grid = (b * n_kv, s // bk)

    kernel = functools.partial(_kernel, bk=bk, window=window,
                               scale=hd ** -0.5, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, hd),
                             lambda bh, ki, lens: (bh // n_kv, bh % n_kv,
                                                   0, 0)),
                pl.BlockSpec((1, bk, 1, hd),
                             lambda bh, ki, lens: (bh // n_kv, ki,
                                                   bh % n_kv, 0)),
                pl.BlockSpec((1, bk, 1, hd),
                             lambda bh, ki, lens: (bh // n_kv, ki,
                                                   bh % n_kv, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd),
                                   lambda bh, ki, lens: (bh // n_kv,
                                                         bh % n_kv, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, hd), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, qg, k, v)
    return out.reshape(b, h, hd)
