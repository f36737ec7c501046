"""Pallas TPU kernel: streaming-softmax (flash) attention with GQA,
causal masking, and optional sliding window — the prefill/train hot spot.

Grid: (batch, q_heads, q_blocks, kv_blocks); the kv dim is innermost and
sequential.  Running max/denominator/accumulator live in VMEM scratch and
are rescaled per kv block (the standard two-pass-free streaming softmax).
GQA is handled in the K/V BlockSpec index maps: q head ``h`` reads kv head
``h // (n_q_heads / n_kv_heads)``, so grouped q heads reuse the same KV
tiles (VMEM-friendly: one KV block serves ``g`` q heads).

Causal + window tiles that are fully masked are skipped via ``pl.when`` —
for long sequences the causal grid does ~half the work, and a sliding
window of size w touches only O(S*w) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq: int, bk: int, causal: bool, window: int | None,
            softcap: float | None, scale: float):
    qi, ki = pl.program_id(2), pl.program_id(3)
    last = pl.num_programs(3) - 1

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # Tile-level skip: fully-masked (causal/window) kv tiles do no work.
    live = jnp.bool_(True)
    if causal:
        live &= (q_start + bq - 1) >= k_start
    if window is not None:
        live &= (q_start - (k_start + bk - 1)) < window

    @pl.when(live)
    def _block():
        q = q_ref[0, :, 0, :]                      # [bq, hd]
        k = k_ref[0, :, 0, :]                      # [bk, hd]
        v = v_ref[0, :, 0, :]
        logits = jnp.dot(q, k.T,
                         preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]                        # [bq, 1]
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
        o_ref[0, :, 0, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: [B,S,h,hd]; k/v: [B,T,kv,hd] -> [B,S,h,hd]."""
    b, s, h, hd = q.shape
    t, n_kv = k.shape[1], k.shape[2]
    if h % n_kv:
        raise ValueError("GQA needs n_q_heads % n_kv_heads == 0")
    g = h // n_kv
    bq, bk = min(bq, s), min(bk, t)
    if s % bq or t % bk:
        raise ValueError(f"seq ({s},{t}) not divisible by blocks ({bq},{bk})")
    grid = (b, h, s // bq, t // bk)

    kernel = functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, softcap=softcap,
                               scale=hd ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, hd),
                         lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda bi, hi, qi, ki: (bi, ki, hi // g, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda bi, hi, qi, ki: (bi, ki, hi // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd),
                               lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((b, s, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
