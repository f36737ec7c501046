"""Pallas TPU kernel: RWKV-6 chunked recurrence.

Grid: (batch, heads, S/chunk); the chunk dim is innermost/sequential and
the [hd, hd] wkv state lives in VMEM scratch across chunk steps — the state
never round-trips to HBM inside a sequence (the whole point of chunking the
recurrence on TPU: r/k/v/w stream through VMEM once, the state stays put).

Inside a chunk a ``fori_loop`` runs the token recurrence:

    y_t = r_t . (S + (u (.) k_t) v_t^T);   S <- diag(w_t) S + k_t v_t^T

Each step is rank-1-update + matvec on a [hd, hd] = [64, 64] tile — VPU
work with MXU-aligned lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sn_ref,
            state_ref, *, chunk: int):
    ci = pl.program_id(2)
    last = pl.num_programs(2) - 1

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0, 0].astype(jnp.float32)            # [1, hd] -> [hd]

    def _step(t, _):
        rt = r_ref[0, t, 0, :].astype(jnp.float32)
        kt = k_ref[0, t, 0, :].astype(jnp.float32)
        vt = v_ref[0, t, 0, :].astype(jnp.float32)
        wt = w_ref[0, t, 0, :].astype(jnp.float32)
        s = state_ref[...]
        kv = kt[:, None] * vt[None, :]
        y = (rt[:, None] * (s + u[:, None] * kv)).sum(axis=0)
        y_ref[0, t, 0, :] = y.astype(y_ref.dtype)
        state_ref[...] = wt[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, chunk, _step, 0)

    @pl.when(ci == last)
    def _emit():
        sn_ref[0, 0] = state_ref[...].astype(sn_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, s0: jax.Array, *, chunk: int = 128,
              interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """r/k/v/w: [B,S,H,hd]; u: [H,hd]; s0: [B,H,hd,hd] (f32).

    Returns (y [B,S,H,hd], s_final [B,H,hd,hd] f32).
    """
    b, s, h, hd = r.shape
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"seq {s} not divisible by chunk {chunk}")
    grid = (b, h, s // chunk)
    kernel = functools.partial(_kernel, chunk=chunk)

    y, sn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1, hd), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1, hd), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1, hd), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, hd), lambda bi, hi, ci: (hi, 0, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, hd), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, hd), s0)
    return y, sn
