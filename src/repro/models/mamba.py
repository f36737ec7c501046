"""Mamba (S6) mixer — Jamba's SSM layer (arXiv:2403.19887 uses Mamba-1).

Selective SSM with diagonal A, input-dependent (delta, B, C).  Training /
prefill runs a **chunked scan**: the sequence is cut into ``cfg.mamba_chunk``
blocks; an outer ``lax.scan`` carries the [B, d_inner, d_state] SSM state
across chunks (rematerialised per chunk), an inner ``lax.scan`` runs the
recurrence within the chunk.  Decode is a single recurrence step carrying
(ssm state, conv tail) — O(1) in sequence length, which is why Jamba runs
the ``long_500k`` cell.

With ``cfg.mamba_inner_norms`` (Jamba) dt, B and C are each RMS-normalised
with a gain of their own right after ``x_proj``, before ``dt_proj`` and
the scan, in prefill and decode alike.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, rms_norm, split_keys


class MambaState(NamedTuple):
    ssm: jax.Array        # [B, d_inner, d_state]
    conv: jax.Array       # [B, d_conv - 1, d_inner]


def mamba_param_shapes(cfg: ModelConfig) -> dict:
    d, di = cfg.d_model, cfg.mamba_d_inner
    ds, dc = cfg.mamba_d_state, cfg.mamba_d_conv
    dt = cfg.mamba_dt_rank_actual
    shapes = {
        "in_proj": (d, 2 * di),
        "conv_w": (dc, di),
        "conv_b": (di,),
        "x_proj": (di, dt + 2 * ds),
        "dt_proj": (dt, di),
        "dt_bias": (di,),
        "a_log": (di, ds),
        "d_skip": (di,),
        "out_proj": (di, d),
        "norm": (d,),
    }
    if cfg.mamba_inner_norms:
        shapes.update(dt_norm=(dt,), b_norm=(ds,), c_norm=(ds,))
    return shapes


def mamba_init(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    shapes = mamba_param_shapes(cfg)
    keys = split_keys(key, len(shapes))
    out = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, dtype)
        elif name == "a_log":
            # S4D-real init: A = -(1..d_state), stored as log.
            a = jnp.broadcast_to(jnp.arange(1, shape[1] + 1,
                                            dtype=jnp.float32), shape)
            out[name] = jnp.log(a)
        elif name == "d_skip":
            out[name] = jnp.ones(shape, jnp.float32)
        elif name in ("conv_b", "dt_bias"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = dense_init(k, shape, dtype)
    return out


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 tail: jax.Array | None) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv along seq.  x [B,S,di], w [dc,di].

    ``tail`` is the previous (dc-1) inputs for streaming; returns the new
    tail so decode can continue the stream.
    """
    dc = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], dc - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(dc))
    new_tail = xp[:, -(dc - 1):, :] if dc > 1 else tail
    return out + b.astype(x.dtype), new_tail


def _ssm_chunk(carry: jax.Array, inputs, a: jax.Array):
    """Inner recurrence over one chunk.  carry: h [B,di,ds] (f32)."""
    def step(h, xs):
        delta, bu, cu, xu = xs       # [B,di], [B,ds], [B,ds], [B,di]
        da = jnp.exp(delta[..., None] * a)                  # [B,di,ds]
        h = h * da + delta[..., None] * xu[..., None] * bu[:, None, :]
        y = jnp.einsum("bis,bs->bi", h, cu)
        return h, y
    return jax.lax.scan(step, carry, inputs)


def mamba(params: dict, x: jax.Array, cfg: ModelConfig,
          state: MambaState | None = None,
          ) -> tuple[jax.Array, MambaState]:
    """Pre-norm Mamba block.  x [B,S,d] -> ([B,S,d], new state)."""
    b, s, d = x.shape
    di, ds = cfg.mamba_d_inner, cfg.mamba_d_state
    dt_rank = cfg.mamba_dt_rank_actual
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    xz = xn @ params["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)

    conv_tail = state.conv if state is not None else None
    xc, new_tail = _causal_conv(xin, params["conv_w"], params["conv_b"],
                                conv_tail)
    xc = jax.nn.silu(xc)

    proj = xc @ params["x_proj"]
    dt_raw, b_ssm, c_ssm = jnp.split(
        proj, [dt_rank, dt_rank + ds], axis=-1)
    if cfg.mamba_inner_norms:
        dt_raw = rms_norm(dt_raw, params["dt_norm"], cfg.norm_eps)
        b_ssm = rms_norm(b_ssm, params["b_norm"], cfg.norm_eps)
        c_ssm = rms_norm(c_ssm, params["c_norm"], cfg.norm_eps)
    delta = jax.nn.softplus(dt_raw @ params["dt_proj"]
                            + params["dt_bias"]).astype(jnp.float32)
    a = -jnp.exp(params["a_log"])                            # [di, ds]
    b_ssm = b_ssm.astype(jnp.float32)
    c_ssm = c_ssm.astype(jnp.float32)
    xc_f = xc.astype(jnp.float32)

    h0 = state.ssm if state is not None else \
        jnp.zeros((b, di, ds), jnp.float32)

    if s == 1:
        # Decode: one recurrence step.
        da = jnp.exp(delta[:, 0, :, None] * a)
        h = h0 * da + delta[:, 0, :, None] * xc_f[:, 0, :, None] \
            * b_ssm[:, 0, None, :]
        y = jnp.einsum("bis,bs->bi", h, c_ssm[:, 0])[:, None, :]
        hN = h
    else:
        chunk = min(cfg.mamba_chunk, s)
        n_chunks = -(-s // chunk)
        pad = n_chunks * chunk - s
        if pad:
            # delta=0 padding leaves the state untouched (exp(0*A)=1,
            # zero input contribution); padded outputs are sliced off.
            padfn = lambda t: jnp.pad(t, ((0, 0), (0, pad)) +
                                      ((0, 0),) * (t.ndim - 2))
            delta, b_ssm, c_ssm, xc_f = map(padfn,
                                            (delta, b_ssm, c_ssm, xc_f))

        def to_chunks(t):
            return t.reshape(b, n_chunks, chunk, *t.shape[2:]) \
                    .swapaxes(0, 1).swapaxes(1, 2)  # [n,chunk,B,...]

        xs = (to_chunks(delta), to_chunks(b_ssm), to_chunks(c_ssm),
              to_chunks(xc_f))

        def outer(h, chunk_xs):
            h, ys = jax.checkpoint(
                lambda h_, cx: _ssm_chunk(h_, cx, a))(h, chunk_xs)
            return h, ys
        hN, ys = jax.lax.scan(outer, h0, xs)
        # ys: [n_chunks, chunk, B, di] -> [B, S(+pad), di]
        y = ys.reshape(n_chunks * chunk, b, di).swapaxes(0, 1)[:, :s]

    y = y.astype(x.dtype) + xc * params["d_skip"].astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = y @ params["out_proj"]
    return out, MambaState(ssm=hN, conv=new_tail)


def mamba_init_state(cfg: ModelConfig, batch: int) -> MambaState:
    return MambaState(
        ssm=jnp.zeros((batch, cfg.mamba_d_inner, cfg.mamba_d_state),
                      jnp.float32),
        conv=jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.mamba_d_inner),
                       jnp.dtype(cfg.dtype)))
