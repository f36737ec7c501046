"""Mixture-of-Experts FFN: top-k router + GShard-style capacity dispatch,
or a dropless grouped-matmul dispatch over a chip's held experts.

Expert-parallel by construction: expert tensors lead with the E dim (sharded
over the ``model`` mesh axis), tokens are grouped (group dim sharded over
``data``), and dispatch/combine are one-hot einsums that GSPMD lowers to
all-to-all-style collectives.

Group size bounds the dispatch tensor: per group of ``S_g`` tokens, capacity
``C = ceil(S_g * top_k / E * capacity_factor)``, so the [G, S_g, E, C]
dispatch one-hot stays ~tokens * S_g * top_k * cf elements regardless of E.
(Hillclimb note: the one-hot einsum burns E*C*d MACs per token; the sparse
gather-based dispatch is the documented beyond-paper optimisation.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, rms_norm, split_keys

MOE_GROUP_SIZE = 512  # tokens per dispatch group (see module docstring)


def moe_param_shapes(cfg: ModelConfig) -> dict:
    """The router is ``n_experts`` wide; the expert weights are the held
    experts' (``n_experts_here``)."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts_here
    return {
        "router": (d, cfg.n_experts),
        "w_gate": (e, d, f),
        "w_up": (e, d, f),
        "w_down": (e, f, d),
        "norm": (d,),
    }


def moe_init(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    shapes = moe_param_shapes(cfg)
    keys = split_keys(key, len(shapes))
    out = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name == "norm":
            out[name] = jnp.ones(shape, dtype)
        elif name == "router":
            out[name] = dense_init(k, shape, jnp.float32)  # router in f32
        else:
            out[name] = dense_init(k, shape, dtype)
    return out


def capacity(group_size: int, top_k: int, n_experts: int,
             factor: float) -> int:
    return max(int(group_size * top_k / n_experts * factor), top_k)


def route_topk(logits: jax.Array, top_k: int, renormalize: bool = True
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (gate values [T,k], expert ids [T,k], probs [T,E]); the
    gates are the top-k softmax values, rescaled to sum to 1 when
    ``renormalize``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    if renormalize:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return vals, idx, probs


def moe_gather(params: dict, x: jax.Array, cfg: ModelConfig
               ) -> tuple[jax.Array, jax.Array]:
    """Sort/gather-based dispatch (beyond-paper optimization, §Perf):
    instead of the GShard one-hot [T,E,C] einsums (E*C*d MACs per token),
    tokens are argsorted by expert id, gathered into the [E,C,d] buffer,
    and combined back by index — dispatch becomes memory ops, not matmul
    FLOPs.  Semantics match ``moe`` when capacity is not exceeded; under
    overflow, drop priority is slot-major/token-order (same rule).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    c = capacity(t, k, e, cfg.capacity_factor)

    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    flat = xn.reshape(t, d)
    logits = flat.astype(jnp.float32) @ params["router"]
    vals, idx, probs = route_topk(logits, k, cfg.moe_renormalize)  # [T,k]

    # Flatten (token, slot) pairs; slot-major order preserves the one-hot
    # version's drop priority (all slot-0 assignments outrank slot-1).
    expert_flat = idx.T.reshape(-1)                   # [k*T], slot-major
    token_flat = jnp.tile(jnp.arange(t), k)
    gate_flat = vals.T.reshape(-1)
    order = jnp.argsort(expert_flat, stable=True)
    sorted_exp = expert_flat[order]
    first = jnp.searchsorted(sorted_exp, sorted_exp, side="left")
    pos = jnp.arange(k * t) - first                   # position in expert
    keep = pos < c
    dest = jnp.where(keep, sorted_exp * c + pos, e * c)  # sentinel row

    # Gather tokens -> [E*C(+1), d] buffer; run experts; combine back.
    gathered = flat[token_flat[order]]
    buf = jnp.zeros((e * c + 1, d), flat.dtype).at[dest].set(gathered)
    x_e = buf[:e * c].reshape(e, c, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_e, params["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", x_e, params["w_up"])
    y_e = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(e * c, d)
    y_e = jnp.concatenate([y_e, jnp.zeros((1, d), y_e.dtype)])  # sentinel

    contrib = y_e[dest] * gate_flat[order][:, None].astype(y_e.dtype)
    out = jnp.zeros((t, d), x.dtype).at[token_flat[order]].add(
        contrib.astype(x.dtype))

    frac = jnp.zeros((e,), jnp.float32).at[sorted_exp].add(
        keep.astype(jnp.float32)) / t
    aux = e * jnp.sum(frac / k * jnp.mean(probs, axis=0))
    return out.reshape(b, s, d), aux.astype(jnp.float32)


def held_mask(cfg: ModelConfig, idx: jax.Array) -> jax.Array:
    """Which expert ids in ``idx`` this chip holds."""
    return (idx >= cfg.experts_offset) & \
        (idx < cfg.experts_offset + cfg.n_experts_here)


def route_stats(cfg: ModelConfig, prefill: jax.Array, steps: tuple
                ) -> jax.Array:
    """``[assigned, hit]`` int32 of one request's expert choices:
    ``prefill`` [n_moe, T, k] and ``steps``, each decode step's [n_moe,
    B, k] (-1 where no step ran).  ``assigned`` counts the (token, slot)
    pairs routed to experts held here, over prefill and decode; ``hit``
    the held experts a decode step gave at least one token, summed over
    the steps and expert layers."""
    assigned = jnp.sum(held_mask(cfg, prefill))
    hit = jnp.zeros((), jnp.int32)
    if steps:
        dec = jnp.stack(steps)                          # [n, n_moe, B, k]
        held = held_mask(cfg, dec)
        local = jnp.where(held, dec - cfg.experts_offset, -1)
        ids = jnp.arange(cfg.n_experts_here)
        hit = jnp.sum(jnp.any(local[..., None] == ids, axis=(2, 3)))
        assigned = assigned + jnp.sum(held)
    return jnp.stack([assigned, hit]).astype(jnp.int32)


def moe_dropless(params: dict, x: jax.Array, cfg: ModelConfig
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless dispatch over the experts this chip holds (serving).

    The router scores all ``n_experts`` in float32 and picks the top-k;
    the (token, slot) pairs whose expert is held here are sorted by
    expert and run through the three expert products as grouped matmuls
    (``jax.lax.ragged_dot``: each expert's rows, no capacity, no
    padding), then scatter-added back with their gates.  Pairs routed to
    experts held elsewhere add nothing here: with every expert held this
    is the whole layer, with a share it is that share's part of the sum
    (expert parallelism without its exchange).  Returns (output [B,S,d],
    aux loss, expert ids [B*S, k] int32).
    """
    b, s, d = x.shape
    k, n_here = cfg.top_k, cfg.n_experts_here
    t = b * s
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    flat = xn.reshape(t, d)
    logits = flat.astype(jnp.float32) @ params["router"]
    vals, idx, probs = route_topk(logits, k, cfg.moe_renormalize)
    held = held_mask(cfg, idx).reshape(-1)              # [T*k], token-major
    group = jnp.where(held, idx.reshape(-1) - cfg.experts_offset, n_here)
    order = jnp.argsort(group, stable=True)             # held pairs first
    sizes = jnp.bincount(group, length=n_here + 1)[:n_here].astype(
        jnp.int32)
    tok = order // k
    rows = flat[tok]
    h = jax.nn.silu(jax.lax.ragged_dot(rows, params["w_gate"], sizes)) \
        * jax.lax.ragged_dot(rows, params["w_up"], sizes)
    y = jax.lax.ragged_dot(h, params["w_down"], sizes)
    # Rows past the held pairs are left undefined by the TPU's grouped
    # product (any bits, NaN among them): select, never scale, them away.
    y = jnp.where(held[order][:, None],
                  y.astype(jnp.float32) * vals.reshape(-1)[order][:, None],
                  0.0)
    out = jnp.zeros((t, d), jnp.float32).at[tok].add(y)
    frac = jnp.zeros((cfg.n_experts,), jnp.float32).at[
        idx.reshape(-1)].add(1.0) / (t * k)
    aux = cfg.n_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
    return out.astype(x.dtype).reshape(b, s, d), aux, idx.astype(jnp.int32)


def moe(params: dict, x: jax.Array, cfg: ModelConfig,
        group_size: int = MOE_GROUP_SIZE
        ) -> tuple[jax.Array, jax.Array]:
    """Returns (output [B,S,d], aux load-balancing loss scalar)."""
    if cfg.moe_dispatch == "dropless":
        return moe_dropless(params, x, cfg)[:2]
    if getattr(cfg, "moe_dispatch", "onehot") == "gather":
        return moe_gather(params, x, cfg)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    sg = min(group_size, t)
    if t % sg:
        raise ValueError(f"tokens {t} not divisible by group size {sg}")
    g = t // sg
    c = capacity(sg, k, e, cfg.capacity_factor)

    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    flat = xn.reshape(g, sg, d)
    logits = jnp.einsum("gsd,de->gse", flat.astype(jnp.float32),
                        params["router"])
    vals, idx, probs = route_topk(logits.reshape(t, e), k,
                                  cfg.moe_renormalize)
    vals = vals.reshape(g, sg, k)
    idx = idx.reshape(g, sg, k)

    # Position-in-expert bookkeeping across the k slots.
    dispatch = jnp.zeros((g, sg, e, c), dtype=x.dtype)
    combine = jnp.zeros((g, sg, e, c), dtype=jnp.float32)
    counts = jnp.zeros((g, e), dtype=jnp.int32)
    for slot in range(k):
        oh = jax.nn.one_hot(idx[..., slot], e, dtype=jnp.int32)   # [g,sg,e]
        pos = jnp.cumsum(oh, axis=1) - 1 + counts[:, None, :]
        keep = (pos < c) & (oh > 0)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, -1), c,
                                dtype=x.dtype)                    # [g,sg,e,c]
        slot_dispatch = pos_oh * oh[..., None].astype(x.dtype)
        dispatch = dispatch + slot_dispatch
        combine = combine + slot_dispatch.astype(jnp.float32) * \
            vals[..., slot][..., None, None]
        counts = counts + jnp.sum(oh * keep.astype(jnp.int32), axis=1)

    # Dispatch -> expert FFN -> combine (all einsums; E leads for EP).
    x_e = jnp.einsum("gsec,gsd->egcd", dispatch, flat)
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", x_e, params["w_gate"])) \
        * jnp.einsum("egcd,edf->egcf", x_e, params["w_up"])
    y_e = jnp.einsum("egcf,efd->egcd", h, params["w_down"])
    out = jnp.einsum("egcd,gsec->gsd", y_e, combine.astype(x.dtype))

    # Load-balancing aux loss (Switch/GShard): E * sum_e f_e * P_e.
    probs_g = probs.reshape(g, sg, e)
    frac_dispatched = jnp.mean(
        (dispatch.sum(axis=-1) > 0).astype(jnp.float32), axis=1)  # [g,e]
    mean_prob = jnp.mean(probs_g, axis=1)                          # [g,e]
    aux = e * jnp.mean(jnp.sum(frac_dispatched * mean_prob, axis=-1))
    return out.reshape(b, s, d), aux.astype(jnp.float32)
