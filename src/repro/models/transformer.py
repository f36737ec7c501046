"""Patterned decoder-only LM: the chassis for 9 of the 10 assigned archs.

The layer plan (``cfg.layer_plan()``) assigns each layer a (mixer, ffn)
kind; the plan's smallest repeating *period* becomes the scan block:
parameters are stacked ``[n_repeats, ...]`` per period position and a
``lax.scan`` runs the repeats (remainder layers unrolled at the end).  This
keeps the HLO O(period) instead of O(n_layers) — essential for compile
times at 64 layers and for remat at scale.

Examples: dense Qwen = period 1; gemma3 = period 6 (5 local + 1 global);
Jamba = period 8 (7 mamba + 1 attn, MoE on odd layers); rwkv6 = period 1.

Anytime width nesting (``cfg.nest_levels > 1``) swaps in the nested
attention/MLP blocks; ``level`` selects a prefix subnetwork, and
``all_levels=True`` emits one logits tensor per level from a single forward
pass (the nesting property) for joint training.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.nesting import StripeSpec, prefix_rmsnorm
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models.attention import KVCache
from repro.models.common import embed_init, rms_norm, split_keys

# Optional activation-sharding constraint (hillclimb lever, set by the
# dry-run): Megatron-SP-style — annotate the residual stream so GSPMD uses
# reduce-scatter/all-gather pairs over the model axis instead of full
# all-reduces between blocks.
ACTIVATION_SHARDING = None


def _constrain(x):
    if ACTIVATION_SHARDING is not None:
        return jax.lax.with_sharding_constraint(x, ACTIVATION_SHARDING)
    return x


def _resolve_policy(cfg):
    """Remat policy (hillclimb lever): 'full' recomputes everything in the
    backward pass (min memory, +1 forward of FLOPs AND collectives);
    'save_dots' keeps matmul/collective outputs (no recompute of dots or
    their gathers/reduces, more saved activations)."""
    if cfg.remat_policy == "save_dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


class LMOutput(NamedTuple):
    logits: jax.Array | list[jax.Array]
    aux_loss: jax.Array
    caches: Any
    # Dropless MoE only: every expert layer's choices, [n_moe_layers,
    # B*S, top_k] int32 in layer order (None for other configs).
    routing: Any = None


# --------------------------------------------------------------------- #
# Per-layer init / apply                                                 #
# --------------------------------------------------------------------- #
def init_layer(key: jax.Array, cfg: ModelConfig, mixer: str,
               ffn: str) -> dict:
    k1, k2 = jax.random.split(key)
    if mixer in ("attn", "attn_local"):
        mp = attn_mod.attn_init(k1, cfg)
    elif mixer == "mamba":
        mp = mamba_mod.mamba_init(k1, cfg)
    elif mixer == "rwkv":
        mp = rwkv_mod.rwkv_init(k1, cfg)
    else:
        raise ValueError(mixer)
    if mixer == "rwkv":
        fp = {}
    elif ffn == "dense":
        fp = mlp_mod.mlp_init(k2, cfg)
    else:
        fp = moe_mod.moe_init(k2, cfg)
    return {"mixer": mp, "ffn": fp}


def init_cache_for(cfg: ModelConfig, mixer: str, batch: int,
                   max_len: int):
    if mixer in ("attn", "attn_local"):
        dtype = jnp.dtype(cfg.dtype)
        shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
    if mixer == "mamba":
        return mamba_mod.mamba_init_state(cfg, batch)
    if mixer == "rwkv":
        return rwkv_mod.rwkv_init_state(cfg, batch)
    raise ValueError(mixer)


def apply_layer(lp: dict, x: jax.Array, positions: jax.Array,
                cfg: ModelConfig, mixer: str, ffn: str, *,
                pos3d: jax.Array | None = None, cache=None,
                cache_len=None, level: int | None = None):
    """Returns (x, aux, new_cache, route): ``route`` is a dropless MoE
    layer's expert ids [B*S, top_k], else None."""
    aux = jnp.zeros((), jnp.float32)
    nested = cfg.nest_levels > 1
    if mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if mixer == "attn_local" else None
        if nested:
            a, new_cache = attn_mod.nested_attention(
                lp["mixer"], x, positions, cfg, level=level, window=window,
                cache=cache, cache_len=cache_len)
        else:
            a, new_cache = attn_mod.attention(
                lp["mixer"], x, positions, cfg, window=window,
                cache=cache, cache_len=cache_len, positions_3d=pos3d)
        x = x + a
    elif mixer == "mamba":
        m, new_cache = mamba_mod.mamba(lp["mixer"], x, cfg, state=cache)
        x = x + m
    elif mixer == "rwkv":
        t, wkv, tail_t = rwkv_mod.rwkv_time_mix(lp["mixer"], x, cfg,
                                                state=cache)
        x = x + t
        c, tail_c = rwkv_mod.rwkv_channel_mix(lp["mixer"], x, cfg,
                                              state=cache)
        x = x + c
        new_cache = rwkv_mod.RwkvState(wkv, tail_t, tail_c)
        return x, aux, new_cache, None
    else:
        raise ValueError(mixer)

    route = None
    if ffn == "dense":
        if nested:
            x = x + mlp_mod.nested_mlp(lp["ffn"], x, cfg, level=level)
        else:
            x = x + mlp_mod.mlp(lp["ffn"], x, cfg)
    elif cfg.moe_dispatch == "dropless":
        o, aux, route = moe_mod.moe_dropless(lp["ffn"], x, cfg)
        x = x + o
    else:
        o, aux = moe_mod.moe(lp["ffn"], x, cfg)
        x = x + o
    return x, aux, new_cache, route


# --------------------------------------------------------------------- #
# Whole-model init                                                       #
# --------------------------------------------------------------------- #
def _grouping(cfg: ModelConfig) -> tuple[int, int, int]:
    """(period, n_repeats, n_remainder).

    ``unroll_layers`` forces everything into the unrolled remainder path —
    no while loop in the HLO, so ``cost_analysis`` counts every layer
    (XLA counts a while body once; see launch/dryrun.py calibration).
    """
    p = cfg.layer_period()
    if cfg.unroll_layers:
        return p, 0, cfg.n_layers
    r = cfg.n_layers // p
    return p, r, cfg.n_layers - p * r


def init_lm(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    plan = cfg.layer_plan()
    p, r, rem = _grouping(cfg)
    keys = split_keys(key, 3 + cfg.n_layers)
    params: dict = {
        "embed": embed_init(keys[0], (cfg.vocab, cfg.d_model), dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(
            keys[1], (cfg.d_model, cfg.vocab), dtype) * cfg.d_model ** -0.5
    # Stacked group params: one stack per period position.
    if r > 0:
        group = {}
        for pos in range(p):
            mixer, ffn = plan[pos]
            stack = [init_layer(keys[3 + rep * p + pos], cfg, mixer, ffn)
                     for rep in range(r)]
            group[f"pos{pos}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *stack)
        params["group"] = group
    for i in range(rem):
        li = r * p + i
        mixer, ffn = plan[li]
        params[f"rem{i}"] = init_layer(keys[3 + li], cfg, mixer, ffn)
    return params


def init_caches(cfg: ModelConfig, batch: int, max_len: int):
    plan = cfg.layer_plan()
    p, r, rem = _grouping(cfg)
    caches: dict = {}
    if r > 0:
        group = {}
        for pos in range(p):
            mixer, _ = plan[pos]
            one = init_cache_for(cfg, mixer, batch, max_len)
            group[f"pos{pos}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (r,) + x.shape), one)
        caches["group"] = group
    for i in range(rem):
        mixer, _ = plan[r * p + i]
        caches[f"rem{i}"] = init_cache_for(cfg, mixer, batch, max_len)
    return caches


# --------------------------------------------------------------------- #
# Whole-model apply                                                      #
# --------------------------------------------------------------------- #
def lm_apply(params: dict, cfg: ModelConfig, tokens: jax.Array, *,
             pos3d: jax.Array | None = None, mode: str = "train",
             caches=None, cache_len: jax.Array | None = None,
             level: int | None = None, all_levels: bool = False,
             embeds: jax.Array | None = None,
             return_hidden: bool = False) -> LMOutput:
    """Forward pass.

    * ``mode='train'``: no caches in or out.
    * ``mode='prefill'``: no caches in; per-layer kv/state returned (length
      == prompt length; the serving engine pads into its max_len buffers).
    * ``mode='decode'``: ``caches`` + scalar ``cache_len`` given; tokens
      [B, 1]; updated caches returned.
    * ``embeds`` overrides token embedding (whisper/vlm frontend stub path).
    """
    assert mode in ("train", "prefill", "decode")
    plan = cfg.layer_plan()
    p, r, rem = _grouping(cfg)
    b, s = (tokens.shape if embeds is None else embeds.shape[:2])
    decode = mode == "decode"
    want_cache = mode in ("prefill", "decode")
    if decode:
        positions = jnp.broadcast_to(
            jnp.asarray(cache_len)[..., None], (b, s)).astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x = params["embed"][tokens] if embeds is None else embeds
    if cfg.nest_levels > 1 and level is not None and \
            level < cfg.nest_levels:
        # Level-k execution runs the whole pipeline on the d_k prefix
        # (nesting property: identical to the standalone subnetwork).
        d_spec_trunc = StripeSpec.pow2(cfg.d_model, cfg.nest_levels)
        x = x[..., :d_spec_trunc.width(level)]
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: dict = {}
    routes: list = []

    def block(x, block_params, block_caches):
        """One period of layers (positions 0..p-1)."""
        aux_sum = jnp.zeros((), jnp.float32)
        outs, routes = {}, []
        if mode != "decode":
            x = _constrain(x)
        for pos in range(p):
            mixer, ffn = plan[pos]
            cache = block_caches.get(f"pos{pos}") if block_caches else None
            x, aux, nc, route = apply_layer(
                block_params[f"pos{pos}"], x, positions, cfg, mixer, ffn,
                pos3d=pos3d, cache=cache, cache_len=cache_len, level=level)
            aux_sum = aux_sum + aux
            outs[f"pos{pos}"] = nc if want_cache else None
            if route is not None:
                routes.append(route)
        return x, aux_sum, (outs, routes)

    if r > 0:
        def scan_body(carry, xs):
            x, aux = carry
            bp, bc = xs
            fn = block
            if cfg.remat and mode == "train":
                fn = jax.checkpoint(block, policy=_resolve_policy(cfg))
            x, aux_sum, outs = fn(x, bp, bc)
            return (x, aux + aux_sum), outs

        if decode:
            (x, aux_total), outs = jax.lax.scan(
                scan_body, (x, aux_total),
                (params["group"], caches["group"]))
        else:
            def scan_body_nc(carry, bp):
                return scan_body(carry, (bp, {f"pos{q}": None
                                              for q in range(p)}))
            (x, aux_total), outs = jax.lax.scan(
                scan_body_nc, (x, aux_total), params["group"])
        outs, group_routes = outs
        if want_cache:
            new_caches["group"] = outs
        if group_routes:
            # [r, n_pos, T, k] -> layer order (repeat-major).
            stacked = jnp.stack(group_routes, axis=1)
            routes.extend(stacked.reshape((-1,) + stacked.shape[2:]))

    for i in range(rem):
        li = r * p + i
        mixer, ffn = plan[li]
        cache = caches.get(f"rem{i}") if decode else None
        def layer_fn(lp, x_, mixer=mixer, ffn=ffn, cache=cache):
            return apply_layer(lp, x_, positions, cfg, mixer, ffn,
                               pos3d=pos3d, cache=cache,
                               cache_len=cache_len, level=level)
        if cfg.remat and mode == "train":
            layer_fn = jax.checkpoint(layer_fn, policy=_resolve_policy(cfg))
        x, aux, nc, route = layer_fn(params[f"rem{i}"], x)
        aux_total = aux_total + aux
        if want_cache:
            new_caches[f"rem{i}"] = nc
        if route is not None:
            routes.append(route)

    if mode == "prefill" and cfg.prefill_last_only:
        # Serving semantics (hillclimb lever): prefill's product is the KV
        # cache; only the last position's logits are needed to start
        # decoding.  Avoids the [B, S, vocab] logits tensor and its
        # all-gather entirely.
        x = x[:, -1:, :]

    if return_hidden:
        # Chunked-loss path: caller projects to vocab chunk-by-chunk.
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return LMOutput(h, aux_total, new_caches if want_cache else None,
                        _stack_routes(routes))

    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T

    if cfg.nest_levels > 1:
        d_spec = StripeSpec.pow2(cfg.d_model, cfg.nest_levels)
        levels = range(1, cfg.nest_levels + 1) if all_levels else \
            [level if level is not None else cfg.nest_levels]
        logits_per_level = []
        for k in levels:
            hk = prefix_rmsnorm(x, params["final_norm"], d_spec, k,
                                cfg.norm_eps)
            logits_per_level.append(hk @ unembed[:d_spec.width(k), :])
        logits = logits_per_level if all_levels else logits_per_level[0]
    else:
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = h @ unembed
    return LMOutput(logits, aux_total, new_caches if want_cache else None,
                    _stack_routes(routes))


def _stack_routes(routes: list):
    """The expert layers' choices as one array, or None without any."""
    return jnp.stack(routes) if routes else None
