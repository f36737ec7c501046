"""The width-nested anytime LM: weights, plain reference, FLOP count.

* :func:`make_params` — the served weights, drawn from the seed on the
  device in one jitted call, in the served dtype and in the parameter
  layout the program's model reads (a stack of identical layers).
* :func:`forward` — the plain reference: a full causal forward pass at
  nesting level ``k`` in float32 at the highest matmul precision, written
  from the paper's nesting rule (arXiv:1911.00119 Sec. 4.2.1): a layer of
  width ``D`` is split into power-of-two stripes, output stripe ``i``
  reads input stripes ``j <= i``, and each stripe's input is normalised
  as the standalone level-``i`` network normalises it.  With ``fp8=True``
  every matmul operand is rounded to float8 (e4m3) first: the control.
* :func:`request_flops` — the operations a request at level ``k`` needs:
  every projection and MLP product at the level's nested widths, causal
  attention over the prefix, and the LM head on the one position whose
  token is kept.
"""

from __future__ import annotations

import functools
import math

import numpy as np

# ----------------------------------------------------------------- shapes


def width(total: int, levels: int, level: int) -> int:
    """Cumulative width of ``level`` of a power-of-two striped dim."""
    return total * 2 ** (level - 1) // 2 ** (levels - 1)


def stripes(total: int, levels: int, level: int):
    """``[(start, stop)]`` of stripes 1..level."""
    b = [0] + [width(total, levels, k) for k in range(1, levels + 1)]
    return [(b[i - 1], b[i]) for i in range(1, level + 1)]


def head_total(cfg: dict, kind: str) -> int:
    """Channels of the query or key/value heads."""
    n = cfg["n_heads"] if kind == "q" else cfg["n_kv_heads"]
    return n * cfg["head_dim"]


def _check_nestable(cfg: dict) -> None:
    """Heads split into power-of-two stripes only when divisible."""
    denom = 2 ** (cfg["nest_levels"] - 1)
    for k in ("n_heads", "n_kv_heads"):
        if cfg[k] % denom:
            raise ValueError(f"{k}={cfg[k]} does not split into "
                             f"{cfg['nest_levels']} power-of-two levels")


# ---------------------------------------------------------------- weights
def key_for(seed: int):
    """A PRNG key from any non-negative integer seed."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make_params(cfg: dict, seed: int):
    """The served weights, on the default device, in ``cfg["dtype"]``:
    fan-in scaled truncated normals (the output projection further by
    ``1/sqrt(2 L)``), norm gains ``1 + U(-0.1, 0.1)``."""
    _check_nestable(cfg)
    return _param_fn(cfg["n_layers"], cfg["d_model"], head_total(cfg, "q"),
                     head_total(cfg, "kv"), cfg["d_ff"], cfg["vocab"],
                     cfg["dtype"])(key_for(seed))


@functools.lru_cache(maxsize=None)
def _param_fn(n_layers, d, hq, hkv, f, vocab, dtype):
    """The jitted weight maker for one shape."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def tn(k, shape, std):
        return (jax.random.truncated_normal(k, -3.0, 3.0, shape,
                                            jnp.float32) * std).astype(dt)

    def gain(k, shape):
        return (1.0 + jax.random.uniform(k, shape, jnp.float32, -0.1,
                                         0.1)).astype(dt)

    def make(key):
        k = jax.random.split(key, 12)
        lay = (n_layers,)
        return {
            "embed": tn(k[0], (vocab, d), 1.0),
            "final_norm": gain(k[1], (d,)),
            "unembed": tn(k[2], (d, vocab), d ** -0.5),
            "group": {"pos0": {
                "mixer": {
                    "wq": tn(k[3], lay + (d, hq), d ** -0.5),
                    "wk": tn(k[4], lay + (d, hkv), d ** -0.5),
                    "wv": tn(k[5], lay + (d, hkv), d ** -0.5),
                    "wo": tn(k[6], lay + (hq, d),
                             hq ** -0.5 / math.sqrt(2 * n_layers)),
                    "norm": gain(k[7], lay + (d,)),
                },
                "ffn": {
                    "w_gate": tn(k[8], lay + (d, f), d ** -0.5),
                    "w_up": tn(k[9], lay + (d, f), d ** -0.5),
                    "w_down": tn(k[10], lay + (f, d), f ** -0.5),
                    "norm": gain(k[11], lay + (d,)),
                },
            }},
        }

    return jax.jit(make)


# -------------------------------------------------------------- reference
def forward(params, cfg: dict, tokens, level: int, first: int, count: int,
            fp8: bool = False):
    """Logits ``[B, count, vocab]`` (float32) at positions ``first ..
    first + count - 1`` of a causal pass over ``tokens`` ``[B, T]`` at
    nesting ``level``: one jitted program per shape and level."""
    import jax.numpy as jnp

    _check_nestable(cfg)
    fn = _forward_fn(cfg["d_model"], cfg["n_heads"],
                     cfg["n_kv_heads"], cfg["head_dim"], cfg["d_ff"],
                     cfg["nest_levels"], float(cfg.get("rope_theta", 1e4)),
                     float(cfg.get("norm_eps", 1e-6)), int(level),
                     int(first), int(count), bool(fp8))
    return fn(params, jnp.asarray(tokens, jnp.int32))


@functools.lru_cache(maxsize=None)
def _forward_fn(d, n_heads, n_kv, hd, f, levels, theta, eps, level, first,
                count, fp8):
    """The jitted reference pass for one configuration and level."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST

    def q8(x):
        """Operand rounding of the control (identity otherwise)."""
        if not fp8:
            return x
        return x.astype(jnp.float8_e4m3fn).astype(f32)

    def mm(x, w):
        return jnp.matmul(q8(x), q8(w), precision=hi)

    def rms(x, g):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * g

    def norm_linear(x, g, w, out_total):
        """Output stripe i = RMSNorm of the level-i input prefix, times
        the level-i rows of its columns."""
        outs = []
        for i, (a, b) in enumerate(stripes(out_total, levels, level), 1):
            din = width(d, levels, i)
            outs.append(mm(rms(x[..., :din], g[:din]), w[:din, a:b]))
        return jnp.concatenate(outs, axis=-1)

    def linear(x, w, in_total, out_total):
        """Output stripe i reads the level-i prefix of the input."""
        outs = []
        for i, (a, b) in enumerate(stripes(out_total, levels, level), 1):
            din = width(in_total, levels, i)
            outs.append(mm(x[..., :din], w[:din, a:b]))
        return jnp.concatenate(outs, axis=-1)

    def rope(x, pos):
        """Rotary embedding, the two halves of each head rotated."""
        inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
        ang = pos[:, None].astype(f32) * jnp.asarray(inv, f32)[None]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1)

    def run(params, tokens):
        p = jax.tree.map(lambda a: a.astype(f32), params)
        b, t = tokens.shape
        dk = width(d, levels, level)
        nq = width(n_heads * hd, levels, level) // hd
        nkv = width(n_kv * hd, levels, level) // hd
        x = q8(p["embed"])[tokens][..., :dk]
        pos = jnp.arange(t)
        causal = pos[:, None] >= pos[None, :]

        def layer(x, lp):
            at, ff = lp["mixer"], lp["ffn"]
            q = norm_linear(x, at["norm"], at["wq"], n_heads * hd)
            k = norm_linear(x, at["norm"], at["wk"], n_kv * hd)
            v = norm_linear(x, at["norm"], at["wv"], n_kv * hd)
            q = rope(q.reshape(b, t, nq, hd), pos)
            k = rope(k.reshape(b, t, nkv, hd), pos)
            v = v.reshape(b, t, nkv, hd)
            rep = nq // nkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q8(q), q8(k),
                           precision=hi) * hd ** -0.5
            s = jnp.where(causal[None, None], s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", q8(w), q8(v), precision=hi)
            x = x + linear(o.reshape(b, t, nq * hd), at["wo"], n_heads * hd,
                           d)
            gate = norm_linear(x, ff["norm"], ff["w_gate"], f)
            up = norm_linear(x, ff["norm"], ff["w_up"], f)
            return x + linear(jax.nn.silu(gate) * up, ff["w_down"], f,
                              d), None

        x, _ = jax.lax.scan(layer, x, p["group"]["pos0"])
        h = rms(x[:, first:first + count], p["final_norm"][:dk])
        return mm(h, p["unembed"][:dk])

    return jax.jit(run)


def served_gaps(ref_logits, tokens) -> np.ndarray:
    """Per served token, how far its reference logit lies below the
    reference's best at that position."""
    ref = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens)
    got = np.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
    return ref.max(axis=-1) - got


# ------------------------------------------------------------------ FLOPs
def _nested_macs(in_total, out_total, levels, level) -> int:
    """Multiply-adds per row of a nested linear at ``level``."""
    return sum(width(in_total, levels, i) * (b - a) for i, (a, b) in
               enumerate(stripes(out_total, levels, level), 1))


def token_flops(cfg: dict, level: int) -> int:
    """FLOPs of one token through every layer's projections and MLP at
    ``level`` (attention scores and the LM head excluded)."""
    d, f, lv = cfg["d_model"], cfg["d_ff"], cfg["nest_levels"]
    hq, hkv = head_total(cfg, "q"), head_total(cfg, "kv")
    macs = (_nested_macs(d, hq, lv, level) + 2 * _nested_macs(d, hkv, lv,
                                                              level)
            + _nested_macs(hq, d, lv, level)
            + 2 * _nested_macs(d, f, lv, level)
            + _nested_macs(f, d, lv, level))
    return 2 * macs * cfg["n_layers"]


def attention_flops(cfg: dict, level: int, n_keys: int) -> int:
    """FLOPs of one query attending over ``n_keys`` keys in every layer
    (scores and the weighted sum of values)."""
    nq_ch = width(head_total(cfg, "q"), cfg["nest_levels"], level)
    return 4 * nq_ch * n_keys * cfg["n_layers"]


def head_flops(cfg: dict, level: int) -> int:
    """FLOPs of the LM head for one position at ``level``."""
    return 2 * width(cfg["d_model"], cfg["nest_levels"], level) * \
        cfg["vocab"]


def request_flops(cfg: dict, level: int, batch: int, prompt: int,
                  new_tokens: int) -> int:
    """FLOPs a request needs: the prompt through the network with causal
    attention and the head on its last position, then each further token
    through the network, attending over the cache, with its head."""
    per_tok = token_flops(cfg, level)
    total = prompt * per_tok + head_flops(cfg, level)
    total += sum(attention_flops(cfg, level, p + 1) for p in range(prompt))
    for s in range(max(new_tokens - 1, 0)):
        total += per_tok + head_flops(cfg, level) + \
            attention_flops(cfg, level, prompt + s + 1)
    return batch * total
