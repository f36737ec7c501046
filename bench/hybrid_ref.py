"""The Jamba hybrid (Mamba-1 + NoPE GQA + MoE): weights, plain reference,
FLOP and byte counts.

* :func:`model_config` — the configuration file (``config.json`` keys)
  as the program's ``ModelConfig``, with the experts held here.
* :func:`make_params` — the served weights, drawn from the seed on the
  device in one jitted call, in the served dtype and in the layout the
  program's model reads; every large leaf is drawn in slices of at most
  32 MB straight into place, in its own dtype.
* :class:`Reference` — the plain reference: a causal forward pass in
  float32 at the highest matmul precision, written from ``transformers``'
  ``modeling_jamba`` (pre-norm blocks; attention with no positional
  encoding; the Mamba mixer with RMSNorms over dt, B and C and the
  textbook recurrence one token at a time; a softmax router over all
  experts whose top-k gates are not renormalised; SwiGLU experts).  It
  runs in blocks so that it fits beside the served weights: one layer at
  a time, each weight upcast to float32 only inside its block, one
  expert at a time over the tokens routed to it, the feed-forwards in
  column blocks.  The one departure from ``modeling_jamba``: only the
  experts held here add to an expert layer, as in the program.  With
  ``fp8=True`` every matmul operand is rounded to float8 (e4m3): the
  control.
* :func:`request_flops`, :func:`decode_step_bytes` — the operations a request
  needs and the least bytes a decode step must move.
"""

from __future__ import annotations

import functools

import numpy as np

from bench.lm_ref import key_for, served_gaps

SLICE_BYTES = 32 << 20      # largest piece of a leaf drawn at once
FF_BLOCK = 2048             # feed-forward columns per reference block
VOCAB_BLOCK = 8192          # LM-head columns per reference block


# ------------------------------------------------------------ configuration
def model_config(cfg: dict):
    """The configuration file as the program's ``ModelConfig``: the
    published widths, ``num_experts`` of them held here from
    ``experts_offset``, the router ``router_width`` wide, Jamba's
    equations, the dropless expert layer and last-position prefill."""
    from repro.configs.base import ModelConfig

    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="hybrid", n_layers=cfg["num_hidden_layers"],
        d_model=d, n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["assumed"]["head_dim"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], n_experts=cfg["router_width"],
        experts_held=cfg["num_experts"],
        experts_offset=cfg["experts_offset"],
        top_k=cfg["num_experts_per_tok"],
        moe_every=cfg["expert_layer_period"],
        moe_offset=cfg["expert_layer_offset"],
        attn_every=cfg["attn_layer_period"],
        attn_offset=cfg["attn_layer_offset"],
        mamba_d_state=cfg["mamba_d_state"], mamba_expand=cfg["mamba_expand"],
        mamba_d_conv=cfg["mamba_d_conv"], mamba_dt_rank=cfg["mamba_dt_rank"],
        mamba_inner_norms=True, pos_encoding="none", moe_renormalize=False,
        moe_dispatch="dropless", norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"],
        prefill_last_only=True)


# ------------------------------------------------------------------ weights
def make_params(mcfg, seed: int):
    """The served weights on the default device: fan-in scaled truncated
    normals (the residual outputs ``wo``, ``out_proj`` and ``w_down``
    further by ``1/sqrt(2 L)``), norm gains ``1 + U(-0.1, 0.1)``, Mamba's
    S4D-real ``A``, a ``dt`` bias that puts softplus(dt) log-uniformly in
    [0.001, 0.1], and small conv biases."""
    return _param_fn(mcfg)(key_for(seed))


def _leaf_name(path) -> str:
    """The last key of a tree path."""
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


@functools.lru_cache(maxsize=None)
def _param_fn(mcfg):
    """The jitted weight maker for one configuration."""
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import init_lm

    shapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), mcfg))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    resid = (2 * mcfg.n_layers) ** -0.5

    def piece(k, name, shape, dt, std):
        """One slice of a leaf, drawn in its own dtype."""
        if name.endswith("norm"):
            return (1.0 + jax.random.uniform(k, shape, dt, -0.1, 0.1)
                    ).astype(dt)
        if name == "conv_b":
            return jax.random.uniform(k, shape, dt, -0.1, 0.1)
        if name == "dt_bias":
            lo, hi = np.log(1e-3), np.log(1e-1)
            v = jnp.exp(jax.random.uniform(k, shape, dt, lo, hi))
            return v + jnp.log(-jnp.expm1(-v))     # softplus^-1
        return jax.random.truncated_normal(k, -3.0, 3.0, shape, dt) * \
            jnp.asarray(std, dt)

    def leaf(k, name, s):
        """A whole leaf, drawn in slices along its leading rows."""
        shape, dt = s.shape, s.dtype
        if name == "a_log":
            a = jnp.arange(1, shape[-1] + 1, dtype=dt)
            return jnp.broadcast_to(jnp.log(a), shape)
        if name == "d_skip":
            return jnp.ones(shape, dt)
        fan_in = shape[-2] if len(shape) >= 2 and name != "embed" else 1
        std = fan_in ** -0.5 * (resid if name in ("wo", "out_proj",
                                                   "w_down") else 1.0)
        rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        row_bytes = s.size // rows * dt.itemsize
        n = next(n for n in range(1, rows + 1)
                 if rows % n == 0 and rows // n * row_bytes <= SLICE_BYTES
                 or n == rows)
        if n == 1:
            return piece(k, name, shape, dt, std)
        sub = (rows // n, s.size // rows)
        out = jax.lax.map(lambda kk: piece(kk, name, sub, dt, std),
                          jax.random.split(k, n))
        return out.reshape(shape)

    def make(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            tree, [leaf(keys[i], _leaf_name(p), s)
                   for i, (p, s) in enumerate(flat)])

    return jax.jit(make)


# -------------------------------------------------------------- reference
class Reference:
    """The float32 reference pass, block by block on the device.

    ``forward`` runs the prompt plus the served tokens through the whole
    network and returns the float32 logits at the compared positions,
    with the routing comparison made layer by layer on the way: at each
    (expert layer, token) the program processed, the reference's own
    top-k choice is held to the program's.  Where the program swapped
    experts within ``margin`` (the rounding band of the program's bfloat16
    activations) of the reference's probabilities, as :func:`swap_gap`
    measures it, the reference takes the program's choice and counts it
    ``followed``; any other difference counts as a ``mismatch`` and the
    reference keeps its own."""

    def __init__(self, mcfg, margin: float, fp8: bool = False):
        self.cfg = mcfg
        self.margin = margin
        self.p = mcfg.layer_period()
        self.r = mcfg.n_layers // self.p
        self.fns = _ref_fns(mcfg, fp8)

    def layer(self, params, i: int):
        """Layer ``i``'s parameter tree and its index in the stack (None
        for a remainder layer, whose tree holds that one layer unstacked:
        adding a leading axis would copy it)."""
        if i < self.r * self.p:
            return params["group"][f"pos{i % self.p}"], i // self.p
        return params[f"rem{i - self.r * self.p}"], None

    def forward(self, params, tokens, first: int, count: int,
                prog_routing=None, seen: list | None = None):
        """Logits [B, count, vocab] at positions ``first ..`` of a pass
        over ``tokens`` [B, T], and ``(compared, followed, mismatches)``
        of the routing against ``prog_routing`` [n_moe, B, T, k] (-1
        where the program processed no token).  Each expert layer's
        choices [B*T, k] are appended to ``seen`` where given."""
        import jax.numpy as jnp

        f, cfg = self.fns, self.cfg
        tokens = jnp.asarray(tokens, jnp.int32)
        b, t = tokens.shape
        x = f["embed"](params["embed"], tokens)
        stats = np.zeros(3, np.int64)
        n_moe = 0
        for i, (mixer, ffn) in enumerate(cfg.layer_plan()):
            lp, rep = self.layer(params, i)
            x = f[mixer](lp["mixer"], rep, x)
            if ffn == "dense":
                x = f["dense"](lp["ffn"], rep, x)
                continue
            h, probs = f["route"](lp["ffn"], rep, x)
            prog = None if prog_routing is None else \
                np.asarray(prog_routing[n_moe]).reshape(b * t, -1)
            idx, gates, st = self.choose(np.asarray(probs), prog)
            stats += st
            if seen is not None:
                seen.append(idx)
            out = jnp.zeros((b * t, cfg.d_model), jnp.float32)
            for j in range(cfg.n_experts_here):
                rows, g = np.nonzero(idx == cfg.experts_offset + j)
                if rows.size == 0:
                    continue
                n = _bucket(rows.size)
                pad_rows = np.zeros(n, np.int32)
                pad_rows[:rows.size] = rows
                pad_g = np.zeros(n, np.float32)
                pad_g[:rows.size] = gates[rows, g]
                out = f["expert"](lp["ffn"], rep, j, h, out, pad_rows, pad_g)
            x = x + out.reshape(b, t, -1)
            n_moe += 1
        logits = f["head"](params["final_norm"], params["unembed"],
                           x[:, first:first + count])
        return logits, tuple(int(v) for v in stats)

    def choose(self, probs, prog):
        """Expert ids and gates [T, k] and (compared, followed, mismatch)
        counts: the reference's own top k, or the program's where a swap
        within the margin explains the difference."""
        k = self.cfg.top_k
        own = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
        if prog is None:
            gates = np.take_along_axis(probs, own, -1)
            return own, gates, np.zeros(3, np.int64)
        done = prog[:, 0] >= 0
        gap = swap_gap(probs, own, prog)
        differ = done & (gap > -np.inf)
        follow = differ & (gap < self.margin)
        idx = np.where(follow[:, None], prog, own)
        gates = np.take_along_axis(probs, idx, -1)
        stats = np.array([done.sum(), follow.sum(), (differ & ~follow).sum()])
        return idx, gates, stats


def swap_gap(probs, own, prog):
    """Per row of router probabilities ``probs`` [T, E], how far the
    program's top-k choice ``prog`` [T, k] lies from the reference's
    ``own``: the largest probability of an expert it left out of ``own``
    less the smallest of an expert it took in its place, or -inf where
    the two choices are the same set.  A swap of near-equal experts (two
    or more of them tied around the k-th) reads below the rounding band
    however far down the ranking the expert taken in lies."""
    p_own = np.take_along_axis(probs, own, -1)
    p_prog = np.take_along_axis(probs, np.maximum(prog, 0), -1)
    left = ~(own[:, :, None] == prog[:, None, :]).any(-1)
    taken = ~(prog[:, :, None] == own[:, None, :]).any(-1)
    return np.max(np.where(left, p_own, -np.inf), -1) - \
        np.min(np.where(taken, p_prog, np.inf), -1)


def _bucket(n: int) -> int:
    """Rows of an expert block: the next power of two from 256, so few
    shapes compile."""
    return max(256, 1 << (int(n) - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _ref_fns(mcfg, fp8: bool):
    """The reference's jitted blocks for one configuration."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    eps = mcfg.norm_eps
    d, di = mcfg.d_model, mcfg.mamba_d_inner
    ds, dtr = mcfg.mamba_d_state, mcfg.mamba_dt_rank_actual
    nh, nkv, hd = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim

    def q8(a):
        """Operand rounding of the control (identity otherwise)."""
        return a.astype(jnp.float8_e4m3fn).astype(f32) if fp8 else a

    def up(a):
        """A served weight (block) in float32."""
        return a.astype(f32)

    def at(a, rep):
        """Layer ``rep`` of a stacked leaf, or a remainder layer's own
        leaf where ``rep`` is None."""
        return a if rep is None else a[rep]

    def lead(rep, *rest):
        """The leading indices of a matrix in a (stacked) leaf."""
        return rest if rep is None else (rep,) + rest

    def mm(a, w):
        return jnp.matmul(q8(a), q8(up(w)), precision=hi)

    def rms(a, g):
        return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps) \
            * up(g)

    def embed(table, tokens):
        return up(table[tokens])

    def attn(p, rep, x):
        """x + causal GQA attention without positional encoding."""
        b, t, _ = x.shape
        h = rms(x, at(p["norm"], rep))
        q = mm(h, at(p["wq"], rep)).reshape(b, t, nh, hd)
        k = mm(h, at(p["wk"], rep)).reshape(b, t, nkv, hd)
        v = mm(h, at(p["wv"], rep)).reshape(b, t, nkv, hd)
        causal = jnp.tril(jnp.ones((t, t), bool))

        def one(args):
            qb, kb, vb = args
            kb = jnp.repeat(kb, nh // nkv, axis=1)
            vb = jnp.repeat(vb, nh // nkv, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q8(qb), q8(kb),
                           precision=hi) / np.sqrt(hd)
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", q8(w), q8(vb), precision=hi)

        o = jax.lax.map(one, (q, k, v)).reshape(b, t, nh * hd)
        return x + mm(o, at(p["wo"], rep))

    def mamba(p, rep, x):
        """x + the Mamba-1 mixer, the recurrence one token at a time."""
        t = x.shape[1]
        h = rms(x, at(p["norm"], rep))
        w_in = at(p["in_proj"], rep)
        xin, z = mm(h, w_in[:, :di]), mm(h, w_in[:, di:])
        w = up(at(p["conv_w"], rep))
        dc = w.shape[0]
        xp = jnp.pad(xin, ((0, 0), (dc - 1, 0), (0, 0)))
        xc = jax.nn.silu(sum(xp[:, i:i + t] * w[i] for i in range(dc))
                         + up(at(p["conv_b"], rep)))
        proj = mm(xc, at(p["x_proj"], rep))
        dt = rms(proj[..., :dtr], at(p["dt_norm"], rep))
        bm = rms(proj[..., dtr:dtr + ds], at(p["b_norm"], rep))
        cm = rms(proj[..., dtr + ds:], at(p["c_norm"], rep))
        delta = jax.nn.softplus(mm(dt, at(p["dt_proj"], rep))
                                + up(at(p["dt_bias"], rep)))
        a = -jnp.exp(up(at(p["a_log"], rep)))

        def step(s, inp):
            d_t, b_t, c_t, x_t = inp
            s = jnp.exp(d_t[..., None] * a) * s + \
                d_t[..., None] * b_t[:, None, :] * x_t[..., None]
            return s, jnp.einsum("bis,bs->bi", s, c_t, precision=hi)

        s0 = jnp.zeros((x.shape[0], di, ds), f32)
        seq = tuple(jnp.swapaxes(v, 0, 1) for v in (delta, bm, cm, xc))
        _, ys = jax.lax.scan(step, s0, seq)
        y = (jnp.swapaxes(ys, 0, 1) + xc * up(at(p["d_skip"], rep))) \
            * jax.nn.silu(z)
        return x + mm(y, at(p["out_proj"], rep))

    def block(w, lead, a, n, axis):
        """``n`` columns (``axis`` -1) or rows (-2) of the matrix at index
        ``lead`` of the stacked ``w``, from ``a``: one slice, no copy of
        the rest of the stack."""
        starts = list(lead) + [0, 0]
        sizes = [1] * len(lead) + list(w.shape[-2:])
        starts[axis], sizes[axis] = a, n
        return jax.lax.dynamic_slice(w, starts, sizes).reshape(sizes[-2:])

    def ffn(h, p, lead):
        """SwiGLU over column blocks of the feed-forward width."""
        out = 0.0
        for a in range(0, p["w_gate"].shape[-1], FF_BLOCK):
            n = min(FF_BLOCK, p["w_gate"].shape[-1] - a)
            g = jax.nn.silu(mm(h, block(p["w_gate"], lead, a, n, -1))) * \
                mm(h, block(p["w_up"], lead, a, n, -1))
            out = out + mm(g, block(p["w_down"], lead, a, n, -2))
        return out

    def dense(p, rep, x):
        h = rms(x, at(p["norm"], rep))
        return x + ffn(h, p, lead(rep))

    def route(p, rep, x):
        """The normalised rows [B*T, d] and the router's probabilities."""
        h = rms(x, at(p["norm"], rep)).reshape(-1, d)
        return h, jax.nn.softmax(mm(h, at(p["router"], rep)), axis=-1)

    def expert(p, rep, j, h, out, rows, gates):
        """``out`` plus expert ``j``'s gated output on ``rows`` of h."""
        y = ffn(h[rows], p, lead(rep, j))
        return out.at[rows].add(y * gates[:, None])

    def head(g, unembed, x):
        h = rms(x, g)
        return jnp.concatenate(
            [mm(h, unembed[:, a:a + VOCAB_BLOCK])
             for a in range(0, unembed.shape[1], VOCAB_BLOCK)], axis=-1)

    return {"embed": jax.jit(embed), "attn": jax.jit(attn),
            "mamba": jax.jit(mamba), "dense": jax.jit(dense),
            "route": jax.jit(route),
            "expert": jax.jit(expert, donate_argnums=(4,)),
            "head": jax.jit(head)}


def program_routing(res: dict, batch: int, total: int, k: int):
    """A request's expert choices as the program made them, [n_moe, B,
    total, k] with -1 where it processed no token: the prefill's, then
    each decode step's at its position."""
    pre = np.asarray(res["prefill"])
    n_moe, s0 = pre.shape[0], pre.shape[1] // batch
    out = np.full((n_moe, batch, total, k), -1, np.int64)
    out[:, :, :s0] = pre.reshape(n_moe, batch, s0, k)
    for i, r in enumerate(res["decode"]):
        out[:, :, s0 + i] = np.asarray(r).reshape(n_moe, batch, k)
    return out


def check(params, mcfg, sample: list, gen_tokens: int, margin: float,
          fp8: bool = False) -> dict:
    """The model comparison on the sampled requests.

    Per request, one reference pass over the prompt and ``gen_tokens -
    1`` tokens (a request cut short is padded after its last token, which
    a causal pass never reads).  ``logit_gap``: the widest gap by which a
    served token lies below the reference's best at its position;
    ``routing_mismatches``: routing differences no swap within the margin
    explains;
    ``routing_followed``: the share of compared (layer, token) choices
    taken from the program within the margin.  With ``fp8`` a float8
    pass plays the program: its top tokens and its routing are held to
    the float32 reference the same way."""
    ref = Reference(mcfg, margin)
    low = Reference(mcfg, margin, fp8=True) if fp8 else None
    gaps, stats = [], np.zeros(3, np.int64)
    for r in sample:
        prompt, toks = np.asarray(r["prompt"]), np.asarray(r["tokens"])
        b, s0 = prompt.shape
        n = toks.shape[1]
        full = np.zeros((b, s0 + gen_tokens - 1), np.int32)
        full[:, :s0] = prompt
        full[:, s0:s0 + n - 1] = toks[:, :-1]
        first = s0 - 1
        prog = program_routing(r["routing"], b, full.shape[1], mcfg.top_k)
        prog[:, :, s0 + n - 1:] = -1
        if low is not None:
            seen = []
            lg, _ = low.forward(params, full, first, gen_tokens, seen=seen)
            toks = np.asarray(lg)[:, :n].argmax(-1)
            prog = np.stack(seen).reshape(prog.shape).astype(np.int64)
            prog[:, :, s0 + n - 1:] = -1
        want, st = ref.forward(params, full, first, gen_tokens, prog)
        gaps.append(served_gaps(np.asarray(want)[:, :n], toks))
        stats += st
    compared = max(int(stats[0]), 1)
    return {"routing_mismatches": float(stats[2]),
            "routing_followed": float(stats[1]) / compared,
            "logit_gap": float(max(g.max() for g in gaps)) if gaps
            else float("inf")}


# ------------------------------------------------------- FLOPs and bytes
def _layer_macs(mcfg) -> dict:
    """Multiply-adds per token of each layer kind's products (experts:
    per routed pair)."""
    d, f = mcfg.d_model, mcfg.d_ff
    di, ds, dtr = mcfg.mamba_d_inner, mcfg.mamba_d_state, \
        mcfg.mamba_dt_rank_actual
    hq, hkv = mcfg.n_heads * mcfg.head_dim, mcfg.n_kv_heads * mcfg.head_dim
    return {"attn": d * (hq + 2 * hkv) + hq * d,
            "mamba": d * 2 * di + mcfg.mamba_d_conv * di
            + di * (dtr + 2 * ds) + dtr * di + 3 * di * ds + di * d,
            "dense": 3 * d * f, "expert": 3 * d * f,
            "router": d * mcfg.n_experts}


def request_flops(mcfg, batch: int, prompt: int, new_tokens: int,
                  assigned: int) -> int:
    """FLOPs a request needs: every token through the mixers, the dense
    feed-forwards and the routers, the ``assigned`` (token, slot) pairs
    routed to the held experts through their SwiGLU, causal attention
    over the prefix, and the LM head once per kept token."""
    macs = _layer_macs(mcfg)
    plan = mcfg.layer_plan()
    per_tok = sum(macs[m] + (macs["dense"] if f == "dense"
                             else macs["router"]) for m, f in plan)
    tokens = batch * (prompt + max(new_tokens - 1, 0))
    n_attn = sum(m == "attn" for m, _ in plan)
    q_ch = mcfg.n_heads * mcfg.head_dim
    keys = batch * (prompt * (prompt + 1) // 2 +
                    sum(prompt + s + 1 for s in range(new_tokens - 1)))
    total = per_tok * tokens + assigned * macs["expert"] \
        + 2 * q_ch * keys * n_attn \
        + batch * new_tokens * mcfg.d_model * mcfg.vocab
    return 2 * total


def decode_step_bytes(mcfg, batch: int) -> dict:
    """The least bytes a decode step moves, in parts: ``fixed`` — every
    weight outside the expert FFNs once (of the embedding only the batch's
    rows), and each Mamba layer's SSM state and conv tail read and
    written; ``per_position`` — the KV read per cached position;
    ``per_expert`` — one held expert's three matrices."""
    import jax

    from repro.models.transformer import init_lm

    shapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), mcfg))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    weights = 0
    for path, s in flat:
        name = _leaf_name(path)
        if name in ("w_gate", "w_up", "w_down") and _is_moe(mcfg, path):
            continue
        size = s.size * s.dtype.itemsize
        if name == "embed":
            size = batch * mcfg.d_model * s.dtype.itemsize
        weights += size
    plan = mcfg.layer_plan()
    n_mamba = sum(m == "mamba" for m, _ in plan)
    n_attn = sum(m == "attn" for m, _ in plan)
    act = np.dtype(mcfg.dtype).itemsize
    di = mcfg.mamba_d_inner
    state = batch * (di * mcfg.mamba_d_state * 4
                     + (mcfg.mamba_d_conv - 1) * di * act)
    kv = 2 * batch * mcfg.n_kv_heads * mcfg.head_dim * act
    return {"fixed": weights + 2 * n_mamba * state,
            "per_position": n_attn * kv,
            "per_expert": 3 * mcfg.d_model * mcfg.d_ff * act}


def _is_moe(mcfg, path) -> bool:
    """Whether a parameter path lies in an expert layer."""
    keys = [str(getattr(k, "key", k)) for k in path]
    p = mcfg.layer_period()
    r = mcfg.n_layers // p
    if keys[0] == "group":
        pos = int(keys[1][3:])
        return mcfg.ffn_kind(pos) == "moe"
    return mcfg.ffn_kind(r * p + int(keys[0][3:])) == "moe"
