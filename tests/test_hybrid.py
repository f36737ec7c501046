"""The Jamba hybrid (AI21-Jamba2-Mini's reduced preset) against the plain
float32 reference, ``repro.models.reference``, on seeded random weights:

* ``ServeEngine``'s prefill, then decode through the cache, reproduce the
  reference's full forward at every position and step;
* the equations one by one: attention without positional encoding, the
  RMSNorms over dt, B and C, top-k gates without renormalisation;
* the dropless expert layer drops no pair when one expert takes every
  token, and the results of disjoint expert shares add up to the uncut
  layer;
* the counters of the held experts and the ``cache_setup`` span.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import reference as ref
from repro.models import transformer as tfm
from repro.models.registry import build_model
from repro.obs import FlightRecorder
from repro.serving.engine import ServeEngine

HIGHEST = jax.default_matmul_precision("highest")


@pytest.fixture(scope="module")
def hybrid():
    """The reduced preset in float32: 8 layers, d 64, 4 heads over 2 KV
    heads, 8 routed experts of which 4 are held, top-2, d_state 4, vocab
    256."""
    cfg = configs.get_reduced("AI21-Jamba2-Mini").replace(dtype="float32")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.n_experts, cfg.n_experts_here, cfg.top_k,
            cfg.mamba_d_state, cfg.vocab) == (8, 64, 4, 2, 8, 4, 2, 4, 256)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def test_registry_and_published_widths():
    """The published config is registered, with Jamba's equations."""
    cfg = configs.get_config("AI21-Jamba2-Mini")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab, cfg.n_experts, cfg.top_k,
            cfg.mamba_dt_rank_actual) == (32, 4096, 32, 8, 128, 14336,
                                          65536, 16, 2, 256)
    assert (cfg.pos_encoding, cfg.mamba_inner_norms,
            cfg.moe_renormalize) == ("none", True, False)
    assert cfg.layer_plan()[:8] == configs.get_config(
        "jamba-v0.1-52b").layer_plan()[:8]


def test_engine_prefill_then_decode_matches_reference(hybrid):
    """Prefill logits at every prompt position and the logits of every
    cached decode step equal the reference's full forward, and the
    engine's greedy tokens and routing are the reference's."""
    cfg, model, params = hybrid
    engine = ServeEngine(model, max_len=24, batch_size=2)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, (2, 12)).astype(np.int32)
    n_new = 8
    with HIGHEST:
        res = engine.generate(params, prompt, n_new)
        full = np.concatenate([prompt, res["tokens"][:, :-1]], axis=1)
        want, routes = ref.forward(params, cfg, full)
        want = np.asarray(want)
        out = engine.prefill(params, prompt)
        np.testing.assert_allclose(np.asarray(out.logits), want[:, :12],
                                   rtol=1e-4, atol=1e-4)
        caches = engine._merge(engine.cache_shapes(), out.caches)
        for i in range(n_new - 1):
            tok = jnp.asarray(full[:, 12 + i:13 + i])
            step = tfm.lm_apply(params, cfg, tok, mode="decode",
                                caches=caches,
                                cache_len=jnp.asarray(12 + i, jnp.int32))
            caches = step.caches
            np.testing.assert_allclose(np.asarray(step.logits[:, 0]),
                                       want[:, 12 + i], rtol=1e-4,
                                       atol=1e-4)
    np.testing.assert_array_equal(res["tokens"],
                                  want[:, 11:].argmax(-1))
    routes = np.asarray(routes).reshape(4, 2, full.shape[1], 2)
    np.testing.assert_array_equal(
        np.asarray(res["routing"]["prefill"]).reshape(4, 2, 12, 2),
        routes[:, :, :12])
    for i, r in enumerate(res["routing"]["decode"]):
        np.testing.assert_array_equal(np.asarray(r).reshape(4, 2, 2),
                                      routes[:, :, 12 + i])


def test_nope_attention_has_no_positions(hybrid):
    """With ``pos_encoding='none'`` the attention block equals the
    reference's, and a permutation of the earlier keys leaves the last
    query's output unchanged; RoPE breaks both."""
    cfg, _, _ = hybrid
    p = attn_mod.attn_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, cfg.d_model))
    pos = jnp.arange(6)[None]
    perm = jnp.asarray([2, 0, 4, 1, 3, 5])
    with HIGHEST:
        for enc in ("none", "rope"):
            c = cfg.replace(pos_encoding=enc)
            got, _ = attn_mod.attention(p, x, pos, c)
            want = ref.attention(p, ref.rms(x, p["norm"], c.norm_eps), c)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-5)
            shuffled, _ = attn_mod.attention(p, x[:, perm], pos, c)
            same = np.allclose(np.asarray(shuffled[:, -1]),
                               np.asarray(got[:, -1]), atol=1e-5)
            assert same == (enc == "none")


def test_mamba_inner_norms(hybrid):
    """The mixer RMS-normalises dt, B and C with gains of their own: it
    equals the reference with the norms, and the norms' gains move its
    output; without them the leaves are absent and it equals the
    reference without them."""
    cfg, _, _ = hybrid
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, cfg.d_model))
    for norms in (True, False):
        c = cfg.replace(mamba_inner_norms=norms)
        p = mamba_mod.mamba_init(jax.random.PRNGKey(4), c)
        assert {"dt_norm", "b_norm", "c_norm"} <= set(p) if norms \
            else not {"dt_norm", "b_norm", "c_norm"} & set(p)
        with HIGHEST:
            got, _ = mamba_mod.mamba(p, x, c)
            want = ref.mamba(p, ref.rms(x, p["norm"], c.norm_eps), c)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-5)
            if norms:
                p2 = dict(p, b_norm=p["b_norm"] * 2.0)
                moved, _ = mamba_mod.mamba(p2, x, c)
                assert not np.allclose(np.asarray(moved), np.asarray(got))
    dt, ds = cfg.mamba_dt_rank_actual, cfg.mamba_d_state
    assert cfg.param_count() - cfg.replace(
        mamba_inner_norms=False).param_count() == 7 * (dt + 2 * ds)


@pytest.mark.parametrize("renormalize", [True, False])
def test_topk_gates(renormalize):
    """Top-k gates are the softmax values over all experts, rescaled to
    sum to 1 only when asked."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (16, 8))
    vals, idx, probs = moe_mod.route_topk(logits, 2, renormalize)
    raw = np.take_along_axis(np.asarray(probs), np.asarray(idx), -1)
    sums = np.asarray(vals).sum(-1)
    if renormalize:
        np.testing.assert_allclose(sums, 1.0, rtol=1e-6)
    else:
        np.testing.assert_allclose(np.asarray(vals), raw, rtol=1e-6)
        assert (sums < 1.0).all()


def _moe_layer(cfg, key=6):
    """An uncut (all 8 held) expert layer and a normalised input."""
    full = cfg.replace(experts_held=0)
    p = moe_mod.moe_init(jax.random.PRNGKey(key), full)
    x = jax.random.normal(jax.random.PRNGKey(key + 1), (2, 16, cfg.d_model))
    return full, p, x


def test_dropless_keeps_every_pair_when_one_expert_takes_all(hybrid):
    """Every token routed to the same two experts: the dropless layer
    keeps all 2 x 32 pairs and equals the reference, where the capacity
    dispatch drops most of them."""
    cfg, _, _ = hybrid
    full, p, x = _moe_layer(cfg)
    router = jnp.zeros_like(p["router"]).at[:, 3].set(1.0).at[:, 5].set(0.5)
    p = dict(p, router=router)
    x = jnp.abs(x) + 0.1                       # positive: 3 then 5 win
    with HIGHEST:
        got, _, idx = moe_mod.moe_dropless(p, x, full)
        want, ridx = ref.moe(p, ref.rms(x, p["norm"], full.norm_eps), full)
        capped, _ = moe_mod.moe(p, x, full.replace(moe_dispatch="onehot",
                                                   capacity_factor=1.0))
    assert set(np.unique(np.asarray(idx))) == {3, 5}
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert not np.allclose(np.asarray(capped), np.asarray(got), atol=1e-3)


def test_dropless_ignores_rows_past_the_held_pairs(hybrid, monkeypatch):
    """The grouped product may leave the rows past its groups undefined
    (the TPU's does): NaN there changes nothing in the layer's output."""
    cfg, _, _ = hybrid
    full, p, x = _moe_layer(cfg, key=10)
    c = full.replace(experts_held=4)
    share = dict(p, **{k: p[k][:4] for k in ("w_gate", "w_up", "w_down")})
    ragged = jax.lax.ragged_dot

    def nan_tail(lhs, rhs, sizes, **kw):
        out = ragged(lhs, rhs, sizes, **kw)
        tail = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(tail[:, None], jnp.nan, out)

    with HIGHEST:
        want, _, _ = moe_mod.moe_dropless(share, x, c)
        monkeypatch.setattr(jax.lax, "ragged_dot", nan_tail)
        got, _, _ = moe_mod.moe_dropless(share, x, c)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_expert_shares_add_up_to_the_uncut_layer(hybrid):
    """Two chips holding experts 0-3 and 4-7: their partial outputs, each
    computed by the dropless layer over the same router, add up to the
    uncut reference layer."""
    cfg, _, _ = hybrid
    full, p, x = _moe_layer(cfg, key=8)
    parts = []
    with HIGHEST:
        for off in (0, 4):
            c = full.replace(experts_held=4, experts_offset=off)
            share = dict(p, **{k: p[k][off:off + 4]
                               for k in ("w_gate", "w_up", "w_down")})
            out, _, _ = moe_mod.moe_dropless(share, x, c)
            parts.append(np.asarray(out))
        want, _ = ref.moe(p, ref.rms(x, p["norm"], full.norm_eps), full)
    assert not np.allclose(parts[0], 0.0) and not np.allclose(parts[1], 0.0)
    np.testing.assert_allclose(parts[0] + parts[1], np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_counters_and_cache_setup_span(hybrid):
    """``cache_setup`` nests in ``first_token``; ``moe_assigned_here``
    counts the pairs routed to held experts over prefill and decode and
    ``moe_experts_hit`` the held experts each decode step reached, as the
    routing the request returns says."""
    cfg, model, params = hybrid
    engine = ServeEngine(model, max_len=20, batch_size=2)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab, (2, 10))
    obs = FlightRecorder()
    res = engine.generate(params, prompt.astype(np.int32), 5, obs=obs)
    ev = obs.spans.events
    by_id = {e["id"]: e for e in ev}
    setups = [e for e in ev if e["name"] == "cache_setup"]
    assert len(setups) == 1 and setups[0]["cat"] == "engine"
    assert by_id[setups[0]["parent"]]["name"] == "first_token"
    routing = res["routing"]
    held = lambda r: (np.asarray(r) < cfg.n_experts_here).sum()
    assigned = held(routing["prefill"]) + sum(held(r)
                                              for r in routing["decode"])
    hit = sum(len(set(np.asarray(r)[li].ravel()) & set(range(4)))
              for r in routing["decode"] for li in range(4))
    counters = {m["name"]: m["value"] for m in obs.metrics.snapshot()}
    assert counters["moe_assigned_here"] == assigned == \
        res["moe_assigned_here"]
    assert counters["moe_experts_hit"] == hit == res["moe_experts_hit"]
    assert 0 < hit <= 4 * 4 * 4
    first = np.asarray(routing["decode"][0])
    none = np.full_like(first, -1)
    st = moe_mod.route_stats(cfg, routing["prefill"], (first, none))
    assert int(st[0]) == held(routing["prefill"]) + held(first)
    assert int(st[1]) == sum(len(set(first[li].ravel()) & set(range(4)))
                             for li in range(4))


def test_dense_engine_keeps_its_programs(hybrid):
    """A model without the dropless layer returns no routing and no
    expert counters."""
    from repro.configs.base import ModelConfig

    cfg = ModelConfig(name="d", family="dense", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, head_dim=8, d_ff=32,
                      vocab=32, dtype="float32")
    model = build_model(cfg)
    engine = ServeEngine(model, max_len=8, batch_size=1)
    assert not engine.routed
    res = engine.generate(model.init(jax.random.PRNGKey(0)),
                          np.zeros((1, 3), np.int32), 3)
    assert "routing" not in res and res["tokens"].shape == (1, 3)
    assert "moe_experts_hit" not in res


@pytest.mark.parametrize("steps_run", [0, 2, 4])
def test_counters_of_a_request_cut_by_its_deadline(hybrid, steps_run):
    """A deadline that stops the loop after ``steps_run`` decode steps:
    the counters count the steps that ran, and every cut reuses the one
    counting program."""
    cfg, model, params = hybrid
    engine = ServeEngine(model, max_len=20, batch_size=2)
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab, (2, 10)).astype(np.int32)
    engine.generate(params, prompt, 5)
    reads = iter(range(100))
    # Read 0 is the entry, read 1 + i the check before step i.
    res = engine.generate(params, prompt, 5, deadline_s=0.5,
                          clock=lambda: float(next(reads) > steps_run))
    steps = res["routing"]["decode"]
    assert len(steps) == steps_run == res["tokens"].shape[1] - 1
    held = lambda r: ((np.asarray(r) >= 0)
                      & (np.asarray(r) < cfg.n_experts_here)).sum()
    assert res["moe_assigned_here"] == held(res["routing"]["prefill"]) + \
        sum(held(r) for r in steps)
    assert res["moe_experts_hit"] == sum(
        len(set(np.asarray(r)[li].ravel()) & set(range(4)))
        for r in steps for li in range(4))
    assert engine._route_stats._cache_size() == 1
