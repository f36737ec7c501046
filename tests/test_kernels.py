"""Per-kernel interpret-mode validation: sweep shapes/dtypes, allclose vs
the pure-jnp oracle in ref.py — plus the fused `alert_select` decision
kernel, held to the float64 XLA engine under the margin contract: equal
picks on every lane that clears the tie margins, predictions within a
stated tolerance (docs/KERNELS.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched import BatchedAlertEngine
from repro.core.nesting import StripeSpec
from repro.core.precision import x64_scope
from repro.kernels import ref
from repro.kernels.alert_select import (alert_select, alert_select_cost,
                                        clear_lanes, margin_report)
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.nested_matmul import nested_matmul, nested_matmul_flops
from repro.kernels.rwkv_scan import rwkv_scan

KEY = jax.random.PRNGKey(42)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestNestedMatmul:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("m,kin,n,levels,bm,bn,bk", [
        (32, 64, 64, 3, 16, 16, 16),
        (64, 128, 256, 4, 32, 32, 16),
        (16, 32, 32, 1, 16, 16, 16),   # degenerate: plain matmul
        (128, 64, 64, 2, 64, 32, 32),
    ])
    def test_matches_ref(self, dtype, m, kin, n, levels, bm, bn, bk):
        si, so = StripeSpec.pow2(kin, levels), StripeSpec.pow2(n, levels)
        x = rand(KEY, (m, kin), dtype)
        w = rand(jax.random.PRNGKey(1), (kin, n), dtype)
        got = nested_matmul(x, w, si, so, bm=bm, bn=bn, bk=bk,
                            interpret=True)
        want = ref.nested_matmul_ref(x, w, si, so)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **tol(dtype))

    @pytest.mark.parametrize("level", [1, 2, 3])
    def test_partial_level_matches_prefix(self, level):
        si, so = StripeSpec.pow2(64, 3), StripeSpec.pow2(64, 3)
        x = rand(KEY, (32, 64), jnp.float32)
        w = rand(jax.random.PRNGKey(2), (64, 64), jnp.float32)
        full = nested_matmul(x, w, si, so, bm=16, bn=16, bk=16,
                             interpret=True)
        part = nested_matmul(x, w, si, so, level=level, bm=16, bn=16,
                             bk=16, interpret=True)
        np.testing.assert_allclose(part, full[:, :so.width(level)],
                                   rtol=2e-5, atol=2e-5)

    def test_flops_accounting_triangular(self):
        si = so = StripeSpec.uniform(64, 4)
        tri = nested_matmul_flops(32, si, so)
        dense = 2 * 32 * 64 * 64
        assert tri / dense == pytest.approx(10 / 16)

    def test_indivisible_boundary_raises(self):
        si, so = StripeSpec.pow2(64, 3), StripeSpec.pow2(64, 3)
        x = rand(KEY, (32, 64), jnp.float32)
        w = rand(KEY, (64, 64), jnp.float32)
        with pytest.raises(ValueError):
            nested_matmul(x, w, si, so, bm=32, bn=32, bk=32,
                          interpret=True)  # stripe width 16 < bk 32


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,s,t,h,kv,hd,causal,window", [
        (2, 64, 64, 4, 4, 32, True, None),
        (1, 128, 128, 8, 2, 16, True, None),     # GQA 4:1
        (2, 64, 64, 4, 1, 32, True, None),       # MQA
        (1, 64, 64, 2, 2, 32, False, None),      # bidirectional (encoder)
        (1, 128, 128, 4, 4, 32, True, 32),       # sliding window
    ])
    def test_matches_ref(self, dtype, b, s, t, h, kv, hd, causal, window):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (b, s, h, hd), dtype)
        k = rand(ks[1], (b, t, kv, hd), dtype)
        v = rand(ks[2], (b, t, kv, hd), dtype)
        got = flash_attention(q, k, v, causal=causal, window=window,
                              bq=32, bk=32, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **tol(dtype))

    def test_softcap(self):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 64, 2, 32), jnp.float32)
        k = rand(ks[1], (1, 64, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 64, 2, 32), jnp.float32)
        got = flash_attention(q, k, v, softcap=20.0, bq=32, bk=32,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, softcap=20.0)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_block_shape_sweep(self):
        """Different tilings must agree bit-for-bit-ish (streaming softmax
        is tiling-dependent only at float rounding level)."""
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 128, 2, 32), jnp.float32)
        k = rand(ks[1], (1, 128, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 128, 2, 32), jnp.float32)
        outs = [flash_attention(q, k, v, bq=bq, bk=bk, interpret=True)
                for bq, bk in [(32, 32), (64, 32), (32, 64), (128, 128)]]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,s,h,kv,hd,lens", [
        (2, 256, 4, 4, 32, (256, 100)),
        (1, 512, 8, 2, 16, (300,)),
        (2, 128, 4, 1, 32, (64, 128)),
        (1, 256, 4, 4, 64, (1,)),        # fresh cache
    ])
    def test_matches_ref(self, dtype, b, s, h, kv, hd, lens):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (b, h, hd), dtype)
        k = rand(ks[1], (b, s, kv, hd), dtype)
        v = rand(ks[2], (b, s, kv, hd), dtype)
        cl = jnp.asarray(lens, jnp.int32)
        got = decode_attention(q, k, v, cl, bk=64, interpret=True)
        want = ref.decode_attention_ref(q, k, v, cl)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **tol(dtype))

    def test_window(self):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 4, 32), jnp.float32)
        k = rand(ks[1], (1, 256, 4, 32), jnp.float32)
        v = rand(ks[2], (1, 256, 4, 32), jnp.float32)
        cl = jnp.asarray([200], jnp.int32)
        got = decode_attention(q, k, v, cl, window=64, bk=64,
                               interpret=True)
        want = ref.decode_attention_ref(q, k, v, cl, window=64)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _hetero_state(rng, table, s, garbage=np.nan):
    """Random heterogeneous fleet state with dead lanes full of garbage."""
    med_lat = float(np.median(table.latency))
    med_en = float(np.median(table.run_power)) * med_lat
    state = dict(
        mu=rng.uniform(0.5, 3.0, s), sigma=rng.uniform(0.01, 0.5, s),
        phi=rng.uniform(0.05, 0.8, s),
        deadline=rng.uniform(0.1, 3.0, s) * med_lat,
        accuracy_goal=rng.uniform(0.2, 1.1, s),
        energy_goal=rng.uniform(0.0, 2.5, s) * med_en,
        goal_kind=rng.integers(0, 2, s),
        active=rng.random(s) < 0.85)
    for k in ("mu", "sigma", "phi", "deadline", "accuracy_goal",
              "energy_goal"):
        state[k][~state["active"]] = garbage
    return state


def _kernel_out(engine, state, *, x64=True, **kw):
    """Run the raw kernel with an engine's baked constants: float64
    inputs by default, float32 inputs (the chip's precision) with
    ``x64=False``."""
    dt = np.float64 if x64 else np.float32
    with x64_scope(x64):
        out = alert_select(
            *(np.asarray(state[n], dt) for n in (
                "mu", "sigma", "phi", "deadline", "accuracy_goal",
                "energy_goal")),
            state["goal_kind"], state["active"],
            latency=engine._c_latency, run_power=engine._c_run_power,
            weights=engine._c_weights, q_fail=engine._c_q_fail,
            overhead=engine.overhead, **kw)
    return [np.asarray(o) for o in out]


def _reference(engine, state):
    """The float64 XLA engine's decisions on ``state`` and the lanes
    whose decision clears the tie margins."""
    st = state
    batch = engine.select(st["mu"], st["sigma"], st["phi"], st["deadline"],
                          accuracy_goal=st["accuracy_goal"],
                          energy_goal=st["energy_goal"],
                          goal_kind=st["goal_kind"], active=st["active"])
    est = engine.estimate(st["mu"], np.maximum(st["sigma"], 1e-6),
                          st["phi"],
                          np.maximum(st["deadline"] - engine.overhead, 1e-9),
                          active=st["active"])
    return batch, clear_lanes(est.accuracy, est.energy,
                              st["accuracy_goal"], st["energy_goal"],
                              st["goal_kind"], st["active"])


def _assert_margin_parity(batch, clear, out, min_clear=0.5):
    rep = margin_report(batch, out, clear)
    assert rep["mismatches"] == 0, rep
    assert rep["pred_ok"], rep
    assert rep["n_clear"] >= min_clear * len(clear), rep


class TestAlertSelect:
    """Fused decision kernel vs the float64 XLA engine under the margin
    contract: picks, feasibility and relax codes equal on every lane
    that clears the tie margins, predictions within tolerance."""

    @pytest.mark.parametrize("s", [1, 5, 64, 257])
    def test_bitwise_parity_hetero(self, s):
        from benchmarks.controller_bench import random_table
        rng = np.random.default_rng(100 + s)
        table = random_table(rng)
        engine = BatchedAlertEngine(
            table, None, overhead=0.1 * float(np.median(table.latency)))
        st = _hetero_state(rng, table, s)
        batch, clear = _reference(engine, st)
        _assert_margin_parity(batch, clear, _kernel_out(engine, st),
                              min_clear=0.5 if s > 5 else 0.0)

    @pytest.mark.parametrize("garbage", [np.nan, np.inf, -np.inf, 1e300])
    def test_dead_lane_garbage_is_inert(self, garbage):
        from benchmarks.controller_bench import random_table
        rng = np.random.default_rng(7)
        table = random_table(rng)
        engine = BatchedAlertEngine(table, None)
        st = _hetero_state(rng, table, 33, garbage=garbage)
        out = _kernel_out(engine, st)
        i, j, lat, acc, en, feas, rel = out
        dead = ~st["active"]
        assert np.all(i[dead] == 0) and np.all(j[dead] == 0)
        assert not feas[dead].any() and np.all(rel[dead] == 0)
        assert np.all(lat[dead] == 0.0) and np.all(en[dead] == 0.0)
        batch, clear = _reference(engine, st)
        _assert_margin_parity(batch, clear, out)

    def test_float32_inputs_hold_the_margin_contract(self):
        """Float32 inputs run the kernel in float32, the precision of the
        compiled kernel on the chip: same contract against float64."""
        from benchmarks.controller_bench import random_table
        rng = np.random.default_rng(17)
        table = random_table(rng)
        engine = BatchedAlertEngine(table, None)
        st = _hetero_state(rng, table, 300)
        out = _kernel_out(engine, st, x64=False)
        assert out[2].dtype == np.float32
        batch, clear = _reference(engine, st)
        _assert_margin_parity(batch, clear, out)

    def test_block_size_invariance(self):
        """Lane tiling must not change a single bit of any output."""
        from benchmarks.controller_bench import random_table
        rng = np.random.default_rng(11)
        table = random_table(rng)
        engine = BatchedAlertEngine(table, None)
        st = _hetero_state(rng, table, 3000)
        outs = [_kernel_out(engine, st, block_s=bs)
                for bs in (1024, 2048, 4096)]
        for o in outs[1:]:
            for a, b in zip(o, outs[0]):
                assert np.array_equal(a, b)

    def test_pick_only_matches_full(self):
        from benchmarks.controller_bench import random_table
        rng = np.random.default_rng(13)
        table = random_table(rng)
        engine = BatchedAlertEngine(table, None)
        st = _hetero_state(rng, table, 50)
        full = _kernel_out(engine, st)
        pick = _kernel_out(engine, st, predictions=False)
        for a, b in zip(pick[:2] + pick[5:], full[:2] + full[5:]):
            assert np.array_equal(a, b)
        for z in pick[2:5]:
            assert np.all(z == 0.0)

    def test_cost_model_is_compute_bound(self):
        """Roofline sanity: per-lane HBM traffic is O(1) while compute is
        O(K·L), so intensity grows with the table and clears the VPU
        ridge for production-sized tables."""
        c = alert_select_cost(65536, 8, 8)
        assert c["transcendentals"] == 65536 * 64
        assert c["arithmetic_intensity_flops_per_byte"] > 10.0
        assert alert_select_cost(65536, 8, 8, predictions=True)["flops"] \
            > c["flops"]


class TestRwkvScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,s,h,hd,chunk", [
        (2, 64, 2, 16, 16),
        (1, 128, 4, 32, 32),
        (2, 32, 1, 64, 32),
    ])
    def test_matches_ref(self, dtype, b, s, h, hd, chunk):
        ks = jax.random.split(KEY, 6)
        r = rand(ks[0], (b, s, h, hd), dtype)
        k = rand(ks[1], (b, s, h, hd), dtype)
        v = rand(ks[2], (b, s, h, hd), dtype)
        # decay in (0, 1), bonus small positive
        w = jax.nn.sigmoid(rand(ks[3], (b, s, h, hd), jnp.float32)) \
            .astype(dtype)
        u = (jax.nn.sigmoid(rand(ks[4], (h, hd), jnp.float32)) * 0.5)
        s0 = rand(ks[5], (b, h, hd, hd), jnp.float32) * 0.1
        got_y, got_s = rwkv_scan(r, k, v, w, u, s0, chunk=chunk,
                                 interpret=True)
        want_y, want_s = ref.rwkv_scan_ref(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(got_y, np.float32),
                                   np.asarray(want_y, np.float32),
                                   **tol(dtype))
        np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=1e-4)

    def test_state_carries_across_chunks(self):
        """Chunked result must equal one-big-chunk result."""
        ks = jax.random.split(KEY, 5)
        b, s, h, hd = 1, 64, 2, 16
        r = rand(ks[0], (b, s, h, hd), jnp.float32)
        k = rand(ks[1], (b, s, h, hd), jnp.float32)
        v = rand(ks[2], (b, s, h, hd), jnp.float32)
        w = jax.nn.sigmoid(rand(ks[3], (b, s, h, hd), jnp.float32))
        u = jnp.zeros((h, hd))
        s0 = jnp.zeros((b, h, hd, hd))
        y1, s1 = rwkv_scan(r, k, v, w, u, s0, chunk=16, interpret=True)
        y2, s2 = rwkv_scan(r, k, v, w, u, s0, chunk=64, interpret=True)
        np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-5)
