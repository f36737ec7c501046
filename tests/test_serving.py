"""Serving-layer tests: engine per-level programs, batcher, simulator,
golden-trace scheme regression, and environment-trace determinism."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import ModelConfig
from repro.core.controller import Constraints, Goal
from repro.models.registry import build_model
from repro.serving.batcher import DeadlineBatcher, Request
from repro.serving.engine import ServeEngine
from repro.serving.sim import (ENVS, EnvironmentTrace, InferenceSim, Phase,
                               TraceResult)
from benchmarks.common import family_table


@pytest.fixture(scope="module")
def nested_setup():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=4, head_dim=8, d_ff=64,
                      vocab=64, nest_levels=2, dtype="float32",
                      attn_chunk=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


class TestServeEngine:
    def test_per_level_generate_and_staircase_latency(self, nested_setup):
        cfg, model, params = nested_setup
        engine = ServeEngine(model, max_len=32, batch_size=2)
        prompt = np.zeros((2, 4), np.int32)
        outs = {}
        for lvl in engine.levels:
            outs[lvl] = engine.generate(params, prompt, 4, level=lvl)
            assert outs[lvl]["tokens"].shape == (2, 4)
            assert outs[lvl]["complete"]
        # levels produce different results (deeper model != shallow)
        assert not np.array_equal(outs[1]["tokens"], outs[2]["tokens"])

    def test_level_decode_matches_level_forward(self, nested_setup):
        """Per-level KV-cached decode == per-level full forward."""
        cfg, model, params = nested_setup
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, 64, (2, 8)), jnp.int32)
        for lvl in (1, 2):
            full, _ = model.train_logits(params, {"tokens": toks},
                                         level=lvl)
            from repro.models import transformer as tfm
            out = tfm.lm_apply(params, cfg, toks[:, :7], mode="prefill",
                               level=lvl)
            engine = ServeEngine(model, max_len=16, batch_size=2)
            caches = engine._merge(engine.init_caches(lvl), out.caches)
            step = tfm.lm_apply(params, cfg, toks[:, 7:8], mode="decode",
                                caches=caches,
                                cache_len=jnp.asarray(7, jnp.int32),
                                level=lvl)
            np.testing.assert_allclose(np.asarray(step.logits[:, 0]),
                                       np.asarray(full[:, 7]),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("lvl", [1, 2])
    def test_generate_matches_stepwise_greedy_reference(self, nested_setup,
                                                        lvl):
        """``generate``'s tokens are the host-side greedy argmax of
        ``lm_apply`` run one position at a time (prefill, then decode),
        and each level keeps one decode trace after warm-up."""
        from repro.models import transformer as tfm

        cfg, model, params = nested_setup
        engine = ServeEngine(model, max_len=16, batch_size=2)
        rng = np.random.default_rng(lvl)
        prompt = rng.integers(0, cfg.vocab, (2, 5), dtype=np.int32)
        n_new = 7
        out = tfm.lm_apply(params, cfg, jnp.asarray(prompt),
                           mode="prefill", level=lvl)
        caches = engine._merge(engine.init_caches(lvl), out.caches)
        tok = np.argmax(np.asarray(out.logits[:, -1:]), axis=-1)
        want = [tok]
        for i in range(n_new - 1):
            o = tfm.lm_apply(params, cfg, jnp.asarray(tok, jnp.int32),
                             mode="decode", caches=caches,
                             cache_len=jnp.asarray(5 + i, jnp.int32),
                             level=lvl)
            caches = o.caches
            tok = np.argmax(np.asarray(o.logits[:, -1:]), axis=-1)
            want.append(tok)
        want = np.concatenate(want, axis=1).astype(np.int32)
        engine.generate(params, prompt, n_new, level=lvl)
        warm = engine.n_compiles()
        got = engine.generate(params, prompt, n_new, level=lvl)
        np.testing.assert_array_equal(got["tokens"], want)
        assert got["complete"]
        assert engine.n_compiles() == warm == (1, 1)

    @pytest.mark.parametrize("k", [1, 2, 3, 5, 9])
    def test_deadline_after_kth_clock_read(self, nested_setup, k):
        """A clock that expires after its ``k``-th read: read 1 is entry
        and read ``i + 2`` precedes step ``i``, so exactly the steps whose
        read came before expiry are dispatched, and the last dispatched
        step's token is returned."""
        from repro.obs import FlightRecorder

        class ExpiringClock:
            def __init__(self):
                self.reads = 0

            def __call__(self):
                self.reads += 1
                return 0.0 if self.reads <= k else 1e9

        cfg, model, params = nested_setup
        engine = ServeEngine(model, max_len=16, batch_size=2)
        prompt = np.zeros((2, 4), np.int32)
        n_new = 6
        full = engine.generate(params, prompt, n_new, level=2)["tokens"]
        obs = FlightRecorder()
        out = engine.generate(params, prompt, n_new, level=2,
                              deadline_s=1.0, clock=ExpiringClock(),
                              obs=obs)
        n_tok = min(n_new, k)
        assert out["tokens"].shape == (2, n_tok)
        np.testing.assert_array_equal(out["tokens"], full[:, :n_tok])
        assert out["complete"] == (n_tok == n_new)
        assert out["latency"] == (0.0 if k > n_tok else 1e9)
        steps = obs.metrics.counter("decode_steps").value
        assert steps == n_tok - 1
        assert 0 <= obs.metrics.counter("decode_overlapped").value <= steps
        assert obs.metrics.counter("deadline_cutoffs").value == \
            (0 if n_tok == n_new else 1)

    def test_deadline_cuts_generation_short(self, nested_setup):
        cfg, model, params = nested_setup
        engine = ServeEngine(model, max_len=64, batch_size=2)
        prompt = np.zeros((2, 4), np.int32)
        out = engine.generate(params, prompt, 40, deadline_s=1e-9)
        assert not out["complete"]
        assert out["tokens"].shape[1] < 40


class TestFleetServer:
    def test_fleet_tick_scores_all_streams_in_one_pass(self, nested_setup):
        """FleetAlertServer: one batched engine call per tick serves S
        streams over the real per-level compiled programs."""
        from repro.serving.alert_server import FleetAlertServer

        cfg, model, params = nested_setup
        engine = ServeEngine(model, max_len=32, batch_size=2)
        srv = FleetAlertServer(engine, params,
                               level_accuracies=[0.6, 0.9],
                               goal=Goal.MAXIMIZE_ACCURACY, n_streams=3,
                               profile_iters=1, gen_tokens=3)
        prompts = [np.zeros((2, 4), np.int32)] * 3
        budget = float(np.median(srv.table.run_power)) * \
            float(np.max(srv.table.latency)) * 2.0
        cons = [Constraints(deadline=10.0, energy_goal=budget)] * 3
        n0, _ = srv.scoring.n_compiles()
        outs = srv.serve_tick(prompts, cons)
        outs2 = srv.serve_tick(prompts, cons)
        assert len(outs) == 3 and len(outs2) == 3
        assert all(o.latency > 0 and o.energy > 0 for o in outs)
        # feedback reached every stream's filter lane
        assert np.all(srv.slowdown.n_updates == 2)
        # scoring stayed on one compiled executable across ticks
        _, n_sel = srv.scoring.n_compiles()
        assert n_sel == 1


class TestBatcher:
    def test_edf_order_and_batch_deadline(self):
        b = DeadlineBatcher(batch_size=2)
        b.submit(Request(deadline=3.0))
        b.submit(Request(deadline=1.0))
        b.submit(Request(deadline=2.0))
        batch, dl = b.next_batch(now=0.0)
        assert dl == 1.0 and len(batch) == 2
        assert [r.deadline for r in batch] == [1.0, 2.0]

    def test_admission_control_rejects_infeasible(self):
        b = DeadlineBatcher(batch_size=4, min_feasible_latency=0.5)
        b.submit(Request(deadline=0.1))
        b.submit(Request(deadline=2.0))
        batch, _ = b.next_batch(now=0.0)
        assert len(batch) == 1 and len(b.rejected) == 1


class TestSimulator:
    @pytest.fixture(scope="class")
    def sim(self):
        table = family_table("image")
        trace = EnvironmentTrace(ENVS["memory"], seed=1)
        return table, trace, InferenceSim(table, trace)

    def test_paired_traces_are_deterministic(self, sim):
        table, trace, s = sim
        t2 = EnvironmentTrace(ENVS["memory"], seed=1)
        np.testing.assert_array_equal(trace.xi, t2.xi)

    def test_oracle_dominates_static_on_error(self, sim):
        table, trace, s = sim
        from benchmarks.common import deadline_range
        dl = float(deadline_range(table, 3)[1])
        cons = Constraints.from_power_budget(dl, 170.0)
        o = s.run_scheme("oracle", Goal.MAXIMIZE_ACCURACY, cons)
        st = s.run_scheme("oracle_static", Goal.MAXIMIZE_ACCURACY, cons)
        assert o.mean_error <= st.mean_error + 1e-9

    def test_alert_feasible_and_reasonable(self, sim):
        table, trace, s = sim
        from benchmarks.common import deadline_range
        dl = float(deadline_range(table, 3)[1])
        cons = Constraints.from_power_budget(dl, 170.0)
        a = s.run_scheme("alert", Goal.MAXIMIZE_ACCURACY, cons)
        st = s.run_scheme("oracle_static", Goal.MAXIMIZE_ACCURACY, cons)
        assert a.mean_error <= st.mean_error * 1.15

    def test_delivery_tensor_matches_scalar_path(self, sim):
        table, trace, s = sim
        cons = Constraints(deadline=0.1, accuracy_goal=0.8)
        lat, acc, en, missed = s._delivery_tensors(cons)
        for n in (0, 57, 200):
            for i in (0, 3, 6):
                for j in (0, 5):
                    l2, a2, e2, m2, _ = s._deliver(
                        i, j, trace.realized_scale(n), 0.1)
                    assert np.isclose(lat[i, j, n], l2)
                    assert np.isclose(acc[i, j, n], a2)
                    assert np.isclose(en[i, j, n], e2)
                    assert missed[i, j, n] == m2

    def test_violation_windows(self):
        r = TraceResult(energy=np.ones(100), accuracy=np.full(100, 0.9),
                        latency=np.ones(100), missed=np.zeros(100, bool))
        cons = Constraints(deadline=1.0, accuracy_goal=0.8)
        assert not r.violates(Goal.MINIMIZE_ENERGY, cons)
        r.accuracy[:50] = 0.1
        assert r.violates(Goal.MINIMIZE_ENERGY, cons)


class TestTraceDeterminism:
    """EnvironmentTrace randomness is fully threaded through one
    numpy.random.Generator: same seed -> bit-identical trace, every
    array, every construction."""

    def test_same_seed_identical_trace(self):
        for env in ENVS.values():
            a = EnvironmentTrace(env, seed=7, length_cv=0.2,
                                 deadline_cv=0.1)
            b = EnvironmentTrace(env, seed=7, length_cv=0.2,
                                 deadline_cv=0.1)
            np.testing.assert_array_equal(a.xi, b.xi)
            np.testing.assert_array_equal(a.lam, b.lam)
            np.testing.assert_array_equal(a.deadline_scale,
                                          b.deadline_scale)
            np.testing.assert_array_equal(a.phase_id, b.phase_id)

    def test_seed_matches_explicit_generator(self):
        """An int seed is exactly default_rng(seed): callers may thread
        their own Generator and get the same draws."""
        a = EnvironmentTrace(ENVS["memory"], seed=13, deadline_cv=0.1)
        b = EnvironmentTrace(ENVS["memory"],
                             seed=np.random.default_rng(13),
                             deadline_cv=0.1)
        np.testing.assert_array_equal(a.xi, b.xi)
        np.testing.assert_array_equal(a.lam, b.lam)
        np.testing.assert_array_equal(a.deadline_scale, b.deadline_scale)

    def test_no_global_rng_interference(self):
        """Polluting the legacy global RNG state must not change a
        seeded trace (no hidden np.random.* use)."""
        np.random.seed(0)
        a = EnvironmentTrace(ENVS["cpu"], seed=3)
        np.random.seed(12345)
        np.random.random(1000)
        b = EnvironmentTrace(ENVS["cpu"], seed=3)
        np.testing.assert_array_equal(a.xi, b.xi)


class TestGoldenTraces:
    """Checked-in alert-vs-oracle fixtures (tests/golden_traces.json):
    any drift in scheme semantics moves these numbers.  Regenerate ONLY
    for intentional changes: PYTHONPATH=src python
    tests/make_golden_traces.py"""

    @pytest.fixture(scope="class")
    def golden(self):
        path = os.path.join(os.path.dirname(__file__),
                            "golden_traces.json")
        with open(path) as f:
            return json.load(f)

    def test_schemes_match_golden(self, golden):
        from tests.make_golden_traces import compute_golden

        got = compute_golden()
        assert set(got["envs"]) == set(golden["envs"])
        for env, rows in golden["envs"].items():
            for scheme in ("alert", "oracle"):
                for key, want in rows[scheme].items():
                    have = got["envs"][env][scheme][key]
                    np.testing.assert_allclose(
                        have, want, rtol=1e-9, atol=1e-12,
                        err_msg=f"{env}/{scheme}/{key} drifted "
                                f"(golden {want}, got {have})")

    def test_golden_gaps_sane(self, golden):
        """The oracle lower-bounds alert's energy in every env (it has
        perfect knowledge and no conservatism)."""
        for env, rows in golden["envs"].items():
            assert rows["gap"]["energy"] > 0, env
            assert rows["alert"]["mean_error"] < 0.5, env


class TestFleetServerChurn:
    def test_admit_retire_recycles_lanes_without_retrace(self, nested_setup):
        """Streams join/leave between ticks: retired lanes are recycled
        with fresh filter state, mixed goal types share one engine call,
        and churn within capacity never re-traces the scoring pass."""
        from repro.serving.alert_server import FleetAlertServer

        cfg, model, params = nested_setup
        engine = ServeEngine(model, max_len=32, batch_size=2)
        srv = FleetAlertServer(engine, params,
                               level_accuracies=[0.6, 0.9],
                               goal=Goal.MAXIMIZE_ACCURACY, n_streams=3,
                               profile_iters=1, gen_tokens=3)
        prompt = np.zeros((2, 4), np.int32)
        budget = float(np.median(srv.table.run_power)) * \
            float(np.max(srv.table.latency)) * 2.0
        c_max = Constraints(deadline=10.0, energy_goal=budget)
        c_min = Constraints(deadline=10.0, accuracy_goal=0.7,
                            energy_goal=budget)
        outs = srv.serve_tick([prompt] * 3, [c_max] * 3)
        assert all(o is not None for o in outs)

        # stream 1 leaves; its lane must be masked out of the next tick
        srv.retire(1)
        outs = srv.serve_tick([prompt] * 3, [c_max, None, c_max])
        assert outs[1] is None and outs[0] is not None
        assert srv.slowdown.n_updates[1] == 1      # frozen since tick 1
        mu_frozen = float(srv.slowdown.mu[1])

        # a new MIN-ENERGY tenant recycles lane 1 with fresh priors
        lane = srv.admit(goal=Goal.MINIMIZE_ENERGY)
        assert lane == 1
        assert srv.slowdown.mu[1] == 1.0 and srv.slowdown.n_updates[1] == 0
        assert srv.slowdown.mu[1] != mu_frozen or mu_frozen == 1.0
        outs = srv.serve_tick([prompt] * 3, [c_max, c_min, c_max])
        assert outs[1] is not None
        assert srv.slowdown.n_updates[1] == 1
        # mixed goal types all served through ONE compiled select
        _, n_sel = srv.scoring.n_compiles()
        assert n_sel == 1

        # admitting past capacity grows the lane pool (amortised re-trace)
        lanes = [srv.admit() for _ in range(3)]
        assert srv.n_streams == 6 and set(lanes) == {3, 4, 5}
        outs = srv.serve_tick([prompt] * 6,
                              [c_max, c_min, c_max, c_max, c_max, c_max])
        assert sum(o is not None for o in outs) == 6

    def test_min_energy_lane_requires_accuracy_goal(self, nested_setup):
        from repro.serving.alert_server import FleetAlertServer

        cfg, model, params = nested_setup
        engine = ServeEngine(model, max_len=32, batch_size=2)
        srv = FleetAlertServer(engine, params,
                               level_accuracies=[0.6, 0.9],
                               goal=Goal.MINIMIZE_ENERGY, n_streams=1,
                               profile_iters=1, gen_tokens=3)
        prompt = np.zeros((2, 4), np.int32)
        with pytest.raises(ValueError, match="accuracy_goal"):
            srv.serve_tick([prompt], [Constraints(deadline=10.0)])
