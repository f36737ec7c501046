"""Tests for the fleet-scale batched scoring engine (repro.core.batched).

Three layers of guarantees:

* **Parity** — the batched engine's decisions are identical to the scalar
  NumPy reference (seed semantics, repro.core.reference) across random
  profiles, goals, constraints, and both relaxation branches; estimates
  agree to ~1e-12 (both run float64).
* **State parity** — the struct-of-arrays Kalman banks and windowed-goal
  bank reproduce the scalar filters element-for-element.
* **Stability** — with static S, estimate/select compile once and are
  never re-traced across a 400-input trace; the fleet sim in lockstep is
  bit-identical to independent single-stream runs and to the pre-engine
  scalar simulation loop.
"""

import numpy as np
import pytest

from repro.core.batched import (BatchedAlertEngine, GOAL_MAX_ACCURACY,
                                GOAL_MIN_ENERGY, RELAXED_NAMES,
                                WindowedGoalBank, goal_codes)
from repro.core.controller import (AlertController, Constraints, Goal,
                                   WindowedAccuracyGoal)
from repro.core.kalman import (IdlePowerFilter, IdlePowerFilterBank,
                               SlowdownFilter, SlowdownFilterBank)
from repro.core.reference import ScalarReferenceController
from repro.serving.sim import (ENVS, EnvironmentTrace, FleetSim,
                               InferenceSim, StreamSpec, run_fleet)

from benchmarks.common import deadline_range, family_table
from benchmarks.controller_bench import random_state, random_table


def _ref_with_state(table, goal, mu, sigma, phi, overhead=0.0):
    ref = ScalarReferenceController(table, goal, overhead=overhead)
    ref.slowdown.mu = float(mu)
    ref.slowdown.sigma = float(sigma)
    ref.idle_power.phi = float(phi)
    return ref


class TestParity:
    @pytest.mark.parametrize("goal", [Goal.MINIMIZE_ENERGY,
                                      Goal.MAXIMIZE_ACCURACY])
    def test_random_sweep_decisions_identical(self, goal):
        """Random profiles/goals/constraints: engine == scalar reference,
        including anytime staircases and relaxation branches."""
        rng = np.random.default_rng(42)
        for _ in range(8):
            table = random_table(rng)
            med_lat = float(np.median(table.latency))
            med_en = float(np.median(table.run_power)) * med_lat
            overhead = float(rng.uniform(0, 0.1) * med_lat)
            engine = BatchedAlertEngine(table, goal, overhead=overhead)
            s = 12
            mus, sds, phis = random_state(rng, s)
            deadlines = rng.uniform(0.2, 3.0, s) * med_lat
            goals = rng.uniform(0.3, 1.05, s) \
                if goal is Goal.MINIMIZE_ENERGY \
                else rng.uniform(0.0, 2.5, s) * med_en
            kw = {"accuracy_goal" if goal is Goal.MINIMIZE_ENERGY
                  else "energy_goal": goals}
            batch = engine.select(mus, sds, phis, deadlines, **kw)
            est = engine.estimate(mus, sds, phis,
                                  np.maximum(deadlines - overhead, 1e-9))
            for i in range(s):
                ref = _ref_with_state(table, goal, mus[i], sds[i], phis[i],
                                      overhead)
                c_kw = {"accuracy_goal" if goal is Goal.MINIMIZE_ENERGY
                        else "energy_goal": float(goals[i])}
                d = ref.select(Constraints(deadline=float(deadlines[i]),
                                           **c_kw))
                assert d.model_index == int(batch.model_index[i])
                assert d.power_index == int(batch.power_index[i])
                assert d.feasible == bool(batch.feasible[i])
                assert d.relaxed == RELAXED_NAMES[
                    int(batch.relaxed_code[i])]
                e = ref.estimate(max(float(deadlines[i]) - overhead, 1e-9))
                np.testing.assert_allclose(est.accuracy[i], e.accuracy,
                                           rtol=0, atol=1e-12)
                np.testing.assert_allclose(est.energy[i], e.energy,
                                           rtol=1e-12, atol=1e-12)
                np.testing.assert_allclose(est.p_finish[i], e.p_finish,
                                           rtol=0, atol=1e-12)

    def test_relaxation_branches(self):
        """Infeasible constraints relax in the paper's priority order and
        match the reference on both branches."""
        table = family_table("image")
        # Max-accuracy with impossible budget: drop power first.
        eng = BatchedAlertEngine(table, Goal.MAXIMIZE_ACCURACY)
        b = eng.select(1.0, 0.1, 0.25, np.asarray([0.05]),
                       energy_goal=np.asarray([1e-12]))
        assert not b.feasible[0] and b.relaxed_name(0) == "power"
        # Min-energy with unreachable accuracy: relax the goal.
        eng2 = BatchedAlertEngine(table, Goal.MINIMIZE_ENERGY)
        b2 = eng2.select(1.0, 0.1, 0.25, np.asarray([1e-7]),
                         accuracy_goal=np.asarray([0.99]))
        assert not b2.feasible[0] and b2.relaxed_name(0) == "accuracy"

    def test_wrapper_is_engine_s1(self):
        """AlertController (S=1 wrapper) tracks the reference through a
        400-input feedback loop: identical decisions every step."""
        table = family_table("image")
        dls = deadline_range(table, 5)
        ctl = AlertController(table, Goal.MINIMIZE_ENERGY, overhead=1e-4)
        ref = ScalarReferenceController(table, Goal.MINIMIZE_ENERGY,
                                        overhead=1e-4)
        rng = np.random.default_rng(7)
        for _ in range(400):
            cons = Constraints(deadline=float(rng.choice(dls)),
                               accuracy_goal=0.8)
            d1, d2 = ctl.select(cons), ref.select(cons)
            assert (d1.model_index, d1.power_index, d1.feasible,
                    d1.relaxed) == (d2.model_index, d2.power_index,
                                    d2.feasible, d2.relaxed)
            obs = d1.predicted_latency * float(rng.lognormal(0.0, 0.25))
            missed = obs > cons.deadline
            for c in (ctl, ref):
                c.observe(min(obs, cons.deadline),
                          deadline_missed=bool(missed),
                          idle_power=0.2 * table.run_power[
                              d1.model_index, d1.power_index],
                          delivered_accuracy=0.8)
            assert np.isclose(ctl.slowdown.mu, ref.slowdown.mu,
                              rtol=0, atol=0)


class TestMaskedHeterogeneousEngine:
    def test_mixed_goal_codes_match_homogeneous_engines(self):
        """One hetero call == the per-goal homogeneous engines, bitwise."""
        table = family_table("image")
        dls = deadline_range(table, 5)
        rng = np.random.default_rng(9)
        s = 16
        mus, sds, phis = random_state(rng, s)
        d = rng.choice(dls, s)
        qg = rng.uniform(0.6, 0.95, s)
        eg = rng.uniform(0.5, 3.0, s)
        gk = rng.integers(0, 2, s)
        hetero = BatchedAlertEngine(table, None)
        b = hetero.select(mus, sds, phis, d, accuracy_goal=qg,
                          energy_goal=eg, goal_kind=gk)
        b_min = BatchedAlertEngine(table, Goal.MINIMIZE_ENERGY).select(
            mus, sds, phis, d, accuracy_goal=qg)
        b_max = BatchedAlertEngine(table, Goal.MAXIMIZE_ACCURACY).select(
            mus, sds, phis, d, energy_goal=eg)
        for i in range(s):
            src = b_min if gk[i] == GOAL_MIN_ENERGY else b_max
            assert b.model_index[i] == src.model_index[i]
            assert b.power_index[i] == src.power_index[i]
            assert b.predicted_energy[i] == src.predicted_energy[i]
            assert b.feasible[i] == src.feasible[i]
            assert b.relaxed_code[i] == src.relaxed_code[i]

    def test_dead_lane_garbage_cannot_perturb_live_lanes(self):
        """NaN/inf/negative junk in dead lanes: live picks unchanged,
        dead lanes return deterministic nulls."""
        table = family_table("nlp")
        dls = deadline_range(table, 5)
        rng = np.random.default_rng(3)
        s = 10
        mus, sds, phis = random_state(rng, s)
        d = rng.choice(dls, s)
        qg = rng.uniform(0.6, 0.9, s)
        eg = rng.uniform(0.5, 2.0, s)
        gk = rng.integers(0, 2, s)
        engine = BatchedAlertEngine(table, None)
        clean = engine.select(mus, sds, phis, d, accuracy_goal=qg,
                              energy_goal=eg, goal_kind=gk)
        act = np.ones(s, bool)
        act[[1, 4, 7]] = False
        for junk in (np.nan, np.inf, -np.inf, -5.0):
            mus2, d2, qg2 = mus.copy(), d.copy(), qg.copy()
            mus2[~act] = junk
            d2[~act] = junk
            qg2[~act] = junk
            got = engine.select(mus2, sds, phis, d2, accuracy_goal=qg2,
                                energy_goal=eg, goal_kind=gk, active=act)
            for i in range(s):
                if act[i]:
                    assert got.model_index[i] == clean.model_index[i]
                    assert got.predicted_energy[i] == \
                        clean.predicted_energy[i]
                else:
                    assert got.model_index[i] == 0
                    assert got.power_index[i] == 0
                    assert got.predicted_energy[i] == 0.0
                    assert not got.feasible[i]
                    assert got.relaxed_code[i] == 0

    def test_churn_never_retraces(self):
        """200 ticks of mask/goal churn at fixed S: one select executable."""
        table = family_table("image")
        dls = deadline_range(table, 5)
        engine = BatchedAlertEngine(table, None)
        rng = np.random.default_rng(0)
        s = 64
        for _ in range(200):
            mus, sds, phis = random_state(rng, s)
            engine.select(mus, sds, phis, rng.choice(dls, s),
                          accuracy_goal=rng.uniform(0.5, 0.9, s),
                          energy_goal=rng.uniform(0.5, 2.0, s),
                          goal_kind=rng.integers(0, 2, s),
                          active=rng.random(s) < 0.9)
        assert engine.n_compiles()[1] == 1

    def test_goal_kind_required_without_default(self):
        table = family_table("image")
        engine = BatchedAlertEngine(table, None)
        with pytest.raises(ValueError, match="goal_kind"):
            engine.select(1.0, 0.1, 0.25, np.asarray([1.0]),
                          accuracy_goal=np.asarray([0.8]))
        with pytest.raises(ValueError, match="accuracy_goal"):
            engine.select(1.0, 0.1, 0.25, np.asarray([1.0]),
                          energy_goal=np.asarray([1.0]),
                          goal_kind=np.asarray([GOAL_MIN_ENERGY]))
        with pytest.raises(ValueError, match="energy_goal"):
            engine.select(1.0, 0.1, 0.25, np.asarray([1.0]),
                          accuracy_goal=np.asarray([0.8]),
                          goal_kind=np.asarray([GOAL_MAX_ACCURACY]))

    def test_goal_codes_helper(self):
        got = goal_codes([Goal.MINIMIZE_ENERGY, Goal.MAXIMIZE_ACCURACY, 0])
        assert got.tolist() == [GOAL_MIN_ENERGY, GOAL_MAX_ACCURACY,
                                GOAL_MIN_ENERGY]


class TestFilterBanks:
    def test_bank_lane_pool_reset_grow_shrink(self):
        """Lane recycling: reset restores priors on exactly the reset
        lanes; grow/shrink change capacity with fresh lanes."""
        bank = SlowdownFilterBank(4)
        bank.observe(np.full(4, 2.0), np.ones(4))
        bank.reset_lanes([1, 2])
        fresh = SlowdownFilter()
        assert bank.mu[1] == fresh.mu and bank.sigma[1] == fresh.sigma
        assert bank.gain[1] == fresh.gain and bank.n_updates[1] == 0
        assert bank.mu[0] != fresh.mu and bank.n_updates[0] == 1
        bank.grow(6)
        assert bank.n_streams == 6 and bank.mu[5] == fresh.mu
        bank.observe(np.full(6, 1.5), np.ones(6))
        bank.shrink(3)
        assert bank.n_streams == 3
        bank.observe(np.full(3, 1.2), np.ones(3))  # still updatable
        idle = IdlePowerFilterBank(3)
        idle.observe(np.full(3, 20.0), np.full(3, 100.0))
        idle.reset_lanes([0])
        assert idle.phi[0] == IdlePowerFilter().phi
        assert idle.n_updates[0] == 0
        idle.grow(5)
        idle.shrink(2)
        assert idle.n_streams == 2

    def test_goal_bank_reset_lanes_clears_equal_goal_window(self):
        """Re-admission with the SAME goal must still clear the window
        (set_goals alone would keep the departed tenant's history)."""
        bank = WindowedGoalBank(np.asarray([0.8, 0.8]), 2, window=5)
        bank.record(np.asarray([0.1, 0.1]))
        assert bank.current_goal()[0] > 0.8
        bank.reset_lanes([0], goal=0.8)
        got = bank.current_goal()
        assert got[0] == 0.8          # fresh window
        assert got[1] > 0.8           # untouched neighbour
        bank.grow(4, goal_fill=0.9)
        assert bank.current_goal().shape == (4,)
        assert bank.current_goal()[3] == 0.9
    def test_slowdown_bank_matches_scalar(self):
        s = 5
        bank = SlowdownFilterBank(s)
        scalars = [SlowdownFilter() for _ in range(s)]
        rng = np.random.default_rng(3)
        for _ in range(60):
            obs = rng.uniform(0.5, 3.0, s)
            prof = rng.uniform(0.5, 2.0, s)
            miss = rng.random(s) < 0.3
            bank.observe(obs, prof, deadline_missed=miss)
            for i, f in enumerate(scalars):
                f.observe(float(obs[i]), float(prof[i]),
                          deadline_missed=bool(miss[i]))
        np.testing.assert_allclose(bank.mu, [f.mu for f in scalars],
                                   rtol=1e-12, atol=0)
        np.testing.assert_allclose(bank.sigma, [f.sigma for f in scalars],
                                   rtol=1e-12, atol=0)
        np.testing.assert_allclose(bank.gain, [f.gain for f in scalars],
                                   rtol=1e-12, atol=0)

    def test_slowdown_bank_mask_freezes_streams(self):
        bank = SlowdownFilterBank(3)
        mu0 = bank.mu.copy()
        bank.observe(np.full(3, 2.0), np.ones(3),
                     mask=np.asarray([True, False, True]))
        assert bank.mu[1] == mu0[1] and bank.n_updates[1] == 0
        assert bank.mu[0] != mu0[0] and bank.n_updates[0] == 1

    def test_idle_bank_matches_scalar(self):
        s = 4
        bank = IdlePowerFilterBank(s)
        scalars = [IdlePowerFilter() for _ in range(s)]
        rng = np.random.default_rng(4)
        for _ in range(40):
            idle = rng.uniform(5.0, 50.0, s)
            active = rng.uniform(60.0, 200.0, s)
            bank.observe(idle, active)
            for i, f in enumerate(scalars):
                f.observe(float(idle[i]), float(active[i]))
        np.testing.assert_allclose(bank.phi, [f.phi for f in scalars],
                                   rtol=1e-12, atol=0)

    def test_windowed_goal_bank_per_stream_goals(self):
        """Vector goals are honoured per stream; a goal change resets only
        that stream's window (scalar recreate-on-change semantics)."""
        bank = WindowedGoalBank(np.asarray([0.7, 0.9]), 2, window=5)
        np.testing.assert_allclose(bank.current_goal(), [0.7, 0.9])
        bank.record(np.asarray([0.1, 0.1]))
        raised = bank.current_goal()
        assert raised[0] > 0.7 and raised[1] > 0.9
        bank.set_goals(np.asarray([0.8, 0.9]))   # stream 0 changes goal
        g = bank.current_goal()
        assert g[0] == 0.8                        # reset: fresh window
        assert g[1] == raised[1]                  # untouched history

    def test_windowed_goal_bank_matches_scalar(self):
        s, window = 3, 5
        bank = WindowedGoalBank(0.8, s, window)
        scalars = [WindowedAccuracyGoal(0.8, window) for _ in range(s)]
        rng = np.random.default_rng(5)
        np.testing.assert_allclose(bank.current_goal(),
                                   [w.current_goal() for w in scalars])
        for _ in range(12):
            acc = rng.uniform(0.0, 1.0, s)
            bank.record(acc)
            for i, w in enumerate(scalars):
                w.record(float(acc[i]))
            np.testing.assert_allclose(
                bank.current_goal(), [w.current_goal() for w in scalars],
                rtol=0, atol=1e-12)


class TestCompileStability:
    def test_no_retrace_across_400_inputs(self):
        """With static S, estimate/select compile once; varying deadlines,
        goals, and filter state never re-trace."""
        table = family_table("image")
        engine = BatchedAlertEngine(table, Goal.MINIMIZE_ENERGY,
                                    overhead=1e-4)
        rng = np.random.default_rng(0)
        s = 32
        dls = deadline_range(table, 5)
        for _ in range(400):
            mus, sds, phis = random_state(rng, s)
            engine.select(mus, sds, phis, rng.choice(dls, s),
                          accuracy_goal=rng.uniform(0.5, 0.9, s))
            engine.estimate(mus, sds, phis, rng.choice(dls, s))
        n_est, n_sel = engine.n_compiles()
        assert n_est == 1, f"estimate re-traced: {n_est} cache entries"
        assert n_sel == 1, f"select re-traced: {n_sel} cache entries"


class TestFleetSim:
    def test_fleet_matches_seed_scalar_loop(self):
        """FleetSim S=1 reproduces the pre-engine scalar simulation loop
        exactly (windowed goal, miss inflation, anytime uncensored
        observations, overhead subtraction — everything)."""
        table = family_table("image")
        trace = EnvironmentTrace(ENVS["memory"], seed=1, deadline_cv=0.1)
        sim = InferenceSim(table, trace)
        dl = float(deadline_range(table, 3)[1])
        for goal, kw in [
                (Goal.MINIMIZE_ENERGY, dict(accuracy_goal=0.8)),
                (Goal.MAXIMIZE_ACCURACY, dict(energy_goal=None))]:
            cons = Constraints.from_power_budget(dl, 170.0) \
                if goal is Goal.MAXIMIZE_ACCURACY \
                else Constraints(deadline=dl, **kw)
            fleet_res = sim.run_alert(goal, cons, overhead=1e-4)
            # seed-semantics loop, scalar reference controller
            ctl = ScalarReferenceController(table, goal, overhead=1e-4)
            dvec = cons.deadline * trace.deadline_scale
            bvec = None if cons.energy_goal is None else \
                cons.energy_goal * trace.deadline_scale
            for n in range(trace.n):
                cons_n = Constraints(
                    deadline=float(dvec[n]),
                    accuracy_goal=cons.accuracy_goal,
                    energy_goal=None if bvec is None else float(bvec[n]))
                d = ctl.select(cons_n)
                i, j = d.model_index, d.power_index
                lat, acc, en, missed, obs = sim._deliver(
                    i, j, trace.realized_scale(n), float(dvec[n]))
                assert en == fleet_res.energy[n], f"step {n}"
                assert acc == fleet_res.accuracy[n], f"step {n}"
                assert missed == fleet_res.missed[n], f"step {n}"
                if missed and obs is not None:
                    ctl.observe(obs[0], deadline_missed=False,
                                idle_power=sim.phi_true *
                                table.run_power[i, j],
                                delivered_accuracy=acc,
                                profiled_override=obs[1])
                else:
                    ctl.observe(lat, deadline_missed=bool(missed),
                                idle_power=sim.phi_true *
                                table.run_power[i, j],
                                delivered_accuracy=acc)

    def test_fleet_lockstep_equals_independent_streams(self):
        """S streams in one lockstep fleet == S separate single-stream
        runs, element for element (no cross-stream leakage)."""
        table = family_table("nlp")
        dl = float(deadline_range(table, 3)[1])
        cons = Constraints(deadline=dl, accuracy_goal=0.7)
        fleet = FleetSim.from_phases(table, ENVS["cpu"], 3, seed=20)
        fr = fleet.run_alert(Goal.MINIMIZE_ENERGY, cons)
        assert fr.n_streams == 3
        for s in range(3):
            t_s = EnvironmentTrace(ENVS["cpu"], seed=20 + s)
            single = InferenceSim(table, t_s).run_alert(
                Goal.MINIMIZE_ENERGY, cons)
            np.testing.assert_array_equal(fr.stream(s).energy,
                                          single.energy)
            np.testing.assert_array_equal(fr.stream(s).accuracy,
                                          single.accuracy)
            np.testing.assert_array_equal(fr.stream(s).missed,
                                          single.missed)

    def test_heterogeneous_fleet_slices_equal_independent_runs(self):
        """The acceptance fleet: 3 streams with distinct goal types,
        deadlines, environments, and lifetimes (one joins late, one leaves
        early) — every stream's TraceResult is bitwise-equal to its own
        independent InferenceSim.run_alert, and the engine never re-traces
        while the fleet churns."""
        table = family_table("image")
        dls = deadline_range(table, 5)
        specs = [
            StreamSpec(EnvironmentTrace(ENVS["cpu"], seed=11,
                                        deadline_cv=0.1),
                       Goal.MINIMIZE_ENERGY,
                       Constraints(deadline=float(dls[1]),
                                   accuracy_goal=0.8)),
            StreamSpec(EnvironmentTrace(ENVS["memory"], seed=22),
                       Goal.MAXIMIZE_ACCURACY,
                       Constraints.from_power_budget(float(dls[3]), 170.0),
                       arrival=37),          # joins mid-run
            StreamSpec(EnvironmentTrace(ENVS["default"], seed=33),
                       Goal.MINIMIZE_ENERGY,
                       Constraints(deadline=float(dls[2]),
                                   accuracy_goal=0.7),
                       arrival=5),           # departs before the horizon
        ]
        fleet = FleetSim.from_specs(table, specs)
        fr = fleet.run_specs(specs, overhead=1e-4)
        assert fleet.engine.n_compiles() == (0, 1), \
            "churn (join/leave) must not re-trace the engine"
        for s, sp in enumerate(specs):
            single = InferenceSim(table, sp.trace).run_alert(
                sp.goal, sp.constraints, overhead=1e-4)
            got = fr.stream(s)
            assert got.energy.shape == (sp.trace.n,)
            np.testing.assert_array_equal(got.energy, single.energy,
                                          err_msg=f"stream {s}")
            np.testing.assert_array_equal(got.accuracy, single.accuracy)
            np.testing.assert_array_equal(got.latency, single.latency)
            np.testing.assert_array_equal(got.missed, single.missed)
            if sp.constraints.energy_goal is not None:
                np.testing.assert_array_equal(got.budget, single.budget)

    def test_run_fleet_one_call_matches_from_specs(self):
        table = family_table("nlp")
        dl = float(deadline_range(table, 3)[1])
        specs = [
            StreamSpec(EnvironmentTrace(ENVS["default"], seed=1),
                       Goal.MINIMIZE_ENERGY,
                       Constraints(deadline=dl, accuracy_goal=0.7)),
            StreamSpec(EnvironmentTrace(ENVS["cpu"], seed=2),
                       Goal.MAXIMIZE_ACCURACY,
                       Constraints.from_power_budget(dl, 170.0),
                       arrival=3),
        ]
        a = run_fleet(table, specs)
        b = FleetSim.from_specs(table, specs).run_specs(specs)
        np.testing.assert_array_equal(a.energy, b.energy)
        np.testing.assert_array_equal(a.active, b.active)

    def test_heterogeneous_stream_validation(self):
        table = family_table("image")
        tr = EnvironmentTrace(ENVS["default"], seed=0)
        fleet = FleetSim(table, [tr])
        with pytest.raises(ValueError, match="accuracy_goal"):
            fleet.run_streams([Goal.MINIMIZE_ENERGY],
                              [Constraints(deadline=1.0)])
        with pytest.raises(ValueError, match="energy_goal"):
            fleet.run_streams([Goal.MAXIMIZE_ACCURACY],
                              [Constraints(deadline=1.0)])

    def test_ablation_schemes_run_through_fleet(self):
        """The Table-3 ablations (no-anytime / no-power / no-dnn) keep
        working through the batched path."""
        table = family_table("image")
        trace = EnvironmentTrace(ENVS["default"], seed=0)
        sim = InferenceSim(table, trace)
        dl = float(deadline_range(table, 3)[1])
        cons = Constraints.from_power_budget(dl, 170.0)
        for scheme in ("alert", "alert_trad", "alert_dnn", "alert_power",
                       "alert_plus"):
            res = sim.run_scheme(scheme, Goal.MAXIMIZE_ACCURACY, cons)
            assert res.scheme == scheme
            assert np.all(res.energy > 0)
            assert res.accuracy.shape == (trace.n,)


def _assert_pallas_margin(xla, ref, got, mu, sigma, phi, deadline, ag, eg,
                          gk, act, predictions=True):
    """``got`` (Pallas engine) holds the float64 ``ref`` (XLA engine)
    under the kernel's margin contract (docs/KERNELS.md)."""
    from repro.kernels.alert_select import clear_lanes, margin_report

    est = xla.estimate(mu, np.maximum(sigma, 1e-6), phi,
                       np.maximum(deadline - xla.overhead, 1e-9),
                       active=act)
    clear = clear_lanes(est.accuracy, est.energy, ag, eg, gk, act)
    rep = margin_report(ref, got, clear, predictions=predictions)
    assert rep["mismatches"] == 0 and rep["pred_ok"], rep
    return rep


class TestPallasBackend:
    """`backend="pallas"` behind the engine seams: margin parity with
    the float64 XLA engine, churn/no-retrace, and a shadow run along the
    golden FleetSim trajectories (docs/KERNELS.md)."""

    def _pair(self, table, goal=None, **kw):
        return (BatchedAlertEngine(table, goal, **kw),
                BatchedAlertEngine(table, goal, backend="pallas", **kw))

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="backend"):
            BatchedAlertEngine(family_table("image"), None,
                               backend="cuda")

    @pytest.mark.parametrize("goal", [Goal.MINIMIZE_ENERGY,
                                      Goal.MAXIMIZE_ACCURACY])
    def test_homogeneous_bitwise_parity(self, goal):
        rng = np.random.default_rng(21)
        table = random_table(rng)
        med_lat = float(np.median(table.latency))
        med_en = float(np.median(table.run_power)) * med_lat
        xla, pal = self._pair(table, goal, overhead=0.05 * med_lat)
        s = 96
        mus, sds, phis = random_state(rng, s)
        dls = rng.uniform(0.2, 3.0, s) * med_lat
        min_e = goal is Goal.MINIMIZE_ENERGY
        gv = rng.uniform(0.3, 1.05, s) if min_e \
            else rng.uniform(0.0, 2.5, s) * med_en
        kw = {"accuracy_goal" if min_e else "energy_goal": gv}
        gk = np.full(s, GOAL_MIN_ENERGY if min_e else GOAL_MAX_ACCURACY)
        zero = np.zeros(s)
        for pred in (True, False):
            bx = xla.select(mus, sds, phis, dls, predictions=pred, **kw)
            bp = pal.select(mus, sds, phis, dls, predictions=pred, **kw)
            rep = _assert_pallas_margin(
                xla, bx, bp, mus, sds, phis, dls, gv if min_e else zero,
                zero if min_e else gv, gk, np.ones(s, bool),
                predictions=pred)
            assert rep["n_clear"] >= s // 2, rep

    def test_churning_hetero_fleet_no_retrace(self):
        """Goal flips, mask churn, and lane recycling re-use ONE compiled
        kernel executable, with every clear lane's pick equal to XLA."""
        table = family_table("image")
        rng = np.random.default_rng(5)
        xla, pal = self._pair(table, None)
        s = 64
        dls = deadline_range(table, 5)
        gk = rng.integers(0, 2, s)
        act = rng.random(s) < 0.9
        med_en = float(np.median(table.run_power)
                       * np.median(table.latency))
        ag = rng.uniform(0.5, 0.9, s)
        eg = rng.uniform(0.5, 3.0, s) * med_en
        kw = dict(accuracy_goal=ag, energy_goal=eg, predictions=False)
        mus, sds, phis = random_state(rng, s)
        pal.select(mus, sds, phis, rng.choice(dls, s), goal_kind=gk,
                   active=act, **kw)
        n0 = pal.n_compiles()
        for _ in range(12):
            flip = rng.integers(0, s, 4)
            act[flip] = ~act[flip]
            gk = np.where(rng.random(s) < 0.2, 1 - gk, gk)
            mus, sds, phis = random_state(rng, s)
            d = rng.choice(dls, s)
            bx = xla.select(mus, sds, phis, d, goal_kind=gk, active=act,
                            **kw)
            bp = pal.select(mus, sds, phis, d, goal_kind=gk, active=act,
                            **kw)
            _assert_pallas_margin(xla, bx, bp, mus, sds, phis, d, ag, eg,
                                  gk, act, predictions=False)
        assert pal.n_compiles() == n0, "pallas backend re-traced"
        assert pal.n_compiles()[1] == 1

    def test_fleetsim_reproduces_golden_traces(self, monkeypatch):
        """FleetSim reproduces the checked-in golden alert traces, and at
        every tick of those closed-loop trajectories the Pallas engine,
        fed the same state, picks what the XLA engine picked on every
        lane that clears the tie margins."""
        import json
        import os

        from tests.make_golden_traces import GOLDEN_SEED, golden_config

        path = os.path.join(os.path.dirname(__file__),
                            "golden_traces.json")
        with open(path) as f:
            golden = json.load(f)
        table, cons = golden_config()
        calls = []
        select = BatchedAlertEngine.select

        def spy(engine, *args, **kw):
            out = select(engine, *args, **kw)
            # Copies: the filter banks update their state in place.
            calls.append((engine, [np.array(a) for a in args],
                          {n: v if isinstance(v, bool) else np.array(v)
                           for n, v in kw.items()}, out))
            return out

        monkeypatch.setattr(BatchedAlertEngine, "select", spy)
        for env_name in ("default", "cpu", "memory"):
            trace = EnvironmentTrace(ENVS[env_name], seed=GOLDEN_SEED)
            fleet = FleetSim(table, [trace])
            res = fleet.run_alert(Goal.MAXIMIZE_ACCURACY, cons).stream(0)
            want = golden["envs"][env_name]["alert"]
            assert res.mean_energy == want["mean_energy"], env_name
            assert res.mean_error == want["mean_error"], env_name
            assert res.miss_rate == want["miss_rate"], env_name
        monkeypatch.undo()
        assert calls
        pals = {}
        for xla, (mu, sd, phi, dl), kw, out in calls:
            pal = pals.setdefault(id(xla), BatchedAlertEngine(
                xla.table, None, overhead=xla.overhead,
                paper_faithful_energy=xla.paper_faithful_energy,
                backend="pallas"))
            got = pal.select(mu, sd, phi, dl, **kw)
            _assert_pallas_margin(
                xla, out, got, mu, sd, phi, dl, kw["accuracy_goal"],
                kw["energy_goal"], kw["goal_kind"], kw["active"],
                predictions=False)
