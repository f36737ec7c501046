"""Live-profile harness tests (DESIGN.md §12).

Every deterministic test here runs with ZERO real wall-clock dependence:
measured paths are driven through the injectable clock/sync seam
(:mod:`repro.profiling.clock`) with fake timed callables that model JAX
async dispatch.  Covered:

* the async-dispatch regression — the old unsynced timing loop measures
  dispatch cost only, proven with a deliberately-async fake callable
  through the REAL ``measure_mean_latency`` code;
* :class:`ProfileTable` invariants as hypothesis properties (Eq. 10
  staircase monotonicity, ``subset``/``power_subset`` tensor sharing,
  1/f power-bucket ordering, padded/unpadded consistency) under random
  K, L, and nest depths;
* the end-to-end live path: the jointly-trained reduced
  ``alert_anytime`` family profiled through the fake clock, served by
  the gateway (golden-pinned picks + dispositions, megatick bitwise
  parity, app-only / sys-only baseline races);
* the §8 zero-recompile contract at request granularity
  (``ServeEngine.n_compiles`` flat while the controller switches levels
  mid-sweep).
"""

import json
import os

import numpy as np
import pytest

from repro.core.power import PowerModel
from repro.core.profiles import (Candidate, ProfileTable,
                                 extrapolate_power_buckets,
                                 measure_mean_latency, profile_measured)
from repro.profiling import (FakeClock, FakeTimedFn, fake_level_fns,
                             level_flop_fractions, live_profile_table,
                             monotone_accuracies, profile_anytime_measured,
                             train_reduced_anytime)
from tests._hypothesis_compat import given, settings, st

GOLDEN = os.path.join(os.path.dirname(__file__), "golden_traces.json")
PM = PowerModel(p_idle=60.0, p_tdp=200.0)


# --------------------------------------------------------------------- #
# satellite 1: the async-dispatch under-measurement regression
# --------------------------------------------------------------------- #

class TestAsyncDispatchRegression:
    """Timing jitted callables without syncing measures dispatch, not
    compute — the fake callables reproduce that failure mode exactly."""

    def test_unsynced_loop_under_measures(self):
        clock = FakeClock()
        dispatch, compute = 2e-4, 8e-3
        # The OLD path: time bare fn() calls, never block on the result.
        fn = FakeTimedFn(clock, dispatch, compute)
        old = measure_mean_latency([fn], warmup=1, iters=4, clock=clock,
                                   sync=lambda x: x)[0]
        # The fixed contract: the default sync blocks on the handle.
        fn2 = FakeTimedFn(clock, dispatch, compute)
        new = measure_mean_latency([fn2], warmup=1, iters=4,
                                   clock=clock)[0]
        assert old == pytest.approx(dispatch)
        assert new == pytest.approx(dispatch + compute)
        assert new / old > 10  # the under-measurement is not subtle

    def test_default_sync_blocks_fake_handles(self):
        # jax.block_until_ready duck-types on block_until_ready(), so the
        # production default sync drives the fake handles unchanged.
        clock = FakeClock()
        fn = FakeTimedFn(clock, 0.0, 1e-3)
        from repro.core.profiles import default_sync
        h = fn()
        default_sync(h)
        assert clock() == pytest.approx(1e-3)

    def test_profile_measured_is_synced(self):
        clock = FakeClock()
        fns = fake_level_fns(clock, [4e-3, 1.6e-2], dispatch_s=1e-4)
        table = profile_measured(fns, ["a", "b"], [0.5, 0.8], PM,
                                 n_power_buckets=4, warmup=1, iters=3,
                                 clock=clock)
        # Full-cap column is the measured base: dispatch + compute.
        assert table.latency[:, -1] == pytest.approx([4.1e-3, 1.61e-2])
        # Warmup+timed calls all happened, nothing touched a real clock.
        assert all(fn.n_calls == 4 for fn in fns)

    def test_warmup_is_synced_too(self):
        # If warmup did not sync, the first timed call would inherit the
        # outstanding compute advance of the last warmup dispatch.
        clock = FakeClock()
        fn = FakeTimedFn(clock, 1e-4, 5e-3)
        base = measure_mean_latency([fn], warmup=3, iters=2,
                                    clock=clock)[0]
        assert base == pytest.approx(5.1e-3)


# --------------------------------------------------------------------- #
# the harness funnel
# --------------------------------------------------------------------- #

class TestHarness:
    def test_monotone_clamp(self):
        assert monotone_accuracies([0.3, 0.2, 0.5]).tolist() == \
            [0.3, 0.3, 0.5]

    def test_zero_latency_raises(self):
        clock = FakeClock()
        fns = fake_level_fns(clock, [0.0])
        with pytest.raises(ValueError, match="sync seam"):
            profile_anytime_measured(fns, [0.5], PM, clock=clock)

    def test_anytime_table_structure(self):
        clock = FakeClock()
        fns = fake_level_fns(clock, [1e-3, 2e-3, 4e-3])
        table = profile_anytime_measured(fns, [0.4, 0.35, 0.7], PM,
                                         n_power_buckets=5, clock=clock)
        assert table.names == ["level1", "level2", "level3"]
        assert table.anytime_groups() == {"anytime": [0, 1, 2]}
        st_ = table.staircase_tensors()
        assert st_.n_levels.tolist() == [1, 2, 3]
        # Eq. 10 premise: the published staircase never steps down.
        assert table.accuracies.tolist() == [0.4, 0.4, 0.7]

    def test_single_level_is_traditional(self):
        # A 1-level family reduces to Eq. 7: no anytime group.
        clock = FakeClock()
        table = profile_anytime_measured(fake_level_fns(clock, [1e-3]),
                                         [0.6], PM, clock=clock)
        assert not table.candidates[0].is_anytime_level
        assert table.anytime_groups() == {}


# --------------------------------------------------------------------- #
# satellite 2: ProfileTable invariants as hypothesis properties
# --------------------------------------------------------------------- #

def _random_table(seed: int, n_levels: int, n_trad: int,
                  n_caps: int) -> ProfileTable:
    """Random mixed family: ``n_trad`` traditional candidates plus one
    ``n_levels``-deep anytime group, power grid from the 1/f
    extrapolation (the only measured-table latency source)."""
    rng = np.random.default_rng(seed)
    cands = [Candidate(f"trad{t}", 0.0, 0.0,
                       float(rng.uniform(0.2, 0.9)))
             for t in range(n_trad)]
    accs = np.sort(rng.uniform(0.1, 0.95, size=n_levels))
    cands += [Candidate(f"level{k + 1}", 0.0, 0.0, float(accs[k]),
                        is_anytime_level=n_levels > 1,
                        anytime_group="g" if n_levels > 1 else None,
                        level=k + 1)
              for k in range(n_levels)]
    base = rng.uniform(1e-4, 0.5, size=len(cands))
    caps, lat, pw = extrapolate_power_buckets(base, PM, n_caps)
    return ProfileTable(cands, caps, lat, pw, q_fail=0.01)


def _fresh_tensors(table: ProfileTable):
    """Staircase tensors rebuilt from scratch (no cache sharing path)."""
    rebuilt = ProfileTable(list(table.candidates), table.power_caps,
                           table.latency, table.run_power,
                           q_fail=table.q_fail)
    return rebuilt.staircase_tensors()


def _tensors_equal(a, b) -> bool:
    return (np.array_equal(a.lvl_lat, b.lvl_lat)
            and np.array_equal(a.lvl_acc, b.lvl_acc)
            and np.array_equal(a.lvl_valid, b.lvl_valid)
            and np.array_equal(a.n_levels, b.n_levels))


class TestProfileTableProperties:
    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10**6), n_levels=st.integers(1, 4),
           n_trad=st.integers(0, 3), n_caps=st.integers(1, 6))
    def test_power_bucket_ordering(self, seed, n_levels, n_trad, n_caps):
        t = _random_table(seed, n_levels, n_trad, n_caps)
        assert np.all(np.diff(t.power_caps) >= 0)
        # 1/f rule: raising the cap never slows anything down, and the
        # operating-point draw never decreases.
        assert np.all(np.diff(t.latency, axis=1) <= 1e-12)
        assert np.all(np.diff(t.run_power, axis=1) >= -1e-12)

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10**6), n_levels=st.integers(1, 4),
           n_trad=st.integers(0, 3), n_caps=st.integers(1, 6))
    def test_padded_unpadded_consistency(self, seed, n_levels, n_trad,
                                         n_caps):
        t = _random_table(seed, n_levels, n_trad, n_caps)
        st_ = t.staircase_tensors()
        rows = t.staircase_rows()
        for i, r in rows.items():
            n = len(r)
            assert st_.n_levels[i] == n
            assert np.array_equal(st_.lvl_lat[i, :n], t.latency[r])
            assert st_.lvl_acc[i, :n].tolist() == \
                [t.candidates[j].accuracy for j in r]
            assert st_.lvl_valid[i, :n].all()
            assert not st_.lvl_valid[i, n:].any()

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10**6), n_levels=st.integers(2, 4),
           n_trad=st.integers(0, 3), n_caps=st.integers(1, 6))
    def test_staircase_monotone_through_harness(self, seed, n_levels,
                                                n_trad, n_caps):
        rng = np.random.default_rng(seed)
        clock = FakeClock()
        fns = fake_level_fns(clock,
                             rng.uniform(1e-4, 0.2, n_levels).tolist())
        accs = rng.uniform(0.05, 0.95, n_levels).tolist()  # unsorted!
        t = profile_anytime_measured(fns, accs, PM,
                                     n_power_buckets=n_caps, clock=clock)
        st_ = t.staircase_tensors()
        for i in range(len(t.candidates)):
            n = int(st_.n_levels[i])
            assert np.all(np.diff(st_.lvl_acc[i, :n]) >= 0)
        assert t.accuracies.tolist() == \
            np.maximum.accumulate(accs).tolist()

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10**6), n_levels=st.integers(1, 4),
           n_trad=st.integers(1, 3), n_caps=st.integers(1, 6))
    def test_subset_shares_cache_on_whole_groups(self, seed, n_levels,
                                                 n_trad, n_caps):
        t = _random_table(seed, n_levels, n_trad, n_caps)
        t.staircase_tensors()
        rng = np.random.default_rng(seed + 1)
        # Keep the whole anytime group + a random subset of trads:
        # prefixes survive, so the parent cache must carry over without
        # a rebuild (installed eagerly on the subset).
        keep_trad = [i for i in range(n_trad) if rng.random() < 0.5]
        idx = keep_trad + list(range(n_trad, n_trad + n_levels))
        sub = t.subset(idx)
        assert getattr(sub, "_staircase_cache", None) is not None
        assert _tensors_equal(sub.staircase_tensors(),
                              _fresh_tensors(sub))

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10**6), n_levels=st.integers(2, 4),
           n_trad=st.integers(0, 3), n_caps=st.integers(1, 6))
    def test_subset_mid_prefix_rebuilds_lazily(self, seed, n_levels,
                                               n_trad, n_caps):
        t = _random_table(seed, n_levels, n_trad, n_caps)
        t.staircase_tensors()
        # Drop level 1: every surviving level's prefix is cut, so the
        # parent tensors are WRONG for the subset — the cache must not
        # carry over, and the lazy rebuild must match a fresh build
        # (the kept levels re-anchor as a shorter staircase).
        idx = list(range(n_trad)) + \
            list(range(n_trad + 1, n_trad + n_levels))
        sub = t.subset(idx)
        assert getattr(sub, "_staircase_cache", None) is None
        assert _tensors_equal(sub.staircase_tensors(),
                              _fresh_tensors(sub))

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10**6), n_levels=st.integers(1, 4),
           n_trad=st.integers(0, 3), n_caps=st.integers(2, 6))
    def test_power_subset_consistency(self, seed, n_levels, n_trad,
                                      n_caps):
        t = _random_table(seed, n_levels, n_trad, n_caps)
        t.staircase_tensors()
        rng = np.random.default_rng(seed + 2)
        idx = sorted(rng.choice(n_caps, size=rng.integers(1, n_caps + 1),
                                replace=False).tolist())
        sub = t.power_subset(idx)
        assert sub.power_caps.tolist() == t.power_caps[idx].tolist()
        assert np.array_equal(sub.latency, t.latency[:, idx])
        # Candidates untouched -> the cache always carries over sliced.
        assert getattr(sub, "_staircase_cache", None) is not None
        assert _tensors_equal(sub.staircase_tensors(),
                              _fresh_tensors(sub))


# --------------------------------------------------------------------- #
# the end-to-end live path (one training run shared module-wide)
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def trained():
    """The jointly-trained reduced alert_anytime family (default seed —
    the same training the golden generator runs)."""
    return train_reduced_anytime()


@pytest.fixture(scope="module")
def live_cfg(trained):
    """The golden live-profile scenario built from the shared training."""
    from tests.make_golden_traces import live_profile_config
    return live_profile_config(trained)


class TestLiveProfile:
    def test_fake_clock_table_is_deterministic(self, trained):
        a = live_profile_table(trained)
        b = live_profile_table(trained)
        assert np.array_equal(a.latency, b.latency)
        assert np.array_equal(a.run_power, b.run_power)
        assert a.accuracies.tolist() == b.accuracies.tolist()

    def test_staircase_is_real_and_separated(self, trained):
        table = live_profile_table(trained)
        accs = table.accuracies
        # The trained model genuinely beats chance at every level and
        # deeper levels genuinely know more — a live staircase, not the
        # synthetic one.
        assert np.all(accs > table.q_fail)
        assert np.all(np.diff(accs) > 0)
        # Latency follows the true nested-FLOP fractions of the config.
        fracs = level_flop_fractions(trained.cfg)
        assert fracs[-1] == pytest.approx(1.0)
        assert np.all(np.diff(fracs) > 0)
        ratio = table.latency[:, -1] / table.latency[-1, -1]
        assert ratio == pytest.approx(fracs)

    def test_golden_live_profile_pinned(self, live_cfg):
        """Golden-trace pin of the whole measured path: training, eval
        accuracies, the fake-clock measurement, table assembly, and the
        controller's picks + dispositions on the seed-1 workload.  Run
        ``python tests/make_golden_traces.py`` ONLY on intentional
        semantic change."""
        from tests.make_golden_traces import compute_live_profile_golden
        with open(GOLDEN) as f:
            want = json.load(f)["live_profile"]
        got = compute_live_profile_golden(live_cfg)
        assert got == want

    def test_megatick_parity_bitwise_on_live_path(self, live_cfg):
        """The device-resident round clock serves the live-profile table
        (and both derived baseline tables) bitwise-identically to the
        host loop."""
        from repro.traffic import (MegatickGateway, SessionGateway,
                                   app_only_table, generate_requests,
                                   sys_only_table)
        table, sessions, n_lanes, deadline = live_cfg
        reqs = generate_requests(sessions)
        fields = ("sid", "index", "arrival", "status", "start", "latency",
                  "sojourn", "missed", "accuracy", "energy",
                  "model_index", "power_index")
        for tab in (table, app_only_table(table), sys_only_table(table)):
            h = SessionGateway(tab, n_lanes, tick=deadline,
                               max_queue=4 * n_lanes).run(sessions, reqs)
            m = MegatickGateway(tab, n_lanes, tick=deadline,
                                max_queue=4 * n_lanes).run(sessions, reqs)
            for f in fields:
                assert np.array_equal(getattr(h, f), getattr(m, f)), f

    def test_live_sweep_beats_adaptation_baselines(self, live_cfg):
        """ALERT picking real model x level x power configs beats both
        single-dimension adaptation baselines on the same seeded
        workload: less energy per good request than app-only at matched
        goodput, and both less energy and fewer SLO misses than
        sys-only."""
        from repro.core.controller import Constraints, Goal
        from repro.serving.sim import DEFAULT_ENV
        from repro.traffic import PoissonProcess, TenantSpec, sweep_loads
        from tests.make_golden_traces import live_accuracy_goal
        table = live_cfg[0]
        dl = 2.0 * float(table.latency[-1, -1])
        n_lanes, n_sessions = 16, 48
        mix = [TenantSpec("t", Goal.MINIMIZE_ENERGY,
                          Constraints(deadline=dl,
                                      accuracy_goal=live_accuracy_goal(
                                          table)),
                          PoissonProcess(0.5 * (n_lanes / dl)
                                         / n_sessions),
                          n_sessions=n_sessions, phases=DEFAULT_ENV)]
        rows = sweep_loads(table, mix, [0.5, 2.0], n_lanes=n_lanes,
                           horizon=10 * dl, seed=13,
                           max_queue=4 * n_lanes, tick=dl / 4,
                           schemes=("alert", "app_only", "sys_only"))
        matched = 0
        for r in rows:
            a = r["schemes"]["alert"]
            app = r["schemes"]["app_only"]
            sysd = r["schemes"]["sys_only"]
            assert a["n_compiles"] == [0, 1]  # flat across the sweep
            if a["slo_miss_rate"] <= 0.05 and \
                    app["slo_miss_rate"] <= 0.05:
                matched += 1
                assert a["energy_per_good_j"] < app["energy_per_good_j"]
                assert a["energy_per_good_j"] < sysd["energy_per_good_j"]
                assert a["slo_miss_rate"] <= sysd["slo_miss_rate"]
        assert matched > 0


# --------------------------------------------------------------------- #
# satellite 4: the §8 zero-recompile contract at request granularity
# --------------------------------------------------------------------- #

class TestZeroRecompile:
    def test_level_switching_never_recompiles(self, trained):
        """``n_compiles`` stays flat while the controller switches
        anytime levels across requests mid-sweep — one trace per level
        executable, ever."""
        from repro.serving.engine import ServeEngine
        engine = ServeEngine(trained.model, max_len=14, batch_size=2)
        clock = FakeClock()
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, trained.cfg.vocab, size=(2, 8),
                              dtype=np.int32)
        n_levels = trained.cfg.nest_levels
        # Warmup: one request per level traces prefill + decode once.
        for lvl in range(1, n_levels + 1):
            engine.generate(trained.params, prompt, 3, level=lvl,
                            clock=clock)
        warm = engine.n_compiles()
        assert warm == (n_levels, n_levels)
        # Mid-sweep: the controller hops levels request to request.
        for lvl in (2, 3, 1, 3, 2, 1, 3):
            out = engine.generate(trained.params, prompt, 3,
                                  level=min(lvl, n_levels), clock=clock)
            assert out["tokens"].shape == (2, 3)
            assert out["complete"]
        assert engine.n_compiles() == warm

    def test_generate_deadline_uses_injected_clock(self, trained):
        """A fake clock that jumps past the deadline after dispatch makes
        generate stop early — no real timer involved."""
        from repro.serving.engine import ServeEngine
        engine = ServeEngine(trained.model, max_len=14, batch_size=1)
        prompt = np.zeros((1, 4), dtype=np.int32)

        class JumpClock:
            """0 at start, way past any deadline on every later read."""

            def __init__(self):
                self.reads = 0

            def __call__(self):
                self.reads += 1
                return 0.0 if self.reads == 1 else 1e9

        out = engine.generate(trained.params, prompt, 6, level=1,
                              deadline_s=0.5, clock=JumpClock())
        assert not out["complete"]
        assert out["tokens"].shape == (1, 1)  # prefill token only


# --------------------------------------------------------------------- #
# the derived baseline tables
# --------------------------------------------------------------------- #

class TestBaselineTables:
    def test_app_only_pins_system_default_power(self):
        from repro.traffic import app_only_table
        t = _random_table(7, 3, 2, 5)
        t.staircase_tensors()
        app = app_only_table(t)
        assert app.power_caps.tolist() == [t.power_caps[-1]]
        assert np.array_equal(app.latency, t.latency[:, -1:])
        assert len(app.candidates) == len(t.candidates)

    def test_sys_only_freezes_most_accurate_candidate(self):
        from repro.traffic import sys_only_table
        t = _random_table(7, 3, 2, 5)
        sys_ = sys_only_table(t)
        assert len(sys_.candidates) == 1
        assert sys_.candidates[0].accuracy == t.accuracies.max()
        assert sys_.power_caps.tolist() == t.power_caps.tolist()
        # Frozen app = no anytime early exit: a 1-level staircase.
        assert sys_.staircase_tensors().n_levels.tolist() == [1]
