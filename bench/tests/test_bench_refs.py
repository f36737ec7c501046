"""The plain references agree with the program at small sizes on the CPU.

The references import nothing of the program; these tests hold the two
side by side on seeded inputs: the ALERT grid, picks and filters; the
round clock's dispositions and paging; the nested LM's logits and the
weights' layout.
"""

import numpy as np
import pytest

from bench import alert_ref as ref
from bench import fleet_check, fleet_inputs, lm_ref, profiles, traffic_gen
from bench.tests import tiny


@pytest.fixture(scope="module")
def fleet():
    """The fleet configuration at a small size."""
    cfg = tiny.load(f"{tiny.REPO}/bench/configs/fleet-image-100k.json")
    cfg.update(tiny.TINY_CONFIGS["fleet-image-100k"])
    return fleet_inputs.resolve(cfg)


def test_fleet_table_matches_the_programs_benchmark_table(fleet):
    """The copied candidate table equals the one the program's
    benchmarks build from the sizes of the architectures each row
    names under ``sizes_of``."""
    from repro import configs
    from repro.core.power import PowerModel
    from repro.core.profiles import Candidate, profile_from_roofline

    cfg = fleet.cfg
    for a in cfg["family"]:
        c = configs.get_config(a["sizes_of"])
        assert (a["active_params"], a["params"], a["d_model"],
                a["n_layers"]) == (c.active_param_count(), c.param_count(),
                                   c.d_model, c.n_layers)
    cands = [Candidate(c["name"], c["flops"], c["bytes_hbm"], c["accuracy"],
                       is_anytime_level=c.get("anytime_level", 0) > 0,
                       anytime_group="a" if c.get("anytime_level") else None,
                       level=c.get("anytime_level", 0))
             for c in profiles.family_candidates(cfg)]
    want = profile_from_roofline(cands, PowerModel(60.0, 200.0),
                                 n_power_buckets=8, q_fail=0.001)
    np.testing.assert_array_equal(fleet.table.latency, want.latency)
    np.testing.assert_array_equal(fleet.table.run_power, want.run_power)
    assert fleet.table.stairs == [want.staircase_rows()[k]
                                  for k in range(len(cands))]
    assert fleet.t_goal == pytest.approx(1.6 * fleet.table.latency[-1, -1])


def test_select_matches_the_batched_engine(fleet):
    """Eq. 4/5 picks of the reference equal the engine's on every clear
    decision of a mixed fleet."""
    from repro.core.batched import BatchedAlertEngine

    rng = np.random.default_rng(5)
    s = 512
    t = fleet.table
    mu, sd = rng.uniform(0.6, 2.5, s), rng.uniform(0.01, 0.4, s)
    phi = rng.uniform(0.05, 0.6, s)
    dl = rng.choice(profiles.deadline_range(t, 5), s)
    ag = rng.uniform(0.5, 0.9, s)
    eg = rng.uniform(0.5, 3.0, s) * float(np.median(t.run_power)
                                          * np.median(t.latency))
    gk = rng.integers(0, 2, s)
    eng = BatchedAlertEngine(fleet_inputs.program_table(t), None)
    got = eng.select(mu, sd, phi, dl, accuracy_goal=ag, energy_goal=eg,
                     goal_kind=gk.astype(np.int64), active=np.ones(s, bool))
    est = eng.estimate(mu, sd, phi, dl)
    acc, en = ref.estimate(t, mu, sd, phi, dl)
    np.testing.assert_allclose(acc, est.accuracy, rtol=0, atol=1e-14)
    np.testing.assert_allclose(en, est.energy, rtol=1e-14)
    own = ref.select(acc, en, gk, ag, eg)
    ok = ref.clear(acc, en, gk, ag, eg, 1e-9)
    n_l = t.latency.shape[1]
    pick = got.model_index * n_l + got.power_index
    assert ok.mean() > 0.9
    assert np.all(own[ok] == pick[ok])


def test_filters_match_the_programs_scalar_filters():
    """Eq. 6 and Eq. 8 steps equal the program's scalar filters."""
    from repro.core.kalman import IdlePowerFilter, SlowdownFilter

    rng = np.random.default_rng(3)
    sf, pf = SlowdownFilter(), IdlePowerFilter()
    st = {k: np.full(1, v) for k, v in ref.SLOW_PRIOR.items()}
    phi, var = np.full(1, 0.3), np.full(1, 0.01)
    for _ in range(50):
        obs, prof = rng.uniform(0.01, 0.2), rng.uniform(0.01, 0.2)
        miss = bool(rng.random() < 0.3)
        sf.observe(obs, prof, deadline_missed=miss)
        st = ref.slowdown_step(st, [obs], [prof], [miss])
        pw = rng.uniform(60, 200)
        pf.observe(0.25 * pw, pw)
        phi, var = ref.idle_step(phi, var, [0.25 * pw], [pw])
    assert (st["mu"][0], st["sigma"][0], st["gain"][0], st["q"][0]) == \
        (sf.mu, sf.sigma, sf.gain, sf.process_noise)
    assert (phi[0], var[0]) == (pf.phi, pf.variance)


@pytest.mark.parametrize("tick_x", [1.0, 0.25])
def test_round_clock_matches_the_session_gateway(fleet, tick_x):
    """Dispositions, rounds and paging of the reference equal the
    program's host gateway, at the deadline tick and at a finer tick
    where busy lanes hold sessions back."""
    from repro.traffic.gateway import SessionGateway

    traffic = {"arrivals": {"kind": "poisson", "rate_x": 1.2},
               "horizon_x": 5}
    reqs = fleet_inputs.draw(fleet, traffic, 17)
    sessions, requests = fleet_inputs.program_workload(fleet, reqs)
    tick = tick_x * fleet.t_goal
    gw = SessionGateway(fleet_inputs.program_table(fleet.table),
                        fleet.lanes, tick=tick, max_queue=fleet.max_queue)
    res = gw.run(sessions, requests)
    adm = ref.admit(reqs.arrival, reqs.rel, reqs.sid, n_lanes=fleet.lanes,
                    tick=tick, max_queue=fleet.max_queue,
                    min_feasible=float(fleet.table.latency.min()),
                    latency=res.latency if tick_x < 1 else None)
    np.testing.assert_array_equal(adm.status, res.status)
    np.testing.assert_array_equal(adm.start, res.start)
    assert (adm.n_rounds, adm.pages_in, adm.pages_out) == \
        (res.n_rounds, res.pages_in, res.pages_out)
    assert res.pages_in > 0


def test_control_is_told_apart_from_the_program(fleet):
    """The float32 reference, put in the program's place, fails the
    comparison that the float64 reference passes."""
    traffic = {"arrivals": {"kind": "poisson", "rate_x": 1.0},
               "horizon_x": 4}
    reqs = fleet_inputs.draw(fleet, traffic, 23)
    kw = dict(gateway=fleet.gateway(fleet.t_goal),
              sessions=fleet.sessions(), busy=False)
    ctrl = fleet_check.control_program(fleet.table, reqs, **kw)
    got = fleet_check.compare(fleet.table, reqs, ctrl, margin=1e-9, **kw)
    assert got["outcome_gap"] > 1e-8
    same = fleet_check.control_program(fleet.table, reqs, dtype=np.float64,
                                       **kw)
    got = fleet_check.compare(fleet.table, reqs, same, margin=1e-9, **kw)
    assert got["admission_mismatches"] == 0 == got["pick_mismatches"]
    assert got["outcome_gap"] == 0.0


def test_traffic_is_a_function_of_the_seed(fleet):
    """The same seed draws the same requests; another seed does not."""
    tr = {"arrivals": {"kind": "mmpp", "rates_x": [0.5, 2.0],
                       "dwells_x": [1.0, 0.3]}, "horizon_x": 8}
    a = fleet_inputs.draw(fleet, tr, 2 ** 33 + 1)
    b = fleet_inputs.draw(fleet, tr, 2 ** 33 + 1)
    c = fleet_inputs.draw(fleet, tr, 2 ** 33 + 2)
    np.testing.assert_array_equal(a.arrival, b.arrival)
    np.testing.assert_array_equal(a.scale, b.scale)
    assert a.arrival.size != c.arrival.size or \
        not np.array_equal(a.arrival, c.arrival)
    assert np.all(np.diff(a.arrival) >= 0)
    assert np.all(a.scale > 0)


def test_phases_follow_the_scaled_schedule():
    """Each session's inputs take the phase schedule scaled to its count
    (half to even), the last phase taking the rest."""
    phases = [{"n_inputs": 80}, {"n_inputs": 240}, {"n_inputs": 80}]
    for n in range(0, 12):
        idx = np.arange(n)
        got = traffic_gen.phase_of(idx, np.full(n, n), phases)
        a = int(round(n * 80 / 400))
        b = min(int(round(n * 240 / 400)), n - a)
        want = [0] * a + [1] * b + [2] * (n - a - b)
        assert got.tolist() == want


LM_CFG = dict(tiny.TINY_CONFIGS["alert-anytime-120m"], nest_levels=4,
              rope_theta=1e4, norm_eps=1e-6, name="t", family="dense",
              tie_embeddings=False)


def test_weights_have_the_programs_layout():
    """The benchmark's weights have the tree, shapes and dtypes of the
    program's own initialiser."""
    import jax

    from bench.drivers.alert_server import model_config
    from repro.models.registry import build_model

    cfg = dict(LM_CFG, dtype="bfloat16")
    mine = lm_ref.make_params(cfg, 2 ** 31 + 9)
    theirs = build_model(model_config(cfg)).init(jax.random.PRNGKey(0))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)
    assert shape(mine) == shape(theirs)


@pytest.mark.parametrize("level", [1, 2, 4])
def test_reference_logits_match_the_programs_prefill(level):
    """The plain nested forward equals the program's prefill logits in
    float32 at every level."""
    import jax

    from bench.drivers.alert_server import model_config
    from repro.models import transformer as tfm

    params = lm_ref.make_params(LM_CFG, 4)
    toks = np.random.default_rng(0).integers(0, LM_CFG["vocab"], (2, 12))
    mc = model_config(LM_CFG)
    with jax.default_matmul_precision("highest"):
        want = tfm.lm_apply(params, mc, jax.numpy.asarray(toks),
                            mode="prefill", level=level).logits
    got = lm_ref.forward(params, LM_CFG, toks, level, 0, 12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
