"""Property-based parity: masked heterogeneous engine vs scalar reference.

Hypothesis drives random ProfileTables, per-lane goals/constraints/filter
state, and active-lane masks (with adversarial garbage — NaN/inf/negative —
injected into every dead lane's inputs) and asserts, lane by lane:

* active lanes pick EXACTLY what the frozen float64 NumPy reference
  (:mod:`repro.core.reference`) picks for that lane's goal/constraints,
  including feasibility and the Section 3.3 relaxation branch;
* dead lanes come back as deterministic nulls (indices 0, zero
  predictions, infeasible-free, no relaxation) no matter what garbage
  their slots hold;
* the masked fused Kalman-bank update equals scalar filters stepped only
  on the masked-in ticks.

The checks are plain functions (``check_*``) so the same assertions can be
exercised without hypothesis; the ``@given`` wrappers only draw inputs.
Runs under ``tests/_hypothesis_compat``: where hypothesis is missing the
property tests skip and the deterministic smoke test below still covers
one fixed example of each property.
"""

from __future__ import annotations

import numpy as np

from repro.core.batched import (BatchedAlertEngine, GOAL_MAX_ACCURACY,
                                GOAL_MIN_ENERGY, RELAXED_NAMES)
from repro.core.controller import Constraints, Goal
from repro.core.kalman import SlowdownFilter, SlowdownFilterBank
from repro.core.reference import ScalarReferenceController
from benchmarks.controller_bench import random_table

from tests._hypothesis_compat import given, settings, st

# Values planted in every input vector's dead lanes: masking must make all
# of them inert (no NaN leaks into live lanes, no crashes, null outputs).
GARBAGE = (np.nan, np.inf, -np.inf, -1.0, 0.0, 1e300)

_KINDS = {GOAL_MIN_ENERGY: Goal.MINIMIZE_ENERGY,
          GOAL_MAX_ACCURACY: Goal.MAXIMIZE_ACCURACY}


# ------------------------------------------------------------------ #
# plain checkers (hypothesis-independent)                            #
# ------------------------------------------------------------------ #
def check_select_parity(table_seed: int, lanes: list[dict],
                        overhead_frac: float, garbage_idx: int,
                        backend: str = "xla") -> None:
    """One heterogeneous masked select vs per-lane scalar references.

    ``backend="pallas"`` runs the same property through the fused
    `alert_select` kernel under its margin contract: the pick checks
    apply to every lane whose float64 decision clears the tie margins
    (docs/KERNELS.md); dead lanes and the float64 estimates are checked
    as for XLA."""
    from repro.kernels.alert_select import clear_lanes

    rng = np.random.default_rng(table_seed)
    table = random_table(rng)
    med_lat = float(np.median(table.latency))
    med_en = float(np.median(table.run_power)) * med_lat
    overhead = overhead_frac * med_lat

    s = len(lanes)
    mus = np.asarray([ln["mu"] for ln in lanes])
    sds = np.asarray([ln["sigma"] for ln in lanes])
    phis = np.asarray([ln["phi"] for ln in lanes])
    dls = np.asarray([ln["dl_frac"] for ln in lanes]) * med_lat
    gk = np.asarray([ln["kind"] for ln in lanes], dtype=np.int64)
    qgs = np.asarray([ln["q_goal"] for ln in lanes])
    egs = np.asarray([ln["e_frac"] for ln in lanes]) * med_en
    active = np.asarray([ln["active"] for ln in lanes], dtype=bool)
    garbage = GARBAGE[garbage_idx]
    for arr in (mus, sds, phis, dls, qgs, egs):
        arr[~active] = garbage

    engine = BatchedAlertEngine(table, None, overhead=overhead,
                                backend=backend)
    batch = engine.select(mus, sds, phis, dls, accuracy_goal=qgs,
                          energy_goal=egs, goal_kind=gk, active=active)
    est = engine.estimate(mus, sds, phis,
                          np.maximum(dls - overhead, 1e-9), active=active)
    clear = clear_lanes(est.accuracy, est.energy, qgs, egs, gk, active) \
        if backend == "pallas" else np.ones(s, bool)
    for i in range(s):
        if not active[i]:
            assert int(batch.model_index[i]) == 0
            assert int(batch.power_index[i]) == 0
            assert batch.predicted_latency[i] == 0.0
            assert batch.predicted_energy[i] == 0.0
            assert not batch.feasible[i]
            assert int(batch.relaxed_code[i]) == 0
            assert np.all(est.accuracy[i] == 0.0)
            assert np.all(est.energy[i] == 0.0)
            continue
        goal = _KINDS[int(gk[i])]
        ref = ScalarReferenceController(table, goal, overhead=overhead)
        ref.slowdown.mu = float(mus[i])
        ref.slowdown.sigma = float(sds[i])
        ref.idle_power.phi = float(phis[i])
        kw = {"accuracy_goal": float(qgs[i])} \
            if goal is Goal.MINIMIZE_ENERGY \
            else {"energy_goal": float(egs[i])}
        d = ref.select(Constraints(deadline=float(dls[i]), **kw))
        e = ref.estimate(max(float(dls[i]) - overhead, 1e-9))
        np.testing.assert_allclose(est.accuracy[i], e.accuracy,
                                   rtol=0, atol=1e-12)
        np.testing.assert_allclose(est.energy[i], e.energy,
                                   rtol=1e-12, atol=1e-12)
        if not clear[i]:
            continue
        assert d.model_index == int(batch.model_index[i]), f"lane {i}"
        assert d.power_index == int(batch.power_index[i]), f"lane {i}"
        assert d.feasible == bool(batch.feasible[i]), f"lane {i}"
        assert d.relaxed == RELAXED_NAMES[int(batch.relaxed_code[i])]


def check_masked_bank_parity(seed: int, n_streams: int,
                             n_steps: int) -> None:
    """Masked fused bank updates == scalar filters on masked-in ticks."""
    rng = np.random.default_rng(seed)
    bank = SlowdownFilterBank(n_streams)
    scalars = [SlowdownFilter() for _ in range(n_streams)]
    for _ in range(n_steps):
        obs = rng.uniform(0.3, 4.0, n_streams)
        prof = rng.uniform(0.2, 2.0, n_streams)
        miss = rng.random(n_streams) < 0.25
        mask = rng.random(n_streams) < 0.7
        bank.observe(obs, prof, deadline_missed=miss, mask=mask)
        for i, f in enumerate(scalars):
            if mask[i]:
                f.observe(float(obs[i]), float(prof[i]),
                          deadline_missed=bool(miss[i]))
    np.testing.assert_allclose(bank.mu, [f.mu for f in scalars],
                               rtol=1e-12, atol=0)
    np.testing.assert_allclose(bank.sigma, [f.sigma for f in scalars],
                               rtol=1e-12, atol=0)
    assert np.array_equal(bank.n_updates,
                          [f.n_updates for f in scalars])


# ------------------------------------------------------------------ #
# hypothesis drivers                                                 #
# ------------------------------------------------------------------ #
def _draw_lane(data) -> dict:
    return dict(
        mu=data.draw(st.floats(0.5, 3.0)),
        sigma=data.draw(st.floats(0.01, 0.5)),
        phi=data.draw(st.floats(0.05, 0.8)),
        dl_frac=data.draw(st.floats(0.1, 3.0)),
        kind=data.draw(st.sampled_from([GOAL_MIN_ENERGY,
                                        GOAL_MAX_ACCURACY])),
        q_goal=data.draw(st.floats(0.2, 1.1)),
        e_frac=data.draw(st.floats(0.0, 2.5)),
        active=data.draw(st.booleans()),
    )


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_select_parity_random_fleets(data):
    """Random table x heterogeneous lanes x masks: engine == reference."""
    table_seed = data.draw(st.integers(0, 2**31 - 1))
    n = data.draw(st.integers(1, 8))
    lanes = [_draw_lane(data) for _ in range(n)]
    overhead_frac = data.draw(st.floats(0.0, 0.2))
    garbage_idx = data.draw(st.integers(0, len(GARBAGE) - 1))
    check_select_parity(table_seed, lanes, overhead_frac, garbage_idx)


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_select_parity_random_fleets_pallas(data):
    """The fused Pallas kernel under the same property: random hetero
    fleets, garbage-laden dead lanes, both relaxation branches — picks
    equal to the scalar reference on every lane that clears the tie
    margins."""
    table_seed = data.draw(st.integers(0, 2**31 - 1))
    n = data.draw(st.integers(1, 8))
    lanes = [_draw_lane(data) for _ in range(n)]
    overhead_frac = data.draw(st.floats(0.0, 0.2))
    garbage_idx = data.draw(st.integers(0, len(GARBAGE) - 1))
    check_select_parity(table_seed, lanes, overhead_frac, garbage_idx,
                        backend="pallas")


@settings(max_examples=15, deadline=None)
@given(data=st.data())
def test_masked_bank_updates_match_scalar(data):
    """Random masked update schedules: bank lanes == scalar filters."""
    seed = data.draw(st.integers(0, 2**31 - 1))
    n_streams = data.draw(st.integers(1, 6))
    n_steps = data.draw(st.integers(1, 40))
    check_masked_bank_parity(seed, n_streams, n_steps)


# ------------------------------------------------------------------ #
# deterministic smoke (runs even without hypothesis)                 #
# ------------------------------------------------------------------ #
def test_parity_checkers_fixed_examples():
    rng = np.random.default_rng(123)
    for trial in range(6):
        n = int(rng.integers(1, 8))
        lanes = [dict(mu=float(rng.uniform(0.5, 3.0)),
                      sigma=float(rng.uniform(0.01, 0.5)),
                      phi=float(rng.uniform(0.05, 0.8)),
                      dl_frac=float(rng.uniform(0.1, 3.0)),
                      kind=int(rng.integers(0, 2)),
                      q_goal=float(rng.uniform(0.2, 1.1)),
                      e_frac=float(rng.uniform(0.0, 2.5)),
                      active=bool(rng.random() < 0.75))
                 for _ in range(n)]
        backend = "pallas" if trial % 2 else "xla"
        check_select_parity(int(rng.integers(2**31)), lanes,
                            float(rng.uniform(0, 0.2)),
                            int(rng.integers(len(GARBAGE))),
                            backend=backend)
    check_masked_bank_parity(7, 5, 30)
