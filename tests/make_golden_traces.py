"""Regenerate ``tests/golden_traces.json`` — the checked-in scheme-drift
fixtures asserted by ``tests/test_serving.py``.

For each environment (``default``/``cpu``/``memory``) the fixture records
the ``alert`` and ``oracle`` schemes' mean energy / mean error / miss rate
on a fixed seed-1 trace, plus the alert-vs-oracle gaps.  Any change to
controller semantics (estimation, selection, relaxation, feedback, the
windowed goal, delivery) moves these numbers and fails the regression
test; re-run this script ONLY when a semantic change is intentional:

    PYTHONPATH=src python tests/make_golden_traces.py
"""

from __future__ import annotations

import json
import os
import sys

from repro.core.controller import Constraints, Goal
from repro.serving.sim import ENVS, EnvironmentTrace, InferenceSim

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # allow `python tests/make_golden_traces.py`
    sys.path.insert(0, _ROOT)

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "golden_traces.json")

GOLDEN_SEED = 1
GOLDEN_BUDGET_W = 170.0


def golden_config():
    """The fixed scenario both the generator and the test rebuild."""
    from benchmarks.common import deadline_range, family_table

    table = family_table("image")
    deadline = float(deadline_range(table, 3)[1])
    cons = Constraints.from_power_budget(deadline, GOLDEN_BUDGET_W)
    return table, cons


def gateway_config(table):
    """Fixed overloaded multi-tenant gateway scenario (seed-1, 2x the
    lane-saturating rate) shared by the generator and
    ``tests/test_traffic.py``'s golden-trace assertion."""
    from benchmarks.common import deadline_range
    from repro.serving.sim import CPU_ENV, MEMORY_ENV
    from repro.traffic import PoissonProcess, TenantSpec, build_sessions

    deadline = float(deadline_range(table, 5)[3])
    n_lanes, per_tenant = 8, 12
    rate = 2.0 * (n_lanes / deadline) / (2 * per_tenant)
    mix = [
        TenantSpec("minE", Goal.MINIMIZE_ENERGY,
                   Constraints(deadline=deadline, accuracy_goal=0.78),
                   PoissonProcess(rate), n_sessions=per_tenant,
                   phases=CPU_ENV),
        TenantSpec("maxA", Goal.MAXIMIZE_ACCURACY,
                   Constraints.from_power_budget(deadline,
                                                 GOLDEN_BUDGET_W),
                   PoissonProcess(rate), n_sessions=per_tenant,
                   phases=MEMORY_ENV),
    ]
    sessions = build_sessions(mix, 12 * deadline, seed=GOLDEN_SEED)
    return sessions, n_lanes, deadline


def summarize_gateway(res) -> dict:
    """Flatten a GatewayResult into the drift-pinned summary floats."""
    from repro.traffic.gateway import (REJECTED_BACKPRESSURE,
                                       REJECTED_INFEASIBLE, SERVED)

    status = res.status
    return {
        "offered": int(status.size),
        "served": int((status == SERVED).sum()),
        "rejected_infeasible": int((status == REJECTED_INFEASIBLE).sum()),
        "rejected_backpressure": int(
            (status == REJECTED_BACKPRESSURE).sum()),
        "good": int(res.good.sum()),
        "goodput_rps": res.goodput,
        "energy_sum_j": float(res.energy[status == SERVED].sum()),
        "p50_sojourn_s": res.percentile_sojourn(50),
        "p99_sojourn_s": res.percentile_sojourn(99),
        "served_miss_rate": res.served_miss_rate,
        "n_rounds": res.n_rounds,
        "pages_in": res.pages_in,
        "pages_out": res.pages_out,
        "horizon_s": res.horizon,
    }


def compute_gateway_golden(table) -> dict:
    """Golden gateway disposition: the seed-1 overload workload served
    by the host round loop (the megatick is asserted bitwise-identical
    to the host separately, so one fixture pins both)."""
    from repro.traffic import SessionGateway, generate_requests

    sessions, n_lanes, deadline = gateway_config(table)
    gw = SessionGateway(table, n_lanes, tick=deadline,
                        max_queue=4 * n_lanes)
    res = gw.run(sessions, generate_requests(sessions))
    return summarize_gateway(res)


def straggler_config(table):
    """Pinned single-tenant straggler scenario shared by the generator
    and ``tests/test_faults.py``: ``n_sessions == n_lanes`` (no paging,
    so the lane<->session identity is stable and per-lane detection is
    well-posed), one lane ramping to 3x slow-down mid-run."""
    from benchmarks.common import deadline_range
    from repro.serving.sim import CPU_ENV
    from repro.traffic import PoissonProcess, TenantSpec, build_sessions
    from repro.traffic.faults import FaultSchedule, LaneStraggler

    deadline = float(deadline_range(table, 5)[3])
    n_lanes = 8
    mix = [TenantSpec("t", Goal.MINIMIZE_ENERGY,
                      Constraints(deadline=deadline, accuracy_goal=0.78),
                      PoissonProcess(0.8 / deadline), n_sessions=n_lanes,
                      phases=CPU_ENV)]
    sessions = build_sessions(mix, 40 * deadline, seed=7)
    faults = FaultSchedule(n_lanes, [LaneStraggler(
        lane=5, start=10 * deadline, magnitude=2.0,
        ramp_s=5 * deadline)], seed=0)
    return sessions, n_lanes, deadline, faults


def compute_straggler_golden(table) -> dict:
    """Golden detection trace: the Kalman-bank detector's trip set and
    latency on the pinned straggler scenario, plus the clean-trace
    false-positive count (must stay zero)."""
    import numpy as np

    from repro.traffic import SessionGateway, generate_requests
    from repro.traffic.faults import KalmanLaneDetector

    sessions, n_lanes, deadline, faults = straggler_config(table)
    det = KalmanLaneDetector(n_lanes)
    gw = SessionGateway(table, n_lanes, tick=deadline)
    gw.run(sessions, generate_requests(sessions), faults=faults,
           detector=det)
    clean_det = KalmanLaneDetector(n_lanes)
    gw2 = SessionGateway(table, n_lanes, tick=deadline)
    gw2.run(sessions, generate_requests(sessions), detector=clean_det)
    return {
        "fault_lane": 5,
        "fault_start_rounds": 10,
        "tripped_lanes": [int(x) for x in np.nonzero(det.tripped)[0]],
        "first_trip_time_s": float(det.first_trip_time[5]),
        "detection_latency_rounds": float(
            det.detection_latency(5, 10 * deadline) / deadline),
        "clean_false_positives": int(clean_det.tripped.sum()),
    }


def live_accuracy_goal(table) -> float:
    """Accuracy goal of the live-profile scenarios: 90% of the measured
    middle level's accuracy, so the middle level meets the goal and the
    shallowest does not, whatever the training run's exact numerics."""
    return 0.9 * float(table.accuracies[1])


def live_profile_config(trained=None):
    """Fixed live-profile gateway scenario (DESIGN.md §12) shared by the
    generator and ``tests/test_profiling.py``: the reduced
    ``alert_anytime`` family jointly trained on the seeded synthetic
    task, its staircase measured through the FAKE clock seam (zero
    wall-clock dependence — this fixture is bit-reproducible), served
    at ~1.2x lane saturation in the coarse-tick regime so the same
    config also pins megatick parity.  ``trained`` lets the test module
    reuse its one default-parameter training run; the generator trains
    fresh."""
    from repro.core.controller import Constraints, Goal
    from repro.profiling import live_profile_table, train_reduced_anytime
    from repro.serving.sim import DEFAULT_ENV
    from repro.traffic import PoissonProcess, TenantSpec, build_sessions

    if trained is None:
        trained = train_reduced_anytime()
    table = live_profile_table(trained)
    deadline = 2.0 * float(table.latency[-1, -1])
    n_lanes, n_sessions = 8, 24
    cons = Constraints(deadline=deadline,
                       accuracy_goal=live_accuracy_goal(table))
    mix = [TenantSpec("live", Goal.MINIMIZE_ENERGY, cons,
                      PoissonProcess(
                          1.2 * (n_lanes / deadline) / n_sessions),
                      n_sessions=n_sessions, phases=DEFAULT_ENV)]
    sessions = build_sessions(mix, 12 * deadline, seed=GOLDEN_SEED)
    return table, sessions, n_lanes, deadline


def compute_live_profile_golden(config=None) -> dict:
    """Golden live-profile trace: the measured (fake-clock) staircase the
    trained model profiles to, and the controller's per-level / per-cap
    pick histogram plus dispositions when ALERT serves the seed-1
    workload from that table.  Pins the WHOLE measured path: training,
    eval accuracy, the clock seam, table assembly, and selection."""
    from repro.traffic import SessionGateway, generate_requests
    from repro.traffic.gateway import SERVED

    table, sessions, n_lanes, deadline = \
        config if config is not None else live_profile_config()
    gw = SessionGateway(table, n_lanes, tick=deadline,
                        max_queue=4 * n_lanes)
    res = gw.run(sessions, generate_requests(sessions))
    out = summarize_gateway(res)
    served = res.status == SERVED
    k, l = table.latency.shape
    out["level_accuracies"] = [float(a) for a in table.accuracies]
    out["level_latencies_full_cap"] = [float(x)
                                       for x in table.latency[:, -1]]
    out["q_fail"] = float(table.q_fail)
    out["model_picks"] = [int((res.model_index[served] == i).sum())
                          for i in range(k)]
    out["power_picks"] = [int((res.power_index[served] == j).sum())
                          for j in range(l)]
    return out


def compute_golden() -> dict:
    table, cons = golden_config()
    out = {"seed": GOLDEN_SEED, "budget_w": GOLDEN_BUDGET_W,
           "goal": "maximize_accuracy", "envs": {},
           "gateway": compute_gateway_golden(table),
           "straggler": compute_straggler_golden(table),
           "live_profile": compute_live_profile_golden()}
    for env_name in ("default", "cpu", "memory"):
        trace = EnvironmentTrace(ENVS[env_name], seed=GOLDEN_SEED)
        sim = InferenceSim(table, trace)
        rows = {}
        for scheme in ("alert", "oracle"):
            r = sim.run_scheme(scheme, Goal.MAXIMIZE_ACCURACY, cons)
            rows[scheme] = {"mean_energy": r.mean_energy,
                            "mean_error": r.mean_error,
                            "miss_rate": r.miss_rate}
        rows["gap"] = {
            "energy": rows["alert"]["mean_energy"]
            - rows["oracle"]["mean_energy"],
            "error": rows["alert"]["mean_error"]
            - rows["oracle"]["mean_error"],
        }
        out["envs"][env_name] = rows
    return out


def main() -> None:
    data = compute_golden()
    with open(OUT, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {OUT}")
    for env, rows in data["envs"].items():
        print(f"  {env:8s} alert e={rows['alert']['mean_energy']:.4f} "
              f"err={rows['alert']['mean_error']:.4f}  gap "
              f"e={rows['gap']['energy']:+.4f} "
              f"err={rows['gap']['error']:+.4f}")


if __name__ == "__main__":
    main()
