"""The fleet cells' inputs, drawn by the benchmark and handed to the
program in its own types.

The program's gateways take ``ProfileTable``, ``Session`` (with an
``EnvironmentTrace``) and ``TrafficRequest`` objects.  Their contents come
from :mod:`bench.profiles` and :mod:`bench.traffic_gen`; an
``EnvironmentTrace`` is filled with the benchmark's own draws rather
than drawn by its constructor, which would redraw them per session.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import alert_ref, profiles, traffic_gen


@dataclasses.dataclass
class Fleet:
    """A fleet configuration resolved: the table, T_goal, lanes, queue
    bound and what every session shares."""

    cfg: dict
    table: profiles.Table
    t_goal: float
    lanes: int
    max_queue: int
    n_sessions: int

    @property
    def goal_kind(self) -> int:
        """The tenant's goal code (:mod:`bench.alert_ref`)."""
        return alert_ref.GOAL_MIN_ENERGY \
            if self.cfg["tenant"]["goal"] == "minimize_energy" \
            else alert_ref.GOAL_MAX_ACCURACY

    def gateway(self, tick: float) -> dict:
        """The round clock's parameters, as the reference takes them."""
        return {"n_lanes": self.lanes, "tick": tick,
                "max_queue": self.max_queue,
                "min_feasible": float(self.table.latency.min()),
                "phi_true": self.cfg["phi_true"]}

    def sessions(self) -> dict:
        """Per-session goals, as the reference takes them."""
        n = self.n_sessions
        return {"goal_kind": np.full(n, self.goal_kind),
                "acc_goal": np.full(n, self.cfg["tenant"]["accuracy_goal"]),
                "window": self.cfg["accuracy_window"]}


def resolve(cfg: dict) -> Fleet:
    """Build a fleet configuration's table and constants."""
    table = profiles.fleet_table(cfg)
    t_goal = float(profiles.deadline_range(
        table, cfg["deadline_points"])[cfg["t_goal_index"]])
    return Fleet(cfg, table, t_goal, cfg["lanes"],
                 cfg["max_queue_x_lanes"] * cfg["lanes"], cfg["sessions"])


def draw(fleet: Fleet, traffic: dict, seed: int):
    """A run's horizon: the offered requests over ``traffic["horizon_x"]``
    deadlines."""
    rng = traffic_gen.seed_rng(seed, 0)
    reqs = traffic_gen.fleet_requests(
        rng, fleet.n_sessions, traffic["arrivals"],
        traffic["horizon_x"] * fleet.t_goal, fleet.t_goal, fleet.lanes,
        fleet.cfg["phases"])
    reqs.rel = np.full(reqs.sid.size, fleet.t_goal)
    return reqs


def program_table(table: profiles.Table):
    """The table as the program's ``ProfileTable``."""
    from repro.core.profiles import Candidate, ProfileTable

    cands = [Candidate(name=n, flops=0.0, bytes_hbm=0.0, accuracy=float(a),
                       is_anytime_level=lv > 0,
                       anytime_group="anytime" if lv > 0 else None,
                       level=lv)
             for n, a, lv in zip(table.names, table.accuracy, table.levels)]
    return ProfileTable(cands, table.caps.copy(), table.latency.copy(),
                        table.run_power.copy(), q_fail=table.q_fail)


def program_workload(fleet: Fleet, reqs):
    """``(sessions, requests)`` in the program's types for ``reqs``."""
    from repro.core.controller import Constraints, Goal
    from repro.serving.sim import EnvironmentTrace
    from repro.traffic.workloads import Session, TrafficRequest

    tenant = fleet.cfg["tenant"]
    goal = Goal(tenant["goal"])
    cons = Constraints(deadline=fleet.t_goal,
                       accuracy_goal=tenant.get("accuracy_goal"),
                       energy_goal=tenant.get("energy_goal"))
    by_sess = np.lexsort((reqs.index, reqs.sid))
    counts = reqs.counts
    ends = np.cumsum(counts)
    arr_s = reqs.arrival[by_sess]
    xi_s = reqs.scale[by_sess]
    ones = np.ones(reqs.sid.size)
    sessions = []
    lo = 0
    for sid in range(fleet.n_sessions):
        hi = int(ends[sid])
        tr = EnvironmentTrace.__new__(EnvironmentTrace)
        tr.phases, tr.seed, tr.length_cv, tr.deadline_cv = (), None, 0.0, 0.0
        tr.xi, tr.lam, tr.deadline_scale = xi_s[lo:hi], ones[lo:hi], \
            ones[lo:hi]
        tr.n, tr.phase_id = hi - lo, None
        sessions.append(Session(sid=sid, tenant=tenant["name"], goal=goal,
                                constraints=cons, arrivals=arr_s[lo:hi],
                                trace=tr))
        lo = hi
    rel = fleet.t_goal
    requests = [TrafficRequest(deadline=a + rel, arrival=a, req_id=k,
                               sid=s, index=i, tenant=tenant["name"],
                               rel_deadline=rel)
                for k, (a, s, i) in enumerate(zip(reqs.arrival.tolist(),
                                                  reqs.sid.tolist(),
                                                  reqs.index.tolist()))]
    return sessions, requests


def gateway_output(res, state) -> dict:
    """A ``GatewayResult`` and the final per-session state as the
    comparison takes them."""
    out = {k: np.asarray(getattr(res, k)) for k in (
        "status", "start", "latency", "sojourn", "missed", "accuracy",
        "energy", "model_index", "power_index")}
    out.update(n_rounds=res.n_rounds, pages_in=res.pages_in,
               pages_out=res.pages_out, state=state)
    return out
