"""The comparison that decides ``correct`` in the fleet cells.

The plain reference (:mod:`bench.alert_ref`) replays the round clock in
float64 and sets each offered request's disposition against the
program's; then it follows every served request of every session in the
program's own order and picks, and compares what each delivered and the
state each session ends in.  Three numbers come out:

* ``admission_mismatches`` — requests whose disposition or round differs,
  plus any difference in rounds served, pages in and pages out (exact);
* ``pick_mismatches`` — served requests whose pick differs from the
  reference's where no tie within ``margin`` could explain it, plus
  delivered miss flags and goal-window counters that differ (exact);
* ``outcome_gap`` — the largest relative gap over every served request's
  run time, energy and sojourn, its delivered accuracy (absolute), and
  every session's final filter state and goal window.

:func:`control_program` stands the reference itself, in float32, in the
program's place: the check must find it wrong.
"""

from __future__ import annotations

import numpy as np

from bench import alert_ref as ref

STATE_KEYS = ("mu", "sigma", "gain", "q", "phi", "var")


def session_inputs(reqs, rows, start, energy_goal=None):
    """The per-request inputs :func:`alert_ref.replay_sessions` takes,
    for the served ``rows`` of :class:`traffic_gen.Requests` ``reqs``."""
    return {"sess": reqs.sid[rows], "now": start[rows],
            "arrival": reqs.arrival[rows], "rel": reqs.rel[rows],
            "scale": reqs.scale[rows],
            "energy_goal": np.zeros(rows.size) if energy_goal is None
            else energy_goal[rows]}


def compare(table, reqs, prog: dict, *, gateway: dict, sessions: dict,
            margin: float, busy: bool) -> dict:
    """Hold one run's output ``prog`` (the ``GatewayResult`` arrays, its
    counts, and ``state``: the final per-session filters and window, or
    ``None`` where the run's state was not kept) to the reference.
    ``gateway`` has ``n_lanes``, ``tick``, ``max_queue``,
    ``min_feasible`` and ``phi_true``; ``sessions`` has per-session
    ``goal_kind``, ``acc_goal`` and the ``window``.  ``busy`` keeps a
    lane busy for the run time the program reported (a tick finer than
    the deadline)."""
    adm = ref.admit(reqs.arrival, reqs.rel, reqs.sid,
                    n_lanes=gateway["n_lanes"], tick=gateway["tick"],
                    max_queue=gateway["max_queue"],
                    min_feasible=gateway["min_feasible"],
                    latency=prog["latency"] if busy else None)
    adm_bad = int(np.sum((adm.status != prog["status"])
                         | (adm.start != prog["start"])))
    adm_bad += abs(adm.n_rounds - prog["n_rounds"])
    adm_bad += abs(adm.pages_in - prog["pages_in"])
    adm_bad += abs(adm.pages_out - prog["pages_out"])

    rows = np.nonzero(prog["status"] == ref.SERVED)[0]
    forced = np.stack([prog["model_index"][rows],
                       prog["power_index"][rows]], axis=1)
    out, st = ref.replay_sessions(
        table, session_inputs(reqs, rows, prog["start"]),
        phi_true=gateway["phi_true"], window=sessions["window"],
        goal_kind=sessions["goal_kind"], acc_goal=sessions["acc_goal"],
        forced=forced, margin=margin)
    n_l = table.latency.shape[1]
    pick = forced[:, 0] * n_l + forced[:, 1]
    pick_bad = int(np.sum(out["clear"] & (out["own"] != pick)))
    pick_bad += int(np.sum(out["missed"] != prog["missed"][rows]))
    gaps = [ref.rel_gap(prog["latency"][rows], out["run_t"]),
            ref.rel_gap(prog["energy"][rows], out["energy"]),
            ref.rel_gap(prog["sojourn"][rows], out["sojourn"]),
            ref.rel_gap(prog["accuracy"][rows], out["accuracy"], 1.0)]
    pst = prog["state"]
    if pst is not None:
        pick_bad += int(np.sum(pst["pos"] != st["pos"]))
        pick_bad += int(np.sum(pst["count"] != st["count"]))
        gaps.append(ref.rel_gap(pst["buf"], st["buf"], 1.0))
        gaps += [ref.rel_gap(pst[k], st[k]) for k in STATE_KEYS]
    return {"admission_mismatches": float(adm_bad),
            "pick_mismatches": float(pick_bad),
            "outcome_gap": max(gaps),
            "n_served": int(rows.size),
            "n_clear": int(out["clear"].sum())}


def control_program(table, reqs, *, gateway: dict, sessions: dict,
                    busy: bool, dtype=np.float32) -> dict:
    """The reference in ``dtype``, shaped like a program run's output:
    each round's batch is served as it is admitted, so a busy lane holds
    its session for the run time this precision gives."""
    d = np.dtype(dtype).type
    n = reqs.sid.size
    ss = ref.Sessions(table, n, phi_true=gateway["phi_true"],
                      window=sessions["window"],
                      goal_kind=sessions["goal_kind"],
                      acc_goal=sessions["acc_goal"], dtype=dtype)
    no_goal = np.zeros(n)

    def serve(rows, now):
        return ss.serve(rows, reqs.sid[rows], np.full(rows.size, now),
                        reqs.arrival[rows], reqs.rel[rows],
                        reqs.scale[rows], no_goal[rows])

    adm = ref.admit(reqs.arrival.astype(dtype), reqs.rel.astype(dtype),
                    reqs.sid, n_lanes=gateway["n_lanes"],
                    tick=d(gateway["tick"]), max_queue=gateway["max_queue"],
                    min_feasible=d(gateway["min_feasible"]),
                    serve=serve if busy else None, dtype=dtype)
    rows = np.nonzero(adm.status == ref.SERVED)[0]
    if busy:
        out, st = ss.out, ss.st
    else:
        out, st = ref.replay_sessions(
            table, session_inputs(reqs, rows, adm.start),
            phi_true=gateway["phi_true"], window=sessions["window"],
            goal_kind=sessions["goal_kind"], acc_goal=sessions["acc_goal"],
            dtype=dtype)
        full = {k: np.zeros(n, v.dtype) for k, v in out.items()}
        for k, v in out.items():
            full[k][rows] = v
        out = full
    n_l = table.latency.shape[1]
    prog = {"status": adm.status, "start": adm.start,
            "n_rounds": adm.n_rounds, "pages_in": adm.pages_in,
            "pages_out": adm.pages_out, "state": st,
            "model_index": np.where(adm.status == ref.SERVED,
                                    out["own"] // n_l, 0),
            "power_index": np.where(adm.status == ref.SERVED,
                                    out["own"] % n_l, 0),
            "missed": out["missed"]}
    for k, src in (("latency", "run_t"), ("energy", "energy"),
                   ("sojourn", "sojourn"), ("accuracy", "accuracy")):
        prog[k] = out[src].astype(np.float64)
    return prog
