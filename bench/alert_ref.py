"""Plain NumPy reference of ALERT and of the fleet gateway it serves.

Written from the paper (arXiv:1911.00119: Eq. 4-10, Sections 3.2-3.3)
and from the gateway's stated admission rules, imports nothing of the
program, and runs in the precision it is given: float64 is the
reference, float32 the control that must fail.

* :func:`estimate` / :func:`select` / :func:`clear` — Eq. 7/10 accuracy
  and Eq. 9 energy over the ``[K, L]`` grid, the Eq. 4 / Eq. 5 pick with
  Section 3.3 relaxation, and whether a pick sits clear of every tie.
* :func:`deliver` — what a pick delivers in an environment whose true
  latency scale is known: staircase accuracy, Eq. 9 energy with the true
  idle ratio, and the (observed, profiled, censored) feedback triple.
* :func:`slowdown_step` / :func:`idle_step` / the goal window — Eq. 6,
  Eq. 8 and the windowed accuracy goal (paper fn. 3).
* :func:`admit` — the gateway's round clock: EDF queue with fail-fast
  and bounded-queue backpressure, one request per session per round,
  busy lanes, and LRU session paging.
* :func:`replay_sessions` — every served request of every session in
  order, vectorised over sessions, each session's filters and goal window
  carried from one request to its next.
"""

from __future__ import annotations

import dataclasses
import heapq
import math

import numpy as np
from scipy.special import erf

GOAL_MIN_ENERGY = 0     # Eq. 4: least energy s.t. an accuracy goal
GOAL_MAX_ACCURACY = 1   # Eq. 5: most accuracy s.t. an energy budget
TIE = 1e-12             # Eq. 5 accuracy tie window (absolute)

SERVED, REJECTED_INFEASIBLE, REJECTED_BACKPRESSURE = 0, 1, 2

# Eq. 6 (K0, sigma0, Q0, alpha, R, miss inflation) and Eq. 8 (phi0, M0,
# S, V): the paper's constants.
SLOW_PRIOR = dict(mu=1.0, sigma=0.1, gain=0.5, q=0.1)
Q0, ALPHA, R_NOISE, MISS_INFLATION = 0.1, 0.3, 1e-3, 0.2
IDLE_PRIOR = dict(phi=0.3, var=0.01)
S_NOISE, V_NOISE = 1e-4, 1e-3


def normal_cdf(z):
    """Standard normal CDF in the dtype of ``z``."""
    return 0.5 * (1.0 + erf(z / z.dtype.type(math.sqrt(2.0))))


def estimate(table, mu, sigma, phi, t, dtype=np.float64):
    """Per-cell predictions for N decisions: ``(accuracy, energy)``, each
    ``[N, K, L]``.  ``mu``/``sigma``/``phi``/``t`` are ``[N]`` (slow-down
    mean and deviation, idle ratio, deadline)."""
    d = np.dtype(dtype).type
    lat = table.latency.astype(dtype)[None]
    caps = table.run_power.astype(dtype)[None]
    mu = np.asarray(mu, dtype)[:, None, None]
    sd = np.maximum(np.asarray(sigma, dtype), d(1e-6))[:, None, None]
    phi = np.asarray(phi, dtype)[:, None, None]
    t = np.maximum(np.asarray(t, dtype), d(1e-9))[:, None, None]
    lat_mean = mu * lat
    lat_std = np.maximum(sd * lat, d(1e-12))
    f = normal_cdf((t - lat_mean) / lat_std)                  # [N, K, L]
    q = table.accuracy.astype(dtype)
    acc = np.empty_like(f)
    for k, stair in enumerate(table.stairs):
        # Eq. 10: q_fail + sum_m (q_m - q_{m-1}) P(level m done by T);
        # a traditional model is a one-level staircase (Eq. 7).
        prev = d(table.q_fail)
        a = np.full(f.shape[::2], prev, dtype)
        for u in stair:
            a = a + (q[u] - prev) * f[:, u, :]
            prev = q[u]
        acc[:, k, :] = a
    t_run = np.minimum(lat_mean, t)
    energy = caps * t_run + phi * caps * np.maximum(t - t_run, d(0.0))
    return acc, energy


def select(acc, energy, goal_kind, acc_goal, en_goal):
    """Eq. 4 / Eq. 5 picks, ``[N]`` flat indices into ``K * L``.

    Eq. 4 (least energy with expected accuracy at the goal), relaxing the
    accuracy goal to the most accurate cell when no cell reaches it.
    Eq. 5 (most accuracy within the energy budget, equal accuracies to
    the least energy), relaxing the budget when no cell fits.  Ties go to
    the first cell in (model, power) order."""
    n = acc.shape[0]
    a = acc.reshape(n, -1)
    e = energy.reshape(n, -1)
    inf = a.dtype.type(np.inf)
    is_min = (np.asarray(goal_kind) == GOAL_MIN_ENERGY)[:, None]
    feas = np.where(is_min, a >= np.asarray(acc_goal, a.dtype)[:, None],
                    e <= np.asarray(en_goal, a.dtype)[:, None])
    any_f = feas.any(axis=1, keepdims=True)
    sc_min = np.where(any_f, np.where(feas, e, inf), -a)
    use = np.where(feas | ~any_f, a, -inf)
    best = use.max(axis=1, keepdims=True)
    sc_max = np.where(best - use <= a.dtype.type(TIE), e, inf)
    return np.argmin(np.where(is_min, sc_min, sc_max), axis=1)


def _winner_gap(x, mask):
    """Least ``x`` over ``mask`` and its distance to the runner-up, an
    equal value included (``inf`` without one).  An exact tie is no
    clear decision: where the reference's values are equal (a CDF
    saturated to 0 or 1), another float64 implementation's may differ
    in the last bits, and the first cell need not win there."""
    x = np.sort(np.where(mask, x, np.inf), axis=1)
    with np.errstate(invalid="ignore"):     # inf - inf: an empty mask
        return x[:, 1] - x[:, 0], x[:, 0]


def clear(acc, energy, goal_kind, acc_goal, en_goal, margin):
    """``[N]`` bool: no change of any cell by less than ``margin`` (in
    accuracy, and relative in energy) can change the pick.  Only clear
    decisions are held to the reference's pick."""
    n = acc.shape[0]
    a = np.asarray(acc, np.float64).reshape(n, -1)
    e = np.asarray(energy, np.float64).reshape(n, -1)
    ag = np.asarray(acc_goal, np.float64)[:, None]
    eg = np.asarray(en_goal, np.float64)[:, None]
    is_min = np.asarray(goal_kind) == GOAL_MIN_ENERGY
    border = np.where(is_min[:, None], np.abs(a - ag) <= margin,
                      np.abs(e - eg) <= margin * np.abs(eg)).any(axis=1)
    feas = np.where(is_min[:, None], a >= ag, e <= eg)
    any_f = feas.any(axis=1)
    every = np.ones_like(feas)
    # Eq. 4: the winning energy among feasible cells, or (relaxed) the
    # winning accuracy, beats its runner-up by more than the margin.
    g_e, w_e = _winner_gap(e, feas)
    g_a, _ = _winner_gap(-a, every)
    ok_min = np.where(any_f, g_e > margin * np.abs(w_e), g_a > margin)
    # Eq. 5: every usable cell either ties the best exactly or lies
    # below it by more than the tie window and the margin, and the least
    # energy in the tie set beats its runner-up.
    pool = feas | ~any_f[:, None]
    best = np.where(pool, a, -np.inf).max(axis=1, keepdims=True)
    below = best - a
    edge = (pool & (below > 0) & (below <= TIE + margin)).any(axis=1)
    ties = pool & (below <= TIE)
    g_t, w_t = _winner_gap(e, ties)
    ok_max = ~edge & (g_t > margin * np.abs(w_t))
    return ~border & np.where(is_min, ok_min, ok_max)


def deliver(table, i, j, scale, dvec, phi_true, dtype=np.float64):
    """What picks ``(i, j)`` deliver when the true latency scale is
    ``scale`` and the time left is ``dvec`` (all ``[N]``).

    The run stops at the deadline; an anytime model delivers its deepest
    level done by then (q_fail when none is), a traditional model its
    accuracy or q_fail.  Energy is Eq. 9 with the true idle ratio.  The
    feedback is the run time against the pick's profile, censored (and
    so inflated by Eq. 6) on a miss — unless an anytime level finished
    before the deadline, whose own time is then an uncensored sample."""
    d = np.dtype(dtype).type
    scale = np.asarray(scale, dtype)
    dvec = np.asarray(dvec, dtype)
    lat_kl = table.latency.astype(dtype)
    lat = lat_kl[i, j] * scale
    missed = lat > dvec
    n = i.shape[0]
    acc = np.full(n, d(table.q_fail), dtype)
    done_t = np.zeros(n, dtype)
    done_prof = np.zeros(n, dtype)
    any_done = np.zeros(n, bool)
    stairs = table.stairs
    q = table.accuracy.astype(dtype)
    for k in np.unique(i):
        rows = np.nonzero(i == k)[0]
        for u in stairs[k]:
            t_u = lat_kl[u, j[rows]] * scale[rows]
            ok = t_u <= dvec[rows]
            r = rows[ok]
            acc[r] = q[u]
            done_t[r] = t_u[ok]
            done_prof[r] = lat_kl[u, j[r]]
            any_done[r] = True
    run_t = np.minimum(lat, dvec)
    p = table.run_power.astype(dtype)[i, j]
    energy = p * run_t + d(phi_true) * p * np.maximum(dvec - run_t, d(0.0))
    use_obs = missed & table.is_anytime[i] & any_done
    observed = np.where(use_obs, done_t, run_t)
    profiled = np.where(use_obs, done_prof, lat_kl[i, j])
    return dict(run_t=run_t, accuracy=acc, energy=energy, missed=missed,
                power=p, observed=observed, profiled=profiled,
                miss_flag=missed & ~use_obs)


def slowdown_step(st, observed, profiled, miss, dtype=np.float64):
    """Eq. 6 on ``[N]`` filters (``st``: mu, sigma, gain, q), paper order."""
    d = np.dtype(dtype).type
    ratio = np.asarray(observed, dtype) / np.asarray(profiled, dtype)
    ratio = np.where(miss, ratio * d(1.0 + MISS_INFLATION), ratio)
    mu, sigma, gain, q = st["mu"], st["sigma"], st["gain"], st["q"]
    y = ratio - mu
    q_new = np.maximum(d(Q0), d(ALPHA) * q + d(1.0 - ALPHA) * (gain * y) ** 2)
    denom = (d(1.0) - gain) * sigma + q_new + d(R_NOISE)
    gain_new = ((d(1.0) - gain) * sigma + q_new) / denom
    return dict(mu=mu + gain_new * y, sigma=(d(1.0) - gain) * sigma + q_new,
                gain=gain_new, q=q_new)


def idle_step(phi, var, idle, active, dtype=np.float64):
    """Eq. 8 on ``[N]`` filters: returns ``(phi, var)``."""
    d = np.dtype(dtype).type
    measured = np.asarray(idle, dtype) / np.asarray(active, dtype)
    g = (var + d(S_NOISE)) / (var + d(S_NOISE) + d(V_NOISE))
    return phi + g * (measured - phi), (d(1.0) - g) * (var + d(S_NOISE))


def window_goal(goal, buf, count, window):
    """Paper fn. 3: the per-input goal that brings the mean of the last
    ``window`` delivered accuracies to ``goal``."""
    need = goal * window - buf.sum(axis=1)
    per_input = need - (window - count - 1) * goal
    return np.where(count == 0, goal, per_input)


# --------------------------------------------------------------------- #
# the gateway's round clock                                              #
# --------------------------------------------------------------------- #
def round_of(arrival, tick) -> int:
    """First round ``k`` with ``k * tick >= arrival``."""
    k = max(int(np.ceil(arrival / tick)), 0)
    while k * tick < arrival:
        k += 1
    while k > 0 and (k - 1) * tick >= arrival:
        k -= 1
    return k


@dataclasses.dataclass
class Admission:
    """Dispositions of one workload: per offered request its ``status``
    and round instant ``start``; the round count and paging."""

    status: np.ndarray
    start: np.ndarray
    n_rounds: int
    pages_in: int
    pages_out: int


def admit(arrival, rel, sid, *, n_lanes, tick, max_queue, min_feasible,
          latency=None, serve=None, dtype=np.float64) -> Admission:
    """Replay the round clock over requests in arrival order.

    Each round at ``k * tick``: requests that have arrived are queued
    (refused when ``max_queue`` are waiting), then popped earliest
    deadline first onto the idle lanes — a request whose remaining time
    is below ``min_feasible`` fails fast, and a request whose session is
    already in this round's batch, or is being served on a busy lane,
    waits (after at most ``4 * n_lanes`` such deferrals the round
    closes).  Sessions take lanes least recently used first: free idle
    lanes in lane order, then idle lanes whose session is not needed this
    round, oldest use first.  A served request keeps its lane busy for
    its run time: ``latency`` gives it per request, or ``serve(rows,
    now)`` runs the round's batch and returns it; with neither, every
    lane is idle each round."""
    # Python floats are float64 and much faster in the loop below.
    cast = float if np.dtype(dtype) == np.float64 else np.dtype(dtype).type
    n = len(arrival)
    arr = [cast(x) for x in arrival]
    dl = [cast(a + cast(r)) for a, r in zip(arr, rel)]
    tick = cast(tick)
    min_feasible = cast(min_feasible)
    sid = [int(x) for x in sid]
    status = np.full(n, REJECTED_BACKPRESSURE, np.int64)
    start = np.zeros(n, np.float64)
    heap: list = []
    seq = 0
    resident = [-1] * n_lanes
    last_used = [0] * n_lanes
    busy = [cast(0.0)] * n_lanes
    lane_of: dict = {}
    stored: set = set()
    pages_in = pages_out = n_rounds = 0
    ri = 0
    round_k = 0
    while ri < n or heap:
        if not heap:
            round_k = max(round_k, round_of(arr[ri], tick))
        now = round_k * tick
        while ri < n and arr[ri] <= now:
            if max_queue is not None and len(heap) >= max_queue:
                status[ri] = REJECTED_BACKPRESSURE
            else:
                heapq.heappush(heap, (dl[ri], seq, ri))
                seq += 1
            ri += 1
        idle = [b <= now for b in busy]
        avail = sum(idle)
        batch: list = []
        seen: set = set()
        deferred: list = []
        while len(batch) < avail and len(deferred) <= 4 * n_lanes:
            item = None
            while heap:
                top = heapq.heappop(heap)
                if top[0] - now < min_feasible:
                    status[top[2]] = REJECTED_INFEASIBLE
                    start[top[2]] = now
                    continue
                item = top
                break
            if item is None:
                break
            q = sid[item[2]]
            ln = lane_of.get(q, -1)
            if q in seen or (ln >= 0 and not idle[ln]):
                deferred.append(item)
                continue
            seen.add(q)
            batch.append(item[2])
        for item in deferred:
            heapq.heappush(heap, item)
        if batch:
            sids = [sid[r] for r in batch]
            missing = [p for p, q in enumerate(sids) if q not in lane_of]
            if missing:
                free = [ln for ln in range(n_lanes)
                        if resident[ln] < 0 and idle[ln]]
                n_evict = len(missing) - len(free)
                if n_evict > 0:
                    needed = set(sids)
                    cand = sorted((last_used[ln], ln)
                                  for ln in range(n_lanes)
                                  if idle[ln] and resident[ln] >= 0
                                  and resident[ln] not in needed)
                    for _, ln in cand[:n_evict]:
                        old = resident[ln]
                        stored.add(old)
                        del lane_of[old]
                        resident[ln] = -1
                        pages_out += 1
                        free.append(ln)
                if len(free) < len(missing):
                    raise RuntimeError("more sessions than free lanes")
                for p, ln in zip(missing, free):
                    q = sids[p]
                    resident[ln] = q
                    lane_of[q] = ln
                    if q in stored:
                        stored.discard(q)
                        pages_in += 1
            ran = None if serve is None else serve(np.asarray(batch), now)
            for k, (r, q) in enumerate(zip(batch, sids)):
                ln = lane_of[q]
                last_used[ln] = round_k
                status[r] = SERVED
                start[r] = now
                if ran is not None:
                    busy[ln] = cast(now + cast(ran[k]))
                elif latency is not None:
                    busy[ln] = cast(now + cast(latency[r]))
            n_rounds += 1
        round_k += 1
    return Admission(status, start, n_rounds, pages_in, pages_out)


# --------------------------------------------------------------------- #
# every session's requests, in order                                     #
# --------------------------------------------------------------------- #
def fresh_state(n_sessions: int, goal, window: int, dtype=np.float64):
    """Filters at the paper's priors, goal windows empty, per session."""
    st = {k: np.full(n_sessions, v, dtype) for k, v in SLOW_PRIOR.items()}
    st.update({k: np.full(n_sessions, v, dtype)
               for k, v in IDLE_PRIOR.items()})
    st["goal"] = np.broadcast_to(np.asarray(goal, dtype),
                                 (n_sessions,)).copy()
    st["buf"] = np.zeros((n_sessions, max(window - 1, 1)), dtype)
    st["pos"] = np.zeros(n_sessions, np.int64)
    st["count"] = np.zeros(n_sessions, np.int64)
    return st


class Sessions:
    """Every session's filters and goal window, and the outcome of each
    request served so far (``out``, indexed by request row)."""

    def __init__(self, table, n_requests: int, *, phi_true, window,
                 goal_kind, acc_goal, margin=1e-9, dtype=np.float64):
        self.table, self.phi_true, self.window = table, phi_true, window
        self.margin, self.dtype = margin, dtype
        self.gk = np.asarray(goal_kind)
        self.st = fresh_state(len(goal_kind), acc_goal, window, dtype)
        n = n_requests
        self.out = {k: np.zeros(n, np.int64) for k in ("own", "i", "j")}
        self.out["clear"] = np.zeros(n, bool)
        for k in ("run_t", "accuracy", "energy", "sojourn"):
            self.out[k] = np.zeros(n, dtype)
        self.out["missed"] = np.zeros(n, bool)

    def serve(self, rows, sess, now, arrival, rel, scale, energy_goal,
              forced=None):
        """Serve one request of each of the distinct sessions ``sess`` at
        instants ``now`` (all ``[N]``, request ``rows``): pick (the
        reference's own, or ``forced`` ``[N, 2]``), deliver, feed back.
        Returns the run times."""
        d = np.dtype(self.dtype).type
        dt, st, s = self.dtype, self.st, sess
        now = np.asarray(now, dt)
        arr = np.asarray(arrival, dt)
        dvec = np.asarray(rel, dt) - (now - arr)
        depth = max(self.window - 1, 0)
        g_now = st["goal"][s]
        if depth:
            g_now = window_goal(g_now, st["buf"][s], st["count"][s],
                                d(self.window))
        acc, en = estimate(self.table, st["mu"][s], st["sigma"][s],
                           st["phi"][s], dvec, dt)
        e_goal = np.asarray(energy_goal, dt)
        own = select(acc, en, self.gk[s], g_now, e_goal)
        out = self.out
        out["own"][rows] = own
        out["clear"][rows] = clear(acc, en, self.gk[s], g_now, e_goal,
                                   self.margin)
        n_l = self.table.latency.shape[1]
        i, j = (own // n_l, own % n_l) if forced is None else \
            (forced[:, 0], forced[:, 1])
        out["i"][rows], out["j"][rows] = i, j
        dv = deliver(self.table, i, j, scale, dvec, self.phi_true, dt)
        out["run_t"][rows] = dv["run_t"]
        out["accuracy"][rows] = dv["accuracy"]
        out["energy"][rows] = dv["energy"]
        out["missed"][rows] = dv["missed"]
        out["sojourn"][rows] = (now - arr) + dv["run_t"]
        new = slowdown_step({k: st[k][s] for k in SLOW_PRIOR},
                            dv["observed"], dv["profiled"], dv["miss_flag"],
                            dt)
        for k, v in new.items():
            st[k][s] = v
        st["phi"][s], st["var"][s] = idle_step(
            st["phi"][s], st["var"][s], d(self.phi_true) * dv["power"],
            dv["power"], dt)
        if depth:
            st["buf"][s, st["pos"][s]] = dv["accuracy"]
            st["pos"][s] = (st["pos"][s] + 1) % depth
            st["count"][s] = np.minimum(st["count"][s] + 1, depth)
        return dv["run_t"]


def replay_sessions(table, req, *, phi_true, window, goal_kind, acc_goal,
                    forced=None, margin=1e-9, dtype=np.float64):
    """Every served request in order of its round, sessions vectorised.

    ``req`` holds, per served request (``[N]`` each): ``sess`` (dense
    session index), ``now`` (round instant), ``arrival``, ``rel``
    (nominal relative deadline), ``scale`` (true latency scale),
    ``energy_goal`` (its Eq. 5 budget).  ``goal_kind``/``acc_goal`` are
    per session.  Each request's time left is ``rel - (now - arrival)``.

    The pick is the reference's own unless ``forced`` (``[N, 2]`` model
    and power indices) supplies one: then the reference follows it, so
    that a pick made differently at a tie does not carry into later
    requests.  Returns per-request picks (own and used), whether the own
    pick is clear of ties, the delivered outcome and the final per-session
    state."""
    n = len(req["sess"])
    ss = Sessions(table, n, phi_true=phi_true, window=window,
                  goal_kind=goal_kind, acc_goal=acc_goal, margin=margin,
                  dtype=dtype)
    sess = np.asarray(req["sess"], np.int64)
    order = np.lexsort((sess, np.asarray(req["now"])))
    occ = np.zeros(n, np.int64)
    seen = np.zeros(len(goal_kind), np.int64)
    for r in order:
        occ[r] = seen[sess[r]]
        seen[sess[r]] += 1
    for step in range(int(occ.max()) + 1 if n else 0):
        rows = np.nonzero(occ == step)[0]
        ss.serve(rows, sess[rows], *(np.asarray(req[k])[rows] for k in (
            "now", "arrival", "rel", "scale", "energy_goal")),
            forced=None if forced is None else forced[rows])
    return ss.out, ss.st


def rel_gap(got, want, floor: float = 1e-300) -> float:
    """Largest ``|got - want|`` relative to ``max(|want|, floor)``;
    ``floor=1`` makes it absolute for quantities of order one."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.abs(want), floor)))
