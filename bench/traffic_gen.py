"""The one traffic generator: every mix is a data file it reads.

All draws come from one ``numpy.random.Generator`` seeded by the run's
``--seed``, so a seed gives the same inputs on every machine.  The
processes are those of the program's traffic module (Poisson sessions,
two-state Markov-modulated bursts, the paper's contention phases), drawn
in bulk: a session's Poisson arrivals are its Poisson count of uniform
instants over the horizon, and a fleet-wide burst chain is one
modulated Poisson stream whose arrivals are dealt to sessions uniformly.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Requests:
    """Offered requests in arrival order (ties by session, then index):
    session id, per-session input index, arrival instant, the true
    latency scale of the environment when it runs, and (set by the
    driver) the nominal relative deadline."""

    sid: np.ndarray
    index: np.ndarray
    arrival: np.ndarray
    scale: np.ndarray
    n_sessions: int
    rel: np.ndarray | None = None

    @property
    def counts(self) -> np.ndarray:
        """Requests per session."""
        return np.bincount(self.sid, minlength=self.n_sessions)


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator for ``seed`` (any non-negative integer) and a salt."""
    return np.random.default_rng([int(seed), *salt])


def poisson_arrivals(rng, n_sessions: int, rate: float, horizon: float):
    """Independent Poisson sessions at ``rate`` each over ``[0, horizon)``:
    ``(sid, arrival)``."""
    counts = rng.poisson(rate * horizon, n_sessions)
    sid = np.repeat(np.arange(n_sessions), counts)
    return sid, rng.uniform(0.0, horizon, sid.size)


def mmpp_arrivals(rng, n_sessions: int, rates, dwells, horizon: float,
                  chain_rng=None):
    """One two-state modulated Poisson stream for the whole fleet (state 0
    first; state ``s`` lasts an exponential time of mean ``dwells[s]`` at
    total rate ``rates[s]``), each arrival dealt to a uniform session.
    ``chain_rng`` draws the state durations (default ``rng``)."""
    chain_rng = rng if chain_rng is None else chain_rng
    out = []
    t, state = 0.0, 0
    while t < horizon:
        end = min(t + chain_rng.exponential(dwells[state]), horizon)
        n = rng.poisson(rates[state] * (end - t))
        out.append(rng.uniform(t, end, n))
        t, state = end, 1 - state
    arr = np.concatenate(out)
    return rng.integers(0, n_sessions, arr.size), arr


def phase_of(index: np.ndarray, count: np.ndarray, phases) -> np.ndarray:
    """Contention phase of input ``index`` of a session with ``count``
    inputs: the phase schedule's lengths scaled to the session (rounded
    half to even, the last phase taking the rest)."""
    total = sum(p["n_inputs"] for p in phases)
    used = np.zeros(count.shape, np.int64)
    ph = np.full(index.shape, len(phases) - 1, np.int64)
    for k, p in enumerate(phases[:-1]):
        take = np.round(count * p["n_inputs"] / total).astype(np.int64)
        take = np.clip(take, 0, count - used)
        used = used + take
        ph = np.where((ph == len(phases) - 1) & (index < used), k, ph)
    return ph


def environment(rng, index, count, phases) -> np.ndarray:
    """True latency scale per input: the phase's mean slow-down times a
    mean-one lognormal jitter, times ``tail_scale`` with ``tail_prob``."""
    ph = phase_of(index, count, phases)
    slow = np.asarray([p["slowdown"] for p in phases])[ph]
    cv = np.asarray([p["jitter_cv"] for p in phases])[ph]
    tail_p = np.asarray([p["tail_prob"] for p in phases])[ph]
    tail_s = np.asarray([p["tail_scale"] for p in phases])[ph]
    sigma = np.sqrt(np.log1p(cv ** 2))
    xi = slow * rng.lognormal(-sigma ** 2 / 2, sigma)
    return np.where(rng.random(index.size) < tail_p, xi * tail_s, xi)


def fleet_requests(rng, n_sessions: int, arrivals: dict, horizon: float,
                   t_goal: float, lanes: int, phases) -> Requests:
    """A fleet's offered requests over ``horizon`` seconds.

    Rates are in lanes per deadline (``lanes / t_goal``, the rate that
    keeps every lane busy): ``arrivals["kind"]`` is ``poisson`` (total
    rate ``rate_x``) or ``mmpp`` (state rates ``rates_x``, mean dwells
    ``dwells_x`` deadlines).  An ``mmpp`` mix may fix its burst timing
    with ``chain_seed``, so that every run seed offers the same bursts
    and only the arrivals within them change."""
    kind = arrivals["kind"]
    lane_rate = lanes / t_goal
    if kind == "poisson":
        sid, arr = poisson_arrivals(
            rng, n_sessions, arrivals["rate_x"] * lane_rate / n_sessions,
            horizon)
    elif kind == "mmpp":
        chain = arrivals.get("chain_seed")
        sid, arr = mmpp_arrivals(
            rng, n_sessions, [r * lane_rate for r in arrivals["rates_x"]],
            [x * t_goal for x in arrivals["dwells_x"]], horizon,
            None if chain is None else np.random.default_rng(chain))
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    order = np.lexsort((arr, sid))
    sid, arr = sid[order], arr[order]
    counts = np.bincount(sid, minlength=n_sessions)
    first = np.concatenate([[0], np.cumsum(counts)[:-1]])
    index = np.arange(sid.size) - first[sid]
    scale = environment(rng, index, counts[sid], phases)
    order = np.lexsort((index, sid, arr))
    return Requests(sid[order], index[order], arr[order], scale[order],
                    n_sessions)


GOLDEN = 0.6180339887498949


def spread_deadline(k, lo: float, hi: float, base: float):
    """Deadline of request ``k`` in one fixed sequence over
    ``[lo, hi] * base``: the golden-ratio sequence, whose every prefix lies
    evenly over the range.  It is the same for every seed, so the seed
    moves no deadline and no run is dealt easier ones."""
    return base * (lo + (hi - lo) * np.mod(0.5 + np.asarray(k) * GOLDEN,
                                           1.0))
