"""Environment simulator: reproduces the paper's evaluation protocol
(Section 5.1) at production scale.

One *input* = one inference request.  The environment draws, per input n:

    xi_true(n)   — phase-dependent slow-down (Default / CPU / Memory
                   contention phases, paper Table 3) with lognormal jitter
                   and a heavy tail (the paper's Fig. 2 outliers);
    lambda(n)    — input-length latency factor (NLP1-style variance).

Realised latency of config (i, j): t = t_train[i,j] * xi_true * lambda.
Energy follows Eq. 9 with the true phi of the platform.  Accuracy follows
Eq. 3 (traditional) / Eq. 10 (anytime staircase).

Schemes (paper Table 3):
    alert        — full controller, anytime + traditional candidates
    alert_trad   — controller without anytime candidates
    alert_dnn    — controller DNN pick, system-default power (race-to-idle)
    alert_power  — fastest traditional DNN, controller power pick
    oracle       — per-input perfect knowledge, dynamic optimal
    oracle_static— best single (model, power) fixed for the whole trace

Scale: :class:`FleetSim` advances S independent streams on one global
tick grid and scores ALL of them with one :class:`BatchedAlertEngine`
call per tick (struct-of-arrays Kalman banks, vectorised delivery).
Streams may be fully heterogeneous — per-stream :class:`StreamSpec`
bundles a stream's own Phase schedule, goal type, constraints, and
arrival/departure ticks — and lanes outside a stream's lifetime are
masked, not re-padded (DESIGN.md §5).  The single-stream
``InferenceSim.run_alert`` is the S=1 slice of the same path.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.core.batched import (BatchedAlertEngine, WindowedGoalBank,
                                goal_codes)
from repro.core.controller import Constraints, Goal
from repro.core.kalman import (IdlePowerFilterBank, SlowdownFilterBank,
                               observe_fleet)
from repro.core.profiles import ProfileTable


@dataclasses.dataclass(frozen=True)
class Phase:
    """One contention phase of an environment trace: ``n_inputs`` draws
    with mean slow-down ``slowdown``, lognormal jitter ``jitter_cv``, and
    a heavy tail (paper Table 3 / Fig. 2)."""

    n_inputs: int
    slowdown: float = 1.0      # mean xi_true
    jitter_cv: float = 0.08    # lognormal coefficient of variation
    tail_prob: float = 0.02    # heavy-tail outlier probability (Fig. 2)
    tail_scale: float = 3.0


DEFAULT_ENV = (Phase(400),)
CPU_ENV = (Phase(80), Phase(240, slowdown=1.5, jitter_cv=0.15),
           Phase(80))
MEMORY_ENV = (Phase(80), Phase(240, slowdown=2.2, jitter_cv=0.25,
                               tail_prob=0.04, tail_scale=3.0), Phase(80))

ENVS = {"default": DEFAULT_ENV, "cpu": CPU_ENV, "memory": MEMORY_ENV}


@dataclasses.dataclass
class TraceResult:
    """Per-input outcomes of one stream under one scheme (arrays [N])."""

    energy: np.ndarray        # [N] J per input
    accuracy: np.ndarray      # [N] delivered accuracy
    latency: np.ndarray       # [N] realised latency (s)
    missed: np.ndarray        # [N] deadline misses (bool)
    scheme: str = ""
    budget: np.ndarray | None = None   # [N] per-input energy budget
    # (model, power) indices for single-config schemes (oracle_static);
    # None for adaptive schemes.
    config: tuple[int, int] | None = None

    @property
    def mean_energy(self) -> float:
        """Mean per-input energy (J) — the paper's Table 4 column."""
        return float(self.energy.mean())

    @property
    def mean_error(self) -> float:
        """Mean (1 - delivered accuracy)."""
        return float(1.0 - self.accuracy.mean())

    @property
    def miss_rate(self) -> float:
        """Fraction of inputs that missed their deadline."""
        return float(self.missed.mean())

    def violates(self, goal: Goal, cons: Constraints,
                 window: int = 10, tol: float = 0.10) -> bool:
        """Constraint violated in more than ``tol`` of windows (Table 4
        superscript convention)."""
        if goal is Goal.MINIMIZE_ENERGY:
            q = cons.accuracy_goal
            win = np.convolve(self.accuracy, np.ones(window) / window,
                              mode="valid")
            return float((win < q - 1e-9).mean()) > tol
        if self.budget is not None:
            bwin = np.convolve(self.budget, np.ones(window) / window,
                               mode="valid")
        else:
            bwin = cons.energy_goal
        win = np.convolve(self.energy, np.ones(window) / window,
                          mode="valid")
        return float((win > bwin + 1e-9).mean()) > tol


class EnvironmentTrace:
    """Pre-drawn environment randomness so every scheme sees the SAME
    trace (paired comparison, like the paper's fixed input sets).

    All randomness flows through one explicitly threaded
    ``numpy.random.Generator`` — never the legacy global ``np.random``
    state — so a given integer seed yields a bit-identical trace on every
    run and platform (``tests/test_serving.py`` pins this).  ``seed`` may
    also be a pre-built ``Generator`` for callers that manage their own
    stream (e.g. spawned child generators for fleet members); note a
    Generator is consumed by construction, so pass a fresh one per trace.
    """

    def __init__(self, phases: tuple[Phase, ...],
                 seed: int | np.random.Generator = 0,
                 length_cv: float = 0.0, deadline_cv: float = 0.0):
        self.phases = tuple(phases)
        self.seed = seed if isinstance(seed, int) else None
        self.length_cv = length_cv
        self.deadline_cv = deadline_cv
        rng = seed if isinstance(seed, np.random.Generator) \
            else np.random.default_rng(seed)
        xs, phase_id = [], []
        for pi, ph in enumerate(phases):
            sigma = np.sqrt(np.log(1 + ph.jitter_cv ** 2))
            draw = ph.slowdown * rng.lognormal(-sigma ** 2 / 2, sigma,
                                               ph.n_inputs)
            tail = rng.random(ph.n_inputs) < ph.tail_prob
            draw = np.where(tail, draw * ph.tail_scale, draw)
            xs.append(draw)
            phase_id.extend([pi] * ph.n_inputs)
        self.xi = np.concatenate(xs)
        n = len(self.xi)
        if length_cv > 0:
            sigma = np.sqrt(np.log(1 + length_cv ** 2))
            self.lam = rng.lognormal(-sigma ** 2 / 2, sigma, n)
        else:
            self.lam = np.ones(n)
        # Per-input deadline scale (paper: the sentence-prediction task's
        # per-word deadline depends on time the rest of the sentence has
        # already consumed — "requirement variety").  Requirement changes
        # are visible to every scheme at dispatch time; a static config
        # cannot adapt to them.
        if deadline_cv > 0:
            sigma = np.sqrt(np.log(1 + deadline_cv ** 2))
            self.deadline_scale = rng.lognormal(-sigma ** 2 / 2, sigma, n)
        else:
            self.deadline_scale = np.ones(n)
        self.n = n
        self.phase_id = np.asarray(phase_id)

    def realized_scale(self, n: int) -> float:
        """True latency scale of input n (xi_true * lambda)."""
        return float(self.xi[n] * self.lam[n])


class InferenceSim:
    """Run one scheme over one environment trace."""

    def __init__(self, table: ProfileTable, trace: EnvironmentTrace,
                 phi_true: float = 0.25):
        self.table = table
        self.trace = trace
        self.phi_true = phi_true
        groups = table.anytime_groups()
        self._anytime_idx = sorted(
            {i for g in groups.values() for i in g})
        self._trad_idx = [i for i in range(len(table.candidates))
                          if i not in self._anytime_idx]
        # level latencies per anytime candidate (for staircase delivery)
        self._level_rows = {}
        for g in groups.values():
            for pos, i in enumerate(g):
                self._level_rows[i] = g[:pos + 1]

    def _deadline_vec(self, cons: Constraints) -> np.ndarray:
        return cons.deadline * self.trace.deadline_scale

    def _budget_vec(self, cons: Constraints) -> np.ndarray | None:
        if cons.energy_goal is None:
            return None
        # Energy budgets scale with the per-input time allotment
        # (E_goal = P_goal * T_goal, paper Section 3.1).
        return cons.energy_goal * self.trace.deadline_scale

    # -------------------------------------------------------------- #
    def _deliver(self, i: int, j: int, scale: float, deadline: float
                 ) -> tuple[float, float, float, bool,
                            tuple[float, float] | None]:
        """Returns (latency, delivered accuracy, energy, missed, obs).

        ``obs`` is an optional UNCENSORED (observed, profiled) latency pair
        from the deepest *completed* anytime level: when the target level
        misses, the runtime still measured level k's true completion time
        (the anytime DNN emits o_1..o_k with timestamps).  Traditional DNNs
        only yield the censored deadline-capped observation (None here).
        """
        t = self.table
        lat = t.latency[i, j] * scale
        obs = None
        if i in self._level_rows:  # anytime: staircase (Eq. 10)
            acc = t.q_fail
            for k in self._level_rows[i]:
                lk = t.latency[k, j] * scale
                if lk <= deadline:
                    acc = t.candidates[k].accuracy
                    obs = (lk, float(t.latency[k, j]))
            missed = lat > deadline
        else:
            missed = lat > deadline
            acc = t.q_fail if missed else t.candidates[i].accuracy
        run_t = min(lat, deadline)
        p = t.run_power[i, j]
        energy = p * run_t + self.phi_true * p * max(deadline - run_t, 0.0)
        return min(lat, deadline), acc, energy, missed, obs

    # -------------------------------------------------------------- #
    def run_alert(self, goal: Goal, cons: Constraints, *,
                  anytime: bool = True, power_control: bool = True,
                  dnn_control: bool = True, overhead: float = 0.0,
                  paper_faithful_energy: bool = True,
                  scheme_name: str = "alert") -> TraceResult:
        """One ALERT stream = the S=1 slice of the fleet path."""
        fleet = FleetSim(self.table, [self.trace], phi_true=self.phi_true)
        res = fleet.run_alert(
            goal, cons, anytime=anytime, power_control=power_control,
            dnn_control=dnn_control, overhead=overhead,
            paper_faithful_energy=paper_faithful_energy,
            scheme_name=scheme_name)
        return res.stream(0)

    # -------------------------------------------------------------- #
    def _delivery_tensors(self, cons: Constraints):
        """Vectorised delivery over the whole trace: arrays [K, L, N]."""
        t = self.table
        deadline = self._deadline_vec(cons)[None, None, :]  # [1,1,N]
        scale = self.trace.xi * self.trace.lam            # [N]
        lat = t.latency[:, :, None] * scale[None, None, :]
        missed = lat > deadline
        q = t.accuracies[:, None, None]
        acc = np.where(missed, t.q_fail, q)
        for i, rows in self._level_rows.items():          # anytime rows
            acc_i = np.full(lat.shape[1:], t.q_fail)
            for k in rows:
                lk = t.latency[k, :, None] * scale[None, :]
                acc_i = np.where(lk <= deadline[0],
                                 t.candidates[k].accuracy, acc_i)
            acc[i] = acc_i
        run_t = np.minimum(lat, deadline)
        p = t.run_power[:, :, None]
        energy = p * run_t + self.phi_true * p * \
            np.maximum(deadline - run_t, 0.0)
        return np.minimum(lat, deadline), acc, energy, missed

    def run_oracle(self, goal: Goal, cons: Constraints) -> TraceResult:
        """Per-input perfect latency/energy prediction, dynamic optimal,
        traditional DNNs (paper: 'theoretically optimal result using
        traditional DNN designs')."""
        N = self.trace.n
        lat, acc, energy, missed = self._delivery_tensors(cons)
        bvec = self._budget_vec(cons)
        idx = self._trad_idx
        lat, acc = lat[idx], acc[idx]
        energy, missed = energy[idx], missed[idx]
        K, L, _ = lat.shape
        if goal is Goal.MINIMIZE_ENERGY:
            feasible = (acc >= cons.accuracy_goal - 1e-12) & ~missed
            score = np.where(feasible, energy, np.inf)
            flat = score.reshape(K * L, N)
            pick = flat.argmin(axis=0)
            # fallback when nothing feasible: max accuracy
            none = ~feasible.any(axis=(0, 1))
            alt = acc.reshape(K * L, N).argmax(axis=0)
            pick = np.where(none, alt, pick)
        else:
            feasible = energy <= bvec[None, None, :] + 1e-12
            score = np.where(feasible, acc, -np.inf)
            flat = score.reshape(K * L, N)
            pick = flat.argmax(axis=0)
            none = ~feasible.any(axis=(0, 1))
            alt = energy.reshape(K * L, N).argmin(axis=0)
            pick = np.where(none, alt, pick)
        ar = np.arange(N)
        res = TraceResult(
            energy.reshape(K * L, N)[pick, ar],
            acc.reshape(K * L, N)[pick, ar],
            lat.reshape(K * L, N)[pick, ar],
            missed.reshape(K * L, N)[pick, ar], "oracle", budget=bvec)
        return res

    def run_oracle_static(self, goal: Goal, cons: Constraints
                          ) -> TraceResult:
        """Best single (traditional model, power) for the whole trace —
        hindsight-optimal static pick (the Table 4 baseline)."""
        lat, acc, energy, missed = self._delivery_tensors(cons)
        bvec = self._budget_vec(cons)
        best = None
        for i in self._trad_idx:
            for j in range(len(self.table.power_caps)):
                res = TraceResult(energy[i, j], acc[i, j], lat[i, j],
                                  missed[i, j], "oracle_static",
                                  budget=bvec, config=(i, j))
                # "Satisfying constraints" for the static pick is strict
                # (zero violating windows); the 10 %-window rule is only
                # the *reporting* convention (Table 4 superscripts).  A
                # static config must survive the worst phase of the trace
                # — that conservatism is exactly what ALERT exploits.
                strict = res.violates(goal, cons, tol=0.0)
                loose = res.violates(goal, cons)
                if goal is Goal.MINIMIZE_ENERGY:
                    key = (strict, loose, res.mean_energy, res.mean_error)
                else:
                    key = (strict, loose, res.mean_error, res.mean_energy)
                if best is None or key < best[0]:
                    best = (key, res)
        return best[1]

    # -------------------------------------------------------------- #
    def run_alert_fleet(self, goal: Goal, cons: Constraints,
                        n_streams: int, *, seed: int = 0,
                        **kwargs) -> "FleetResult":
        """Clone this sim's environment phases into ``n_streams``
        independently-seeded streams and run them in lockstep (one batched
        engine call per tick)."""
        t = self.trace
        fleet = FleetSim.from_phases(self.table, t.phases, n_streams,
                                     seed=seed, phi_true=self.phi_true,
                                     length_cv=t.length_cv,
                                     deadline_cv=t.deadline_cv)
        return fleet.run_alert(goal, cons, **kwargs)

    # -------------------------------------------------------------- #
    def run_scheme(self, scheme: str, goal: Goal,
                   cons: Constraints) -> TraceResult:
        """Dispatch one paper Table-3 scheme name (``alert``,
        ``alert_trad``/``alert_dnn``/``alert_power`` ablations,
        ``oracle``, ``oracle_static``, beyond-paper ``alert_plus``)."""
        if scheme == "alert":
            return self.run_alert(goal, cons, scheme_name="alert")
        if scheme == "alert_plus":
            # Beyond-paper controller: probabilistic E[min(t, T)] energy
            # estimator instead of Eq. 9's mean-latency form.
            return self.run_alert(goal, cons, paper_faithful_energy=False,
                                  scheme_name="alert_plus")
        if scheme == "alert_trad":
            return self.run_alert(goal, cons, anytime=False,
                                  scheme_name="alert_trad")
        if scheme == "alert_dnn":
            return self.run_alert(goal, cons, power_control=False,
                                  scheme_name="alert_dnn")
        if scheme == "alert_power":
            return self.run_alert(goal, cons, anytime=False,
                                  dnn_control=False,
                                  scheme_name="alert_power")
        if scheme == "oracle":
            return self.run_oracle(goal, cons)
        if scheme == "oracle_static":
            return self.run_oracle_static(goal, cons)
        raise ValueError(scheme)


# ------------------------------------------------------------------ #
# Shared delivery kernel: one synchronous engine tick                  #
# ------------------------------------------------------------------ #
@dataclasses.dataclass(frozen=True)
class DeliveredTick:
    """Realised outcomes of one synchronous delivery tick (arrays [S]):
    deadline-capped ``latency``, staircase-delivered ``accuracy``
    (Eq. 10), Eq. 9 ``energy``, the miss vector, plus the feedback pair
    (``observed``/``profiled`` latencies and the censored ``miss_flag``)
    implementing the anytime uncensored-observation co-design."""

    latency: np.ndarray     # [S] run time, capped at the deadline
    accuracy: np.ndarray    # [S] delivered accuracy (staircase Eq. 10)
    energy: np.ndarray      # [S] Eq. 9 with the platform's true phi
    missed: np.ndarray      # [S] bool: target level missed its deadline
    run_power: np.ndarray   # [S] active power of the executed config
    observed: np.ndarray    # [S] latency observation fed to Eq. 6
    profiled: np.ndarray    # [S] matching profiled latency
    miss_flag: np.ndarray   # [S] censored-miss flag for the filter


def deliver_tick(table: ProfileTable, st, i_glob: np.ndarray,
                 j_act: np.ndarray, scale: np.ndarray, dvec: np.ndarray,
                 phi_true: float, is_anytime: np.ndarray,
                 profiled_pick: np.ndarray) -> DeliveredTick:
    """Vectorised delivery for one synchronous tick — the single delivery
    kernel behind both the closed-loop :class:`FleetSim` tick and the
    open-loop traffic gateway (``repro.traffic.gateway``): the tick sim is
    the special case where every lane has an input every round
    (DESIGN.md §7).

    ``i_glob``/``j_act`` are the executed (model, power) indices into the
    full ``table``, ``scale`` the true per-input latency scale
    (xi * lambda), ``dvec`` the effective per-input deadline, ``st`` the
    table's precomputed staircase tensors.  ``profiled_pick`` is the
    profiled latency of the *controller's* pick (it differs from
    ``table.latency[i_glob, j_act]`` only under the ALERT_DNN ablation,
    where the executed power is forced to the system default) — it seeds
    the censored feedback path.  A missed deadline whose staircase still
    completed level k yields an UNCENSORED (observed, profiled) pair from
    level k instead (paper Section 3.3 co-design).
    """
    m = st.lvl_lat.shape[1]
    lat = table.latency[i_glob, j_act] * scale
    missed = lat > dvec
    lvl_lat = st.lvl_lat[i_glob, :, j_act]                      # [S, M]
    completed = st.lvl_valid[i_glob] & \
        (lvl_lat * scale[:, None] <= dvec[:, None])
    any_done = completed.any(axis=1)
    last_done = (m - 1) - np.argmax(completed[:, ::-1], axis=1)
    acc = np.where(any_done,
                   st.lvl_acc[i_glob, last_done], table.q_fail)
    run_t = np.minimum(lat, dvec)
    p = table.run_power[i_glob, j_act]
    energy = p * run_t + phi_true * p * np.maximum(dvec - run_t, 0.0)
    rows = np.arange(i_glob.shape[0])
    use_obs = missed & is_anytime[i_glob] & any_done
    obs_lat = lvl_lat[rows, last_done] * scale
    obs_prof = lvl_lat[rows, last_done]
    observed = np.where(use_obs, obs_lat, run_t)
    profiled = np.where(use_obs, obs_prof, profiled_pick)
    miss_flag = np.where(use_obs, False, missed)
    return DeliveredTick(latency=run_t, accuracy=acc, energy=energy,
                         missed=missed, run_power=p, observed=observed,
                         profiled=profiled, miss_flag=miss_flag)


def deliver_step(i_glob, j_act, scale, dvec, phi_true, *,
                 latency_kl, run_power_kl, q_fail, is_anytime_k,
                 lvl_lat_kml, lvl_valid_km, lvl_acc_km, f_zero=0.0):
    """Traceable twin of :func:`deliver_tick` for jitted callers (the
    traffic megatick scan — DESIGN.md §7): identical op-for-op math on
    jnp arrays, so under f64 every output is bitwise-equal to the numpy
    kernel on the same inputs (``tests/test_traffic.py`` pins this).

    ``i_glob``/``j_act``/``scale``/``dvec`` are the traced per-lane
    round inputs; the keyword arrays are the profile-table constants the
    host kernel reads from ``table``/``st`` (baked into the caller's
    trace once).  ``profiled_pick`` is fixed to the *executed* config's
    profiled latency (the gateway case — only the ALERT_DNN ablation,
    which never runs through this path, decouples the two).  Returns the
    :class:`DeliveredTick` fields as a plain tuple in declaration order.

    ``f_zero``: jitted callers must pass a RUNTIME zero (a traced scalar
    argument).  XLA CPU contracts ``a * b + c`` into one-rounding FMAs —
    the ``energy`` accumulation is the one mul+add chain here — while
    the numpy kernel always rounds twice; adding a runtime zero to each
    product pins the numpy rounding (``fma(a, b, 0) == round(a * b)``
    exactly, so the value is identical whether or not the compiler
    contracts).  Eager callers can leave the default — eager ops never
    contract.
    """
    import jax.numpy as jnp

    # The constants arrive as numpy (indexable by tracers only as jnp
    # arrays); asarray at trace time is free and keeps f64 under the
    # caller's x64 scope.
    latency_kl = jnp.asarray(latency_kl)
    run_power_kl = jnp.asarray(run_power_kl)
    is_anytime_k = jnp.asarray(is_anytime_k)
    lvl_lat_kml = jnp.asarray(lvl_lat_kml)
    lvl_valid_km = jnp.asarray(lvl_valid_km)
    lvl_acc_km = jnp.asarray(lvl_acc_km)
    m = lvl_lat_kml.shape[1]
    lat = latency_kl[i_glob, j_act] * scale
    missed = lat > dvec
    # Advanced indices split by a slice put the lane axis first -> [S, M]
    # (numpy semantics, which jnp follows — same layout as the host
    # kernel's fancy index).
    lvl_lat = lvl_lat_kml[i_glob, :, j_act]
    completed = lvl_valid_km[i_glob] & \
        (lvl_lat * scale[:, None] <= dvec[:, None])
    any_done = completed.any(axis=1)
    last_done = (m - 1) - jnp.argmax(completed[:, ::-1], axis=1)
    acc = jnp.where(any_done, lvl_acc_km[i_glob, last_done], q_fail)
    run_t = jnp.minimum(lat, dvec)
    p = run_power_kl[i_glob, j_act]
    energy = (p * run_t + f_zero) + \
        (phi_true * p * jnp.maximum(dvec - run_t, 0.0) + f_zero)
    rows = jnp.arange(i_glob.shape[0])
    use_obs = missed & is_anytime_k[i_glob] & any_done
    obs_lat = lvl_lat[rows, last_done] * scale
    obs_prof = lvl_lat[rows, last_done]
    observed = jnp.where(use_obs, obs_lat, run_t)
    profiled = jnp.where(use_obs, obs_prof, latency_kl[i_glob, j_act])
    miss_flag = jnp.where(use_obs, False, missed)
    return (run_t, acc, energy, missed, p, observed, profiled, miss_flag)


# ------------------------------------------------------------------ #
# Fleet-scale simulation: S streams, one engine call per tick         #
# ------------------------------------------------------------------ #
@dataclasses.dataclass(frozen=True)
class StreamSpec:
    """One tenant of a heterogeneous fleet: its own environment trace
    (per-stream :class:`Phase` schedule), its own optimisation problem
    (``goal`` + ``constraints`` — deadline, accuracy goal, energy budget),
    and its own lifetime (``arrival`` tick; departure is implicit at
    ``arrival + trace.n``, so streams join and leave mid-run)."""

    trace: EnvironmentTrace
    goal: Goal
    constraints: Constraints
    arrival: int = 0


@dataclasses.dataclass
class FleetResult:
    """Per-stream, per-tick outcomes of a fleet run: arrays are [S, T]
    on the shared global tick grid (ragged fleets are zero-padded outside
    each stream's ``[arrival, arrival + length)`` window; ``active`` marks
    the live cells).  :meth:`stream` slices a stream's own local-length
    :class:`TraceResult` back out."""

    energy: np.ndarray
    accuracy: np.ndarray
    latency: np.ndarray
    missed: np.ndarray
    scheme: str = ""
    budget: np.ndarray | None = None       # [S, T]
    arrivals: np.ndarray | None = None     # [S] global arrival tick
    lengths: np.ndarray | None = None      # [S] per-stream trace length
    active: np.ndarray | None = None       # [S, T] live-cell mask
    has_budget: np.ndarray | None = None   # [S] stream has an energy goal

    @property
    def n_streams(self) -> int:
        """Number of streams S in the fleet result."""
        return self.energy.shape[0]

    def _window(self, s: int) -> slice:
        a = 0 if self.arrivals is None else int(self.arrivals[s])
        n = self.energy.shape[1] if self.lengths is None \
            else int(self.lengths[s])
        return slice(a, a + n)

    def stream(self, s: int) -> TraceResult:
        """Stream s's own local-length :class:`TraceResult`, sliced out
        of the global tick grid."""
        w = self._window(s)
        budget = None
        if self.budget is not None and (
                self.has_budget is None or self.has_budget[s]):
            budget = self.budget[s, w]
        return TraceResult(
            self.energy[s, w], self.accuracy[s, w], self.latency[s, w],
            self.missed[s, w], self.scheme, budget=budget)

    @property
    def results(self) -> list[TraceResult]:
        """Every stream's :class:`TraceResult` (see :meth:`stream`)."""
        return [self.stream(s) for s in range(self.n_streams)]

    def _live(self, x: np.ndarray) -> np.ndarray:
        return x if self.active is None else x[self.active]

    @property
    def mean_energy(self) -> float:
        """Mean per-input energy (J) over live cells only."""
        return float(self._live(self.energy).mean())

    @property
    def mean_error(self) -> float:
        """Mean (1 - delivered accuracy) over live cells only."""
        return float(1.0 - self._live(self.accuracy).mean())

    @property
    def miss_rate(self) -> float:
        """Deadline-miss fraction over live cells only."""
        return float(self._live(self.missed).mean())


class FleetSim:
    """S independent ALERT streams advanced on one global tick grid.

    Every stream has its own environment randomness, Kalman state,
    windowed accuracy goal — and, in the general form, its own goal type,
    constraints, arrival tick, and lifetime.  Per tick the estimation +
    selection for ALL live streams is ONE :class:`BatchedAlertEngine` call
    over the [S, K, L] grid (per-stream ``goal_kind`` codes + active-lane
    mask, DESIGN.md §5), and the filter banks apply one fused masked
    update.  Streams outside their ``[arrival, arrival + n)`` window are
    dead lanes: masked out of selection and feedback, never re-padded, so
    the engine's jit cache is untouched by churn.

    Semantics per stream are identical to the scalar loop the paper
    describes (and that ``InferenceSim.run_alert`` exposed pre-fleet):
    windowed accuracy goal, miss inflation, overhead subtraction,
    relaxation priority, and the anytime uncensored-observation co-design
    are all preserved — ``tests/test_batched.py`` pins this with exact
    trajectory and join/leave slice-equality tests.
    """

    def __init__(self, table: ProfileTable,
                 traces: Sequence[EnvironmentTrace],
                 phi_true: float = 0.25,
                 arrivals: Sequence[int] | None = None):
        self.table = table
        self.phi_true = phi_true
        self.n_streams = len(traces)
        self.lengths = np.asarray([t.n for t in traces], dtype=np.int64)
        self.arrivals = np.zeros(self.n_streams, dtype=np.int64) \
            if arrivals is None else np.asarray(arrivals, dtype=np.int64)
        assert self.arrivals.shape == (self.n_streams,)
        assert np.all(self.arrivals >= 0)
        self.n_ticks = int((self.arrivals + self.lengths).max())
        self.n_inputs = self.n_ticks   # lockstep-era alias
        s_n, t_n = self.n_streams, self.n_ticks
        # Padded [S, T] environment grids: each stream's trace occupies its
        # arrival window; padding is a benign 1.0 (dead lanes are masked
        # out of everything anyway).
        self.xi = np.ones((s_n, t_n))
        self.lam = np.ones((s_n, t_n))
        self.deadline_scale = np.ones((s_n, t_n))
        self.active = np.zeros((s_n, t_n), dtype=bool)
        for s, tr in enumerate(traces):
            a, n = int(self.arrivals[s]), int(self.lengths[s])
            self.xi[s, a:a + n] = tr.xi
            self.lam[s, a:a + n] = tr.lam
            self.deadline_scale[s, a:a + n] = tr.deadline_scale
            self.active[s, a:a + n] = True
        groups = table.anytime_groups()
        self._anytime_idx = sorted({i for g in groups.values() for i in g})
        self._trad_idx = [i for i in range(len(table.candidates))
                          if i not in self._anytime_idx]
        self._is_anytime = np.zeros(len(table.candidates), bool)
        self._is_anytime[self._anytime_idx] = True
        self.engine: BatchedAlertEngine | None = None  # last run's engine

    @classmethod
    def from_phases(cls, table: ProfileTable, phases: tuple[Phase, ...],
                    n_streams: int, *, seed: int = 0,
                    phi_true: float = 0.25, length_cv: float = 0.0,
                    deadline_cv: float = 0.0) -> "FleetSim":
        """Homogeneous lockstep fleet: ``n_streams`` independently seeded
        clones of one :class:`Phase` schedule."""
        traces = [EnvironmentTrace(phases, seed=seed + s,
                                   length_cv=length_cv,
                                   deadline_cv=deadline_cv)
                  for s in range(n_streams)]
        return cls(table, traces, phi_true=phi_true)

    @classmethod
    def from_specs(cls, table: ProfileTable, specs: Sequence[StreamSpec],
                   phi_true: float = 0.25) -> "FleetSim":
        """Heterogeneous, churning fleet from :class:`StreamSpec` tenants
        (run it with :meth:`run_specs`)."""
        return cls(table, [sp.trace for sp in specs], phi_true=phi_true,
                   arrivals=[sp.arrival for sp in specs])

    # -------------------------------------------------------------- #
    def run_alert(self, goal: Goal, cons: Constraints, *,
                  anytime: bool = True, power_control: bool = True,
                  dnn_control: bool = True, overhead: float = 0.0,
                  paper_faithful_energy: bool = True,
                  mesh=None, backend: str = "xla",
                  scheme_name: str = "alert",
                  faults=None) -> FleetResult:
        """Fleet-wide uniform goal/constraints (the Table-3 schemes)."""
        return self.run_streams(
            [goal] * self.n_streams, [cons] * self.n_streams,
            anytime=anytime, power_control=power_control,
            dnn_control=dnn_control, overhead=overhead,
            paper_faithful_energy=paper_faithful_energy,
            mesh=mesh, backend=backend, scheme_name=scheme_name,
            faults=faults)

    def run_specs(self, specs: Sequence[StreamSpec],
                  **kwargs) -> FleetResult:
        """Run the per-spec goals/constraints (fleet built via
        :meth:`from_specs`, same stream order).  Keyword arguments —
        including ``mesh=`` — forward to :meth:`run_streams`."""
        assert len(specs) == self.n_streams
        return self.run_streams([sp.goal for sp in specs],
                                [sp.constraints for sp in specs], **kwargs)

    def run_streams(self, goals: Sequence[Goal],
                    constraints: Sequence[Constraints], *,
                    anytime: bool = True, power_control: bool = True,
                    dnn_control: bool = True, overhead: float = 0.0,
                    paper_faithful_energy: bool = True,
                    mesh=None, backend: str = "xla",
                    scheme_name: str = "alert",
                    faults=None) -> FleetResult:
        """Advance the whole (possibly ragged, heterogeneous) fleet; one
        masked engine call per global tick.

        ``goals``/``constraints`` are per-stream (length ``n_streams``):
        every minimize-energy stream needs ``accuracy_goal`` on its
        Constraints, every maximize-accuracy stream ``energy_goal``.

        ``mesh`` (optional 1-D lane mesh,
        :func:`repro.launch.mesh.make_lane_mesh`) runs the decision path
        device-sharded: the engine scores lane shards SPMD and the Kalman
        banks keep their state lane-sharded with donated updates.  The
        lane pool is padded to the next mesh-size multiple with
        permanently dead lanes (masked, never delivered, never observed),
        so any fleet size works and per-stream results are bit-identical
        to the unsharded run (DESIGN.md §6).

        ``backend`` forwards to :class:`BatchedAlertEngine` —
        ``"pallas"`` scores every tick through the fused
        ``alert_select`` kernel, whose picks match the XLA engine's on
        every lane outside the kernel's tie margins (docs/KERNELS.md).

        ``faults`` (a :class:`~repro.traffic.faults.FaultSchedule` over
        ``n_streams`` lanes — this sim is lane-per-stream) injects
        volatility at each tick instant: the slow-down row multiplies
        onto the environment's true scale, and a lane inside a
        device-loss window drops its in-flight input (recorded as a
        miss: the request was on the dead device) and is masked out of
        selection and feedback until the device restores (DESIGN.md
        §10).
        """
        table = self.table
        assert len(goals) == self.n_streams
        assert len(constraints) == self.n_streams
        if faults is not None and faults.n_lanes != self.n_streams:
            raise ValueError(
                f"FaultSchedule covers {faults.n_lanes} lanes but the "
                f"fleet has {self.n_streams} streams")
        for g, c in zip(goals, constraints):
            if g is Goal.MINIMIZE_ENERGY and c.accuracy_goal is None:
                raise ValueError(f"{g} stream needs accuracy_goal")
            if g is Goal.MAXIMIZE_ACCURACY and c.energy_goal is None:
                raise ValueError(f"{g} stream needs energy_goal")
        idx = list(range(len(table.candidates)))
        if not anytime:
            idx = self._trad_idx
        if not dnn_control:
            # fastest traditional DNN only (ALERT_Power ablation)
            fastest = min(self._trad_idx,
                          key=lambda i: table.latency[i, -1])
            idx = [fastest]
        idx_arr = np.asarray(idx)
        sub = table.subset(idx)
        engine = BatchedAlertEngine(
            sub, None, overhead=overhead,
            paper_faithful_energy=paper_faithful_energy, mesh=mesh,
            backend=backend)
        self.engine = engine
        s_n, t_n = self.n_streams, self.n_ticks
        # Lane padding for the sharded engine: S must divide the mesh, so
        # the pool gains `pad` always-dead lanes (sanitised inside the
        # traced pass — they cannot perturb live lanes, see DESIGN.md §5).
        pad = 0 if mesh is None else (-s_n) % mesh.size
        s_all = s_n + pad
        gk = goal_codes(goals)                                      # [S]
        slow = SlowdownFilterBank(s_all, mesh=mesh)
        idle = IdlePowerFilterBank(s_all, mesh=mesh)
        has_q = np.asarray([c.accuracy_goal is not None
                            for c in constraints])
        q0 = np.asarray([c.accuracy_goal if c.accuracy_goal is not None
                         else 0.0 for c in constraints])
        has_b = np.asarray([c.energy_goal is not None
                            for c in constraints])
        e_base = np.asarray([c.energy_goal if c.energy_goal is not None
                             else 0.0 for c in constraints])
        dls = np.asarray([c.deadline for c in constraints])
        d_scale, act_grid = self.deadline_scale, self.active
        scale_mat = self.xi * self.lam                              # [S, T]
        if pad:
            gk = np.concatenate([gk, np.zeros(pad, dtype=np.int64)])
            q0 = np.concatenate([q0, np.zeros(pad)])
            e_base = np.concatenate([e_base, np.zeros(pad)])
            dls = np.concatenate([dls, np.ones(pad)])
            ones = np.ones((pad, t_n))
            d_scale = np.vstack([d_scale, ones])
            scale_mat = np.vstack([scale_mat, ones])
            act_grid = np.vstack([act_grid,
                                  np.zeros((pad, t_n), dtype=bool)])
        # The goal bank stays on host even under a mesh: its window-sum
        # compensation is the one place an XLA reduce could differ from
        # numpy in the final ulp, and the sharded sim pins *bitwise*
        # equality with the unsharded run (the Kalman banks' recurrences
        # are pure elementwise chains — those shard exactly).
        goal_bank = WindowedGoalBank(q0, s_all) if has_q.any() else None
        # System default power: race-to-idle = always the max cap.
        full_power_j = len(table.power_caps) - 1

        # Full-table staircases for vectorised anytime delivery.
        st = table.staircase_tensors()

        dmat = dls[:, None] * d_scale                               # [S, T]
        # Energy budgets scale with the per-input time allotment
        # (E_goal = P_goal * T_goal, paper Section 3.1).
        bmat = e_base[:, None] * d_scale                            # [S, T]
        out = FleetResult(np.zeros((s_n, t_n)), np.zeros((s_n, t_n)),
                          np.zeros((s_n, t_n)),
                          np.zeros((s_n, t_n), bool), scheme_name,
                          budget=bmat[:s_n] if has_b.any() else None,
                          arrivals=self.arrivals, lengths=self.lengths,
                          active=self.active, has_budget=has_b)

        for n in range(t_n):
            act = act_grid[:, n]                                    # [S]
            lost = None
            if faults is not None:
                dead = faults.dead_at(float(n))                     # [S]
                if pad:
                    dead = np.concatenate([dead, np.zeros(pad, bool)])
                lost = act & dead
                if lost.any():
                    # The in-flight input died with its device: a miss
                    # with no completion (zero accuracy/energy) —
                    # Zygarde's lost-work semantics.
                    out.missed[np.nonzero(lost[:s_n])[0], n] = True
                act = act & ~dead
            dvec = dmat[:, n]
            q_goal_eff = q0 if goal_bank is None else \
                goal_bank.current_goal()
            e_goal = bmat[:, n]
            # Pick-only pass: delivery below re-derives the real outcomes,
            # so the per-pick prediction gathers would be dead weight.
            batch = engine.select(slow.mu, slow.sigma, idle.phi, dvec,
                                  accuracy_goal=q_goal_eff,
                                  energy_goal=e_goal,
                                  goal_kind=gk, active=act,
                                  predictions=False)
            i_local = batch.model_index                             # [S]
            j_pick = batch.power_index                              # [S]
            j_act = np.full(s_all, full_power_j) if not power_control \
                else j_pick
            i_glob = idx_arr[i_local]
            scale = scale_mat[:, n]
            if faults is not None:
                fmul = faults.slow_at(float(n))
                if pad:
                    fmul = np.concatenate([fmul, np.ones(pad)])
                scale = scale * fmul

            # --- vectorised delivery + feedback pair (the shared tick
            # kernel: staircase Eq. 10 for real, anytime co-design — a
            # missed deadline with a completed level is UNCENSORED) ---
            d = deliver_tick(table, st, i_glob, j_act, scale, dvec,
                             self.phi_true, self._is_anytime,
                             sub.latency[i_local, j_pick])
            live = np.nonzero(act)[0]
            out.latency[live, n] = d.latency[live]
            out.accuracy[live, n] = d.accuracy[live]
            out.energy[live, n] = d.energy[live]
            out.missed[live, n] = d.missed[live]

            observe_fleet(
                slow, idle, d.observed, d.profiled,
                deadline_missed=d.miss_flag,
                idle_power=self.phi_true * d.run_power,
                active_power=sub.run_power[i_local, j_pick], mask=act)
            if goal_bank is not None:
                goal_bank.record(d.accuracy, mask=act)
        return out


def run_fleet(table: ProfileTable, specs: Sequence[StreamSpec], *,
              phi_true: float = 0.25, **kwargs) -> FleetResult:
    """One-call heterogeneous fleet run: build a :class:`FleetSim` from
    ``specs`` (per-stream traces, goals, constraints, arrivals) and advance
    it tick by tick through one masked batched-engine call per tick.
    Pass ``mesh=`` (see :func:`repro.launch.mesh.make_lane_mesh`) to run
    the decision path lane-sharded over devices — results are
    bit-identical either way (DESIGN.md §6)."""
    fleet = FleetSim.from_specs(table, specs, phi_true=phi_true)
    return fleet.run_specs(specs, **kwargs)
