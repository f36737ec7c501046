"""The ALERT runtime controller (paper Section 3).

Per input n the controller runs the paper's four steps (Section 3.2.1):

1. *Measurement* — the caller reports the previous input's latency / power.
2. *Goal adjustment* — subtract the controller's own worst-case overhead from
   T_goal; re-derive the per-input accuracy goal from the N-window average.
3. *Feedback-based estimation* — update the slow-down filter xi (Eq. 6) and
   the idle-power filter phi (Eq. 8); predict latency (Idea 1), accuracy
   (Eq. 7 / staircase Eq. 10) and energy (Eq. 9) for every (model, power)
   cell.
4. *Pick a configuration* — Eq. 4 (minimize energy s.t. accuracy) or Eq. 5
   (maximize accuracy s.t. energy).  If no cell satisfies every constraint,
   constraints are relaxed in the paper's priority order: latency highest,
   then accuracy, then power (Section 3.3).

Scoring (estimation + selection) is delegated to the fleet-scale
:class:`repro.core.batched.BatchedAlertEngine`: this class is the S=1
wrapper that keeps the paper-shaped single-stream API (scalar Kalman
filters, windowed accuracy goal, one ``Decision`` per input) while the
grid math runs as one jit-compiled ``[S, K, L]`` pass.  The pre-engine
NumPy implementation survives verbatim in :mod:`repro.core.reference` as
the parity/benchmark baseline.
"""

from __future__ import annotations

import dataclasses
import enum
import math

import numpy as np
from scipy.special import erf as _erf

from repro.core.kalman import IdlePowerFilter, SlowdownFilter
from repro.core.profiles import ProfileTable
from repro.obs.trace import span as obs_span

_SQRT2 = math.sqrt(2.0)


def normal_cdf(x: np.ndarray) -> np.ndarray:
    """Vectorised standard-normal CDF (no ``np.vectorize``: scipy's ufunc
    erf evaluates the whole grid in C)."""
    return 0.5 * (1.0 + _erf(np.asarray(x, dtype=float) / _SQRT2))


class Goal(enum.Enum):
    """Which optimisation problem a stream solves: the paper's Eq. 2/4
    (minimize energy s.t. an accuracy goal) or Eq. 1/5 (maximize accuracy
    s.t. an energy budget).  Fleet callers encode these as per-lane int
    codes via :func:`repro.core.batched.goal_codes`."""

    MINIMIZE_ENERGY = "minimize_energy"      # Eq. 2 / Eq. 4
    MAXIMIZE_ACCURACY = "maximize_accuracy"  # Eq. 1 / Eq. 5


@dataclasses.dataclass(frozen=True)
class Constraints:
    """One stream's requirements: ``deadline`` (T_goal, seconds) plus the
    goal value its :class:`Goal` needs — ``accuracy_goal`` (Q_goal) for
    minimize-energy streams, ``energy_goal`` (E_goal, joules) for
    maximize-accuracy streams."""

    deadline: float                    # T_goal (seconds)
    accuracy_goal: float | None = None  # Q_goal  (min-energy task)
    energy_goal: float | None = None    # E_goal (J) (max-accuracy task)

    @staticmethod
    def from_power_budget(deadline: float, power_budget: float,
                          accuracy_goal: float | None = None) -> "Constraints":
        """Section 3.1: E_goal = P_goal * T_goal."""
        return Constraints(deadline=deadline,
                           accuracy_goal=accuracy_goal,
                           energy_goal=power_budget * deadline)


@dataclasses.dataclass(frozen=True)
class Decision:
    """One selection outcome: the picked (model, power-cap) cell, its
    predicted latency/accuracy/energy, and whether (or which) constraint
    had to be relaxed (Section 3.3)."""

    model_index: int
    power_index: int
    model_name: str
    power_cap: float
    predicted_latency: float
    predicted_accuracy: float
    predicted_energy: float
    feasible: bool          # did a cell satisfy every constraint?
    relaxed: str            # "" | "power" | "accuracy" — what had to give


@dataclasses.dataclass
class _Estimates:
    """All per-cell predictions for one selection round."""

    lat_mean: np.ndarray    # [K, L]
    lat_std: np.ndarray     # [K, L]
    accuracy: np.ndarray    # [K, L]  expected accuracy under the deadline
    energy: np.ndarray      # [K, L]  Eq. 9
    p_finish: np.ndarray    # [K, L]  P(t <= T_goal)


class WindowedAccuracyGoal:
    """Paper fn.3: the accuracy goal is the average over any continuous N
    inputs, so the per-input goal compensates for recently delivered
    accuracy."""

    def __init__(self, goal: float, window: int = 10):
        self.goal = goal
        self.window = window
        self._recent: list[float] = []

    def record(self, delivered: float) -> None:
        """Push one delivered accuracy into the last-N-1 window."""
        self._recent.append(delivered)
        if len(self._recent) > self.window - 1:
            self._recent.pop(0)

    def current_goal(self) -> float:
        """Effective per-input Q_goal after window compensation (the
        vectorised twin is ``WindowedGoalBank.current_goal``)."""
        if not self._recent:
            return self.goal
        need = self.goal * self.window - sum(self._recent)
        remaining = self.window - len(self._recent)
        return need - (remaining - 1) * self.goal


class AlertController:
    """The ALERT decision loop over a :class:`ProfileTable`.

    Parameters
    ----------
    table:
        Candidate models x power buckets with profiled latency/power.
    goal:
        Which of the paper's two optimisation problems to solve.
    kappa:
        Deviation multiplier used when treating latency probabilistically is
        not enough (e.g. ranking equally-accurate cells); the paper's
        "three standard deviations = 99.7 %" knob.  The *accuracy* estimate
        always integrates the full Normal distribution (Eq. 7), this knob
        never replaces it.
    overhead:
        Controller's own worst-case per-input overhead (seconds), subtracted
        from T_goal (Section 3.2.1 step 2).  Paper measures 0.6-1.7 % of
        input processing time.
    accuracy_window:
        N for the windowed accuracy goal (paper fn.3).
    paper_faithful_energy:
        If True (default) use Eq. 9 verbatim (mean-latency energy).  If
        False, use E[min(t, T)] under the Normal model — a strictly better
        estimator we evaluate as a beyond-paper variant in benchmarks.
    obs:
        Optional :class:`~repro.obs.FlightRecorder` (a pure observer):
        :meth:`select` records the batched engine's pick as a span
        ``engine_select``, which leaves the goal adjustment and the
        decision's host bookkeeping outside.
    """

    def __init__(self, table: ProfileTable, goal: Goal,
                 kappa: float = 3.0, overhead: float = 0.0,
                 accuracy_window: int = 10,
                 paper_faithful_energy: bool = True, obs=None):
        from repro.core.batched import BatchedAlertEngine

        self.obs = obs
        self.table = table
        self.goal = goal
        self.kappa = kappa
        self.overhead = overhead
        self.paper_faithful_energy = paper_faithful_energy
        self.slowdown = SlowdownFilter()
        self.idle_power = IdlePowerFilter()
        self._windowed_goal: WindowedAccuracyGoal | None = None
        self.accuracy_window = accuracy_window
        self._last_decision: Decision | None = None
        # The batched engine precomputes the padded anytime staircases from
        # the table and owns all grid scoring; this wrapper only keeps the
        # per-stream state (filters, windowed goal, last decision).
        self.engine = BatchedAlertEngine(
            table, goal, overhead=overhead,
            paper_faithful_energy=paper_faithful_energy)

    # ------------------------------------------------------------------ #
    # Step 1+3: measurement feedback                                      #
    # ------------------------------------------------------------------ #
    def observe(self, observed_latency: float,
                deadline_missed: bool = False,
                idle_power: float | None = None,
                delivered_accuracy: float | None = None,
                profiled_override: float | None = None) -> None:
        """Feed the previous input's measurements.

        ``profiled_override`` supports the anytime co-design: when the
        deepest level missed the deadline but level k completed, the level-k
        completion time is an UNCENSORED latency observation — pass it with
        level k's profiled latency.  (A traditional DNN only yields the
        censored "it was still running at T" observation, which the paper
        handles with the 0.2 inflation.)
        """
        if self._last_decision is None:
            return
        d = self._last_decision
        profiled = profiled_override if profiled_override is not None \
            else self.table.latency[d.model_index, d.power_index]
        self.slowdown.observe(observed_latency, profiled,
                              deadline_missed=deadline_missed)
        if idle_power is not None:
            active = self.table.run_power[d.model_index, d.power_index]
            self.idle_power.observe(idle_power, active)
        if delivered_accuracy is not None and self._windowed_goal is not None:
            self._windowed_goal.record(delivered_accuracy)

    # ------------------------------------------------------------------ #
    # Step 3: per-cell estimation                                         #
    # ------------------------------------------------------------------ #
    def estimate(self, deadline: float) -> _Estimates:
        """One fused engine pass at S=1; returns the paper-shaped [K, L]
        per-cell predictions (Eq. 7 / Eq. 9 / Eq. 10)."""
        est = self.engine.estimate(
            self.slowdown.mu, self.slowdown.sigma, self.idle_power.phi,
            np.asarray([deadline]))
        return _Estimates(est.lat_mean[0], est.lat_std[0],
                          est.accuracy[0], est.energy[0], est.p_finish[0])

    # ------------------------------------------------------------------ #
    # Step 2+4: goal adjustment and selection                             #
    # ------------------------------------------------------------------ #
    def select(self, constraints: Constraints) -> Decision:
        """One paper decision (steps 2+4): adjust the accuracy goal via
        the rolling window (fn.3), subtract overhead from the deadline,
        and pick the Eq. 4/Eq. 5 optimum with Section 3.3 relaxation —
        the S=1 slice of :meth:`BatchedAlertEngine.select`."""
        q_goal = constraints.accuracy_goal
        if q_goal is not None:
            if self._windowed_goal is None or \
                    self._windowed_goal.goal != q_goal:
                self._windowed_goal = WindowedAccuracyGoal(
                    q_goal, self.accuracy_window)
            q_goal_eff = self._windowed_goal.current_goal()
        else:
            q_goal_eff = None

        # Eq. 4 / Eq. 5 + Section 3.3 relaxation, fused with estimation in
        # one engine pass (the engine subtracts ``overhead`` from T_goal).
        with obs_span(self.obs, "engine_select", "controller"):
            batch = self.engine.select(
                self.slowdown.mu, self.slowdown.sigma,
                self.idle_power.phi, np.asarray([constraints.deadline]),
                accuracy_goal=q_goal_eff,
                energy_goal=constraints.energy_goal)
        i = int(batch.model_index[0])
        j = int(batch.power_index[0])
        decision = Decision(
            model_index=i, power_index=j,
            model_name=self.table.candidates[i].name,
            power_cap=float(self.table.power_caps[j]),
            predicted_latency=float(batch.predicted_latency[0]),
            predicted_accuracy=float(batch.predicted_accuracy[0]),
            predicted_energy=float(batch.predicted_energy[0]),
            feasible=bool(batch.feasible[0]),
            relaxed=batch.relaxed_name(0))
        self._last_decision = decision
        return decision
