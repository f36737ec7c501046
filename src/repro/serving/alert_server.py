"""The end-to-end ALERT serving loop over a REAL model on this host.

Ties together: ServeEngine (per-level compiled programs), the batched
scoring engine (Kalman feedback + Eq. 4/5 selection), DeadlineBatcher, and
a measured ProfileTable built at startup (paper: t^train profiling).  This
is what ``examples/serve_alert.py`` drives.

Two frontends share the profiling pass and the scoring engine:

* :class:`AlertServer` — one request stream; its ``AlertController`` is the
  S=1 wrapper over :class:`~repro.core.batched.BatchedAlertEngine`.
* :class:`FleetAlertServer` — S request streams multiplexed onto one
  ServeEngine: per tick, ONE batched engine call scores every live
  stream's (model, power) grid (per-lane goal codes + active mask — the
  tenants may mix Eq. 4 and Eq. 5 goals), then the per-level compiled
  programs execute each stream's pick and a fused masked filter-bank
  update absorbs all measurements.  Streams are admitted and retired
  between ticks: lanes are recycled, not re-padded, so churn never
  re-traces the scoring executable (DESIGN.md §5).

Power on this host cannot be actuated (see DESIGN.md §2), so the power
dimension is bookkeeping through the same PowerModel the profiles use; the
DNN dimension (anytime level) is fully real — levels are separately
compiled programs with genuinely different latencies.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core.batched import (BatchedAlertEngine, GOAL_MAX_ACCURACY,
                                GOAL_MIN_ENERGY, WindowedGoalBank,
                                goal_codes)
from repro.core.controller import AlertController, Constraints, Goal
from repro.core.kalman import (IdlePowerFilterBank, SlowdownFilterBank,
                               observe_fleet)
from repro.core.power import PowerModel
from repro.core.profiles import Candidate, ProfileTable
from repro.obs.trace import count as obs_count, span as obs_span
from repro.serving.engine import ServeEngine


@dataclasses.dataclass
class ServedInput:
    """One served request's outcome: the executed anytime level, the
    booked power cap, realised latency/accuracy/energy, and whether the
    controller's pick was feasible."""

    level: int
    power_cap: float
    latency: float
    missed: bool
    accuracy: float
    energy: float
    feasible: bool


def profile_serve_table(engine: ServeEngine, params,
                        level_accuracies: list[float],
                        power_model: PowerModel,
                        n_power_buckets: int = 4,
                        profile_iters: int = 3, q_fail: float = 0.0,
                        prompt_len: int = 8,
                        gen_tokens: int = 4) -> ProfileTable:
    """t^train profiling pass: measure each anytime level on this host and
    extrapolate across power buckets with the compute-bound 1/f rule."""
    cfg = engine.model.cfg
    levels = engine.levels
    base = np.zeros(len(levels))
    prompt = np.zeros((engine.batch_size, prompt_len), np.int32)
    for li, lvl in enumerate(levels):
        engine.generate(params, prompt, gen_tokens, level=lvl)  # warmup
        ts = []
        for _ in range(profile_iters):
            r = engine.generate(params, prompt, gen_tokens, level=lvl)
            ts.append(r["latency"])
        base[li] = float(np.mean(ts))

    caps = power_model.buckets(n_power_buckets)
    lat = np.zeros((len(levels), len(caps)))
    pw = np.zeros_like(lat)
    for j, cap in enumerate(caps):
        f = power_model.speed_fraction(cap)
        lat[:, j] = base / f
        pw[:, j] = power_model.power_at_fraction(f)
    cands = [
        Candidate(name=f"level{lvl}", flops=0.0, bytes_hbm=0.0,
                  accuracy=level_accuracies[li],
                  is_anytime_level=cfg.nest_levels > 1,
                  anytime_group="anytime" if cfg.nest_levels > 1
                  else None, level=li + 1)
        for li, lvl in enumerate(levels)]
    return ProfileTable(cands, caps, lat, pw, q_fail=q_fail)


class AlertServer:
    """One request stream over a real model: profile the levels at
    startup (t^train), then serve inputs one at a time through the
    :class:`~repro.core.controller.AlertController` loop (S=1 wrapper of
    the batched engine).

    ``obs`` (an optional :class:`~repro.obs.FlightRecorder`, a pure
    observer) records each request as a root span ``request`` (args: the
    request id ``rid`` and the level) over ``controller_select``, the
    engine's ``first_token`` / ``decode_step`` / ``token_fetch`` and
    ``controller_observe``, all sharing the request id, and counts
    ``requests``; the same spans reach a running profiler's trace
    (:mod:`repro.obs.trace`)."""

    def __init__(self, engine: ServeEngine, params,
                 level_accuracies: list[float], goal: Goal,
                 power_model: PowerModel | None = None,
                 n_power_buckets: int = 4,
                 profile_iters: int = 3, q_fail: float = 0.0,
                 prompt_len: int = 8, gen_tokens: int = 4, obs=None):
        self.obs = obs
        self.engine = engine
        self.params = params
        self.goal = goal
        self.prompt_len = prompt_len
        self.gen_tokens = gen_tokens
        pm = power_model or PowerModel()
        self.power_model = pm
        self.table = profile_serve_table(
            engine, params, level_accuracies, pm,
            n_power_buckets=n_power_buckets, profile_iters=profile_iters,
            q_fail=q_fail, prompt_len=prompt_len, gen_tokens=gen_tokens)
        self.controller = AlertController(self.table, goal, obs=obs)
        self.history: list[ServedInput] = []

    def serve_one(self, prompt: np.ndarray, constraints: Constraints
                  ) -> ServedInput:
        """Select a (level, power) for this input, run the level's
        compiled program under the deadline, book energy through the
        power model, and feed the outcome back to the controller."""
        ob = self.obs
        with obs_span(ob, "request", "serve",
                      rid=len(self.history)) as req:
            with obs_span(ob, "controller_select", "serve"):
                d = self.controller.select(constraints)
            lvl = self.engine.levels[d.model_index]
            req.set(level=lvl or 0)
            r = self.engine.generate(self.params, prompt, self.gen_tokens,
                                     level=lvl,
                                     deadline_s=constraints.deadline,
                                     obs=ob)
            lat = r["latency"]
            missed = (lat > constraints.deadline) or not r["complete"]
            acc = self.table.candidates[d.model_index].accuracy \
                if not missed else self.table.q_fail
            f = self.power_model.speed_fraction(d.power_cap)
            p = self.power_model.power_at_fraction(f)
            run_t = min(lat, constraints.deadline)
            energy = p * run_t + self.controller.idle_power.phi * p * \
                max(constraints.deadline - run_t, 0.0)
            with obs_span(ob, "controller_observe", "serve"):
                self.controller.observe(
                    run_t, deadline_missed=missed,
                    idle_power=0.25 * p, delivered_accuracy=acc)
            out = ServedInput(level=lvl or 0, power_cap=d.power_cap,
                              latency=lat, missed=missed, accuracy=acc,
                              energy=energy, feasible=d.feasible)
            self.history.append(out)
        obs_count(ob, "requests", server="alert")
        return out


class FleetAlertServer:
    """Concurrent request streams, scored by one batched engine call.

    Each stream keeps its own Kalman state (slow-down xi, idle-power phi),
    windowed accuracy goal, and — unlike a lockstep fleet — its own *goal
    type*: Eq. 4 (minimize-energy) and Eq. 5 (maximize-accuracy) tenants
    share one engine call via per-lane ``goal_kind`` codes.  A
    ``serve_tick`` scores ALL live streams' (model, power) grids in a
    single jit-compiled pass, executes every live stream's pick through
    the per-level compiled programs, and absorbs all measurements with one
    fused masked bank update — the controller overhead per stream shrinks
    with S, which is the paper's overhead argument (0.6-1.7 % per input)
    at fleet scale.

    Streams churn between ticks: :meth:`admit` leases a free lane (the
    filter banks recycle the departed tenant's slot — no re-padding, no
    re-trace while within capacity) and :meth:`retire` releases one.  When
    every lane is occupied, :meth:`admit` doubles capacity (banks
    :meth:`~repro.core.kalman.SlowdownFilterBank.grow`), which re-traces
    once at the new ``[S]`` — the amortised cost model of a dynamic array.

    ``mesh=`` (1-D lane mesh, :func:`repro.launch.mesh.make_lane_mesh`)
    shards the scoring pass and all bank state over devices: capacity is
    rounded up to — and always grows in — mesh-size multiples (the spare
    lanes start dead and are leased by later admissions), filter/goal
    state stays lane-sharded on device between ticks, and churn remains
    re-trace-free (DESIGN.md §6).

    ``backend="pallas"`` scores ticks through the fused ``alert_select``
    kernel instead of the XLA passes — margin-contract picks, same
    churn/no-retrace contract (docs/KERNELS.md).
    """

    def __init__(self, engine: ServeEngine, params,
                 level_accuracies: list[float], goal: Goal,
                 n_streams: int,
                 power_model: PowerModel | None = None,
                 n_power_buckets: int = 4,
                 profile_iters: int = 3, q_fail: float = 0.0,
                 prompt_len: int = 8, gen_tokens: int = 4,
                 accuracy_window: int = 10,
                 start_active: bool = True,
                 mesh=None, backend: str = "xla", obs=None):
        # Optional flight recorder (repro.obs.FlightRecorder): tick
        # timing + served/miss/energy counters, pure observer only.
        self.obs = obs
        self._ob = obs if (obs is not None
                           and getattr(obs, "enabled", False)) else None
        self.engine = engine
        self.params = params
        self.goal = goal
        self.gen_tokens = gen_tokens
        pm = power_model or PowerModel()
        self.power_model = pm
        self.table = profile_serve_table(
            engine, params, level_accuracies, pm,
            n_power_buckets=n_power_buckets, profile_iters=profile_iters,
            q_fail=q_fail, prompt_len=prompt_len, gen_tokens=gen_tokens)
        self.mesh = mesh
        # Sharded lane pools round up to a device multiple; the extra
        # lanes start dead and are recycled by admissions like any other.
        pad = 0 if mesh is None else (-n_streams) % mesh.size
        cap = n_streams + pad
        self.scoring = BatchedAlertEngine(self.table, goal, mesh=mesh,
                                          backend=backend)
        self.slowdown = SlowdownFilterBank(cap, mesh=mesh)
        self.idle_power = IdlePowerFilterBank(cap, mesh=mesh)
        self.accuracy_window = accuracy_window
        self._goal_bank: WindowedGoalBank | None = None
        self.active = np.concatenate(
            [np.full(n_streams, bool(start_active)), np.zeros(pad, bool)])
        # Quarantined lanes (device loss, persistent stragglers): never
        # leased again until the operator clears them (DESIGN.md §10).
        self._dead = np.zeros(cap, bool)
        self.goal_kinds = np.full(cap, goal_codes([goal])[0],
                                  dtype=np.int64)
        # Per-lane Constraints overrides (installed by admit): tenants may
        # carry their own deadlines/goals instead of sharing the
        # serve_tick argument.
        self.lane_constraints: list[Constraints | None] = [None] * cap
        self.history: list[list[ServedInput | None]] = []

    @property
    def n_streams(self) -> int:
        """Lane capacity (live + free); ``active`` marks the live ones."""
        return self.active.shape[0]

    # ------------------------------------------------------------------ #
    # churn: lane lease / release between ticks                          #
    # ------------------------------------------------------------------ #
    def admit(self, goal: Goal | None = None,
              constraints: Constraints | None = None) -> int:
        """Lease a lane for a new stream; returns its lane id.

        The lane's filter state is re-initialised to the paper's priors and
        its accuracy window cleared (a new tenant must not inherit the
        departed stream's environment estimate).  Within capacity this
        touches only ``[S]`` vectors — the engine's compiled executables
        are untouched.

        ``constraints`` installs a per-lane override: gateway-style
        tenants carry their own deadline and accuracy/energy goal, used
        by :meth:`serve_tick` whenever its ``constraints`` argument (or
        this lane's entry in it) is ``None``.
        """
        free = np.nonzero(~self.active & ~self._dead)[0]
        if free.size == 0:
            new_cap = max(2 * self.n_streams, 1)
            if self.mesh is not None:
                # Grow in sharded multiples (doubling preserves this as
                # long as capacity starts as a multiple, which __init__
                # guarantees; max(..., mesh.size) covers the degenerate 0).
                new_cap = max(new_cap, self.mesh.size)
            lane = self.n_streams
            self.slowdown.grow(new_cap)
            self.idle_power.grow(new_cap)
            if self._goal_bank is not None:
                self._goal_bank.grow(new_cap)
            self.active = np.concatenate(
                [self.active, np.zeros(new_cap - lane, bool)])
            self._dead = np.concatenate(
                [self._dead, np.zeros(new_cap - lane, bool)])
            self.goal_kinds = np.concatenate(
                [self.goal_kinds,
                 np.full(new_cap - lane, goal_codes([self.goal])[0],
                         dtype=np.int64)])
            self.lane_constraints.extend([None] * (new_cap - lane))
        else:
            lane = int(free[0])
        self.slowdown.reset_lanes([lane])
        self.idle_power.reset_lanes([lane])
        if self._goal_bank is not None:
            self._goal_bank.reset_lanes([lane])
        self.goal_kinds[lane] = goal_codes([goal or self.goal])[0]
        self.lane_constraints[lane] = constraints
        self.active[lane] = True
        return lane

    def retire(self, lane: int) -> None:
        """Release a lane; its slot is recycled by a later :meth:`admit`."""
        self.active[lane] = False
        self.lane_constraints[lane] = None

    def fail_lanes(self, lanes) -> None:
        """Quarantine ``lanes`` (device loss or a tripped persistent
        straggler — e.g. everything a
        :func:`repro.runtime.elastic.dead_lane_mask` marks): their
        streams stop serving immediately and the lanes are never leased
        by :meth:`admit` again, so capacity re-rounds to the survivors
        without touching any other lane's state — the §5 churn
        protocol, no re-traces.  Tenants re-admit onto surviving lanes
        via :meth:`admit` as usual."""
        lanes = np.atleast_1d(np.asarray(lanes, dtype=np.int64))
        for lane in lanes:
            self.active[lane] = False
            self._dead[lane] = True
            self.lane_constraints[lane] = None
        if self._ob is not None and lanes.size:
            self._ob.metrics.counter(
                "quarantine_events", gateway="fleet_server").inc()
            self._ob.metrics.counter(
                "lanes_quarantined", gateway="fleet_server").inc(
                int(lanes.size))
            self._ob.spans.event("quarantine", cat="fault",
                                 lanes=[int(x) for x in lanes])

    def revive_lanes(self, lanes) -> None:
        """Clear the quarantine on ``lanes`` (device restored after a
        power cycle); the lanes return to the free pool for
        :meth:`admit` to lease — state re-initialised on lease, exactly
        like any recycled lane."""
        for lane in np.atleast_1d(np.asarray(lanes, dtype=np.int64)):
            self._dead[lane] = False

    # ------------------------------------------------------------------ #
    def _effective_accuracy_goal(self, constraints) -> np.ndarray:
        """Per-stream effective Q_goal from each live stream's constraint.
        A stream whose goal changes gets its accuracy window reset (same
        semantics as the scalar controller's recreate-on-change), without
        discarding the other streams' history.  Lanes that are dead or
        optimise Eq. 5 ride along with a zero placeholder."""
        goals = np.zeros(self.n_streams, dtype=np.float64)
        for s in np.nonzero(self.active)[0]:
            if self.goal_kinds[s] != GOAL_MIN_ENERGY:
                continue
            c = constraints[s]
            if c is None or c.accuracy_goal is None:
                raise ValueError(f"minimize-energy stream {s} needs "
                                 "accuracy_goal on its Constraints")
            goals[s] = c.accuracy_goal
        if self._goal_bank is None:
            self._goal_bank = WindowedGoalBank(goals, self.n_streams,
                                               self.accuracy_window,
                                               mesh=self.mesh)
        else:
            self._goal_bank.set_goals(goals)
        return self._goal_bank.current_goal()

    def serve_tick(self, prompts,
                   constraints=None) -> list[ServedInput | None]:
        """Serve one input per live stream; one engine call scores all of
        them.  ``prompts``/``constraints`` are capacity-length sequences;
        entries at dead lanes are ignored (``None`` is fine).  A ``None``
        ``constraints`` argument — or a ``None`` entry at a live lane —
        falls back to the lane's :meth:`admit`-installed override, so
        gateway tenants carry their own deadlines.  Returns one
        ``ServedInput`` per live lane, ``None`` at dead lanes."""
        t_tick = time.perf_counter() if self._ob is not None else 0.0
        cap = self.n_streams
        assert len(prompts) == cap
        if constraints is None:
            constraints = self.lane_constraints
        else:
            assert len(constraints) == cap
            constraints = [c if c is not None else self.lane_constraints[s]
                           for s, c in enumerate(constraints)]
        act = self.active.copy()
        deadlines = np.ones(cap)
        e_goals = np.zeros(cap)
        for s in np.nonzero(act)[0]:
            c = constraints[s]
            if c is None:
                raise ValueError(f"live stream {s} needs Constraints")
            deadlines[s] = c.deadline
            if self.goal_kinds[s] == GOAL_MAX_ACCURACY:
                if c.energy_goal is None:
                    raise ValueError(f"maximize-accuracy stream {s} needs "
                                     "energy_goal on its Constraints")
                e_goals[s] = c.energy_goal
        q_goals = self._effective_accuracy_goal(constraints)
        batch = self.scoring.select(
            self.slowdown.mu, self.slowdown.sigma, self.idle_power.phi,
            deadlines, accuracy_goal=q_goals, energy_goal=e_goals,
            goal_kind=self.goal_kinds, active=act)

        outs: list[ServedInput | None] = [None] * cap
        observed = np.zeros(cap)
        missed = np.zeros(cap, bool)
        accs = np.zeros(cap)
        active_p = np.ones(cap)
        # One host snapshot of phi for this tick's energy bookkeeping (it
        # only changes in the end-of-tick observe); per-lane indexing of a
        # sharded array would otherwise sync once per live stream.
        phi_host = np.asarray(self.idle_power.phi)
        for s in np.nonzero(act)[0]:
            i = int(batch.model_index[s])
            lvl = self.engine.levels[i]
            r = self.engine.generate(self.params, prompts[s],
                                     self.gen_tokens, level=lvl,
                                     deadline_s=float(deadlines[s]))
            lat = r["latency"]
            miss = (lat > deadlines[s]) or not r["complete"]
            acc = self.table.q_fail if miss \
                else self.table.candidates[i].accuracy
            cap_w = float(self.table.power_caps[int(batch.power_index[s])])
            f = self.power_model.speed_fraction(cap_w)
            p = self.power_model.power_at_fraction(f)
            run_t = min(lat, float(deadlines[s]))
            energy = p * run_t + float(phi_host[s]) * p * \
                max(float(deadlines[s]) - run_t, 0.0)
            observed[s], missed[s], accs[s] = run_t, miss, acc
            active_p[s] = p
            outs[s] = ServedInput(
                level=lvl or 0, power_cap=cap_w, latency=lat,
                missed=bool(miss), accuracy=float(acc),
                energy=float(energy), feasible=bool(batch.feasible[s]))

        profiled = self.table.latency[batch.model_index, batch.power_index]
        # One fused masked update for both banks (bit-identical per lane
        # to separate observes, at a single dispatch — the tick's whole
        # feedback step).
        observe_fleet(self.slowdown, self.idle_power, observed, profiled,
                      deadline_missed=missed, idle_power=0.25 * active_p,
                      active_power=active_p, mask=act)
        if self._goal_bank is not None:
            self._goal_bank.record(accs, mask=act)
        if self._ob is not None:
            m = self._ob.metrics
            lab = dict(gateway="fleet_server")
            m.counter("requests_served", **lab).inc(int(act.sum()))
            m.counter("deadline_misses", **lab).inc(int(missed.sum()))
            m.counter("energy_served_j", **lab).inc(
                float(sum(o.energy for o in outs if o is not None)))
            m.counter("rounds_served", **lab).inc()
            m.gauge("n_compiles_estimate", **lab).set(
                self.scoring.n_compiles()[0])
            m.gauge("n_compiles_select", **lab).set(
                self.scoring.n_compiles()[1])
            m.timer("serve_tick", **lab).observe(
                time.perf_counter() - t_tick)
        self.history.append(outs)
        return outs
