"""Device-resident gateway megatick: the round clock as ONE jitted scan.

The host loop of :class:`~repro.traffic.gateway.SessionGateway` makes
one engine dispatch, one delivery call, one feedback call, and one
paging pass *per round*.  :class:`MegatickGateway` serves the same
workload with the whole inner round clock — effective-deadline math
(``T_goal - queueing delay``), the masked select, the shared delivery
kernel, and the Eq. 6/8 + goal-window feedback — inside ONE jitted
``lax.scan`` over rounds, dispatched in fixed-size *super-round* chunks
with every state buffer donated: a full load sweep never gathers state
and never re-traces.

**Regime contract.**  At ``tick >= max(rel_deadline)`` — the gateway's
default tick — a round's run time is capped at its effective deadline
(``run_t = min(lat, dvec) <= dvec <= rel_deadline <= tick``), so every
lane is idle at every round boundary and no admission decision depends
on a latency.  The loop then splits in two exact halves:

* a **host planner** that runs the gateway's own host half
  (:class:`~repro.traffic.gateway._GatewayCore`: request intake, round
  clock, device-loss quarantine, EDF admission with backpressure,
  fail-fast and same-session deferral, LRU lane placement) with no lane
  busy, and adds only the fill of a dense ``[R, L]`` round schedule and
  a page-in that moves metadata, never state;
* a **device scan** over that schedule, holding all per-session filter
  and goal-window state ``[S]``-resident (sessions are gathered to lanes
  by index and scattered back each round) — which makes session paging a
  semantic no-op: the host loop's ``export_lanes``/``import_lanes``
  round-trips are bitwise lossless and every per-lane operation is
  lane-independent, so lane placement cannot alter any outcome.

A finer tick couples admission to in-scan latencies (a busy lane defers
its session's next request); that regime stays on the host loop, and
:meth:`run` raises on it rather than silently diverge.

Every traced piece is the host loop's op-for-op twin —
:meth:`~repro.core.batched.BatchedAlertEngine.select_step_impl` (sigma
floor included), :func:`~repro.serving.sim.deliver_step`,
:func:`~repro.core.kalman.fused_fleet_step`, the goal bank's record step
and the numpy-pairwise window sum
(:func:`~repro.core.batched.goal_current_step_hostsum`) — so a megatick
:class:`~repro.traffic.gateway.GatewayResult` is bitwise-identical per
session to the fixed host loop at matched tick (``tests/test_traffic.py``
pins this against the gateway golden trace).  ``backend="pallas"``
launches the fused ``alert_select`` kernel inside the scan; ``mesh=``
shards the lane axis of every round via ``shard_map``
(:func:`repro.launch.mesh.lane_shard_map`).  DESIGN.md §7 has the layout.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import jax
import numpy as np

from repro.core.batched import (_goal_record_step, goal_codes,
                                goal_current_step_hostsum)
from repro.core.kalman import (IdlePowerFilterBank, SlowdownFilterBank,
                               fused_fleet_step)
from repro.core.precision import x64_scope
from repro.core.profiles import ProfileTable
from repro.obs.metrics import MetricsRegistry
from repro.obs.ring import round_aggregates
from repro.obs.trace import count as obs_count, span as obs_span
from repro.serving.sim import deliver_step
from repro.traffic.gateway import SERVED, GatewayResult, _GatewayCore
from repro.traffic.workloads import Session, TrafficRequest


@dataclasses.dataclass
class _Plan:
    """The planner's dense round schedule: ``[R, L]`` per-lane inputs for
    ``n_active`` real rounds (padded with all-inactive rounds to a
    super-round multiple), plus the :class:`GatewayResult` shell with
    every disposition already decided."""

    out: GatewayResult
    n_active: int
    act: np.ndarray         # [R, L] bool
    sid: np.ndarray         # [R, L] int64 dense session index; S inactive
    row: np.ndarray         # [R, L] int64 result row; -1 inactive
    rel: np.ndarray         # [R, L] f64 nominal relative deadline
    arr: np.ndarray         # [R, L] f64 arrival instant
    e_goal: np.ndarray      # [R, L] f64 effective energy goal
    scale: np.ndarray       # [R, L] f64 effective latency scale
    gk: np.ndarray          # [R, L] int64 goal codes
    dead: np.ndarray        # [R, L] bool lane-death mask (faults)
    now: np.ndarray         # [R] f64 round instants k * tick


class MegatickGateway(_GatewayCore):
    """Open-loop traffic with the round clock flattened on device.

    Drop-in for :class:`~repro.traffic.gateway.SessionGateway` in the
    coarse-tick regime (``tick >= max(rel_deadline)`` — the gateway's
    default tick): same constructor surface, same :meth:`run` contract,
    bitwise-identical :class:`GatewayResult` per session, but the inner
    round loop runs as a chunked, donated ``lax.scan`` with all
    per-session state ``[S]``-resident on device (see the module
    docstring for the regime contract).  ``chunk`` is the super-round
    size: rounds per device dispatch (the schedule is padded to a chunk
    multiple, so every dispatch reuses one compiled executable —
    ``n_compiles`` stays flat across a whole load sweep).
    """

    _label = "megatick"

    def __init__(self, table: ProfileTable, n_lanes: int, *,
                 phi_true: float = 0.25, overhead: float = 0.0,
                 tick: float | None = None,
                 max_queue: int | None = None,
                 min_feasible_latency: float | None = None,
                 accuracy_window: int = 10, backend: str = "xla",
                 mesh=None, chunk: int = 128, obs=None):
        # An attached flight recorder also adds the telemetry-ring
        # outputs to the scan (a separate jit cache entry).
        super().__init__(table, n_lanes, phi_true=phi_true,
                         overhead=overhead, tick=tick, max_queue=max_queue,
                         min_feasible_latency=min_feasible_latency,
                         accuracy_window=accuracy_window, backend=backend,
                         mesh=mesh, obs=obs)
        # Phase timers live in a registry even with no recorder attached
        # so plan/scan wall time ACCUMULATES across repeated run() calls
        # (total_s/count); last_plan_s/last_scan_s stay as read-through
        # aliases of the most recent observation.
        reg = self._ob.metrics if self._ob else MetricsRegistry()
        self._plan_timer = reg.timer("megatick_plan", gateway="megatick")
        self._scan_timer = reg.timer("megatick_scan", gateway="megatick")
        self.chunk = int(chunk)
        if mesh is not None and self.n_lanes % mesh.size:
            raise ValueError(
                f"lane-sharded megatick needs n_lanes divisible by the "
                f"mesh size ({mesh.size}); got {self.n_lanes}")
        self._chunk_jits: dict = {}

    # -------------------------------------------------------------- #
    # phase timers                                                    #
    # -------------------------------------------------------------- #
    @property
    def last_plan_s(self) -> float:
        """Wall time of the most recent :meth:`run`'s host planner
        (read-through alias of the ``megatick_plan`` phase timer; 0.0
        before the first run)."""
        return self._plan_timer.last_s

    @property
    def last_scan_s(self) -> float:
        """Wall time of the most recent :meth:`run`'s device round
        clock — scan dispatches + result scatter (read-through alias of
        the ``megatick_scan`` phase timer; 0.0 before the first run)."""
        return self._scan_timer.last_s

    @property
    def total_plan_s(self) -> float:
        """Planner wall time accumulated over every :meth:`run` of this
        gateway's lifetime (a load sweep's total planning cost)."""
        return self._plan_timer.total_s

    @property
    def total_scan_s(self) -> float:
        """Round-clock wall time accumulated over every :meth:`run` of
        this gateway's lifetime."""
        return self._scan_timer.total_s

    # -------------------------------------------------------------- #
    # host planner                                                    #
    # -------------------------------------------------------------- #
    def _page_in_meta(self, sids: np.ndarray,
                      round_k: int) -> np.ndarray:
        """:meth:`SessionGateway._page_in`'s lane placement and paging
        accounting (:meth:`~repro.traffic.gateway._GatewayCore._place`),
        without moving any state.

        The ``[S]``-resident scan buffers make the page *transfers* a
        semantic no-op (export/import round-trips are bitwise lossless
        and every per-lane op is lane-independent), but WHICH sessions
        page — and therefore ``pages_in``/``pages_out`` — is still the
        host loop's observable.  Under the regime contract every lane is
        idle at every round boundary, so every live lane may take a
        session.  ``sids`` are dense session indices (``sid_index`` of
        :meth:`_plan`: sessions in the order offered).
        """
        return self._place(sids, sids, ~self._dead, round_k)[0]

    def _plan(self, sessions: Sequence[Session],
              requests: list[TrafficRequest] | None,
              sid_index: dict[int, int], faults=None) -> _Plan:
        """Run the host loop's clock and admission up front: the
        gateway's own intake, round clock, device-loss quarantine and
        admission (:class:`~repro.traffic.gateway._GatewayCore`) with no
        lane busy (the regime contract), the metadata-only page-in, and
        the fill of the dense round schedule the scan consumes.

        ``faults`` is read at the host loop's round instants: death
        transitions quarantine lanes (residents marked stored, capacity
        shrinks), and each scheduled round records the schedule's
        numpy-f64 slow-down row — multiplied onto the ``[R, L]`` scale
        grid in the host's exact ``(xi*lam) * f`` order, so the scan
        sees bit-identical inputs.
        """
        rs = self._offer(sessions, requests)
        out, requests, sess = rs.out, rs.requests, rs.sess
        n = len(requests)
        if n == 0:
            return _Plan(out, 0, *(np.zeros((0, self.n_lanes)),) * 9,
                         np.zeros(0))
        max_rel = max(r.rel_deadline for r in requests)
        if rs.tick < max_rel:
            raise ValueError(
                f"megatick needs tick >= max relative deadline "
                f"({rs.tick} < {max_rel}): a finer tick couples admission "
                f"to in-round latencies (busy lanes at round "
                f"boundaries) — use SessionGateway for that regime")
        self._reset_pool(len(sessions))
        ob = self._ob
        code_of = {g: int(goal_codes([g])[0])
                   for g in {s.goal for s in sessions}}
        gk_of = {s.sid: code_of[s.goal] for s in sessions}
        # Flat per-field accumulators (one entry per served request),
        # scattered into the [R, L] schedule in one vectorized pass —
        # the planner's per-request Python is the megatick's only
        # remaining host cost, so keep the inner loop lean.
        now_l: list[float] = []
        f_round: list[int] = []
        f_lane: list[int] = []
        f_row: list[int] = []
        f_sid: list[int] = []
        f_rel: list[float] = []
        f_arr: list[float] = []
        f_eg: list[float] = []
        f_sc: list[float] = []
        f_gk: list[int] = []
        fault_mul: list[np.ndarray] = []    # [L] per scheduled round
        fault_dead: list[np.ndarray] = []   # [L] per scheduled round
        while rs.ri < n or len(rs.queue):
            now = self._next_round(rs)
            round_k = rs.round_k
            if faults is not None:
                self._quarantine(now, faults)
            with obs_span(ob, "plan_admit", "megatick", round_k=round_k):
                batch, _ = self._admit(rs, now)
            if batch:
                obs_count(ob, "rounds", gateway="megatick")
                with obs_span(ob, "plan_page", "megatick",
                              round_k=round_k):
                    dense = [sid_index[r.sid] for r in batch]
                    lanes = self._page_in_meta(
                        np.asarray(dense, dtype=np.int64), round_k)
                k = len(now_l)
                now_l.append(now)
                if faults is not None:
                    fault_mul.append(faults.slow_at(now))
                    fault_dead.append(self._dead.copy())
                for req, lane, dk in zip(batch, lanes, dense):
                    s = sess[req.sid]
                    f_round.append(k)
                    f_lane.append(int(lane))
                    f_row.append(req._row)
                    f_sid.append(dk)
                    f_rel.append(req.rel_deadline)
                    f_arr.append(req.arrival)
                    f_eg.append((s.constraints.energy_goal or 0.0)
                                * s.trace.deadline_scale[req.index])
                    f_sc.append(s.trace.xi[req.index]
                                * s.trace.lam[req.index])
                    f_gk.append(gk_of[req.sid])
            rs.round_k += 1
        n_active = len(now_l)
        n_pad = -n_active % self.chunk
        r_tot = n_active + n_pad
        s_tot = len(sessions)
        ln = self.n_lanes
        act = np.zeros((r_tot, ln), bool)
        sid = np.full((r_tot, ln), s_tot, dtype=np.int64)
        row = np.full((r_tot, ln), -1, dtype=np.int64)
        rel = np.zeros((r_tot, ln))
        arr = np.zeros((r_tot, ln))
        e_goal = np.zeros((r_tot, ln))
        scale = np.ones((r_tot, ln))
        gk = np.zeros((r_tot, ln), dtype=np.int64)
        now_v = np.zeros(r_tot)
        now_v[:n_active] = now_l
        kk = np.asarray(f_round, dtype=np.int64)
        lv = np.asarray(f_lane, dtype=np.int64)
        rw = np.asarray(f_row, dtype=np.int64)
        act[kk, lv] = True
        sid[kk, lv] = f_sid
        row[kk, lv] = rw
        rel[kk, lv] = f_rel
        arr[kk, lv] = f_arr
        e_goal[kk, lv] = f_eg
        scale[kk, lv] = f_sc
        gk[kk, lv] = f_gk
        dead = np.zeros((r_tot, ln), bool)
        if faults is not None and n_active:
            # The same elementwise f64 multiply the host applies after
            # its per-lane fill: (xi*lam) * f, bit for bit.
            scale[:n_active] = scale[:n_active] * np.stack(fault_mul)
            dead[:n_active] = np.stack(fault_dead)
        # Each row's disposition is unique (served XOR rejected XOR
        # shed), so the batched assignment reproduces the host loop's
        # in-round writes exactly.
        out.status[rw] = SERVED
        out.start[rw] = now_v[kk]
        return _Plan(out, n_active, act, sid, row, rel, arr, e_goal,
                     scale, gk, dead, now_v)

    # -------------------------------------------------------------- #
    # device scan                                                     #
    # -------------------------------------------------------------- #
    def _chunk_fn(self, policy: str, static_config, ring: bool = False):
        """Build (once per policy/config) the jitted super-round chunk:
        a donated ``lax.scan`` over ``chunk`` rounds.  Profile constants
        are baked into the trace; all shapes are fixed at
        ``[chunk, n_lanes]`` / ``[S]``, so every dispatch of a run — and
        every run of a load sweep — reuses one compiled executable.

        ``ring=True`` (an attached flight recorder) appends the
        telemetry-ring reductions (:func:`repro.obs.ring.
        round_aggregates`) as extra stacked ``ys`` — per-round scalars
        reduced from values the body already computes, with the donated
        carries untouched.  The flag is part of the jit key: the bare
        and instrumented executables coexist and the per-lane ops are
        identical (the pure-observer tests pin their outputs bitwise)."""
        key = (policy, static_config, ring)
        if key in self._chunk_jits:
            return self._chunk_jits[key]
        import jax.numpy as jnp

        ln = self.n_lanes
        st = self._st
        consts = dict(
            latency_kl=np.asarray(self.table.latency, np.float64),
            run_power_kl=np.asarray(self.table.run_power, np.float64),
            q_fail=float(self.table.q_fail),
            is_anytime_k=self._is_anytime,
            lvl_lat_kml=np.asarray(st.lvl_lat, np.float64),
            lvl_valid_km=np.asarray(st.lvl_valid, bool),
            lvl_acc_km=np.asarray(st.lvl_acc, np.float64))
        phi_true = self.phi_true
        window = self.accuracy_window
        depth = max(window - 1, 0)

        if policy == "static":
            i_fix, j_fix = int(static_config[0]), int(static_config[1])

            def body_static(fz, x):
                """Deliver-only round: fixed config, no controller
                state (the hindsight-static baseline)."""
                act, sidv, gkv, relv, arrv, egl, scl, deadv, now = x
                # Lane-death mask carried through the scan: the planner
                # never schedules onto a dead lane, so this is a no-op
                # by construction — kept as in-scan hardening (ROADMAP
                # item 1c) so a planner bug masks instead of serving.
                act = act & ~deadv
                dvec = jnp.where(act, relv - (now - arrv), 1.0)
                i = jnp.full((ln,), i_fix, jnp.int64)
                j = jnp.full((ln,), j_fix, jnp.int64)
                with jax.named_scope("deliver"):
                    run_t, acc, energy, missed, *_ = deliver_step(
                        i, j, scl, dvec, phi_true, f_zero=fz, **consts)
                sojourn = (now - arrv) + run_t
                ys = (run_t, acc, energy, missed, i, j, sojourn)
                if ring:
                    # Static picks have no feasibility/relaxation
                    # machinery: every active lane counts feasible,
                    # none relaxed.
                    ys = ys + round_aggregates(
                        act, act, jnp.zeros_like(i), energy, missed)
                return fz, ys

            def chunk_static(f_zero, xs):
                """One super-round dispatch of the static policy
                (``f_zero``: runtime zero pinning mul+add rounding
                against FMA contraction — see `deliver_step`)."""
                _, ys = jax.lax.scan(body_static, f_zero, xs)
                return ys

            fn = jax.jit(chunk_static)
            self._chunk_jits[key] = fn
            return fn

        select = self.engine.select_step_impl()
        slow_tpl = SlowdownFilterBank(1)
        idle_tpl = IdlePowerFilterBank(1)
        slow_params = slow_tpl.step_params()
        idle_params = idle_tpl.step_params()

        def body(carry, x, goal, fz):
            """One round, the host `_serve_round` op for op: gather the
            round's sessions to lanes, effective-deadline select,
            deliver, fused Eq. 6/8 + goal-window feedback, scatter
            back.  Inactive lanes carry the host loop's benign defaults
            (dvec 1, scale 1, goal 0) and their session index points
            one past the state buffers, so gathers clamp to a sanitised
            row and scatters drop — no masking pass anywhere."""
            mu, sigma, gain, qn, phv, var, buf, pos, count = carry
            act, sidv, gkv, relv, arrv, egl, scl, deadv, now = x
            # Lane-death mask in the carry path (ROADMAP item 1c): the
            # planner never schedules a dead lane, so this only hardens
            # the scan against a planner/schedule mismatch.
            act = act & ~deadv
            with jax.named_scope("select"):
                mu_l, sd_l, ph_l = mu[sidv], sigma[sidv], phv[sidv]
                g_l, q_l, v_l = gain[sidv], qn[sidv], var[sidv]
                dvec = jnp.where(act, relv - (now - arrv), 1.0)
                if depth:
                    acc_goal = goal_current_step_hostsum(
                        goal[sidv], buf[sidv], count[sidv], window, fz)
                else:
                    acc_goal = goal[sidv]
                i, j, _lat, _acc, _en, feas, relaxed = select(
                    mu_l, sd_l, ph_l, dvec, acc_goal, egl, gkv, act)
            with jax.named_scope("deliver"):
                (run_t, acc, energy, missed, p, observed, profiled,
                 miss_flag) = deliver_step(i, j, scl, dvec, phi_true,
                                           f_zero=fz, **consts)
            with jax.named_scope("feedback"):
                prof_m = jnp.where(act, profiled, 1.0)
                act_p = jnp.where(act, p, 1.0)
                mu_n, sd_n, g_n, q_n, ph_n, v_n = fused_fleet_step(
                    mu_l, sd_l, g_l, q_l, observed, prof_m, miss_flag,
                    act, *slow_params, ph_l, v_l, phi_true * p, act_p,
                    *idle_params)
                put = lambda s, v: s.at[sidv].set(v, mode="drop")
                mu, sigma = put(mu, mu_n), put(sigma, sd_n)
                gain, qn = put(gain, g_n), put(qn, q_n)
                phv, var = put(phv, ph_n), put(var, v_n)
                if depth:
                    buf_n, pos_n, cnt_n = _goal_record_step(
                        buf[sidv], pos[sidv], count[sidv], acc, act,
                        depth)
                    buf = buf.at[sidv].set(buf_n, mode="drop")
                    pos, count = put(pos, pos_n), put(count, cnt_n)
            sojourn = (now - arrv) + run_t
            ys = (run_t, acc, energy, missed, i, j, sojourn)
            if ring:
                # Per-round telemetry reductions over values the body
                # already computed (feasibility + relaxation come out
                # of the same select call that produced the picks).
                ys = ys + round_aggregates(act, feas, relaxed, energy,
                                           missed)
            return ((mu, sigma, gain, qn, phv, var, buf, pos, count),
                    ys)

        def chunk_alert(carry, goal, f_zero, xs):
            """One super-round dispatch: scan `chunk` rounds with the
            `[S]` state carried (and donated) across dispatches
            (``f_zero``: runtime zero pinning mul+add rounding against
            FMA contraction — see `goal_current_step_hostsum`)."""
            return jax.lax.scan(lambda c, x: body(c, x, goal, f_zero),
                                carry, xs)

        fn = jax.jit(chunk_alert, donate_argnums=0)
        self._chunk_jits[key] = fn
        return fn

    def _init_carry(self, sessions: Sequence[Session]):
        """Fresh ``[S]``-resident state: every session starts at the
        filter priors and its own goal (exactly what the host loop's
        first-touch ``reset_lanes`` installs), so first-round behaviour
        matches the host gateway bit for bit."""
        import jax.numpy as jnp

        s = len(sessions)
        slow = SlowdownFilterBank(s)
        idle = IdlePowerFilterBank(s)
        depth = max(self.accuracy_window - 1, 0)
        goal0 = np.asarray(
            [sess.constraints.accuracy_goal or 0.0 for sess in sessions],
            dtype=np.float64)
        with x64_scope():
            carry = tuple(jnp.asarray(a) for a in (
                slow.mu, slow.sigma, slow.gain, slow.process_noise,
                idle.phi, idle.variance,
                np.zeros((s, max(depth, 1))),
                np.zeros(s, dtype=np.int64),
                np.zeros(s, dtype=np.int64)))
            goal = jnp.asarray(goal0)
        return carry, goal

    # -------------------------------------------------------------- #
    # public API                                                      #
    # -------------------------------------------------------------- #
    def run(self, sessions: Sequence[Session],
            requests: list[TrafficRequest] | None = None, *,
            policy: str = "alert",
            static_config: tuple[int, int] | None = None,
            faults=None) -> GatewayResult:
        """Serve one workload to completion — the
        :meth:`SessionGateway.run` contract, executed as planner +
        chunked device scan.  Raises when the effective tick is below
        the workload's largest relative deadline (the coarse-tick
        regime contract; see the module docstring).

        ``faults`` (a :class:`~repro.traffic.faults.FaultSchedule`)
        replays the host gateway's fault protocol exactly: the planner
        evaluates the schedule at identical round instants and the scan
        carries the lane-death mask, so the result stays
        bitwise-identical to ``SessionGateway.run(..., faults=...)``
        (``tests/test_faults.py`` pins the whole fault matrix)."""
        self._check_run(policy, static_config, faults)
        ob = self._ob
        t0 = time.perf_counter()
        with obs_span(ob, "plan", "megatick"):
            sid_index = {s.sid: k for k, s in enumerate(sessions)}
            plan = self._plan(sessions, requests, sid_index, faults)
        self._plan_timer.observe(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out = plan.out
        if plan.n_active:
            fn = self._chunk_fn(policy, static_config, ring=ob is not None)
            with x64_scope():
                if policy == "alert":
                    carry, goal = self._init_carry(sessions)
                for lo in range(0, plan.act.shape[0], self.chunk):
                    hi = lo + self.chunk
                    xs = (plan.act[lo:hi], plan.sid[lo:hi],
                          plan.gk[lo:hi], plan.rel[lo:hi],
                          plan.arr[lo:hi], plan.e_goal[lo:hi],
                          plan.scale[lo:hi], plan.dead[lo:hi],
                          plan.now[lo:hi])
                    with obs_span(ob, "scan_dispatch", "megatick",
                                  chunk_lo=lo):
                        if policy == "alert":
                            carry, ys = fn(carry, goal, 0.0, xs)
                        else:
                            ys = fn(0.0, xs)
                    with obs_span(ob, "scan_wait", "megatick",
                                  chunk_lo=lo):
                        ys = jax.block_until_ready(ys)
                    with obs_span(ob, "scan_scatter", "megatick",
                                  chunk_lo=lo):
                        self._scatter(plan, lo, hi, ys, out)
                    if ob is not None:
                        # Drop the all-inactive pad rounds of the final
                        # chunk; ring energy is the scan's own sum (may
                        # differ in the last ulp from the host FMA
                        # recompute in `_scatter` —
                        # docs/OBSERVABILITY.md).
                        n_real = min(self.chunk, plan.n_active - lo)
                        if n_real > 0:
                            ob.ring.push_rounds(
                                now_s=plan.now[lo:lo + n_real],
                                n_active=np.asarray(ys[7])[:n_real],
                                n_feasible=np.asarray(ys[8])[:n_real],
                                n_relaxed=np.asarray(ys[9])[:n_real],
                                energy_j=np.asarray(ys[10])[:n_real],
                                n_missed=np.asarray(ys[11])[:n_real])
        # Wall time of the round clock itself (scan dispatch + result
        # scatter), separate from the host planner — what the megatick
        # bench reports as the device-resident rounds/sec.
        self._scan_timer.observe(time.perf_counter() - t0)
        served = out.status == SERVED
        last_completion = float(np.max(out.start[served]
                                       + out.latency[served])) \
            if served.any() else 0.0
        return self._finish(out, last_completion, plan.n_active,
                            self.n_compiles(), policy)

    def _scatter(self, plan: _Plan, lo: int, hi: int, ys,
                 out: GatewayResult) -> None:
        """Copy rounds ``[lo, hi)`` of a chunk's outputs ``ys`` into the
        result rows their lanes served."""
        a = plan.act[lo:hi]
        rows = plan.row[lo:hi][a]
        out.latency[rows] = np.asarray(ys[0])[a]
        out.accuracy[rows] = np.asarray(ys[1])[a]
        out.missed[rows] = np.asarray(ys[3])[a]
        out.model_index[rows] = np.asarray(ys[4])[a]
        out.power_index[rows] = np.asarray(ys[5])[a]
        out.sojourn[rows] = np.asarray(ys[6])[a]
        # Energy is recomputed HERE, in numpy, from bitwise-stable scan
        # outputs: its mul+add chain is the one expression XLA CPU may
        # still contract into an FMA inside the fused scan body, and the
        # host loop's numpy kernel never does.
        rt = out.latency[rows]
        ii, jj = out.model_index[rows], out.power_index[rows]
        pw = self.table.run_power[ii, jj]
        dv = (plan.rel[lo:hi]
              - (plan.now[lo:hi, None] - plan.arr[lo:hi]))[a]
        out.energy[rows] = pw * rt + self.phi_true * pw * \
            np.maximum(dv - rt, 0.0)

    def n_compiles(self) -> tuple[int, int]:
        """(estimate, scan) jit-cache sizes, the
        :meth:`BatchedAlertEngine.n_compiles` convention lifted to the
        megatick: the second entry counts compiled super-round
        executables — 1 means every dispatch of every run (a whole load
        sweep) reused one compiled scan."""
        return (0, sum(f._cache_size()
                       for f in self._chunk_jits.values()))
