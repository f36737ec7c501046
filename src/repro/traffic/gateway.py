"""Discrete-event session gateway: many sessions over few engine lanes.

The tick-synchronous :class:`~repro.serving.sim.FleetSim` gives every
stream a lane and an input every tick.  Production traffic is open-loop:
requests *arrive* (``repro.traffic.workloads``), far more sessions exist
than engine lanes, and the controller must hold its constraints as load
shifts.  :class:`SessionGateway` serves that regime with ONE
:class:`~repro.core.batched.BatchedAlertEngine` sized to ``n_lanes``:

* **Clock** — rounds fire on a fixed tick grid ``t_k = k * tick`` and
  each lane is busy until its request completes (or is abandoned at its
  T_goal, the paper's miss semantics), so ``tick`` may be much finer
  than a deadline: a round scores whatever is due on whatever lanes are
  free.  ``tick`` defaults to the largest nominal deadline, which makes
  every lane free every round — the closed-loop tick sim is exactly
  that special case with one input due per session per round
  (DESIGN.md §7).
* **Admission** — arrivals queue in a
  :class:`~repro.serving.batcher.DeadlineBatcher`: EDF order, fail-fast
  rejection of requests whose remaining slack can no longer fit the
  fastest profiled config, and bounded-queue backpressure at submit.
* **Session paging** — each served session needs its own Kalman/goal
  state, but only ``n_lanes`` lanes exist.  The gateway keeps a resident
  set; a round that needs non-resident sessions evicts the
  least-recently-used residents (one ``export_lanes`` per bank,
  scattered into a host store of columns indexed by session) and
  restores the incomers (one ``import_lanes`` per bank) — same-shape
  ``[S]`` writes only, so paging reuses the churn-no-retrace protocol of
  DESIGN.md §5 and the engine never re-traces.
* **Delivery** — the shared :func:`~repro.serving.sim.deliver_tick`
  kernel, so per-session outcomes at zero queueing delay are
  bitwise-identical to an equivalent :class:`FleetSim` run (paging is
  invisible; ``tests/test_traffic.py`` pins this).
"""

from __future__ import annotations

import dataclasses
import itertools
from collections.abc import Iterator, Mapping
from typing import Sequence

import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.core.batched import (BatchedAlertEngine, WindowedGoalBank,
                                goal_codes)
from repro.core.kalman import (IdlePowerFilterBank, SlowdownFilterBank,
                               observe_fleet)
from repro.core.profiles import ProfileTable
from repro.obs.trace import count as obs_count, span as obs_span
from repro.runtime.ft import InjectedFailure
from repro.serving.batcher import DeadlineBatcher
from repro.serving.sim import TraceResult, deliver_tick
from repro.traffic.workloads import (Session, TrafficRequest,
                                     generate_requests)

# Request disposition codes recorded per offered request.
SERVED = 0
REJECTED_INFEASIBLE = 1     # EDF fail-fast: slack below any feasible run
REJECTED_BACKPRESSURE = 2   # bounded queue was full at arrival


def _lru_pick(resident: np.ndarray, last_used: np.ndarray,
              avail: np.ndarray, needed: np.ndarray,
              n_missing: int) -> tuple[np.ndarray, np.ndarray]:
    """Lanes for ``n_missing`` non-resident sessions: the free ``avail``
    lanes in ascending order, and, where they are too few, the
    least-recently-used ``avail`` residents not in ``needed`` to evict,
    ties broken by lane (a stable argsort of ``last_used`` over
    ascending lanes: the ``(last_used, lane)`` order).  Returns
    ``(free, evict)``; the caller takes them in that order.  The LRU
    policy both gateways page by."""
    free = np.nonzero((resident < 0) & avail)[0]
    n_evict = n_missing - free.size
    if n_evict <= 0:
        return free, free[:0]
    cand = avail & (resident >= 0)
    cand[cand] = ~np.isin(resident[cand], needed)
    cand = np.nonzero(cand)[0]
    return free, cand[np.argsort(last_used[cand], kind="stable")][:n_evict]


# GatewayResult arrays a checkpoint must carry (the loop mutates these;
# sid/index/arrival are rebuilt from the workload at resume).
_CKPT_OUT_FIELDS = ("status", "start", "latency", "sojourn", "missed",
                    "accuracy", "energy", "model_index", "power_index")


@dataclasses.dataclass
class _RunState:
    """Everything one gateway run mutates outside the lane pool and
    banks: the sorted requests, the queue, the result and the round
    clock — the resumable unit a checkpoint captures (DESIGN.md §10)."""

    requests: list
    sess: dict
    tick: float
    queue: DeadlineBatcher
    out: "GatewayResult"
    q_depth: object = None      # queue-depth histogram (a recorder on)
    sidx: np.ndarray | None = None  # dense session index per request row
    ri: int = 0                 # next unsubmitted request index
    round_k: int = 0            # round clock
    n_rounds: int = 0           # rounds that served a batch
    last_completion: float = 0.0
    iters: int = 0              # loop iterations (checkpoint cadence)


@dataclasses.dataclass
class GatewayResult:
    """Per-request dispositions and outcomes of one gateway run.

    All arrays are indexed by offered-request row — requests sorted by
    ``(arrival, req_id)``, which for :func:`~repro.traffic.workloads.
    generate_requests` workloads coincides with ``req_id`` order.
    ``status`` holds the disposition codes (:data:`SERVED` /
    :data:`REJECTED_INFEASIBLE` / :data:`REJECTED_BACKPRESSURE`);
    outcome fields are zero for unserved requests.  ``sojourn`` is
    queueing delay + run time — the latency a client observes.
    """

    sid: np.ndarray
    index: np.ndarray
    arrival: np.ndarray
    status: np.ndarray
    start: np.ndarray
    latency: np.ndarray
    sojourn: np.ndarray
    missed: np.ndarray
    accuracy: np.ndarray
    energy: np.ndarray
    model_index: np.ndarray
    power_index: np.ndarray
    horizon: float = 0.0
    n_rounds: int = 0
    pages_in: int = 0
    pages_out: int = 0
    n_compiles: tuple = (0, 0)

    @property
    def offered(self) -> int:
        """Number of requests the workload offered."""
        return int(self.status.shape[0])

    @property
    def served(self) -> np.ndarray:
        """Bool mask of requests that reached a lane."""
        return self.status == SERVED

    @property
    def good(self) -> np.ndarray:
        """Served AND met the absolute deadline (goodput numerator)."""
        return self.served & ~self.missed

    @property
    def goodput(self) -> float:
        """Deadline-met completions per second of gateway time."""
        return float(self.good.sum() / max(self.horizon, 1e-12))

    @property
    def served_miss_rate(self) -> float:
        """Miss fraction among *served* requests (what admission control
        is supposed to bound: hopeless requests are shed, not started)."""
        n = int(self.served.sum())
        return float(self.missed[self.served].sum() / n) if n else 0.0

    @property
    def reject_rate(self) -> float:
        """Fraction of offered requests shed (fail-fast + backpressure)."""
        return float((self.status != SERVED).mean()) if self.offered \
            else 0.0

    @property
    def slo_miss_rate(self) -> float:
        """Fraction of offered requests that did NOT complete in
        deadline (served-but-missed plus every rejection)."""
        return float(1.0 - self.good.sum() / self.offered) \
            if self.offered else 0.0

    def percentile_sojourn(self, q: float) -> float:
        """Sojourn-time percentile (seconds) over served requests."""
        s = self.sojourn[self.served]
        return float(np.percentile(s, q)) if s.size else 0.0

    @property
    def mean_energy_served(self) -> float:
        """Mean energy (J) per served request."""
        n = int(self.served.sum())
        return float(self.energy[self.served].mean()) if n else 0.0

    @property
    def energy_per_good(self) -> float:
        """Total served energy divided by deadline-met completions —
        the efficiency axis of the load sweep."""
        n = int(self.good.sum())
        return float(self.energy[self.served].sum() / n) if n else \
            float("inf")

    def stream(self, sid: int) -> TraceResult:
        """Session ``sid``'s served outcomes in input-index order, as a
        :class:`~repro.serving.sim.TraceResult` — comparable (bitwise, at
        zero queueing delay) with a FleetSim stream."""
        sel = np.nonzero((self.sid == sid) & self.served)[0]
        sel = sel[np.argsort(self.index[sel], kind="stable")]
        return TraceResult(self.energy[sel], self.accuracy[sel],
                           self.latency[sel], self.missed[sel],
                           scheme="gateway")


class _GatewayCore:
    """The host half both gateways share: a run's checks, request
    intake, round clock, admission and device-loss quarantine, over the
    lane pool's metadata (``_resident``, ``_last_used``, ``_dead`` per
    lane; ``_lane_of``, ``_stored`` per dense session index).
    :class:`SessionGateway` runs it round by round,
    :class:`~repro.traffic.megatick.MegatickGateway` up front as its
    planner."""

    _label = "host"     # the `gateway=` label of the telemetry

    def __init__(self, table: ProfileTable, n_lanes: int, *,
                 phi_true: float, overhead: float, tick: float | None,
                 max_queue: int | None,
                 min_feasible_latency: float | None,
                 accuracy_window: int, backend: str, mesh, obs):
        self.table = table
        # Optional flight recorder (repro.obs.FlightRecorder).  Strictly
        # a pure observer: every pick, bank state, and golden trace is
        # bitwise identical with or without it (tests/test_obs.py).  A
        # disabled one leaves `_ob` None like no recorder, so every
        # instrumentation site is one pointer check (the ~zero-cost
        # disabled mode of docs/OBSERVABILITY.md).
        self.obs = obs
        self._ob = obs if getattr(obs, "enabled", False) else None
        self.n_lanes = int(n_lanes)
        self.phi_true = float(phi_true)
        self.tick = tick
        self.max_queue = max_queue
        self.min_feasible_latency = float(table.latency.min()) \
            if min_feasible_latency is None else float(min_feasible_latency)
        self.accuracy_window = int(accuracy_window)
        self.mesh = mesh
        self.engine = BatchedAlertEngine(table, None, overhead=overhead,
                                         backend=backend, mesh=mesh)
        self._st = table.staircase_tensors()
        groups = table.anytime_groups()
        self._is_anytime = np.zeros(len(table.candidates), bool)
        self._is_anytime[sorted({i for g in groups.values()
                                 for i in g})] = True
        self._stored = np.zeros(0, dtype=bool)
        self._lane_of = np.zeros(0, dtype=np.int64)
        self._reset_pool(0)

    # -------------------------------------------------------------- #
    # lane pool                                                       #
    # -------------------------------------------------------------- #
    def _reset_pool(self, n_sessions: int) -> None:
        """An empty lane pool over ``n_sessions`` dense session indices:
        no residents, no session stored or on a lane, no lane dead,
        paging counters at zero."""
        if self._stored.shape[0] != n_sessions:
            self._stored = np.zeros(n_sessions, dtype=bool)
            self._lane_of = np.zeros(n_sessions, dtype=np.int64)
        self._resident = np.full(self.n_lanes, -1, dtype=np.int64)
        self._stored[:] = False
        self._lane_of[:] = -1
        self._last_used = np.zeros(self.n_lanes, dtype=np.int64)
        self._dead = np.zeros(self.n_lanes, dtype=bool)
        self.pages_in = self.pages_out = 0

    def _dense(self, keys) -> np.ndarray:
        """Dense session indices of the session keys ``_resident``
        holds: the keys themselves, unless a subclass keys lanes by sid."""
        return keys

    def _export(self, lanes: np.ndarray, dense: np.ndarray) -> None:
        """Move the state of the sessions on ``lanes`` (dense indices
        ``dense``) to the session store: nothing to move unless a
        subclass keeps per-lane state."""

    def _evict_lanes(self, ev_lanes) -> None:
        """Page the residents of ``ev_lanes`` out to the session store
        (:meth:`_export`) and free the lanes.  Shared by LRU eviction and
        device-loss quarantine — a dead lane's session state survives
        the device and can be re-admitted on a surviving lane
        (DESIGN.md §10)."""
        ev = np.asarray(ev_lanes, dtype=np.int64)
        if not ev.size:
            return
        olds = self._dense(self._resident[ev])
        self._export(ev, olds)
        self._stored[olds] = True
        self._lane_of[olds] = -1
        self._resident[ev] = -1
        self.pages_out += int(ev.size)

    def _place(self, keys: np.ndarray, dense: np.ndarray,
               idle: np.ndarray, round_k: int):
        """Put the distinct sessions ``keys`` (dense indices ``dense``)
        on lanes, aligned with them: residents keep theirs, the others
        take the free ``idle`` lanes in ascending order, then those of
        the least-recently-used ``idle`` residents not among ``keys``,
        evicted (:func:`_lru_pick`).  Returns the lanes, the positions
        that were off-lane, and which of those come back from the store
        (counted in ``pages_in``)."""
        lanes = self._lane_of[dense]
        miss = np.nonzero(lanes < 0)[0]
        paged = np.zeros(0, dtype=bool)
        if miss.size:
            free, ev = _lru_pick(self._resident, self._last_used, idle,
                                 keys, miss.size)
            if ev.size:
                self._evict_lanes(ev)
                free = np.concatenate([free, ev])
            if free.size < miss.size:
                # Eviction could not produce enough idle lanes (every
                # other resident is busy or needed this round).  A
                # silent truncation here would leave a lane of -1 and
                # corrupt the last lane downstream, so fail loudly.
                raise RuntimeError(
                    f"page-in underflow: {miss.size} non-resident "
                    f"session(s) need lanes but only {free.size} "
                    "lane(s) are free or evictable (the rest are busy"
                    " or needed this round)")
            take = free[:miss.size]
            inc = dense[miss]
            lanes[miss] = take
            self._resident[take] = keys[miss]
            self._lane_of[inc] = take
            paged = self._stored[inc]
            self._stored[inc] = False
            self.pages_in += int(paged.sum())
        self._last_used[lanes] = round_k
        return lanes, miss, paged

    def _quarantine(self, now: float, faults) -> None:
        """The fault schedule's device loss at round instant ``now``:
        newly dead lanes page their residents out (their Kalman/goal
        state survives the device) and leave the pool until restored —
        the §5 churn protocol, no re-traces."""
        dead_now = faults.dead_at(now)
        newly_dead = dead_now & ~self._dead
        if newly_dead.any():
            self._evict_lanes(
                np.nonzero(newly_dead & (self._resident >= 0))[0])
            ob = self._ob
            if ob:
                lanes = [int(x) for x in np.nonzero(newly_dead)[0]]
                ob.metrics.counter("quarantine_events",
                                   gateway=self._label).inc()
                ob.metrics.counter("lanes_quarantined",
                                   gateway=self._label).inc(len(lanes))
                ob.spans.event("quarantine", cat="fault", lanes=lanes,
                               now_s=float(now))
        self._dead = dead_now

    # -------------------------------------------------------------- #
    # intake and clock                                                #
    # -------------------------------------------------------------- #
    def _check_run(self, policy: str, static_config, faults) -> None:
        """Refuse a run's arguments before any state is touched."""
        if policy not in ("alert", "static"):
            raise ValueError(policy)
        if policy == "static" and static_config is None:
            raise ValueError("policy='static' needs static_config=(i, j)")
        if faults is not None and faults.n_lanes != self.n_lanes:
            raise ValueError(
                f"FaultSchedule covers {faults.n_lanes} lanes but the "
                f"gateway has {self.n_lanes}")

    def _offer(self, sessions: Sequence[Session],
               requests: list[TrafficRequest] | None) -> _RunState:
        """One run's intake: the requests in arrival order, each paired
        with its result row, the result shell (every request shed until
        decided), the tick and an empty queue."""
        sess = {s.sid: s for s in sessions}
        if requests is None:
            requests = generate_requests(sessions)
        # The event loop needs arrival order; caller-supplied lists may
        # be merged/unsorted, so sort defensively (stable — equal keys
        # keep their input order) and index results by sorted row.
        requests = sorted(
            requests,
            key=lambda r: (r.arrival,
                           0 if r.req_id is None else r.req_id))
        # Pair every request with its sorted result row directly
        # (enumerate after the sort).  Keying rows on object identity
        # would collapse two occurrences of the same object into one
        # row, so true duplicates are rejected up front instead.
        if len({id(r) for r in requests}) != len(requests):
            raise ValueError(
                "the same TrafficRequest object was offered more than "
                "once; every offered request must be a distinct object")
        for k, r in enumerate(requests):
            r._row = k
        n = len(requests)
        out = GatewayResult(
            sid=np.asarray([r.sid for r in requests], dtype=np.int64),
            index=np.asarray([r.index for r in requests], dtype=np.int64),
            arrival=np.asarray([r.arrival for r in requests]),
            status=np.full(n, REJECTED_BACKPRESSURE, dtype=np.int64),
            start=np.zeros(n), latency=np.zeros(n), sojourn=np.zeros(n),
            missed=np.zeros(n, bool), accuracy=np.zeros(n),
            energy=np.zeros(n), model_index=np.zeros(n, dtype=np.int64),
            power_index=np.zeros(n, dtype=np.int64))
        tick = self.tick if self.tick is not None else \
            (max(r.rel_deadline for r in requests) if n else 1.0)
        ob = self._ob
        queue = DeadlineBatcher(batch_size=self.n_lanes,
                                min_feasible_latency=
                                self.min_feasible_latency,
                                max_queue=self.max_queue,
                                metrics=ob.metrics if ob else None)
        q_depth = ob.metrics.histogram("queue_depth",
                                       gateway=self._label) if ob else None
        return _RunState(requests=requests, sess=sess, tick=float(tick),
                         queue=queue, out=out, q_depth=q_depth)

    @staticmethod
    def _round_of(arrival: float, tick: float) -> int:
        """Smallest round k with ``k * tick >= arrival`` (float-safe:
        a request arriving exactly on a round boundary is served in that
        round, which is what makes zero queueing delay *exactly* zero)."""
        k = max(int(np.ceil(arrival / tick)), 0)
        while k * tick < arrival:
            k += 1
        while k > 0 and (k - 1) * tick >= arrival:
            k -= 1
        return k

    def _next_round(self, rs: _RunState) -> float:
        """The instant of round ``rs.round_k``, skipped ahead to the next
        arrival's round when the queue is empty."""
        if not len(rs.queue):
            rs.round_k = max(rs.round_k, self._round_of(
                rs.requests[rs.ri].arrival, rs.tick))
        return rs.round_k * rs.tick

    # -------------------------------------------------------------- #
    # admission                                                       #
    # -------------------------------------------------------------- #
    def _admit(self, rs: _RunState, now: float,
               busy_until: np.ndarray | None = None):
        """One round's admission: submit the arrivals due by ``now``
        (backpressure at submit), then pop the EDF queue onto the live
        lanes, at most one request per session, failing fast what can no
        longer meet its deadline.  ``busy_until`` (per-lane completion
        instants) also keeps busy lanes out of the round and defers a
        session mid-service on one; ``None`` says no lane is busy (the
        megatick's regime contract), decided once for the round.
        Returns the round's batch and the requests popped and put back
        (deferred)."""
        requests, queue, out = rs.requests, rs.queue, rs.out
        n = len(requests)
        ri = rs.ri
        while ri < n and requests[ri].arrival <= now:
            req = requests[ri]
            if not queue.submit(req):
                out.status[req._row] = REJECTED_BACKPRESSURE
            ri += 1
        rs.ri = ri
        if rs.q_depth is not None:
            rs.q_depth.observe(len(queue))
        # A session is sequential: whether queued behind itself or
        # mid-service on a busy lane, its later requests wait.  The scan
        # is bounded: a run of blocked same-session requests longer than
        # the deferral budget waits for the next round instead of
        # churning the whole backlog through the heap every round.
        n_rej = len(queue.rejected)
        idle = ~self._dead if busy_until is None else \
            (busy_until <= now) & ~self._dead
        avail = int(idle.sum())
        batch: list[TrafficRequest] = []
        seen: set[int] = set()
        deferred: list[TrafficRequest] = []
        defer_budget = 4 * self.n_lanes
        while len(batch) < avail and len(deferred) <= defer_budget:
            req = queue.pop_one(now)
            if req is None:
                break
            if req.sid in seen:
                deferred.append(req)
                continue
            if busy_until is not None:
                lane = self._lane_of[rs.sidx[req._row]]
                if lane >= 0 and busy_until[lane] > now:
                    deferred.append(req)
                    continue
            seen.add(req.sid)
            batch.append(req)
        for req in deferred:
            # Deferral is not a new arrival: requeue() bypasses
            # max_queue backpressure (the request was already admitted)
            # and restores the original heap seq so the EDF
            # submission-order tie-break survives deferral.
            queue.requeue(req)
        for req in queue.rejected[n_rej:]:   # failed fast this round
            out.status[req._row] = REJECTED_INFEASIBLE
            out.start[req._row] = now
        return batch, deferred

    def _finish(self, out: GatewayResult, last_completion: float,
                n_rounds: int, n_compiles: tuple,
                policy: str) -> GatewayResult:
        """Close a run's result (horizon, round, paging and compile
        counts) and fold it into an attached recorder's registry:
        disposition counters, paging totals and the headline
        SLO/efficiency gauges, one metric catalog for both gateways."""
        out.horizon = max(last_completion,
                          float(out.arrival[-1]) if out.offered else 0.0)
        out.n_rounds = n_rounds
        out.pages_in, out.pages_out = self.pages_in, self.pages_out
        out.n_compiles = n_compiles
        if not self._ob:
            return out
        metrics = self._ob.metrics
        lab = dict(gateway=self._label, policy=policy)
        metrics.counter("requests_offered", **lab).inc(out.offered)
        metrics.counter("requests_served", **lab).inc(int(out.served.sum()))
        metrics.counter("requests_rejected_infeasible", **lab).inc(
            int((out.status == REJECTED_INFEASIBLE).sum()))
        metrics.counter("requests_rejected_backpressure", **lab).inc(
            int((out.status == REJECTED_BACKPRESSURE).sum()))
        metrics.counter("requests_good", **lab).inc(int(out.good.sum()))
        metrics.counter("deadline_misses", **lab).inc(
            int(out.missed[out.served].sum()))
        metrics.counter("energy_served_j", **lab).inc(
            float(out.energy[out.served].sum()))
        metrics.counter("rounds_served", **lab).inc(out.n_rounds)
        metrics.counter("pages_in", **lab).inc(out.pages_in)
        metrics.counter("pages_out", **lab).inc(out.pages_out)
        metrics.gauge("slo_miss_rate", **lab).set(out.slo_miss_rate)
        metrics.gauge("served_miss_rate", **lab).set(out.served_miss_rate)
        metrics.gauge("reject_rate", **lab).set(out.reject_rate)
        metrics.gauge("goodput_rps", **lab).set(out.goodput)
        eg = out.energy_per_good
        metrics.gauge("energy_per_good_j", **lab).set(
            eg if np.isfinite(eg) else 0.0)
        metrics.gauge("n_compiles_estimate", gateway=self._label).set(
            out.n_compiles[0])
        metrics.gauge("n_compiles_select", gateway=self._label).set(
            out.n_compiles[1])
        return out


class SessionGateway(_GatewayCore):
    """Open-loop traffic over one fixed-size batched scoring engine.

    The engine, filter banks, goal bank, and lane pool are built once at
    ``n_lanes`` and reused across :meth:`run` calls (a load sweep pays
    one trace for its whole grid); every run resets the lane pool and
    session store.  ``policy="alert"`` drives the full controller;
    ``policy="static"`` executes one fixed ``(model, power)`` config
    through the identical clock/queue/delivery path (the hindsight
    ``oracle_static`` baseline of ``repro.traffic.loadsweep``).
    ``backend`` forwards to the engine (``"pallas"`` scores rounds with
    the fused ``alert_select`` kernel — margin-contract picks, same
    no-retrace paging contract; docs/KERNELS.md).
    """

    def __init__(self, table: ProfileTable, n_lanes: int, *,
                 phi_true: float = 0.25, overhead: float = 0.0,
                 tick: float | None = None,
                 max_queue: int | None = None,
                 min_feasible_latency: float | None = None,
                 accuracy_window: int = 10, backend: str = "xla",
                 mesh=None, obs=None):
        super().__init__(table, n_lanes, phi_true=phi_true,
                         overhead=overhead, tick=tick, max_queue=max_queue,
                         min_feasible_latency=min_feasible_latency,
                         accuracy_window=accuracy_window, backend=backend,
                         mesh=mesh, obs=obs)
        self.slow = SlowdownFilterBank(self.n_lanes, mesh=mesh)
        self.idle = IdlePowerFilterBank(self.n_lanes, mesh=mesh)
        # The goal window stays host-resident even under a mesh (bitwise
        # window sums, mirroring FleetSim.run_streams).
        self.goal_bank = WindowedGoalBank(
            np.zeros(self.n_lanes), self.n_lanes, accuracy_window)
        # The session store: columns over a dense session index, built
        # per run by _index_sessions (empty until the first run).
        self._banks = {"slow": self.slow, "idle": self.idle,
                       "goal": self.goal_bank}
        self._sess = None
        self._sids = np.zeros(0, dtype=np.int64)
        self._cols: dict[str, dict[str, np.ndarray]] = {}
        self._reset_lane_pool()

    # -------------------------------------------------------------- #
    # session paging                                                  #
    # -------------------------------------------------------------- #
    def _reset_lane_pool(self) -> None:
        """Fresh lane pool + empty session store (between runs).  The
        ``[S]`` shapes are untouched, so the engine's jit cache
        survives."""
        self._reset_pool(self._stored.shape[0])
        self._goal_kinds = np.zeros(self.n_lanes, dtype=np.int64)
        self._busy_until = np.zeros(self.n_lanes)
        all_lanes = np.arange(self.n_lanes)
        self.slow.reset_lanes(all_lanes)
        self.idle.reset_lanes(all_lanes)
        self.goal_bank.reset_lanes(all_lanes, goal=np.zeros(self.n_lanes))

    def _index_sessions(self, sess: dict[int, Session]) -> None:
        """Index one run's sessions densely, in ascending sid order, over
        an empty lane pool: the session store is a set of columns over
        that index (one per bank state field, ``[n, W-1]`` for the goal
        ring), with its ``stored`` mask and the ``lane_of`` map beside
        them.  Each session's goal code and accuracy goal are read here
        once, so a round never touches a :class:`Session`.  The columns
        are kept for the next run of the same session count."""
        if (self._resident >= 0).any() or self._stored.any():
            raise RuntimeError("sessions are indexed only over an empty "
                               "lane pool and session store")
        sids = np.fromiter(sess, dtype=np.int64, count=len(sess))
        order = np.argsort(sids, kind="stable")
        self._sids = sids[order]
        goals = [s.goal for s in sess.values()]
        code_of = {g: int(goal_codes([g])[0]) for g in set(goals)}
        self._sess_goal_kind = np.asarray(
            [code_of[g] for g in goals], dtype=np.int64)[order]
        self._sess_accuracy_goal = np.asarray(
            [s.constraints.accuracy_goal or 0.0 for s in sess.values()],
            dtype=np.float64)[order]
        n = len(sids)
        if self._stored.shape[0] != n:
            none = np.zeros(0, dtype=np.int64)
            self._cols = {
                part: {name: np.zeros((n,) + v.shape[1:], v.dtype)
                       for name, v in bank.export_lanes(none).items()}
                for part, bank in self._banks.items()}
            self._reset_pool(n)
        self._sess = sess

    def _dense(self, sids) -> np.ndarray:
        """Dense session indices of raw ``sids`` (all indexed)."""
        return np.searchsorted(self._sids, sids)

    def _export(self, lanes: np.ndarray, dense: np.ndarray) -> None:
        """One batched ``export_lanes`` per bank, scattered into the
        session store's columns at ``dense``."""
        for part, bank in self._banks.items():
            cols = self._cols[part]
            for name, v in bank.export_lanes(lanes).items():
                cols[name][dense] = v

    def _page_in(self, sids: Sequence[int],
                 sessions: dict[int, Session], round_k: int,
                 now: float) -> np.ndarray:
        """Make every session in ``sids`` (distinct) lane-resident;
        returns their lanes aligned with ``sids``.

        Non-residents land in free idle lanes first, then evict the
        least-recently-used *idle* residents not needed this round (a
        busy lane's session is mid-service and cannot move;
        :meth:`_place`).  The evictees' filter + goal-window state goes
        to the session store (one ``export_lanes`` per bank) and the
        incomers' state is restored (one ``import_lanes`` per bank for
        paged sessions, one ``reset_lanes`` for first-time sessions) —
        same-shape writes only, so paging can never re-trace the engine
        (DESIGN.md §7).  ``sessions`` is the run's mapping; a direct
        call with another one indexes it first.
        """
        if sessions is not self._sess:
            self._index_sessions(sessions)
        with obs_span(self._ob, "page_in", "paging",
                      round_k=round_k) as sp:
            sids = np.asarray(sids, dtype=np.int64)
            idx = self._dense(sids)
            n_out = self.pages_out
            lanes, miss, paged = self._place(
                sids, idx, (self._busy_until <= now) & ~self._dead,
                round_k)
            take, inc = lanes[miss], idx[miss]
            self._goal_kinds[take] = self._sess_goal_kind[inc]
            n_in = int(paged.sum())
            if n_in:
                p_lanes, p_idx = take[paged], inc[paged]
                for part, bank in self._banks.items():
                    bank.import_lanes(p_lanes, {
                        name: col[p_idx]
                        for name, col in self._cols[part].items()})
            if n_in < miss.size:
                f_lanes, f_idx = take[~paged], inc[~paged]
                self.slow.reset_lanes(f_lanes)
                self.idle.reset_lanes(f_lanes)
                self.goal_bank.reset_lanes(
                    f_lanes, goal=self._sess_accuracy_goal[f_idx])
            sp.set(n_in=n_in, n_fresh=int(miss.size) - n_in,
                   n_out=self.pages_out - n_out)
            if np.any(lanes < 0):
                raise RuntimeError(
                    "page-in invariant violated: a requested session has "
                    "no lane after paging (lanes={})".format(
                        lanes.tolist()))
        return lanes

    @property
    def _store(self) -> "_SessionStoreView":
        """The paged-out sessions as a read-only mapping: sid ->
        ``{part: {name: [1]-array}}`` (``[1, W-1]`` for the goal ring),
        read from the columns."""
        return _SessionStoreView(self)

    # -------------------------------------------------------------- #
    # the event loop                                                  #
    # -------------------------------------------------------------- #
    def _init_run(self, sessions: Sequence[Session],
                  requests: list[TrafficRequest] | None, *,
                  policy: str, static_config, faults) -> "_RunState":
        """Validate one run's inputs and build its fresh, resumable
        loop state (requests sorted + row-assigned, result shell, round
        clock, empty queue, reset lane pool)."""
        self._check_run(policy, static_config, faults)
        rs = self._offer(sessions, requests)
        self._reset_lane_pool()
        self._index_sessions(rs.sess)
        sids = rs.out.sid
        rs.sidx = sidx = self._dense(sids)
        known = sidx < len(self._sids)
        known[known] = self._sids[sidx[known]] == sids[known]
        if not known.all():
            raise ValueError("a request names a session that is not "
                             "among the offered sessions")
        return rs

    def run(self, sessions: Sequence[Session],
            requests: list[TrafficRequest] | None = None, *,
            policy: str = "alert",
            static_config: tuple[int, int] | None = None,
            faults=None, detector=None,
            checkpoint_dir: str | None = None,
            checkpoint_every: int = 8,
            kill_at_round: int | None = None) -> GatewayResult:
        """Serve one workload to completion; returns per-request
        dispositions and outcomes.

        ``requests`` defaults to ``generate_requests(sessions)``.
        ``policy="static"`` runs the fixed ``static_config`` (model,
        power) through the same clock/queue/delivery path with no
        controller state (used for the hindsight-static baseline).

        Fault subsystem hooks (DESIGN.md §10):

        * ``faults`` — a :class:`~repro.traffic.faults.FaultSchedule`
          evaluated at every round instant: its slow-down multiplier
          composes onto the environment's true scale, and its
          lane-death mask quarantines lanes (residents paged out to the
          host store, capacity shrinks, survivors keep their state —
          the §5 churn protocol, no re-traces).
        * ``detector`` — a
          :class:`~repro.traffic.faults.KalmanLaneDetector` observing
          the slow-down bank's (mu, sigma) each served round (pure
          observer; never perturbs selection).
        * ``checkpoint_dir`` — atomically snapshot the full gateway +
          bank + queue state every ``checkpoint_every`` loop iterations
          (:mod:`repro.checkpoint.io`); :meth:`resume` continues a
          killed run bit-exactly.
        * ``kill_at_round`` — raise
          :class:`~repro.runtime.ft.InjectedFailure` at that loop
          iteration (before it executes), for kill/resume tests.
        """
        rs = self._init_run(sessions, requests, policy=policy,
                            static_config=static_config, faults=faults)
        if rs.out.offered == 0:
            return rs.out
        return self._drive(rs, policy, static_config, faults, detector,
                           checkpoint_dir, checkpoint_every,
                           kill_at_round)

    def resume(self, sessions: Sequence[Session],
               requests: list[TrafficRequest] | None = None, *,
               checkpoint_dir: str,
               policy: str = "alert",
               static_config: tuple[int, int] | None = None,
               faults=None, detector=None,
               checkpoint_every: int = 8,
               kill_at_round: int | None = None) -> GatewayResult:
        """Resume a killed :meth:`run` from its latest checkpoint and
        drive it to completion — bit-exactly: the resumed trajectory is
        indistinguishable from the uninterrupted one.

        The caller must offer the SAME workload (sessions/requests are
        regenerated deterministically from their seeds; the checkpoint
        stores loop state, not the workload).  A gateway built over a
        different lane mesh may resume the same checkpoint — bank state
        is resharded onto the new mesh via
        :func:`repro.runtime.elastic.reshard_state` (elastic restore).
        """
        rs = self._init_run(sessions, requests, policy=policy,
                            static_config=static_config, faults=faults)
        with obs_span(self._ob, "checkpoint_restore", "checkpoint"):
            self._load_checkpoint(rs, checkpoint_dir)
        return self._drive(rs, policy, static_config, faults, detector,
                           checkpoint_dir, checkpoint_every,
                           kill_at_round)

    def _drive(self, rs: "_RunState", policy: str, static_config,
               faults, detector, checkpoint_dir: str | None,
               checkpoint_every: int,
               kill_at_round: int | None) -> GatewayResult:
        """The round loop, resumable at any iteration boundary: every
        mutation lives in ``rs`` / the lane pool / the banks, all of
        which the checkpoint captures."""
        sess, out = rs.sess, rs.out
        n = len(rs.requests)
        lanes_arange = np.arange(self.n_lanes)
        ob = self._ob
        while rs.ri < n or len(rs.queue):
            if kill_at_round is not None and rs.iters == kill_at_round:
                raise InjectedFailure(
                    f"injected kill at gateway iteration {rs.iters}")
            now = self._next_round(rs)
            # --- fault schedule at the round instant: pure numpy f64,
            # read at the same instants by the megatick planner so both
            # paths see bit-identical perturbations ---
            fmul = None
            if faults is not None:
                self._quarantine(now, faults)
                fmul = faults.slow_at(now)
            with obs_span(ob, "admit", "gateway", round_k=rs.round_k):
                batch, deferred = self._admit(rs, now, self._busy_until)
            obs_count(ob, "pops", len(batch) + len(deferred),
                      gateway="host")
            obs_count(ob, "deferred", len(deferred), gateway="host")
            if batch:
                obs_count(ob, "rounds", gateway="host")
                with obs_span(ob, "serve_round", "gateway",
                              round_k=rs.round_k, batch=len(batch)):
                    rs.last_completion = max(
                        rs.last_completion, self._serve_round(
                            batch, sess, now, rs.round_k, policy,
                            static_config, lanes_arange, out, fmul,
                            detector))
                rs.n_rounds += 1
            rs.round_k += 1
            rs.iters += 1
            if checkpoint_dir is not None and \
                    rs.iters % max(checkpoint_every, 1) == 0:
                with obs_span(ob, "checkpoint_write", "checkpoint",
                              iters=rs.iters):
                    self._save_checkpoint(rs, checkpoint_dir)
        return self._finish(out, rs.last_completion, rs.n_rounds,
                            self.engine.n_compiles(), policy)

    # -------------------------------------------------------------- #
    # checkpoint / resume                                             #
    # -------------------------------------------------------------- #
    def _save_checkpoint(self, rs: "_RunState", directory: str) -> None:
        """Atomic snapshot of everything :meth:`_drive` mutates: loop
        scalars, the EDF heap (internal list order + seq counter —
        restored pops are bitwise), the lane pool, full-bank filter/goal
        state, the paged-session store, and the partial result arrays.
        Written via :func:`repro.checkpoint.io.save` (torn-write safe)."""
        q = rs.queue
        # Peek the seq counter without perturbing it: consume one value
        # and replace the counter with a fresh count from that value.
        n0 = next(q._counter)
        q._counter = itertools.count(n0)
        all_lanes = np.arange(self.n_lanes)
        held = np.nonzero(self._stored)[0]
        store: dict = {"sids": self._sids[held]}
        if held.size:
            for part, cols in self._cols.items():
                for name, col in cols.items():
                    store[f"{part}.{name}"] = col[held]
        tree = {
            "meta": {
                "ri": np.int64(rs.ri),
                "round_k": np.int64(rs.round_k),
                "n_rounds": np.int64(rs.n_rounds),
                "iters": np.int64(rs.iters),
                "last_completion": np.float64(rs.last_completion),
                "pages_in": np.int64(self.pages_in),
                "pages_out": np.int64(self.pages_out),
                "next_seq": np.int64(n0),
                "tick": np.float64(rs.tick),
                "n_requests": np.int64(len(rs.requests)),
            },
            "queue": {
                "seq": np.asarray([s for _, s, _ in q._heap],
                                  dtype=np.int64),
                "row": np.asarray([r._row for _, _, r in q._heap],
                                  dtype=np.int64),
            },
            "lanes": {
                "resident": self._resident.copy(),
                "goal_kinds": self._goal_kinds.copy(),
                "last_used": self._last_used.copy(),
                "busy_until": self._busy_until.copy(),
                "dead": self._dead.copy(),
            },
            "slow": {k: np.asarray(v) for k, v in
                     self.slow.export_lanes(all_lanes).items()},
            "idle": {k: np.asarray(v) for k, v in
                     self.idle.export_lanes(all_lanes).items()},
            "goal": {k: np.asarray(v) for k, v in
                     self.goal_bank.export_lanes(all_lanes).items()},
            "store": store,
            "out": {f: getattr(rs.out, f).copy() for f in
                    _CKPT_OUT_FIELDS},
        }
        ckpt_io.save(directory, tree, step=rs.iters)

    def _load_checkpoint(self, rs: "_RunState", directory: str) -> None:
        """Overwrite the fresh ``rs`` + lane pool + banks with the
        snapshot under ``directory``.  When the gateway carries a lane
        mesh the restored bank state is resharded onto it first
        (:func:`repro.runtime.elastic.reshard_state`) — the
        mesh-shape-change restore path."""
        tree, _step = ckpt_io.restore_tree(directory)
        meta = tree["meta"]
        if int(meta["n_requests"]) != len(rs.requests):
            raise ValueError(
                f"checkpoint was taken over {int(meta['n_requests'])} "
                f"requests but this run offers {len(rs.requests)}: "
                "resume needs the identical workload")
        if float(meta["tick"]) != rs.tick:
            raise ValueError(
                f"checkpoint tick {float(meta['tick'])} != run tick "
                f"{rs.tick}: resume needs the identical round clock")
        rs.ri = int(meta["ri"])
        rs.round_k = int(meta["round_k"])
        rs.n_rounds = int(meta["n_rounds"])
        rs.iters = int(meta["iters"])
        rs.last_completion = float(meta["last_completion"])
        self.pages_in = int(meta["pages_in"])
        self.pages_out = int(meta["pages_out"])
        q = rs.queue
        q._counter = itertools.count(int(meta["next_seq"]))
        heap = []
        for s, rw in zip(tree["queue"]["seq"].tolist(),
                         tree["queue"]["row"].tolist()):
            req = rs.requests[int(rw)]
            req._seq = int(s)
            heap.append((req.deadline, int(s), req))
        # Saved in internal list order, so the heap invariant is
        # preserved verbatim — restored pops are bitwise-identical.
        q._heap = heap
        ln = tree["lanes"]
        self._resident = ln["resident"].astype(np.int64)
        self._goal_kinds = ln["goal_kinds"].astype(np.int64)
        self._last_used = ln["last_used"].astype(np.int64)
        self._busy_until = ln["busy_until"].astype(np.float64)
        self._dead = ln["dead"].astype(bool)
        on = np.nonzero(self._resident >= 0)[0]
        self._lane_of[self._dense(self._resident[on])] = on
        all_lanes = np.arange(self.n_lanes)
        slow_state, idle_state = tree["slow"], tree["idle"]
        if self.mesh is not None:
            from repro.launch.mesh import lane_pspec
            from repro.runtime.elastic import reshard_state

            spec = lane_pspec(self.mesh)
            slow_state = reshard_state(slow_state, self.mesh,
                                       lambda p, leaf: spec)
            idle_state = reshard_state(idle_state, self.mesh,
                                       lambda p, leaf: spec)
        self.slow.import_lanes(all_lanes, slow_state)
        self.idle.import_lanes(all_lanes, idle_state)
        self.goal_bank.import_lanes(all_lanes, tree["goal"])
        held = self._dense(tree["store"]["sids"])
        self._stored[held] = True
        for key, arr in tree["store"].items():
            if key != "sids":
                part, name = key.split(".", 1)
                self._cols[part][name][held] = arr
        for f in _CKPT_OUT_FIELDS:
            getattr(rs.out, f)[:] = tree["out"][f]

    def _serve_round(self, batch, sess, now: float, round_k: int,
                     policy: str, static_config, lanes_arange,
                     out: GatewayResult, fmul=None,
                     detector=None) -> float:
        """One synchronous round: page the batch's sessions in, score all
        lanes with one masked engine call (or the fixed static config),
        deliver through the shared tick kernel, absorb feedback.  Returns
        the round's last completion time."""
        ob = self._ob
        lanes = self._page_in([r.sid for r in batch], sess, round_k, now)
        act = np.zeros(self.n_lanes, bool)
        dvec = np.ones(self.n_lanes)
        e_goal = np.zeros(self.n_lanes)
        scale = np.ones(self.n_lanes)
        for req, lane in zip(batch, lanes):
            s = sess[req.sid]
            act[lane] = True
            # Effective T_goal: the nominal allotment minus queueing
            # delay — computed from the *relative* deadline so a request
            # served on its arrival instant sees its nominal bitwise.
            dvec[lane] = req.rel_deadline - (now - req.arrival)
            e_goal[lane] = (s.constraints.energy_goal or 0.0) * \
                s.trace.deadline_scale[req.index]
            scale[lane] = s.trace.xi[req.index] * s.trace.lam[req.index]
        if fmul is not None:
            # Injected slow-down composes onto the environment's true
            # scale AFTER the per-lane fill, as (xi*lam) * f — the same
            # multiplication order the megatick planner uses, so both
            # paths see bit-identical effective scales.
            scale = scale * fmul
        if policy == "alert":
            with obs_span(ob, "select", "gateway", round_k=round_k):
                b = self.engine.select(
                    self.slow.mu, self.slow.sigma, self.idle.phi, dvec,
                    accuracy_goal=self.goal_bank.current_goal(),
                    energy_goal=e_goal, goal_kind=self._goal_kinds,
                    active=act, predictions=False)
            i_pick, j_pick = b.model_index, b.power_index
        else:
            b = None
            i_pick = np.full(self.n_lanes, static_config[0],
                             dtype=np.int64)
            j_pick = np.full(self.n_lanes, static_config[1],
                             dtype=np.int64)
        with obs_span(ob, "deliver", "gateway", round_k=round_k):
            d = deliver_tick(self.table, self._st, i_pick, j_pick, scale,
                             dvec, self.phi_true, self._is_anytime,
                             self.table.latency[i_pick, j_pick])
        # Pre-update Eq. 6 prior, snapshotted only for the innovation
        # histogram below (reads never perturb the bank).
        mu_prev = np.asarray(self.slow.mu) \
            if (ob is not None and policy == "alert") else None
        if policy == "alert":
            with obs_span(ob, "feedback", "gateway", round_k=round_k):
                observe_fleet(self.slow, self.idle, d.observed,
                              d.profiled, deadline_missed=d.miss_flag,
                              idle_power=self.phi_true * d.run_power,
                              active_power=self.table.run_power[i_pick,
                                                                j_pick],
                              mask=act)
                self.goal_bank.record(d.accuracy, mask=act)
            if detector is not None:
                # Detection reads the Eq.7 posterior AFTER the round's
                # update — ALERT's own estimate, not an oracle flag.
                # Pure observer: selection above never sees it.
                newly = detector.observe(np.asarray(self.slow.mu),
                                         np.asarray(self.slow.sigma),
                                         act, now)
                if ob is not None and newly.size:
                    ob.metrics.counter("fault_trips",
                                       gateway="host").inc(newly.size)
                    ob.spans.event("fault_trip", cat="fault",
                                   lanes=[int(x) for x in newly],
                                   now_s=float(now))
        if ob is not None:
            if mu_prev is not None:
                # |z - mu_prior| with z the Eq. 6 measurement
                # observed/profiled — the innovation magnitude the
                # Kalman gain weighs this round.
                z = np.asarray(d.observed) / np.asarray(d.profiled)
                ob.metrics.histogram(
                    "kalman_innovation", gateway="host").observe_many(
                    np.abs(z - mu_prev)[act])
            feas = (np.asarray(b.feasible) & act) if b is not None \
                else act
            relaxed = ((np.asarray(b.relaxed_code) != 0) & act) \
                if b is not None else np.zeros_like(act)
            ob.ring.push_rounds(
                now_s=[now], n_active=[int(act.sum())],
                n_feasible=[int(feas.sum())],
                n_relaxed=[int(relaxed.sum())],
                energy_j=[float(np.asarray(d.energy)[act].sum())],
                n_missed=[int(np.asarray(d.missed)[act].sum())])
        last = now
        for req, lane in zip(batch, lanes):
            rid = req._row
            out.status[rid] = SERVED
            out.start[rid] = now
            out.latency[rid] = d.latency[lane]
            out.sojourn[rid] = (now - req.arrival) + d.latency[lane]
            out.missed[rid] = d.missed[lane]
            out.accuracy[rid] = d.accuracy[lane]
            out.energy[rid] = d.energy[lane]
            out.model_index[rid] = i_pick[lane]
            out.power_index[rid] = j_pick[lane]
            self._busy_until[lane] = now + float(d.latency[lane])
            last = max(last, now + float(d.latency[lane]))
        return last


class _SessionStoreView(Mapping):
    """Read-only view of a :class:`SessionGateway`'s paged-out sessions
    in ascending sid order: each entry is ``{part: {name: array}}``, one
    ``[1]`` slice of each state column (``[1, W-1]`` for the goal ring),
    copied out of the columns."""

    def __init__(self, gw: SessionGateway):
        self._gw = gw

    def __getitem__(self, sid) -> dict:
        gw = self._gw
        k = int(gw._dense(sid))
        if k >= len(gw._sids) or gw._sids[k] != sid or not gw._stored[k]:
            raise KeyError(sid)
        return {part: {name: col[k:k + 1].copy()
                       for name, col in cols.items()}
                for part, cols in gw._cols.items()}

    def __iter__(self) -> Iterator[int]:
        gw = self._gw
        return iter(gw._sids[gw._stored].tolist())

    def __len__(self) -> int:
        return int(self._gw._stored.sum())
