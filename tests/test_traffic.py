"""Traffic-subsystem tests: workload determinism, EDF batcher properties
(hypothesis), bank paging round-trips, the columnar session store against
the per-session paging it replaced, gateway-vs-FleetSim bitwise parity
through session paging, admission control under overload, and the load
sweep."""

import numpy as np
import pytest

from benchmarks.common import deadline_range, family_table
from repro.core.batched import WindowedGoalBank
from repro.core.controller import Constraints, Goal
from repro.core.kalman import (IdlePowerFilterBank, SlowdownFilterBank,
                               observe_fleet)
from repro.serving.batcher import DeadlineBatcher, Request
from repro.serving.sim import CPU_ENV, ENVS, EnvironmentTrace, FleetSim
from repro.traffic import (DiurnalProcess, FlashCrowdProcess, MMPPProcess,
                           PoissonProcess, Session, SessionGateway,
                           TenantSpec, build_sessions, generate_requests,
                           sweep_loads)
from repro.traffic.gateway import (REJECTED_BACKPRESSURE,
                                   REJECTED_INFEASIBLE, SERVED)
from repro.traffic.workloads import TrafficRequest
from tests._hypothesis_compat import given, settings, st


@pytest.fixture(scope="module")
def table():
    return family_table("image")


# ------------------------------------------------------------------ #
# workloads                                                           #
# ------------------------------------------------------------------ #
class TestWorkloads:
    def test_processes_deterministic_and_in_horizon(self):
        for proc in (PoissonProcess(3.0), MMPPProcess(1.0, 8.0, 5.0, 2.0),
                     DiurnalProcess(3.0, 0.5, 20.0),
                     FlashCrowdProcess(1.0, 10.0, 10.0, 5.0)):
            a = proc.times(40.0, np.random.default_rng(3))
            b = proc.times(40.0, np.random.default_rng(3))
            np.testing.assert_array_equal(a, b)
            assert np.all((a >= 0) & (a < 40.0))

    def test_poisson_rate_and_scaling(self):
        rng = np.random.default_rng(0)
        n = PoissonProcess(5.0).times(200.0, rng).shape[0]
        assert 800 < n < 1200          # ~1000 +- 6 sigma
        n2 = PoissonProcess(5.0).scaled(2.0).times(
            200.0, np.random.default_rng(0)).shape[0]
        assert n2 > 1.5 * n

    def test_flash_crowd_spikes_inside_window(self):
        proc = FlashCrowdProcess(rate=0.5, spike_rate=20.0,
                                 spike_start=10.0, spike_len=5.0)
        ts = proc.times(30.0, np.random.default_rng(1))
        in_spike = ((ts >= 10.0) & (ts < 15.0)).sum()
        assert in_spike > 0.6 * ts.shape[0]

    def test_build_sessions_tags_and_request_ids(self):
        mix = [TenantSpec("minE", Goal.MINIMIZE_ENERGY,
                          Constraints(deadline=0.2, accuracy_goal=0.7),
                          PoissonProcess(2.0), n_sessions=3),
               TenantSpec("maxQ", Goal.MAXIMIZE_ACCURACY,
                          Constraints.from_power_budget(0.2, 170.0),
                          MMPPProcess(), n_sessions=2)]
        sessions = build_sessions(mix, 20.0, seed=4)
        assert [s.tenant for s in sessions] == \
            ["minE"] * 3 + ["maxQ"] * 2
        assert all(s.trace.n == s.n_requests for s in sessions)
        reqs = generate_requests(sessions)
        # ids are 0..N-1 in arrival order, deterministically
        assert [r.req_id for r in reqs] == list(range(len(reqs)))
        arr = np.asarray([r.arrival for r in reqs])
        assert np.all(np.diff(arr) >= 0)
        reqs2 = generate_requests(build_sessions(mix, 20.0, seed=4))
        assert [(r.sid, r.index, r.arrival) for r in reqs] == \
            [(r.sid, r.index, r.arrival) for r in reqs2]


# ------------------------------------------------------------------ #
# EDF batcher (satellite: per-batcher ids + property tests)           #
# ------------------------------------------------------------------ #
class TestBatcherProperties:
    def test_request_ids_deterministic_per_batcher(self):
        """Two batchers (or two runs) see identical id sequences — the
        counter is per-batcher, not process-global."""
        ids = []
        for _ in range(2):
            b = DeadlineBatcher(batch_size=4)
            for d in (3.0, 1.0, 2.0):
                r = Request(deadline=d)
                b.submit(r)
                ids.append(r.req_id)
        assert ids == [0, 1, 2, 0, 1, 2]

    @settings(max_examples=60, deadline=None)
    @given(deadlines=st.lists(st.floats(0.01, 100.0), min_size=1,
                              max_size=40),
           batch_size=st.integers(1, 8))
    def test_batch_deadline_is_tightest_member(self, deadlines,
                                               batch_size):
        b = DeadlineBatcher(batch_size=batch_size)
        for d in deadlines:
            b.submit(Request(deadline=d))
        got = b.next_batch(now=0.0)
        assert got is not None
        batch, dl = got
        assert dl == min(r.deadline for r in batch)
        assert dl == min(deadlines)        # EDF: head is globally tightest

    @settings(max_examples=60, deadline=None)
    @given(deadlines=st.lists(st.floats(0.01, 100.0), min_size=1,
                              max_size=40),
           batch_size=st.integers(1, 8))
    def test_no_starvation_of_earliest_deadline(self, deadlines,
                                                batch_size):
        """Draining the queue batch by batch serves requests in
        non-decreasing deadline order — the earliest deadline is always
        in the very next batch."""
        b = DeadlineBatcher(batch_size=batch_size)
        for d in deadlines:
            b.submit(Request(deadline=d))
        popped = []
        while True:
            got = b.next_batch(now=0.0)
            if got is None:
                break
            popped.extend(r.deadline for r in got[0])
        assert popped == sorted(deadlines)
        assert not b.rejected

    @settings(max_examples=60, deadline=None)
    @given(deadlines=st.lists(st.floats(0.0, 10.0), min_size=1,
                              max_size=40),
           now=st.floats(0.0, 10.0), min_lat=st.floats(0.0, 5.0))
    def test_fail_fast_requests_never_batched(self, deadlines, now,
                                              min_lat):
        b = DeadlineBatcher(batch_size=4, min_feasible_latency=min_lat)
        for d in deadlines:
            b.submit(Request(deadline=d))
        served = []
        while True:
            got = b.next_batch(now=now)
            if got is None:
                break
            served.extend(got[0])
        assert all(r.deadline - now >= min_lat for r in served)
        assert all(r.deadline - now < min_lat for r in b.rejected)
        assert len(served) + len(b.rejected) == len(deadlines)

    def test_backpressure_bounds_queue(self):
        b = DeadlineBatcher(batch_size=4, max_queue=3)
        oks = [b.submit(Request(deadline=float(d))) for d in range(5)]
        assert oks == [True] * 3 + [False] * 2
        assert len(b) == 3 and len(b.overflowed) == 2


# ------------------------------------------------------------------ #
# bank paging primitives                                              #
# ------------------------------------------------------------------ #
class TestExportImport:
    def _scrambled_banks(self, s=8, ticks=5, seed=0):
        rng = np.random.default_rng(seed)
        slow = SlowdownFilterBank(s)
        idle = IdlePowerFilterBank(s)
        goal = WindowedGoalBank(rng.uniform(0.5, 0.9, s), s, window=4)
        for _ in range(ticks):
            mask = rng.random(s) < 0.8
            observe_fleet(slow, idle, rng.uniform(0.5, 2.0, s),
                          rng.uniform(0.5, 2.0, s),
                          deadline_missed=rng.random(s) < 0.2,
                          idle_power=rng.uniform(0.1, 0.5, s),
                          active_power=rng.uniform(0.5, 1.5, s),
                          mask=mask)
            goal.record(rng.uniform(0.4, 1.0, s), mask=mask)
        return slow, idle, goal

    def test_round_trip_bitwise_identity(self):
        """export -> reset (another tenant scrambles the lane) -> import
        restores every state vector bit for bit."""
        slow, idle, goal = self._scrambled_banks()
        lanes = [1, 3, 6]
        snap = {"slow": slow.export_lanes(lanes),
                "idle": idle.export_lanes(lanes),
                "goal": goal.export_lanes(lanes)}
        before = {
            "slow": {n: np.asarray(getattr(slow, n)).copy()
                     for n in slow._state_names + ("n_updates",)},
            "idle": {n: np.asarray(getattr(idle, n)).copy()
                     for n in idle._state_names + ("n_updates",)},
            "goal": {"goal": goal.goal.copy(), "buf": goal._buf.copy(),
                     "count": goal._count.copy(),
                     "pos": goal._pos.copy()},
        }
        # another tenant occupies + scrambles the lanes
        slow.reset_lanes(lanes)
        idle.reset_lanes(lanes)
        goal.reset_lanes(lanes, goal=[0.1, 0.2, 0.3])
        observe_fleet(slow, idle, np.full(8, 1.7), np.ones(8),
                      idle_power=np.full(8, 0.3), active_power=np.ones(8))
        goal.record(np.full(8, 0.5))
        snap2 = {"slow": slow.export_lanes([0, 2, 4, 5, 7]),
                 "idle": idle.export_lanes([0, 2, 4, 5, 7]),
                 "goal": goal.export_lanes([0, 2, 4, 5, 7])}
        del snap2
        slow.import_lanes(lanes, snap["slow"])
        idle.import_lanes(lanes, snap["idle"])
        goal.import_lanes(lanes, snap["goal"])
        for n, want in before["slow"].items():
            np.testing.assert_array_equal(
                np.asarray(getattr(slow, n))[lanes], want[lanes], err_msg=n)
        for n, want in before["idle"].items():
            np.testing.assert_array_equal(
                np.asarray(getattr(idle, n))[lanes], want[lanes], err_msg=n)
        np.testing.assert_array_equal(goal.goal[lanes],
                                      before["goal"]["goal"][lanes])
        np.testing.assert_array_equal(goal._buf[lanes],
                                      before["goal"]["buf"][lanes])
        np.testing.assert_array_equal(goal._count[lanes],
                                      before["goal"]["count"][lanes])
        np.testing.assert_array_equal(goal._pos[lanes],
                                      before["goal"]["pos"][lanes])

    def test_import_does_not_touch_other_lanes(self):
        slow, idle, goal = self._scrambled_banks(seed=3)
        others = [0, 2, 4, 5, 7]
        keep = {n: np.asarray(getattr(slow, n)).copy()[others]
                for n in slow._state_names}
        snap = slow.export_lanes([1])
        slow.import_lanes([3], snap)
        for n in slow._state_names:
            np.testing.assert_array_equal(
                np.asarray(getattr(slow, n))[others], keep[n], err_msg=n)

    def test_round_trip_on_one_device_mesh(self):
        """Sharded banks page bitwise too (1-device lane mesh)."""
        from repro.launch.mesh import make_lane_mesh
        mesh = make_lane_mesh(1)
        slow = SlowdownFilterBank(4, mesh=mesh)
        slow.observe(np.asarray([1.2, 0.8, 1.5, 1.0]), np.ones(4))
        want = {n: np.asarray(getattr(slow, n)).copy()
                for n in slow._state_names + ("n_updates",)}
        snap = slow.export_lanes([1, 2])
        slow.reset_lanes([1, 2])
        slow.import_lanes([1, 2], snap)
        for n, w in want.items():
            np.testing.assert_array_equal(np.asarray(getattr(slow, n)), w,
                                          err_msg=n)

    def test_goal_bank_round_trip_on_one_device_mesh(self):
        """The windowed-goal bank's sharded page path round-trips too."""
        from repro.launch.mesh import make_lane_mesh
        mesh = make_lane_mesh(1)
        goal = WindowedGoalBank([0.6, 0.7, 0.8, 0.9], 4, window=3,
                                mesh=mesh)
        goal.record(np.asarray([0.5, 0.6, 0.7, 0.8]))
        goal.record(np.asarray([0.9, 0.8, 0.7, 0.6]),
                    mask=np.asarray([True, False, True, False]))
        want = {n: np.asarray(getattr(goal, n)).copy()
                for n in ("goal", "_buf", "_count", "_pos")}
        snap = goal.export_lanes([0, 3])
        goal.reset_lanes([0, 3], goal=[0.1, 0.1])
        goal.import_lanes([0, 3], snap)
        for n, w in want.items():
            np.testing.assert_array_equal(np.asarray(getattr(goal, n)), w,
                                          err_msg=n)
        # compensation rule still computes from the restored window (the
        # sharded sum may differ from numpy in the last ulp — DESIGN §6's
        # documented exception — hence allclose, not array_equal)
        np.testing.assert_allclose(np.asarray(goal.current_goal()),
                                   np.asarray(want["goal"]) * 3
                                   - np.asarray(want["_buf"]).sum(1)
                                   - (3 - np.asarray(want["_count"])
                                      - 1) * np.asarray(want["goal"]),
                                   rtol=0, atol=1e-12)


# ------------------------------------------------------------------ #
# gateway: paging-invisible parity + admission under overload         #
# ------------------------------------------------------------------ #
def _short_trace(env, seed, n, deadline_cv=0.0):
    tr = EnvironmentTrace(env, seed=seed, deadline_cv=deadline_cv)
    tr.n = n
    tr.xi, tr.lam = tr.xi[:n], tr.lam[:n]
    tr.deadline_scale = tr.deadline_scale[:n]
    return tr


class TestGatewayParity:
    def test_low_load_bitwise_equals_fleetsim_through_paging(self, table):
        """THE acceptance property: 6 sessions multiplexed over 3 lanes
        with zero queueing delay — per-session outcomes are
        bitwise-identical to independent FleetSim runs even though every
        session's Kalman/goal state pages in and out of recycled lanes
        between rounds, and paging never re-traces the engine."""
        dl = float(deadline_range(table, 5)[3])
        tick = dl * 2.5
        sessions = []
        for sid in range(6):
            tr = _short_trace(ENVS["cpu"] if sid % 2 else ENVS["memory"],
                              40 + sid, 25, deadline_cv=0.1)
            # odd/even sessions alternate rounds -> 6 sessions never fit
            # the 3 lanes without paging
            arrivals = (2 * np.arange(25) + (sid % 2)) * tick
            goal = Goal.MINIMIZE_ENERGY if sid % 3 else \
                Goal.MAXIMIZE_ACCURACY
            cons = Constraints(deadline=dl, accuracy_goal=0.8) \
                if sid % 3 else Constraints.from_power_budget(dl, 170.0)
            sessions.append(Session(sid, "t", goal, cons, arrivals, tr))
        gw = SessionGateway(table, 3, tick=tick)
        res = gw.run(sessions)
        assert res.served.all()
        assert res.pages_in > 50 and res.pages_out > 50, \
            "scenario must actually exercise paging"
        assert res.n_compiles == (0, 1), \
            "session paging must never re-trace the engine"
        for s in sessions:
            fr = FleetSim(table, [s.trace]).run_streams([s.goal],
                                                        [s.constraints])
            got, want = res.stream(s.sid), fr.stream(0)
            np.testing.assert_array_equal(got.energy, want.energy,
                                          err_msg=f"sid {s.sid}")
            np.testing.assert_array_equal(got.accuracy, want.accuracy)
            np.testing.assert_array_equal(got.latency, want.latency)
            np.testing.assert_array_equal(got.missed, want.missed)

    def test_reused_gateway_is_reset_between_runs(self, table):
        """A second run on the same gateway sees fresh state (and still
        zero re-traces) — the load sweep leans on this."""
        dl = float(deadline_range(table, 5)[3])
        tr = _short_trace(ENVS["cpu"], 9, 10)
        sess = [Session(0, "t", Goal.MINIMIZE_ENERGY,
                        Constraints(deadline=dl, accuracy_goal=0.75),
                        np.arange(10) * dl, tr)]
        gw = SessionGateway(table, 2, tick=dl)
        a = gw.run(sess)
        b = gw.run(sess)
        np.testing.assert_array_equal(a.energy, b.energy)
        np.testing.assert_array_equal(a.accuracy, b.accuracy)
        assert b.n_compiles == (0, 1)

    def test_static_policy_matches_fixed_config_delivery(self, table):
        """policy='static' executes exactly the fixed config."""
        dl = float(deadline_range(table, 5)[3])
        tr = _short_trace(ENVS["default"], 2, 8)
        sess = [Session(0, "t", Goal.MINIMIZE_ENERGY,
                        Constraints(deadline=dl, accuracy_goal=0.7),
                        np.arange(8) * dl, tr)]
        gw = SessionGateway(table, 2, tick=dl)
        res = gw.run(sess, policy="static", static_config=(1, 2))
        assert res.served.all()
        assert np.all(res.model_index[res.served] == 1)
        assert np.all(res.power_index[res.served] == 2)
        want = table.latency[1, 2] * tr.xi * tr.lam
        got = res.stream(0)
        np.testing.assert_array_equal(got.latency,
                                      np.minimum(want, dl))

    def test_static_policy_requires_config(self, table):
        gw = SessionGateway(table, 2)
        with pytest.raises(ValueError, match="static_config"):
            gw.run([], policy="static")


class TestGatewayOverload:
    @pytest.fixture(scope="class")
    def overload(self, table):
        dl = float(deadline_range(table, 5)[3])
        cons = Constraints(deadline=dl, accuracy_goal=0.78)
        n_lanes, s = 16, 64
        rate = 8.0 * (n_lanes / dl) / s      # ~8x a conservative capacity
        mix = [TenantSpec("minE", Goal.MINIMIZE_ENERGY, cons,
                          PoissonProcess(rate), n_sessions=s,
                          phases=CPU_ENV)]
        sessions = build_sessions(mix, 10 * dl, seed=11)
        requests = generate_requests(sessions)
        return table, dl, n_lanes, sessions, requests

    def test_admission_sheds_and_bounds_served_miss(self, overload):
        table, dl, n_lanes, sessions, requests = overload
        gw = SessionGateway(table, n_lanes, tick=dl / 4,
                            max_queue=4 * n_lanes)
        res = gw.run(sessions, requests)
        gw_off = SessionGateway(table, n_lanes, tick=dl / 4,
                                max_queue=None, min_feasible_latency=0.0)
        off = gw_off.run(sessions, requests)
        assert res.reject_rate > 0.05, "overload must shed load"
        assert (res.status == REJECTED_INFEASIBLE).any()
        # admission control keeps the *served* miss rate below the
        # no-admission ablation's (hopeless requests are shed, not run)
        assert res.served_miss_rate < off.served_miss_rate
        assert res.goodput > 0
        assert res.n_compiles == (0, 1)

    def test_backpressure_rejections_recorded(self, overload):
        table, dl, n_lanes, sessions, requests = overload
        gw = SessionGateway(table, n_lanes, tick=dl / 4, max_queue=8)
        res = gw.run(sessions, requests)
        from repro.traffic.gateway import REJECTED_BACKPRESSURE
        assert (res.status == REJECTED_BACKPRESSURE).any()
        assert res.offered == len(requests)
        served = int(res.served.sum())
        assert served + int((res.status != 0).sum()) == res.offered


# ------------------------------------------------------------------ #
# load sweep                                                          #
# ------------------------------------------------------------------ #
class TestLoadSweep:
    def test_sweep_runs_end_to_end(self, table):
        dl = float(deadline_range(table, 5)[3])
        cons = Constraints(deadline=dl, accuracy_goal=0.78)
        n_lanes, s = 16, 32
        base = 0.5 * (n_lanes / dl) / s
        mix = [TenantSpec("minE", Goal.MINIMIZE_ENERGY, cons,
                          PoissonProcess(base), n_sessions=s,
                          phases=CPU_ENV)]
        rows = sweep_loads(table, mix, [0.5, 4.0], n_lanes=n_lanes,
                           horizon=8 * dl, seed=3,
                           max_queue=4 * n_lanes, tick=dl / 4)
        assert len(rows) == 2
        for r in rows:
            a = r["schemes"]["alert"]
            st_ = r["schemes"]["oracle_static"]
            assert a["n_compiles"] == [0, 1]
            assert a["goodput_rps"] > 0 and st_["goodput_rps"] > 0
        # at the comfortable load point ALERT's adaptation wins energy
        low = rows[0]["schemes"]
        assert low["alert"]["energy_per_good_j"] < \
            low["oracle_static"]["energy_per_good_j"]

    def test_multi_tenant_static_rejected(self, table):
        c = Constraints(deadline=0.1, accuracy_goal=0.7)
        mix = [TenantSpec("a", Goal.MINIMIZE_ENERGY, c, PoissonProcess(1.0)),
               TenantSpec("b", Goal.MINIMIZE_ENERGY, c, PoissonProcess(1.0))]
        with pytest.raises(ValueError, match="single-tenant"):
            sweep_loads(table, mix, [1.0], n_lanes=4, horizon=1.0)


# ------------------------------------------------------------------ #
# FleetAlertServer constraints override (satellite)                   #
# ------------------------------------------------------------------ #
class TestFleetServerConstraintOverride:
    def test_admit_installs_per_lane_constraints(self):
        import jax

        from repro.configs.base import ModelConfig
        from repro.models.registry import build_model
        from repro.serving.alert_server import FleetAlertServer
        from repro.serving.engine import ServeEngine

        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=4, head_dim=8, d_ff=64,
                          vocab=64, nest_levels=2, dtype="float32",
                          attn_chunk=32)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        engine = ServeEngine(model, max_len=32, batch_size=2)
        srv = FleetAlertServer(engine, params,
                               level_accuracies=[0.6, 0.9],
                               goal=Goal.MAXIMIZE_ACCURACY, n_streams=2,
                               profile_iters=1, gen_tokens=3,
                               start_active=False)
        budget = float(np.median(srv.table.run_power)) * \
            float(np.max(srv.table.latency)) * 2.0
        c0 = Constraints(deadline=10.0, energy_goal=budget)
        c1 = Constraints(deadline=5.0, accuracy_goal=0.7,
                         energy_goal=budget)
        lane0 = srv.admit(constraints=c0)
        lane1 = srv.admit(goal=Goal.MINIMIZE_ENERGY, constraints=c1)
        prompt = np.zeros((2, 4), np.int32)
        # no serve_tick constraints at all: lanes carry their own
        outs = srv.serve_tick([prompt, prompt])
        assert outs[lane0] is not None and outs[lane1] is not None
        # a per-call entry overrides only that lane; None entries fall
        # back to the admit-installed constraints
        outs = srv.serve_tick([prompt, prompt],
                              [Constraints(deadline=20.0,
                                           energy_goal=budget), None])
        assert outs[lane0] is not None and outs[lane1] is not None
        # retiring clears the override: a live lane without constraints
        # anywhere must raise
        srv.retire(lane1)
        srv.admit()     # same lane, no constraints installed
        with pytest.raises(ValueError, match="Constraints"):
            srv.serve_tick([prompt, prompt], [c0, None])


# ------------------------------------------------------------------ #
# round-loop regressions: requeue semantics, duplicate offers,        #
# page-in invariants                                                  #
# ------------------------------------------------------------------ #
class TestRoundLoopRegressions:
    def test_requeue_bypasses_backpressure_on_full_queue(self):
        """A deferred (already admitted) request re-enters the heap even
        when the queue sits at max_queue — deferral is not a new
        arrival, so it can never be shed or recorded as overflow."""
        b = DeadlineBatcher(batch_size=4, max_queue=2)
        r1, r2 = Request(deadline=1.0), Request(deadline=2.0)
        assert b.submit(r1) and b.submit(r2)
        got = b.pop_one(now=0.0)
        assert got is r1
        r3 = Request(deadline=3.0)
        assert b.submit(r3)              # queue back at max_queue
        b.requeue(r1)                    # len 3 > max_queue: still ok
        assert len(b) == 3
        assert not b.overflowed and not b.rejected

    def test_requeue_preserves_edf_tie_break_over_later_submits(self):
        """Deferral keeps the request's ORIGINAL heap seq: after a
        requeue it still beats same-deadline requests submitted after
        it (the old submit-based requeue handed out a fresh seq and
        inverted EDF submission order)."""
        b = DeadlineBatcher(batch_size=4)
        reqs = [Request(deadline=5.0) for _ in range(3)]
        for r in reqs:
            b.submit(r)
        first = b.pop_one(now=0.0)
        assert first is reqs[0]
        b.requeue(first)
        order = [b.pop_one(now=0.0) for _ in range(3)]
        assert order == reqs             # seq 0 still wins the tie

    def test_requeue_of_never_admitted_request_raises(self):
        b = DeadlineBatcher(batch_size=4)
        with pytest.raises(ValueError, match="submit"):
            b.requeue(Request(deadline=1.0))

    def test_refused_submit_consumes_no_seq(self):
        """Backpressure refusal must not burn an id/seq — the next
        admitted request's EDF tie-break is unaffected by the shed
        one."""
        b = DeadlineBatcher(batch_size=4, max_queue=1)
        r1 = Request(deadline=5.0)
        b.submit(r1)
        shed = Request(deadline=5.0)
        assert not b.submit(shed)
        assert shed._seq is None and shed.req_id is None
        b.pop_one(now=0.0)
        r2 = Request(deadline=5.0)
        b.submit(r2)
        assert r2._seq == 1              # not 2: refusal consumed nothing

    @pytest.mark.parametrize("busy", ["host", "megatick"])
    def test_round_admission(self, table, busy):
        """The one round admission both gateways call, with the host's
        per-lane completion instants (``busy="host"``) and without them
        (``"megatick"``: no lane busy by the regime contract).  On three
        lanes, a queue of five and a fail-fast floor of 0.5 s at
        ``now = 1``: the sixth arrival is shed by backpressure, the
        hopeless head fails fast with ``start = now``, a session's
        second request waits behind its first, and the session resident
        on a busy lane waits only where busy lanes are given, which also
        takes that lane out of the round.  Deferred requests keep their
        EDF seq; a run of one session's requests stops the round after
        ``4 * n_lanes + 1`` deferrals."""
        dl = float(deadline_range(table, 5)[3])
        tr = _short_trace(ENVS["default"], 3, 4)

        def sessions(sids):
            return [Session(sid, "t", Goal.MINIMIZE_ENERGY,
                            Constraints(deadline=dl, accuracy_goal=0.7),
                            np.arange(4) * dl, tr) for sid in sids]

        def request(k, sid, arrival, rel):
            return TrafficRequest(deadline=arrival + rel, arrival=arrival,
                                  req_id=k, sid=sid, rel_deadline=rel)

        def admit(gw, sess, reqs):
            rs = gw._init_run(sess, reqs, policy="alert",
                              static_config=None, faults=None)
            # Session 11 resident on lane 1, mid-service until t = 5.
            gw._lane_of[gw._dense(11)] = 1
            gw._resident[1] = 11
            until = np.asarray([0.0, 5.0, 0.0]) if busy == "host" \
                else None
            return rs, gw._admit(rs, 1.0, until)

        gw = SessionGateway(table, 3, tick=1.0, max_queue=5,
                            min_feasible_latency=0.5)
        reqs = [request(0, 10, 0.5, 0.9),     # slack 0.4: fails fast
                request(1, 10, 0.5, 1.5),     # served
                request(2, 10, 0.5, 1.5),     # same session: deferred
                request(3, 11, 0.5, 2.5),     # on the busy lane
                request(4, 12, 0.5, 3.5),     # served
                request(5, 13, 0.5, 4.5),     # queue full: shed
                request(6, 13, 2.0, 1.0)]     # not yet arrived
        rs, (batch, deferred) = admit(gw, sessions([10, 11, 12, 13]),
                                      reqs)
        if busy == "host":
            assert batch == [reqs[1], reqs[4]]
            assert deferred == [reqs[2], reqs[3]]
        else:
            assert batch == [reqs[1], reqs[3], reqs[4]]
            assert deferred == [reqs[2]]
        assert rs.ri == 6 and rs.queue.overflowed == [reqs[5]]
        assert rs.queue.rejected == [reqs[0]]
        out = rs.out
        assert out.status[0] == REJECTED_INFEASIBLE and out.start[0] == 1.0
        assert out.status[5] == REJECTED_BACKPRESSURE
        assert not (out.status[[1, 3, 4]] == SERVED).any()
        assert (out.start[1:] == 0.0).all()
        # Submitted after them, a request due with the deferred one
        # pops after it.
        late = TrafficRequest(deadline=2.0, arrival=1.0, sid=14)
        rs.queue.submit(late)
        popped = [rs.queue.pop_one(1.0) for _ in range(len(rs.queue))]
        assert popped == [reqs[2], late] + deferred[1:]

        gw = SessionGateway(table, 3, tick=1.0)
        run = [request(k, 20, 0.0, 3.0) for k in range(16)]
        other = request(16, 21, 0.0, 4.0)
        rs, (batch, deferred) = admit(gw, sessions([11, 20, 21]),
                                      run + [other])
        assert batch == run[:1] and deferred == run[1:14]
        popped = [rs.queue.pop_one(1.0) for _ in range(len(rs.queue))]
        assert popped == run[1:] + [other]

    def test_duplicate_request_object_rejected(self, table):
        dl = float(deadline_range(table, 5)[3])
        tr = _short_trace(ENVS["default"], 3, 4)
        sess = [Session(0, "t", Goal.MINIMIZE_ENERGY,
                        Constraints(deadline=dl, accuracy_goal=0.7),
                        np.arange(4) * dl, tr)]
        reqs = generate_requests(sess)
        gw = SessionGateway(table, 2, tick=dl)
        with pytest.raises(ValueError, match="distinct object"):
            gw.run(sess, reqs + [reqs[0]])

    def test_page_in_underflow_raises(self, table):
        """More sessions needing lanes than can ever be freed must fail
        loudly (the old zip() silently truncated the batch)."""
        dl = float(deadline_range(table, 5)[3])
        tr = _short_trace(ENVS["default"], 3, 4)
        sessions = {sid: Session(sid, "t", Goal.MINIMIZE_ENERGY,
                                 Constraints(deadline=dl,
                                             accuracy_goal=0.7),
                                 np.arange(4) * dl, tr)
                    for sid in range(3)}
        gw = SessionGateway(table, 2, tick=dl)
        gw._busy_until[:] = 1e9          # every lane mid-service
        with pytest.raises(RuntimeError, match="page-in"):
            gw._page_in([0, 1, 2], sessions, round_k=0, now=0.0)


# ------------------------------------------------------------------ #
# columnar session store vs the per-session paging it replaced        #
# ------------------------------------------------------------------ #
class _DictPager:
    """The per-session LRU paging the columnar store replaced, kept as
    the reference: a dict store of ``[1]`` slices keyed by sid and a
    sid -> lane dict, over the banks and lane arrays of its own
    gateway (whose own ``_page_in`` it never calls)."""

    def __init__(self, gw):
        self.gw = gw
        self.lane_of: dict = {}
        self.store: dict = {}
        self.pages_in = self.pages_out = 0

    def evict(self, ev_lanes):
        gw = self.gw
        if not len(ev_lanes):
            return
        slow_s = gw.slow.export_lanes(ev_lanes)
        idle_s = gw.idle.export_lanes(ev_lanes)
        goal_s = gw.goal_bank.export_lanes(ev_lanes)
        for k, ln in enumerate(ev_lanes):
            old = int(gw._resident[ln])
            self.store[old] = {
                "slow": {n: v[k:k + 1] for n, v in slow_s.items()},
                "idle": {n: v[k:k + 1] for n, v in idle_s.items()},
                "goal": {n: v[k:k + 1] for n, v in goal_s.items()},
            }
            del self.lane_of[old]
            gw._resident[ln] = -1
            self.pages_out += 1

    def page_in(self, sids, sessions, round_k, now):
        from repro.core.batched import goal_codes

        gw = self.gw
        needed = set(sids)
        lanes = np.empty(len(sids), dtype=np.int64)
        missing = []
        for pos, sid in enumerate(sids):
            lane = self.lane_of.get(sid, -1)
            lanes[pos] = lane
            if lane < 0:
                missing.append(pos)
        if missing:
            idle = (gw._busy_until <= now) & ~gw._dead
            free = [int(x) for x in
                    np.nonzero((gw._resident < 0) & idle)[0]]
            n_evict = len(missing) - len(free)
            ev_lanes = []
            if n_evict > 0:
                evictable = sorted(
                    (int(gw._last_used[ln]), ln)
                    for ln in range(gw.n_lanes)
                    if idle[ln] and gw._resident[ln] >= 0
                    and int(gw._resident[ln]) not in needed)
                ev_lanes = [ln for _, ln in evictable[:n_evict]]
            if ev_lanes:
                self.evict(ev_lanes)
                free += ev_lanes
            assert len(free) >= len(missing)
            paged_lanes, paged_sids, fresh_lanes, fresh_sids = \
                [], [], [], []
            for pos, ln in zip(missing, free):
                sid = sids[pos]
                lanes[pos] = ln
                gw._resident[ln] = sid
                self.lane_of[sid] = ln
                if sid in self.store:
                    paged_lanes.append(ln)
                    paged_sids.append(sid)
                else:
                    fresh_lanes.append(ln)
                    fresh_sids.append(sid)
                gw._goal_kinds[ln] = goal_codes([sessions[sid].goal])[0]
            if paged_lanes:
                def cat(part):
                    return {n: np.concatenate([self.store[s][part][n]
                                               for s in paged_sids])
                            for n in self.store[paged_sids[0]][part]}
                gw.slow.import_lanes(paged_lanes, cat("slow"))
                gw.idle.import_lanes(paged_lanes, cat("idle"))
                gw.goal_bank.import_lanes(paged_lanes, cat("goal"))
                for s in paged_sids:
                    del self.store[s]
                self.pages_in += len(paged_lanes)
            if fresh_lanes:
                gw.slow.reset_lanes(fresh_lanes)
                gw.idle.reset_lanes(fresh_lanes)
                gw.goal_bank.reset_lanes(
                    fresh_lanes,
                    goal=[sessions[s].constraints.accuracy_goal or 0.0
                          for s in fresh_sids])
        gw._last_used[lanes] = round_k
        return lanes


def _bank_state(gw) -> dict:
    """Every lane's full bank state, on the host by bank and field."""
    lanes = np.arange(gw.n_lanes)
    return {part: {n: np.asarray(v) for n, v in
                   bank.export_lanes(lanes).items()}
            for part, bank in (("slow", gw.slow), ("idle", gw.idle),
                               ("goal", gw.goal_bank))}


class TestColumnarPaging:
    @pytest.mark.parametrize("mesh_size", [None, 1])
    def test_page_in_matches_per_session_reference(self, table,
                                                   mesh_size):
        """50 random rounds of 96 sessions (sparse, shuffled sids) over
        16 lanes, with busy lanes, dead lanes (residents quarantined
        through ``_evict_lanes``) and batches that hold resident
        sessions: the columnar gateway picks the same lanes, pages the
        same sessions and ends every round with the same bank state, bit
        for bit, as the per-session dict paging it replaced."""
        from repro.launch.mesh import make_lane_mesh

        mesh = None if mesh_size is None else make_lane_mesh(mesh_size)
        n_lanes, n_sess = 16, 96
        dl = float(deadline_range(table, 5)[3])
        tr = _short_trace(ENVS["default"], 3, 4)
        rng = np.random.default_rng(11)
        sessions = {}
        for k in rng.permutation(n_sess):
            sid = int(7 * k + 3)
            if k % 3:
                goal, cons = Goal.MINIMIZE_ENERGY, Constraints(
                    deadline=dl, accuracy_goal=0.6 + 0.003 * k)
            else:
                goal = Goal.MAXIMIZE_ACCURACY
                cons = Constraints.from_power_budget(dl, 170.0)
            sessions[sid] = Session(sid, "t", goal, cons, np.zeros(1), tr)
        all_sids = np.asarray(sorted(sessions))
        gw = SessionGateway(table, n_lanes, tick=1.0, mesh=mesh)
        ref = _DictPager(SessionGateway(table, n_lanes, tick=1.0,
                                        mesh=mesh))
        rg = ref.gw
        n_fresh = 0
        for k in range(50):
            now = float(k)
            if k % 7 == 3:
                dead_now = rng.random(n_lanes) < 0.15
                newly = dead_now & ~gw._dead
                gw._evict_lanes(np.nonzero(newly & (gw._resident >= 0))[0])
                ref.evict([int(x) for x in np.nonzero(newly)[0]
                           if rg._resident[x] >= 0])
                gw._dead, rg._dead = dead_now.copy(), dead_now.copy()
            idle = (gw._busy_until <= now) & ~gw._dead
            held = gw._resident[(gw._resident >= 0) & ~idle]
            on_idle = gw._resident[(gw._resident >= 0) & idle]
            others = np.setdiff1d(all_sids, gw._resident)
            n = int(rng.integers(1, int(idle.sum()) + 1))
            mine = rng.choice(on_idle, int(rng.integers(
                0, min(n, on_idle.size) + 1)), replace=False)
            rest = rng.choice(others, n - mine.size, replace=False)
            sids = [int(s) for s in rng.permutation(
                np.concatenate([mine, rest]))]
            assert not set(sids) & set(held.tolist())
            n_fresh += sum(s not in ref.store and s not in ref.lane_of
                           for s in sids)
            got = gw._page_in(sids, sessions, k, now)
            want = ref.page_in(sids, sessions, k, now)
            np.testing.assert_array_equal(got, want, err_msg=f"round {k}")
            # The round's feedback, the same on both, so that every
            # session carries state of its own through the store.
            act = np.zeros(n_lanes, bool)
            act[got] = True
            obs = [rng.uniform(0.5, 2.0, n_lanes) for _ in range(5)]
            miss = rng.random(n_lanes) < 0.2
            for g in (gw, rg):
                observe_fleet(g.slow, g.idle, obs[0], obs[1],
                              deadline_missed=miss, idle_power=obs[2],
                              active_power=obs[3], mask=act)
                g.goal_bank.record(obs[4], mask=act)
            busy = now + rng.uniform(0.0, 3.0, got.size)
            gw._busy_until[got] = busy
            rg._busy_until[got] = busy
            for name in ("_resident", "_goal_kinds", "_last_used"):
                np.testing.assert_array_equal(getattr(gw, name),
                                              getattr(rg, name),
                                              err_msg=f"{name} round {k}")
            assert (gw.pages_in, gw.pages_out) == \
                (ref.pages_in, ref.pages_out), f"round {k}"
            mine_s, ref_s = _bank_state(gw), _bank_state(rg)
            for part in ref_s:
                for name, v in ref_s[part].items():
                    np.testing.assert_array_equal(
                        mine_s[part][name], v,
                        err_msg=f"{part}.{name} round {k}")
            store = gw._store
            assert set(store) == set(ref.store), f"round {k}"
            assert len(store) == len(ref.store)
            for sid, entry in ref.store.items():
                mine_e = store[sid]
                for part in entry:
                    assert mine_e[part].keys() == entry[part].keys()
                    for name, v in entry[part].items():
                        assert mine_e[part][name].shape == v.shape
                        np.testing.assert_array_equal(
                            mine_e[part][name], v,
                            err_msg=f"sid {sid} {part}.{name}")
        assert ref.pages_in > 50 and ref.pages_out > 100 and n_fresh > 40, \
            "the rounds must page in, out and fresh"
        assert all(e["slow"]["mu"].shape == (1,) and
                   e["goal"]["buf"].shape == (1, 9)
                   for e in gw._store.values())
        resident = int(gw._resident[gw._resident >= 0][0])
        assert resident not in gw._store and 10 ** 6 not in gw._store
        with pytest.raises(KeyError):
            gw._store[resident]


# ------------------------------------------------------------------ #
# megatick building blocks: bitwise twins of the host kernels         #
# ------------------------------------------------------------------ #
class TestMegatickKernels:
    @pytest.mark.parametrize("depth", list(range(1, 17)) + [
        24, 40, 127, 128, 129, 200, 257])
    def test_pairwise_sum_matches_numpy_bitwise(self, depth):
        """The traced window sum reproduces numpy's pairwise-summation
        order exactly, at every depth the recursion changes shape."""
        import jax
        from repro.core.precision import x64_scope
        from repro.core.batched import pairwise_sum_cols

        rng = np.random.default_rng(depth)
        buf = rng.uniform(-1.0, 1.0, (7, depth))
        want = buf.sum(axis=1)
        with x64_scope():
            got = np.asarray(jax.jit(
                lambda b: pairwise_sum_cols(
                    [b[:, c] for c in range(b.shape[1])]))(buf))
        np.testing.assert_array_equal(got, want)

    def test_goal_current_hostsum_matches_bank_bitwise(self):
        """Traced effective-goal compensation == the host bank's numpy
        path, including the runtime-zero FMA-contraction guard."""
        import jax
        from repro.core.precision import x64_scope
        from repro.core.batched import goal_current_step_hostsum

        rng = np.random.default_rng(7)
        s, window = 64, 10
        bank = WindowedGoalBank(rng.uniform(0.5, 0.9, s), s, window)
        for _ in range(6):
            bank.record(rng.uniform(0.0, 1.0, s),
                        mask=rng.random(s) < 0.7)
        want = bank.current_goal()
        with x64_scope():
            got = np.asarray(jax.jit(goal_current_step_hostsum,
                                     static_argnums=3)(
                bank.goal, bank._buf, bank._count, window, 0.0))
        np.testing.assert_array_equal(got, want)

    def test_deliver_step_matches_deliver_tick_bitwise(self, table):
        """The traced delivery twin == the numpy kernel on every field,
        under jit (where XLA's FMA contraction would bite without the
        runtime-zero guard)."""
        import jax
        from repro.core.precision import x64_scope
        from repro.serving.sim import deliver_step, deliver_tick

        st = table.staircase_tensors()
        k, l = table.latency.shape
        groups = table.anytime_groups()
        is_any = np.zeros(len(table.candidates), bool)
        is_any[sorted({i for g in groups.values() for i in g})] = True
        rng = np.random.default_rng(3)
        n = 256
        i = rng.integers(0, k, n)
        j = rng.integers(0, l, n)
        scale = rng.uniform(0.5, 2.0, n)
        dvec = rng.uniform(0.01, 2.0 * float(table.latency.max()), n)
        want = deliver_tick(table, st, i, j, scale, dvec, 0.25, is_any,
                            table.latency[i, j])
        consts = dict(latency_kl=table.latency,
                      run_power_kl=table.run_power,
                      q_fail=float(table.q_fail), is_anytime_k=is_any,
                      lvl_lat_kml=st.lvl_lat, lvl_valid_km=st.lvl_valid,
                      lvl_acc_km=st.lvl_acc)
        with x64_scope():
            got = jax.jit(lambda ii, jj, sc, dv, fz: deliver_step(
                ii, jj, sc, dv, 0.25, f_zero=fz, **consts))(
                    i, j, scale, dvec, 0.0)
        for name, a, b in zip(
                ("latency", "accuracy", "energy", "missed", "run_power",
                 "observed", "profiled", "miss_flag"),
                (want.latency, want.accuracy, want.energy, want.missed,
                 want.run_power, want.observed, want.profiled,
                 want.miss_flag), got):
            np.testing.assert_array_equal(np.asarray(b), a,
                                          err_msg=name)


# ------------------------------------------------------------------ #
# megatick gateway: the device-resident round clock                   #
# ------------------------------------------------------------------ #
_RESULT_FIELDS = ("sid", "index", "arrival", "status", "start",
                  "latency", "sojourn", "missed", "accuracy", "energy",
                  "model_index", "power_index")


def _assert_results_identical(host, mega):
    for f in _RESULT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(mega, f)), np.asarray(getattr(host, f)),
            err_msg=f)
    assert mega.horizon == host.horizon
    assert mega.n_rounds == host.n_rounds
    assert mega.pages_in == host.pages_in
    assert mega.pages_out == host.pages_out


def _paging_sessions(table, tick, dl):
    sessions = []
    for sid in range(6):
        tr = _short_trace(ENVS["cpu"] if sid % 2 else ENVS["memory"],
                          40 + sid, 25, deadline_cv=0.1)
        arrivals = (2 * np.arange(25) + (sid % 2)) * tick
        goal = Goal.MINIMIZE_ENERGY if sid % 3 else \
            Goal.MAXIMIZE_ACCURACY
        cons = Constraints(deadline=dl, accuracy_goal=0.8) \
            if sid % 3 else Constraints.from_power_budget(dl, 170.0)
        sessions.append(Session(sid, "t", goal, cons, arrivals, tr))
    return sessions


class TestMegatickGateway:
    def test_bitwise_parity_through_paging(self, table):
        """THE megatick acceptance property: the scanned round clock
        reproduces the fixed host loop bitwise on a workload whose
        sessions page in and out every round — every per-request field,
        the paging counters, the round count, and the horizon."""
        from repro.traffic import MegatickGateway

        dl = float(deadline_range(table, 5)[3])
        tick = dl * 2.5
        sessions = _paging_sessions(table, tick, dl)
        host = SessionGateway(table, 3, tick=tick).run(sessions)
        mega = MegatickGateway(table, 3, tick=tick, chunk=16)
        res = mega.run(sessions)
        assert host.pages_in > 50, "must actually exercise paging"
        _assert_results_identical(host, res)
        assert res.n_compiles == (0, 1)

    def test_overload_parity_and_no_retrace_across_loads(self, table):
        """Backpressure, fail-fast, and same-session deferral all run on
        the megatick's host planner — bitwise-equal dispositions under
        8x overload, for both policies, with ONE compiled scan per
        policy across all load points."""
        from repro.traffic import MegatickGateway

        dl = float(deadline_range(table, 5)[3])
        cons = Constraints(deadline=dl, accuracy_goal=0.78)
        n_lanes, s = 16, 64
        mega = MegatickGateway(table, n_lanes, tick=dl,
                               max_queue=4 * n_lanes, chunk=32)
        for load in (2.0, 8.0):
            rate = load * (n_lanes / dl) / s
            mix = [TenantSpec("minE", Goal.MINIMIZE_ENERGY, cons,
                              PoissonProcess(rate), n_sessions=s,
                              phases=CPU_ENV)]
            sessions = build_sessions(mix, 10 * dl, seed=11)
            host = SessionGateway(table, n_lanes, tick=dl,
                                  max_queue=4 * n_lanes)
            res_h = host.run(sessions, generate_requests(sessions))
            res_m = mega.run(sessions, generate_requests(sessions))
            assert (res_h.status == REJECTED_INFEASIBLE).any() or \
                (res_h.reject_rate > 0), "overload must shed"
            _assert_results_identical(res_h, res_m)
            res_hs = host.run(sessions, generate_requests(sessions),
                              policy="static", static_config=(2, 1))
            res_ms = mega.run(sessions, generate_requests(sessions),
                              policy="static", static_config=(2, 1))
            _assert_results_identical(res_hs, res_ms)
        assert mega.n_compiles() == (0, 2)   # one scan per policy

    def test_lane_mesh_composes_bitwise(self, table):
        """A lane-sharded megatick (select shard_mapped inside the
        scan) returns the same bits as the host loop."""
        from repro.launch.mesh import make_lane_mesh
        from repro.traffic import MegatickGateway

        dl = float(deadline_range(table, 5)[3])
        tick = dl * 2.5
        sessions = _paging_sessions(table, tick, dl)
        host = SessionGateway(table, 3, tick=tick).run(sessions)
        res = MegatickGateway(table, 3, tick=tick,
                              mesh=make_lane_mesh(1), chunk=16
                              ).run(sessions)
        _assert_results_identical(host, res)

    def test_fine_tick_regime_raises(self, table):
        """A tick below the largest relative deadline couples admission
        to in-round latencies — the megatick refuses it instead of
        silently diverging from the host loop."""
        from repro.traffic import MegatickGateway

        dl = float(deadline_range(table, 5)[3])
        tr = _short_trace(ENVS["default"], 2, 4)
        sess = [Session(0, "t", Goal.MINIMIZE_ENERGY,
                        Constraints(deadline=dl, accuracy_goal=0.7),
                        np.arange(4) * dl, tr)]
        mega = MegatickGateway(table, 2, tick=dl / 4)
        with pytest.raises(ValueError, match="SessionGateway"):
            mega.run(sess)

    def test_sweep_megatick_matches_host(self, table):
        """sweep_loads(gateway='megatick') returns records identical to
        the host gateway sweep (identical floats, not approximately)."""
        dl = float(deadline_range(table, 5)[3])
        cons = Constraints(deadline=dl, accuracy_goal=0.78)
        n_lanes = 8
        mix = [TenantSpec("minE", Goal.MINIMIZE_ENERGY, cons,
                          PoissonProcess(2.0 * (n_lanes / dl) / 16),
                          n_sessions=16, phases=CPU_ENV)]
        kw = dict(n_lanes=n_lanes, horizon=8 * dl, seed=3,
                  max_queue=4 * n_lanes, tick=dl)
        host = sweep_loads(table, mix, [0.5, 4.0], **kw)
        mega = sweep_loads(table, mix, [0.5, 4.0], gateway="megatick",
                           **kw)
        for rh, rm in zip(host, mega):
            for scheme in rh["schemes"]:
                sh, sm = rh["schemes"][scheme], rm["schemes"][scheme]
                for key in sh:
                    if key == "n_compiles":
                        assert sm[key] == [0, 1]
                        continue
                    if key == "gateway":
                        assert (sh[key], sm[key]) == \
                            ("host", "megatick")
                        continue
                    assert sh[key] == sm[key], (scheme, key)


class TestGatewayGoldenTrace:
    def test_gateway_matches_checked_in_golden(self, table):
        """Scheme-drift pin for the round loop itself: the seed-1
        overload fixture's dispositions / energy / sojourn percentiles
        match ``golden_traces.json`` exactly — for the host loop AND
        the megatick (one fixture pins both, since the megatick must be
        bitwise-equal)."""
        import json
        import os

        from tests.make_golden_traces import (gateway_config,
                                              summarize_gateway)
        from repro.traffic import MegatickGateway

        path = os.path.join(os.path.dirname(__file__),
                            "golden_traces.json")
        with open(path) as f:
            want = json.load(f)["gateway"]
        sessions, n_lanes, deadline = gateway_config(table)
        for GW in (SessionGateway, MegatickGateway):
            gw = GW(table, n_lanes, tick=deadline, max_queue=4 * n_lanes)
            got = summarize_gateway(gw.run(sessions,
                                           generate_requests(sessions)))
            assert got == want, GW.__name__
