"""Chaos test harness (DESIGN.md §10): fault injection, Kalman-bank
detection, elastic re-meshing, and bit-exact checkpointed resume.

The matrix this module pins, per fault class in
``repro.traffic.faults.FAULT_KINDS``:

* **injection is replayable and neutral-at-zero** — a schedule built
  twice from the same seed replays bit for bit, and an *empty* schedule
  leaves every gateway result bitwise-identical to a no-faults run;
* **detection goes through ALERT's own machinery** — the lane detector
  reads the Eq. 7 posterior (mu, sigma), trips on the pinned straggler
  scenario at the golden latency (``tests/golden_traces.json``), stays
  silent on clean traces, and deliberately does NOT trip on *global*
  drift (DVFS / brownout — the fleet median moves too, and ALERT
  absorbs it through conservative re-selection);
* **response is elastic** — device loss pages the dead lanes' sessions
  out to the host store (the §5 churn protocol: no re-traces), and a
  killed run resumes from an atomic checkpoint bit-exactly, including
  onto a *different* lane mesh (``repro.runtime.elastic``);
* **both round clocks agree under fire** — the megatick scan carries
  the lane-death mask and reproduces the host gateway bitwise under
  every fault class.
"""

import json
import os
import tempfile

import jax
import numpy as np
import pytest

from benchmarks.common import deadline_range, family_table
from repro.checkpoint import io as ckpt_io
from repro.core.controller import Constraints, Goal
from repro.launch.mesh import LANE_AXIS, lane_shardings, make_lane_mesh
from repro.runtime.elastic import (dead_lane_mask, lane_groups,
                                   remesh_lanes, surviving_lane_capacity)
from repro.runtime.ft import InjectedFailure, Supervisor
from repro.runtime.straggler import StragglerMonitor
from repro.serving.sim import CPU_ENV, FleetSim
from repro.traffic import (FAULT_KINDS, Brownout, DeviceLoss, DVFSDrift,
                           FaultSchedule, KalmanLaneDetector,
                           LaneStraggler, MegatickGateway,
                           SessionGateway, generate_requests, scenario)
from tests._hypothesis_compat import given, settings, st
from tests.make_golden_traces import gateway_config, straggler_config

GOLDEN = os.path.join(os.path.dirname(__file__), "golden_traces.json")

#: Every per-request field a GatewayResult carries; "bitwise" below
#: always means all of these via np.array_equal.
FIELDS = ("sid", "index", "arrival", "status", "start", "latency",
          "sojourn", "missed", "accuracy", "energy", "model_index",
          "power_index")


def assert_bitwise(a, b):
    bad = [f for f in FIELDS
           if not np.array_equal(getattr(a, f), getattr(b, f))]
    assert not bad, f"results diverge on {bad}"
    assert a.n_rounds == b.n_rounds
    assert (a.pages_in, a.pages_out) == (b.pages_in, b.pages_out)
    assert a.horizon == b.horizon


@pytest.fixture(scope="module")
def table():
    return family_table("image")


@pytest.fixture(scope="module")
def workload(table):
    """The golden overload workload (24 sessions over 8 lanes) plus a
    no-faults reference run — shared across the module so each bitwise
    comparison pays for one run, not two."""
    sessions, n_lanes, deadline = gateway_config(table)
    gw = SessionGateway(table, n_lanes, tick=deadline,
                        max_queue=4 * n_lanes)
    ref = gw.run(sessions, generate_requests(sessions))
    return sessions, n_lanes, deadline, ref


def _gw(table, n_lanes, deadline, **kw):
    return SessionGateway(table, n_lanes, tick=deadline,
                          max_queue=4 * n_lanes, **kw)


# ------------------------------------------------------------------ #
# the schedule: seeded, replayable, pure                              #
# ------------------------------------------------------------------ #
class TestFaultSchedule:
    def test_replay_identical_int_and_generator_seeds(self):
        """Same seed -> identical perturbation series; a pre-advanced
        Generator threads through like an int seed (the EnvironmentTrace
        seed discipline)."""
        ev = [LaneStraggler(lane=2, start=1.0, magnitude=1.5, ramp_s=3.0),
              DVFSDrift(start=4.0, rate_per_s=0.1),
              Brownout(start=2.0, period=2.0),
              DeviceLoss(at=5.0, lanes=(0, 1))]
        a = FaultSchedule(4, ev, seed=9, jitter_cv=0.3)
        b = FaultSchedule(4, ev, seed=np.random.default_rng(9),
                          jitter_cv=0.3)
        c = FaultSchedule(4, ev, seed=10, jitter_cv=0.3)
        ts = np.linspace(0.0, 12.0, 49)
        for t in ts:
            np.testing.assert_array_equal(a.slow_at(t), b.slow_at(t))
            np.testing.assert_array_equal(a.dead_at(t), b.dead_at(t))
        assert any(not np.array_equal(a.slow_at(t), c.slow_at(t))
                   for t in ts)

    def test_zero_jitter_is_exact(self):
        """jitter_cv=0 draws are exactly 1.0 (scale-0 normal is exactly
        0), so the plateau multiplier is exactly 1 + magnitude."""
        fs = FaultSchedule(4, [LaneStraggler(lane=1, start=2.0,
                                             magnitude=2.0, ramp_s=4.0)])
        f = fs.slow_at(6.0)
        assert f[1] == 3.0
        np.testing.assert_array_equal(f[[0, 2, 3]], np.ones(3))
        # before start and at mid-ramp
        np.testing.assert_array_equal(fs.slow_at(1.9), np.ones(4))
        assert fs.slow_at(4.0)[1] == 2.0

    def test_brownout_duty_and_dvfs_cap(self):
        fs = FaultSchedule(2, [Brownout(start=10.0, period=4.0, duty=0.5,
                                        slowdown=1.5, until=30.0)])
        assert fs.slow_at(11.0)[0] == 1.5      # inside duty window
        assert fs.slow_at(13.0)[0] == 1.0      # outside duty window
        assert fs.slow_at(31.0)[0] == 1.0      # past until
        fd = FaultSchedule(2, [DVFSDrift(start=0.0, rate_per_s=1.0,
                                         cap=1.8)])
        assert fd.slow_at(0.5)[1] == 1.5
        assert fd.slow_at(100.0)[1] == 1.8     # capped

    def test_device_loss_restore_window(self):
        fs = FaultSchedule(6, [DeviceLoss(at=3.0, lanes=(4, 5),
                                          restore_at=7.0)])
        assert not fs.dead_at(2.9).any()
        np.testing.assert_array_equal(
            fs.dead_at(3.0), [False] * 4 + [True] * 2)
        assert not fs.dead_at(7.0).any()
        perm = FaultSchedule(6, [DeviceLoss(at=3.0, lanes=(4,))])
        assert perm.dead_at(1e9)[4]

    def test_lane_bounds_validated(self):
        with pytest.raises(ValueError):
            FaultSchedule(4, [LaneStraggler(lane=4, start=0.0)])
        with pytest.raises(ValueError):
            FaultSchedule(4, [DeviceLoss(at=0.0, lanes=(3, 9))])

    def test_scenario_matrix(self):
        for kind in FAULT_KINDS:
            fs = scenario(kind, 8, start=2.0, horizon=10.0, seed=3,
                          n_devices=4)
            assert fs.has_faults and fs.n_lanes == 8
            # every scenario actually perturbs something in-window
            perturbed = any(
                not np.array_equal(fs.slow_at(t), np.ones(8))
                or fs.dead_at(t).any()
                for t in np.linspace(2.0, 9.9, 40))
            assert perturbed, kind
        assert not FaultSchedule(8).has_faults
        with pytest.raises(ValueError):
            scenario("meteor_strike", 8, start=0.0, horizon=1.0)


# ------------------------------------------------------------------ #
# gateway under fire: neutrality, quarantine, kill/resume             #
# ------------------------------------------------------------------ #
class TestGatewayFaults:
    def test_empty_schedule_is_bitwise_neutral(self, table, workload):
        sessions, n_lanes, deadline, ref = workload
        gw = _gw(table, n_lanes, deadline)
        res = gw.run(sessions, generate_requests(sessions),
                     faults=FaultSchedule(n_lanes))
        assert_bitwise(ref, res)

    def test_lane_count_mismatch_raises(self, table, workload):
        sessions, n_lanes, deadline, _ = workload
        gw = _gw(table, n_lanes, deadline)
        with pytest.raises(ValueError, match="lanes"):
            gw.run(sessions, generate_requests(sessions),
                   faults=FaultSchedule(n_lanes + 1))

    def test_device_loss_quarantines_without_retrace(self, table,
                                                     workload):
        """Losing a device's lane group mid-run pages its residents out
        (their state survives to re-admit on survivors), perturbs the
        trajectory, and never re-traces the engine — the §5 churn
        protocol under §10 faults."""
        sessions, n_lanes, deadline, ref = workload
        fs = scenario("device_loss", n_lanes, start=4 * deadline,
                      horizon=12 * deadline, n_devices=4)
        gw = _gw(table, n_lanes, deadline)
        res = gw.run(sessions, generate_requests(sessions), faults=fs)
        assert res.n_compiles == (0, 1)
        assert int(res.served.sum()) > 0
        # the loss is permanent, so the gateway ends with exactly the
        # lost device's lane group quarantined
        np.testing.assert_array_equal(gw._dead,
                                      dead_lane_mask(n_lanes, 4, [3]))
        # and the shrunken capacity visibly perturbs the trajectory
        assert not np.array_equal(ref.status, res.status) or \
            (res.pages_in, res.pages_out) != (ref.pages_in,
                                              ref.pages_out)

    def test_kill_resume_is_bitwise(self, table, workload, tmp_path):
        """THE checkpoint acceptance property: a run killed mid-sweep
        (InjectedFailure at iteration 7, snapshots every 3) resumes from
        the atomic checkpoint and finishes indistinguishable from the
        uninterrupted run — every per-request field, the round count,
        the paging counters, and the compile count."""
        sessions, n_lanes, deadline, ref = workload
        ck = str(tmp_path / "ck")
        gw = _gw(table, n_lanes, deadline)
        with pytest.raises(InjectedFailure):
            gw.run(sessions, generate_requests(sessions),
                   checkpoint_dir=ck, checkpoint_every=3,
                   kill_at_round=7)
        assert ckpt_io.latest_step(ck) == 6
        gw2 = _gw(table, n_lanes, deadline)
        res = gw2.resume(sessions, generate_requests(sessions),
                         checkpoint_dir=ck)
        assert_bitwise(ref, res)
        assert res.n_compiles == (0, 1)

    def test_kill_resume_across_mesh_change(self, table, workload,
                                            tmp_path):
        """Elastic restore: the checkpoint written by a mesh-less
        gateway resumes on a gateway built over a lane mesh — bank
        state is resharded onto the new mesh
        (repro.runtime.elastic.reshard_state) and the trajectory stays
        bitwise."""
        sessions, n_lanes, deadline, ref = workload
        ck = str(tmp_path / "ck")
        gw = _gw(table, n_lanes, deadline)
        with pytest.raises(InjectedFailure):
            gw.run(sessions, generate_requests(sessions),
                   checkpoint_dir=ck, checkpoint_every=4,
                   kill_at_round=9)
        mesh = make_lane_mesh()
        gw2 = _gw(table, n_lanes, deadline, mesh=mesh)
        res = gw2.resume(sessions, generate_requests(sessions),
                         checkpoint_dir=ck)
        assert_bitwise(ref, res)

    def test_kill_resume_under_faults(self, table, workload, tmp_path):
        """Kill/resume composes with an active fault schedule: the
        resumed run replays the same seeded perturbations and still
        matches the uninterrupted faulted run bitwise."""
        sessions, n_lanes, deadline, _ = workload
        fs = scenario("brownout", n_lanes, start=3 * deadline,
                      horizon=12 * deadline, seed=11)
        gw = _gw(table, n_lanes, deadline)
        ref = gw.run(sessions, generate_requests(sessions), faults=fs)
        ck = str(tmp_path / "ck")
        gw2 = _gw(table, n_lanes, deadline)
        with pytest.raises(InjectedFailure):
            gw2.run(sessions, generate_requests(sessions), faults=fs,
                    checkpoint_dir=ck, checkpoint_every=3,
                    kill_at_round=6)
        gw3 = _gw(table, n_lanes, deadline)
        res = gw3.resume(sessions, generate_requests(sessions),
                         checkpoint_dir=ck, faults=fs)
        assert_bitwise(ref, res)

    def test_kill_resume_bitwise_under_heavy_paging(self, table,
                                                    tmp_path):
        """40 sessions over 4 lanes at a tick of a quarter deadline, so
        lanes stay busy across rounds and nearly every round pages: a
        run killed mid-horizon resumes from the checkpoint of its
        session store's columns and ends bitwise equal to the
        uninterrupted run, result and final state (banks, residents,
        the store's every entry).  The checkpoint keeps the store's
        on-disk keys."""
        from repro.traffic import PoissonProcess, TenantSpec, build_sessions
        from repro.serving.sim import MEMORY_ENV

        deadline = float(deadline_range(table, 5)[3])
        n_lanes, per_tenant = 4, 20
        rate = 0.9 * (n_lanes / deadline) / (2 * per_tenant)
        sessions = build_sessions([
            TenantSpec("minE", Goal.MINIMIZE_ENERGY,
                       Constraints(deadline=deadline, accuracy_goal=0.78),
                       PoissonProcess(rate), n_sessions=per_tenant,
                       phases=CPU_ENV),
            TenantSpec("maxA", Goal.MAXIMIZE_ACCURACY,
                       Constraints.from_power_budget(deadline, 170.0),
                       PoissonProcess(rate), n_sessions=per_tenant,
                       phases=MEMORY_ENV)], 40 * deadline, seed=5)

        def gateway():
            return SessionGateway(table, n_lanes, tick=deadline / 4,
                                  max_queue=4 * n_lanes)

        def final_state(gw):
            lanes = np.arange(n_lanes)
            return (gw._resident.copy(),
                    {part: bank.export_lanes(lanes) for part, bank in
                     (("slow", gw.slow), ("idle", gw.idle),
                      ("goal", gw.goal_bank))},
                    dict(gw._store.items()))

        gw = gateway()
        ref = gw.run(sessions, generate_requests(sessions))
        want = final_state(gw)
        assert ref.pages_in > 50 and ref.pages_out > 80
        ck = str(tmp_path / "ck")
        with pytest.raises(InjectedFailure):
            gateway().run(sessions, generate_requests(sessions),
                          checkpoint_dir=ck, checkpoint_every=5,
                          kill_at_round=ref.n_rounds // 2)
        tree, _ = ckpt_io.restore_tree(ck)
        assert set(tree["store"]) == {
            "sids", "slow.mu", "slow.sigma", "slow.gain",
            "slow.process_noise", "slow.n_updates", "idle.phi",
            "idle.variance", "idle.n_updates", "goal.goal", "goal.buf",
            "goal.count", "goal.pos"}
        sids = tree["store"]["sids"]
        assert sids.size and np.all(np.diff(sids) > 0)
        assert tree["store"]["goal.buf"].shape == (sids.size, 9)
        gw2 = gateway()
        res = gw2.resume(sessions, generate_requests(sessions),
                         checkpoint_dir=ck)
        assert_bitwise(ref, res)
        got = final_state(gw2)
        np.testing.assert_array_equal(got[0], want[0])
        for part, fields in want[1].items():
            for name, v in fields.items():
                np.testing.assert_array_equal(got[1][part][name], v,
                                              err_msg=f"{part}.{name}")
        assert got[2].keys() == want[2].keys()
        for sid, entry in want[2].items():
            for part, fields in entry.items():
                for name, v in fields.items():
                    np.testing.assert_array_equal(
                        got[2][sid][part][name], v,
                        err_msg=f"sid {sid} {part}.{name}")

    def test_resume_rejects_different_workload(self, table, workload,
                                               tmp_path):
        sessions, n_lanes, deadline, _ = workload
        ck = str(tmp_path / "ck")
        gw = _gw(table, n_lanes, deadline)
        with pytest.raises(InjectedFailure):
            gw.run(sessions, generate_requests(sessions),
                   checkpoint_dir=ck, checkpoint_every=3,
                   kill_at_round=7)
        gw2 = _gw(table, n_lanes, deadline)
        with pytest.raises(ValueError, match="identical workload"):
            gw2.resume(sessions, generate_requests(sessions)[:-5],
                       checkpoint_dir=ck)


# ------------------------------------------------------------------ #
# detection: ALERT's Eq. 7 posterior as the straggler sensor          #
# ------------------------------------------------------------------ #
class TestDetection:
    @pytest.fixture(scope="class")
    def straggler_run(self, table):
        sessions, n_lanes, deadline, faults = straggler_config(table)
        det = KalmanLaneDetector(n_lanes)
        gw = SessionGateway(table, n_lanes, tick=deadline)
        res = gw.run(sessions, generate_requests(sessions),
                     faults=faults, detector=det)
        return sessions, n_lanes, deadline, res, det

    def test_straggler_trips_at_golden_latency(self, straggler_run):
        """The pinned straggler scenario reproduces the golden
        detection trace exactly: only the faulted lane trips, at the
        recorded first-trip time and round latency."""
        _, n_lanes, deadline, _, det = straggler_run
        with open(GOLDEN) as f:
            g = json.load(f)["straggler"]
        assert [int(x) for x in np.nonzero(det.tripped)[0]] == \
            g["tripped_lanes"]
        lane = g["fault_lane"]
        assert float(det.first_trip_time[lane]) == \
            g["first_trip_time_s"]
        start = g["fault_start_rounds"] * deadline
        assert det.detection_latency(lane, start) / deadline == \
            g["detection_latency_rounds"]
        assert det.recommendation(lane) == "reshard"

    def test_detector_is_pure_observer(self, table, straggler_run):
        """Attaching a detector never perturbs selection: the faulted
        run with and without a detector is bitwise-identical."""
        sessions, n_lanes, deadline, res, _ = straggler_run
        _, _, _, faults = straggler_config(table)
        gw = SessionGateway(table, n_lanes, tick=deadline)
        res2 = gw.run(sessions, generate_requests(sessions),
                      faults=faults)
        assert_bitwise(res, res2)

    def test_clean_trace_has_zero_false_positives(self, table,
                                                  straggler_run):
        sessions, n_lanes, deadline, _, _ = straggler_run
        with open(GOLDEN) as f:
            g = json.load(f)["straggler"]
        det = KalmanLaneDetector(n_lanes)
        gw = SessionGateway(table, n_lanes, tick=deadline)
        gw.run(sessions, generate_requests(sessions), detector=det)
        assert int(det.tripped.sum()) == g["clean_false_positives"] == 0
        assert det.recommendation(0) == "tolerate"
        assert np.isnan(det.detection_latency(0, 0.0))

    def test_global_dvfs_drift_does_not_trip(self, table,
                                             straggler_run):
        """Global drift moves every lane's mu together — the fleet
        median rises with it, so no lane is a *relative* straggler and
        the detector stays silent while ALERT visibly reacts (mean mu
        well above nominal)."""
        sessions, n_lanes, deadline, _, _ = straggler_run
        fs = scenario("dvfs_drift", n_lanes, start=5 * deadline,
                      horizon=40 * deadline, magnitude=1.0)
        det = KalmanLaneDetector(n_lanes)
        gw = SessionGateway(table, n_lanes, tick=deadline)
        gw.run(sessions, generate_requests(sessions), faults=fs,
               detector=det)
        assert int(det.tripped.sum()) == 0
        assert float(np.asarray(gw.slow.mu).mean()) > 1.5

    def test_straggler_monitor_detects_and_escalates(self):
        """The training-side twin (StragglerMonitor on step-time
        ratios): a host running 3x slow flags within a handful of
        steps and escalates to "reshard" after persistent_after; the
        healthy hosts never flag."""
        mon = StragglerMonitor(4, persistent_after=3)
        for _ in range(5):                    # healthy warm-up
            assert mon.observe([1.0, 1.0, 1.0, 1.0]) == []
        first_flag = None
        for k in range(10):
            flagged = mon.observe([1.0, 1.0, 3.0, 1.0])
            if flagged and first_flag is None:
                first_flag = k
                assert flagged == [2]
        assert first_flag is not None and first_flag <= 5
        assert mon.recommendation(2) == "reshard"
        assert all(mon.recommendation(h) == "tolerate"
                   for h in (0, 1, 3))


# ------------------------------------------------------------------ #
# megatick parity under fire (ROADMAP 1c: scan carries death mask)    #
# ------------------------------------------------------------------ #
class TestMegatickFaultParity:
    def test_all_fault_kinds_bitwise(self, table, workload):
        """THE fault-parity acceptance property: for every fault class,
        the device-resident round clock (planner evaluates the schedule
        at identical round instants; the scan carries the lane-death
        mask) reproduces the host gateway bitwise."""
        sessions, n_lanes, deadline, _ = workload
        gw = _gw(table, n_lanes, deadline)
        mega = MegatickGateway(table, n_lanes, tick=deadline,
                               max_queue=4 * n_lanes, chunk=8)
        for kind in FAULT_KINDS:
            fs = scenario(kind, n_lanes, start=3 * deadline,
                          horizon=12 * deadline, seed=11, n_devices=4)
            rh = gw.run(sessions, generate_requests(sessions),
                        faults=fs)
            rm = mega.run(sessions, generate_requests(sessions),
                          faults=fs)
            bad = [f for f in FIELDS
                   if not np.array_equal(getattr(rh, f),
                                         getattr(rm, f))]
            assert not bad, f"{kind}: diverges on {bad}"
            assert (rh.n_rounds, rh.pages_in, rh.pages_out) == \
                (rm.n_rounds, rm.pages_in, rm.pages_out), kind

    def test_megatick_validates_lane_count(self, table, workload):
        sessions, n_lanes, deadline, _ = workload
        mega = MegatickGateway(table, n_lanes, tick=deadline,
                               max_queue=4 * n_lanes)
        with pytest.raises(ValueError, match="lanes"):
            mega.run(sessions, generate_requests(sessions),
                     faults=FaultSchedule(n_lanes + 1))


# ------------------------------------------------------------------ #
# lockstep fleet: faults through FleetSim                             #
# ------------------------------------------------------------------ #
class TestFleetSimFaults:
    def test_empty_schedule_neutral_and_loss_window_misses(self, table):
        deadline = float(deadline_range(table, 3)[1])
        cons = Constraints(deadline=deadline, accuracy_goal=0.78)
        s = 12
        clean = FleetSim.from_phases(table, CPU_ENV, s, seed=5) \
            .run_alert(Goal.MINIMIZE_ENERGY, cons)
        empty = FleetSim.from_phases(table, CPU_ENV, s, seed=5) \
            .run_alert(Goal.MINIMIZE_ENERGY, cons,
                       faults=FaultSchedule(s))
        np.testing.assert_array_equal(clean.energy, empty.energy)
        np.testing.assert_array_equal(clean.missed, empty.missed)
        # Losing streams 9-11 for ticks [5, 12) costs exactly 3 lanes x
        # 7 ticks of missed inputs (a lost in-flight input is a miss —
        # the intermittent-power semantics); after restore the tail
        # matches the clean run again.
        fs = FaultSchedule(s, [DeviceLoss(at=5.0, lanes=(9, 10, 11),
                                          restore_at=12.0)])
        loss = FleetSim.from_phases(table, CPU_ENV, s, seed=5) \
            .run_alert(Goal.MINIMIZE_ENERGY, cons, faults=fs)
        assert int(loss.missed[9:, 5:12].sum()) == 3 * 7
        assert int(loss.missed[:9, 5:12].sum()) == \
            int(clean.missed[:9, 5:12].sum())

    def test_lane_count_mismatch_raises(self, table):
        deadline = float(deadline_range(table, 3)[1])
        fleet = FleetSim.from_phases(table, CPU_ENV, 4, seed=5)
        with pytest.raises(ValueError, match="lanes|streams"):
            fleet.run_alert(
                Goal.MINIMIZE_ENERGY,
                Constraints(deadline=deadline, accuracy_goal=0.78),
                faults=FaultSchedule(5))


# ------------------------------------------------------------------ #
# quarantine on the serve-path fleet server                           #
# ------------------------------------------------------------------ #
class TestFleetServerQuarantine:
    @pytest.fixture(scope="class")
    def server(self):
        from repro.configs.base import ModelConfig
        from repro.models.registry import build_model
        from repro.serving.alert_server import FleetAlertServer
        from repro.serving.engine import ServeEngine

        cfg = ModelConfig(name="t", family="dense", n_layers=2,
                          d_model=32, n_heads=4, n_kv_heads=4,
                          head_dim=8, d_ff=64, vocab=64, nest_levels=2,
                          dtype="float32", attn_chunk=32)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        engine = ServeEngine(model, max_len=32, batch_size=2)
        return FleetAlertServer(engine, params,
                                level_accuracies=[0.6, 0.9],
                                goal=Goal.MAXIMIZE_ACCURACY,
                                n_streams=4, profile_iters=1,
                                gen_tokens=3)

    def test_fail_lanes_never_leased_until_revived(self, server):
        """fail_lanes quarantines a device's lane group: the lanes stop
        serving, admit() skips them (re-rounding capacity to the
        survivors without growing), and revive_lanes returns them to
        the pool."""
        srv = server
        dead = np.nonzero(dead_lane_mask(4, 2, [1]))[0]   # lanes 2, 3
        srv.fail_lanes(dead)
        assert not srv.active[dead].any()
        # retire a survivor, then admit twice: both leases must land on
        # surviving lanes, never the quarantined ones
        srv.retire(0)
        srv.retire(1)
        lanes = [srv.admit(), srv.admit()]
        assert set(lanes) == {0, 1}
        # pool exhausted (survivors busy, dead quarantined): the next
        # admit grows capacity rather than leasing a dead lane
        n0 = srv.n_streams
        lane = srv.admit()
        assert lane >= n0 and srv.n_streams > n0
        assert not srv.active[dead].any()
        srv.revive_lanes(dead)
        srv.retire(lane)
        assert srv.admit() in set(int(x) for x in dead)


# ------------------------------------------------------------------ #
# training-side supervisor: restart correctness                       #
# ------------------------------------------------------------------ #
class TestSupervisor:
    @staticmethod
    def _sup(ckpt_dir, **kw):
        # float32 state/batches: the training dtype, and the dtype the
        # restore path preserves under default (x64-off) jax config —
        # which is exactly the config the supervisor runs under.
        def train_step(state, batch):
            w = state["w"] + batch
            return {"w": w, "m": state["m"] * np.float32(0.9)
                    + np.float32(0.1) * batch}, {"sum": float(w.sum())}

        def batch_at(step):
            return np.full(3, step + 1, dtype=np.float32)

        return Supervisor(train_step=train_step, batch_at=batch_at,
                          ckpt_dir=ckpt_dir, **kw)

    @staticmethod
    def _state():
        return {"w": np.zeros(3, np.float32), "m": np.ones(3, np.float32)}

    def test_crash_before_first_checkpoint_restarts_from_entry(
            self, tmp_path):
        """A crash BEFORE any checkpoint exists must restart from the
        state run() entered with — not the mutated in-flight state —
        and converge to the uninterrupted run bit-exactly."""
        ref, step_ref = self._sup(str(tmp_path / "a"), ckpt_every=50) \
            .run(self._state(), 0, 10)
        got, step = self._sup(str(tmp_path / "b"), ckpt_every=50) \
            .run(self._state(), 0, 10, fail_at=4)
        assert step == step_ref == 10
        for k in ("w", "m"):
            np.testing.assert_array_equal(np.asarray(ref[k]),
                                          np.asarray(got[k]))

    def test_crash_after_checkpoint_resumes_bit_exact(self, tmp_path):
        ref, _ = self._sup(str(tmp_path / "a"), ckpt_every=3) \
            .run(self._state(), 0, 12)
        got, step = self._sup(str(tmp_path / "b"), ckpt_every=3) \
            .run(self._state(), 0, 12, fail_at=8)
        assert step == 12
        for k in ("w", "m"):
            np.testing.assert_array_equal(np.asarray(ref[k]),
                                          np.asarray(got[k]))

    def test_max_restarts_exceeded_reraises(self, tmp_path):
        sup = self._sup(str(tmp_path / "c"), ckpt_every=50,
                        max_restarts=0)
        with pytest.raises(InjectedFailure):
            sup.run(self._state(), 0, 10, fail_at=2)


# ------------------------------------------------------------------ #
# elastic lane helpers                                                #
# ------------------------------------------------------------------ #
class TestElasticLanes:
    def test_lane_groups_and_dead_mask(self):
        np.testing.assert_array_equal(lane_groups(8, 4),
                                      [0, 0, 1, 1, 2, 2, 3, 3])
        np.testing.assert_array_equal(
            dead_lane_mask(8, 4, [3]),
            [False] * 6 + [True] * 2)
        np.testing.assert_array_equal(
            dead_lane_mask(8, 4, [0, 2]),
            [True, True, False, False, True, True, False, False])
        with pytest.raises(ValueError, match="divisible"):
            lane_groups(10, 4)

    def test_surviving_capacity(self):
        assert surviving_lane_capacity(8, 4, 1) == 6
        assert surviving_lane_capacity(8, 4, 4) == 0

    def test_remesh_lanes_builds_1d_lane_mesh(self):
        mesh = remesh_lanes()
        assert mesh.axis_names == (LANE_AXIS,)
        assert mesh.size == len(jax.devices())


# ------------------------------------------------------------------ #
# checkpoint io: atomicity + round-trip properties                    #
# ------------------------------------------------------------------ #
class TestCheckpointIO:
    def test_roundtrip_nested_mixed_dtypes(self, tmp_path):
        tree = {"a": {"b": np.arange(6, dtype=np.int64),
                      "c": np.linspace(0, 1, 5)},
                "d": np.array([True, False, True]),
                "e": np.float32(3.25),
                "f": np.zeros((0, 4))}          # empty leaf survives
        d = str(tmp_path / "ck")
        ckpt_io.save(d, tree, step=7, extra={"tag": "x"})
        # restore returns jax arrays; x64 scoped on, the repo
        # discipline, so f64 leaves round-trip without downcast
        from repro.core.precision import x64_scope
        with x64_scope():
            got, step = ckpt_io.restore(d, tree)
        assert step == 7
        flat_a = jax.tree_util.tree_leaves(tree)
        flat_b = jax.tree_util.tree_leaves(got)
        assert len(flat_a) == len(flat_b)
        for va, vb in zip(flat_a, flat_b):
            np.testing.assert_array_equal(np.asarray(va),
                                          np.asarray(vb))
        assert ckpt_io.load_manifest(d)["extra"] == {"tag": "x"}
        assert ckpt_io.latest_step(d) == 7

    def test_restore_tree_rebuilds_without_like(self, tmp_path):
        tree = {"meta": {"x": np.int64(3)},
                "bank": {"mu": np.linspace(1, 2, 4)}}
        d = str(tmp_path / "ck")
        ckpt_io.save(d, tree, step=2)
        got, step = ckpt_io.restore_tree(d)
        assert step == 2
        assert got["meta"]["x"] == 3
        np.testing.assert_array_equal(got["bank"]["mu"],
                                      tree["bank"]["mu"])

    def test_empty_tree_roundtrip(self, tmp_path):
        d = str(tmp_path / "ck")
        ckpt_io.save(d, {}, step=1)
        got, step = ckpt_io.restore_tree(d)
        assert got == {} and step == 1

    def test_latest_step_none_when_missing(self, tmp_path):
        assert ckpt_io.latest_step(str(tmp_path / "nope")) is None

    def test_overwrite_leaves_no_debris(self, tmp_path):
        d = str(tmp_path / "ck")
        ckpt_io.save(d, {"w": np.zeros(2)}, step=1)
        ckpt_io.save(d, {"w": np.ones(2)}, step=2)
        assert ckpt_io.latest_step(d) == 2
        assert not os.path.exists(d + ".tmp")
        assert not os.path.exists(d + ".old")
        got, _ = ckpt_io.restore(d, {"w": np.zeros(2)})
        np.testing.assert_array_equal(np.asarray(got["w"]), np.ones(2))

    def test_torn_write_falls_back_to_old(self, tmp_path):
        """Regression for the rmtree-before-replace torn-write window:
        a crash between parking the live checkpoint at .old and
        promoting the new one must leave the OLD checkpoint findable,
        and the next save must recover."""
        d = str(tmp_path / "ck")
        ckpt_io.save(d, {"w": np.full(2, 5.0)}, step=5)
        # simulate the crash window: live checkpoint parked, promote
        # never happened
        os.replace(d, d + ".old")
        assert ckpt_io.latest_step(d) == 5
        got, step = ckpt_io.restore(d, {"w": np.zeros(2)})
        assert step == 5
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.full(2, 5.0))
        # the next save promotes cleanly over the torn state
        ckpt_io.save(d, {"w": np.full(2, 6.0)}, step=6)
        assert ckpt_io.latest_step(d) == 6
        assert not os.path.exists(d + ".old")

    def test_restore_with_lane_mesh_shardings(self, tmp_path):
        """Elastic restore at the io level: a host-written checkpoint
        restores onto a lane mesh via explicit shardings, values
        bitwise."""
        mesh = make_lane_mesh()
        sharded, _ = lane_shardings(mesh)
        tree = {"mu": np.linspace(1, 3, 8), "sigma": np.ones(8)}
        d = str(tmp_path / "ck")
        ckpt_io.save(d, tree, step=4)
        from repro.core.precision import x64_scope
        with x64_scope():
            got, step = ckpt_io.restore(
                d, tree, shardings={"mu": sharded, "sigma": sharded})
        assert step == 4
        for k in tree:
            np.testing.assert_array_equal(np.asarray(got[k]), tree[k])
            assert got[k].sharding == sharded

    @settings(max_examples=25, deadline=None)
    @given(vals=st.lists(st.floats(allow_nan=False,
                                   allow_infinity=False, width=64),
                         min_size=0, max_size=12),
           dtype=st.sampled_from(["float64", "float32", "int64",
                                  "bool"]),
           step=st.integers(0, 10 ** 9),
           nest=st.booleans())
    def test_roundtrip_property(self, vals, dtype, step, nest):
        """Property: save/restore is the identity on any pytree of
        arrays — every dtype, any shape (including length 0), any
        nesting, any step — and restore_tree agrees with restore."""
        arr = np.asarray(vals, dtype=np.float64).astype(dtype)
        tree = {"x": {"y": arr}} if nest else {"x": arr}
        with tempfile.TemporaryDirectory() as td:
            d = os.path.join(td, "ck")
            ckpt_io.save(d, tree, step=step)
            got, s1 = ckpt_io.restore(d, tree)
            raw, s2 = ckpt_io.restore_tree(d)
            assert s1 == s2 == step
            leaf = got["x"]["y"] if nest else got["x"]
            rleaf = raw["x"]["y"] if nest else raw["x"]
            np.testing.assert_array_equal(np.asarray(leaf), arr)
            np.testing.assert_array_equal(rleaf, arr)
            assert rleaf.dtype == arr.dtype
