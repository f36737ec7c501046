"""Lane-sharded decision path (DESIGN.md §6).

Two tiers of coverage:

* **1-device mesh, in-process** — a `make_lane_mesh()` over the single
  test-process CPU device exercises the whole mesh code path (sharded jit,
  device-resident donated banks, lane padding) cheaply inside tier-1.
* **8-fake-device mesh, subprocess** — real SPMD partitioning needs
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before* jax
  imports, hence the isolation (same pattern as ``tests/test_distributed``):
  lane-by-lane bitwise pick parity at S=1024, churn-no-retrace under
  sharding, and the sharded FleetSim reproducing the checked-in golden
  traces.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_subprocess(code: str) -> str:
    """Run ``code`` with 8 fake host devices; return its stdout."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([SRC, ROOT]),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _mesh1():
    from repro.launch.mesh import make_lane_mesh
    return make_lane_mesh(1)


class TestLaneMeshInProcess:
    """Mesh-mode plumbing on the 1-device mesh (cheap tier-1 coverage)."""

    def test_engine_mesh_mode_matches_host(self):
        from benchmarks.common import family_table, deadline_range
        from repro.core.batched import BatchedAlertEngine

        table = family_table("image")
        rng = np.random.default_rng(0)
        s = 64
        mus, sds, phis = (rng.uniform(0.6, 2.5, s),
                          rng.uniform(0.01, 0.4, s),
                          rng.uniform(0.05, 0.6, s))
        d = rng.choice(deadline_range(table, 5), s)
        qg = rng.uniform(0.5, 0.9, s)
        eg = rng.uniform(0.5, 3.0, s) * float(
            np.median(table.run_power) * np.median(table.latency))
        gk = rng.integers(0, 2, s)
        act = rng.random(s) < 0.9
        host = BatchedAlertEngine(table, None)
        mesh = BatchedAlertEngine(table, None, mesh=_mesh1())
        a = host.select(mus, sds, phis, d, accuracy_goal=qg,
                        energy_goal=eg, goal_kind=gk, active=act)
        b = mesh.select(mus, sds, phis, d, accuracy_goal=qg,
                        energy_goal=eg, goal_kind=gk, active=act)
        for f in ("model_index", "power_index", "predicted_latency",
                  "predicted_accuracy", "predicted_energy", "feasible",
                  "relaxed_code"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f), f)

    def test_pallas_backend_composes_with_lane_mesh(self):
        """`backend="pallas"` under a lane mesh (shard_map: one kernel
        launch per device on its lane shard) — picks equal to the
        unsharded XLA engine on every lane that clears the tie margins,
        churn never re-traces."""
        from benchmarks.common import family_table, deadline_range
        from repro.core.batched import BatchedAlertEngine

        table = family_table("image")
        rng = np.random.default_rng(3)
        s = 48
        mus, sds, phis = (rng.uniform(0.6, 2.5, s),
                          rng.uniform(0.01, 0.4, s),
                          rng.uniform(0.05, 0.6, s))
        d = rng.choice(deadline_range(table, 5), s)
        qg = rng.uniform(0.5, 0.9, s)
        eg = rng.uniform(0.5, 3.0, s) * float(
            np.median(table.run_power) * np.median(table.latency))
        gk = rng.integers(0, 2, s)
        act = rng.random(s) < 0.9
        from repro.kernels.alert_select import clear_lanes, margin_report

        host = BatchedAlertEngine(table, None)
        pal = BatchedAlertEngine(table, None, mesh=_mesh1(),
                                 backend="pallas")
        kw = dict(accuracy_goal=qg, energy_goal=eg)
        a = host.select(mus, sds, phis, d, goal_kind=gk, active=act, **kw)
        b = pal.select(mus, sds, phis, d, goal_kind=gk, active=act, **kw)
        est = host.estimate(mus, sds, phis, d, active=act)
        rep = margin_report(a, b, clear_lanes(est.accuracy, est.energy,
                                              qg, eg, gk, act))
        assert rep["mismatches"] == 0 and rep["pred_ok"], rep
        assert rep["n_clear"] >= s // 2, rep
        n0 = pal.n_compiles()
        for _ in range(4):
            act[rng.integers(0, s)] ^= True
            gk = np.where(rng.random(s) < 0.3, 1 - gk, gk)
            pal.select(mus, sds, phis, d, goal_kind=gk, active=act, **kw)
        assert pal.n_compiles() == n0, "sharded pallas churn re-traced"

    def test_engine_as_arrays_returns_jax(self):
        import jax
        from benchmarks.common import family_table, deadline_range
        from repro.core.batched import BatchedAlertEngine

        table = family_table("image")
        mesh = _mesh1()
        e = BatchedAlertEngine(table, None, mesh=mesh)
        s = 8
        d = np.full(s, float(deadline_range(table, 3)[1]))
        b = e.select(np.ones(s), np.full(s, 0.1), np.full(s, 0.25), d,
                     accuracy_goal=np.full(s, 0.8),
                     goal_kind=np.zeros(s, np.int64),
                     active=np.ones(s, bool), as_arrays=True)
        assert isinstance(b.model_index, jax.Array)
        assert b.model_index.sharding.mesh.size == mesh.size

    def test_mesh_divisibility_error(self):
        from benchmarks.common import family_table
        from repro.core.batched import BatchedAlertEngine
        from repro.launch.mesh import make_lane_mesh

        # a 1-device mesh divides everything; fake the constraint via a
        # bank instead, then check the engine error message path directly
        table = family_table("image")
        e = BatchedAlertEngine(table, None, mesh=_mesh1())
        e.mesh = type("M", (), {"size": 8})()  # S % 8 != 0 must raise
        with pytest.raises(ValueError, match="divisible"):
            e.select(np.ones(3), np.ones(3), np.ones(3), np.ones(3),
                     accuracy_goal=np.ones(3),
                     goal_kind=np.zeros(3, np.int64),
                     active=np.ones(3, bool))

    def test_sharded_banks_match_host_banks(self):
        import jax
        from repro.core.kalman import (IdlePowerFilterBank,
                                       SlowdownFilterBank, observe_fleet)

        mesh = _mesh1()
        s = 32
        rng = np.random.default_rng(1)
        h_s, h_i = SlowdownFilterBank(s), IdlePowerFilterBank(s)
        d_s = SlowdownFilterBank(s, mesh=mesh)
        d_i = IdlePowerFilterBank(s, mesh=mesh)
        assert isinstance(d_s.mu, jax.Array)
        assert d_s.mu.dtype == np.float64
        for t in range(6):
            obs = rng.uniform(0.01, 1.0, s)
            prof = rng.uniform(0.01, 1.0, s)
            miss = rng.random(s) < 0.2
            m = rng.random(s) < 0.9
            ip, ap = rng.uniform(10, 50, s), rng.uniform(60, 200, s)
            for slow, idle in ((h_s, h_i), (d_s, d_i)):
                observe_fleet(slow, idle, obs, prof, deadline_missed=miss,
                              idle_power=ip, active_power=ap, mask=m)
            if t == 3:
                h_s.reset_lanes([2, 5])
                d_s.reset_lanes([2, 5])
        for name in ("mu", "sigma", "gain", "process_noise", "n_updates"):
            np.testing.assert_array_equal(np.asarray(getattr(d_s, name)),
                                          getattr(h_s, name), name)
        for name in ("phi", "variance"):
            np.testing.assert_array_equal(np.asarray(getattr(d_i, name)),
                                          getattr(h_i, name), name)

    def test_sharded_goal_bank_matches_host(self):
        from repro.core.batched import WindowedGoalBank

        mesh = _mesh1()
        s = 16
        rng = np.random.default_rng(2)
        h = WindowedGoalBank(0.8, s, window=5)
        d = WindowedGoalBank(0.8, s, window=5, mesh=mesh)
        for t in range(9):
            acc = rng.uniform(0.4, 1.0, s)
            m = rng.random(s) < 0.85
            h.record(acc, mask=m)
            d.record(acc, mask=m)
            if t == 3:
                h.reset_lanes([1, 4], goal=[0.9, 0.6])
                d.reset_lanes([1, 4], goal=[0.9, 0.6])
            np.testing.assert_allclose(np.asarray(d.current_goal()),
                                       h.current_goal(), rtol=0,
                                       atol=1e-12)
        # window *contents* are bitwise (only the reduce may differ)
        np.testing.assert_array_equal(np.asarray(d._buf), h._buf)
        np.testing.assert_array_equal(np.asarray(d._pos), h._pos)

    def test_fleetsim_mesh_bitwise_and_bank_capacity_error(self):
        from benchmarks.common import family_table, deadline_range
        from repro.core.controller import Constraints, Goal
        from repro.core.kalman import SlowdownFilterBank
        from repro.serving.sim import (EnvironmentTrace, Phase, StreamSpec,
                                       run_fleet)

        table = family_table("image")
        dl = float(deadline_range(table, 3)[1])
        specs = []
        for s in range(3):
            tr = EnvironmentTrace((Phase(25), Phase(25, slowdown=1.5)),
                                  seed=40 + s, deadline_cv=0.1)
            goal, cons = (
                (Goal.MINIMIZE_ENERGY,
                 Constraints(deadline=dl, accuracy_goal=0.8))
                if s % 2 else
                (Goal.MAXIMIZE_ACCURACY,
                 Constraints.from_power_budget(dl, 170.0)))
            specs.append(StreamSpec(trace=tr, goal=goal, constraints=cons,
                                    arrival=5 * s))
        r_host = run_fleet(table, specs)
        r_mesh = run_fleet(table, specs, mesh=_mesh1())
        for f in ("energy", "accuracy", "latency", "missed"):
            np.testing.assert_array_equal(getattr(r_host, f),
                                          getattr(r_mesh, f), f)
        # bank capacity must respect the mesh multiple
        big = type("M", (), {"size": 8, "axis_names": ("lanes",)})()
        with pytest.raises(ValueError, match="multiple"):
            SlowdownFilterBank(12, mesh=big)


class TestShardedSubprocess:
    """Real 8-fake-device SPMD runs (subprocess isolation for XLA_FLAGS)."""

    def test_pick_parity_s1024_on_8_devices(self):
        """Lane-by-lane bitwise pick equality, sharded vs single-device,
        at S=1024 across mixed goals, dead lanes, and both select modes
        (the ISSUE-3 acceptance bar)."""
        out = run_subprocess("""
            import os, sys
            import numpy as np
            from benchmarks.common import family_table, deadline_range
            from repro.core.batched import BatchedAlertEngine
            from repro.core.controller import Goal
            from repro.launch.mesh import make_lane_mesh
            import jax
            assert len(jax.devices()) == 8
            table = family_table("image")
            rng = np.random.default_rng(123)
            S = 1024
            mus = rng.uniform(0.6, 2.5, S)
            sds = rng.uniform(0.01, 0.4, S)
            phis = rng.uniform(0.05, 0.6, S)
            d = rng.choice(deadline_range(table, 5), S)
            qg = rng.uniform(0.5, 0.9, S)
            eg = rng.uniform(0.5, 3.0, S) * float(
                np.median(table.run_power) * np.median(table.latency))
            gk = rng.integers(0, 2, S)
            act = rng.random(S) < 0.9
            mesh = make_lane_mesh()
            host = BatchedAlertEngine(table, None)
            shard = BatchedAlertEngine(table, None, mesh=mesh)
            for pred in (True, False):
                a = host.select(mus, sds, phis, d, accuracy_goal=qg,
                                energy_goal=eg, goal_kind=gk, active=act,
                                predictions=pred)
                b = shard.select(mus, sds, phis, d, accuracy_goal=qg,
                                 energy_goal=eg, goal_kind=gk, active=act,
                                 predictions=pred)
                for f in ("model_index", "power_index",
                          "predicted_latency", "predicted_accuracy",
                          "predicted_energy", "feasible", "relaxed_code"):
                    assert np.array_equal(getattr(a, f), getattr(b, f)), f
            # homogeneous fast path too
            h1 = BatchedAlertEngine(table, Goal.MINIMIZE_ENERGY)
            h8 = BatchedAlertEngine(table, Goal.MINIMIZE_ENERGY,
                                    mesh=mesh)
            a = h1.select(mus, sds, phis, d, accuracy_goal=qg)
            b = h8.select(mus, sds, phis, d, accuracy_goal=qg)
            assert np.array_equal(a.model_index, b.model_index)
            assert np.array_equal(a.predicted_energy, b.predicted_energy)
            print("PARITY_OK")
        """)
        assert "PARITY_OK" in out

    def test_churn_no_retrace_under_sharding(self):
        """Departures/admissions/goal flips on a sharded fleet: lane
        recycling touches only device state; the sharded engine never
        re-traces and its state buffers stay lane-sharded."""
        out = run_subprocess("""
            import numpy as np, jax
            from benchmarks.common import family_table, deadline_range
            from repro.core.batched import BatchedAlertEngine
            from repro.core.kalman import (IdlePowerFilterBank,
                                           SlowdownFilterBank,
                                           observe_fleet)
            from repro.launch.mesh import make_lane_mesh
            table = family_table("image")
            dls = deadline_range(table, 5)
            rng = np.random.default_rng(9)
            mesh = make_lane_mesh()
            S = 512
            engine = BatchedAlertEngine(table, None, mesh=mesh)
            slow = SlowdownFilterBank(S, mesh=mesh)
            idle = IdlePowerFilterBank(S, mesh=mesh)
            act = rng.random(S) < 0.9
            gk = rng.integers(0, 2, S)
            d = rng.choice(dls, S)
            qg = rng.uniform(0.5, 0.9, S)
            eg = rng.uniform(0.5, 3.0, S) * float(
                np.median(table.run_power) * np.median(table.latency))
            kw = dict(accuracy_goal=qg, energy_goal=eg, predictions=False)
            engine.select(slow.mu, slow.sigma, idle.phi, d, goal_kind=gk,
                          active=act, **kw)
            n0 = engine.n_compiles()
            assert n0 == (0, 1), n0
            for tick in range(12):
                live = np.nonzero(act)[0]
                dep = rng.choice(live, size=20, replace=False)
                act[dep] = False
                arr = rng.choice(np.nonzero(~act)[0], size=20,
                                 replace=False)
                slow.reset_lanes(arr)
                idle.reset_lanes(arr)
                gk[arr] = rng.integers(0, 2, arr.size)
                d[arr] = rng.choice(dls, arr.size)
                act[arr] = True
                batch = engine.select(slow.mu, slow.sigma, idle.phi, d,
                                      goal_kind=gk, active=act, **kw)
                prof = table.latency[batch.model_index, batch.power_index]
                observe_fleet(slow, idle,
                              prof * rng.lognormal(0.0, 0.1, S), prof,
                              idle_power=0.25 * np.ones(S),
                              active_power=np.ones(S), mask=act)
            assert engine.n_compiles() == n0, "churn re-traced"
            assert slow.mu.sharding.mesh.size == 8
            print("CHURN_OK")
        """)
        assert "CHURN_OK" in out

    def test_sharded_fleetsim_reproduces_golden_traces(self):
        """The sharded FleetSim (S=1 padded to 8 lanes across 8 devices)
        reproduces the checked-in alert golden traces bit-for-bit."""
        path = os.path.join(os.path.dirname(__file__),
                            "golden_traces.json")
        with open(path) as f:
            golden = json.load(f)
        out = run_subprocess("""
            import json
            import numpy as np
            from repro.core.controller import Goal
            from repro.launch.mesh import make_lane_mesh
            from repro.serving.sim import ENVS, EnvironmentTrace, FleetSim
            from tests.make_golden_traces import (GOLDEN_SEED,
                                                  golden_config)
            table, cons = golden_config()
            mesh = make_lane_mesh()
            rows = {}
            for env_name in ("default", "cpu", "memory"):
                trace = EnvironmentTrace(ENVS[env_name], seed=GOLDEN_SEED)
                fleet = FleetSim(table, [trace])
                res = fleet.run_alert(Goal.MAXIMIZE_ACCURACY, cons,
                                      mesh=mesh).stream(0)
                rows[env_name] = {"mean_energy": res.mean_energy,
                                  "mean_error": res.mean_error,
                                  "miss_rate": res.miss_rate}
            print("GOLDEN" + json.dumps(rows))
        """)
        line = [ln for ln in out.splitlines() if ln.startswith("GOLDEN")]
        assert line, out
        rows = json.loads(line[0][len("GOLDEN"):])
        for env, want in golden["envs"].items():
            for key, val in want["alert"].items():
                np.testing.assert_allclose(
                    rows[env][key], val, rtol=1e-9, atol=1e-12,
                    err_msg=f"sharded FleetSim drifted at {env}/{key}")

    def test_sharded_fleet_server_grows_in_mesh_multiples(self):
        """FleetAlertServer on an 8-device mesh: capacity rounds up to a
        device multiple, churn recycles lanes without re-trace, and every
        live stream is served each tick."""
        out = run_subprocess("""
            import numpy as np, jax
            from repro.configs.base import ModelConfig
            from repro.core.controller import Constraints, Goal
            from repro.launch.mesh import make_lane_mesh
            from repro.models.registry import build_model
            from repro.serving.alert_server import FleetAlertServer
            from repro.serving.engine import ServeEngine
            cfg = ModelConfig(name="t", family="dense", n_layers=2,
                              d_model=32, n_heads=4, n_kv_heads=4,
                              head_dim=8, d_ff=64, vocab=64,
                              nest_levels=2, dtype="float32",
                              attn_chunk=32)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            engine = ServeEngine(model, max_len=32, batch_size=2)
            mesh = make_lane_mesh()
            srv = FleetAlertServer(engine, params,
                                   level_accuracies=[0.6, 0.9],
                                   goal=Goal.MAXIMIZE_ACCURACY,
                                   n_streams=3, profile_iters=1,
                                   gen_tokens=3, mesh=mesh)
            assert srv.n_streams == 8, srv.n_streams  # 3 -> 8 lanes
            assert not srv.active[3:].any()           # pad lanes dead
            prompt = np.zeros((2, 4), np.int32)
            budget = float(np.median(srv.table.run_power)) * \\
                float(np.max(srv.table.latency)) * 2.0
            c = Constraints(deadline=10.0, energy_goal=budget)
            outs = srv.serve_tick([prompt] * 8, [c] * 8)
            assert sum(o is not None for o in outs) == 3
            srv.retire(1)
            lane = srv.admit(goal=Goal.MINIMIZE_ENERGY)
            assert lane == 1
            c_min = Constraints(deadline=10.0, accuracy_goal=0.7,
                                energy_goal=budget)
            cons = [c, c_min, c] + [c] * 5
            outs = srv.serve_tick([prompt] * 8, cons)
            assert outs[1] is not None
            _, n_sel = srv.scoring.n_compiles()
            assert n_sel == 1, n_sel                  # churn: no re-trace
            # fill capacity, then one more admission grows 8 -> 16
            for _ in range(5):
                srv.admit()
            assert srv.n_streams == 8
            srv.admit()
            assert srv.n_streams == 16
            assert srv.slowdown.mu.sharding.mesh.size == 8
            print("SERVER_OK")
        """)
        assert "SERVER_OK" in out
