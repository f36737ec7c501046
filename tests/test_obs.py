"""Observability tests: the flight recorder's pure-observer contract.

Three layers of assertion (docs/OBSERVABILITY.md):

* **unit** — registry get-or-create/label semantics, histogram
  bounded-sample accounting, accumulating phase timers, span buffer +
  JSONL schema validation, telemetry-ring wraparound;
* **pure observer** — both gateways reproduce the checked-in golden
  traces (``gateway`` AND ``straggler``) byte-identically with full
  instrumentation attached, and every result array is bitwise equal
  across bare / disabled / instrumented runs (the megatick's
  instrumented run exercises the ring-extended scan executable);
* **consistency** — the device-resident ring's aggregates reconcile
  with the :class:`~repro.traffic.gateway.GatewayResult` they observed,
  and an instrumented ``sweep_loads`` records the same numbers as a
  bare one.
"""

import json
import os

import numpy as np
import pytest

from benchmarks.common import family_table
from repro.obs import (FlightRecorder, MetricsRegistry, SpanTracer,
                       TelemetryRing, validate_jsonl)
from repro.obs import metrics as obs_metrics
from repro.obs.report import render_recorder, render_run_dir
from repro.traffic import SessionGateway, generate_requests
from repro.traffic.megatick import MegatickGateway
from tests.make_golden_traces import (gateway_config, straggler_config,
                                      summarize_gateway)

# GatewayResult fields whose bitwise equality defines neutrality.
RESULT_FIELDS = ("status", "start", "latency", "sojourn", "missed",
                 "accuracy", "energy", "model_index", "power_index")


@pytest.fixture(scope="module")
def table():
    return family_table("image")


@pytest.fixture(scope="module")
def golden():
    path = os.path.join(os.path.dirname(__file__), "golden_traces.json")
    with open(path) as f:
        return json.load(f)


def _assert_results_bitwise(a, b, ctx=""):
    for f in RESULT_FIELDS:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f"{ctx}:{f}")
    assert (a.n_rounds, a.pages_in, a.pages_out) == \
        (b.n_rounds, b.pages_in, b.pages_out), ctx


# ------------------------------------------------------------------ #
# metrics registry                                                    #
# ------------------------------------------------------------------ #
class TestMetrics:
    def test_get_or_create_identity_and_labels(self):
        reg = MetricsRegistry()
        c1 = reg.counter("served", gateway="host")
        c1.inc(3)
        assert reg.counter("served", gateway="host") is c1
        c2 = reg.counter("served", gateway="megatick")
        assert c2 is not c1 and c2.value == 0.0
        assert len(reg) == 2

    def test_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")

    def test_histogram_stats_and_bounded_sample(self, monkeypatch):
        monkeypatch.setattr(obs_metrics, "HISTOGRAM_SAMPLE_CAP", 4)
        h = obs_metrics.Histogram()
        h.observe_many([5.0, 1.0, 3.0])
        h.observe(7.0)
        h.observe_many([9.0, 11.0])          # past the cap
        s = h.snapshot()
        assert s["count"] == 6 and s["sum"] == 36.0
        assert s["min"] == 1.0 and s["max"] == 11.0
        # exact moments survive the cap; only percentile raws drop
        assert s["dropped_observations"] == 2
        assert s["p50"] == pytest.approx(4.0)  # over retained [5,1,3,7]

    def test_timer_accumulates_and_times(self):
        t = obs_metrics.PhaseTimer()
        t.observe(0.5)
        t.observe(0.25)
        with t.time():
            pass
        assert t.count == 3
        assert t.total_s == pytest.approx(0.75, abs=0.2)
        assert t.min_s <= t.last_s <= 0.2

    def test_snapshot_save_load_roundtrip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("a", k="v").inc(2)
        reg.gauge("b").set(1.5)
        reg.histogram("c").observe(3.0)
        reg.timer("d").observe(0.1)
        p = str(tmp_path / "m.json")
        reg.save(p)
        snap = MetricsRegistry.load_snapshot(p)
        assert snap == reg.snapshot()
        kinds = {m["name"]: m["type"] for m in snap}
        assert kinds == {"a": "counter", "b": "gauge", "c": "histogram",
                         "d": "timer"}


# ------------------------------------------------------------------ #
# spans: schema + exporters                                           #
# ------------------------------------------------------------------ #
class TestSpans:
    def test_span_and_event_totals(self):
        tr = SpanTracer()
        with tr.span("plan", rounds=3):
            pass
        with tr.span("plan"):
            pass
        tr.event("trip", lane=4)
        tot = tr.phase_totals()
        assert tot["plan"]["count"] == 2
        assert "trip" not in tot          # instants are not phases
        assert len(tr) == 3

    def test_jsonl_schema_validates(self, tmp_path):
        tr = SpanTracer()
        with tr.span("plan"):
            pass
        tr.event("trip", lane=1)
        p = str(tmp_path / "spans.jsonl")
        tr.write_jsonl(p)
        assert validate_jsonl(p) == 2

    def test_jsonl_validation_rejects_malformed(self, tmp_path):
        p = str(tmp_path / "bad.jsonl")
        with open(p, "w") as f:
            f.write('{"_meta": {"schema": ["nope"], "version": 1}}\n')
        with pytest.raises(ValueError, match="_meta"):
            validate_jsonl(p)
        tr = SpanTracer()
        tr.event("x")
        tr.write_jsonl(p)
        with open(p) as f:
            lines = f.readlines()
        rec = json.loads(lines[1])
        rec["ph"] = "Z"
        with open(p, "w") as f:
            f.writelines([lines[0], json.dumps(rec) + "\n"])
        with pytest.raises(ValueError, match="bad ph"):
            validate_jsonl(p)

    def test_chrome_trace_structure(self, tmp_path):
        tr = SpanTracer()
        with tr.span("plan"):
            pass
        tr.event("trip")
        p = str(tmp_path / "trace.json")
        tr.write_chrome_trace(p)
        with open(p) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        assert len(evs) == 2
        x = next(e for e in evs if e["ph"] == "X")
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(x)
        i = next(e for e in evs if e["ph"] == "i")
        assert "dur" not in i and i["s"] == "t"

    def test_buffer_cap_counts_drops(self):
        tr = SpanTracer(capacity=2)
        for k in range(5):
            tr.event("e", k=k)
        assert len(tr) == 2 and tr.dropped == 3


# ------------------------------------------------------------------ #
# telemetry ring                                                      #
# ------------------------------------------------------------------ #
def _push(ring, vals):
    n = len(vals)
    ring.push_rounds(now_s=vals, n_active=vals, n_feasible=vals,
                     n_relaxed=np.zeros(n), energy_j=vals,
                     n_missed=np.zeros(n))


class TestRing:
    def test_push_view_order(self):
        r = TelemetryRing(8)
        _push(r, [1.0, 2.0, 3.0])
        v = r.view()
        np.testing.assert_array_equal(v["now_s"], [1.0, 2.0, 3.0])
        assert len(r) == 3 and r.n_seen == 3

    def test_wraparound_keeps_newest(self):
        r = TelemetryRing(4)
        _push(r, [1.0, 2.0, 3.0])
        _push(r, [4.0, 5.0, 6.0])
        v = r.view()
        np.testing.assert_array_equal(v["now_s"], [3.0, 4.0, 5.0, 6.0])
        assert r.n_seen == 6 and len(r) == 4
        assert r.summary()["rounds_retained"] == 4

    def test_oversize_push_keeps_tail(self):
        r = TelemetryRing(3)
        _push(r, np.arange(10, dtype=float))
        np.testing.assert_array_equal(r.view()["now_s"], [7.0, 8.0, 9.0])

    def test_length_mismatch_raises(self):
        r = TelemetryRing(4)
        with pytest.raises(ValueError, match="length mismatch"):
            r.push_rounds(now_s=[1.0], n_active=[1.0, 2.0],
                          n_feasible=[1.0], n_relaxed=[0.0],
                          energy_j=[1.0], n_missed=[0.0])

    def test_save_load_roundtrip(self, tmp_path):
        r = TelemetryRing(4)
        _push(r, [1.0, 2.0])
        p = str(tmp_path / "ring.json")
        r.save(p)
        doc = TelemetryRing.load(p)
        assert doc["summary"] == r.summary()
        np.testing.assert_array_equal(doc["rounds"]["now_s"], [1.0, 2.0])


# ------------------------------------------------------------------ #
# pure-observer contract on the serving path                          #
# ------------------------------------------------------------------ #
class TestPureObserver:
    @pytest.mark.parametrize("GW", [SessionGateway, MegatickGateway])
    def test_gateway_golden_with_full_instrumentation(self, table,
                                                      golden, GW):
        """The checked-in seed-1 overload golden is reproduced
        BYTE-identically with a flight recorder attached — for the host
        loop and for the megatick's ring-extended scan executable."""
        sessions, n_lanes, deadline = gateway_config(table)
        obs = FlightRecorder()
        gw = GW(table, n_lanes, tick=deadline, max_queue=4 * n_lanes,
                obs=obs)
        got = summarize_gateway(gw.run(sessions,
                                       generate_requests(sessions)))
        assert got == golden["gateway"], GW.__name__
        assert obs.ring.n_seen == got["n_rounds"]
        assert len(obs.metrics) > 0

    def test_straggler_golden_with_full_instrumentation(self, table,
                                                        golden):
        """The pinned straggler-detection golden (trip set + latency +
        clean false positives) is unchanged when both the gateway and
        the detector carry the recorder — and the trips show up in it."""
        from repro.traffic.faults import KalmanLaneDetector

        sessions, n_lanes, deadline, faults = straggler_config(table)
        obs = FlightRecorder()
        det = KalmanLaneDetector(n_lanes, obs=obs)
        gw = SessionGateway(table, n_lanes, tick=deadline, obs=obs)
        gw.run(sessions, generate_requests(sessions), faults=faults,
               detector=det)
        want = golden["straggler"]
        assert [int(x) for x in np.nonzero(det.tripped)[0]] == \
            want["tripped_lanes"]
        assert float(det.first_trip_time[want["fault_lane"]]) == \
            want["first_trip_time_s"]
        n_trips = len(want["tripped_lanes"])
        assert obs.metrics.counter("detector_trips").value == n_trips
        assert obs.metrics.counter("fault_trips",
                                   gateway="host").value == n_trips
        trip_events = [e for e in obs.spans.events
                       if e["name"] in ("detector_trip", "fault_trip")]
        assert len(trip_events) == 2 * n_trips  # detector + gateway

    @pytest.mark.parametrize("GW", [SessionGateway, MegatickGateway])
    def test_bitwise_neutral_bare_disabled_instrumented(self, table, GW):
        """Every result array is bitwise equal across obs=None,
        a disabled recorder, and a fully attached one."""
        sessions, n_lanes, deadline = gateway_config(table)
        runs = {}
        for name, obs in (("bare", None),
                          ("disabled", FlightRecorder(enabled=False)),
                          ("instrumented", FlightRecorder())):
            gw = GW(table, n_lanes, tick=deadline,
                    max_queue=4 * n_lanes, obs=obs)
            runs[name] = gw.run(sessions, generate_requests(sessions))
        _assert_results_bitwise(runs["bare"], runs["disabled"],
                                f"{GW.__name__}:disabled")
        _assert_results_bitwise(runs["bare"], runs["instrumented"],
                                f"{GW.__name__}:instrumented")

    @pytest.mark.parametrize("GW", [SessionGateway, MegatickGateway])
    def test_ring_reconciles_with_result(self, table, GW):
        """The per-round ring aggregates sum to the result's totals
        (ring energy is the scan's own sum for the megatick — equal to
        the host recompute here, where no FMA contraction differs)."""
        sessions, n_lanes, deadline = gateway_config(table)
        obs = FlightRecorder()
        gw = GW(table, n_lanes, tick=deadline, max_queue=4 * n_lanes,
                obs=obs)
        res = gw.run(sessions, generate_requests(sessions))
        s = obs.ring.summary()
        assert s["rounds_seen"] == res.n_rounds
        assert s["lane_rounds_active"] == int(res.served.sum())
        assert s["missed"] == int(res.missed[res.served].sum())
        assert s["energy_j"] == pytest.approx(
            float(res.energy[res.served].sum()), rel=1e-9)

    def test_host_and_megatick_rings_agree(self, table):
        """Same workload, both regimes instrumented: identical
        per-round counts (feasible/relaxed/missed/active) — the
        device-resident reductions compute the host's numbers."""
        sessions, n_lanes, deadline = gateway_config(table)
        rings = {}
        for GW in (SessionGateway, MegatickGateway):
            obs = FlightRecorder()
            gw = GW(table, n_lanes, tick=deadline,
                    max_queue=4 * n_lanes, obs=obs)
            gw.run(sessions, generate_requests(sessions))
            rings[GW.__name__] = obs.ring.view()
        a, b = rings["SessionGateway"], rings["MegatickGateway"]
        for f in ("now_s", "n_active", "n_feasible", "n_relaxed",
                  "n_missed"):
            np.testing.assert_array_equal(a[f], b[f], err_msg=f)

    def test_phase_timers_accumulate_across_runs(self, table):
        """Satellite: last_plan_s/last_scan_s are read-through aliases
        of registry timers that ACCUMULATE across run() calls instead
        of silently overwriting."""
        sessions, n_lanes, deadline = gateway_config(table)
        gw = MegatickGateway(table, n_lanes, tick=deadline,
                             max_queue=4 * n_lanes)
        assert gw.last_plan_s == 0.0 and gw.last_scan_s == 0.0
        gw.run(sessions, generate_requests(sessions))
        p1, s1 = gw.total_plan_s, gw.total_scan_s
        assert p1 > 0.0 and s1 > 0.0
        gw.run(sessions, generate_requests(sessions))
        assert gw.total_plan_s > p1 and gw.total_scan_s > s1
        assert gw.last_plan_s <= gw.total_plan_s
        assert gw._plan_timer.count == 2
        # attached recorders expose the same timers by name
        obs = FlightRecorder()
        gw2 = MegatickGateway(table, n_lanes, tick=deadline,
                              max_queue=4 * n_lanes, obs=obs)
        gw2.run(sessions, generate_requests(sessions))
        assert obs.metrics.timer(
            "megatick_plan", gateway="megatick").count == 1

    def test_queue_and_paging_metrics_recorded(self, table):
        sessions, n_lanes, deadline = gateway_config(table)
        obs = FlightRecorder()
        gw = SessionGateway(table, n_lanes, tick=deadline,
                            max_queue=4 * n_lanes, obs=obs)
        res = gw.run(sessions, generate_requests(sessions))
        m = obs.metrics
        lab = dict(gateway="host", policy="alert")
        assert m.counter("requests_offered", **lab).value == res.offered
        assert m.counter("requests_served", **lab).value == \
            int(res.served.sum())
        assert m.counter("pages_in", **lab).value == res.pages_in
        assert m.counter("queue_submitted").value > 0
        assert m.histogram("queue_depth", gateway="host").count > 0
        assert m.histogram("kalman_innovation",
                           gateway="host").count == int(res.served.sum())

    def test_page_in_spans_count_the_run_pages(self, table):
        """Each ``page_in`` span carries its round's paging: summed over
        the run, ``n_in`` / ``n_out`` are the result's ``pages_in`` /
        ``pages_out``, and ``n_fresh`` counts each served session once
        (its first page-in)."""
        sessions, n_lanes, deadline = gateway_config(table)
        obs = FlightRecorder()
        gw = SessionGateway(table, n_lanes, tick=deadline / 4,
                            max_queue=4 * n_lanes, obs=obs)
        res = gw.run(sessions, generate_requests(sessions))
        spans = [e for e in obs.spans.events
                 if e["name"] == "page_in" and e["ph"] == "X"]
        assert len(spans) == res.n_rounds
        assert res.pages_in > 0 and res.pages_out > 0
        assert sum(e["args"]["n_in"] for e in spans) == res.pages_in
        assert sum(e["args"]["n_out"] for e in spans) == res.pages_out
        assert sum(e["args"]["n_fresh"] for e in spans) == \
            np.unique(res.sid[res.served]).size


# ------------------------------------------------------------------ #
# sweep-level observation (satellite: uniform n_compiles + obs)       #
# ------------------------------------------------------------------ #
class TestSweepObs:
    def test_sweep_records_unchanged_and_compiles_flat(self, table):
        from benchmarks.common import deadline_range
        from repro.core.controller import Constraints, Goal
        from repro.serving.sim import CPU_ENV
        from repro.traffic import (PoissonProcess, TenantSpec,
                                   sweep_loads)

        dl = float(deadline_range(table, 5)[3])
        n_lanes = 4
        mix = [TenantSpec("t", Goal.MINIMIZE_ENERGY,
                          Constraints(deadline=dl, accuracy_goal=0.75),
                          PoissonProcess(n_lanes / dl), n_sessions=8,
                          phases=CPU_ENV)]
        kw = dict(n_lanes=n_lanes, horizon=8 * dl, seed=3,
                  max_queue=4 * n_lanes, tick=dl)
        for gateway in ("host", "megatick"):
            bare = sweep_loads(table, mix, [0.5, 4.0], gateway=gateway,
                               **kw)
            obs = FlightRecorder()
            seen = sweep_loads(table, mix, [0.5, 4.0], gateway=gateway,
                               obs=obs, **kw)
            assert bare == seen, gateway      # numbers never move
            assert len(obs.metrics) > 0 and obs.ring.n_seen > 0
            for row in seen:
                for scheme, rec in row["schemes"].items():
                    assert rec["gateway"] == gateway, scheme
            # flat-compile accounting across load points, per scheme
            for scheme in seen[0]["schemes"]:
                first = seen[0]["schemes"][scheme]["n_compiles"]
                last = seen[-1]["schemes"][scheme]["n_compiles"]
                assert first == last, (gateway, scheme)
                assert first[0] == 0 and first[1] <= 1, \
                    (gateway, scheme, first)


# ------------------------------------------------------------------ #
# recorder bundle + report CLI                                        #
# ------------------------------------------------------------------ #
class TestRecorderAndReport:
    def _recorded(self, table):
        sessions, n_lanes, deadline = gateway_config(table)
        obs = FlightRecorder()
        gw = MegatickGateway(table, n_lanes, tick=deadline,
                             max_queue=4 * n_lanes, obs=obs)
        gw.run(sessions, generate_requests(sessions))
        return obs

    def test_save_validates_and_renders(self, table, tmp_path):
        obs = self._recorded(table)
        paths = obs.save(str(tmp_path / "run"))
        assert validate_jsonl(paths["spans"]) == len(obs.spans)
        live = render_recorder(obs, trace_paths=paths)
        saved = render_run_dir(str(tmp_path / "run"))
        for text in (live, saved):
            assert "== metrics ==" in text
            assert "== host phases ==" in text
            assert "telemetry ring" in text
            assert "megatick_plan" in text

    def test_report_cli(self, table, tmp_path, capsys):
        from repro.obs.report import main

        obs = self._recorded(table)
        obs.save(str(tmp_path / "run"))
        assert main([str(tmp_path / "run")]) == 0
        assert "flight recording" in capsys.readouterr().out
        assert main([]) == 2
        assert main([str(tmp_path / "nope")]) == 2

    def test_disabled_recorder_records_nothing(self, table):
        sessions, n_lanes, deadline = gateway_config(table)
        obs = FlightRecorder(enabled=False)
        gw = SessionGateway(table, n_lanes, tick=deadline,
                            max_queue=4 * n_lanes, obs=obs)
        gw.run(sessions, generate_requests(sessions))
        assert len(obs.metrics) == 0
        assert len(obs.spans) == 0
        assert obs.ring.n_seen == 0
