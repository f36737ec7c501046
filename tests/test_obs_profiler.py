"""The program's spans on the profiler's clock (``repro.obs.trace``).

* under ``jax.profiler.start_trace`` the serving path's spans appear in
  the trace's host plane as ``<cat>.<name>`` and in the process
  recorder; with the profiler off both stay empty, and a span with no
  recorder costs one check;
* the spans of one ``AlertServer.serve_one`` share a request id and nest
  under its ``request`` span;
* results are bitwise identical with the profiler on or off and with a
  recorder attached or not (both gateways and the model server);
* each level's prefill and decode programs carry their own name.
"""

import glob
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import family_table
from repro.configs.base import ModelConfig
from repro.core.controller import Constraints, Goal
from repro.models.registry import build_model
from repro.obs import (FlightRecorder, SpanTracer, process_recorder,
                       span)
from repro.serving import engine as engine_mod
from repro.serving.alert_server import AlertServer
from repro.serving.engine import ServeEngine
from repro.traffic import SessionGateway, generate_requests
from repro.traffic.megatick import MegatickGateway
from tests.make_golden_traces import gateway_config
from tests.test_obs import _assert_results_bitwise

MEGATICK_SPANS = {"megatick.plan", "megatick.plan_admit",
                  "megatick.plan_page", "megatick.scan_dispatch",
                  "megatick.scan_wait", "megatick.scan_scatter"}
GATEWAY_SPANS = {"gateway.admit", "gateway.serve_round", "paging.page_in",
                 "gateway.select", "gateway.deliver", "gateway.feedback"}
SERVE_SPANS = {"serve.request", "serve.controller_select",
               "controller.engine_select", "engine.first_token",
               "engine.cache_setup", "engine.decode_step",
               "engine.token_fetch", "serve.controller_observe"}


@pytest.fixture(scope="module")
def table():
    return family_table("image")


@pytest.fixture(scope="module")
def model_setup():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=4, head_dim=8, d_ff=64,
                      vocab=64, nest_levels=2, dtype="float32",
                      attn_chunk=32)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


class _Profiled:
    """``jax.profiler`` over the block; ``host`` holds the names of the
    host-plane events of the trace it wrote."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "prof")
        self.host: set = set()

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        prof = jax.profiler.ProfileData.from_file(path)
        for plane in prof.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    self.host.update(e.name for e in line.events)
        return False


def _names(tracer) -> set:
    return {f"{e['cat']}.{e['name']}" for e in tracer.events}


def _fleet_runs(table):
    """One megatick and one host-loop run of the golden workload."""
    sessions, n_lanes, deadline = gateway_config(table)
    out = []
    for GW in (MegatickGateway, SessionGateway):
        gw = GW(table, n_lanes, tick=deadline, max_queue=4 * n_lanes)
        out.append(gw.run(sessions, generate_requests(sessions)))
    return out


def _steady_clock():
    """A stand-in ``time`` module whose clock advances 1 ms per read, so
    every generate reports the same latencies run after run."""
    t = iter(range(1, 1 << 40))
    return types.SimpleNamespace(perf_counter=lambda: next(t) * 1e-3)


def _served(model_setup, monkeypatch, obs=None, n=4):
    """A small ``AlertServer`` run on the steady clock: every request's
    outcome and tokens."""
    model, params = model_setup
    monkeypatch.setattr(engine_mod, "time", _steady_clock())
    engine = ServeEngine(model, max_len=16, batch_size=2)
    server = AlertServer(engine, params, [0.6, 0.8],
                         Goal.MAXIMIZE_ACCURACY, profile_iters=1,
                         prompt_len=4, gen_tokens=3, obs=obs)
    tokens = []
    gen = engine.generate
    engine.generate = lambda *a, **kw: tokens.append(gen(*a, **kw)) or \
        tokens[-1]
    rng = np.random.default_rng(5)
    for k in range(n):
        prompt = rng.integers(0, 64, (2, 4), dtype=np.int32)
        server.serve_one(prompt, Constraints.from_power_budget(
            0.004 + 0.002 * k, 100.0))
    return server.history, [t["tokens"] for t in tokens]


class TestProfilerGate:
    def test_spans_reach_trace_and_process_recorder(self, table,
                                                    tmp_path):
        """While the profiler records, both gateways' spans are host
        events ``<cat>.<name>`` and land in the process recorder with
        the round counters; no recorder is attached."""
        process_recorder().clear()
        with _Profiled(tmp_path) as prof:
            mega, host = _fleet_runs(table)
        rec = process_recorder()
        want = MEGATICK_SPANS | GATEWAY_SPANS
        assert want <= prof.host
        assert want <= _names(rec.spans)
        rounds = {m["labels"]["gateway"]: m["value"]
                  for m in rec.metrics.snapshot() if m["name"] == "rounds"}
        assert rounds == {"megatick": mega.n_rounds, "host": host.n_rounds}
        pops = rec.metrics.counter("pops", gateway="host").value
        assert pops >= host.served.sum()
        process_recorder().clear()

    def test_profiler_off_records_nothing(self, table):
        process_recorder().clear()
        _fleet_runs(table)
        assert len(process_recorder()) == 0
        assert not jax.profiler.TraceAnnotation.is_enabled()

    def test_off_span_is_one_shared_null(self):
        """With no recorder and no profiler a span is the one shared
        no-op object: nothing is built or timed."""
        a, b = span(None, "x", "y", k=1), span(None, "z", "w")
        assert a is b
        with a as s:
            s.set(level=3)
        disabled = FlightRecorder(enabled=False)
        assert span(disabled, "x", "y") is a

    def test_spans_nest_by_parent_id(self):
        tr = SpanTracer()
        with tr.span("outer", rid=7):
            with tr.span("inner"):
                tr.event("mark")
        ev, inner, outer = tr.events
        assert outer["parent"] == 0
        assert inner["parent"] == outer["id"]
        assert ev["parent"] == inner["id"] and ev["ph"] == "i"
        assert inner["args"]["rid"] == 7


class TestServeSpans:
    def test_request_spans_share_id_and_nest(self, model_setup,
                                             monkeypatch):
        """Every span of a request carries its id and descends from its
        ``request`` span, which names the level served."""
        obs = FlightRecorder()
        history, tokens = _served(model_setup, monkeypatch, obs=obs)
        ev = obs.spans.events
        assert SERVE_SPANS <= _names(obs.spans)
        by_id = {e["id"]: e for e in ev}
        roots = [e for e in ev if e["name"] == "request"]
        assert [e["args"]["rid"] for e in roots] == \
            list(range(len(history)))
        assert [e["args"]["level"] for e in roots] == \
            [h.level for h in history]
        for e in ev:
            if e["name"] == "request" or "rid" not in e["args"]:
                continue
            up = e
            while up["name"] != "request":
                up = by_id[up["parent"]]
            assert up["args"]["rid"] == e["args"]["rid"], e["name"]
        served = [e for e in ev if e["cat"] != "serve"
                  and e["name"] in ("first_token", "decode_step")]
        assert served and all("rid" in e["args"] for e in served)
        assert obs.metrics.counter("requests", server="alert").value == \
            len(history)
        assert obs.metrics.counter("decode_steps").value == \
            sum(t.shape[1] - 1 for t in tokens)
        assert 0 <= obs.metrics.counter("decode_overlapped").value <= \
            obs.metrics.counter("decode_steps").value

    def test_decode_steps_fetch_one_step_behind(self, model_setup,
                                                monkeypatch):
        """Each request's first decode step fetches nothing; every later
        one holds one ``token_fetch`` (the previous step's token), and
        the last token's fetch follows the loop: one fetch per token."""
        obs = FlightRecorder()
        _, tokens = _served(model_setup, monkeypatch, obs=obs)
        ev = obs.spans.events
        steps = [e for e in ev if e["name"] == "decode_step"]
        fetches = [e for e in ev if e["name"] == "token_fetch"]
        fetch_parents = [f["parent"] for f in fetches]
        for e in steps:
            assert fetch_parents.count(e["id"]) == \
                (0 if e["args"]["step"] == 0 else 1)
        assert len(fetches) == sum(t.shape[1] for t in tokens)

    def test_serve_spans_reach_the_trace(self, model_setup, monkeypatch,
                                         tmp_path):
        process_recorder().clear()
        with _Profiled(tmp_path) as prof:
            _served(model_setup, monkeypatch, n=2)
        assert SERVE_SPANS <= prof.host
        assert SERVE_SPANS <= _names(process_recorder().spans)
        process_recorder().clear()


class TestPureObserverUnderProfiler:
    @pytest.mark.parametrize("GW", [SessionGateway, MegatickGateway])
    def test_gateway_bitwise_profiler_and_recorder(self, table, tmp_path,
                                                   GW):
        sessions, n_lanes, deadline = gateway_config(table)

        def run(obs=None):
            gw = GW(table, n_lanes, tick=deadline, max_queue=4 * n_lanes,
                    obs=obs)
            return gw.run(sessions, generate_requests(sessions))

        bare = run()
        with _Profiled(tmp_path):
            profiled = run()
            both = run(FlightRecorder())
        _assert_results_bitwise(bare, profiled, f"{GW.__name__}:profiled")
        _assert_results_bitwise(bare, both, f"{GW.__name__}:both")
        process_recorder().clear()

    def test_alert_server_bitwise_profiler_and_recorder(
            self, model_setup, monkeypatch, tmp_path):
        bare = _served(model_setup, monkeypatch)
        attached = _served(model_setup, monkeypatch, obs=FlightRecorder())
        with _Profiled(tmp_path):
            profiled = _served(model_setup, monkeypatch,
                               obs=FlightRecorder())
        process_recorder().clear()
        for other in (attached, profiled):
            assert other[0] == bare[0]
            assert len(other[1]) == len(bare[1])
            for a, b in zip(bare[1], other[1]):
                np.testing.assert_array_equal(a, b)


def test_level_programs_carry_distinct_names(model_setup):
    """Each level's prefill and decode program is named after it, so a
    profiler trace tells them apart."""
    model, params = model_setup
    engine = ServeEngine(model, max_len=8, batch_size=1)
    toks = jnp.zeros((1, 4), jnp.int32)
    names = set()
    for lvl in engine.levels:
        pre = engine._prefill[lvl].lower(params, {"tokens": toks})
        dec = engine._decode[lvl].lower(
            params, toks[:, :1], jnp.asarray(4, jnp.int32),
            engine.init_caches(lvl))
        assert f"@jit_prefill_level{lvl}" in pre.as_text()
        assert f"@jit_decode_level{lvl}" in dec.as_text()
        names |= {f"prefill_level{lvl}", f"decode_level{lvl}"}
    assert len(names) == 2 * len(engine.levels)
