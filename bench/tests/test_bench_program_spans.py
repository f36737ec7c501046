"""The ``program_span`` readers of the program's own spans and counters:
exact on a hand-built recorder, silent on an empty one or a program
without the recorder, and reported (and consistent with the harness's
own spans) in a traced run of every cell."""

import importlib.util
import os

import pytest

from bench import harness
from bench.tests import tiny
from bench.tests.test_bench_drivers import SEED

METRICS = os.path.join(tiny.REPO, "bench", "metrics")
NEW = ("plan_admit_ms_per_round", "plan_page_ms_per_round",
       "admit_ms_per_round", "select_ms_per_round", "first_token_ms",
       "decode_host_ms_per_token", "controller_select_ms")


def reader(name):
    """The reader module of metric ``name``."""
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def rec():
    """The program's process recorder, empty before and after."""
    from repro.obs import process_recorder

    r = process_recorder()
    r.clear()
    yield r
    r.clear()


def add(rec, cat, name, dur_ms, span_id=0, parent=0):
    """One complete span of ``dur_ms`` milliseconds."""
    rec.spans.add(name, cat, 0.0, dur_ms * 1e-3, {}, span_id, parent)


def test_fleet_readers_divide_by_rounds(rec):
    for cat, name, ms in (("megatick", "plan_admit", (4.0, 6.0)),
                          ("megatick", "plan_page", (1.0, 2.0)),
                          ("gateway", "admit", (3.0, 5.0)),
                          ("gateway", "select", (0.5, 1.5))):
        for m in ms:
            add(rec, cat, name, m)
    add(rec, "gateway", "serve_round", 9.0)      # not read
    rec.metrics.counter("rounds", gateway="megatick").inc(4)
    rec.metrics.counter("rounds", gateway="host").inc(2)
    assert reader("plan_admit_ms_per_round")({}) == pytest.approx(2.5)
    assert reader("plan_page_ms_per_round")({}) == pytest.approx(0.75)
    assert reader("admit_ms_per_round")({}) == pytest.approx(4.0)
    assert reader("select_ms_per_round")({}) == pytest.approx(1.0)


def test_model_readers(rec):
    """First token is a mean; a decode step's host share leaves out its
    own ``token_fetch`` child, not the first token's."""
    add(rec, "engine", "first_token", 3.0, span_id=1)
    add(rec, "engine", "token_fetch", 0.5, span_id=2, parent=1)
    add(rec, "engine", "first_token", 5.0, span_id=3)
    for k in range(3):
        add(rec, "engine", "decode_step", 3.0, span_id=10 + k)
        add(rec, "engine", "token_fetch", 2.0, span_id=20 + k,
            parent=10 + k)
    rec.metrics.counter("decode_steps").inc(3)
    for ms in (4.0, 5.0):
        add(rec, "serve", "controller_select", ms)
    add(rec, "controller", "engine_select", 1.0)  # not read
    rec.metrics.counter("requests", server="alert").inc(2)
    assert reader("first_token_ms")({}) == pytest.approx(4.0)
    assert reader("decode_host_ms_per_token")({}) == pytest.approx(1.0)
    assert reader("controller_select_ms")({}) == pytest.approx(4.5)


@pytest.mark.parametrize("name", NEW)
def test_empty_recorder_reads_nothing(rec, name):
    assert reader(name)({}) is None


@pytest.mark.parametrize("name", NEW)
def test_spans_without_their_counter_read_nothing(rec, name):
    for cat, span in (("megatick", "plan_admit"), ("megatick", "plan_page"),
                      ("gateway", "admit"), ("gateway", "select"),
                      ("engine", "decode_step"),
                      ("serve", "controller_select")):
        add(rec, cat, span, 1.0)
    assert reader(name)({}) is None


def test_a_program_without_the_recorder_reads_nothing(rec, monkeypatch):
    """An older program (no ``process_recorder``) gives no reading and
    raises nothing."""
    import repro.obs

    add(rec, "engine", "first_token", 3.0)
    monkeypatch.delattr(repro.obs, "process_recorder")
    for name in NEW:
        assert reader(name)({}) is None


@pytest.mark.parametrize("cell,new", [
    ("fleet-image-100k.megatick",
     {"plan_admit_ms_per_round", "plan_page_ms_per_round"}),
    ("fleet-image-100k.finetick",
     {"admit_ms_per_round", "select_ms_per_round"}),
    ("alert-anytime-120m.decode",
     {"first_token_ms", "decode_host_ms_per_token",
      "controller_select_ms"}),
    ("alert-anytime-120m.oneshot",
     {"first_token_ms", "controller_select_ms"})])
def test_traced_run_reports_program_spans(tmp_path, rec, cell, new):
    """A traced tiny run of each cell reports its program-span metrics,
    and each sits inside the harness's coarser reading of its layer."""
    root = tiny.make_root(str(tmp_path), [cell])
    if cell.startswith("alert-anytime-120m"):
        # Deadlines far beyond a tiny request's time, so every request
        # runs its decode steps however loaded the host is.
        path = os.path.join(root, "bench", "traffic", f"{cell}.json")
        tr = tiny.load(path)
        tr["deadline_base_s"] = 10.0
        tiny.dump(tr, path)
    res = harness.run(root, cell, SEED, 1.0, True, require_tpu=False)
    assert res["correct"], res["compared"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert new <= set(got)
    assert not (set(NEW) - new) & set(got)
    if "plan_admit_ms_per_round" in got:
        assert got["plan_admit_ms_per_round"] + \
            got["plan_page_ms_per_round"] <= got["plan_ms_per_round"]
    if "decode_host_ms_per_token" in got:
        assert got["decode_host_ms_per_token"] <= got["decode_ms_per_token"]
    if "first_token_ms" in got:
        assert got["first_token_ms"] >= got["prefill_ms"]
        assert got["controller_select_ms"] <= got["controller_ms"]
