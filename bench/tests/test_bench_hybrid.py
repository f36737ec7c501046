"""The Jamba hybrid cell at a tiny size on the CPU: the driver end to end
through the harness, faults planted under it that its comparison must
catch, its reference against the program's, its weight maker's layout,
its FLOP and byte counts, the float8 control failing the comparison,
and the readers of its new metrics."""

import json
import os

import numpy as np
import pytest

from bench import harness, hybrid_ref
from bench.drivers import alert_hybrid
from bench.tests import tiny
from bench.tests.test_bench_drivers import SEED, check_result
from bench.tests.test_bench_faults_model import patch_generate
from bench.tests.test_bench_program_spans import add, rec, reader  # noqa: F401
from bench.tests.test_bench_trace import _plane, write_xspace

CELL = "jamba2-mini-ep2.decode"
# The tiny copy: every width cut, the layer plan, the expert share and
# the equations kept.
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 96,
        "vocab_size": 256, "mamba_d_state": 4, "mamba_dt_rank": 8,
        "dtype": "float32"}
TINY_TRAFFIC = {"batch": 2, "prompt_len": 12, "gen_tokens": 5,
                "check_requests": 3, "deadline_base_s": 0.01,
                "trace_seconds": 1.0}


def tiny_config():
    """The cell's configuration cut to toy widths."""
    with open(os.path.join(tiny.REPO, "bench", "configs",
                           "jamba2-mini-ep2.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["assumed"] = dict(cfg["assumed"], head_dim=16)
    return cfg


def make_root(tmp_path):
    """A checkout-like root holding only the tiny hybrid cell."""
    root = tiny.make_root(str(tmp_path), [CELL], tiny=False)
    tiny.dump(tiny_config(), os.path.join(root, "bench", "configs",
                                          "jamba2-mini-ep2.json"))
    path = os.path.join(root, "bench", "traffic", f"{CELL}.json")
    tr = tiny.load(path)
    tr.update(TINY_TRAFFIC)
    tiny.dump(tr, path)
    return root


@pytest.mark.parametrize("trace", [False, True])
def test_hybrid_cell_runs_end_to_end(tmp_path, trace):
    """The driver serves requests through AlertServer and passes its
    comparison; a traced run reports the program-span metrics."""
    res = harness.run(make_root(tmp_path), CELL, SEED, 1.5, trace,
                      require_tpu=False)
    check_result(res, trace)
    assert res["failed"] == 0
    assert res["compared"]["routing_mismatches"]["value"] == 0
    names = set(res["metrics"])
    if trace:
        # hbm_share.hybrid_decode reads the chip's trace: silent here.
        assert {"first_token_ms", "decode_host_ms_per_token",
                "controller_select_ms", "cache_setup_ms", "prefill_ms",
                "decode_ms_per_token", "controller_ms"} <= names
    else:
        assert {"request_p95_ms", "goodput_per_s", "setup_s"} == names


def test_weights_are_the_configuration_s_not_the_seed_s(tmp_path):
    """Every run serves the same model, drawn from the configuration's
    ``weights_seed``; the run's seed draws the traffic."""
    import jax

    cell = harness.load_cell(make_root(tmp_path), CELL)
    devs = harness.find_chips(1, require_tpu=False)
    params = []
    for seed in (SEED, SEED + 1):
        drv = cell.driver.Driver(cell, seed, harness.Context(False, 0.0),
                                 devs)
        drv.setup()
        params.append(drv.params)
    want = hybrid_ref.make_params(
        hybrid_ref.model_config(cell.config),
        cell.config["assumed"]["weights_seed"])
    for got in params:
        jax.tree.map(np.testing.assert_array_equal, got, want)


@pytest.fixture(scope="module")
def served():
    """Tiny weights, an engine over them, and one served request."""
    from repro.models.registry import build_model
    from repro.serving.engine import ServeEngine

    mcfg = hybrid_ref.model_config(tiny_config())
    params = hybrid_ref.make_params(mcfg, SEED)
    engine = ServeEngine(build_model(mcfg), max_len=24, batch_size=2)
    prompt = np.random.default_rng(3).integers(0, 256, (2, 16)).astype(
        np.int32)
    r = engine.generate(params, prompt, 6)
    return mcfg, params, dict(prompt=prompt, **r)


def test_weights_follow_the_program_layout(served):
    """The weight maker gives the tree ``init_lm`` gives, leaf for leaf
    in shape and dtype, with the expert stacks holding the share."""
    import jax

    from repro.models.transformer import init_lm

    mcfg, params, _ = served
    want = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), mcfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    ffn = params["group"]["pos1"]["ffn"]
    assert ffn["w_gate"].shape[1] == mcfg.n_experts_here == 8
    assert ffn["router"].shape[-1] == mcfg.n_experts == 16


def test_blocked_reference_matches_the_repository_reference(served):
    """The benchmark's blocked reference and ``repro.models.reference``
    give the same logits and routing on the same tokens."""
    from repro.models import reference

    mcfg, params, r = served
    full = np.concatenate([r["prompt"], r["tokens"][:, :-1]], axis=1)
    seen = []
    got, _ = hybrid_ref.Reference(mcfg, 1e-3).forward(
        params, full, 0, full.shape[1], seen=seen)
    want, routes = reference.forward(params, mcfg, full)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.stack(seen), np.asarray(routes))


@pytest.mark.parametrize("fp8", [False, True])
def test_check_passes_and_the_float8_control_fails(served, fp8):
    """The served request agrees with the float32 reference; a float8
    pass in the program's place breaks one of the cell's limits."""
    mcfg, params, r = served
    out = hybrid_ref.check(params, mcfg, [r], 6, 1e-3, fp8=fp8)
    if fp8:
        limits = tiny.load(os.path.join(tiny.REPO, "bench", "traffic",
                                        f"{CELL}.json"))["limits"]
        assert any(out[k] > limits[k] for k in out), out
    else:
        assert out == {"routing_mismatches": 0.0, "routing_followed": 0.0,
                       "logit_gap": pytest.approx(0.0, abs=1e-4)}


def test_the_control_fails(tmp_path):
    """The float32 controller and the float8 model, in the program's
    place, are not correct under the cell's own limits."""
    cell = harness.load_cell(make_root(tmp_path), CELL)
    drv = cell.driver.Driver(cell, 6, harness.Context(False, 0.0),
                             harness.find_chips(1, require_tpu=False))
    drv.setup()
    drv.window(1.0)
    drv.release()
    got = drv.control()
    limits = cell.traffic["limits"]
    assert any(got[k] > limits[k] for k in limits), got


def _wrong_choice(monkeypatch):
    """The program's router takes the next expert after each it picks."""
    from repro.models import moe

    real = moe.route_topk

    def route(logits, k, renormalize=True):
        vals, idx, probs = real(logits, k, renormalize)
        return vals, (idx + 1) % logits.shape[-1], probs

    monkeypatch.setattr(moe, "route_topk", route)


def _wrong_offset(monkeypatch):
    """The program holds experts 1-8 in place of 0-7."""
    from repro.models import registry

    real = registry.build_model
    monkeypatch.setattr(registry, "build_model", lambda cfg: real(
        cfg.replace(experts_offset=cfg.experts_offset + 1)))


def _state_unchanged(monkeypatch):
    """The controller's feedback step does nothing."""
    from repro.core.controller import AlertController

    monkeypatch.setattr(AlertController, "observe",
                        lambda self, *a, **kw: None)


def _half_the_batch(monkeypatch):
    """The second half of every batch gets no real tokens."""
    def half(tok):
        tok[tok.shape[0] // 2:] = 0
        return tok

    patch_generate(monkeypatch, half)


def _altered_token(monkeypatch):
    """One served token changes where the engine produces it."""
    def alter(tok):
        tok[0, -1] = (tok[0, -1] + 1) % 256
        return tok

    patch_generate(monkeypatch, alter)


@pytest.mark.parametrize("plant, caught_by", [
    (_state_unchanged, "controller_gap"),
    (_half_the_batch, "logit_gap"),
    (_altered_token, None),
    (_wrong_choice, "routing_mismatches"),
    (_wrong_offset, None),
], ids=["state_unchanged", "half_the_batch", "altered_token",
        "wrong_expert_choice", "wrong_experts_offset"])
def test_a_planted_fault_is_caught(tmp_path, monkeypatch, plant, caught_by):
    """A fault planted under a tiny run of the cell makes it not
    correct (by the named comparison, where one is named)."""
    plant(monkeypatch)
    res = harness.run(make_root(tmp_path), CELL, SEED, 1.0, False,
                      require_tpu=False)
    assert not res["correct"]
    if caught_by is not None:
        got = res["compared"][caught_by]
        assert got["value"] > got["limit"]


# One router row, top-2: experts 1, 2 and 3 tie around the 2nd place.
PROBS = np.array([[0.30, 0.20, 0.199, 0.198, 0.103]])


@pytest.mark.parametrize("prog, stats", [
    ([0, 1], (1, 0, 0)),        # the reference's own choice
    ([2, 0], (1, 1, 0)),        # the 3rd for the 2nd, within the margin
    ([0, 3], (1, 1, 0)),        # the 4th, still within it (a 3-way tie)
    ([0, 4], (1, 0, 1)),        # the 5th, 0.097 below the 2nd
    ([2, 3], (1, 0, 1)),        # the 1st left out
    ([-1, -1], (0, 0, 0)),      # a position the program did not process
])
def test_routing_follows_only_swaps_within_the_margin(served, prog, stats):
    """The reference takes the program's experts where every expert it
    took lies within the margin of the one it left out, wherever it
    ranks, and counts any other difference as a mismatch."""
    ref = hybrid_ref.Reference(served[0], 0.004)
    prog = np.array([prog])
    idx, gates, got = ref.choose(PROBS, prog)
    assert tuple(got) == stats
    want = prog if stats[1] else np.array([[0, 1]])
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_allclose(gates, np.take_along_axis(PROBS, want, -1))


def test_flops_and_bytes_count_the_published_cut():
    """The cut's counts: 7.39 B parameters, about 7.9 GB for a decode
    step that reaches 3.3 held experts per expert layer, about 18.6 TFLOP
    for a request of 4 x (1,024 + 31) tokens with half its pairs here."""
    with open(os.path.join(tiny.REPO, "bench", "configs",
                           "jamba2-mini-ep2.json")) as f:
        mcfg = hybrid_ref.model_config(json.load(f))
    assert mcfg.param_count() == pytest.approx(7.39e9, rel=1e-3)
    parts = hybrid_ref.decode_step_bytes(mcfg, 4)
    assert parts["per_expert"] == 3 * 4096 * 14336 * 2
    step = parts["fixed"] + 1040 * parts["per_position"] + \
        4 * 3.3 * parts["per_expert"]
    assert step == pytest.approx(7.94e9, rel=0.01)
    flops = hybrid_ref.request_flops(mcfg, 4, 1024, 32, 4 * 1055 * 4)
    assert flops == pytest.approx(18.56e12, rel=0.01)


def test_new_readers(rec):  # noqa: F811
    """``cache_setup_ms`` is a mean; ``hbm_share.hybrid_decode`` is the
    decode bytes over the decode program's device seconds, and is silent
    without a trace or peaks."""
    add(rec, "engine", "cache_setup", 2.0)
    add(rec, "engine", "cache_setup", 4.0)
    assert reader("cache_setup_ms")({}) == pytest.approx(3.0)
    read = reader("hbm_share.hybrid_decode")
    data = {"peaks": {"hbm_bytes_per_s": 1e9}}
    assert read(data) is None                    # no trace
    data.update(decode_bytes=6e6, decode_device_s=8e-3)
    assert read(data) == pytest.approx(75.0)
    assert read(dict(data, peaks=None)) is None


def test_decode_device_time_reads_the_decode_program_in_the_window(
        tmp_path):
    """The decode program's runs on the device, cut to the traced
    window; other programs and runs outside the window do not count."""
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Ops", [(1, 10_000, 5_000)]),
        ("XLA Modules", [(2, 5_000, 20_000), (3, 30_000, 10_000),
                         (2, 50_000, 20_000), (2, 95_000, 20_000),
                         (2, 200_000, 5_000)]),
    ], {1: "fusion.1", 2: "jit_decode(4)", 3: "jit_prefill(3)"})
    host = _plane(2, "/host:CPU", [("python", [(4, 0, 100_000)])],
                  {4: "bench.window"})
    write_xspace(str(tmp_path / "t.xplane.pb"), [dev, host])
    sec, n = alert_hybrid.decode_device_time(str(tmp_path))
    assert n == 3
    assert sec == pytest.approx((20 + 20 + 5) * 1e-6)
    assert alert_hybrid.decode_device_time(None) is None
    assert alert_hybrid.decode_device_time(str(tmp_path / "none")) is None


@pytest.mark.parametrize("found, counted", [
    ((4e-3, 2), True),          # the traced requests' two steps
    ((4e-3, 3), False),         # a decode run that is not one of them
    (None, False),              # no trace
])
def test_decode_bytes_count_the_traced_steps(monkeypatch, found, counted):
    """Only requests served under the profiler count, each step at its
    cache length, and each held expert a step reached once; a trace
    whose decode runs are not those steps reads as nothing."""
    monkeypatch.setattr(alert_hybrid, "decode_device_time",
                        lambda _dir: found)
    drv = alert_hybrid.Driver.__new__(alert_hybrid.Driver)
    drv.ctx = harness.Context(True, 1.0)
    drv.traffic = {"batch": 2, "prompt_len": 10}
    drv.mcfg = hybrid_ref.model_config(tiny_config())
    reqs = [{"tokens": np.zeros((2, 3)), "hit": 5, "traced": True},
            {"tokens": np.zeros((2, 1)), "hit": 0, "traced": True},
            {"tokens": np.zeros((2, 4)), "hit": 7, "traced": False}]
    got = drv._decode_traffic(reqs)
    if not counted:
        assert got == {}
        return
    parts = hybrid_ref.decode_step_bytes(drv.mcfg, 2)
    n_bytes = 2 * parts["fixed"] + parts["per_position"] * (11 + 12) \
        + 5 * parts["per_expert"]
    assert got == {"decode_bytes": n_bytes, "decode_device_s": 4e-3}
