"""Metric arithmetic: tails over every request, rates over the whole
window, shares of the peak, and the level FLOP count against a hand
count."""

import numpy as np
import pytest

from bench import harness, lm_ref, traffic_gen
from bench.tests import tiny


def reader(name):
    """The metric reader file ``bench/metrics/<name>.py``."""
    return harness.load_module(f"{tiny.REPO}/bench/metrics/{name}.py",
                               "m_" + name.replace(".", "_"))


def test_p95_is_over_every_request():
    """The 95th percentile of all requests' wall times, in ms."""
    lat = list(np.linspace(0.001, 0.100, 100)) + [5.0] * 6
    got = reader("request_p95_ms").read({"latencies_s": lat})
    assert got == pytest.approx(1e3 * np.percentile(lat, 95))
    assert got > 100.0          # the slow tail counts
    assert reader("request_p95_ms").read({"latencies_s": []}) is None


def test_rates_are_over_the_whole_window():
    """Counts over all of the window's seconds."""
    data = {"decided": 300_000, "good": 90, "elapsed_s": 30.0}
    assert reader("decided_per_s").read(data) == pytest.approx(10_000)
    assert reader("goodput_per_s").read(data) == pytest.approx(3.0)


def test_deadlines_are_one_fixed_even_sequence():
    """Every prefix of the deadline sequence lies evenly over the range,
    and no seed enters it."""
    d = traffic_gen.spread_deadline(np.arange(200), 0.4, 2.0, 0.1)
    assert d.min() >= 0.04 and d.max() <= 0.2
    for n in (40, 200):
        hist = np.histogram(d[:n], bins=8, range=(0.04, 0.2))[0]
        assert np.abs(hist - n / 8).max() <= 2
    assert traffic_gen.spread_deadline(7, 0.4, 2.0, 0.1) == d[7]


def test_goodput_is_read_from_the_harness_clock(tmp_path, monkeypatch):
    """A request is good by the wall time the harness took around it and
    the tokens it delivered, not by the program's own miss flag."""
    root = tiny.make_root(str(tmp_path), ["alert-anytime-120m.decode"])
    cell = harness.load_cell(root, "alert-anytime-120m.decode")
    drv = cell.driver.Driver(cell, 3, harness.Context(False, 0.0), None)
    tokens = np.zeros((2, 4), np.int32)
    req = {"deadline": 0.1, "tokens": tokens, "level": 1}
    drv.requests = [
        dict(req, wall_s=0.09, complete=True, missed=True),    # good
        dict(req, wall_s=0.11, complete=True, missed=False),   # late
        dict(req, wall_s=0.05, complete=False, missed=False)]  # cut short
    drv.failed, drv.elapsed = 0, 1.0
    assert drv.record()["good"] == 1


def test_per_round_and_per_step_times():
    """Phase timers per round, spans per request or decode step."""
    data = {"plan_s": 1.2, "scan_s": 0.3, "rounds": 48, "units": 4,
            "decode_steps": 10,
            "spans": {"bench.controller": [0.001] * 8,
                      "bench.prefill": [0.01, 0.03],
                      "bench.generate": [0.05, 0.07]}}
    assert reader("plan_ms_per_round").read(data) == pytest.approx(25.0)
    assert reader("scan_ms_per_round").read(data) == pytest.approx(6.25)
    assert reader("controller_ms").read(data) == pytest.approx(2.0)
    assert reader("prefill_ms").read(data) == pytest.approx(20.0)
    assert reader("decode_ms_per_token").read(data) == pytest.approx(8.0)
    assert reader("decode_ms_per_token").read(
        dict(data, spans={})) is None


def test_shares_of_the_window_and_of_the_peak():
    """Idle share from busy time, MFU from FLOPs; silent without input."""
    tr = {"busy_s": 1.5, "window_s": 6.0}
    for name in ("device_idle_share.fleet", "device_idle_share.serve"):
        assert reader(name).read({"trace": tr}) == pytest.approx(75.0)
        assert reader(name).read({"trace": None}) is None
    data = {"model_flops": 197e12 * 3.0, "elapsed_s": 30.0,
            "peaks": {"bf16_flops": 197e12}}
    assert reader("mfu.serve").read(data) == pytest.approx(10.0)
    assert reader("mfu.serve").read(dict(data, peaks=None)) is None


def test_level_flops_by_hand():
    """The nested FLOP count of the tiny configuration at level 2, by
    hand: d=64 and d_ff=128 in 4 power-of-two stripes."""
    cfg = dict(tiny.TINY_CONFIGS["alert-anytime-120m"], nest_levels=4)
    # d stripes end at 8, 16, 32, 64; d_ff at 16, 32, 64, 128; heads
    # (8 x 8 channels) like d.  Level 2 keeps stripes 1 and 2.
    proj = 8 * 8 + 16 * 8                   # d -> heads: rows x stripe
    mlp_in = 8 * 16 + 16 * 16               # d -> d_ff
    mlp_out = 16 * 8 + 32 * 8               # d_ff -> d
    macs = 4 * proj + 2 * mlp_in + mlp_out  # q, k, v, o; gate, up; down
    per_token = 2 * macs * 2                # two layers
    assert lm_ref.token_flops(cfg, 2) == per_token
    assert lm_ref.head_flops(cfg, 2) == 2 * 16 * 256
    assert lm_ref.attention_flops(cfg, 2, 5) == 4 * 16 * 5 * 2
    # batch 3, prompt 4, 2 new tokens: 4 prompt tokens with causal
    # attention over 1..4 keys and one head; one decode step with 5 keys.
    want = 3 * (4 * per_token + 2 * 16 * 256 + sum(
        4 * 16 * k * 2 for k in range(1, 5))
        + per_token + 2 * 16 * 256 + 4 * 16 * 5 * 2)
    assert lm_ref.request_flops(cfg, 2, 3, 4, 2) == want


def test_full_width_flops_near_two_per_parameter():
    """At the deepest level the projections and MLP cost two FLOPs per
    layer parameter and token."""
    cfg = tiny.load(f"{tiny.REPO}/bench/configs/alert-anytime-120m.json")
    d, f, layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    dense = 2 * layers * (4 * d * d + 3 * d * f)
    nested = lm_ref.token_flops(cfg, cfg["nest_levels"])
    # The block-triangular weights drop the upper blocks.
    assert 0.5 * dense < nested < dense
    levels = [lm_ref.token_flops(cfg, k) for k in range(1, 5)]
    assert levels == sorted(levels)
