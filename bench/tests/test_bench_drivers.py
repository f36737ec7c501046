"""Every driver end to end at a tiny size on the CPU, through the same
entry the command uses (with the look for a chip skipped)."""

import math

import pytest

from bench import harness
from bench.tests import tiny

SEED = 2 ** 31 + 77


def run_cell(tmp_path, cell, trace=False, seconds=1.0, root=None):
    """One tiny run of ``cell``; returns the result object."""
    root = root or tiny.make_root(str(tmp_path), [cell])
    return harness.run(root, cell, SEED, seconds, trace, require_tpu=False)


def check_result(res, trace):
    """The result line's keys and a correct comparison."""
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert list(res)[-1] == "compared"
    assert res["correct"], res["compared"]
    assert 0 <= res["failed"] < res["attempted"]
    for c in res["compared"].values():
        assert math.isfinite(c["value"]) and c["value"] <= c["limit"]
    assert res["device"]["platform"] == "cpu"
    if not trace:
        assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("cell,trace,layer", [
    ("fleet-image-100k.megatick", False, None),
    ("fleet-image-100k.megatick", True,
     {"plan_ms_per_round", "scan_ms_per_round"}),
    ("fleet-image-100k.finetick", False, None),
    ("fleet-image-100k.finetick", True,
     {"serve_round_p95_ms", "page_in_ms_per_round"})])
def test_fleet_gateways_run_end_to_end(tmp_path, cell, trace, layer):
    """Both fleet drivers serve their horizon and pass their comparison."""
    res = run_cell(tmp_path, cell, trace)
    check_result(res, trace)
    # Every offered request has a disposition; a refusal is no failure.
    disp = res["run"]["dispositions"]
    assert res["failed"] == disp.get("none", 0) == 0
    assert sum(disp.values()) == res["attempted"]
    names = set(res["metrics"])
    if trace:
        assert layer <= names
    else:
        assert {"decided_per_s", "setup_s"} == names


@pytest.mark.parametrize("cell,trace", [
    ("alert-anytime-120m.decode", False),
    ("alert-anytime-120m.decode", True),
    ("alert-anytime-120m.oneshot", False)])
def test_alert_server_runs_end_to_end(tmp_path, cell, trace):
    """The model driver serves requests and passes its comparison."""
    res = run_cell(tmp_path, cell, trace)
    check_result(res, trace)
    assert res["failed"] == 0
    names = set(res["metrics"])
    if trace:
        assert {"controller_ms", "prefill_ms"} <= names
    else:
        assert {"request_p95_ms", "goodput_per_s", "setup_s"} == names


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new traffic file and a workload entry make a new cell: no code
    changes, and the harness runs it."""
    root = tiny.make_root(str(tmp_path), ["fleet-image-100k.megatick"])
    tiny.add_cell(root, "fleet-image-100k.half-load",
                  "fleet-image-100k.megatick",
                  arrivals={"kind": "poisson", "rate_x": 0.5})
    res = harness.run(root, "fleet-image-100k.half-load", SEED, 1.0, False,
                      require_tpu=False)
    check_result(res, False)
    assert "decided_per_s" in res["metrics"]
