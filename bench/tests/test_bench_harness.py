"""The harness finds every piece by file name, keeps to the contract's
shape, and refuses to measure without a TPU."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    """The repository's ``BENCHMARK.json``."""
    return tiny.load(os.path.join(tiny.REPO, "BENCHMARK.json"))


def test_every_cell_finds_its_files(bench):
    """Each cell resolves to its configuration, traffic mix and driver,
    and every metric it reports has a reader file."""
    for w in bench["workloads"]:
        cell = harness.load_cell(tiny.REPO, w["name"])
        assert cell.traffic["config"] == w["config"]
        assert hasattr(cell.driver, "Driver")
        for trace in (False, True):
            names = [m["name"] for m in harness.metrics_for(cell, trace)]
            assert names, (w["name"], trace)
            for n in names:
                mod = harness.load_module(
                    os.path.join(tiny.REPO, "bench", "metrics", f"{n}.py"),
                    "m_" + n.replace(".", "_"))
                assert callable(mod.read)
        assert set(cell.traffic["limits"]) >= {"pick_mismatches"}


def test_benchmark_json_keeps_to_the_contract_shape(bench):
    """Keys, names, units, bounds and per-cell coverage."""
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(tiny.REPO, c["file"]))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        for w in m["workloads"]:
            assert w in [x["name"] for x in bench["workloads"]]
            assert w in e2e[m["moves"]].get("workloads", [w])
    for w in bench["workloads"]:
        mine = [m for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2 and "setup_s" in [m["name"] for m in mine]
        assert any(w["name"] in m["workloads"] for m in bench["per_layer"])
        assert len(w["why"]) <= 200


def test_unknown_device_kind_is_an_error():
    """A device missing from the peak table is an error, not a default."""
    assert harness.device_peaks(tiny.REPO, "TPU v5 lite")["bf16_flops"] \
        == 197e12
    with pytest.raises(KeyError):
        harness.device_peaks(tiny.REPO, "TPU v9 imaginary")


def test_measurement_path_refuses_without_a_tpu():
    """The command exits non-zero and prints no result on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "fleet-image-100k.megatick", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tiny.REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_refuses_fewer_chips_than_the_cell_asks_for():
    """Four chips asked of a one-device host: no run."""
    with pytest.raises(harness.NoChip):
        harness.find_chips(4, require_tpu=False)


def test_a_missing_driver_or_reader_is_an_error(tmp_path):
    """A cell naming a driver no file provides does not run."""
    root = tiny.make_root(str(tmp_path), ["fleet-image-100k.megatick"])
    path = os.path.join(root, "bench", "traffic",
                        "fleet-image-100k.megatick.json")
    tr = tiny.load(path)
    tr["driver"] = "no_such_driver"
    with open(path, "w") as f:
        json.dump(tr, f)
    with pytest.raises(FileNotFoundError):
        harness.load_cell(root, "fleet-image-100k.megatick")
