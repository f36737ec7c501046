"""The model-cell comparison catches a broken timed path.

Each test breaks the program underneath a tiny run of each model
cell, and the run must come out not correct.  (The cells run on one chip, so there is no
exchange between chips to leave out.)
"""

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

CELLS = ["alert-anytime-120m.decode", "alert-anytime-120m.oneshot"]


def run(tmp_path, cell):
    """One tiny run of a model cell; returns the result object."""
    root = tiny.make_root(str(tmp_path), [cell])
    return harness.run(root, cell, 41, 1.0, False, require_tpu=False)


def patch_generate(monkeypatch, edit):
    """Make every generate's tokens pass through ``edit``."""
    from repro.serving.engine import ServeEngine

    real = ServeEngine.generate

    def generate(self, *a, **kw):
        r = real(self, *a, **kw)
        r["tokens"] = edit(np.array(r["tokens"]))
        return r

    monkeypatch.setattr(ServeEngine, "generate", generate)


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_is_caught(tmp_path, cell, monkeypatch):
    """The controller's feedback step does nothing."""
    from repro.core.controller import AlertController

    monkeypatch.setattr(AlertController, "observe",
                        lambda self, *a, **kw: None)
    res = run(tmp_path, cell)
    assert not res["correct"]
    assert res["compared"]["controller_gap"]["value"] > 1e-3


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out_is_caught(tmp_path, cell, monkeypatch):
    """The second half of every batch gets no real tokens."""
    def half(tok):
        tok[tok.shape[0] // 2:] = 0
        return tok

    patch_generate(monkeypatch, half)
    res = run(tmp_path, cell)
    assert not res["correct"]
    assert res["compared"]["logit_gap"]["value"] > \
        res["compared"]["logit_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_token_is_caught(tmp_path, cell, monkeypatch):
    """One served token changes where the engine produces it."""
    def alter(tok):
        tok[0, -1] = (tok[0, -1] + 1) % 256
        return tok

    patch_generate(monkeypatch, alter)
    res = run(tmp_path, cell)
    assert not res["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_fails(tmp_path, name):
    """The float32 controller and the float8 model, in the program's
    place, are not correct under the cell's limits."""
    root = tiny.make_root(str(tmp_path), [name])
    cell = harness.load_cell(root, name)
    drv = cell.driver.Driver(cell, 6, harness.Context(False, 0.0), None)
    drv.setup()
    drv.window(1.0)
    drv.release()
    got = drv.control()
    limits = cell.traffic["limits"]
    assert any(got[k] > limits[k] for k in limits), got
