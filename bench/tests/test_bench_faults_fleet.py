"""The fleet comparison catches a broken timed path.

Each test breaks the program underneath a tiny run of a fleet cell —
the timed path itself, not the check — and the run must come out not
correct.  (The cells run on one chip, so there is no exchange between
chips to leave out.)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import fleet_inputs, harness
from bench.tests import tiny

CELL = "fleet-image-100k.megatick"
FINE = "fleet-image-100k.finetick"


def run(tmp_path, cell=CELL):
    """One tiny run of a fleet cell; returns the result object."""
    root = tiny.make_root(str(tmp_path), [cell])
    return harness.run(root, cell, 31, 1.0, False, require_tpu=False)


def test_state_left_unchanged_is_caught(tmp_path, monkeypatch):
    """The feedback step returns every session's filters as they were."""
    import repro.traffic.megatick as mt

    def frozen(mu, sd, gain, q, *a):
        phi, var = a[8], a[9]
        return mu, sd, gain, q, phi, var

    monkeypatch.setattr(mt, "fused_fleet_step", frozen)
    res = run(tmp_path)
    assert not res["correct"]
    assert res["compared"]["outcome_gap"]["value"] > 1e-3


def test_half_the_lanes_left_out_is_caught(tmp_path, monkeypatch):
    """Delivery drops the odd lanes of every round (their run time,
    accuracy and energy come back as zero)."""
    import repro.traffic.megatick as mt

    real = mt.deliver_step

    def half(i, j, scale, dvec, phi_true, **kw):
        out = list(real(i, j, scale, dvec, phi_true, **kw))
        keep = (jnp.arange(i.shape[0]) % 2) == 0
        for k in (0, 1, 2):
            out[k] = jnp.where(keep, out[k], 0.0)
        return tuple(out)

    monkeypatch.setattr(mt, "deliver_step", half)
    res = run(tmp_path)
    assert not res["correct"]


def test_an_altered_answer_is_caught(tmp_path, monkeypatch):
    """The select step moves lane 0's pick to the next power cap."""
    from repro.core.batched import BatchedAlertEngine

    real = BatchedAlertEngine.select_step_impl

    def altered(self):
        step = real(self)
        n_l = self.table.latency.shape[1]

        def call(*a):
            i, j, *rest = step(*a)
            j = j.at[0].set((j[0] + 1) % n_l)
            return (i, j, *rest)
        return call

    monkeypatch.setattr(BatchedAlertEngine, "select_step_impl", altered)
    res = run(tmp_path)
    assert not res["correct"]
    assert res["compared"]["pick_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", [CELL, FINE])
def test_the_control_fails(tmp_path, cell):
    """The reference in float32, in the program's place, is not correct
    under the cell's limits."""
    root = tiny.make_root(str(tmp_path), [cell])
    c = harness.load_cell(root, cell)
    drv = c.driver.Driver(c, 5, harness.Context(False, 0.0), None)
    drv.req = fleet_inputs.draw(drv.fleet, c.traffic, 5)
    got = drv.control()
    limits = c.traffic["limits"]
    assert got["outcome_gap"] > 1e-8
    assert any(got[k] > limits[k] for k in limits), got


def test_host_loop_state_left_unchanged_is_caught(tmp_path, monkeypatch):
    """The fine-tick gateway's feedback step does nothing."""
    import repro.traffic.gateway as gw

    monkeypatch.setattr(gw, "observe_fleet", lambda *a, **kw: None)
    res = run(tmp_path, FINE)
    assert not res["correct"]


def test_host_loop_half_the_lanes_left_out_is_caught(tmp_path,
                                                      monkeypatch):
    """The fine-tick gateway's delivery zeroes the odd lanes' outcome."""
    import dataclasses

    import repro.traffic.gateway as gw

    real = gw.deliver_tick

    def half(*a, **kw):
        d = real(*a, **kw)
        odd = (np.arange(d.latency.shape[0]) % 2) == 1
        return dataclasses.replace(
            d, accuracy=np.where(odd, 0.0, d.accuracy),
            energy=np.where(odd, 0.0, d.energy))

    monkeypatch.setattr(gw, "deliver_tick", half)
    res = run(tmp_path, FINE)
    assert not res["correct"]


def test_host_loop_altered_answer_is_caught(tmp_path, monkeypatch):
    """The fine-tick gateway's select moves lane 0's pick."""
    import dataclasses

    from repro.core.batched import BatchedAlertEngine

    real = BatchedAlertEngine.select

    def altered(self, *a, **kw):
        b = real(self, *a, **kw)
        j = np.array(b.power_index)
        j[0] = (j[0] + 1) % self.table.latency.shape[1]
        return dataclasses.replace(b, power_index=j)

    monkeypatch.setattr(BatchedAlertEngine, "select", altered)
    res = run(tmp_path, FINE)
    assert not res["correct"]
