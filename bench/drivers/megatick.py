"""Driver: ``MegatickGateway.run`` serving a fleet horizon back to back
(see :mod:`bench.fleet_driver`).

The per-session state a run ends on is the state its last device scan
chunk returns, kept as it comes out of the gateway's chunk program.
"""

from __future__ import annotations

import numpy as np

from bench.fleet_driver import FleetDriver

CARRY = ("mu", "sigma", "gain", "q", "phi", "var", "buf", "pos", "count")


class Driver(FleetDriver):
    """The megatick cell."""

    def make_gateway(self, table):
        """A ``MegatickGateway`` whose chunk program's returned state is
        kept (the last chunk's is the run's final per-session state)."""
        from repro.traffic.megatick import MegatickGateway

        fl = self.fleet
        gw = MegatickGateway(table, fl.lanes, phi_true=fl.cfg["phi_true"],
                             tick=self.tick, max_queue=fl.max_queue,
                             accuracy_window=fl.cfg["accuracy_window"],
                             chunk=self.traffic["chunk_rounds"])
        self._carry = None
        chunk_fn = gw._chunk_fn

        def keep_carry(*key, **kw):
            fn = chunk_fn(*key, **kw)

            def call(*args):
                out = fn(*args)
                self._carry = out[0]
                return out
            return call

        gw._chunk_fn = keep_carry
        return gw

    def before(self) -> None:
        """Start the phase timers' window totals."""
        self._plan0 = self.gw.total_plan_s
        self._scan0 = self.gw.total_scan_s

    def record(self) -> dict:
        """The window's counts and the planner's and scan's time."""
        out = super().record()
        out["plan_s"] = self.gw.total_plan_s - self._plan0
        out["scan_s"] = self.gw.total_scan_s - self._scan0
        return out

    def final_state(self) -> dict:
        """The state the window's last chunk returned, by name."""
        return {k: np.asarray(v) for k, v in zip(CARRY, self._carry)}
