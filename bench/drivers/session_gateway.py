"""Driver: ``SessionGateway.run``, the host round loop, serving a fleet
horizon back to back (see :mod:`bench.fleet_driver`).

A tick finer than the deadline keeps lanes busy across round
boundaries, which only this gateway serves.  The state a run ends on
lives in the gateway's lane banks and its store of paged-out sessions,
and is read after the window.  In a
traced run a flight recorder is attached and its ``serve_round`` and
``page_in`` spans are read.
"""

from __future__ import annotations

import numpy as np

from bench import alert_ref
from bench.fleet_driver import FleetDriver


class Driver(FleetDriver):
    """The fine-tick cell."""

    busy = True

    def make_gateway(self, table):
        """A ``SessionGateway``; with a flight recorder when tracing."""
        from repro.obs import FlightRecorder
        from repro.traffic.gateway import SessionGateway

        fl = self.fleet
        self.obs = FlightRecorder() if self.ctx.trace else None
        return SessionGateway(table, fl.lanes, phi_true=fl.cfg["phi_true"],
                              tick=self.tick, max_queue=fl.max_queue,
                              accuracy_window=fl.cfg["accuracy_window"],
                              obs=self.obs)

    def before(self) -> None:
        """Forget the warm-up's spans."""
        if self.obs is not None:
            self.obs.spans.events.clear()

    def record(self) -> dict:
        """The window's counts and the recorder's round and paging spans."""
        out = super().record()
        if self.obs is not None:
            ev = self.obs.spans.events
            for name in ("serve_round", "page_in"):
                out[f"{name}_s"] = [e["dur_us"] * 1e-6 for e in ev
                                    if e["name"] == name and e["ph"] == "X"]
        return out

    def final_state(self) -> dict:
        """Every session's filters and window after the last run: those
        on a lane from the lane banks, those paged out from the store,
        the rest never served (at their priors)."""
        gw, fl = self.gw, self.fleet
        n = fl.n_sessions
        st = alert_ref.fresh_state(n, fl.cfg["tenant"]["accuracy_goal"],
                                   fl.cfg["accuracy_window"])
        names = {"slow": {"mu": "mu", "sigma": "sigma", "gain": "gain",
                          "q": "process_noise"},
                 "idle": {"phi": "phi", "var": "variance"},
                 "goal": {"buf": "buf", "pos": "pos", "count": "count"}}
        lanes = np.nonzero(gw._resident >= 0)[0]
        sids = gw._resident[lanes]
        banks = {"slow": gw.slow.export_lanes(lanes),
                 "idle": gw.idle.export_lanes(lanes),
                 "goal": gw.goal_bank.export_lanes(lanes)}
        for part, keys in names.items():
            for mine, theirs in keys.items():
                st[mine][sids] = banks[part][theirs]
        for sid, entry in gw._store.items():
            for part, keys in names.items():
                for mine, theirs in keys.items():
                    st[mine][sid] = entry[part][theirs][0]
        return st
