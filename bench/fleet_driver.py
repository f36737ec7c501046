"""What the fleet drivers share: one horizon served back to back.

Set-up draws one horizon from the seed, builds the gateway once and
serves the first ``warmup_horizon_x`` deadlines of it (its programs
compile or load from the cache).  The window serves the horizon again
and again until ``--seconds`` have passed, each run to completion, so
every offered request of a finished run has its disposition.  Each run
starts from fresh sessions, so every run serves the same work; the
window's last run, with the per-session state it ended on, is compared.
"""

from __future__ import annotations

import time

import numpy as np

from bench import fleet_check, fleet_inputs

# The gateway's disposition codes (``repro.traffic.gateway``), by name.
# A refusal is an answer of admission control, compared exactly with the
# reference; only a request left with no disposition is a failure.
DISPOSITIONS = {0: "served", 1: "failed_fast", 2: "refused_full"}


class FleetDriver:
    """Set-up, window, record and check of a fleet cell; a subclass says
    how to build its gateway (:meth:`make_gateway`) and read the state a
    run ended on (:meth:`final_state`)."""

    busy = False            # lanes stay busy past a round boundary

    def __init__(self, cell, seed: int, ctx, devices):
        self.cell = cell
        self.seed = seed
        self.ctx = ctx
        self.fleet = fleet_inputs.resolve(cell.config)
        self.traffic = cell.traffic
        self.tick = self.traffic["tick_x"] * self.fleet.t_goal

    def make_gateway(self, table):
        """The program's gateway over the program's ``table``."""
        raise NotImplementedError

    def final_state(self) -> dict:
        """Every session's filters and window after the window's last
        run, on the host by name."""
        raise NotImplementedError

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        """Draw the horizon, build the gateway, serve the warm-up."""
        fl = self.fleet
        t0 = time.perf_counter()
        self.req = fleet_inputs.draw(fl, self.traffic, self.seed)
        t1 = time.perf_counter()
        self.work = fleet_inputs.program_workload(fl, self.req)
        t2 = time.perf_counter()
        self.gw = self.make_gateway(fleet_inputs.program_table(fl.table))
        # Warm up on the horizon's opening deadlines: the same sessions,
        # so every program the window runs is ready.
        sessions, requests = self.work
        until = self.traffic["warmup_horizon_x"] * fl.t_goal
        self.gw.run(sessions, [r for r in requests if r.arrival < until])
        self.setup_split = {"draw_s": t1 - t0, "objects_s": t2 - t1,
                            "warmup_s": time.perf_counter() - t2}

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> None:
        """Serve the horizon until ``seconds`` have passed."""
        self.offered = self.rounds = self.runs = 0
        self.dispositions = dict.fromkeys(DISPOSITIONS.values(), 0)
        self.before()
        clock = self.ctx.clock
        t0 = clock()
        while True:
            with self.ctx.span("bench.horizon"):
                res = self.gw.run(*self.work)
            self.runs += 1
            self.offered += res.offered
            codes, counts = np.unique(res.status, return_counts=True)
            for code, n in zip(codes.tolist(), counts.tolist()):
                name = DISPOSITIONS.get(code, "none")
                self.dispositions[name] = self.dispositions.get(name, 0) + n
            self.rounds += res.n_rounds
            self.ctx.unit_done()
            if clock() - t0 >= seconds:
                break
        self.elapsed = clock() - t0
        self.last = res

    def before(self) -> None:
        """Called once before the window opens."""

    def record(self) -> dict:
        """What the window did, for the metric readers."""
        return {"attempted": self.offered,
                "failed": self.dispositions.get("none", 0),
                "elapsed_s": self.elapsed, "decided": self.offered,
                "dispositions": self.dispositions, "rounds": self.rounds,
                "units": self.runs}

    def release(self) -> None:
        """Bring the last run's outcome and final state to the host; drop
        the gateway."""
        self.checked = fleet_inputs.gateway_output(self.last,
                                                   self.final_state())
        self.gw = self.work = self.last = None

    # ------------------------------------------------------------- check
    def _compare(self, prog):
        fl = self.fleet
        return fleet_check.compare(
            fl.table, self.req, prog, gateway=fl.gateway(self.tick),
            sessions=fl.sessions(), margin=self.traffic["tie_margin"],
            busy=self.busy)

    def check(self) -> dict:
        """The compared numbers of the window's last run."""
        got = self._compare(self.checked)
        return {k: got[k] for k in self.traffic["limits"]}

    def control(self) -> dict:
        """The same comparison with the float32 reference in the
        program's place, over the same horizon."""
        fl = self.fleet
        prog = fleet_check.control_program(
            fl.table, self.req, gateway=fl.gateway(self.tick),
            sessions=fl.sessions(), busy=self.busy)
        got = self._compare(prog)
        return {k: got[k] for k in self.traffic["limits"]}
