"""Driver: one closed-loop client on ``AlertServer.serve_one`` over the
Jamba hybrid (Mamba-1 + NoPE GQA + a share of a 16-expert MoE).

The client, the records and the controller replay are those of
:mod:`bench.drivers.alert_server`: one request after another, a
``[batch, prompt_len]`` prompt drawn from the seed over the configuration's
vocabulary slice, a deadline from the fixed sequence over ``deadline_x``
times ``deadline_base_s`` (the single level's full-cap latency as once
profiled on the chip, fixed in the traffic file), the power budget times
that deadline as the energy goal, and the wall time around ``serve_one``
as the request's time.  What differs: the configuration file holds
``config.json``'s keys and is read through
:func:`bench.hybrid_ref.model_config` before anything is allocated; the
weights come from :func:`bench.hybrid_ref.make_params` with the
configuration's fixed ``weights_seed``, the same model in every run (the
seed draws the prompts): random weights greedily repeat a handful of
tokens, whose routing then sets how many held experts each decode step
reads, so weights drawn per seed would make each seed a model of its own
speed; each request keeps the expert choices the timed path made and its
routed-here and experts-hit counts; a traced run also reads the decode
program's device time from the trace (:func:`decode_device_time`); and
the model check is :func:`bench.hybrid_ref.check` (routing and logits
against the blocked float32 reference).
"""

from __future__ import annotations

import gc
import glob
import os
import sys
import time
import traceback

import numpy as np

from bench import hybrid_ref, serve_check, traffic_gen, xtrace
from bench.drivers import alert_server

DECODE_MODULE = "jit_decode"


def decode_device_time(profile_dir: str | None):
    """``(seconds, count)`` of the decode program's runs on the device in
    the profiler trace under ``profile_dir``, inside its ``bench.window``
    annotation; ``None`` without a trace."""
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True) if profile_dir else []
    if not paths:
        return None
    from jax.profiler import ProfileData

    devices, host = xtrace.read_planes(ProfileData.from_file(paths[0]))
    win = [(s, e) for s, e, n in host if n == xtrace.WINDOW]
    if not win:
        return None
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    runs = xtrace.clip([(s, e) for _, mods in devices.values()
                        for s, e, n in mods if n == DECODE_MODULE], lo, hi)
    return 1e-9 * sum(e - s for s, e in runs), len(runs)


class Driver(alert_server.Driver):
    """The hybrid cell's set-up, window and check."""

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        """Configuration, weights, engine, server (profiling), controller
        warm-up."""
        t0 = time.perf_counter()
        import jax

        from repro.core.controller import Goal
        from repro.core.power import PowerModel
        from repro.models.registry import build_model
        from repro.serving.alert_server import AlertServer
        from repro.serving.engine import ServeEngine

        cfg, tr = self.cfg, self.traffic
        ctl = cfg["controller"]
        # A program whose ModelConfig lacks the hybrid's keys fails here,
        # before any weight is made.
        self.mcfg = hybrid_ref.model_config(cfg)
        split = self.setup_split = {"imports_s": time.perf_counter() - t0}
        t0 = time.perf_counter()
        # A driver set up before this one in the same process (the
        # control's seeds) lets go of its weights only in a reference
        # cycle, and two sets do not fit on one chip.
        gc.collect()
        self.params = hybrid_ref.make_params(
            self.mcfg, cfg["assumed"]["weights_seed"])
        jax.block_until_ready(self.params)
        split["weights_s"] = time.perf_counter() - t0
        engine = ServeEngine(build_model(self.mcfg),
                             max_len=tr["prompt_len"] + tr["gen_tokens"],
                             batch_size=tr["batch"])
        gen, first = engine.generate, []

        def timed(*a, **kw):
            t = time.perf_counter()
            out = gen(*a, **kw)
            if not first:
                first.append(time.perf_counter() - t)
            return out

        engine.generate = timed
        t0 = time.perf_counter()
        pm = ctl["power_model"]
        self.server = AlertServer(
            engine, self.params, cfg["assumed"]["level_accuracies"],
            Goal(ctl["goal"]), power_model=PowerModel(
                pm["p_idle"], pm["p_tdp"], pm["min_fraction"]),
            n_power_buckets=ctl["power_buckets"],
            profile_iters=ctl["profile_iters"], q_fail=ctl["q_fail"],
            prompt_len=tr["prompt_len"], gen_tokens=tr["gen_tokens"])
        split["first_generates_s"] = first[0]
        split["profiling_s"] = time.perf_counter() - t0 - first[0]
        engine.generate = gen
        split["profiled_top_level_s"] = float(
            self.server.table.latency[-1, -1])
        self.base = float(tr["deadline_base_s"])
        c = self.server.controller
        t0 = time.perf_counter()
        c.engine.select(c.slowdown.mu, c.slowdown.sigma, c.idle_power.phi,
                        np.asarray([self.base]),
                        energy_goal=self.base * ctl["power_budget_w"])
        split["controller_warmup_s"] = time.perf_counter() - t0
        self._wrap(engine, c)

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> None:
        """Serve requests one after another until ``seconds`` passed."""
        from repro.core.controller import Constraints

        tr = self.traffic
        rng = traffic_gen.seed_rng(self.seed, 7)
        budget = self.cfg["controller"]["power_budget_w"]
        vocab = self.cfg["vocab_size"]
        self.requests: list = []
        self.failed = 0
        clock = self.ctx.clock
        t0 = clock()
        while clock() - t0 < seconds:
            prompt = rng.integers(0, vocab, (tr["batch"], tr["prompt_len"]),
                                  dtype=np.int32)
            deadline = float(traffic_gen.spread_deadline(
                len(self.requests) + self.failed, *tr["deadline_x"],
                self.base))
            cons = Constraints.from_power_budget(deadline, budget)
            n_gen = len(self.generated)
            # The profiler stops only between requests, so a request
            # begun under it ran wholly inside the trace.
            traced = self.ctx.profiling
            t1 = time.perf_counter()
            try:
                out = self.server.serve_one(prompt, cons)
            except Exception:  # noqa: BLE001 — a failed request is counted
                traceback.print_exc(file=sys.stderr)
                self.failed += 1
                continue
            wall = time.perf_counter() - t1
            if not np.isfinite(out.latency):
                self.failed += 1
            r = self.generated[n_gen] if len(self.generated) > n_gen \
                else None
            self.requests.append({
                "wall_s": wall, "deadline": deadline, "prompt": prompt,
                "tokens": None if r is None else r["tokens"],
                "routing": None if r is None else r["routing"],
                "assigned": 0 if r is None else r["moe_assigned_here"],
                "hit": 0 if r is None else r["moe_experts_hit"],
                "traced": traced,
                "complete": bool(r is not None and r["complete"]),
                # The table's one row: the model at its single level.
                "measured": out.latency, "level": 1,
                "cap": out.power_cap, "missed": out.missed,
                "accuracy": out.accuracy, "energy": out.energy})
            self.ctx.unit_done()
        self.elapsed = clock() - t0

    def record(self) -> dict:
        """What the window did, for the metric readers."""
        tr = self.traffic
        reqs = [r for r in self.requests if r["tokens"] is not None]
        flops = sum(hybrid_ref.request_flops(
            self.mcfg, tr["batch"], tr["prompt_len"], r["tokens"].shape[1],
            r["assigned"]) for r in reqs)
        out = {"attempted": len(self.requests) + self.failed,
               "failed": self.failed, "elapsed_s": self.elapsed,
               "latencies_s": [r["wall_s"] for r in self.requests],
               "good": sum(1 for r in self.requests
                           if r["complete"] and r["wall_s"] <= r["deadline"]),
               "decode_steps": sum(r["tokens"].shape[1] - 1 for r in reqs),
               "units": len(self.requests), "model_flops": flops}
        out.update(self._decode_traffic(reqs))
        return out

    def _decode_traffic(self, reqs: list) -> dict:
        """The decode steps of the requests served under the profiler:
        the least bytes they had to move (``decode_bytes``, from each
        request's steps, cache lengths and held experts hit) and the
        decode program's device seconds in the trace
        (``decode_device_s``).  Empty without a trace, or where the
        trace's decode runs are not those steps one for one (the bytes
        would not be the time's)."""
        got = decode_device_time(self.ctx.profile_dir)
        traced = [r for r in reqs if r["traced"]]
        steps = sum(r["tokens"].shape[1] - 1 for r in traced)
        if got is None or got[1] != steps or steps == 0:
            return {}
        parts = hybrid_ref.decode_step_bytes(self.mcfg,
                                             self.traffic["batch"])
        s0 = self.traffic["prompt_len"]
        n_bytes = sum(parts["fixed"] + parts["per_position"] * (s0 + i + 1)
                      for r in traced for i in range(r["tokens"].shape[1] - 1))
        n_bytes += parts["per_expert"] * sum(r["hit"] for r in traced)
        return {"decode_bytes": n_bytes, "decode_device_s": got[0]}

    # ------------------------------------------------------------- check
    def _model_check(self, fp8: bool) -> dict:
        """Routing and logits of the sampled requests against the
        blocked float32 reference (a float8 pass in the program's place
        with ``fp8``)."""
        return hybrid_ref.check(self.params, self.mcfg, self.sample(),
                                self.traffic["gen_tokens"],
                                self.traffic["routing_margin"], fp8=fp8)

    def check(self) -> dict:
        """The controller over every request, the model on the sample."""
        out = serve_check.controller_check(
            self.cfg, self.table_prog, self.requests, self.state_prog,
            self.traffic["tie_margin"])
        out.update(self._model_check(False))
        return out

    def control(self) -> dict:
        """The float32 controller and the float8 model in the program's
        place, over the same requests."""
        out = serve_check.controller_control(
            self.cfg, self.table_prog["latency"][:, -1], self.requests,
            self.traffic["tie_margin"])
        out.update(self._model_check(True))
        return out
