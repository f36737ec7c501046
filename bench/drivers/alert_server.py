"""Driver: one closed-loop client on ``AlertServer.serve_one``.

Set-up makes the weights on the device from the seed, builds the
serving engine at the cell's batch and lengths and the ``AlertServer``
over it (which profiles every level: each level's prefill and decode
programs compile or load from the cache and run), and warms the
controller's select program.  The window sends one request after
another: a ``[batch, prompt_len]`` prompt drawn from the seed, a deadline
from one fixed sequence spread evenly over ``deadline_x`` times
``deadline_base_s`` (the deepest level's full-cap latency as once
profiled on the chip, fixed in the traffic file so that a faster program
meets more deadlines; the same sequence for every seed), and the
configuration's power budget times that deadline as the energy goal.
Each request's time is the wall time around ``serve_one`` (the
controller's pick, the level's generate, the feedback), and a request is
good when all its tokens came and that time is within its deadline.
"""

from __future__ import annotations

import sys
import time
import traceback

import numpy as np

from bench import lm_ref, serve_check, traffic_gen


def model_config(cfg: dict):
    """The configuration as the program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig

    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "nest_levels", "rope_theta", "norm_eps",
            "tie_embeddings", "dtype")
    return ModelConfig(name=cfg["name"], family=cfg["family"],
                       **{k: cfg[k] for k in keys})


class Driver:
    """The model cells' set-up, window and check."""

    def __init__(self, cell, seed: int, ctx, devices):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.ctx = ctx

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        """Weights, engine, server (level profiling), controller warm-up."""
        t0 = time.perf_counter()
        import jax

        from repro.core.controller import Goal
        from repro.core.power import PowerModel
        from repro.models.registry import build_model
        from repro.serving.alert_server import AlertServer
        from repro.serving.engine import ServeEngine

        cfg, tr = self.cfg, self.traffic
        ctl = cfg["controller"]
        split = self.setup_split = {"imports_s": time.perf_counter() - t0}
        t0 = time.perf_counter()
        self.params = lm_ref.make_params(cfg, self.seed)
        jax.block_until_ready(self.params)
        split["weights_s"] = time.perf_counter() - t0
        model = build_model(model_config(cfg))
        engine = ServeEngine(model, max_len=tr["prompt_len"]
                             + tr["gen_tokens"], batch_size=tr["batch"])
        # Each level's first generate loads (or compiles) its programs;
        # the rest of the server's start-up is profiling.
        gen, first = engine.generate, set()
        split["first_generates_s"] = 0.0

        def timed(params, prompt, n, level=None, **kw):
            t = time.perf_counter()
            out = gen(params, prompt, n, level=level, **kw)
            if level not in first:
                first.add(level)
                split["first_generates_s"] += time.perf_counter() - t
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
        split["profiling_s"] = time.perf_counter() - t0 - \
            split["first_generates_s"]
        engine.generate = gen
        split["profiled_top_level_s"] = float(
            self.server.table.latency[-1, -1])
        self.base = float(tr["deadline_base_s"])
        c = self.server.controller
        c.engine.select(c.slowdown.mu, c.slowdown.sigma, c.idle_power.phi,
                        np.asarray([self.base]),
                        energy_goal=self.base * ctl["power_budget_w"])
        split["controller_warmup_s"] = time.perf_counter() - t0 - \
            split["first_generates_s"] - split["profiling_s"]
        self._wrap(engine, c)

    def _wrap(self, engine, controller) -> None:
        """Keep each generate's tokens (the server drops them); in a
        traced run also time the controller, the prefill and the whole
        generate as host spans."""
        ctx = self.ctx
        gen = engine.generate
        self.generated: list = []

        def generate(*a, **kw):
            with ctx.span("bench.generate"):
                r = gen(*a, **kw)
            self.generated.append(r)
            return r

        engine.generate = generate
        if not ctx.trace:
            return
        pre = engine.prefill

        def prefill(*a, **kw):
            import jax
            with ctx.span("bench.prefill"):
                out = pre(*a, **kw)
                jax.block_until_ready(out.logits)
            return out

        engine.prefill = prefill
        for name in ("select", "observe"):
            fn = getattr(controller, name)

            def timed(*a, _fn=fn, **kw):
                with ctx.span("bench.controller"):
                    return _fn(*a, **kw)

            setattr(controller, name, timed)

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> None:
        """Serve requests one after another until ``seconds`` passed."""
        from repro.core.controller import Constraints

        tr = self.traffic
        rng = traffic_gen.seed_rng(self.seed, 7)
        budget = self.cfg["controller"]["power_budget_w"]
        vocab = self.cfg["vocab"]
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
                "complete": bool(r is not None and r["complete"]),
                "measured": out.latency, "level": out.level,
                "cap": out.power_cap, "missed": out.missed,
                "accuracy": out.accuracy, "energy": out.energy})
            self.ctx.unit_done()
        self.elapsed = clock() - t0

    def record(self) -> dict:
        """What the window did, for the metric readers."""
        tr = self.traffic
        reqs = self.requests
        flops = sum(lm_ref.request_flops(
            self.cfg, r["level"], tr["batch"], tr["prompt_len"],
            r["tokens"].shape[1]) for r in reqs if r["tokens"] is not None)
        return {"attempted": len(reqs) + self.failed, "failed": self.failed,
                "elapsed_s": self.elapsed,
                "latencies_s": [r["wall_s"] for r in reqs],
                "good": sum(1 for r in reqs
                            if r["complete"] and r["wall_s"] <= r["deadline"]),
                "decode_steps": sum(r["tokens"].shape[1] - 1 for r in reqs
                                    if r["tokens"] is not None),
                "units": len(reqs), "model_flops": flops}

    def release(self) -> None:
        """Keep what the check needs; drop the server and its caches."""
        c = self.server.controller
        t = self.server.table
        self.table_prog = {"latency": np.array(t.latency),
                           "run_power": np.array(t.run_power),
                           "caps": np.array(t.power_caps)}
        self.state_prog = {"mu": c.slowdown.mu, "sigma": c.slowdown.sigma,
                           "gain": c.slowdown.gain,
                           "q": c.slowdown.process_noise,
                           "phi": c.idle_power.phi,
                           "var": c.idle_power.variance}
        self.server = self.generated = None

    # ------------------------------------------------------------- check
    def sample(self) -> list:
        """The requests the model check reads: the one with the most
        served tokens and ``check_requests - 1`` more drawn from the seed."""
        done = [r for r in self.requests if r["tokens"] is not None]
        if not done:
            return []
        longest = max(range(len(done)),
                      key=lambda k: done[k]["tokens"].shape[1])
        rng = traffic_gen.seed_rng(self.seed, 11)
        rest = [k for k in range(len(done)) if k != longest]
        n = min(len(rest), self.traffic["check_requests"] - 1)
        pick = [longest] + sorted(rng.choice(rest, n, replace=False)
                                  .tolist()) if n else [longest]
        return [done[k] for k in pick]

    def check(self) -> dict:
        """The controller over every request, the model on the sample."""
        margin = self.traffic["tie_margin"]
        out = serve_check.controller_check(self.cfg, self.table_prog,
                                           self.requests, self.state_prog,
                                           margin)
        gaps = serve_check.logit_gaps(self.cfg, self.params, self.sample(),
                                      self.traffic["gen_tokens"])
        out["logit_gap"] = float(max(g.max() for g in gaps)) if gaps \
            else float("inf")
        return out

    def control(self) -> dict:
        """The float32 controller and the float8 model in the program's
        place, over the same requests."""
        out = serve_check.controller_control(
            self.cfg, self.table_prog["latency"][:, -1], self.requests,
            self.traffic["tie_margin"])
        gaps = serve_check.logit_gaps(self.cfg, self.params, self.sample(),
                                      self.traffic["gen_tokens"], fp8=True)
        out["logit_gap"] = float(max(g.max() for g in gaps)) if gaps \
            else float("inf")
        return out
