"""Serving launcher: restore a trained checkpoint (or init fresh weights)
and run the ALERT runtime over a synthetic request stream.

    PYTHONPATH=src python -m repro.launch.serve --arch alert-anytime-120m \
        [--reduced] --requests 40 [--ckpt-dir DIR] \
        [--goal max_acc|min_energy] [--deadline-scale 1.2]

This is the production shape of examples/serve_alert.py: checkpoint
restore, level profiling, deadline-EDF batching, the Kalman/staircase
controller, and a per-phase report.  The model runs at its published
widths, vocabulary and dtype; ``--reduced`` serves the small test-size
variant in float32 with a 32-token vocabulary instead.
"""

from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from repro import configs
from repro.checkpoint import io as ckpt_io
from repro.core.controller import Constraints, Goal
from repro.data.synthetic import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serving.alert_server import AlertServer
from repro.serving.engine import ServeEngine
from repro.train.losses import token_accuracy


def build_served_model(arch: str, *, reduced: bool = False, seed: int = 0):
    """``(cfg, model, params)`` for serving ``arch``: the published config,
    or with ``reduced`` the small variant in float32 with a 32-token
    vocabulary; weights drawn from ``seed``.  A model without nesting
    levels gets two, so the controller has a staircase to pick from."""
    if reduced:
        cfg = configs.get_reduced(arch).replace(dtype="float32", vocab=32)
    else:
        cfg = configs.get_config(arch)
    if cfg.nest_levels <= 1:
        cfg = cfg.replace(nest_levels=2)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(seed))


def level_accuracies(model, params, data: SyntheticLM) -> list[float]:
    """Token accuracy of every nesting level on one held-out batch."""
    evalb = {k: jax.numpy.asarray(v)
             for k, v in data.batch_at(10_000).items()}
    return [float(token_accuracy(
        model.train_logits(params, evalb, level=k)[0], evalb["labels"]))
        for k in range(1, model.cfg.nest_levels + 1)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="alert-anytime-120m",
                    choices=configs.ALL_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the small test-size variant")
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--goal", default="max_acc",
                    choices=["max_acc", "min_energy"])
    ap.add_argument("--deadline-scale", type=float, default=1.2,
                    help="deadline as a multiple of the deepest level's "
                         "profiled latency")
    ap.add_argument("--power-budget", type=float, default=150.0)
    ap.add_argument("--accuracy-goal", type=float, default=0.3)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    cfg, model, params = build_served_model(args.arch, reduced=args.reduced)
    if args.ckpt_dir and os.path.exists(args.ckpt_dir):
        try:
            restored, step = ckpt_io.restore(args.ckpt_dir, params)
            params = restored
            print(f"[serve] restored params from step {step}")
        except Exception as e:
            print(f"[serve] checkpoint restore failed ({e}); "
                  f"serving fresh init")

    # measure per-level accuracy on held-out synthetic data
    data = SyntheticLM(vocab=cfg.vocab, seq_len=32,
                       global_batch=args.batch, noise=0.05)
    accs = level_accuracies(model, params, data)
    print(f"[serve] level accuracies: "
          + " ".join(f"L{i + 1}={a:.3f}" for i, a in enumerate(accs)))

    goal = Goal.MAXIMIZE_ACCURACY if args.goal == "max_acc" \
        else Goal.MINIMIZE_ENERGY
    engine = ServeEngine(model, max_len=32, batch_size=args.batch)
    server = AlertServer(engine, params, accs, goal, prompt_len=8,
                         gen_tokens=4)
    base = float(server.table.latency[-1, -1])
    print(f"[serve] profiled level latencies: "
          + " ".join(f"{t:.3f}s" for t in server.table.latency[:, -1]))

    rng = np.random.default_rng(0)
    results = []
    for i in range(args.requests):
        deadline = base * args.deadline_scale * rng.uniform(0.85, 1.25)
        if goal is Goal.MAXIMIZE_ACCURACY:
            cons = Constraints.from_power_budget(deadline,
                                                 args.power_budget)
        else:
            cons = Constraints(deadline,
                               accuracy_goal=args.accuracy_goal)
        prompt = np.asarray(data.batch_at(20_000 + i)
                            ["tokens"][:args.batch, :8])
        r = server.serve_one(prompt, cons)
        results.append(r)
        if i % 10 == 0:
            print(f"  req {i:3d} level={r.level} cap={r.power_cap:.0f}W "
                  f"lat={r.latency:.3f}s missed={r.missed}")
    acc = np.mean([r.accuracy for r in results])
    miss = np.mean([r.missed for r in results])
    en = np.mean([r.energy for r in results])
    print(f"[serve] {len(results)} requests: delivered_acc={acc:.3f} "
          f"miss_rate={miss:.2f} mean_energy={en:.1f}J "
          f"(slowdown mu={server.controller.slowdown.mu:.2f})")


if __name__ == "__main__":
    main()
