"""DEMO: the fused Pallas decision kernel vs the XLA scoring path.

One churning fleet tick, twice: ``BatchedAlertEngine`` (default XLA
backend) and ``BatchedAlertEngine(backend="pallas")`` — the lane-tiled
`repro.kernels.alert_select` kernel that fuses the Eq. 7/10 staircase
probes, Eq. 9 energy, the Eq. 4/5 feasibility + Section 3.3 relaxation,
and the ``[K·L]`` argmin into a single pass over ``[S, K, L]``
(docs/KERNELS.md).  The demo drives a goal-mixed S=512 fleet through
select → feedback ticks with 10 % lane churn, asserting on every tick
that the two backends pick the same configurations on every lane that
clears the kernel's tie margins (docs/KERNELS.md) and that neither
re-traces while lanes recycle; per-tick wall times are printed for both
(on CPU the kernel runs in Pallas *interpret* mode — the point here is
the precision and no-retrace contracts, not CPU speed).

    PYTHONPATH=src python examples/kernel_demo.py [--streams 512]
"""

import argparse
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # the demo builds its table via benchmarks.common
    sys.path.insert(0, _ROOT)

from benchmarks.common import deadline_range, family_table  # noqa: E402
from repro.core.batched import BatchedAlertEngine  # noqa: E402
from repro.core.kalman import (IdlePowerFilterBank,  # noqa: E402
                               SlowdownFilterBank, observe_fleet)
from repro.kernels.alert_select import (clear_lanes,  # noqa: E402
                                        margin_report)


def main():
    """Run the churning pick-parity demo (see module docstring)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=512)
    ap.add_argument("--ticks", type=int, default=8)
    args = ap.parse_args()

    s = args.streams
    table = family_table("image")
    k, l = table.latency.shape
    dls = deadline_range(table, 5)
    med_en = float(np.median(table.run_power) * np.median(table.latency))
    rng = np.random.default_rng(0)

    print(f"[1/3] engines over the 'image' family table "
          f"(K={k} configs x L={l} power caps), S={s} lanes...")
    xla = BatchedAlertEngine(table, None)
    pal = BatchedAlertEngine(table, None, backend="pallas")

    slow, idle = SlowdownFilterBank(s), IdlePowerFilterBank(s)
    act = rng.random(s) < 0.9
    gk = rng.integers(0, 2, s)
    d = rng.choice(dls, s)
    kw = dict(accuracy_goal=rng.uniform(0.5, 0.9, s),
              energy_goal=rng.uniform(0.5, 3.0, s) * med_en,
              predictions=False)
    # warmup both executables, and the margin check's estimate, outside
    # the timed loop
    for e in (xla, pal):
        e.select(slow.mu, slow.sigma, idle.phi, d, goal_kind=gk,
                 active=act, **kw)
    xla.estimate(slow.mu, slow.sigma, idle.phi, d, active=act)
    n0x, n0p = xla.n_compiles(), pal.n_compiles()

    print(f"[2/3] {args.ticks} churning ticks (10 %/tick, mixed "
          f"Eq. 4/Eq. 5 tenants), pick parity asserted per tick:")
    n_churn = max(s // 10, 1)
    idle_p, active_p = 0.25 * np.ones(s), np.ones(s)
    for tick in range(args.ticks):
        # churn: retire/admit a tenth of the fleet into recycled lanes
        lanes = rng.integers(0, s, n_churn)
        slow.reset_lanes(lanes)
        idle.reset_lanes(lanes)
        gk[lanes] = rng.integers(0, 2, n_churn)
        d[lanes] = rng.choice(dls, n_churn)
        act[lanes] = rng.random(n_churn) < 0.9
        t0 = time.perf_counter()
        bx = xla.select(slow.mu, slow.sigma, idle.phi, d, goal_kind=gk,
                        active=act, **kw)
        t_x = time.perf_counter() - t0
        t0 = time.perf_counter()
        bp = pal.select(slow.mu, slow.sigma, idle.phi, d, goal_kind=gk,
                        active=act, **kw)
        t_p = time.perf_counter() - t0
        est = xla.estimate(slow.mu, slow.sigma, idle.phi, d, active=act)
        rep = margin_report(bx, bp, clear_lanes(
            est.accuracy, est.energy, kw["accuracy_goal"],
            kw["energy_goal"], gk, act), predictions=False)
        assert rep["mismatches"] == 0, \
            f"tick {tick}: pallas picks diverged from XLA: {rep}"
        # shared feedback so both backends score identical state next tick
        prof = table.latency[bx.model_index, bx.power_index]
        observe_fleet(slow, idle, prof * rng.lognormal(0.0, 0.1, s), prof,
                      idle_power=idle_p, active_power=active_p, mask=act)
        print(f"  tick {tick}: xla {t_x * 1e3:6.2f} ms | pallas "
              f"{t_p * 1e3:6.2f} ms | {rep['n_differ']} near-tie picks "
              f"differ, {rep['n_clear']} clear lanes agree")

    assert xla.n_compiles() == n0x and pal.n_compiles() == n0p, \
        "churn re-traced an engine"
    print(f"[3/3] compile counts flat under churn: xla {n0x}, "
          f"pallas {n0p} (one executable each — goal flips, lane "
          f"recycling, and deadline changes are runtime arrays)")
    print("OK: fused Pallas kernel == XLA decision path outside the tie "
          "margins, tick for tick.")


if __name__ == "__main__":
    main()
