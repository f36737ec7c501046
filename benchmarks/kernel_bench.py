"""Paper §4.3 "infrastructure-induced overheads": the nested_matmul kernel
makes partial-level execution pay only the triangular-prefix FLOPs, and
full-level execution pay ~2/3 of dense (pow2 stripes) instead of the up-to
+50 % slowdown the paper measured on PyTorch/TF.

Measured here (CPU host): per-level wall time of the jitted block-
triangular path vs the masked-dense path, plus the analytic kernel FLOPs
staircase (what the Pallas grid executes on TPU).  Also microbenches the
other kernels' jitted ref paths and the fused `alert_select` decision
kernel (interpret mode, bitwise pick parity asserted — docs/KERNELS.md;
TPU wall-times are out of scope for this container — see DESIGN.md §9 on
how perf is tracked here).
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # allow `python benchmarks/kernel_bench.py`
    sys.path.insert(0, _ROOT)

from repro.core.nesting import (StripeSpec, nested_linear_blocks,
                                nested_linear_masked)
from repro.kernels import ref
from repro.kernels.nested_matmul import nested_matmul_flops


def _timeit(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def run() -> dict:
    levels, d = 4, 512
    spec = StripeSpec.pow2(d, levels)
    m = 512
    x = jax.random.normal(jax.random.PRNGKey(0), (m, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, d))

    dense_flops = 2 * m * d * d
    flops = [nested_matmul_flops(m, spec, spec, level=k)
             for k in range(1, levels + 1)]
    t_masked = _timeit(jax.jit(lambda x, w: nested_linear_masked(
        x, w, spec, spec)), x, w)
    t_levels = []
    for k in range(1, levels + 1):
        fn = jax.jit(lambda x, w, k=k: nested_linear_blocks(
            x, w, spec, spec, level=k))
        t_levels.append(_timeit(fn, x, w))

    out = {
        "flops_fraction_per_level": [f / dense_flops for f in flops],
        "time_masked_dense_us": t_masked * 1e6,
        "time_per_level_us": [t * 1e6 for t in t_levels],
        "full_level_flops_fraction": flops[-1] / dense_flops,
    }
    out["checks"] = {
        "flops_staircase_monotone": bool(np.all(np.diff(flops) > 0)),
        "full_level_saves_vs_dense": out["full_level_flops_fraction"] < 0.75,
        "level1_much_cheaper": out["flops_fraction_per_level"][0] < 0.05,
        "blocks_not_slower_than_masked":
            t_levels[-1] < t_masked * 1.5,
    }

    # other kernels: jitted ref path microbench (CPU)
    b, s, h, hd = 2, 256, 4, 64
    q = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, hd))
    k_ = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, hd))
    v = jax.random.normal(jax.random.PRNGKey(4), (b, s, h, hd))
    out["flash_ref_us"] = _timeit(
        jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v)),
        q, k_, v) * 1e6
    qd = q[:, 0]
    cl = jnp.asarray([s, s // 2], jnp.int32)
    out["decode_ref_us"] = _timeit(
        jax.jit(lambda q, k, v, c: ref.decode_attention_ref(q, k, v, c)),
        qd, k_, v, cl) * 1e6
    w6 = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(5),
                                          (b, s, h, hd)))
    u = jnp.zeros((h, hd))
    s0 = jnp.zeros((b, h, hd, hd))
    out["rwkv_ref_us"] = _timeit(
        jax.jit(lambda r, k, v, w, u, s0: ref.rwkv_scan_ref(
            r, k, v, w, u, s0)), q, k_, v, w6, u, s0) * 1e6

    # fused Pallas decision kernel (interpret mode on the CPU): one
    # churning pick-only hetero tick at S=4096, margin pick parity + flat
    # compile count asserted inside; analytic roofline recorded
    # (docs/KERNELS.md).
    from benchmarks.controller_bench import bench_kernel_select
    out["alert_select"] = bench_kernel_select(s=4096, ticks=4,
                                              block_s=1024)
    out["checks"]["alert_select_picks_within_margin"] = \
        out["alert_select"]["picks_within_margin"]
    out["checks"]["alert_select_no_retrace"] = \
        out["alert_select"]["no_retrace"]
    return out


def main() -> list[tuple]:
    t0 = time.time()
    out = run()
    fr = out["flops_fraction_per_level"]
    tl = out["time_per_level_us"]
    print("  nested_matmul FLOPs fraction per level:",
          " ".join(f"{f:.3f}" for f in fr))
    print(f"  wall us/level: {' '.join(f'{t:.0f}' for t in tl)}  "
          f"(masked dense: {out['time_masked_dense_us']:.0f})")
    ks = out["alert_select"]
    print(f"  alert_select S={ks['n_streams']}: "
          f"{ks['pallas_us_per_decision']:.3f} us/dec "
          f"({'interpret' if ks['interpret'] else 'compiled'}), "
          f"{ks['pallas_vs_xla']:.2f}x vs XLA, picks within margin "
          f"{ks['picks_within_margin']}")
    failed = [k for k, v in out["checks"].items() if not v]
    print("claim checks:", "ALL PASS" if not failed else f"FAIL: {failed}")
    rows = [
        ("kernel_nested_matmul_l4", tl[-1],
         f"flops_frac={fr[-1]:.3f};checks_failed={len(failed)}"),
        ("kernel_flash_ref", out["flash_ref_us"], "b2s256h4d64"),
        ("kernel_decode_ref", out["decode_ref_us"], "b2s256h4d64"),
        ("kernel_rwkv_ref", out["rwkv_ref_us"], "b2s256h4d64"),
        ("kernel_alert_select", ks["pallas_us_per_decision"],
         f"s4096;vs_xla={ks['pallas_vs_xla']:.2f}x;"
         f"parity={ks['picks_within_margin']}"),
    ]
    return rows


if __name__ == "__main__":
    main()
