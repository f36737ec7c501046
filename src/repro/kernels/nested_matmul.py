"""Pallas TPU kernel: block-lower-triangular *nested* matmul (paper §4.2.1).

This is the paper's width-nesting compute pattern on the MXU.  A width-
nested linear layer connects input stripe j to output stripe i only when
``j <= i``; a dense masked matmul burns the full M*K*N MACs, while this
kernel's grid guard skips every (k, n) tile above the stripe diagonal:

    FLOPs = sum_i  M * in_width(i) * stripe_size(i)      (triangular)

At anytime level ``k < K`` the output (and grid) shrinks to the level
prefix, so partial-level inference touches only level-k weights — the
TPU-native fix for the paper's §4.3 "infrastructure-induced overheads"
(PyTorch/TF slowdowns up to 50 % for nested execution).

Grid: (M/bm, N/bn, K/bk), k innermost ("arbitrary" = sequential reduction).
The per-output-tile reduction limit arrives via scalar prefetch
(`limits[n_tile]` = number of live k tiles), computed from the static
StripeSpec boundaries.  A float32 VMEM scratch tile accumulates partial
products; the output tile is written once, at the last live k step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nesting import StripeSpec


def _kernel(limits_ref, x_ref, w_ref, o_ref, acc_ref):
    n, k = pl.program_id(1), pl.program_id(2)
    limit = limits_ref[n]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < limit)
    def _accumulate():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == limit - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tile_limits(in_spec: StripeSpec, out_spec: StripeSpec, level: int,
                bn: int, bk: int) -> np.ndarray:
    """limits[n_tile] = number of k tiles the n-th output tile may read."""
    n_cols = out_spec.width(level)
    lv = out_spec.level_of_channel()[:n_cols]
    lims = []
    for n0 in range(0, n_cols, bn):
        tile_levels = lv[n0:n0 + bn]
        if tile_levels.min() != tile_levels.max():
            # A tile spanning a stripe boundary would make its shallow
            # columns read deep inputs through the shared k limit — that is
            # exactly the edge class the paper prunes.  Tiles must align.
            raise ValueError(f"bn={bn} spans an output stripe boundary at "
                             f"column {n0}; choose bn dividing the stripe "
                             f"widths {out_spec.stripe_sizes()}")
        i = int(tile_levels[0])
        w_in = in_spec.width(min(i, in_spec.levels))
        if w_in % bk:
            raise ValueError(f"stripe boundary {w_in} not divisible by "
                             f"bk={bk}")
        lims.append(w_in // bk)
    return np.asarray(lims, np.int32)


@functools.partial(jax.jit, static_argnames=("in_spec", "out_spec", "level",
                                             "bm", "bn", "bk", "interpret"))
def nested_matmul(x: jax.Array, w: jax.Array, in_spec: StripeSpec,
                  out_spec: StripeSpec, level: int | None = None,
                  bm: int = 128, bn: int = 128, bk: int = 128,
                  interpret: bool = False) -> jax.Array:
    """x: [M, K_in]  @  w: [K_in, N] under stripe nesting -> [M, width(level)].
    """
    lvl = out_spec.levels if level is None else level
    m, k_in = x.shape
    n_cols = out_spec.width(lvl)
    bm, bn, bk = min(bm, m), min(bn, n_cols), min(bk, k_in)
    if m % bm or n_cols % bn or k_in % bk:
        raise ValueError(f"shapes ({m},{k_in},{n_cols}) not divisible by "
                         f"blocks ({bm},{bk},{bn})")
    limits_np = tile_limits(in_spec, out_spec, lvl, bn, bk)
    limits = jnp.asarray(limits_np)
    k_tiles_max = int(limits_np.max())
    grid = (m // bm, n_cols // bn, k_tiles_max)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda mi, ni, ki, lims: (mi, ki)),
                pl.BlockSpec((bk, bn), lambda mi, ni, ki, lims: (ki, ni)),
            ],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda mi, ni, ki, lims: (mi, ni)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n_cols), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(limits, x, w[:, :n_cols])


def nested_matmul_flops(m: int, in_spec: StripeSpec, out_spec: StripeSpec,
                        level: int | None = None) -> int:
    """Analytic MACs*2 of the triangular kernel (vs 2*M*K*N dense)."""
    lvl = out_spec.levels if level is None else level
    total = 0
    for i in range(1, lvl + 1):
        sl = out_spec.stripe_slice(i)
        w_in = in_spec.width(min(i, in_spec.levels))
        total += 2 * m * w_in * (sl.stop - sl.start)
    return total
