"""Fused Pallas decision kernel for the ALERT selection hot path.

One ``pl.pallas_call`` evaluates the whole per-tick decision — the
Eq. 7/10 staircase accuracy expectation (erf probe grid contracted with
the precomputed ``[K, K]`` staircase weight matrix), Eq. 9 energy, the
Eq. 4/5 feasibility masks with the Section 3.3 relaxation fallback, the
merged heterogeneous score, and the ``[K·L]`` argmin — in a single tiled
pass.  The XLA engine (:class:`repro.core.batched.BatchedAlertEngine`)
materialises the full ``[S, K, L]`` probe/accuracy/energy grids in HBM
between fused stages; here every intermediate lives only for one lane
tile.

**Layout.**  Lanes sit on the 128-wide minor axis: the ``[S]`` state
vectors are padded and viewed as ``[S/128, 128]``, and each program
takes a ``[rows, 128]`` tile (``rows`` a multiple of 8, the float32
sublane tile).  The ``[K, L]`` latency/power tables and the ``[K, K]``
staircase weights are read as scalars from SMEM, so every vector op is
a lane-dense ``[rows, 128]`` elementwise op.  Three passes over the
``K·L`` cells: (1) a loop over the power buckets computes the K finish
CDFs, the statically unrolled ``[K, K]`` staircase contraction and Eq. 9
energy, writes both grids to VMEM scratch and folds the any-feasible
flag; (2) the Eq. 5 best-accuracy max; (3) the merged score with a
running first-occurrence argmin in the engine's row-major ``(k, l)``
order, carrying the pick's predictions.  Passes 2 and 3 loop over the
K rows with the L buckets unrolled.

**Precision.**  The kernel computes in the dtype of its float inputs.
Mosaic has no float64, so the compiled kernel runs in float32; interpret
mode (CPU) keeps the caller's dtype.  ``erf`` is XLA's float32 rational
approximation, written out (Mosaic has no ``erf`` lowering).  Results
therefore match the float64 XLA engine under a margin contract, not
bitwise: picks, feasibility and relax codes agree on every lane whose
decision clears the tie margins (:func:`clear_lanes`), and predictions
agree within :data:`PRED_RTOL`.  docs/KERNELS.md gives the reasons for
both numbers.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.batched import (GOAL_MIN_ENERGY, RELAXED_ACCURACY,
                                RELAXED_NONE, RELAXED_POWER)
from repro.core.precision import x64_scope
from repro.kernels.ops import use_interpret

_INV_SQRT2 = 1.0 / math.sqrt(2.0)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)

# XLA's float32 erf: x * P(x^2) / Q(x^2) on x clamped to [-4, 4]
# (max abs error 4.2e-7 in float32, 6.5e-8 evaluated in float64).
_ERF_ALPHA = (-2.72614225801306e-10, 2.77068142495902e-08,
              -2.10102402082508e-06, -5.69250639462346e-05,
              -7.34990630326855e-04, -2.95459980854025e-03,
              -1.60960333262415e-02)
_ERF_BETA = (-1.45660718464996e-05, -2.13374055278905e-04,
             -1.68282697438203e-03, -7.37332916720468e-03,
             -1.42647390514189e-02)

# The margin contract against the float64 XLA engine (docs/KERNELS.md).
ACC_TIE_MARGIN = 1e-4       # absolute, on expected accuracy
ENERGY_TIE_MARGIN = 1e-4    # relative, on expected energy
PRED_RTOL = 1e-4            # predictions on lanes that clear the margins
PRED_ATOL = 1e-6

_LANES = 128
_SUBLANES = 8
DEFAULT_BLOCK_S = _LANES * _SUBLANES


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _erf(x):
    """XLA's float32 ``erf`` rational approximation, in ``x``'s dtype,
    saturated to exactly +-1 for ``|x| >= 4`` (where erf is within 1.6e-8
    of +-1), so a finish probability that is 0 or 1 in float64 is 0 or 1
    here too and cells that tie exactly in the reference tie here."""
    xc = jnp.clip(x, -4.0, 4.0)
    x2 = xc * xc
    p = _ERF_ALPHA[0]
    for c in _ERF_ALPHA[1:]:
        p = p * x2 + c
    q = _ERF_BETA[0]
    for c in _ERF_BETA[1:]:
        q = q * x2 + c
    return jnp.where(jnp.abs(x) >= 4.0, jnp.sign(x), xc * p / q)


def _select_kernel(mu_ref, sd_ref, phi_ref, t_ref, ag_ref, eg_ref, gk_ref,
                   act_ref, lat_ref, pw_ref, w_ref,
                   i_ref, j_ref, lat_o_ref, acc_o_ref, en_o_ref, feas_ref,
                   rel_ref, acc_s, en_s, *, k, l, q_fail, overhead,
                   paper_faithful, predictions):
    """One ``[rows, 128]`` lane tile: estimate, hetero score, argmin.

    The semantics of ``BatchedAlertEngine._select_hetero_impl``; the
    homogeneous paths are its all-active single-goal special case.
    """
    # --- dead-lane sanitisation (DESIGN.md §5: garbage-immune) -------- #
    act = act_ref[...] != 0
    mu = jnp.where(act, mu_ref[...], 1.0)
    sd = jnp.where(act, sd_ref[...], 0.1)
    phi = jnp.where(act, phi_ref[...], 0.25)
    t = jnp.maximum(jnp.where(act, t_ref[...], 1.0) - overhead, 1e-9)
    ag = jnp.where(act, ag_ref[...], 0.0)
    eg = jnp.where(act, eg_ref[...], 0.0)
    gk = gk_ref[...]

    # Masks are built with logical ops: Mosaic cannot select between
    # boolean vectors.
    def feasible(acc, en):
        """The lane's own goal constraint (Eq. 4 accuracy, Eq. 5
        energy) on one cell."""
        is_min = gk == GOAL_MIN_ENERGY
        return (is_min & (acc >= ag)) | (~is_min & (en <= eg))

    # --- pass 1, per power bucket: Eq. 7 + Eq. 10 accuracy through the
    # statically unrolled [K, K] staircase contraction, Eq. 9 energy - #
    def bucket(jj, any_f):
        """Pass 1 for power bucket ``jj``: every model's cell."""
        f, t_run = [], []
        for u in range(k):
            lm = mu * lat_ref[u, jj]
            ls = jnp.maximum(sd * lat_ref[u, jj], 1e-12)
            z = (t - lm) / ls
            fu = 0.5 * (1.0 + _erf(z * _INV_SQRT2))
            if paper_faithful:
                tr = jnp.minimum(lm, t)
            else:
                pdf = jnp.exp(-0.5 * z * z) * _INV_SQRT_2PI
                tr = jnp.clip(lm * fu + t * (1.0 - fu) - ls * pdf, 0.0, t)
            f.append(fu)
            t_run.append(tr)
        for kk in range(k):
            acc = q_fail + w_ref[kk, 0] * f[0]
            for u in range(1, k):
                acc = acc + w_ref[kk, u] * f[u]
            p = pw_ref[kk, jj]
            en = p * t_run[kk] + phi * p * jnp.maximum(t - t_run[kk], 0.0)
            acc_s[kk, jj] = acc
            en_s[kk, jj] = en
            any_f = jnp.where(feasible(acc, en), 1, any_f)
        return any_f

    any_i = jax.lax.fori_loop(0, l, bucket,
                              jnp.zeros(act.shape, jnp.int32))

    def usable(kk, jj):
        """Cell ``(kk, jj)``: accuracy, energy, feasibility, and its
        accuracy where the Eq. 5 stage may use it (else -inf)."""
        acc, en = acc_s[kk, jj], en_s[kk, jj]
        feas = feasible(acc, en)
        use = jnp.where(feas | (any_i == 0), acc, -jnp.inf)
        return acc, en, feas, use

    # --- pass 2: Eq. 5 lexicographic stage, best usable accuracy ----- #
    def row_best(kk, best):
        """Pass 2 over model row ``kk``."""
        for jj in range(l):
            best = jnp.maximum(best, usable(kk, jj)[3])
        return best

    best = jax.lax.fori_loop(0, k, row_best,
                             jnp.full(act.shape, -jnp.inf, mu.dtype))

    # --- pass 3: merged score, running first-occurrence argmin over the
    # cells in row-major (k, l) order, the XLA engine's argmin order -- #
    def row_pick(kk, carry):
        """Pass 3 over model row ``kk``."""
        top, pick, p_lat, p_acc, p_en = carry
        for jj in range(l):
            acc, en, feas, acc_use = usable(kk, jj)
            sc_a = jnp.where(best - acc_use <= 1e-12, en, jnp.inf)
            sc_e = jnp.where(any_i != 0, jnp.where(feas, en, jnp.inf),
                             -acc)
            score = jnp.where(gk == GOAL_MIN_ENERGY, sc_e, sc_a)
            better = score < top
            top = jnp.where(better, score, top)
            pick = jnp.where(better, kk * l + jj, pick)
            if predictions:
                p_lat = jnp.where(better, mu * lat_ref[kk, jj], p_lat)
                p_acc = jnp.where(better, acc, p_acc)
                p_en = jnp.where(better, en, p_en)
        return top, pick, p_lat, p_acc, p_en

    zero = jnp.zeros(act.shape, mu.dtype)
    _, pick, p_lat, p_acc, p_en = jax.lax.fori_loop(
        0, k, row_pick, (jnp.full(act.shape, jnp.inf, mu.dtype),
                         jnp.zeros(act.shape, jnp.int32), zero, zero, zero))

    any_f = any_i != 0
    relaxed = jnp.where(any_f, RELAXED_NONE,
                        jnp.where(gk == GOAL_MIN_ENERGY, RELAXED_ACCURACY,
                                  RELAXED_POWER))
    pick = jnp.where(act, pick, 0)
    i_ref[...] = pick // l
    j_ref[...] = pick % l
    feas_ref[...] = (any_f & act).astype(jnp.int32)
    rel_ref[...] = jnp.where(act, relaxed, RELAXED_NONE).astype(jnp.int32)
    lat_o_ref[...] = jnp.where(act, p_lat, 0.0)
    acc_o_ref[...] = jnp.where(act, p_acc, 0.0)
    en_o_ref[...] = jnp.where(act, p_en, 0.0)


def alert_select(mu, sigma, phi, deadline, accuracy_goal, energy_goal,
                 goal_kind, active, *, latency, run_power, weights,
                 q_fail, overhead=0.0, paper_faithful_energy=True,
                 predictions=True, block_s=DEFAULT_BLOCK_S,
                 interpret=None):
    """Fused ``[S]``-vector decision pass: state in, picks out.

    ``mu``/``sigma``/``phi``/``deadline``/``accuracy_goal``/``energy_goal``
    are ``[S]`` float vectors, ``goal_kind`` ``[S]`` int codes
    (``GOAL_MIN_ENERGY``/``GOAL_MAX_ACCURACY``) and ``active`` an ``[S]``
    lane mask — the exact runtime-array contract of
    ``BatchedAlertEngine._select_hetero_impl``, so churn/goal flips never
    re-trace.  ``latency``/``run_power`` are the ``[K, L]`` profile
    tables, ``weights`` the ``[K, K]`` staircase weight matrix, and
    ``q_fail``/``overhead``/``paper_faithful_energy`` the scalar engine
    constants (baked into the trace).

    S is padded up to a whole number of ``[rows, 128]`` tiles with dead
    lanes inside the trace (sanitised in-kernel, sliced off on return),
    so any fleet size works and per-lane results do not depend on the
    tiling.  ``block_s`` lanes per program are rounded up to a multiple
    of 1024 (8 sublanes of 128).  Returns the 7-tuple ``(model_index,
    power_index, predicted_latency, predicted_accuracy,
    predicted_energy, feasible, relaxed_code)``, predictions in the
    inputs' float dtype; with ``predictions=False`` the prediction
    fields come back zero.

    ``interpret=None`` runs the Pallas interpreter on the CPU and
    compiles through Mosaic everywhere else; the compiled kernel
    computes in float32.
    """
    if interpret is None:
        interpret = use_interpret()
    k, l = latency.shape
    fvecs = [jnp.asarray(a) for a in (mu, sigma, phi, deadline,
                                      accuracy_goal, energy_goal)]
    out_dt = jnp.result_type(*fvecs)
    dt = out_dt if interpret else jnp.dtype(jnp.float32)
    s = fvecs[0].shape[0]
    rows = -(-s // _LANES)
    br = min(_round_up(-(-int(block_s) // _LANES), _SUBLANES),
             _round_up(rows, _SUBLANES))
    rows_pad = _round_up(rows, br)
    pad = rows_pad * _LANES - s

    def tile(a, dtype):
        """``[S]`` -> padded ``[rows, 128]``; pads are dead lanes."""
        return jnp.pad(a.astype(dtype), (0, pad)).reshape(rows_pad, _LANES)

    args = [tile(a, dt) for a in fvecs]
    args += [tile(jnp.asarray(goal_kind), jnp.int32),
             tile(jnp.asarray(active), jnp.int32)]
    args += [jnp.asarray(a, dt) for a in (latency, run_power, weights)]
    lane = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _select_kernel, k=k, l=l, q_fail=float(q_fail),
        overhead=float(overhead),
        paper_faithful=bool(paper_faithful_energy),
        predictions=bool(predictions))
    i32 = jnp.dtype(jnp.int32)
    call = pl.pallas_call(
        kern,
        grid=(rows_pad // br,),
        in_specs=[lane] * 8 + [smem] * 3,
        out_specs=[lane] * 7,
        out_shape=[jax.ShapeDtypeStruct((rows_pad, _LANES), d)
                   for d in (i32, i32, dt, dt, dt, i32, i32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        scratch_shapes=[pltpu.VMEM((k, l, br, _LANES), dt)] * 2,
        interpret=interpret,
        name="alert_select",
    )
    # Trace the body with 64-bit types only when it computes in float64:
    # under a caller's x64 scope Python int literals would otherwise
    # become int64, which Mosaic cannot lower.
    with x64_scope(dt == jnp.float64):
        out = call(*args)
    i, j, lat_p, acc_p, en_p, feas, rel = (o.reshape(-1)[:s] for o in out)
    return (i, j, lat_p.astype(out_dt), acc_p.astype(out_dt),
            en_p.astype(out_dt), feas.astype(bool), rel)


def clear_lanes(accuracy, energy, accuracy_goal, energy_goal, goal_kind,
                active) -> np.ndarray:
    """``[S]`` bool: lanes whose float64 decision clears the tie margins.

    ``accuracy``/``energy`` are a reference's ``[S, K, L]`` float64
    estimate grids (``BatchedAlertEngine.estimate`` or the scalar
    reference); the goals, codes and mask are the select call's.  A lane
    is *clear* when a perturbation of every cell by less than
    :data:`ACC_TIE_MARGIN` in accuracy and :data:`ENERGY_TIE_MARGIN`
    (relative) in energy cannot change its pick, feasibility or relax
    code:

    * no cell sits within the margin of the lane's feasibility threshold;
    * Eq. 4 lanes: the winning energy beats the runner-up among feasible
      cells (or, relaxed, the best accuracy beats the runner-up) by more
      than the margin;
    * Eq. 5 lanes: no usable cell's accuracy falls within the margin
      below the best outside the reference's 1e-12 tie set, and the
      tie set's lowest energy beats its runner-up by more than the
      margin.

    Dead lanes are always clear (their outputs are fixed nulls).
    """
    s = accuracy.shape[0]
    acc = np.asarray(accuracy, np.float64).reshape(s, -1)
    en = np.asarray(energy, np.float64).reshape(s, -1)
    ag = np.asarray(accuracy_goal, np.float64)[:, None]
    eg = np.asarray(energy_goal, np.float64)[:, None]
    is_min = np.asarray(goal_kind) == GOAL_MIN_ENERGY
    ma, me = ACC_TIE_MARGIN, ENERGY_TIE_MARGIN
    with np.errstate(invalid="ignore"):
        border = np.where(is_min[:, None], np.abs(acc - ag) <= ma,
                          np.abs(en - eg) <= me * np.abs(eg)).any(axis=1)
        feas = np.where(is_min[:, None], acc >= ag, en <= eg)
        any_f = feas.any(axis=1)

        def gap(x, mask):
            """Winner of ``x`` over ``mask`` and its gap to the best
            strictly worse value (inf when there is none).  Cells equal
            to the winner tie exactly in float64, from identical or
            saturated terms, and break to the first occurrence in both
            implementations."""
            x = np.where(mask, x, np.inf)
            w = x.min(axis=1, keepdims=True)
            second = np.where(x > w, x, np.inf).min(axis=1)
            return second - w[:, 0], w[:, 0]

        g_en, e1 = gap(en, feas)
        g_acc, _ = gap(-acc, np.ones_like(feas))
        clear_min = np.where(any_f, g_en > me * np.abs(e1), g_acc > ma)

        usable = feas | ~any_f[:, None]
        best = np.where(usable, acc, -np.inf).max(axis=1, keepdims=True)
        tie = usable & (best - acc <= 1e-12)
        near = usable & ~tie & (best - acc <= ma + 1e-12)
        g_tie, e1t = gap(en, tie)
        clear_max = ~near.any(axis=1) & (g_tie > me * np.abs(e1t))
    clear = ~border & np.where(is_min, clear_min, clear_max)
    return clear | ~np.asarray(active, bool)


def margin_report(ref, got, clear, *, predictions=True) -> dict:
    """Hold ``got`` to ``ref`` under the margin contract.

    ``ref``/``got`` are select results in the kernel's 7-tuple order (a
    tuple or a :class:`~repro.core.batched.DecisionBatch`); ``clear`` is
    :func:`clear_lanes`.  Returns the counts a caller asserts on:
    ``n_clear`` lanes, ``mismatches`` — clear lanes whose pick,
    feasibility or relax code differ — ``n_differ`` lanes whose pick
    differs at all, and ``pred_ok``, whether every clear lane's
    predictions agree within :data:`PRED_RTOL`/:data:`PRED_ATOL`.
    """
    def fields(b):
        """The 7 result arrays of a tuple or a DecisionBatch."""
        if dataclasses.is_dataclass(b):
            return [getattr(b, f.name) for f in dataclasses.fields(b)]
        return list(b)

    r = [np.asarray(a) for a in fields(ref)]
    g = [np.asarray(a) for a in fields(got)]
    clear = np.asarray(clear, bool)
    pick = (r[0] != g[0]) | (r[1] != g[1])
    bad = pick | (r[5] != g[5]) | (r[6] != g[6])
    pred_ok = True
    if predictions:
        pred_ok = all(np.allclose(g[n][clear], r[n][clear], rtol=PRED_RTOL,
                                  atol=PRED_ATOL) for n in (2, 3, 4))
    return {"n_clear": int(clear.sum()),
            "mismatches": int((bad & clear).sum()),
            "n_differ": int(pick.sum()), "pred_ok": bool(pred_ok)}


def alert_select_cost(s: int, k: int, l: int, *,
                      predictions: bool = False) -> dict:
    """Analytic roofline terms for one fused pass (docs/KERNELS.md).

    FLOP count walks the kernel body: ~12 elementwise ops per
    ``[S, K, L]`` probe cell (latency/z/energy chains), the ``2·S·K²·L``
    staircase contraction, ~8 ops per cell for the merged score +
    reductions, and one erf per cell (counted as a transcendental, not a
    FLOP).  Bytes are the streamed ``[S]`` vectors of the compiled
    float32 kernel (6 float + 2 int32 in, 3 float + 4 int32 out, 4 bytes
    each) — the ``[K, L]``/``[K, K]`` constants sit in SMEM, so per-lane
    HBM traffic is O(1) while per-lane compute is O(K·L): arithmetic
    intensity ~``K·L/2`` FLOP/byte, firmly compute-(VPU-)bound for
    production tables.
    """
    cells = s * k * l
    flops = cells * (12 + 8) + 2 * s * k * k * l
    if predictions:
        flops += 3 * s * k * l      # running selects of the predictions
    bytes_io = s * (8 + 7) * 4
    return {
        "flops": float(flops),
        "bytes_accessed": float(bytes_io),
        "transcendentals": float(cells),
        "arithmetic_intensity_flops_per_byte": flops / bytes_io,
    }
