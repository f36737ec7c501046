"""Fleet-scale batched scoring engine for the ALERT decision loop.

The paper's per-input hot path (Section 3.2: estimation Eq. 7/9/10 +
selection Eq. 4/5 with Section 3.3 relaxation) is evaluated here for
**S streams x K models x L power buckets in one jit-compiled pass**:

* Filter state arrives as struct-of-arrays vectors (``mu``, ``sigma``,
  ``phi`` — from the :mod:`repro.core.kalman` filter banks or from a
  single stream's scalar filters).
* The anytime staircases are precomputed at ProfileTable build time: the
  padded ``[K, M, L]`` level-latency tensor + ``[K, M]`` accuracy/validity
  masks (:meth:`ProfileTable.staircase_tensors`, used for vectorised
  delivery in the fleet sim) and — for scoring — their telescoped form, a
  ``[K, K]`` staircase weight matrix that turns Eq. 7 and Eq. 10 into ONE
  branch-free ``jnp`` expression: erf once per (stream, candidate, power
  bucket) via ``jax.scipy.special``, then a tiny matrix contraction.  No
  ``np.vectorize``, no per-candidate Python loop, no padded level axis in
  the hot pass.  A traditional model is simply a 1-level staircase, for
  which Eq. 10 reduces exactly to Eq. 7.
* Selection is a masked argmin/argmax over the ``[S, K, L]`` grid with the
  paper's relaxation priority (latency > accuracy > power) folded in as a
  branch-free ``where`` between the feasible pick and the relaxed pick.
* Fleets need not be homogeneous: :meth:`BatchedAlertEngine.select` takes
  per-stream goal codes (``goal_kind`` — Eq. 4 lanes and Eq. 5 lanes mixed
  in one call), per-stream goal values, and an ``active`` lane mask.  Both
  optimisation branches are evaluated on the shared estimation grid and the
  per-lane branch is a ``where`` on the goal code; dead lanes are sanitised
  at the top of the traced function (their state may be garbage or NaN
  without perturbing live lanes) and forced to a deterministic null pick.
  Because goal codes and the mask are runtime arrays, streams can join,
  leave, and switch goals every tick without a single re-trace
  (DESIGN.md §5).

* The ``[S]`` lane axis itself shards over devices: construct the engine
  with ``mesh=`` (a 1-D lane mesh from
  :func:`repro.launch.mesh.make_lane_mesh`) and every traced pass runs
  SPMD — ``[S]``-shaped state is lane-sharded, the ``[K, K]`` staircase
  weight matrix and ``[K, L]`` profile constants are replicated, and since
  the decision grid has no cross-lane reduction the partitioned graph
  needs no collectives and its per-lane picks stay bitwise identical to
  the single-device pass (DESIGN.md §6).  Callers that keep state on
  device (the sharded filter banks) pass jax arrays and set
  ``as_arrays=True`` to keep the whole tick loop free of host gathers.

Numerics: scoring runs in float64 under a *scoped* ``x64_scope()`` (the
global flag is never touched), which makes the engine's decisions
bit-identical to the float64 NumPy reference (:mod:`repro.core.reference`)
across the parity sweep in ``benchmarks/controller_bench.py``.

``AlertController`` is a thin S=1 wrapper over this engine;
``repro.serving.sim.FleetSim`` and ``repro.serving.alert_server`` drive
thousands of streams per tick through one :meth:`BatchedAlertEngine.select`
call.  Tensor layout details: DESIGN.md §4; the paper-equation-to-code
map is docs/EQUATIONS.md.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from repro.core.precision import x64_scope

from repro.core.profiles import ProfileTable

_SQRT2 = math.sqrt(2.0)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)

# Relaxation codes (Section 3.3) — returned per stream by select().
RELAXED_NONE = 0        # a cell satisfied every constraint
RELAXED_ACCURACY = 1    # min-energy task: accuracy goal unreachable
RELAXED_POWER = 2       # max-accuracy task: energy budget unreachable
RELAXED_NAMES = {RELAXED_NONE: "", RELAXED_ACCURACY: "accuracy",
                 RELAXED_POWER: "power"}

# Per-stream goal codes for heterogeneous fleets (``goal_kind`` lanes).
GOAL_MIN_ENERGY = 0     # Eq. 4: argmin energy s.t. accuracy
GOAL_MAX_ACCURACY = 1   # Eq. 5: argmax accuracy s.t. energy


def goal_codes(goals) -> np.ndarray:
    """Encode :class:`~repro.core.controller.Goal` values (or raw int
    codes) as an int64 ``goal_kind`` vector for :meth:`select`.  Numeric
    arrays pass through without a per-lane Python loop — this sits on the
    per-tick hot path of fleet callers."""
    from repro.core.controller import Goal  # avoid import cycle

    arr = np.asarray(goals)
    if arr.dtype != object:
        return np.atleast_1d(arr).astype(np.int64)
    return np.asarray([
        (GOAL_MIN_ENERGY if g is Goal.MINIMIZE_ENERGY else GOAL_MAX_ACCURACY)
        if isinstance(g, Goal) else int(g)
        for g in np.atleast_1d(arr)], dtype=np.int64)


def _columns(x):
    return [x[..., c] for c in range(x.shape[-1])]


def _row_argmin(x):
    """First-occurrence argmin along the last axis.

    Same semantics as ``jnp.argmin`` (ties -> lowest index) on rows
    without NaN, built as a running minimum over the columns: elementwise
    compares and selects, no reduction.  XLA CPU lowers variadic argmin
    reduces to scalar loops, and on the TPU, where float64 is emulated
    as float32 pairs, a row-min reduce returned a value equal to no
    element of its row (v5e, S=1, K·L=16), so an argmin built on
    ``x == min(x)`` matched nothing.
    """
    cols = _columns(x)
    best, idx = cols[0], jnp.zeros(cols[0].shape, jnp.int32)
    for c, col in enumerate(cols[1:], start=1):
        better = col < best
        best = jnp.where(better, col, best)
        idx = jnp.where(better, c, idx)
    return idx


def _row_max(x):
    """Row maximum as a running elementwise max (see ``_row_argmin``
    for why not a reduce), shaped ``[..., 1]``."""
    return functools.reduce(jnp.maximum, _columns(x))[..., None]


def _row_pick(x, idx):
    """``x[row, idx[row]]`` by elementwise selects over the columns."""
    cols = _columns(x)
    out = cols[0]
    for c, col in enumerate(cols[1:], start=1):
        out = jnp.where(idx == c, col, out)
    return out


@dataclasses.dataclass(frozen=True)
class EstimateBatch:
    """Per-cell predictions for S streams: all arrays are ``[S, K, L]``."""

    lat_mean: np.ndarray
    lat_std: np.ndarray
    accuracy: np.ndarray
    energy: np.ndarray
    p_finish: np.ndarray


@dataclasses.dataclass(frozen=True)
class DecisionBatch:
    """One selection round for S streams: all arrays are ``[S]``."""

    model_index: np.ndarray        # int
    power_index: np.ndarray        # int
    predicted_latency: np.ndarray
    predicted_accuracy: np.ndarray
    predicted_energy: np.ndarray
    feasible: np.ndarray           # bool
    relaxed_code: np.ndarray       # int, see RELAXED_*

    def __len__(self) -> int:
        return int(self.model_index.shape[0])

    def relaxed_name(self, s: int) -> str:
        """Stream s's relaxed constraint as the reference's string code
        (``""``/``"accuracy"``/``"power"``)."""
        return RELAXED_NAMES[int(self.relaxed_code[s])]


class BatchedAlertEngine:
    """Stateless batched estimation + selection over a ProfileTable.

    The engine owns no filter state — callers pass ``mu``/``sigma``/``phi``
    vectors each round (banks for fleets, scalar filters for S=1), which
    keeps the jit cache stable: for a fixed S every call dispatches to the
    same compiled executable; nothing in the hot path re-traces.

    Parameters mirror :class:`repro.core.controller.AlertController`:
    ``goal`` picks Eq. 4 vs Eq. 5 for every lane that does not override it
    (pass ``goal=None`` for an engine that *requires* per-stream
    ``goal_kind`` codes), ``overhead`` is subtracted from each stream's
    deadline inside :meth:`select` (Section 3.2.1 step 2), and
    ``paper_faithful_energy`` switches Eq. 9 verbatim vs the beyond-paper
    E[min(t, T)] estimator.

    ``mesh`` (optional 1-D lane mesh, see
    :func:`repro.launch.mesh.make_lane_mesh`) turns on **lane sharding**:
    every jitted pass is constrained with
    :class:`~jax.sharding.NamedSharding` so ``[S]`` inputs and outputs
    shard their lane axis over the mesh while the profile constants baked
    into the trace replicate.  S must divide the mesh size (fleet callers
    pad with dead lanes — DESIGN.md §6).  Decisions are bitwise identical
    to the unsharded engine: the grid has no cross-lane op, so
    partitioning cannot reassociate any reduction.

    ``backend`` selects the select-path implementation: ``"xla"`` (the
    fused jnp passes below) or ``"pallas"`` — the lane-tiled
    :func:`repro.kernels.alert_select.alert_select` kernel, which fuses
    estimation, the merged hetero score, and the argmin into one tiled
    pass over ``[S, K, L]``, float32 when compiled, with the float64
    picks on every lane outside its tie margins (interpret mode on the
    CPU; docs/KERNELS.md).  Both backends share the
    same seams, runtime-array contracts, and jit-cache behaviour;
    :meth:`estimate` (the grid-returning debug API) always runs XLA.
    ``pallas_block_s`` overrides the kernel's lane-tile size (benchmarks
    raise it where VMEM is not the constraint).
    """

    def __init__(self, table: ProfileTable, goal=None, *,
                 overhead: float = 0.0,
                 paper_faithful_energy: bool = True,
                 mesh=None, backend: str = "xla",
                 pallas_block_s: int | None = None):
        from repro.core.controller import Goal  # avoid import cycle

        self.table = table
        self.goal = goal
        self.overhead = float(overhead)
        self.paper_faithful_energy = bool(paper_faithful_energy)
        self._minimize_energy = goal is Goal.MINIMIZE_ENERGY
        self.backend = str(backend)
        if self.backend not in ("xla", "pallas"):
            raise ValueError(f"unknown backend {backend!r}: "
                             f"expected 'xla' or 'pallas'")
        self.pallas_block_s = pallas_block_s

        k, l = table.latency.shape
        self._k, self._l = k, l
        # Constants baked into the traced graphs (float64 under scoped x64).
        self._c_latency = np.asarray(table.latency, np.float64)
        self._c_run_power = np.asarray(table.run_power, np.float64)
        self._c_q_fail = float(table.q_fail)
        self._c_weights = self._staircase_weight_matrix(table)

        self.mesh = mesh
        if mesh is None:
            self._lane = None
            jit_kw = {}
        else:
            from repro.launch.mesh import lane_shardings
            self._lane, _ = lane_shardings(mesh)
            # One lane-sharded spec serves every in/out leaf: [S] shards
            # its only axis, [S, K, L] its leading axis (trailing dims
            # unsharded); constants are jaxpr literals and replicate.
            jit_kw = {"in_shardings": self._lane,
                      "out_shardings": self._lane}

        # The four select executables hang off one seam: a dict keyed by
        # (heterogeneous, predictions).  The XLA backend jits the fused
        # jnp implementations below; the Pallas backend swaps in the
        # lane-tiled `alert_select` kernel behind the SAME seams (same
        # runtime-array signatures, so churn/goal flips never re-trace
        # on either backend, and mesh sharding composes identically).
        if self.backend == "pallas":
            impls = self._pallas_select_impls()
        else:
            impls = {
                (False, True): self._select_impl,
                (False, False): functools.partial(
                    self._select_impl, predictions=False),
                (True, True): self._select_hetero_impl,
                (True, False): functools.partial(
                    self._select_hetero_impl, predictions=False),
            }
        self._impls = impls
        self._estimate_jit = jax.jit(self._estimate_impl, **jit_kw)
        self._select_jit = jax.jit(impls[(False, True)], **jit_kw)
        self._select_pick_jit = jax.jit(impls[(False, False)], **jit_kw)
        self._select_hetero_jit = jax.jit(impls[(True, True)], **jit_kw)
        self._select_hetero_pick_jit = jax.jit(impls[(True, False)],
                                               **jit_kw)

    def _pallas_select_impls(self) -> dict:
        """Build the four select implementations on the fused Pallas
        kernel (:func:`repro.kernels.alert_select.alert_select`).

        The kernel's contract matches ``_select_hetero_impl`` — one
        tiled pass fusing estimation, the merged hetero score, and the
        argmin, picks under the margin contract — so the hetero
        seams are direct pass-throughs and the homogeneous seams build
        their all-active single-goal code vectors inside the trace.
        Under a lane mesh each implementation is wrapped in ``shard_map``
        (one kernel launch per device on its ``[S/n]`` lane shard; the
        decision grid has no cross-lane op, so this is exact —
        DESIGN.md §6)."""
        from repro.kernels.alert_select import alert_select

        base = functools.partial(
            alert_select, latency=self._c_latency,
            run_power=self._c_run_power, weights=self._c_weights,
            q_fail=self._c_q_fail, overhead=self.overhead,
            paper_faithful_energy=self.paper_faithful_energy)
        if self.pallas_block_s is not None:
            base = functools.partial(base,
                                     block_s=int(self.pallas_block_s))
        min_energy = self._minimize_energy
        code = GOAL_MIN_ENERGY if min_energy else GOAL_MAX_ACCURACY

        def _homog(predictions):
            def _fn(mu, sd, phi, deadline, goal_val):
                s = mu.shape[0]
                gk = jnp.full((s,), code, jnp.int32)
                act = jnp.ones((s,), jnp.int32)
                zero = jnp.zeros((s,), jnp.float64)
                ag = goal_val if min_energy else zero
                eg = zero if min_energy else goal_val
                return base(mu, sd, phi, deadline, ag, eg, gk, act,
                            predictions=predictions)
            return _fn

        def _hetero(predictions):
            def _fn(mu, sd, phi, deadline, ag, eg, gk, act):
                return base(mu, sd, phi, deadline, ag, eg, gk, act,
                            predictions=predictions)
            return _fn

        impls = {(False, True): _homog(True),
                 (False, False): _homog(False),
                 (True, True): _hetero(True),
                 (True, False): _hetero(False)}
        if self.mesh is not None:
            from repro.launch.mesh import lane_shard_map
            impls = {(het, pred): lane_shard_map(
                         fn, self.mesh, n_in=8 if het else 5, n_out=7)
                     for (het, pred), fn in impls.items()}
        return impls

    @staticmethod
    def _staircase_weight_matrix(table: ProfileTable) -> np.ndarray:
        """Fold Eq. 7 + Eq. 10 into one [K, K] weight matrix ``P``.

        Every staircase level of candidate k is itself a candidate row u
        (traditional models are 1-level staircases), so with
        ``F[s, u, l] = P(t_u <= T)`` — the per-candidate finish CDF — the
        telescoped Eq. 10 sum becomes

            q_hat[s, k, l] = q_fail + sum_u P[k, u] * F[s, u, l],

        with ``P[k, r_m] = q_m - q_{m-1}`` along k's level prefix
        (``q_0 = q_fail``).  For a traditional model this collapses to
        ``P[k, k] = q_k - q_fail``, i.e. Eq. 7 verbatim.  Estimation then
        needs exactly ONE erf evaluation per (stream, candidate, bucket)
        plus a tiny K x K contraction — no padded level axis at all.
        """
        k = len(table.candidates)
        weights = np.zeros((k, k), dtype=np.float64)
        for i, r in table.staircase_rows().items():
            prev = float(table.q_fail)
            for u in r:
                q_u = float(table.candidates[u].accuracy)
                weights[i, u] += q_u - prev
                prev = q_u
        return weights

    # ------------------------------------------------------------------ #
    # traced implementations                                             #
    # ------------------------------------------------------------------ #
    def _estimate_impl(self, mu, sd, phi, deadline, active=None):
        """[S] state vectors -> per-cell [S, K, L] predictions.

        ``active`` masks dead lanes: their inputs are replaced with benign
        constants *before* any arithmetic (a retired stream's slot may hold
        stale or NaN state) and their output rows are zeroed.  ``None``
        (the homogeneous path) skips both rewrites, so the lockstep graphs
        are bit-identical to the unmasked PR-1 engine.
        """
        if active is not None:
            mu = jnp.where(active, mu, 1.0)
            sd = jnp.where(active, sd, 0.1)
            phi = jnp.where(active, phi, 0.25)
            deadline = jnp.where(active, deadline, 1.0)
        lat = self._c_latency[None, :, :]                # [1, K, L]
        t = deadline[:, None, None]                      # [S, 1, 1]
        mu_ = mu[:, None, None]
        sd_ = sd[:, None, None]

        # Full-candidate latency (Idea 1: t = xi * t_train).
        lat_mean = mu_ * lat                             # [S, K, L]
        lat_std = jnp.maximum(sd_ * lat, 1e-12)
        z = (t - lat_mean) / lat_std

        # Eq. 7 + Eq. 10 in one branch-free expression: the finish CDF of
        # every candidate (the only erf in the pass), contracted with the
        # precomputed staircase weight matrix (see
        # ``_staircase_weight_matrix``).  The deepest level of k's
        # staircase is k itself, so p_finish IS the CDF grid.
        f = 0.5 * (1.0 + jax.scipy.special.erf(z / _SQRT2))
        accuracy = self._c_q_fail + jnp.einsum(
            "ku,sul->skl", self._c_weights, f)
        p_finish = f

        # Energy, Eq. 9: run phase capped at the deadline (a missed input
        # is abandoned at T_goal, Section 3.3); idle phase draws phi * p.
        caps = self._c_run_power[None, :, :]
        if self.paper_faithful_energy:
            t_run = jnp.minimum(lat_mean, t)
        else:
            pdf = jnp.exp(-0.5 * z ** 2) * _INV_SQRT_2PI
            t_run = (lat_mean * p_finish + t * (1.0 - p_finish)
                     - lat_std * pdf)
            t_run = jnp.clip(t_run, 0.0, t)
        phi_ = phi[:, None, None]
        energy = caps * t_run + phi_ * caps * jnp.maximum(t - t_run, 0.0)
        out = (lat_mean, lat_std, accuracy, energy, p_finish)
        if active is not None:
            a3 = active[:, None, None]
            out = tuple(jnp.where(a3, x, 0.0) for x in out)
        return out

    @staticmethod
    def _score_min_energy(acc_f, en_f, goal_val):
        """Eq. 4 score rows: argmin of the result IS the pick.

        argmin e s.t. q_hat >= Q_goal — the latency constraint is folded
        into q_hat (a high miss probability drags expected accuracy to
        q_fail).  Relaxation: sacrifice the accuracy goal but stay
        latency-aware via argmax expected accuracy.

        One fused score, no argmin here: feasible rows rank by energy
        among feasible cells; rows with no feasible cell rank by negated
        accuracy, which is argmax accuracy with the identical
        first-occurrence tie-break.  Picks are bit-identical to the
        two-argmin form (and to the NumPy reference) at a fraction of the
        reduction passes — selection is bandwidth-bound at fleet sizes,
        and deferring the single shared argmin lets the heterogeneous
        path rank BOTH goal types with one reduce.
        """
        feas = acc_f >= goal_val[:, None]
        any_f = feas.any(axis=1)
        score = jnp.where(any_f[:, None],
                          jnp.where(feas, en_f, jnp.inf), -acc_f)
        relaxed = jnp.where(any_f, RELAXED_NONE, RELAXED_ACCURACY)
        return score, any_f, relaxed

    @staticmethod
    def _score_max_accuracy(acc_f, en_f, goal_val):
        """Eq. 5 score rows: argmin of the result IS the pick.

        argmax q_hat s.t. e <= E_goal; equal-accuracy cells tie-break to
        lower energy.  Power/energy is the lowest-priority constraint —
        relaxation drops it first: the fallback is the same lexicographic
        pick with the feasibility mask removed, so both cases share one
        max + one tie.

        The tie test ``best - acc <= 1e-12`` equals the reference's
        ``isclose(acc, best, rtol=0, atol=1e-12)`` for every finite cell
        (``acc <= best`` by construction); -inf-masked cells never tie
        (``best - (-inf) = inf``), and the all-infeasible row where both
        would be -inf uses the unmasked accuracies instead.
        """
        feas = en_f <= goal_val[:, None]
        any_f = feas.any(axis=1)
        acc_use = jnp.where(feas | ~any_f[:, None], acc_f, -jnp.inf)
        best = _row_max(acc_use)
        score = jnp.where(best - acc_use <= 1e-12, en_f, jnp.inf)
        relaxed = jnp.where(any_f, RELAXED_NONE, RELAXED_POWER)
        return score, any_f, relaxed

    def _gather_pick(self, s, kl, pick, lat_mean, acc, energy, any_f,
                     relaxed, predictions=True):
        if not predictions:
            # Pick-only mode: fleet callers re-derive outcomes from real
            # delivery, so the three [S, K*L] prediction gathers are pure
            # waste on their tick — skip them (fields come back zero).
            z = jnp.zeros(s)
            return (pick // self._l, pick % self._l, z, z, z, any_f,
                    relaxed)
        # Elementwise gathers (XLA CPU gathers are row-by-row).
        gather = lambda a: _row_pick(a.reshape(s, kl), pick)
        return (pick // self._l, pick % self._l, gather(lat_mean),
                gather(acc), gather(energy), any_f, relaxed)

    def _select_impl(self, mu, sd, phi, deadline, goal_val, *,
                     predictions=True):
        """Fused estimate + Eq. 4/5 pick with Section 3.3 relaxation
        (homogeneous fast path: the goal is a compile-time branch)."""
        t_eff = jnp.maximum(deadline - self.overhead, 1e-9)
        lat_mean, lat_std, acc, energy, p_fin = self._estimate_impl(
            mu, sd, phi, t_eff)
        s = acc.shape[0]
        kl = self._k * self._l
        acc_f = acc.reshape(s, kl)
        en_f = energy.reshape(s, kl)
        if self._minimize_energy:
            score, any_f, relaxed = self._score_min_energy(acc_f, en_f,
                                                           goal_val)
        else:
            score, any_f, relaxed = self._score_max_accuracy(acc_f, en_f,
                                                             goal_val)
        return self._gather_pick(s, kl, _row_argmin(score), lat_mean, acc,
                                 energy, any_f, relaxed,
                                 predictions=predictions)

    def _select_hetero_impl(self, mu, sd, phi, deadline, acc_goal, en_goal,
                            goal_kind, active, *, predictions=True):
        """Masked heterogeneous select: Eq. 4 lanes and Eq. 5 lanes mixed
        in one pass, dead lanes sanitised and pinned to a null pick.

        Estimation (the erf grid — the expensive part) is shared by both
        branches; the per-lane goal is a branch-free ``where`` on
        ``goal_kind``.  All of ``goal_kind``/``active``/goal values are
        runtime arrays, so churn and goal changes never re-trace.

        Dead-lane handling is all ``[S]``-sized: inputs are sanitised
        before the grid math (so garbage can't generate NaNs that stall
        the lane later) and the gathered outputs are zeroed at the end —
        no ``[S, K, L]`` masking pass anywhere.
        """
        mu = jnp.where(active, mu, 1.0)
        sd = jnp.where(active, sd, 0.1)
        phi = jnp.where(active, phi, 0.25)
        deadline = jnp.where(active, deadline, 1.0)
        acc_goal = jnp.where(active, acc_goal, 0.0)
        en_goal = jnp.where(active, en_goal, 0.0)
        t_eff = jnp.maximum(deadline - self.overhead, 1e-9)
        lat_mean, lat_std, acc, energy, p_fin = self._estimate_impl(
            mu, sd, phi, t_eff)
        s = acc.shape[0]
        kl = self._k * self._l
        acc_f = acc.reshape(s, kl)
        en_f = energy.reshape(s, kl)
        is_min = goal_kind == GOAL_MIN_ENERGY
        is_min_ = is_min[:, None]
        # Unified feasibility: each lane's rows already follow its own
        # goal's constraint, so ONE mask, ONE any-reduce, and ONE max
        # serve the whole mixed fleet — vs the homogeneous fast path the
        # only extra reduce is the Eq. 5 best-accuracy max; everything
        # else merges into the same fused elementwise chain.  Per-lane
        # results are bit-identical to the per-goal score builders
        # (`_score_min_energy` / `_score_max_accuracy`).
        feas = jnp.where(is_min_, acc_f >= acc_goal[:, None],
                         en_f <= en_goal[:, None])
        any_f = feas.any(axis=1)
        any_ = any_f[:, None]
        # Eq. 5 lexicographic stage (see _score_max_accuracy); for Eq. 4
        # lanes the max is computed but unused.
        acc_use = jnp.where(feas | ~any_, acc_f, -jnp.inf)
        best = _row_max(acc_use)
        sc_a = jnp.where(best - acc_use <= 1e-12, en_f, jnp.inf)
        # Eq. 4 score (see _score_min_energy), merged per lane.
        sc_e = jnp.where(any_, jnp.where(feas, en_f, jnp.inf), -acc_f)
        pick = _row_argmin(jnp.where(is_min_, sc_e, sc_a))
        relaxed = jnp.where(any_f, RELAXED_NONE,
                            jnp.where(is_min, RELAXED_ACCURACY,
                                      RELAXED_POWER))
        # Dead lanes: deterministic null outputs (pick 0, infeasible-free).
        pick = jnp.where(active, pick, 0)
        any_f = any_f & active
        relaxed = jnp.where(active, relaxed, RELAXED_NONE)
        i, j, lat, acc_p, en_p, any_f, relaxed = self._gather_pick(
            s, kl, pick, lat_mean, acc, energy, any_f, relaxed,
            predictions=predictions)
        if predictions:
            zero = lambda x: jnp.where(active, x, 0.0)
            lat, acc_p, en_p = zero(lat), zero(acc_p), zero(en_p)
        return (i, j, lat, acc_p, en_p, any_f, relaxed)

    # ------------------------------------------------------------------ #
    # public API (numpy in, numpy out; float64 via scoped x64; jax        #
    # arrays pass through untouched for device-resident callers)         #
    # ------------------------------------------------------------------ #
    @staticmethod
    def _vec(x, s: int, floor: float | None = None):
        """``[S]`` float64 vector from a scalar, numpy, or jax input.

        jax arrays pass through without a host transfer (``floor`` applied
        on device) — the contract for device-resident fleet loops; host
        inputs follow the original numpy path bit for bit.
        """
        if isinstance(x, jax.Array):
            if x.ndim == 0:
                x = jnp.broadcast_to(x, (s,))
            return x if floor is None else jnp.maximum(x, floor)
        a = np.asarray(x, np.float64)
        if a.ndim == 0:
            a = np.broadcast_to(a, (s,))
        return a if floor is None else np.maximum(a, floor)

    def _n_lanes(self, deadline) -> int:
        """Infer S from ``deadline`` and enforce the mesh divisibility
        contract (fleet callers pad to a device multiple, DESIGN.md §6)."""
        if isinstance(deadline, jax.Array):
            s = deadline.shape[0] if deadline.ndim else 1
        else:
            t = np.asarray(deadline)
            s = t.shape[0] if t.ndim else 1
        if self.mesh is not None and s % self.mesh.size:
            raise ValueError(
                f"lane-sharded engine needs S divisible by the mesh size "
                f"({self.mesh.size}); got S={s} — pad with dead lanes")
        return s

    def estimate(self, mu, sigma, phi, deadline, *,
                 active=None) -> EstimateBatch:
        """Score every (stream, model, power) cell.

        ``mu``/``sigma``/``phi`` are the ``[S]`` filter-state vectors
        (slow-down mean/deviation, idle-power ratio); ``deadline`` is the
        effective deadline (overhead already applied by the caller,
        matching ``AlertController.estimate``); scalars broadcast across
        streams.  ``active`` (optional ``[S]`` bool mask) sanitises dead
        lanes and zeroes their output rows.  Returns ``[S, K, L]`` grids.
        """
        s = self._n_lanes(deadline)
        args = [self._vec(mu, s), self._vec(sigma, s, floor=1e-6),
                self._vec(phi, s), self._vec(deadline, s)]
        if active is not None:
            args.append(active if isinstance(active, jax.Array)
                        else np.broadcast_to(np.asarray(active, bool),
                                             (s,)))
        with x64_scope():
            out = self._estimate_jit(*args)
        return EstimateBatch(*(np.asarray(o) for o in out))

    def _resolve_goal_kind(self, goal_kind, s: int):
        """``[S]`` int64 goal codes from ints, Goals, jax arrays, or the
        engine default (raises when the engine was built with
        ``goal=None`` and no per-stream codes were passed)."""
        if goal_kind is not None:
            if isinstance(goal_kind, jax.Array):
                return goal_kind            # device caller: trusted int64
            if isinstance(goal_kind, np.ndarray) and \
                    goal_kind.dtype == np.int64:
                return np.broadcast_to(goal_kind, (s,))  # hot path: no copy
            return np.broadcast_to(goal_codes(goal_kind), (s,))
        if self.goal is None:
            raise ValueError("engine has no default goal: pass goal_kind")
        code = GOAL_MIN_ENERGY if self._minimize_energy \
            else GOAL_MAX_ACCURACY
        return np.full(s, code, dtype=np.int64)

    def select(self, mu, sigma, phi, deadline, *,
               accuracy_goal=None, energy_goal=None,
               goal_kind=None, active=None,
               predictions: bool = True,
               as_arrays: bool = False) -> DecisionBatch:
        """One decision per stream.

        ``mu``/``sigma``/``phi`` are ``[S]`` filter-state vectors (scalars
        broadcast); ``deadline`` is the raw per-stream T_goal — the engine
        subtracts its configured ``overhead`` (Section 3.2.1 step 2).

        ``predictions=False`` skips the per-pick prediction gathers (the
        returned latency/accuracy/energy fields are zero) — fleet callers
        that re-derive outcomes from real delivery use this leaner pass;
        indices, feasibility, and relax codes are identical either way.

        Homogeneous fleets (no ``goal_kind``/``active``, engine built with
        a ``goal``) dispatch to the PR-1 fast path: min-energy engines need
        ``accuracy_goal`` (per-stream effective Q_goal, e.g. from the
        windowed-goal bank); max-accuracy engines need ``energy_goal``.

        Heterogeneous/churning fleets pass ``goal_kind`` (``[S]`` int codes
        ``GOAL_MIN_ENERGY``/``GOAL_MAX_ACCURACY``, or a sequence of
        :class:`~repro.core.controller.Goal`) and/or ``active`` (``[S]``
        bool lane mask).  Every *active* Eq. 4 lane needs a finite
        ``accuracy_goal`` entry and every active Eq. 5 lane a finite
        ``energy_goal`` entry; the other vector may be omitted (zero-filled)
        when no lane of that kind is active.  Dead lanes may hold arbitrary
        garbage in every input vector and come back with a deterministic
        null decision (indices 0, zero predictions, ``feasible=False`` off,
        ``relaxed_code=RELAXED_NONE``).

        Device-resident callers (sharded filter banks in a mesh-mode
        engine) pass jax arrays — these are trusted as ``[S]`` vectors of
        the right dtype and skip the host-side goal-coverage validation —
        and set ``as_arrays=True`` so the returned
        :class:`DecisionBatch` holds lane-sharded jax arrays instead of
        gathered numpy: with both, a select → feedback tick never touches
        the host (DESIGN.md §6).
        """
        s = self._n_lanes(deadline)
        if goal_kind is None and active is None and self.goal is not None:
            goal_val = accuracy_goal if self._minimize_energy \
                else energy_goal
            if goal_val is None:
                need = "accuracy_goal" if self._minimize_energy else \
                    "energy_goal"
                raise ValueError(f"{self.goal} task needs {need}")
            fn = self._select_jit if predictions else self._select_pick_jit
            with x64_scope():
                out = fn(
                    self._vec(mu, s), self._vec(sigma, s, floor=1e-6),
                    self._vec(phi, s), self._vec(deadline, s),
                    self._vec(goal_val, s))
        else:
            gk = self._resolve_goal_kind(goal_kind, s)
            if active is None:
                act = np.ones(s, bool)
            elif isinstance(active, jax.Array):
                act = active                # device caller: trusted bool
            else:
                act = np.broadcast_to(np.asarray(active, bool), (s,))
            on_host = isinstance(act, np.ndarray) and \
                isinstance(gk, np.ndarray)
            if on_host and accuracy_goal is None and \
                    np.any(act & (gk == GOAL_MIN_ENERGY)):
                raise ValueError("active minimize-energy lanes need "
                                 "accuracy_goal")
            if on_host and energy_goal is None and \
                    np.any(act & (gk == GOAL_MAX_ACCURACY)):
                raise ValueError("active maximize-accuracy lanes need "
                                 "energy_goal")
            ag = self._vec(0.0 if accuracy_goal is None else accuracy_goal,
                           s)
            eg = self._vec(0.0 if energy_goal is None else energy_goal, s)
            fn = self._select_hetero_jit if predictions else \
                self._select_hetero_pick_jit
            with x64_scope():
                out = fn(
                    self._vec(mu, s), self._vec(sigma, s, floor=1e-6),
                    self._vec(phi, s), self._vec(deadline, s),
                    ag, eg, gk, act)
        if not as_arrays:
            out = tuple(np.asarray(o) for o in out)
        i, j, lat, acc, en, feas, relaxed = out
        return DecisionBatch(model_index=i, power_index=j,
                             predicted_latency=lat, predicted_accuracy=acc,
                             predicted_energy=en, feasible=feas,
                             relaxed_code=relaxed)

    def n_compiles(self) -> tuple[int, int]:
        """(estimate, select) jit-cache sizes — 1 each means every call
        after warmup reused the compiled executable (no re-tracing).  The
        select count sums the homogeneous/heterogeneous and full/pick-only
        executables, so a fleet that sticks to one path still reads 1
        while it churns."""
        return (self._estimate_jit._cache_size(),
                self._select_jit._cache_size()
                + self._select_pick_jit._cache_size()
                + self._select_hetero_jit._cache_size()
                + self._select_hetero_pick_jit._cache_size())

    def select_step_impl(self):
        """Traceable heterogeneous pick-only select for embedding inside a
        caller's OWN jitted graph (the traffic megatick's per-round scan
        body, DESIGN.md §7).

        Returns a callable ``(mu, sigma, phi, deadline, accuracy_goal,
        energy_goal, goal_kind, active) -> 7-tuple`` with the exact
        semantics of :meth:`select` with ``predictions=False`` — including
        the host wrapper's sigma floor, which is applied inside the
        returned callable so per-lane picks are bitwise identical to the
        standalone dispatch.  On a Pallas engine the callable launches the
        fused ``alert_select`` kernel (already ``shard_map``-wrapped under
        a mesh); on an XLA engine under a mesh it is wrapped in
        ``shard_map`` here so the caller's scan shards its lane axis the
        same way (the decision grid has no cross-lane op, so per-device
        execution is exact).
        """
        base = self._impls[(True, False)]
        if self.mesh is not None and self.backend == "xla":
            from repro.launch.mesh import lane_shard_map
            base = lane_shard_map(base, self.mesh, n_in=8, n_out=7)

        def step(mu, sd, phi, deadline, acc_goal, en_goal, gk, act):
            """One traced pick-only select (sigma floored like `_vec`)."""
            return base(mu, jnp.maximum(sd, 1e-6), phi, deadline,
                        acc_goal, en_goal, gk, act)

        return step


def _goal_record_step(buf, pos, count, delivered, m, depth):
    """Jitted masked ring-buffer push for the sharded goal bank — the
    device twin of :meth:`WindowedGoalBank.record` (donated state)."""
    rows = jnp.arange(buf.shape[0])
    cur = buf[rows, pos]
    buf = buf.at[rows, pos].set(jnp.where(m, delivered, cur))
    pos = jnp.where(m, (pos + 1) % depth, pos)
    count = jnp.where(m, jnp.minimum(count + 1, depth), count)
    return buf, pos, count


def _goal_current_step(goal, buf, count, window):
    """Jitted compensation rule (Eq. 4 effective Q_goal, paper fn.3) for
    the sharded goal bank — device twin of
    :meth:`WindowedGoalBank.current_goal`."""
    total = buf.sum(axis=1)
    need = goal * window - total
    remaining = window - count
    per_input = need - (remaining - 1) * goal
    return jnp.where(count == 0, goal, per_input)


def pairwise_sum_cols(cols):
    """Sum a list of equal-shaped arrays in numpy's pairwise-summation
    order, as a static expression tree of binary adds.

    ``np.sum(buf, axis=1)`` is NOT a left fold: numpy accumulates in
    8-wide blocks combined as ``((r0+r1)+(r2+r3))+((r4+r5)+(r6+r7))``
    (with a plain fold below 8 terms and recursive halving above 128,
    the halving point rounded down to a multiple of 8).  An XLA
    ``sum(axis=1)`` reduce uses yet another order.  Building the same
    tree column by column makes a traced window sum bitwise-identical
    to the host goal bank's — the one ulp hazard DESIGN.md §6 documents
    for the sharded bank, closed here for the traffic megatick
    (``tests/test_traffic.py`` pins this against numpy for every depth
    the recursion shape changes at).
    """
    n = len(cols)
    if n == 0:
        raise ValueError("pairwise_sum_cols needs at least one column")
    if n < 8:
        res = cols[0]
        for c in cols[1:]:
            res = res + c
        return res
    if n <= 128:
        r = list(cols[:8])
        i = 8
        while i + 8 <= n:
            for j in range(8):
                r[j] = r[j] + cols[i + j]
            i += 8
        res = ((r[0] + r[1]) + (r[2] + r[3])) + \
            ((r[4] + r[5]) + (r[6] + r[7]))
        while i < n:
            res = res + cols[i]
            i += 1
        return res
    n2 = n // 2
    n2 -= n2 % 8
    return pairwise_sum_cols(cols[:n2]) + pairwise_sum_cols(cols[n2:])


def goal_current_step_hostsum(goal, buf, count, window, f_zero=0.0):
    """:func:`_goal_current_step` with the window total summed in numpy's
    pairwise order (:func:`pairwise_sum_cols`) — the traceable twin of
    the HOST :meth:`WindowedGoalBank.current_goal`, bitwise included,
    used by the traffic megatick scan (DESIGN.md §7).

    ``f_zero`` must be a RUNTIME zero (a traced scalar argument, not a
    literal) when this runs under jit: XLA CPU contracts ``a * b + c``
    chains into one-rounding FMAs, which numpy never does, so the two
    products below are pinned by adding the runtime zero — the compiler
    can't fold the add away, and even if it contracts it,
    ``fma(a, b, 0) == round(a * b)`` exactly, so both products round
    separately just like the host bank's.  Eager callers can leave the
    default (eager ops never contract)."""
    total = pairwise_sum_cols([buf[:, c] for c in range(buf.shape[1])])
    need = (goal * window + f_zero) - total
    remaining = window - count
    per_input = need - ((remaining - 1) * goal + f_zero)
    return jnp.where(count == 0, goal, per_input)


class WindowedGoalBank:
    """Vectorised :class:`~repro.core.controller.WindowedAccuracyGoal`:
    per-stream ring buffers of the last N-1 delivered accuracies (paper
    fn.3) with the same compensation rule as the scalar class.  ``goal``
    may be a scalar (shared Q_goal) or an [S] vector (per-stream goals);
    :meth:`set_goals` resets exactly the streams whose goal changed,
    mirroring the scalar class's recreate-on-change semantics per lane.

    ``mesh=`` (1-D lane mesh) keeps the window state — ``goal [S]``,
    ``buf [S, N-1]``, ``count/pos [S]`` — lane-sharded on device, with the
    per-tick :meth:`record` running as a donated jitted scatter and
    :meth:`current_goal` returning a lane-sharded vector that feeds the
    sharded engine directly (DESIGN.md §6).  Per-lane window *contents*
    match the host bank exactly; the window *sum* in the compensation rule
    is an XLA reduce, which may differ from numpy's pairwise summation in
    the final ulp — callers that pin bitwise goal trajectories (the fleet
    sim's parity fixtures) keep this one bank on host.
    """

    def __init__(self, goal, n_streams: int, window: int = 10,
                 mesh=None):
        self.goal = np.broadcast_to(
            np.asarray(goal, dtype=np.float64), (n_streams,)).copy()
        self.window = int(window)
        self._depth = max(self.window - 1, 0)
        self._buf = np.zeros((n_streams, max(self._depth, 1)))
        self._count = np.zeros(n_streams, dtype=np.int64)
        self._pos = np.zeros(n_streams, dtype=np.int64)
        self.mesh = mesh
        if mesh is not None:
            from repro.core.kalman import _jit_f64_sharded, _lane_put
            if n_streams % mesh.size:
                raise ValueError(
                    f"goal-bank capacity {n_streams} must be a multiple "
                    f"of the lane-mesh size {mesh.size}")
            self.goal, self._buf, self._count, self._pos = _lane_put(
                mesh, self.goal, self._buf, self._count, self._pos)
            self._record = _jit_f64_sharded(_goal_record_step, mesh,
                                            donate=(0, 1, 2))
            self._current = _jit_f64_sharded(_goal_current_step, mesh,
                                             donate=())

    def _where_reset(self, changed) -> None:
        """Clear window state on the ``changed`` lanes (device mode)."""
        with x64_scope():
            c = changed[:, None]
            self._buf = jnp.where(c, 0.0, self._buf)
            self._count = jnp.where(changed, 0, self._count)
            self._pos = jnp.where(changed, 0, self._pos)

    def set_goals(self, goals) -> None:
        """Install per-stream goals; lanes whose goal changed get a fresh
        window (the scalar class's recreate-on-change semantics), other
        lanes keep their history."""
        if self.mesh is not None:
            from repro.core.kalman import _lane_put
            new = _lane_put(self.mesh, np.broadcast_to(
                np.asarray(goals, dtype=np.float64), self.goal.shape))
            with x64_scope():
                changed = new != self.goal
                self.goal = jnp.where(changed, new, self.goal)
            self._where_reset(changed)
            return
        new = np.broadcast_to(np.asarray(goals, dtype=np.float64),
                              self.goal.shape)
        changed = new != self.goal
        if changed.any():
            self._buf[changed] = 0.0
            self._count[changed] = 0
            self._pos[changed] = 0
            self.goal = np.where(changed, new, self.goal)

    def reset_lanes(self, lanes, goal=None) -> None:
        """Recycle ``lanes`` for newly admitted streams: clear their window
        history and (optionally) install a new per-lane goal — even one
        equal to the departed tenant's, which ``set_goals`` would keep."""
        lanes = np.asarray(lanes)
        if self.mesh is not None:
            from repro.core.kalman import _lane_put
            sel = np.zeros(self.goal.shape[0], bool)
            sel[lanes] = True
            if goal is not None:
                new = np.zeros(self.goal.shape[0])
                new[lanes] = np.asarray(goal, dtype=np.float64)
                sel_d, new_d = _lane_put(self.mesh, sel, new)
                with x64_scope():
                    self.goal = jnp.where(sel_d, new_d, self.goal)
            else:
                sel_d = _lane_put(self.mesh, sel)
            self._where_reset(sel_d)
            return
        if goal is not None:
            self.goal[lanes] = np.asarray(goal, dtype=np.float64)
        self._buf[lanes] = 0.0
        self._count[lanes] = 0
        self._pos[lanes] = 0

    def export_lanes(self, lanes) -> dict:
        """Snapshot ``lanes``' window state (goal, ring buffer, count,
        position) as host arrays — the page-out half of session paging
        (DESIGN.md §7), bitwise round-trippable through
        :meth:`import_lanes`.  Sharded banks gather just these lanes."""
        lanes = np.asarray(lanes)
        return {"goal": np.asarray(self.goal)[lanes].copy(),
                "buf": np.asarray(self._buf)[lanes].copy(),
                "count": np.asarray(self._count)[lanes].copy(),
                "pos": np.asarray(self._pos)[lanes].copy()}

    def import_lanes(self, lanes, state: dict) -> None:
        """Restore an :meth:`export_lanes` snapshot into ``lanes`` (the
        page-in half of session paging): same-shape writes, no re-trace,
        bitwise lossless.  On a sharded bank this is a masked on-device
        rewrite."""
        lanes = np.asarray(lanes)
        if self.mesh is not None:
            from repro.core.kalman import _lane_put
            s = self.goal.shape[0]
            sel = np.zeros(s, bool)
            sel[lanes] = True
            goal = np.zeros(s)
            goal[lanes] = state["goal"]
            buf = np.zeros((s, self._buf.shape[1]))
            buf[lanes] = state["buf"]
            count = np.zeros(s, dtype=np.int64)
            count[lanes] = state["count"]
            pos = np.zeros(s, dtype=np.int64)
            pos[lanes] = state["pos"]
            sel_d, goal_d, buf_d, count_d, pos_d = _lane_put(
                self.mesh, sel, goal, buf, count, pos)
            with x64_scope():
                self.goal = jnp.where(sel_d, goal_d, self.goal)
                self._buf = jnp.where(sel_d[:, None], buf_d, self._buf)
                self._count = jnp.where(sel_d, count_d, self._count)
                self._pos = jnp.where(sel_d, pos_d, self._pos)
            return
        self.goal[lanes] = state["goal"]
        self._buf[lanes] = state["buf"]
        self._count[lanes] = state["count"]
        self._pos[lanes] = state["pos"]

    def grow(self, n_streams: int, goal_fill: float = 0.0) -> None:
        """Extend the bank to ``n_streams`` lanes; new lanes start with a
        fresh window and ``goal_fill`` (set the real goal on admission).
        Sharded banks grow in mesh-size multiples and round-trip state
        through host once (amortised, like the filter banks)."""
        extra = int(n_streams) - self.goal.shape[0]
        if extra <= 0:
            return
        if self.mesh is not None and int(n_streams) % self.mesh.size:
            raise ValueError(
                f"sharded goal-bank capacity must grow in multiples of "
                f"the mesh size {self.mesh.size}; got {n_streams}")
        self.goal = np.concatenate(
            [np.asarray(self.goal),
             np.full(extra, goal_fill, dtype=np.float64)])
        self._buf = np.concatenate(
            [np.asarray(self._buf), np.zeros((extra, self._buf.shape[1]))])
        self._count = np.concatenate(
            [np.asarray(self._count), np.zeros(extra, dtype=np.int64)])
        self._pos = np.concatenate(
            [np.asarray(self._pos), np.zeros(extra, dtype=np.int64)])
        if self.mesh is not None:
            from repro.core.kalman import _lane_put
            self.goal, self._buf, self._count, self._pos = _lane_put(
                self.mesh, self.goal, self._buf, self._count, self._pos)

    def record(self, delivered: np.ndarray,
               mask: np.ndarray | None = None) -> None:
        """Push this tick's delivered accuracies (``[S]``) into the
        per-lane ring buffers; ``mask`` (``[S]`` bool) freezes masked-out
        lanes.  Sharded banks run this as one donated jitted scatter."""
        if self._depth == 0:
            return
        s = self._buf.shape[0]
        if self.mesh is not None:
            m = np.ones(s, bool) if mask is None else mask
            self._buf, self._pos, self._count = self._record(
                self._buf, self._pos, self._count, delivered, m,
                self._depth)
            return
        m = np.ones(s, bool) if mask is None else np.asarray(mask, bool)
        rows = np.nonzero(m)[0]
        self._buf[rows, self._pos[rows]] = np.asarray(delivered)[rows]
        self._pos[rows] = (self._pos[rows] + 1) % self._depth
        self._count[rows] = np.minimum(self._count[rows] + 1, self._depth)

    def current_goal(self) -> np.ndarray:
        """Per-stream *effective* Q_goal after window compensation
        (paper fn.3): lanes with an empty window return their raw goal.
        Sharded banks return a lane-sharded jax vector (feed it straight
        to the sharded engine — no gather)."""
        if self._depth == 0:
            return self.goal.copy()
        if self.mesh is not None:
            return self._current(self.goal, self._buf, self._count,
                                 self.window)
        total = self._buf.sum(axis=1)
        need = self.goal * self.window - total
        remaining = self.window - self._count
        per_input = need - (remaining - 1) * self.goal
        return np.where(self._count == 0, self.goal, per_input)
