"""Kalman-filter estimators from the ALERT paper (Eqs. 6 and 8).

Two filters:

* :class:`SlowdownFilter` — tracks the *global slow-down factor* xi, i.e. the
  ratio between observed latency and profiled latency, as a Normal random
  variable N(mu, sigma^2).  This is ALERT Idea 1 + Idea 2: one scalar that is
  independent of which (model, power) configuration produced the observation,
  so every observation updates the latency prediction of *every*
  configuration.  The filter tracks both the mean and the deviation; the
  deviation is what lets the controller be conservative in volatile
  environments (Section 3.2.2 of the paper).

* :class:`IdlePowerFilter` — tracks phi, the DNN-idle power ratio
  (idle power / active power under the current cap), Eq. 8.  Used by the
  energy predictor (Eq. 9).

The scalar filters sit on the host control path of a single stream (one
update per input) and stay plain Python on purpose.  For fleet-scale serving
(S streams advanced in lockstep) :class:`SlowdownFilterBank` and
:class:`IdlePowerFilterBank` hold the same state as struct-of-arrays
``[S]``-shaped vectors and apply the identical recurrences to every stream
in one fused, jit-compiled update — the per-stream math is bit-for-bit the
scalar filters'.  The batched scoring path that consumes the bank state
lives in ``repro.core.batched``; the equation-to-code map is
docs/EQUATIONS.md.

Banks built with ``mesh=`` (a 1-D lane mesh,
:func:`repro.launch.mesh.make_lane_mesh`) keep their ``[S]`` state as
**lane-sharded jax arrays** and run every update through a jitted step
whose state buffers are *donated* — the per-tick feedback loop of a
sharded fleet then updates filter state in place on the devices, never
copying or gathering it to host (DESIGN.md §6).  Per-lane results remain
bit-identical to the host banks (same f64 recurrence, no cross-lane op).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class SlowdownFilter:
    """ALERT Eq. 6 — adaptive-noise Kalman filter for the slow-down factor.

    Paper constants (Section 3.2.2): ``K0=0.5, R=0.001, Q0=0.1, alpha=0.3,
    mu0=1, sigma0=0.1``.  ``alpha`` is the forgetting factor of the process
    variance [Akhlaghi et al. 2017].
    """

    mu: float = 1.0          # mu^(0)
    sigma: float = 0.1       # sigma^(0)
    gain: float = 0.5        # K^(0)
    meas_noise: float = 1e-3             # R
    process_noise_floor: float = 0.1     # Q^(0)
    process_noise: float = 0.1           # Q^(n)
    alpha: float = 0.3                   # forgetting factor
    miss_inflation: float = 0.2
    n_updates: int = 0

    def observe(self, observed_latency: float, profiled_latency: float,
                deadline_missed: bool = False) -> float:
        """Feed one (observed, profiled) latency pair; returns updated mu.

        When a deadline is missed ALERT cannot observe the full latency
        (it abandons the input), so the measured latency is inflated by a
        factor of ``miss_inflation`` (Section 3.3) to push the filter toward
        conservative configurations.
        """
        if profiled_latency <= 0.0:
            raise ValueError("profiled_latency must be positive")
        ratio = observed_latency / profiled_latency
        if deadline_missed:
            ratio *= (1.0 + self.miss_inflation)
        # Eq. 6, in paper order.
        y = ratio - self.mu
        self.process_noise = max(
            self.process_noise_floor,
            self.alpha * self.process_noise
            + (1.0 - self.alpha) * (self.gain * y) ** 2,
        )
        prior_gain = self.gain
        denom = (1.0 - prior_gain) * self.sigma + self.process_noise + self.meas_noise
        self.gain = ((1.0 - prior_gain) * self.sigma + self.process_noise) / denom
        self.mu = self.mu + self.gain * y
        self.sigma = (1.0 - prior_gain) * self.sigma + self.process_noise
        self.n_updates += 1
        return self.mu

    @property
    def std(self) -> float:
        """Standard deviation of xi.

        Eq. 7 defines ``xi ~ N(mu, sigma^2)`` — the paper's sigma *is* the
        standard deviation, used directly (its Eq. 6 recurrence mixes units
        with the noise terms, but we follow the paper verbatim).  The Q0
        floor makes the steady-state sigma 0.1, i.e. ALERT never trusts the
        environment to be quieter than +-10 % — this is the source of its
        conservatism in quiet environments and its fast reaction in noisy
        ones.
        """
        return max(self.sigma, 1e-6)

    def predict_latency(self, profiled_latency: float) -> tuple[float, float]:
        """Predicted (mean, std) of the latency of a config profiled at
        ``profiled_latency`` — Idea 1: t_ij = xi * t_ij_train."""
        return self.mu * profiled_latency, self.std * profiled_latency


@dataclasses.dataclass
class IdlePowerFilter:
    """ALERT Eq. 8 — Kalman filter for the DNN-idle power ratio phi.

    Paper constants: ``M0=0.01, S=1e-4, V=1e-3``; phi0 defaults to the
    measured idle/TDP ratio of the platform (we default to 0.3 which matches
    typical idle/active ratios; the filter converges in a handful of steps
    regardless of init).
    """

    phi: float = 0.3
    variance: float = 0.01   # M^(0)
    process_noise: float = 1e-4  # S
    meas_noise: float = 1e-3     # V
    n_updates: int = 0

    def observe(self, idle_power: float, active_power: float) -> float:
        """Feed one (idle, active) power pair; returns the updated phi
        (Eq. 8 — a plain scalar Kalman on the measured ratio)."""
        if active_power <= 0.0:
            raise ValueError("active_power must be positive")
        measured = idle_power / active_power
        # Eq. 8.
        gain = (self.variance + self.process_noise) / (
            self.variance + self.process_noise + self.meas_noise)
        self.variance = (1.0 - gain) * (self.variance + self.process_noise)
        self.phi = self.phi + gain * (measured - self.phi)
        self.n_updates += 1
        return self.phi


_BANK_STEPS: dict = {}


def _is_jax_array(x) -> bool:
    """True for jax arrays without importing jax when no one has."""
    import sys

    jax = sys.modules.get("jax")
    return jax is not None and isinstance(x, jax.Array)


def _masked_positive(values, mask, what: str):
    """Shared bank-observation preamble: require strictly positive values
    on the masked-in lanes, and give masked-out lanes a harmless positive
    divisor (they still flow through the fused update, discarded by the
    final ``where``).  Device-resident callers pass jax arrays — those
    skip the host-side validation (it would force a device sync) and are
    trusted positive on live lanes."""
    if _is_jax_array(values):
        import jax.numpy as jnp
        return jnp.where(mask, values, 1.0)
    v = np.asarray(values, np.float64)
    if np.any(v[mask] <= 0.0):
        raise ValueError(f"{what} must be positive")
    return np.where(mask, v, 1.0)


def _jit_f64(fn):
    """jit ``fn`` and dispatch it under scoped x64 so the bank updates run
    in float64 (matching the scalar filters) without flipping global jax
    config for the rest of the process.  Jitted wrappers are cached per
    function, so every bank instance shares one compiled step (the steps
    take all hyperparameters as arguments — nothing instance-specific is
    baked into the trace)."""
    if fn in _BANK_STEPS:
        return _BANK_STEPS[fn]
    import jax

    jfn = jax.jit(fn)

    def call(*args):
        """Numpy-in/numpy-out dispatch of the jitted step under x64."""
        from repro.core.precision import x64_scope
        with x64_scope():
            out = jfn(*[np.asarray(a) for a in args])
        return tuple(np.asarray(o) for o in out)

    _BANK_STEPS[fn] = call
    return call


def _jit_f64_sharded(fn, mesh, donate: tuple):
    """Device-resident twin of :func:`_jit_f64` for lane-sharded banks.

    The ``donate`` argnums are the bank's ``[S]`` state vectors: they are
    *donated* to the jitted step (in/out shardings match, so XLA updates
    the buffers in place — zero copies per tick) and the step's outputs
    come back as lane-sharded jax arrays, never gathered to host.
    Non-state ``[S]`` inputs (observations, masks) may arrive as numpy and
    are lane-sharded on the way in; scalars pass through.  One compiled
    step is cached per (fn, mesh, donate).
    """
    key = (fn, mesh, donate)
    if key in _BANK_STEPS:
        return _BANK_STEPS[key]
    import jax

    from repro.launch.mesh import lane_shardings

    lane, _ = lane_shardings(mesh)
    jfn = jax.jit(fn, donate_argnums=donate)

    def put(a):
        """Lane-shard [S] operands; scalars pass through untouched."""
        if isinstance(a, jax.Array) or np.ndim(a):
            return jax.device_put(a, lane)
        return a                       # python/0-d scalar hyperparameter

    def call(*args):
        """Device-in/device-out dispatch (donating state) under x64."""
        from repro.core.precision import x64_scope
        with x64_scope():
            return jfn(*[put(a) for a in args])

    _BANK_STEPS[key] = call
    return call


def _lane_put(mesh, *arrays):
    """device_put host arrays onto ``mesh`` lane-sharded, preserving f64
    (dtype canonicalisation is scoped out via ``x64_scope``)."""
    import jax
    from repro.core.precision import x64_scope

    from repro.launch.mesh import lane_shardings

    lane, _ = lane_shardings(mesh)
    with x64_scope():
        out = tuple(jax.device_put(np.asarray(a), lane) for a in arrays)
    return out if len(out) > 1 else out[0]


def _slowdown_bank_step(mu, sigma, gain, q, obs, prof, miss, mask,
                        q0, alpha, r, miss_inflation):
    import jax.numpy as jnp

    ratio = obs / prof
    ratio = jnp.where(miss, ratio * (1.0 + miss_inflation), ratio)
    y = ratio - mu
    q_new = jnp.maximum(q0, alpha * q + (1.0 - alpha) * (gain * y) ** 2)
    denom = (1.0 - gain) * sigma + q_new + r
    gain_new = ((1.0 - gain) * sigma + q_new) / denom
    mu_new = mu + gain_new * y
    sigma_new = (1.0 - gain) * sigma + q_new
    return (jnp.where(mask, mu_new, mu), jnp.where(mask, sigma_new, sigma),
            jnp.where(mask, gain_new, gain), jnp.where(mask, q_new, q))


def _idle_bank_step(phi, var, idle, active, mask, s, v):
    import jax.numpy as jnp

    measured = idle / active
    gain = (var + s) / (var + s + v)
    var_new = (1.0 - gain) * (var + s)
    phi_new = phi + gain * (measured - phi)
    return (jnp.where(mask, phi_new, phi), jnp.where(mask, var_new, var))


def _fused_fleet_step(mu, sigma, gain, q, obs, prof, miss, mask,
                      q0, alpha, r, miss_inflation,
                      phi, var, idle, active, s_noise, v_noise):
    """Both per-tick bank recurrences (Eq. 6 + Eq. 8) in ONE jitted graph —
    per-stream math identical to the standalone steps, one dispatch."""
    slow = _slowdown_bank_step(mu, sigma, gain, q, obs, prof, miss, mask,
                               q0, alpha, r, miss_inflation)
    idle_out = _idle_bank_step(phi, var, idle, active, mask,
                               s_noise, v_noise)
    return slow + idle_out


#: Public traceable alias of the fused Eq. 6 + Eq. 8 bank step, for
#: callers that embed the feedback update inside their own jitted graph
#: (the traffic megatick's per-round scan — DESIGN.md §7).  Same
#: per-lane math, bit for bit, as :func:`observe_fleet`'s dispatch.
fused_fleet_step = _fused_fleet_step


def _mask_vec(mask, s: int):
    """``[S]`` bool mask from ``None`` / numpy / jax input."""
    if mask is None:
        return np.ones(s, bool)
    if _is_jax_array(mask):
        return mask
    return np.asarray(mask, bool)


def _coerce_obs(x):
    """Observation vector: numpy f64 on host, passthrough on device."""
    return x if _is_jax_array(x) else np.asarray(x, np.float64)


def observe_fleet(slow: "SlowdownFilterBank", idle: "IdlePowerFilterBank",
                  observed_latency, profiled_latency, *,
                  deadline_missed=None, idle_power, active_power,
                  mask=None) -> None:
    """One fused masked update for BOTH banks (the fleet tick's entire
    feedback step): same per-lane results, bit for bit, as calling
    ``slow.observe(...)`` then ``idle.observe(...)``, at a single jit
    dispatch — the dispatch overhead, not the [S] math, dominates the
    standalone calls at fleet sizes.

    All ``[S]`` inputs may be numpy or jax arrays.  When the banks are
    lane-sharded (built with ``mesh=``), the fused step runs SPMD with the
    six state buffers donated — filter state stays on device, in place.
    """
    if slow.mesh is not idle.mesh:
        raise ValueError("observe_fleet needs both banks on the same "
                         "mesh (or both on host)")
    s = slow.n_streams
    miss = np.zeros(s, bool) if deadline_missed is None \
        else (deadline_missed if _is_jax_array(deadline_missed)
              else np.asarray(deadline_missed, bool))
    m = _mask_vec(mask, s)
    prof = _masked_positive(profiled_latency, m, "profiled_latency")
    active = _masked_positive(active_power, m, "active_power")
    if slow.mesh is not None:
        step = _jit_f64_sharded(_fused_fleet_step, slow.mesh,
                                donate=(0, 1, 2, 3, 12, 13))
    else:
        step = _jit_f64(_fused_fleet_step)
    (slow.mu, slow.sigma, slow.gain, slow.process_noise,
     idle.phi, idle.variance) = step(
        slow.mu, slow.sigma, slow.gain, slow.process_noise,
        _coerce_obs(observed_latency), prof, miss, m,
        slow.process_noise_floor, slow.alpha, slow.meas_noise,
        slow.miss_inflation,
        idle.phi, idle.variance, _coerce_obs(idle_power),
        active, idle.process_noise, idle.meas_noise)
    slow._count_updates(m)
    idle._count_updates(m)


class _LaneBank:
    """Shared lane-pool plumbing for the struct-of-arrays filter banks.

    ``_state_names`` lists the ``[S]`` float64 state vectors; subclasses
    provide ``_priors()`` (per-vector reset values).  The bank runs in one
    of two homes:

    * **host** (``mesh=None``) — state is numpy, updates run through the
      shared jitted step and come back as numpy (the original semantics);
    * **lane-sharded** (``mesh=`` a 1-D lane mesh) — state lives on the
      devices as lane-sharded f64 jax arrays; updates donate the state
      buffers and the per-tick loop never gathers them to host.  Capacity
      must stay a multiple of the mesh size.
    """

    _state_names: tuple = ()

    def _priors(self) -> tuple:
        raise NotImplementedError

    def _init_home(self, mesh) -> None:
        """Install ``mesh`` and move freshly built numpy state to it."""
        self.mesh = mesh
        if mesh is None:
            return
        if len(mesh.axis_names) != 1:
            raise ValueError("lane-sharded banks need a 1-D mesh "
                             f"(got axes {mesh.axis_names})")
        if self.n_streams % mesh.size:
            raise ValueError(
                f"bank capacity {self.n_streams} must be a multiple of "
                f"the lane-mesh size {mesh.size}")
        for name in self._state_names + ("n_updates",):
            setattr(self, name, _lane_put(mesh, getattr(self, name)))

    @property
    def n_streams(self) -> int:
        """Lane capacity S (live + recyclable lanes)."""
        return getattr(self, self._state_names[0]).shape[0]

    def _count_updates(self, m) -> None:
        """Advance per-lane update counters by mask ``m`` (device add when
        either side lives on device — no host sync)."""
        if _is_jax_array(self.n_updates) or _is_jax_array(m):
            from repro.core.precision import x64_scope
            with x64_scope():  # int64 counters stay int64
                self.n_updates = self.n_updates + m
        else:
            self.n_updates += m

    def export_lanes(self, lanes) -> dict:
        """Snapshot ``lanes``' full filter state as host arrays (one entry
        per ``_state_names`` vector plus ``n_updates``, each ``[len(lanes)]``)
        — the page-out half of session paging (DESIGN.md §7): a session
        leaving its lane carries its state to the host store so the lane
        can be recycled, and a later :meth:`import_lanes` restores it
        bitwise.  Sharded banks gather just the selected lanes."""
        lanes = np.asarray(lanes)
        return {name: np.asarray(getattr(self, name))[lanes].copy()
                for name in self._state_names + ("n_updates",)}

    def import_lanes(self, lanes, state: dict) -> None:
        """Restore a :meth:`export_lanes` snapshot into ``lanes`` — the
        page-in half of session paging.  Same-shape ``[S]`` writes, so the
        engine's jit cache is untouched (the churn-no-retrace protocol of
        DESIGN.md §5); round-tripping export → import is bitwise lossless.
        On a sharded bank this is a masked on-device rewrite."""
        lanes = np.asarray(lanes)
        names = self._state_names + ("n_updates",)
        if self.mesh is not None:
            import jax.numpy as jnp
            from repro.core.precision import x64_scope
            sel = np.zeros(self.n_streams, bool)
            sel[lanes] = True
            with x64_scope():
                for name in names:
                    vals = np.zeros(self.n_streams,
                                    dtype=np.asarray(state[name]).dtype)
                    vals[lanes] = state[name]
                    sel_d, val_d = _lane_put(self.mesh, sel, vals)
                    setattr(self, name, jnp.where(sel_d, val_d,
                                                  getattr(self, name)))
            return
        first = getattr(self, self._state_names[0])
        if not first.flags.writeable:  # observe() returns jax-backed views
            for name in self._state_names:
                setattr(self, name, getattr(self, name).copy())
        for name in names:
            np.asarray(getattr(self, name))[lanes] = state[name]

    def reset_lanes(self, lanes) -> None:
        """Reinitialise ``lanes`` (host indices) to the filter priors —
        stream admission into a recycled lane.  Same-shape state: the
        engine's jit cache is untouched.  On a sharded bank this is a
        masked on-device rewrite (no gather)."""
        lanes = np.asarray(lanes)
        priors = self._priors()
        if self.mesh is not None:
            import jax.numpy as jnp
            from repro.core.precision import x64_scope
            sel = np.zeros(self.n_streams, bool)
            sel[lanes] = True
            sel = _lane_put(self.mesh, sel)
            with x64_scope():  # keep the f64 state f64 (scoped, like steps)
                for name, prior in zip(self._state_names, priors):
                    setattr(self, name, jnp.where(sel, prior,
                                                  getattr(self, name)))
                self.n_updates = jnp.where(sel, 0, self.n_updates)
            return
        first = getattr(self, self._state_names[0])
        if not first.flags.writeable:  # observe() returns jax-backed views
            for name in self._state_names:
                setattr(self, name, getattr(self, name).copy())
        for name, prior in zip(self._state_names, priors):
            getattr(self, name)[lanes] = prior
        self.n_updates[lanes] = 0

    def grow(self, n_streams: int) -> None:
        """Extend capacity to ``n_streams``; new lanes hold fresh priors.
        A new ``[S]`` shape re-traces the fused step once (dynamic-array
        amortisation); sharded banks round-trip state through host here —
        churn within capacity never does."""
        extra = int(n_streams) - self.n_streams
        if extra <= 0:
            return
        if self.mesh is not None and int(n_streams) % self.mesh.size:
            raise ValueError(
                f"sharded bank capacity must grow in multiples of the "
                f"mesh size {self.mesh.size}; got {n_streams}")
        priors = self._priors()
        for name, prior in zip(self._state_names, priors):
            cur = np.asarray(getattr(self, name))
            setattr(self, name,
                    np.concatenate([cur, np.full(extra, prior)]))
        self.n_updates = np.concatenate(
            [np.asarray(self.n_updates),
             np.zeros(extra, dtype=np.int64)])
        if self.mesh is not None:
            self._init_home(self.mesh)

    def shrink(self, n_streams: int) -> None:
        """Truncate capacity to the first ``n_streams`` lanes (re-traces
        once at the new ``[S]``, like :meth:`grow`)."""
        s = int(n_streams)
        if self.mesh is not None and s % self.mesh.size:
            raise ValueError(
                f"sharded bank capacity must shrink in multiples of the "
                f"mesh size {self.mesh.size}; got {n_streams}")
        for name in self._state_names:
            setattr(self, name, np.asarray(getattr(self, name))[:s].copy())
        self.n_updates = np.asarray(self.n_updates)[:s].copy()
        if self.mesh is not None:
            self._init_home(self.mesh)


class SlowdownFilterBank(_LaneBank):
    """Struct-of-arrays :class:`SlowdownFilter` over S streams (Eq. 6).

    One fused update advances every stream; ``mask`` lets streams that had
    no measurement this tick keep their state untouched.  For churning
    fleets the bank doubles as a lane pool: :meth:`reset_lanes` recycles a
    departed stream's lane for a new tenant (fresh filter state, no
    re-trace — the array shape is unchanged), while :meth:`grow` /
    :meth:`shrink` change capacity itself (a new ``[S]`` shape, so the
    next fused update traces once at the new size).  ``mesh=`` keeps the
    ``[S]`` state lane-sharded on device with donated updates
    (DESIGN.md §6).
    """

    _state_names = ("mu", "sigma", "gain", "process_noise")

    def __init__(self, n_streams: int, *, mu0: float = 1.0,
                 sigma0: float = 0.1, gain0: float = 0.5,
                 meas_noise: float = 1e-3, process_noise_floor: float = 0.1,
                 alpha: float = 0.3, miss_inflation: float = 0.2,
                 mesh=None):
        s = n_streams
        self.mu0, self.sigma0, self.gain0 = mu0, sigma0, gain0
        self.mu = np.full(s, mu0, dtype=np.float64)
        self.sigma = np.full(s, sigma0, dtype=np.float64)
        self.gain = np.full(s, gain0, dtype=np.float64)
        self.process_noise = np.full(s, process_noise_floor,
                                     dtype=np.float64)
        self.meas_noise = meas_noise
        self.process_noise_floor = process_noise_floor
        self.alpha = alpha
        self.miss_inflation = miss_inflation
        self.n_updates = np.zeros(s, dtype=np.int64)
        self._init_home(mesh)
        self._step = _jit_f64_sharded(_slowdown_bank_step, mesh,
                                      donate=(0, 1, 2, 3)) \
            if mesh is not None else _jit_f64(_slowdown_bank_step)

    def _priors(self) -> tuple:
        return (self.mu0, self.sigma0, self.gain0,
                self.process_noise_floor)

    def step_params(self) -> tuple:
        """The scalar hyperparameters of this bank's Eq. 6 recurrence, in
        the argument order :func:`fused_fleet_step` expects after the
        slow-down state and observation vectors: ``(Q0, alpha, R,
        miss_inflation)``."""
        return (self.process_noise_floor, self.alpha, self.meas_noise,
                self.miss_inflation)

    def observe(self, observed_latency: np.ndarray,
                profiled_latency: np.ndarray,
                deadline_missed: np.ndarray | None = None,
                mask: np.ndarray | None = None) -> np.ndarray:
        """Fused Eq. 6 update for all S lanes.

        ``observed_latency``/``profiled_latency`` are ``[S]`` (profiled
        must be positive on masked-in lanes), ``deadline_missed`` an
        optional ``[S]`` bool (miss-inflated ratio, Section 3.3), ``mask``
        an optional ``[S]`` bool — masked-out lanes keep their state bit
        for bit.  Returns the updated ``mu`` vector.
        """
        s = self.n_streams
        miss = np.zeros(s, bool) if deadline_missed is None \
            else (deadline_missed if _is_jax_array(deadline_missed)
                  else np.asarray(deadline_missed, bool))
        m = _mask_vec(mask, s)
        prof = _masked_positive(profiled_latency, m, "profiled_latency")
        self.mu, self.sigma, self.gain, self.process_noise = self._step(
            self.mu, self.sigma, self.gain, self.process_noise,
            _coerce_obs(observed_latency), prof, miss, m,
            self.process_noise_floor, self.alpha, self.meas_noise,
            self.miss_inflation)
        self._count_updates(m)
        return self.mu

    @property
    def std(self) -> np.ndarray:
        """Per-lane xi standard deviation (sigma floored at 1e-6), same
        convention as :attr:`SlowdownFilter.std`."""
        if _is_jax_array(self.sigma):
            import jax.numpy as jnp
            from repro.core.precision import x64_scope
            with x64_scope():
                return jnp.maximum(self.sigma, 1e-6)
        return np.maximum(self.sigma, 1e-6)


class IdlePowerFilterBank(_LaneBank):
    """Struct-of-arrays :class:`IdlePowerFilter` over S streams (Eq. 8),
    with the same lane-pool operations (and ``mesh=`` sharded home) as
    :class:`SlowdownFilterBank`."""

    _state_names = ("phi", "variance")

    def __init__(self, n_streams: int, *, phi0: float = 0.3,
                 variance0: float = 0.01, process_noise: float = 1e-4,
                 meas_noise: float = 1e-3, mesh=None):
        self.phi0, self.variance0 = phi0, variance0
        self.phi = np.full(n_streams, phi0, dtype=np.float64)
        self.variance = np.full(n_streams, variance0, dtype=np.float64)
        self.process_noise = process_noise
        self.meas_noise = meas_noise
        self.n_updates = np.zeros(n_streams, dtype=np.int64)
        self._init_home(mesh)
        self._step = _jit_f64_sharded(_idle_bank_step, mesh,
                                      donate=(0, 1)) \
            if mesh is not None else _jit_f64(_idle_bank_step)

    def _priors(self) -> tuple:
        return (self.phi0, self.variance0)

    def step_params(self) -> tuple:
        """The scalar hyperparameters of this bank's Eq. 8 recurrence, in
        the argument order :func:`fused_fleet_step` expects after the
        idle-power state and observation vectors: ``(S, V)``."""
        return (self.process_noise, self.meas_noise)

    def observe(self, idle_power: np.ndarray, active_power: np.ndarray,
                mask: np.ndarray | None = None) -> np.ndarray:
        """Fused Eq. 8 update for all S lanes: ``idle_power`` /
        ``active_power`` are ``[S]`` watt vectors (active must be positive
        on masked-in lanes); ``mask`` as in
        :meth:`SlowdownFilterBank.observe`.  Returns the updated phi."""
        s = self.n_streams
        m = _mask_vec(mask, s)
        active = _masked_positive(active_power, m, "active_power")
        self.phi, self.variance = self._step(
            self.phi, self.variance, _coerce_obs(idle_power),
            active, m, self.process_noise, self.meas_noise)
        self._count_updates(m)
        return self.phi


@dataclasses.dataclass
class ScalarKalman:
    """Generic scalar Kalman filter (constant-velocity-free, random-walk
    model).  Used by the straggler monitor in ``repro.runtime`` — one filter
    per host tracking that host's step-time ratio, mirroring the paper's ξ
    mechanism at pod scale."""

    mean: float = 1.0
    variance: float = 0.1
    process_noise: float = 1e-3
    meas_noise: float = 1e-2

    def observe(self, value: float) -> float:
        """One predict+update step on a scalar measurement; returns the
        posterior mean."""
        prior_var = self.variance + self.process_noise
        gain = prior_var / (prior_var + self.meas_noise)
        self.mean = self.mean + gain * (value - self.mean)
        self.variance = (1.0 - gain) * prior_var
        return self.mean

    @property
    def std(self) -> float:
        """Posterior standard deviation (variance floored at 1e-12)."""
        return math.sqrt(max(self.variance, 1e-12))
