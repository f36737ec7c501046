"""Serving engine: batched prefill + KV-cached decode, with per-level
compiled programs for anytime models.

One compiled ``decode_step`` per (nesting level) — static shapes, so the
controller can switch levels between requests at zero recompile cost after
warmup.  Each level's programs carry its name (``prefill_level<k>``,
``decode_level<k>``), which a profiler trace shows as the module
``jit_prefill_level<k>``.  The engine is mesh-agnostic: pass
``shardings`` built from launch/shardings.py to serve under pjit on a
pod; on CPU (tests, examples) it runs single-device.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.moe import route_stats
from repro.models.registry import Model
from repro.models import transformer as tfm
from repro.obs.trace import count as obs_count, span as obs_span


def _named(fn, name: str):
    """``fn`` under ``name``, the name its jitted program carries."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _greedy_step(cfg, lvl):
    """The greedy decode step at level ``lvl``, for one device program."""
    def step(params, token, cache_len, caches):
        """``(params, token [B, 1] int32, cache_len, caches)`` to the next
        token [B, 1] int32, ``cache_len + 1``, the updated caches and a
        dropless MoE model's expert choices [n_moe, B, k] (else None)."""
        o = tfm.lm_apply(params, cfg, token, mode="decode", caches=caches,
                         cache_len=cache_len, level=lvl)
        lg = o.logits[-1] if isinstance(o.logits, list) else o.logits
        nxt = jnp.argmax(lg[:, -1:], axis=-1).astype(jnp.int32)
        return nxt, cache_len + 1, o.caches, o.routing
    return step


@functools.partial(jax.jit, static_argnums=1)
def _grow(pre, shape):
    """A prefill KV leaf zero-padded along its sequence axis to the
    decode buffer's ``shape`` (one program per shape)."""
    return jnp.pad(pre, [(0, n - m) for n, m in zip(shape, pre.shape)])


@dataclasses.dataclass
class ServeEngine:
    """Per-level compiled serving programs for one (possibly nested)
    model: one prefill + one decode executable per anytime level, static
    shapes, so the controller switches levels between requests at zero
    recompile cost (DESIGN.md §8)."""

    model: Model
    max_len: int
    batch_size: int

    def __post_init__(self):
        cfg = self.model.cfg
        self.levels = list(range(1, cfg.nest_levels + 1)) \
            if cfg.nest_levels > 1 else [None]
        self._prefill = {}
        self._decode = {}
        for lvl in self.levels:
            tag = "" if lvl is None else f"_level{lvl}"
            self._prefill[lvl] = jax.jit(_named(
                lambda p, b, lvl=lvl: tfm.lm_apply(
                    p, cfg, b["tokens"], mode="prefill", level=lvl,
                    pos3d=b.get("pos3d")), f"prefill{tag}"))
            self._decode[lvl] = jax.jit(
                _named(_greedy_step(cfg, lvl), f"decode{tag}"),
                donate_argnums=(3,))
        self._route_stats = jax.jit(functools.partial(route_stats, cfg))
        self._cache_shapes, self._no_step = {}, {}

    @property
    def routed(self) -> bool:
        """Whether the programs also return their expert choices (a
        dropless MoE model)."""
        return self.model.cfg.moe_dispatch == "dropless"

    def init_caches(self, level: int | None = None):
        """Fresh decode caches sized to ``level`` (level-k programs write
        level-k KV widths)."""
        cfg = self.model.cfg
        if cfg.nest_levels > 1 and level is not None:
            # Level-k programs write level-k KV widths; size the buffers to
            # the level (the controller fixes the level per request, so a
            # request's cache stays consistent — DESIGN.md §8).
            from repro.models.attention import head_stripe_specs
            _, _, kv_spec = head_stripe_specs(cfg)
            n_kv = kv_spec.width(level) // cfg.head_dim
            lvl_cfg = cfg.replace(n_kv_heads=max(n_kv, 1))
            return tfm.init_caches(lvl_cfg, self.batch_size, self.max_len)
        return self.model.init_caches(self.batch_size, self.max_len)

    def prefill(self, params, prompt, level: int | None = None):
        """Run ``level``'s compiled prefill program on ``prompt`` [B, S0]
        (the deepest level when ``level`` is None); returns the model's
        :class:`~repro.models.transformer.LMOutput`."""
        cfg = self.model.cfg
        lvl = level if level is not None else \
            (cfg.nest_levels if cfg.nest_levels > 1 else None)
        return self._prefill[lvl](params, {"tokens": jnp.asarray(prompt)})

    def n_compiles(self) -> tuple[int, int]:
        """(prefill, decode) trace counts summed across level executables.

        The §8 zero-recompile contract at request granularity: after one
        warmup per level, switching levels between requests must leave both
        counts flat (one trace per level executable, ever).
        """
        return (sum(f._cache_size() for f in self._prefill.values()),
                sum(f._cache_size() for f in self._decode.values()))

    def generate(self, params, prompt: np.ndarray, n_new: int,
                 level: int | None = None,
                 deadline_s: float | None = None,
                 clock=None, obs=None) -> dict:
        """Greedy-decode ``n_new`` tokens after ``prompt`` [B, S0].

        Anytime semantics: when ``level`` is None and the model is nested,
        runs at the deepest level; a deadline (wall-clock seconds) makes
        generate return whatever tokens are complete at expiry (paper
        Eq. 10 staircase measured for real): no step is dispatched after
        the deadline, and the step dispatched before it is kept.  Prefill
        and every decode step run through the per-level compiled
        executables (zero recompiles after warmup — assert with
        :meth:`n_compiles`).  A decode step is one dispatch of its level's
        program, which takes the previous token, position and caches as
        device arrays and returns the next ones (the caches donated), and
        the host reads each token one step behind: step *i* is dispatched
        before token *i-1* is fetched, so the device runs while the host
        dispatches.  The decode buffers are the prefill's caches: a KV
        leaf is padded to ``max_len`` on the device, a state without a
        sequence axis (Mamba's SSM state and conv tail) is taken as it
        is.  ``clock`` injects the timebase (default
        ``time.perf_counter``; read once at entry, once before each step
        and once at the end) so deterministic tests drive deadlines and
        reported latency without real wall clocks; the reported latency
        is compute-inclusive because the last token is fetched before the
        final clock read.

        For a dropless MoE model the result also holds ``routing``: the
        prefill's expert choices [n_moe, B*S0, k] and each decode step's
        [n_moe, B, k], as device arrays (the loop never waits for them),
        and the request's ``moe_assigned_here`` and ``moe_experts_hit``
        (the counters below), reduced from them on the device by one
        program after the loop and fetched once, with the last token.

        Spans (``obs`` is an optional :class:`~repro.obs.FlightRecorder`;
        see :mod:`repro.obs.trace`): ``first_token`` from entry to the
        first token on the host, with a child ``cache_setup`` over making
        the decode buffers from the prefill's caches, then one
        ``decode_step`` per step over
        its dispatch and the fetch of the previous step's token, in a
        child ``token_fetch``; the last token's ``token_fetch`` follows the
        loop.  Counters: ``decode_steps``, ``decode_overlapped`` (steps
        dispatched while the previous one was still on the device) and
        ``deadline_cutoffs`` (a deadline stopped the loop early), and for
        a dropless MoE model ``moe_assigned_here`` ((token, slot) pairs
        routed to the held experts, prefill and decode) and
        ``moe_experts_hit`` (held experts given a token, summed over
        decode steps and expert layers).
        """
        if clock is None:
            clock = time.perf_counter
        t0 = clock()
        cfg = self.model.cfg
        lvl = level if level is not None else \
            (cfg.nest_levels if cfg.nest_levels > 1 else None)
        b, s0 = prompt.shape
        routed, stats, steps = self.routed, None, []
        with obs_span(obs, "first_token", "engine", level=lvl):
            out = self.prefill(params, prompt, lvl)
            with obs_span(obs, "cache_setup", "engine"):
                caches = self._merge(self.cache_shapes(lvl), out.caches)
            logits = out.logits if not isinstance(out.logits, list) \
                else out.logits[-1]
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            with obs_span(obs, "token_fetch", "engine"):
                toks = [np.asarray(tok)]
        decode, pending, overlapped = self._decode[lvl], None, 0
        pos = jnp.asarray(s0, jnp.int32) if n_new > 1 else None
        for i in range(n_new - 1):
            if deadline_s is not None and clock() - t0 > deadline_s:
                obs_count(obs, "deadline_cutoffs")
                break
            with obs_span(obs, "decode_step", "engine", step=i):
                if pending is not None and not pending.is_ready():
                    overlapped += 1
                tok, pos, caches, r = decode(params, tok, pos, caches)
                steps.append(r)
                if pending is not None:
                    with obs_span(obs, "token_fetch", "engine"):
                        toks.append(np.asarray(pending))
                pending = tok
        if routed:
            # Steps a deadline left out read as routed nowhere (one
            # program for any number of steps run).
            stats = self._route_stats(out.routing, tuple(steps) + (
                self._unrouted(out.routing.shape[0], b),)
                * (n_new - 1 - len(steps)))
        if pending is not None:
            with obs_span(obs, "token_fetch", "engine"):
                last, stats = jax.device_get((pending, stats))
                toks.append(last)
        else:
            stats = jax.device_get(stats)
        obs_count(obs, "decode_steps", len(toks) - 1)
        obs_count(obs, "decode_overlapped", overlapped)
        res = {
            "tokens": np.concatenate(toks, axis=1),
            "latency": clock() - t0,
            "level": lvl,
            "complete": len(toks) == n_new,
        }
        if routed:
            res["moe_assigned_here"], res["moe_experts_hit"] = \
                int(stats[0]), int(stats[1])
            obs_count(obs, "moe_assigned_here", res["moe_assigned_here"])
            obs_count(obs, "moe_experts_hit", res["moe_experts_hit"])
            res["routing"] = {"prefill": out.routing, "decode": steps}
        return res

    def _unrouted(self, n_moe: int, batch: int):
        """A decode step's expert choices where no step ran: -1, held
        nowhere."""
        key = (n_moe, batch)
        if key not in self._no_step:
            self._no_step[key] = jnp.full(
                (n_moe, batch, self.model.cfg.top_k), -1, jnp.int32)
        return self._no_step[key]

    def cache_shapes(self, level: int | None = None):
        """The decode buffers' shapes at ``level`` (no allocation)."""
        if level not in self._cache_shapes:
            self._cache_shapes[level] = jax.eval_shape(
                lambda: self.init_caches(level))
        return self._cache_shapes[level]

    @staticmethod
    def _merge(buffers, prefill):
        """The decode caches from the prefill's: each leaf whose shape
        differs from its buffer in ``buffers`` (arrays or shapes) — a KV
        leaf, shorter along its sequence axis — is zero-padded to it on
        the device; any other leaf is the prefill's own array, uncopied."""
        def merge(buf, pre):
            """One decode cache leaf from its prefill leaf."""
            if buf.shape == pre.shape:
                return pre
            return _grow(pre.astype(buf.dtype), tuple(buf.shape))
        return jax.tree.map(merge, buffers, prefill)
