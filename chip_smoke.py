#!/usr/bin/env python3
"""Chip smoke test: the ALERT serving path and its fleet decision plane
on a TPU, through the entry points a user calls.

    python chip_smoke.py [--seed 0]      # one chip: model, decisions, kernel
    python chip_smoke.py --chips 4       # lane-sharded decisions, 4 chips

Phases (one chip):

* **model** — ``alert-anytime-120m`` at its published widths (bf16,
  weights from ``--seed``) served through ``launch/serve.py``'s path:
  ``ServeEngine`` + ``AlertServer`` profile all four levels, answer 16
  requests, then ``FleetAlertServer`` runs 6 ticks; no program compiles
  after warm-up.  Prefill logits at levels 1 and 4 are checked against
  the same prefill in float32 on the host CPU.
* **decisions** — ``MegatickGateway``: 100,000 Poisson sessions over
  4,096 lanes, coarse tick, 48 rounds, with zero re-traces; the
  ``BatchedAlertEngine`` picks on 1,024 sampled lanes against the scalar
  NumPy reference (``core/reference.py``) under the kernel's tie margins.
* **kernel** — ``BatchedAlertEngine(backend="pallas")`` at S=65,536
  against the XLA engine on the same state, under the margin contract
  (docs/KERNELS.md); the compiled program holds the Mosaic kernel.

``--chips 4`` runs only the decisions phase, on a 4-device lane mesh and
on one chip of the same host, and requires identical results.

Every phase prints one line naming the device.  The last line of
standard output is ``{"ok": true, "device": {...}}``; any failure exits
non-zero before it.  Without a TPU the script exits non-zero before any
phase.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "alert-anytime-120m"
BATCH, PROMPT_LEN, GEN_TOKENS, MAX_LEN = 4, 8, 4, 64
# Prefill logits, bf16 on the chip vs float32 on the CPU: the largest
# difference may be this fraction of the largest reference logit.
LOGIT_RTOL = 0.05
SESSIONS, LANES, ROUNDS = 100_000, 4096, 48
SAMPLED_LANES = 1024
KERNEL_LANES = 65_536


def say(phase: str, kind: str, msg: str) -> None:
    print(f"[{phase}] {kind}: {msg}", flush=True)


class SmokeFailure(Exception):
    """A phase's result is wrong."""


def check(ok, msg="check failed") -> None:
    """Fail the run when ``ok`` is false (kept under ``python -O``)."""
    if not ok:
        raise SmokeFailure(msg)


def lane_state(rng, table, s: int) -> dict:
    """A mixed-goal fleet's per-lane decision inputs at the slow-down,
    deadline and goal ranges the serving paths produce."""
    from benchmarks.common import deadline_range

    med_en = float(np.median(table.run_power) * np.median(table.latency))
    return dict(mu=rng.uniform(0.6, 2.5, s), sigma=rng.uniform(0.01, 0.4, s),
                phi=rng.uniform(0.05, 0.6, s),
                deadline=rng.choice(deadline_range(table, 5), s),
                accuracy_goal=rng.uniform(0.5, 0.9, s),
                energy_goal=rng.uniform(0.5, 3.0, s) * med_en,
                goal_kind=rng.integers(0, 2, s).astype(np.int64),
                active=np.ones(s, bool))


def select(engine, st, **kw):
    return engine.select(st["mu"], st["sigma"], st["phi"], st["deadline"],
                         accuracy_goal=st["accuracy_goal"],
                         energy_goal=st["energy_goal"],
                         goal_kind=st["goal_kind"], active=st["active"],
                         **kw)


# ---------------------------------------------------------------- #
# model                                                            #
# ---------------------------------------------------------------- #
def phase_model(kind: str, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.controller import Constraints, Goal
    from repro.data.synthetic import SyntheticLM
    from repro.launch.serve import build_served_model, level_accuracies
    from repro.models import transformer as tfm
    from repro.serving.alert_server import AlertServer, FleetAlertServer
    from repro.serving.engine import ServeEngine

    t0 = time.perf_counter()
    cfg, model, params = build_served_model(ARCH, seed=seed)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=32, global_batch=BATCH,
                       noise=0.05)
    accs = level_accuracies(model, params, data)
    engine = ServeEngine(model, max_len=MAX_LEN, batch_size=BATCH)
    goal = Goal.MAXIMIZE_ACCURACY
    server = AlertServer(engine, params, accs, goal, prompt_len=PROMPT_LEN,
                         gen_tokens=GEN_TOKENS)
    warm = engine.n_compiles()
    n_lvl = cfg.nest_levels
    check(warm == (n_lvl, n_lvl), f"warm-up compiled {warm}")
    lat = server.table.latency[:, -1]
    check(lat.shape == (n_lvl,) and np.all(np.isfinite(lat))
          and np.all(lat > 0), f"level profile {lat}")
    t_warm = time.perf_counter() - t0

    base = float(server.table.latency[-1, -1])
    rng = np.random.default_rng(seed)
    served = []
    for i in range(16):
        cons = Constraints.from_power_budget(
            base * 1.2 * rng.uniform(0.85, 1.25), 150.0)
        prompt = np.asarray(data.batch_at(20_000 + i)
                            ["tokens"][:BATCH, :PROMPT_LEN])
        served.append(server.serve_one(prompt, cons))
    check(all(np.isfinite(r.latency) and r.energy >= 0 for r in served),
          "non-finite request outcome")

    fleet = FleetAlertServer(engine, params, accs, goal, n_streams=4,
                             prompt_len=PROMPT_LEN, gen_tokens=GEN_TOKENS)
    cons = [Constraints.from_power_budget(base * 1.2, 150.0)] * 4
    for t in range(6):
        prompts = [np.asarray(data.batch_at(30_000 + 4 * t + s)
                              ["tokens"][:BATCH, :PROMPT_LEN])
                   for s in range(4)]
        outs = fleet.serve_tick(prompts, cons)
        check(all(o is not None and np.isfinite(o.latency) for o in outs),
              f"fleet tick {t}: a live stream was not served")
    check(engine.n_compiles() == warm,
          f"recompiled after warm-up: {warm} -> {engine.n_compiles()}")
    check(fleet.scoring.n_compiles()[1] == 1, "fleet scoring re-traced")

    # Plain reference: the same prefill in float32 on the host CPU.
    cpu = jax.devices("cpu")[0]
    cfg32 = cfg.replace(dtype="float32")
    p32 = jax.device_put(jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params), cpu)
    prompt = np.asarray(data.batch_at(40_000)["tokens"][:BATCH, :PROMPT_LEN])
    checks = []
    for lvl in (1, n_lvl):
        got = np.asarray(engine.prefill(params, prompt, lvl).logits,
                         np.float32)
        ref_fn = jax.jit(lambda p, t, lvl=lvl: tfm.lm_apply(
            p, cfg32, t, mode="prefill", level=lvl).logits)
        want = np.asarray(ref_fn(p32, jax.device_put(prompt, cpu)))
        check(got.shape == want.shape and np.all(np.isfinite(got)),
              f"level {lvl}: logits {got.shape} vs {want.shape}")
        err = float(np.abs(got - want).max())
        scale = float(np.abs(want).max())
        check(err <= LOGIT_RTOL * scale,
              f"level {lvl}: logits error {err} > {LOGIT_RTOL} x {scale}")
        top2 = np.sort(want, axis=-1)[..., -2:]
        decided = (top2[..., 1] - top2[..., 0]) > 2 * err
        agree = got.argmax(-1) == want.argmax(-1)
        check(np.all(agree[decided]), f"level {lvl}: greedy tokens differ")
        checks.append(f"L{lvl} max|dlogit|={err:.4g} "
                      f"({err / scale:.4g} of max|logit|), greedy "
                      f"{int(agree.sum())}/{agree.size} equal, "
                      f"{int(decided.sum())} decided all equal")
    miss = float(np.mean([r.missed for r in served]))
    levels = [r.level for r in served]
    say("model", kind,
        f"{ARCH} {n_params / 1e6:.1f}M params {cfg.dtype}, "
        f"{n_lvl} levels profiled (full-cap latency "
        f"{', '.join(f'{x * 1e3:.3f}' for x in lat)} ms), warm-up "
        f"{t_warm:.1f} s; 16 requests (levels {levels}, miss rate "
        f"{miss:.2f}); 6 fleet ticks x 4 streams; n_compiles {warm} "
        f"flat; vs float32 CPU prefill: {'; '.join(checks)}")


# ---------------------------------------------------------------- #
# decisions                                                        #
# ---------------------------------------------------------------- #
def decisions_workload(seed: int):
    """``bench_megatick``'s workload: Poisson min-energy sessions at
    about lane saturation, coarse tick (one deadline), 48 rounds."""
    from benchmarks.common import deadline_range, family_table
    from repro.core.controller import Constraints, Goal
    from repro.serving.sim import CPU_ENV
    from repro.traffic import (PoissonProcess, TenantSpec, build_sessions,
                               generate_requests)

    table = family_table("image")
    dl = float(deadline_range(table, 5)[3])
    cons = Constraints(deadline=dl, accuracy_goal=0.78)
    mix = [TenantSpec("min-energy", Goal.MINIMIZE_ENERGY, cons,
                      PoissonProcess((LANES / dl) / SESSIONS),
                      n_sessions=SESSIONS, phases=CPU_ENV)]
    sessions = build_sessions(mix, ROUNDS * dl, seed=seed)
    return table, dl, sessions, generate_requests(sessions)


def run_megatick(table, dl, sessions, requests, mesh=None):
    """Two runs of the gateway: the second must re-use the first's one
    compiled scan.  Returns the second run's result and timings."""
    from repro.traffic import MegatickGateway

    gw = MegatickGateway(table, LANES, tick=dl, max_queue=4 * LANES,
                         chunk=ROUNDS, mesh=mesh)
    t0 = time.perf_counter()
    gw.run(sessions, requests)
    t_first = time.perf_counter() - t0
    res = gw.run(sessions, requests)
    check(res.n_compiles == (0, 1), f"megatick re-traced: {res.n_compiles}")
    served = res.served
    check(served.any() and np.all(np.isfinite(res.latency[served]))
          and np.all(np.isfinite(res.energy[served])),
          "megatick served nothing or non-finite outcomes")
    return res, t_first, gw.last_plan_s, gw.last_scan_s


RESULT_FIELDS = ("sid", "index", "arrival", "status", "start", "latency",
                 "sojourn", "missed", "accuracy", "energy", "model_index",
                 "power_index")


def phase_decisions(kind: str, seed: int) -> None:
    from repro.core.batched import GOAL_MIN_ENERGY, BatchedAlertEngine
    from repro.core.controller import Constraints, Goal
    from repro.core.reference import ScalarReferenceController
    from repro.kernels.alert_select import clear_lanes

    table, dl, sessions, requests = decisions_workload(seed)
    res, t_first, plan_s, scan_s = run_megatick(table, dl, sessions,
                                                requests)

    rng = np.random.default_rng(seed)
    st = lane_state(rng, table, LANES)
    engine = BatchedAlertEngine(table, None)
    batch = select(engine, st)
    est = engine.estimate(st["mu"], st["sigma"], st["phi"], st["deadline"],
                          active=st["active"])
    lanes = np.sort(rng.choice(LANES, SAMPLED_LANES, replace=False))
    picks, acc_g, en_g = [], [], []
    for n in lanes:
        min_e = st["goal_kind"][n] == GOAL_MIN_ENERGY
        ref = ScalarReferenceController(
            table, Goal.MINIMIZE_ENERGY if min_e else Goal.MAXIMIZE_ACCURACY)
        ref.slowdown.mu = float(st["mu"][n])
        ref.slowdown.sigma = float(st["sigma"][n])
        ref.idle_power.phi = float(st["phi"][n])
        goal = {"accuracy_goal": float(st["accuracy_goal"][n])} if min_e \
            else {"energy_goal": float(st["energy_goal"][n])}
        d = ref.select(Constraints(deadline=float(st["deadline"][n]), **goal))
        picks.append((d.model_index, d.power_index))
        e = ref.estimate(float(st["deadline"][n]))
        acc_g.append(e.accuracy)
        en_g.append(e.energy)
    picks = np.asarray(picks)
    acc_g, en_g = np.asarray(acc_g), np.asarray(en_g)
    clear = clear_lanes(acc_g, en_g, st["accuracy_goal"][lanes],
                        st["energy_goal"][lanes], st["goal_kind"][lanes],
                        st["active"][lanes])
    differ = (picks[:, 0] != batch.model_index[lanes]) | \
        (picks[:, 1] != batch.power_index[lanes])
    check(not np.any(differ & clear),
          f"{int((differ & clear).sum())} clear lanes disagree with reference")
    d_acc = float(np.abs(est.accuracy[lanes] - acc_g).max())
    d_en = float(np.abs(est.energy[lanes] - en_g).max())
    bitwise = d_acc == 0.0 and d_en == 0.0 and not differ.any()
    say("decisions", kind,
        f"megatick {len(sessions)} sessions / {LANES} lanes / "
        f"{res.n_rounds} rounds: {int(res.served.sum())} of {res.offered} "
        f"served, n_compiles {res.n_compiles} (zero re-traces), first run "
        f"{t_first:.2f} s, then plan {plan_s:.3f} s + scan {scan_s:.3f} s; "
        f"engine vs scalar reference on {SAMPLED_LANES} lanes: "
        f"{int(differ.sum())} picks differ, {int((differ & clear).sum())} "
        f"outside the tie margins ({int(clear.sum())} clear); emulated "
        f"f64 estimates {'bitwise' if bitwise else 'not bitwise'} "
        f"(max |d acc| {d_acc:.3g}, max |d energy| {d_en:.3g})")


def phase_decisions_sharded(kind: str, seed: int, n_chips: int) -> None:
    from repro.core.batched import BatchedAlertEngine
    from repro.launch.mesh import make_lane_mesh

    mesh = make_lane_mesh(n_chips)
    table, dl, sessions, requests = decisions_workload(seed)
    one, t1, plan1, scan1 = run_megatick(table, dl, sessions, requests)
    res, t4, plan4, scan4 = run_megatick(table, dl, sessions, requests,
                                         mesh=mesh)
    bad = [f for f in RESULT_FIELDS
           if not np.array_equal(getattr(one, f), getattr(res, f))]
    check(not bad, f"sharded megatick differs from one chip in {bad}")
    check((one.n_rounds, one.pages_in, one.pages_out)
          == (res.n_rounds, res.pages_in, res.pages_out),
          "sharded megatick rounds or paging differ from one chip")

    st = lane_state(np.random.default_rng(seed), table, LANES)
    a = select(BatchedAlertEngine(table, None), st)
    b = select(BatchedAlertEngine(table, None, mesh=mesh), st)
    fields = ("model_index", "power_index", "predicted_latency",
              "predicted_accuracy", "predicted_energy", "feasible",
              "relaxed_code")
    bad = [f for f in fields
           if not np.array_equal(getattr(a, f), getattr(b, f))]
    check(not bad, f"sharded engine differs from one chip in {bad}")
    say("decisions-sharded", kind,
        f"megatick {len(sessions)} sessions / {LANES} lanes / "
        f"{res.n_rounds} rounds on a {n_chips}-chip lane mesh == one chip "
        f"in all {len(RESULT_FIELDS)} result arrays "
        f"({int(res.served.sum())} served), n_compiles {res.n_compiles}; "
        f"one chip: plan {plan1:.3f} s + scan {scan1:.3f} s, "
        f"{n_chips} chips: plan {plan4:.3f} s + scan {scan4:.3f} s; "
        f"engine picks and predictions at S={LANES} identical")


# ---------------------------------------------------------------- #
# kernel                                                           #
# ---------------------------------------------------------------- #
def phase_kernel(kind: str, seed: int) -> None:
    import functools

    import jax

    from benchmarks.common import family_table
    from repro.core.batched import BatchedAlertEngine
    from repro.core.precision import x64_scope
    from repro.kernels.alert_select import (alert_select, clear_lanes,
                                            margin_report)

    table = family_table("image")
    st = lane_state(np.random.default_rng(seed + 1), table, KERNEL_LANES)
    xla = BatchedAlertEngine(table, None)
    pal = BatchedAlertEngine(table, None, backend="pallas")
    ref = select(xla, st)
    got = select(pal, st)
    est = xla.estimate(st["mu"], st["sigma"], st["phi"], st["deadline"],
                       active=st["active"])
    clear = clear_lanes(est.accuracy, est.energy, st["accuracy_goal"],
                        st["energy_goal"], st["goal_kind"], st["active"])
    rep = margin_report(ref, got, clear)
    check(rep["mismatches"] == 0 and rep["pred_ok"], rep)
    check(pal.n_compiles()[1] == 1, "pallas engine re-traced")

    kern = functools.partial(
        alert_select, latency=xla._c_latency, run_power=xla._c_run_power,
        weights=xla._c_weights, q_fail=xla._c_q_fail)
    args = [st[n] for n in ("mu", "sigma", "phi", "deadline",
                            "accuracy_goal", "energy_goal", "goal_kind",
                            "active")]
    with x64_scope():
        hlo = jax.jit(kern).lower(*args).compile().as_text()
    check("tpu_custom_call" in hlo, "alert_select did not compile to Mosaic")

    times = {}
    for name, eng in (("xla", xla), ("pallas", pal)):
        select(eng, st, predictions=False)
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            select(eng, st, predictions=False)
            ts.append(time.perf_counter() - t0)
        times[name] = sorted(ts)[2]
    say("kernel", kind,
        f"alert_select (Mosaic, tpu_custom_call) vs float64 XLA engine at "
        f"S={KERNEL_LANES}: {rep['n_clear']} lanes clear the tie margins, "
        f"0 of them disagree, predictions within tolerance; "
        f"{rep['n_differ']} picks differ in all (near-ties); median "
        f"pick-only select incl. host transfer: XLA f64 "
        f"{times['xla'] * 1e3:.2f} ms, Pallas "
        f"{times['pallas'] * 1e3:.2f} ms")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, workload and lane states")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the lane-sharded decisions phase")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    if len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices; JAX found {len(jax.devices())}")
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    kind = dev.device_kind
    if args.chips == 1:
        phase_model(kind, args.seed)
        phase_decisions(kind, args.seed)
        phase_kernel(kind, args.seed)
    else:
        phase_decisions_sharded(kind, args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
