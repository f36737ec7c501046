"""The comparison that decides ``correct`` in the model cells.

* The controller: the plain reference (:mod:`bench.alert_ref`) rebuilds
  the profile table from the measured full-cap latency of each level and
  its own power model, then follows the served requests in order — each
  request's deadline and budget, the level and cap the program picked,
  the latency it measured — through Eq. 5 and the Eq. 6/8 filters.
  ``pick_mismatches`` counts picks that differ where no tie within the
  margin could explain it, and delivered miss flags or accuracies that
  differ; ``controller_gap`` is the largest relative gap over the table,
  the booked energies and the filters' final state.
* The model: on a sample of served requests, a float32 pass of the
  reference over the prompt and the served tokens, at the picked level,
  gives every served token's logit; ``logit_gap`` is the widest gap by
  which a served token lies below the reference's best at its position.
  The control reads, at the same positions, the gap of the token a
  float8 pass ranks first.
"""

from __future__ import annotations

import numpy as np

from bench import alert_ref as ref
from bench import lm_ref, profiles


def power_model(cfg: dict) -> profiles.Power:
    """The configuration's power model."""
    pm = cfg["controller"]["power_model"]
    return profiles.Power(pm["p_idle"], pm["p_tdp"], pm["min_fraction"])


def serve_table(cfg: dict, base_latency) -> profiles.Table:
    """The table the controller works on: each level's measured full-cap
    latency stretched by ``1/f`` for each power bucket's clock fraction."""
    pm = power_model(cfg)
    caps = pm.buckets(cfg["controller"]["power_buckets"])
    f = np.asarray([pm.speed_fraction(c) for c in caps])
    base = np.asarray(base_latency, np.float64)
    accs = cfg["assumed"]["level_accuracies"]
    return profiles.Table(
        names=[f"level{k + 1}" for k in range(len(accs))],
        accuracy=np.asarray(accs, np.float64), caps=caps,
        latency=base[:, None] / f[None, :],
        run_power=np.tile([pm.power_at_fraction(x) for x in f],
                          (len(accs), 1)),
        q_fail=float(cfg["controller"]["q_fail"]),
        levels=list(range(1, len(accs) + 1)))


def serve_chain(cfg: dict, table, served: list, dtype=np.float64,
                follow: bool = True, margin: float = 1e-9):
    """The controller over ``served`` in order, in ``dtype``: each
    request's Eq. 5 pick (and whether it is clear of ties), what the
    measured latency delivers at the level and cap used (the program's
    when ``follow``, else the reference's own), and the Eq. 6/8 update.
    Returns per-request records and the filters' final state.

    ``served`` holds per request ``deadline``, ``complete``, ``measured``
    (the generate latency) and, to follow, the program's ``level`` and
    ``cap``."""
    d = np.dtype(dtype).type
    budget = cfg["controller"]["power_budget_w"]
    st = {k: np.full(1, v, dtype) for k, v in ref.SLOW_PRIOR.items()}
    st["phi"] = np.full(1, ref.IDLE_PRIOR["phi"], dtype)
    st["var"] = np.full(1, ref.IDLE_PRIOR["var"], dtype)
    lat_t = table.latency.astype(dtype)
    pw_t = table.run_power.astype(dtype)
    q = table.accuracy.astype(dtype)
    n_l = table.latency.shape[1]
    out = []
    for r in served:
        t = d(r["deadline"])
        acc, en = ref.estimate(table, st["mu"], st["sigma"], st["phi"],
                               [t], dtype)
        e_goal = [t * d(budget)]
        own = int(ref.select(acc, en, [ref.GOAL_MAX_ACCURACY], [0.0],
                             e_goal)[0])
        if follow:
            i = int(r["level"]) - 1
            j = int(np.argmin(np.abs(table.caps - r["cap"])))
        else:
            i, j = own // n_l, own % n_l
        lat = d(r["measured"])
        run_t = min(lat, t)
        missed = bool(lat > t) or not r["complete"]
        p = pw_t[i, j]
        out.append(dict(
            r, own=own, pick=i * n_l + j, level=i + 1,
            cap=float(table.caps[j]), missed=missed,
            clear=bool(ref.clear(acc, en, [ref.GOAL_MAX_ACCURACY], [0.0],
                                 e_goal, margin)[0]),
            accuracy=q[i] if not missed else d(table.q_fail),
            energy=p * run_t + st["phi"][0] * p * max(t - run_t, d(0.0))))
        st.update(ref.slowdown_step(st, [run_t], [lat_t[i, j]], [missed],
                                    dtype))
        st["phi"], st["var"] = ref.idle_step(st["phi"], st["var"],
                                             [d(0.25) * p], [p], dtype)
    return out, st


def controller_check(cfg: dict, table_prog: dict, served: list,
                     state_prog: dict, margin: float) -> dict:
    """Hold the controller's run to the float64 reference.

    ``table_prog``: the program's ``latency``/``run_power``/``caps``;
    ``served``: as :func:`serve_chain` takes it, with the program's
    ``missed``, ``accuracy`` and ``energy``; ``state_prog``: the filters'
    final mu, sigma, gain, q, phi, var."""
    table = serve_table(cfg, np.asarray(table_prog["latency"])[:, -1])
    gaps = [ref.rel_gap(table_prog["latency"], table.latency),
            ref.rel_gap(table_prog["run_power"], table.run_power),
            ref.rel_gap(table_prog["caps"], table.caps)]
    mine, st = serve_chain(cfg, table, served, margin=margin)
    bad = sum(int(m["clear"] and m["own"] != m["pick"])
              + int(m["missed"] != bool(r["missed"]))
              + int(m["accuracy"] != r["accuracy"])
              for m, r in zip(mine, served))
    gaps.append(ref.rel_gap([r["energy"] for r in served],
                            [m["energy"] for m in mine]))
    gaps += [ref.rel_gap(np.atleast_1d(state_prog[k]), st[k])
             for k in ("mu", "sigma", "gain", "q", "phi", "var")]
    return {"pick_mismatches": float(bad), "controller_gap": max(gaps)}


def controller_control(cfg: dict, base_latency, served: list,
                       margin: float) -> dict:
    """The float32 reference in the controller's place, held to the
    float64 reference over the same requests and measured latencies."""
    table = serve_table(cfg, base_latency)
    low = np.float32
    out, st = serve_chain(cfg, table, served, low, follow=False)
    out = [dict(r, accuracy=float(r["accuracy"]), energy=float(r["energy"]))
           for r in out]
    prog_table = {k: np.asarray(v).astype(low).astype(np.float64)
                  for k, v in (("latency", table.latency),
                               ("run_power", table.run_power),
                               ("caps", table.caps))}
    return controller_check(cfg, prog_table, out, st, margin)


def logit_gaps(cfg: dict, params, sample: list, gen_tokens: int,
               fp8: bool = False):
    """Per sampled request, the served tokens' gaps below the float32
    reference's best; with ``fp8`` the gaps of the tokens a float8 pass
    ranks first at the same positions.  Every pass is over the prompt
    and ``gen_tokens - 1`` tokens (a request cut short by its deadline is
    padded after its last token, which a causal pass never reads), so
    one program per level serves every request."""
    gaps = []
    for r in sample:
        prompt, toks = np.asarray(r["prompt"]), np.asarray(r["tokens"])
        n = toks.shape[1]
        full = np.zeros((prompt.shape[0], prompt.shape[1] + gen_tokens - 1),
                        np.int32)
        full[:, :prompt.shape[1]] = prompt
        full[:, prompt.shape[1]:prompt.shape[1] + n - 1] = toks[:, :-1]
        first = prompt.shape[1] - 1
        want = np.asarray(lm_ref.forward(params, cfg, full, r["level"],
                                         first, gen_tokens))[:, :n]
        if fp8:
            low = np.asarray(lm_ref.forward(params, cfg, full, r["level"],
                                            first, gen_tokens,
                                            fp8=True))[:, :n]
            toks = low.argmax(axis=-1)
        gaps.append(lm_ref.served_gaps(want, toks))
    return gaps
