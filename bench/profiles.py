"""Profile tables the cells run, built by the benchmark's own arithmetic.

A copy of what the program's benchmarks build (the cubic-DVFS power
model, the roofline profile, the image-task candidate family and the
paper's Table 3 deadline range), so that a later change to the program
cannot move the yardstick.  The per-candidate FLOPs and bytes come from
the configuration file (``configs/fleet-image-100k.json``), which records
how they were derived.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Power:
    """Cubic DVFS: ``p(f) = p_idle + (p_tdp - p_idle) f^3``, clock
    fraction ``f`` in ``[min_fraction, 1]``."""

    p_idle: float
    p_tdp: float
    min_fraction: float = 0.3

    def speed_fraction(self, cap: float) -> float:
        """Clock fraction reachable under power ``cap``."""
        if cap >= self.p_tdp:
            return 1.0
        usable = max(cap - self.p_idle, 0.0)
        f = (usable / (self.p_tdp - self.p_idle)) ** (1.0 / 3.0)
        return float(np.clip(f, self.min_fraction, 1.0))

    def power_at_fraction(self, f: float) -> float:
        """Draw (W) at clock fraction ``f``."""
        f = float(np.clip(f, self.min_fraction, 1.0))
        return self.p_idle + (self.p_tdp - self.p_idle) * f ** 3

    def buckets(self, n: int) -> np.ndarray:
        """``n`` power caps from the lowest operating point to TDP."""
        return np.linspace(self.power_at_fraction(self.min_fraction),
                           self.p_tdp, n)


@dataclasses.dataclass
class Table:
    """Candidates x power buckets: profiled latency and active power, the
    candidates' accuracies, and each candidate's staircase (the candidate
    indices of its anytime levels 1..m; a traditional model is ``[k]``)."""

    names: list
    accuracy: np.ndarray        # [K]
    caps: np.ndarray            # [L]
    latency: np.ndarray         # [K, L] s
    run_power: np.ndarray       # [K, L] W
    q_fail: float
    levels: list                # [K] anytime level, 0 for traditional

    @property
    def stairs(self) -> list:
        """Per candidate, the candidate indices of its levels 1..m."""
        return stairs_of(self.levels)

    @property
    def is_anytime(self) -> np.ndarray:
        """[K] bool: the candidate is a level of an anytime group."""
        return np.asarray(self.levels) > 0


def stairs_of(levels: list) -> list:
    """Staircases from per-candidate anytime levels (0: traditional)."""
    group = sorted((lv, k) for k, lv in enumerate(levels) if lv > 0)
    order = [k for _, k in group]
    out = []
    for k, lv in enumerate(levels):
        out.append([k] if lv == 0 else order[:order.index(k) + 1])
    return out


def roofline_table(cands: list, power: Power, n_power: int, q_fail: float,
                   peak_flops: float, hbm_bw: float) -> Table:
    """Latency under each cap: compute term scales with 1/f, memory term
    does not, the larger wins; active power is the cap's operating point."""
    caps = power.buckets(n_power)
    lat = np.zeros((len(cands), n_power))
    pw = np.zeros_like(lat)
    for i, c in enumerate(cands):
        for j, cap in enumerate(caps):
            f = power.speed_fraction(cap)
            lat[i, j] = max(c["flops"] / (peak_flops * f),
                            c["bytes_hbm"] / hbm_bw)
            pw[i, j] = power.power_at_fraction(f)
    return Table(names=[c["name"] for c in cands],
                 accuracy=np.asarray([c["accuracy"] for c in cands]),
                 caps=caps, latency=lat, run_power=pw, q_fail=float(q_fail),
                 levels=[c.get("anytime_level", 0) for c in cands])


def per_input_cost(arch: dict, tokens: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one ``tokens``-token input of a dense-served
    architecture: 2 FLOPs per active parameter and token; bf16 weights
    plus the K/V of every layer."""
    flops = 2.0 * arch["active_params"] * tokens
    byts = 2.0 * arch["params"] + 2.0 * tokens * arch["d_model"] * 2 \
        * arch["n_layers"]
    return flops, byts


def nested_level_fractions(levels: int) -> list:
    """FLOP share of each width-nested level (power-of-2 stripes,
    block-lower-triangular) relative to the dense full-width matmul."""
    total = 2 ** (levels + 2)
    bounds = [0] + [total * 2 ** (k - 1) // 2 ** (levels - 1)
                    for k in range(1, levels + 1)]
    out = []
    for lv in range(1, levels + 1):
        macs = sum(bounds[i] * (bounds[i] - bounds[i - 1])
                   for i in range(1, lv + 1))
        out.append(macs / (total * total))
    return out


def family_candidates(cfg: dict) -> list:
    """The candidate list of a fleet configuration: its traditional
    architectures, then a width-nested anytime copy of the largest one
    whose level accuracies rise with the square root of the level index
    from just below the smallest model to just below the largest."""
    fam = cfg["family"]
    tokens = cfg["tokens_per_input"]
    cands = []
    for a in fam:
        f, b = per_input_cost(a, tokens)
        cands.append({"name": a["name"], "flops": f, "bytes_hbm": b,
                      "accuracy": a["accuracy"]})
    top_f, top_b = per_input_cost(fam[-1], tokens)
    n_lv = cfg["anytime_levels"]
    accs = np.interp(np.linspace(0, 1, n_lv) ** 0.5, [0, 1],
                     [fam[0]["accuracy"] - cfg["anytime_acc_drop"][0],
                      fam[-1]["accuracy"] - cfg["anytime_acc_drop"][1]])
    for k, (fr, acc) in enumerate(zip(nested_level_fractions(n_lv), accs),
                                  start=1):
        cands.append({"name": f"anytime-l{k}", "flops": top_f * fr,
                      "bytes_hbm": top_b * (0.3 + 0.7 * fr),
                      "accuracy": float(acc), "anytime_level": k})
    return cands


def fleet_table(cfg: dict) -> Table:
    """The fleet configuration's candidate table."""
    pm = cfg["power_model"]
    return roofline_table(family_candidates(cfg),
                          Power(pm["p_idle"], pm["p_tdp"],
                                pm["min_fraction"]),
                          cfg["power_buckets"], cfg["q_fail"],
                          cfg["roofline"]["peak_flops"],
                          cfg["roofline"]["hbm_bw"])


def deadline_range(table: Table, n: int = 5) -> np.ndarray:
    """Paper Table 3: 0.4x to 2x the full-cap latency of the deepest
    anytime level."""
    any_k = [k for k in range(len(table.names)) if table.is_anytime[k]]
    base = max(table.latency[k, -1] for k in any_k)
    return base * np.linspace(0.4, 2.0, n)
