"""Tiny copies of the benchmark's cells, for runs on the CPU.

:func:`make_root` lays out a checkout-like directory that holds a
``BENCHMARK.json`` and a ``bench/`` tree of configuration, traffic,
driver and metric files, so the harness finds everything by name there
exactly as it does in the repository.  Sizes are cut so a whole run,
comparison included, takes seconds.
"""

from __future__ import annotations

import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Per configuration: the keys a tiny copy changes.
TINY_CONFIGS = {
    "fleet-image-100k": {"sessions": 1500, "lanes": 64},
    "alert-anytime-120m": {"n_layers": 2, "d_model": 64, "n_heads": 8,
                           "n_kv_heads": 8, "head_dim": 8, "d_ff": 128,
                           "vocab": 256, "dtype": "float32"},
}
# Per cell: the keys a tiny copy of its traffic file changes.
TINY_TRAFFIC = {
    "fleet-image-100k.megatick": {"horizon_x": 6, "chunk_rounds": 8,
                                  "warmup_horizon_x": 2},
    "fleet-image-100k.finetick": {"horizon_x": 4},
    "alert-anytime-120m.decode": {"batch": 2, "prompt_len": 8,
                                  "gen_tokens": 4, "check_requests": 3,
                                  "deadline_base_s": 0.002},
    "alert-anytime-120m.oneshot": {"batch": 2, "prompt_len": 16,
                                   "check_requests": 3,
                                   "deadline_base_s": 0.002},
}


def load(path):
    """A JSON file's object."""
    with open(path) as f:
        return json.load(f)


def dump(obj, path):
    """Write ``obj`` as JSON at ``path``, making its directory."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(dest: str, cells=None, tiny: bool = True) -> str:
    """A checkout-like root under ``dest`` with the repository's cells
    (or only ``cells``), their files cut to tiny sizes."""
    bench = load(os.path.join(REPO, "BENCHMARK.json"))
    if cells is not None:
        bench["workloads"] = [w for w in bench["workloads"]
                              if w["name"] in cells]
    src = os.path.join(REPO, "bench")
    for sub in ("drivers", "metrics"):
        shutil.copytree(os.path.join(src, sub), os.path.join(dest, "bench",
                                                              sub))
    shutil.copy(os.path.join(src, "peaks.json"),
                os.path.join(dest, "bench", "peaks.json"))
    for c in bench["configs"]:
        cfg = load(os.path.join(REPO, c["file"]))
        if tiny:
            cfg.update(TINY_CONFIGS.get(c["name"], {}))
        dump(cfg, os.path.join(dest, c["file"]))
    for w in bench["workloads"]:
        tr = load(os.path.join(src, "traffic", f"{w['name']}.json"))
        if tiny:
            tr.update(TINY_TRAFFIC.get(w["name"], {}))
        dump(tr, os.path.join(dest, "bench", "traffic", f"{w['name']}.json"))
    dump(bench, os.path.join(dest, "BENCHMARK.json"))
    return dest


def add_cell(root: str, name: str, like: str, **traffic) -> dict:
    """Add cell ``name`` to ``root`` by files alone: a copy of cell
    ``like``'s traffic file with ``traffic`` changed, and its entry."""
    bench = load(os.path.join(root, "BENCHMARK.json"))
    entry = copy.deepcopy(next(w for w in bench["workloads"]
                               if w["name"] == like))
    entry["name"] = name
    bench["workloads"].append(entry)
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    tr = load(os.path.join(root, "bench", "traffic", f"{like}.json"))
    tr.update(traffic)
    dump(tr, os.path.join(root, "bench", "traffic", f"{name}.json"))
    dump(bench, os.path.join(root, "BENCHMARK.json"))
    return entry
