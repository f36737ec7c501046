"""Sharded checkpointing with reshard-on-load (elasticity).

Format: one ``.npz`` per save (CPU container: single host) plus a JSON
manifest recording the flattened tree structure, shapes, dtypes, and the
training step.  On a real pod each host writes only the leaves-slices it
owns (the manifest records the global layout); restore reads the global
arrays and ``jax.device_put``s them with whatever shardings the *current*
mesh prescribes — so a checkpoint written on a 2x16x16 multi-pod mesh
restores onto 16x16 (elastic downscale) or vice versa without conversion.

Atomicity: writes go to ``<dir>.tmp``; the previous checkpoint (if any)
is renamed to ``<dir>.old`` before ``os.replace(tmp, dir)`` promotes the
new one, and ``.old`` is removed only after the promote.  A crash at ANY
point leaves either the old or the new checkpoint intact and findable —
:func:`load_manifest` / :func:`restore` / :func:`restore_tree` fall back
to ``<dir>.old`` when the primary directory is missing (the crash window
between the rename and the replace).
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Any

import jax
import numpy as np

from repro.core.precision import x64_scope


def _flatten(tree) -> tuple[list[tuple[str, Any]], Any]:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    items = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        items.append((key, leaf))
    return items, treedef


def save(directory: str, tree, step: int = 0, extra: dict | None = None
         ) -> str:
    """Atomically write ``tree`` (any pytree of arrays) under
    ``directory``.  Safe against a crash at any point: the previous
    checkpoint survives as ``directory`` or ``<directory>.old`` until
    the new one is fully promoted.  Returns ``directory``."""
    tmp = directory + ".tmp"
    old = directory + ".old"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    items, _ = _flatten(tree)
    arrays = {}
    manifest = {"step": step, "extra": extra or {}, "leaves": []}
    for i, (key, leaf) in enumerate(items):
        arr = np.asarray(leaf)
        name = f"leaf_{i}"
        arrays[name] = arr
        manifest["leaves"].append({
            "name": name, "path": key,
            "shape": list(arr.shape), "dtype": str(arr.dtype)})
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    # Torn-write safety: never rmtree the live checkpoint before the
    # replacement exists.  Park it at .old, promote tmp, then drop .old.
    if os.path.exists(old):
        shutil.rmtree(old)
    if os.path.exists(directory):
        os.replace(directory, old)
    os.replace(tmp, directory)
    if os.path.exists(old):
        shutil.rmtree(old)
    return directory


def _resolve(directory: str) -> str:
    """Pick the live checkpoint dir: ``directory`` if present, else
    ``<directory>.old`` (save crashed between park and promote)."""
    if os.path.exists(directory):
        return directory
    old = directory + ".old"
    if os.path.exists(old):
        return old
    return directory


def load_manifest(directory: str) -> dict:
    """Read the checkpoint manifest (step / extra / leaf layout),
    falling back to ``<directory>.old`` if a save was torn."""
    with open(os.path.join(_resolve(directory), "manifest.json")) as f:
        return json.load(f)


def restore(directory: str, like, shardings=None) -> tuple[Any, int]:
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs).  ``shardings`` (optional pytree of
    jax.sharding.Sharding, same structure) reshards onto the current mesh.

    Returns (tree, step).
    """
    directory = _resolve(directory)
    manifest = load_manifest(directory)
    data = np.load(os.path.join(directory, "arrays.npz"))
    items, treedef = _flatten(like)
    saved = {l["path"]: l for l in manifest["leaves"]}
    leaves = []
    for key, leaf in items:
        if key not in saved:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        rec = saved[key]
        arr = data[rec["name"]]
        want_shape = tuple(leaf.shape)
        if tuple(arr.shape) != want_shape:
            raise ValueError(f"leaf {key}: checkpoint shape {arr.shape} != "
                             f"model shape {want_shape}")
        leaves.append(arr.astype(leaf.dtype))
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    if shardings is not None:
        tree = jax.tree.map(lambda x, s: jax.device_put(x, s), tree,
                            shardings)
    else:
        # Keep each leaf's saved dtype: outside a 64-bit scope asarray
        # would narrow float64/int64 leaves to 32 bits.
        with x64_scope():
            tree = jax.tree.map(jax.numpy.asarray, tree)
    return tree, manifest["step"]


def restore_tree(directory: str) -> tuple[dict, int]:
    """Restore a checkpoint as a nested dict WITHOUT a ``like`` tree,
    rebuilt from the manifest's ``/``-joined paths.  Needed when leaf
    shapes aren't known up front (e.g. a gateway checkpoint whose queue
    length varies); shapes/dtypes come from the saved arrays verbatim.

    Returns (nested_dict, step).
    """
    directory = _resolve(directory)
    manifest = load_manifest(directory)
    data = np.load(os.path.join(directory, "arrays.npz"))
    tree: dict = {}
    for rec in manifest["leaves"]:
        parts = rec["path"].split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = data[rec["name"]]
    return tree, manifest["step"]


def latest_step(directory: str) -> int | None:
    """Step recorded in the checkpoint under ``directory`` (or its
    ``.old`` fallback); ``None`` when no checkpoint exists."""
    try:
        return load_manifest(directory)["step"]
    except (FileNotFoundError, KeyError):
        return None
