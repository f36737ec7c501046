"""Production meshes.

``make_production_mesh`` is a FUNCTION (spec requirement): importing this
module never touches jax device state, so smoke tests and benchmarks see
one CPU device while the dry-run (which sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import) sees the full placeholder fleet.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the pod
axis extends data parallelism across the ICI/DCN boundary.

The *control plane* uses a different, 1-D mesh: ``make_lane_mesh`` lays
the batched ALERT engine's stream ("lane") axis over devices so fleet
scoring scales with the hardware it manages (DESIGN.md §6).  The decision
grid has no cross-lane reduction anywhere, so lane sharding needs no
collectives — each device scores its lane shard independently.
"""

from __future__ import annotations

import jax

LANE_AXIS = "lanes"


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in ``Auto`` mode.  The sharding
    rules here annotate inputs and outputs and let the compiler
    propagate the rest; ``jax.make_mesh``'s default (``Explicit`` axes)
    would instead demand an output sharding on every gather and
    contraction that touches a sharded axis."""
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_lane_mesh(n_devices: int | None = None):
    """1-D control-plane mesh: the fleet's ``[S]`` lane axis over devices.

    ``n_devices`` defaults to every visible device (CI sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in a subprocess
    to fake a multi-device host — the flag must be exported before jax is
    imported).  Pass the mesh to ``BatchedAlertEngine(mesh=...)``, the
    filter banks, ``FleetSim.run_*(mesh=...)``, or
    ``FleetAlertServer(mesh=...)``; the single axis is named
    :data:`LANE_AXIS`.
    """
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return _make_mesh((n,), (LANE_AXIS,))


def lane_shardings(mesh):
    """(lane-sharded, replicated) :class:`~jax.sharding.NamedSharding`
    pair for a 1-D lane mesh: ``[S]``-shaped state shards its leading
    axis over the mesh's single axis (:data:`LANE_AXIS` for meshes built
    by :func:`make_lane_mesh`); profile constants replicate.  The single
    source for lane-sharding construction — the engine, the filter
    banks, and the sharded benchmark all build their shardings here."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if len(mesh.axis_names) != 1:
        raise ValueError("lane sharding needs a 1-D mesh "
                         f"(got axes {mesh.axis_names})")
    return (NamedSharding(mesh, P(mesh.axis_names[0])),
            NamedSharding(mesh, P()))


def lane_pspec(mesh):
    """``PartitionSpec`` over a 1-D lane mesh's single axis — the
    ``shard_map`` twin of :func:`lane_shardings`, used by the Pallas
    select backend to launch one `alert_select` kernel per device on its
    lane shard (the decision grid has no cross-lane op, so per-device
    kernels are exact — DESIGN.md §6)."""
    from jax.sharding import PartitionSpec

    if len(mesh.axis_names) != 1:
        raise ValueError("lane sharding needs a 1-D mesh "
                         f"(got axes {mesh.axis_names})")
    return PartitionSpec(mesh.axis_names[0])


def lane_shard_map(fn, mesh, *, n_in: int, n_out: int):
    """``shard_map`` a flat-signature traceable ``fn`` over a 1-D lane
    mesh: all ``n_in`` inputs and ``n_out`` outputs shard their leading
    (lane) axis per :func:`lane_pspec`.  The single seam behind every
    per-device lane launch — the Pallas select backend and the traffic
    megatick's in-scan select both wrap through here, so the
    no-collectives contract (the decision grid has no cross-lane op —
    DESIGN.md §6) is enforced in one place (``check_vma=False``: the
    kernels return unreplicated per-shard outputs)."""
    p = lane_pspec(mesh)
    return jax.shard_map(fn, mesh=mesh, in_specs=(p,) * n_in,
                         out_specs=(p,) * n_out, check_vma=False)


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes the global batch shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_host_mesh(model_parallel: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    mp = model_parallel
    while mp > 1 and n % mp:
        mp //= 2
    return _make_mesh((n // mp, mp), ("data", "model"))
