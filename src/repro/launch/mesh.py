"""Production meshes.

Single pod: (16, 16) = 256 chips, axes (data, model) — a TPU v5e pod.
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); the pod
axis is pure data parallelism over the inter-pod (DCN/optical) links —
in the paper's terms, independent orbital planes training replicas whose
gradients all-reduce over inter-plane ISLs.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: sharding follows the explicit
    ``with_sharding_constraint`` / ``device_put`` placements the code
    makes (``models/param.constrain``, the fleet's ``plane_sharding``),
    not sharding-in-types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever this host actually has (tests / examples): (n//m, m)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    return _make_mesh((n // model, model), ("data", "model"))


def make_fleet_mesh(planes: int):
    """A 1-D ``("plane",)`` mesh for the fleet engine's plane axis.

    The axis size is the largest divisor of ``planes`` this host's
    device count supports, so a ``(P, N)``-laid-out fleet always shards
    evenly: 4 planes on 2 CPU host devices -> 2-way plane sharding, any
    plane count on 1 device -> a trivial (replicated) mesh.  In the
    paper's terms each mesh slot carries one or more orbital planes;
    inter-plane checkpoint averaging all-reduces over this axis (the
    inter-plane ISL exchange).
    """
    n = len(jax.devices())
    planes = max(1, int(planes))
    size = max(d for d in range(1, min(planes, n) + 1) if planes % d == 0)
    return _make_mesh((size,), ("plane",))


def plane_sharding(mesh, axis: str = "plane"):
    """``NamedSharding`` splitting leading-axis-(P,) arrays over ``axis``.

    Works with :func:`make_fleet_mesh` (axis ``"plane"``) or any other
    mesh that carries a suitable axis (e.g. :func:`make_host_mesh`'s
    ``"data"`` axis for CPU-device tests); trailing dims replicate.
    """
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axis))


# TPU v5e roofline constants (per chip) — §Roofline hardware targets.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link (~45 GB/s usable)
