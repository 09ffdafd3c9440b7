"""Production meshes.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the first
jax device query, and smoke tests must keep seeing one device.

Topology (TPU v5e):
  * single pod: (16, 16)  axes ("data", "model")          = 256 chips
  * multi-pod:  (2, 16, 16) axes ("pod", "data", "model") = 512 chips

"model" maps to the intra-pod ICI dimension with the densest wiring (TP and
EP collectives are latency-bound); "data"/"pod" carry the FSDP/DP collectives
(bandwidth-bound all-gather / reduce-scatter, DCN-tolerant across pods).

Every mesh of the repo is built by :func:`make_mesh`, whose axes are Auto:
the sharding layer steers GSPMD with ``with_sharding_constraint``
(parallel/sharding.py), which only Auto axes accept.
"""
from __future__ import annotations

import jax

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_test_mesh",
    "mesh_name",
    "mesh_chips",
    "gemm_partition",
]


def make_mesh(shape, axes, *, devices=None):
    """The one mesh constructor: ``shape`` over ``axes``, every axis Auto
    (``jax.make_mesh`` defaults to Explicit axes, which the sharding
    constraints of parallel/sharding.py refuse).  ``devices`` defaults to
    ``jax.devices()``; pass a topology's devices to compile for a chip that
    is described rather than attached."""
    from jax.sharding import AxisType

    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Shrunken topology for CI-scale dry-run tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_name(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def mesh_chips(mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        n *= mesh.shape[a]
    return n


def gemm_partition(mesh):
    """The canonical GEMM sharding on this mesh: M over the data-ish axes
    ("pod", "data"), N over "model", K unsharded.

    This is the default partition ``Engine.plan_gemm``/``plan_conv`` use to
    derive local per-shard shapes when given a mesh without an explicit
    PartitionSpec (DESIGN.md §6).
    """
    from jax.sharding import PartitionSpec as P

    data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model = "model" if "model" in mesh.axis_names else None
    if len(data) == 1:
        data = data[0]
    return P(data or None, model)
