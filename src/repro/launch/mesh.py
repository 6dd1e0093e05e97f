"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required for the dry-run's forced host device
count to take effect first.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh for tests/benchmarks (host devices or real)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_serve_mesh(pods: int = 1, pod_axis: str = "pod", devices=None):
    """The serving fabric's mesh: a flat DP ring at ``pods=1``, a
    two-level ``(pod_axis, "data")`` topology otherwise — the shape
    ``ServeConfig.pods`` / ``--pods`` flows into
    ``serving/dispatch.make_serve_step`` (pod-aware leader emission) and
    ``serving/event_loop.channel_affinity`` (topology-aware loop
    ownership). ``devices`` defaults to every visible device; ``pods``
    must divide the count (the pod is a physical partition, not a
    round-robin)."""
    n = len(devices if devices is not None else jax.devices())
    if pods < 1:
        raise ValueError(f"pods must be >= 1, got {pods}")
    if n % pods != 0:
        raise ValueError(
            f"pods={pods} does not divide the device count {n}; a pod is "
            "a physical partition of the fabric — pick a pod count that "
            f"divides {n} (divisors: "
            f"{[d for d in range(1, n + 1) if n % d == 0]})")
    if pods == 1:
        return make_mesh((n,), ("data",))
    return make_mesh((pods, n // pods), (pod_axis, "data"))


def make_abstract_mesh(shape: tuple, axes: tuple):
    """Device-free mesh for sharding-rule tests."""
    return AbstractMesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The DP axes of a mesh (everything that is not 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
