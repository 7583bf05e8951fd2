"""Production mesh construction.

Single pod : (16, 16)    axes ("data", "model")      = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Defined as a *function* so importing this module never touches JAX device
state (device count is locked at first backend init — the dry-run sets
XLA_FLAGS before any import; tests and benches see the real 1-CPU world).
"""
from __future__ import annotations

import jax


def mesh_context(mesh):
    """``jax.sharding.set_mesh(mesh)``: the mesh of everything traced inside."""
    return jax.sharding.set_mesh(mesh)


def current_mesh():
    """The abstract mesh set by :func:`mesh_context` (empty outside one)."""
    return jax.sharding.get_abstract_mesh()


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic resize)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def host_device_flag(n: int = 512) -> str:
    return f"--xla_force_host_platform_device_count={n}"
