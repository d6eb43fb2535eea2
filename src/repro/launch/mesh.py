"""Production meshes (DESIGN.md §4).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run must set XLA_FLAGS before the first jax call.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # Auto axes: the models place arrays with with_sharding_constraint,
    # which jax.make_mesh's default Explicit axes turn into an assertion
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_agents: int = 4, model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if multi_pod:
        return _auto_mesh((2, n_agents, model), ("pod", "data", "model"))
    return _auto_mesh((n_agents, model), ("data", "model"))


def n_agents_of(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes.get("data", 1)
