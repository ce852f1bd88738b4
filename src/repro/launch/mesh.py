"""Production device meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count`` before first jax init; smoke
tests and benchmarks must keep seeing the single real device.
"""

from __future__ import annotations


import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_for(
    n_devices: int | None = None,
    *,
    model_parallel: int = 1,
    pods: int = 1,
) -> Mesh:
    """Best-effort (pod, data, model) mesh over however many devices exist —
    the elastic-rescale path (checkpoint restores reshard to this)."""
    n = n_devices or len(jax.devices())
    if n % (model_parallel * pods):
        raise ValueError(f"{n} devices not divisible by tp*pods")
    data = n // (model_parallel * pods)
    if pods > 1:
        return _make_mesh(
            (pods, data, model_parallel), ("pod", "data", "model")
        )
    return _make_mesh((data, model_parallel), ("data", "model"))


def dp_axes_of(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
