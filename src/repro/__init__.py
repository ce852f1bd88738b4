"""repro — a TPU-native finite-difference / stencil framework.

JAX + Pallas reproduction (and extension) of:

    cuSten — CUDA Finite Difference and Stencil Library
    Gloster & Ó Náraigh, 2019.

**The four-function facade** (:mod:`repro.api`) is the public surface —
cuSten's Create / Compute / Swap / Destroy, one entry point per verb
across every plan family (2D, batched-1D, 3D stencils; 2D/3D ADI):

>>> import repro
>>> plan = repro.create("laplacian", (256, 256), bc="periodic")  # Create
>>> out = repro.compute(plan, field)                             # Compute
>>> field, out = repro.swap((out, field))                        # Swap
>>> repro.destroy(plan)                                          # Destroy

:func:`repro.create` infers the plan family from the rank/geometry of
``shape`` (``mode='batch'`` for (B, M) stacks, ``mode='adi'`` for the
implicit operators); plans are JAX pytrees (weights as leaves, geometry
as static aux) so they pass through ``jit``/``vmap``/donation as
arguments.  Named operators come from the user-extensible registry
(:func:`repro.register_operator` / :func:`repro.get_operator`).  The
pre-facade per-dimension functions (``stencil_create_2d`` & co,
``make_adi_operator*``) remain importable as deprecation shims for one
release.

The package is organised as a production framework:

- :mod:`repro.api`        — the four-function facade + operator registry.
- :mod:`repro.core`       — the paper's contribution: plan-based stencil
  engine, ADI time stepping, Cahn–Hilliard / WENO applications, distributed
  domain decomposition with halo exchange.
- :mod:`repro.kernels`    — Pallas TPU kernels (BlockSpec VMEM tiling) with
  jnp oracles, for the compute hot spots the paper optimises.
- :mod:`repro.models`     — LM substrate for the assigned architecture pool.
- :mod:`repro.configs`    — architecture / problem configurations.
- :mod:`repro.optim`, :mod:`repro.data`, :mod:`repro.checkpoint`,
  :mod:`repro.runtime`    — training substrate (optimizers, pipelines,
  fault-tolerant checkpointing, sharding rules).
- :mod:`repro.launch`     — meshes, dry-run driver, train/serve entry points.
- :mod:`repro.obs`        — stage scopes, host spans and compile counters
  (``custen.*`` names in a profile; :func:`repro.obs.counters`).
"""

__version__ = "2.0.0"  # tracks cuSten's published version

from repro import obs  # registers the compile counters' listeners
from repro.api import (
    OperatorDef,
    compute,
    create,
    destroy,
    get_operator,
    operator_names,
    plan_key,
    register_operator,
    swap,
)
from repro.core.adi import (
    ADIOperator,
    ADIOperator3D,
    make_adi_operator,
    make_adi_operator_3d,
)
from repro.kernels.spectral import SpectralBackendError
from repro.core.stencil import (
    DoubleBuffer,
    PlanCore,
    Stencil2D,
    Stencil3D,
    StencilBatch1D,
    central_difference_weights,
    laplacian3d_weights,
    plan_destroy,
    stencil_create_2d,
    stencil_compute_2d,
    stencil_destroy_2d,
    stencil_create_1d_batch,
    stencil_compute_1d_batch,
    stencil_destroy_1d_batch,
    stencil_create_3d,
    stencil_compute_3d,
    stencil_destroy_3d,
)

# The public surface, snapshot-checked by tests/test_api_surface.py —
# additions and removals are deliberate API events, not side effects.
__all__ = [
    # the four-function facade + operator registry (repro.api)
    "create",
    "compute",
    "swap",
    "destroy",
    "register_operator",
    "get_operator",
    "operator_names",
    "plan_key",
    "OperatorDef",
    # plan classes (pytree-native)
    "PlanCore",
    "Stencil2D",
    "StencilBatch1D",
    "Stencil3D",
    "ADIOperator",
    "ADIOperator3D",
    "DoubleBuffer",
    # the spectral (fft) execution backend's named Create-time refusal
    "SpectralBackendError",
    # engine-level destroy + weight helpers
    "plan_destroy",
    "central_difference_weights",
    "laplacian3d_weights",
    # deprecated pre-facade entry points (one release, warn on call)
    "stencil_create_2d",
    "stencil_compute_2d",
    "stencil_destroy_2d",
    "stencil_create_1d_batch",
    "stencil_compute_1d_batch",
    "stencil_destroy_1d_batch",
    "stencil_create_3d",
    "stencil_compute_3d",
    "stencil_destroy_3d",
    "make_adi_operator",
    "make_adi_operator_3d",
]
