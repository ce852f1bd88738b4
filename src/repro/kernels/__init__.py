"""Pallas TPU kernels for the compute hot-spots cuSten optimises.

Each kernel module contains the ``pl.pallas_call`` + ``BlockSpec`` VMEM
tiling; :mod:`repro.kernels.ops` holds the jit'd public wrappers with
backend dispatch; :mod:`repro.kernels.ref` the pure-jnp oracles.

Kernels:

- ``stencil2d``  — generic weighted / function-pointer 2D stencil (X/Y/XY,
  periodic/np) with halo-neighbour BlockSpecs (the cuSten compute kernel).
- ``stencil1d_batch`` — batched-1D stencil over a (B, M) stack (cuSten's
  ``1DBatch`` family): batch tiled over the grid, M on the lanes, halos
  along M only.
- ``penta``      — batched pentadiagonal substitution (cuPentBatch) in both
  layouts (column: batch on lanes; row: recurrence along the rows, the
  x-sweep, whose lane chunks the kernel transposes in VMEM so every
  layout's recurrence walks sublanes), plus Create-time LU factorisation
  and rank-4 Woodbury cyclic closure evaluated as broadcast FMAs.
- ``weno``       — WENO5 upwind advection RHS (the 2d_xyADVWENO_p variant).
- ``fused_ch``   — beyond-paper: the whole Cahn–Hilliard explicit RHS fused
  into one VMEM pass, and the RHS + implicit x-sweep fused into a single
  ``pallas_call`` (``ch_rhs_xsweep_pallas``).
"""
