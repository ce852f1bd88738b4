"""``ch_rhs_ms`` (ms/step): device time a step of the Pallas kernels that
the library issues inside its stage ``custen.ch.rhs`` (``repro.obs``):
the plan-built CH right-hand side (``rhs_mode='stencil'``), in 3D the
5x5x5 biharmonic and the function-pointer Laplacian of ``C^3 - C``, two
``stencil3d_pallas`` calls a step.  XLA's fusions are not counted.
Silent where the RHS is fused into the x sweep (2D ``rhs_mode='fused'``,
stage ``adi.x``) and for a library without the stages."""

from yardstick import stages


def read(ctx):
    return stages.kernel_ms(ctx, "ch.rhs")
