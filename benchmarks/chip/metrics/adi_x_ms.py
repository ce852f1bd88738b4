"""``adi_x_ms`` (ms/step): device time a step of the Pallas kernels that
the library issues inside its stage ``custen.adi.x`` (``repro.obs``):
every x sweep of ``ADIOperator``/``ADIOperator3D``, and in CH the fused
RHS + x-sweep.  Keyed on the stage, not on a kernel's wrapper name, so
a rewrite of the x recurrence keeps it.  XLA's fusions are not counted
(the harness gives readers the ``op_name`` of Pallas kernels alone); in
CH none carries this stage.  Silent for a library without the stages."""

from yardstick import stages


def read(ctx):
    return stages.kernel_ms(ctx, "adi.x")
