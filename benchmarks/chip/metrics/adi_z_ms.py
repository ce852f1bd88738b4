"""``adi_z_ms`` (ms/step): device time a step of the Pallas kernels that
the library issues inside its stage ``custen.adi.z`` (``repro.obs``): the
z sweep of ``ADIOperator3D``, the column penta layout on the (nz, ny*nx)
reshape.  XLA's fusions are not counted (the harness gives readers the
``op_name`` of Pallas kernels alone).  Silent for a library without the
stages and in a cell without a z sweep."""

from yardstick import stages


def read(ctx):
    return stages.kernel_ms(ctx, "adi.z")
