"""``adi_y_ms`` (ms/step): device time a step of the Pallas kernels that
the library issues inside its stage ``custen.adi.y`` (``repro.obs``):
every y sweep of ``ADIOperator``/``ADIOperator3D``; in CH the column
penta of ``kernels/penta.py``.  XLA's fusions are not counted (the
harness gives readers the ``op_name`` of Pallas kernels alone): in CH
the y sweep's Woodbury correction fuses into the update, whose root
carries ``custen.ch.update``.  Silent for a library without the stages."""

from yardstick import stages


def read(ctx):
    return stages.kernel_ms(ctx, "adi.y")
