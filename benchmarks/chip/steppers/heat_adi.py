"""Traffic ``adi``: LOD backward-Euler steps of the periodic heat equation.

One step is ``c <- S_z S_y S_x c`` with ``S_a = (I - r delta_a^2)^{-1}``:
one ``repro.compute`` on a periodic 3D ``diffusion`` ADI plan
(``ADIOperator3D``), whose x, y and z sweeps run the row, plane and column
penta layouts.  A call is a jitted ``lax.scan`` of ``steps_per_call``
such steps with the field donated, the way a user advances an implicit
scheme between outputs.  Set-up creates the plan (``backend='auto'``,
``tune='off'``), makes the field from the seed on the device, compiles
the call and runs it once.

The answer is the field after every step since the seed; ``check``
compares it with the exact float64 solution of the same steps.
``FAULTS`` plant a fault in the sweeps' coefficient, for the readings
that the limit is set from.
"""

from __future__ import annotations

import contextlib

import jax
import numpy as np

from references import heat_lod as reference
from yardstick import fields, harness, work3d


class Adi:
    def __init__(self, config, traffic, seed):
        import repro

        shape = tuple(config["grid"])
        clock = harness.Stopwatch()
        plan = repro.create("diffusion", shape, mode="adi", alpha=traffic["r"],
                            dtype=config["dtype"], backend="auto", tune="off")
        clock.mark("create")
        self.steps_per_call = int(traffic["steps_per_call"])

        def advance(c):
            step = lambda c, _: (repro.compute(plan, c), None)  # noqa: E731
            return jax.lax.scan(step, c, None, length=self.steps_per_call)[0]

        c0 = fields.uniform(seed, shape, config["ic_amp"], config["dtype"])
        jax.block_until_ready(c0)
        clock.mark("initial_field")
        self.program = jax.jit(advance, donate_argnums=0).lower(c0).compile()
        clock.mark("compile")
        self.field = jax.block_until_ready(self.program(c0))
        clock.mark("first_call")
        self.steps_done = self.steps_per_call
        self.setup_parts = clock.parts
        itemsize = np.dtype(config["dtype"]).itemsize
        self.work = {"grid": shape, "itemsize": itemsize,
                     "step": work3d.heat_lod_step(shape, itemsize)}

    def call(self):
        self.field = self.program(self.field)
        self.steps_done += self.steps_per_call
        return self.field

    def program_text(self) -> str:
        return self.program.as_text()

    def finish(self) -> np.ndarray:
        answer = np.asarray(self.field)
        self.field = self.program = None
        return answer


build = Adi


def _initial(config, seed):
    return fields.uniform(seed, tuple(config["grid"]), config["ic_amp"],
                          config["dtype"])


def check(config, traffic, seed, steps, answer) -> dict:
    """``rel_err``: the largest gap between the answer and the exact
    float64 solution after the same steps, over the solution's largest
    value."""
    c0 = np.asarray(_initial(config, seed), np.float64)
    ref = reference.evolve_exact(c0, traffic["r"], steps)
    return {"rel_err": float(np.max(np.abs(answer - ref)) / np.max(np.abs(ref)))}


def control(config, traffic, seed, steps) -> np.ndarray:
    """The plain iteration in bfloat16, put in the program's place."""
    out = reference.evolve_iterated(_initial(config, seed), traffic["r"],
                                    steps, "bfloat16")
    return np.asarray(out.astype("float32"))


@contextlib.contextmanager
def _alpha_scaled(scale):
    """The plan built, while the block runs, with ``alpha = scale * r``: a
    fault planted in every sweep's coefficient, for ``calibrate.py
    --faults``.  The plan must be created inside the block."""
    import repro

    create = repro.create

    def scaled(*args, alpha, **kwargs):
        return create(*args, alpha=scale * alpha, **kwargs)

    repro.create = scaled
    try:
        yield
    finally:
        repro.create = create


FAULTS = {"alpha_off_1pct": lambda: _alpha_scaled(1.01)}
