"""Traffic ``explicit``: forward-Euler steps of the periodic heat equation.

One step is ``c <- c + r delta^2 c``: one ``repro.compute`` on a periodic
``Stencil3D`` plan whose 3x3x3 weights hold ``I + r delta^2`` (seven
non-zero taps).  A call is a jitted ``lax.scan`` of ``steps_per_call``
such steps with the field donated, the way a user advances an explicit
scheme between outputs.  Set-up creates the plan (``backend='auto'``,
``tune='off'``), makes the field from the seed on the device, compiles
the call and runs it once.

The answer is the field after every step since the seed; ``check``
compares it with the exact float64 solution of the same steps.
"""

from __future__ import annotations

import jax
import numpy as np

from references import heat as reference
from yardstick import fields, harness, work

TAPS = 7


def weights(r: float) -> np.ndarray:
    w = np.zeros((3, 3, 3))
    w[1, 1, 1] = 1 - 6 * r
    for axis in range(3):
        for side in (0, 2):
            idx = [1, 1, 1]
            idx[axis] = side
            w[tuple(idx)] = r
    return w


class Explicit:
    def __init__(self, config, traffic, seed):
        import repro

        shape = tuple(config["grid"])
        clock = harness.Stopwatch()
        plan = repro.create(weights(traffic["r"]), shape, bc="periodic",
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
        self.work = {"grid": shape, "itemsize": itemsize, "taps": TAPS,
                     "step": work.stencil(shape, TAPS, itemsize)}

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


build = Explicit


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
