"""Traffic ``evolve3d``: the 3D CH solver's donated multi-step evolve.

Set-up builds ``CahnHilliardADI`` from the configuration with ``nz`` set
(``rhs_mode='stencil'``, ``backend='auto'``, ``tune='off'``), makes the
deep-quench field from the seed on the device, runs the 3D bootstrap
step, compiles ``make_evolve(steps_per_call)`` (the donated ``lax.scan``
that ``ch_evolve`` drives) and runs it once.  Each window call runs that
same compiled program on the carry it returned.  A step is the RHS from
two ``Stencil3D`` plans, then the x, y and z penta sweeps, then the
update.

The answer is the field after every step since the seed; ``check``
compares it with the plain 3D scheme following the same steps.
``FAULTS`` plant faults in the nonlinear term of the RHS, for the
readings that the limit is set from and for the tests.
"""

from __future__ import annotations

import contextlib

import jax
import numpy as np

from references import cahn_hilliard3d as reference
from yardstick import fields, harness, work3d


def scheme(config: dict) -> dict:
    """The solver's parameters: ``dt = dt_factor * h^4 / (D gamma)``."""
    h = config["lx"] / config["grid"][2]
    return dict(lx=config["lx"], D=config["D"], gamma=config["gamma"],
                dt=config["dt_factor"] * h**4 / (config["D"] * config["gamma"]))


class Evolve3D:
    def __init__(self, config, traffic, seed):
        from repro.core.cahn_hilliard import CahnHilliardADI, CHConfig

        nz, ny, nx = shape = tuple(config["grid"])
        p = scheme(config)
        clock = harness.Stopwatch()
        cfg = CHConfig(nx=nx, ny=ny, nz=nz, lx=p["lx"], ly=p["lx"] * ny / nx,
                       dt=p["dt"], D=p["D"], gamma=p["gamma"],
                       dtype=config["dtype"], rhs_mode=config["rhs_mode"],
                       backend="auto", tune="off")
        self.solver = CahnHilliardADI(cfg)
        clock.mark("create")
        c0 = fields.uniform(seed, shape, config["ic_amp"], config["dtype"])
        jax.block_until_ready(c0)
        clock.mark("initial_field")
        c1 = jax.block_until_ready(self.solver.initial_step(c0))
        clock.mark("bootstrap_step")
        self.steps_per_call = int(traffic["steps_per_call"])
        evolve = self.solver.make_evolve(self.steps_per_call)
        self.program = evolve.lower(c1, c0).compile()
        clock.mark("compile")
        self.carry = jax.block_until_ready(self.program(c1, c0))
        clock.mark("first_call")
        self.steps_done = self.steps_per_call
        self.setup_parts = clock.parts
        itemsize = np.dtype(config["dtype"]).itemsize
        self.work = {"grid": shape, "itemsize": itemsize,
                     "step": work3d.ch3d_step(shape, itemsize)}

    def call(self):
        self.carry = self.program(*self.carry)
        self.steps_done += self.steps_per_call
        return self.carry

    def program_text(self) -> str:
        return self.program.as_text()

    def finish(self) -> np.ndarray:
        answer = np.asarray(self.carry[0])
        self.carry = self.program = self.solver = None
        return answer


build = Evolve3D


def _answer_of_reference(config, seed, steps, dtype):
    c0 = fields.uniform(seed, tuple(config["grid"]), config["ic_amp"],
                        config["dtype"])
    out = reference.evolve(c0, n_steps=steps, dtype=dtype, **scheme(config))
    return np.asarray(out.astype("float32"))


def check(config, traffic, seed, steps, answer) -> dict:
    """``rel_err``: the largest gap between the answer and the plain 3D
    scheme (float32, exact solves) after the same steps, over the
    reference's largest value."""
    ref = _answer_of_reference(config, seed, steps, "float32")
    return {"rel_err": float(np.max(np.abs(answer.astype(np.float64) - ref))
                             / np.max(np.abs(ref)))}


def control(config, traffic, seed, steps) -> np.ndarray:
    """The plain scheme in bfloat16, put in the program's place."""
    return _answer_of_reference(config, seed, steps, "bfloat16")


@contextlib.contextmanager
def _nonlinear_term(term):
    """The RHS's function-pointer plan built, while the block runs, with
    ``term(w)`` in place of ``w^3 - w``: a fault planted where the
    nonlinear term is produced, for ``calibrate.py --faults`` and the
    tests.  The solver must be created inside the block."""
    from repro.core import cahn_hilliard

    def point_fn(windows, coeffs):
        return sum(c * term(w) for w, c in zip(windows, coeffs, strict=True))

    kept = cahn_hilliard.cube_laplacian_point_fn
    cahn_hilliard.cube_laplacian_point_fn = point_fn
    try:
        yield
    finally:
        cahn_hilliard.cube_laplacian_point_fn = kept


FAULTS = {
    "cubic_dropped": lambda: _nonlinear_term(lambda w: -w),
    "nonlinear_dropped": lambda: _nonlinear_term(lambda w: 0.0 * w),
}
