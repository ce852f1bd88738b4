"""Traffic ``evolve``: the CH solver's donated multi-step evolve.

Set-up builds ``CahnHilliardADI`` from the configuration (``backend='auto'``,
``tune='off'``), makes the deep-quench field from the seed on the device,
runs the bootstrap step, compiles ``make_evolve(steps_per_call)`` (the
donated ``lax.scan`` that ``ch_evolve`` drives) and runs it once.  Each
window call runs that same compiled program on the carry it returned,
as ``ch_evolve`` does between chunks.

The answer is the field after every step since the seed; ``check``
compares it with the plain scheme following the same steps.  ``FAULTS``
plants faults in the fused kernel's nonlinear term, for the readings
that the limit is set from and for the tests.
"""

from __future__ import annotations

import contextlib
import inspect
import textwrap

import jax
import numpy as np

from references import cahn_hilliard as reference
from yardstick import fields, harness, work


def scheme(config: dict) -> dict:
    """The solver's parameters: ``dt = dt_factor * h^4 / (D gamma)``."""
    h = config["lx"] / config["grid"][1]
    return dict(lx=config["lx"], D=config["D"], gamma=config["gamma"],
                dt=config["dt_factor"] * h**4 / (config["D"] * config["gamma"]))


class Evolve:
    def __init__(self, config, traffic, seed):
        from repro.core.cahn_hilliard import CahnHilliardADI, CHConfig

        ny, nx = config["grid"]
        p = scheme(config)
        clock = harness.Stopwatch()
        cfg = CHConfig(nx=nx, ny=ny, lx=p["lx"], ly=p["lx"] * ny / nx, dt=p["dt"],
                       D=p["D"], gamma=p["gamma"], dtype=config["dtype"],
                       rhs_mode=config["rhs_mode"], backend="auto", tune="off")
        self.solver = CahnHilliardADI(cfg)
        clock.mark("create")
        c0 = fields.uniform(seed, (ny, nx), config["ic_amp"], config["dtype"])
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
        self.work = {"grid": (ny, nx), "itemsize": itemsize,
                     "step": work.ch_step((ny, nx), itemsize)}

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


build = Evolve


def _answer_of_reference(config, seed, steps, dtype):
    ny, nx = config["grid"]
    c0 = fields.uniform(seed, (ny, nx), config["ic_amp"], config["dtype"])
    out = reference.evolve(c0, n_steps=steps, dtype=dtype, **scheme(config))
    return np.asarray(out.astype("float32"))


def check(config, traffic, seed, steps, answer) -> dict:
    """``rel_err``: the largest gap between the answer and the plain
    scheme (float32, exact solves) after the same steps, over the
    reference's largest value.  Its limit sits below what a fused kernel
    without the nonlinear term reads (``FAULTS``), which at the cell's
    small time step moves the field by only ~5e-4 of its size."""
    ref = _answer_of_reference(config, seed, steps, "float32")
    return {"rel_err": float(np.max(np.abs(answer.astype(np.float64) - ref))
                             / np.max(np.abs(ref)))}


def control(config, traffic, seed, steps) -> np.ndarray:
    """The plain scheme in bfloat16, put in the program's place."""
    return _answer_of_reference(config, seed, steps, "bfloat16")


@contextlib.contextmanager
def _kernel_line(old: str, new: str):
    """The fused RHS + x-sweep kernel rebuilt from its own source with one
    line changed, while the block runs: a fault planted where the
    nonlinear term is produced, for ``calibrate.py --faults`` and the
    tests.  Its jitted wrapper's cache is cleared on the way in and out."""
    from repro.kernels import fused_ch

    src = textwrap.dedent(inspect.getsource(fused_ch._ch_xsweep_kernel))
    if src.count(old) != 1:
        raise LookupError(f"the fused kernel no longer holds {old!r}")
    scope = dict(vars(fused_ch))
    exec(compile(src.replace(old, new), fused_ch.__file__, "exec"), scope)
    kept = fused_ch._ch_xsweep_kernel
    fused_ch.ch_rhs_xsweep_pallas.clear_cache()
    fused_ch._ch_xsweep_kernel = scope["_ch_xsweep_kernel"]
    try:
        yield
    finally:
        fused_ch._ch_xsweep_kernel = kept
        fused_ch.ch_rhs_xsweep_pallas.clear_cache()


FAULTS = {
    "cubic_dropped": lambda: _kernel_line("nl = cn * cn * cn - cn", "nl = -cn"),
    "nonlinear_dropped": lambda: _kernel_line("(lin + hyper + nonlin)", "(lin + hyper)"),
}
