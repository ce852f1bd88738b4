"""Quickstart — the paper's §IV.A/IV.B examples on the four-function facade.

8th-order central difference of sin(x) on an ny x nx grid, first with
standard weights then with a "function pointer", exactly like cuSten's
``2d_x_np.cu`` / ``2d_x_np_fun.cu`` — followed by the batched-1D family
(``1DBatch``) and a registry-operator Laplacian.  Everything goes through
the four functions: ``repro.create`` / ``repro.compute`` / ``repro.swap``
/ ``repro.destroy``.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --nx 512 --ny 256
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.util import init_compile_cache

jax.config.update("jax_enable_x64", True)


def main():
    init_compile_cache()
    ap = argparse.ArgumentParser(
        description="cuSten quickstart on the repro four-function facade"
    )
    ap.add_argument("--nx", type=int, default=1024, help="grid points in x")
    ap.add_argument("--ny", type=int, default=512, help="grid rows")
    ap.add_argument("--batch", type=int, default=64,
                    help="independent 1D lines in the 1DBatch demo")
    args = ap.parse_args()

    # -- the paper's setup: nx=1024, ny=512, lx=2*pi -----------------------
    nx, ny, lx = args.nx, args.ny, 2 * np.pi
    dx = lx / nx
    x = np.linspace(0, lx, nx, endpoint=False)
    data_old = jnp.asarray(np.tile(np.sin(x), (ny, 1)))  # input: sin(x)
    answer = -np.sin(x)  # d2/dx2 sin = -sin

    # -- Create: 9-point (numSten=9, 4 left / 4 right) 8th-order weights ---
    weights = repro.central_difference_weights(8, 2, h=dx)
    plan = repro.create(weights, (ny, nx), bc="np", mode="x")

    # -- Compute / Swap ----------------------------------------------------
    data_new = repro.compute(plan, data_old)
    err = float(jnp.abs(data_new[:, 4:-4] - answer[4:-4]).max())
    print(f"[weights ] interior max|err| = {err:.3e}")
    print(f"[weights ] boundary cells (untouched): {np.asarray(data_new[0, :4])}")
    # the timestepping idiom: the fresh field becomes the next input
    data_old, data_new = repro.swap((data_new, data_old))
    repro.destroy(plan)  # Destroy (idempotent; compute now refuses it)
    data_old, data_new = repro.swap((data_new, data_old))  # flip back

    # -- Function-pointer variant (paper §IV.B): 2nd-order via coefficients -
    def central_difference(windows, coe):
        return coe[0] * (windows[0] - 2.0 * windows[1] + windows[2])

    fun_plan = repro.create(
        central_difference, (ny, nx), bc="np", mode="x",
        coeffs=jnp.asarray([1.0 / dx**2]), extents=dict(left=1, right=1),
    )
    data_new2 = repro.compute(fun_plan, data_old)
    err2 = float(jnp.abs(data_new2[:, 1:-1] - answer[1:-1]).max())
    print(f"[fun mode] interior max|err| = {err2:.3e} (2nd order)")
    repro.destroy(fun_plan)

    # -- periodic boundary: no untouched cells ------------------------------
    periodic = repro.create(weights, (ny, nx), bc="periodic", mode="x")
    err3 = float(jnp.abs(repro.compute(periodic, data_old) - answer).max())
    print(f"[periodic] global max|err|  = {err3:.3e}")
    repro.destroy(periodic)

    # -- batched 1D (cuSten's 1DBatch family): mode='batch' ----------------
    # A (B, M) stack of *independent* 1D problems — B phase-shifted copies
    # of sin — differentiated by ONE plan in ONE Compute call.
    B, M = args.batch, nx
    phases = np.linspace(0, np.pi, B, endpoint=False)[:, None]
    stack = jnp.asarray(np.sin(x[None, :] + phases))  # (B, M)
    batch_plan = repro.create(weights, (B, M), mode="batch")
    d2_stack = repro.compute(batch_plan, stack)
    err4 = float(jnp.abs(d2_stack + stack).max())  # d2/dx2 sin = -sin
    print(f"[batch1d ] {B} lines at once, global max|err| = {err4:.3e}")
    repro.destroy(batch_plan)

    # -- registry operator: a named Laplacian, no weight table in sight -----
    lap = repro.create("laplacian", (ny, nx), bc="periodic", h=dx)
    lap_sin = repro.compute(lap, data_old)  # lap sin(x) = -sin(x)
    err5 = float(jnp.abs(lap_sin - jnp.asarray(answer)[None, :]).max())
    print(f"[registry] laplacian max|err| = {err5:.3e} (2nd order), "
          f"operators: {', '.join(repro.operator_names())}")
    repro.destroy(lap)


if __name__ == "__main__":
    main()
