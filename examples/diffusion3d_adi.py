"""3D diffusion via ADI splitting — the §VI.A extension end to end.

Solves  dC/dt = D grad^2 C  on a periodic box with a locally-one-dimensional
(LOD) backward-Euler splitting: each step applies the three factored
one-dimensional implicit operators in sequence,

    C <- L_z^{-1} L_y^{-1} L_x^{-1} C,     L_i = I - (D dt / h^2) delta_i^2,

all three sweeps transpose-free through :class:`repro.core.adi.ADIOperator3D`
(x: row layout on the (nz*ny, nx) reshape; y: the plane-layout middle-axis
substitution; z: column layout on the (nz, ny*nx) reshape).  The explicit
7-point Laplacian — used here as a diagnostic — runs through a
:class:`repro.core.stencil.Stencil3D` plan, streaming as z-slabs when
``--max-tile-kb`` bounds the working set.

On the separable mode C0 = sin(x) sin(y) sin(z) every sweep acts
diagonally, so the scheme's per-step decay factor is *exactly*

    g = prod_i 1 / (1 + 4 r sin^2(k h / 2)),     r = D dt / h^2,

which the driver checks against the observed field — machine-precision
validation of all three sweeps — and compares with the continuum
exp(-3 D k^2 t).

Both the implicit operator triple and the diagnostic stencil go through
the four-function facade: ``repro.create`` dispatches on the rank-3 shape
(``mode='adi'`` + the registry's ``"diffusion"`` bands for the sweeps, the
``"laplacian"`` weights for the stencil) and ``repro.compute`` is the
single apply path for both.

    PYTHONPATH=src python examples/diffusion3d_adi.py
    PYTHONPATH=src python examples/diffusion3d_adi.py --n 64 --steps 200
    PYTHONPATH=src python examples/diffusion3d_adi.py --max-tile-kb 64  # stream
"""

import argparse
import time

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402
from repro.util import init_compile_cache  # noqa: E402


def main():
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32, help="grid points per axis")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--dt", type=float, default=2e-3)
    ap.add_argument("--D", type=float, default=0.5)
    ap.add_argument(
        "--tune", choices=["off", "cached", "force"], default="off",
        help="Create-time autotuning of the three sweep configurations",
    )
    ap.add_argument(
        "--retune", action="store_true",
        help="force re-measurement even on a warm tune cache "
        "(sets REPRO_TUNE_FORCE)",
    )
    ap.add_argument(
        "--max-tile-kb", type=int, default=None,
        help="per-chunk byte budget: stream the stencil and sweeps as "
        "z-slab / plane chunks instead of monolithic calls",
    )
    args = ap.parse_args()
    if args.retune:
        from repro.tune import enable_force

        enable_force()

    n = args.n
    h = 2.0 * np.pi / n
    r = args.D * args.dt / h**2
    mtb = args.max_tile_kb * 1024 if args.max_tile_kb else None

    # Create: factor the three implicit operators once (+ optional tuning)
    op = repro.create(
        "diffusion", (n, n, n), mode="adi", alpha=r, cyclic=True,
        backend="jnp", max_tile_bytes=mtb,
        tune="cached" if args.retune else args.tune,
    )
    # Create: the explicit Laplacian plan (diagnostics), same streaming knobs
    lap = repro.create(
        "laplacian", (n, n, n), bc="periodic", h=h, backend="jnp",
        max_tile_bytes=mtb,
    )

    x = np.arange(n) * h
    Z, Y, X = np.meshgrid(x, x, x, indexing="ij")
    c = jnp.asarray(np.sin(X) * np.sin(Y) * np.sin(Z))
    amp0 = float(jnp.max(jnp.abs(c)))

    # Compute: one LOD step = the full implicit solve; the operator is a
    # pytree, so it passes through jit as a traced argument
    step = jax.jit(lambda o, c: repro.compute(o, c))

    # exact per-step decay of the k=1 mode under the discrete LOD scheme
    g = float(1.0 / (1.0 + 4.0 * r * np.sin(h / 2.0) ** 2) ** 3)

    print(f"# 3D LOD-ADI diffusion {n}^3, dt={args.dt}, D={args.D}, "
          f"r={r:.4f}, streamed={'yes' if mtb else 'no'}")
    print("# step, amp, amp/exact_discrete, lap_residual")
    t0 = time.time()
    for k in range(1, args.steps + 1):
        c = step(op, c)
        if k % max(args.steps // 8, 1) == 0 or k == 1:
            amp = float(jnp.max(jnp.abs(c)))
            exact = amp0 * g**k
            # diffusion residual: dC/dt - D lap C -> 0 as dt -> 0
            lap_c = repro.compute(lap, c)
            res = float(jnp.max(jnp.abs((1.0 - 1.0 / g) / args.dt * c
                                        - args.D * lap_c)))
            print(f"{k:6d} {amp:12.6e} {amp/exact:12.9f} {res:10.3e}")
    wall = time.time() - t0
    cont = amp0 * np.exp(-3.0 * args.D * args.steps * args.dt)
    amp = float(jnp.max(jnp.abs(c)))
    print(f"# final amp {amp:.6e}; discrete-exact {amp0 * g**args.steps:.6e} "
          f"(ratio {amp/(amp0*g**args.steps):.9f}); continuum {cont:.6e}")
    print(f"# wall: {wall:.2f}s ({wall/args.steps*1e3:.2f} ms/step)")
    repro.destroy(op)
    repro.destroy(lap)


if __name__ == "__main__":
    main()
