"""WENO5 advection example (the paper's ``2d_xyWENOADV_p``).

Rigid-body rotation of a Gaussian blob through one full revolution; the
final field should coincide with the initial one.

    PYTHONPATH=src python examples/weno_advection.py [--n 256]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.weno import (
    AdvectionConfig,
    WenoAdvection2D,
    gaussian_blob,
    solid_body_rotation,
)
from repro.util import init_compile_cache

jax.config.update("jax_enable_x64", True)


def main():
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--revolutions", type=float, default=1.0)
    args = ap.parse_args()

    cfg = AdvectionConfig(nx=args.n, ny=args.n, cfl=0.4, backend="jnp")
    solver = WenoAdvection2D(cfg)
    q0 = gaussian_blob(cfg, x0=np.pi + 1.0, y0=np.pi, sigma=0.4)
    u, v = solid_body_rotation(cfg)

    t_final = 2 * np.pi * args.revolutions  # one revolution period is 2*pi
    t0 = time.time()
    qT, n_steps = solver.run(q0, u, v, t_final)
    wall = time.time() - t0

    l2 = float(jnp.sqrt(jnp.mean((qT - q0) ** 2)))
    print(f"grid {args.n}^2, {n_steps} RK3 steps in {wall:.1f}s")
    print(f"L2 error after {args.revolutions} revolution(s): {l2:.3e}")
    print(f"min/max: {float(qT.min()):+.4f} / {float(qT.max()):.4f} "
          f"(ENO: no significant over/undershoot)")


if __name__ == "__main__":
    main()
