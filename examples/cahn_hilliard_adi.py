"""Cahn–Hilliard ADI end-to-end driver (the paper's §V "cuCahnPentADI").

Runs the deep-quench coarsening experiment and reports s(t) and 1/k1(t)
with their fitted power-law exponents (paper Fig. 1 expects ~t^{1/3}).
The solver's plans are built on the four-function facade internally; the
driver uses it directly too — a registry-operator Laplacian plan computes
the chemical potential mu = C^3 - C - gamma grad^2 C before and after the
run (grad mu drives the flux, so max|grad^2 mu| shrinking is coarsening
made visible).

    PYTHONPATH=src python examples/cahn_hilliard_adi.py                  # 256^2
    PYTHONPATH=src python examples/cahn_hilliard_adi.py --n 1024 --t 100 # Fig. 1
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.core.cahn_hilliard import (
    CahnHilliardADI,
    CHConfig,
    coarsening_metrics,
    deep_quench_ic,
)
from repro.core.metrics import fit_power_law
from repro.util import init_compile_cache

jax.config.update("jax_enable_x64", True)


def chemical_potential(lap_plan, c, gamma):
    """mu = C^3 - C - gamma grad^2 C via one facade Compute call."""
    return c**3 - c - gamma * repro.compute(lap_plan, c)


def main():
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--t", type=float, default=8.0, help="final time")
    ap.add_argument("--dt", type=float, default=2e-3)
    ap.add_argument(
        "--rhs", choices=["fused", "stencil", "batch1d"], default="fused"
    )
    ap.add_argument(
        "--tune", choices=["off", "cached", "force"], default="off",
        help="Create-time autotuning (cached results under "
        "~/.cache/repro-tune or $REPRO_TUNE_CACHE)",
    )
    ap.add_argument(
        "--retune", action="store_true",
        help="force re-measurement even on a warm tune cache — the "
        "escape hatch for caches shipped from another host "
        "(sets REPRO_TUNE_FORCE)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.retune:
        from repro.tune import enable_force

        enable_force()
        if args.tune == "off":
            args.tune = "cached"

    cfg = CHConfig(
        nx=args.n, ny=args.n, dt=args.dt, D=0.6, gamma=0.01,
        rhs_mode=args.rhs, backend="jnp", tune=args.tune,
    )
    solver = CahnHilliardADI(cfg)
    c0 = deep_quench_ic(args.n, args.n, seed=args.seed)
    n_steps = int(args.t / args.dt)
    save_every = max(n_steps // 16, 1)

    # Create: a registry-operator Laplacian for the mu diagnostic
    lap = repro.create("laplacian", (args.n, args.n), h=cfg.dx, backend="jnp")
    mu0 = float(jnp.abs(
        repro.compute(lap, chemical_potential(lap, c0, cfg.gamma))
    ).max())

    print(f"# Cahn-Hilliard {args.n}^2, dt={args.dt}, {n_steps} steps, "
          f"rhs={args.rhs}")
    print("# t, s(t), 1/k1(t), F(t), mass")
    t0 = time.time()
    c_final, hist = solver.run(
        c0, n_steps, save_every=save_every, metrics_fn=coarsening_metrics(cfg)
    )
    wall = time.time() - t0
    for step, (s, invk1, F, m) in hist:
        print(f"{step*cfg.dt:8.3f} {float(s):10.5f} {float(invk1):10.5f} "
              f"{float(F):10.5f} {float(m):+.3e}")

    t = np.array([h[0] for h in hist], float)[len(hist) // 3 :] * cfg.dt
    s = np.array([float(h[1][0]) for h in hist])[len(hist) // 3 :]
    k = np.array([float(h[1][1]) for h in hist])[len(hist) // 3 :]
    print(f"# power-law fits (expect ~1/3): "
          f"s-1 ~ t^{fit_power_law(t, s - 1):.3f}, "
          f"1/k1 ~ t^{fit_power_law(t, k):.3f}")
    print(f"# wall: {wall:.1f}s  ({wall/n_steps*1e3:.2f} ms/step)")
    mu1 = float(jnp.abs(
        repro.compute(lap, chemical_potential(lap, c_final, cfg.gamma))
    ).max())
    print(f"# max|grad^2 mu|: {mu0:.3e} -> {mu1:.3e} "
          f"(the flux divergence dying out as domains coarsen)")
    repro.destroy(lap)


if __name__ == "__main__":
    main()
