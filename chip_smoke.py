"""Chip smoke test: the library's main path, once, end to end, on a TPU.

    python chip_smoke.py             # phases (a)-(f) on one chip
    python chip_smoke.py --chips 4   # only the 2x2-mesh distributed CH phase

Every phase goes through the public entry points (``repro.create`` /
``compute`` / ``swap`` / ``destroy``, ``CahnHilliardADI`` + ``ch_evolve``,
``ServeEngine``) in float32 with ``tune='off'`` and ``backend='auto'``,
and checks its result against a plain float64 numpy reference computed on
the host.  A phase that claims a Pallas kernel must find
``tpu_custom_call`` in its compiled HLO: ``auto`` falling back to jnp
fails the phase.  Each phase prints one line (shapes, dtype, the backend
dispatch chose, compile seconds, error against its tolerance, and an
informational ms/step that is not a benchmark).  The last line is a JSON
object naming the device.  Any failure raises; without a TPU the script
exits non-zero before printing any result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

F32 = "float32"
SEED = 0
# how a Pallas kernel appears in compiled TPU HLO
KERNEL = 'custom_call_target="tpu_custom_call"'

# f32-vs-f64 tolerances: max |out - ref| / max |ref| (for the ADI solves,
# of the band operator's residual against the right-hand side)
TOL_STENCIL = 1e-5
TOL_ADI = 1e-5
TOL_CH = 1e-4  # the first CH steps against the f64 scheme
TOL_MASS = 1e-5  # |mean(c_end) - mean(c_0)| over the whole CH run
TOL_DIST = 1e-4  # four-chip CH against the one-device solver


def _phase(name, *, shapes, backend, kernels, compile_s, err, tol, ms):
    print(
        f"[{name}] shapes={shapes} dtype={F32} backend={backend} "
        f"tpu_custom_call={kernels} compile_s={compile_s:.2f} "
        f"max_rel_err={err:.3e} tol={tol:.0e} ms_per_step={ms:.3f}",
        flush=True,
    )
    if not err <= tol:
        raise AssertionError(f"[{name}] error {err:.3e} exceeds {tol:.0e}")


def _aot(fn, *args):
    """Compile ``fn`` for ``args``; return (compiled, seconds, #kernels)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    return compiled, secs, compiled.as_text().count(KERNEL)


def _need_kernels(name, n, want):
    if n < want:
        raise AssertionError(
            f"[{name}] expected {want} Pallas kernel(s) in the compiled HLO, "
            f"found {n}: backend='auto' did not pick Pallas"
        )
    return "pallas"


def _timed(fn, reps):
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def _rel(out, ref):
    out = np.asarray(out, np.float64)
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


# -- float64 numpy references (periodic) -------------------------------------


def np_stencil(x, w):
    """Centred periodic stencil ``w`` over the last ``w.ndim`` axes."""
    w = np.asarray(w, np.float64)
    axes = tuple(range(x.ndim - w.ndim, x.ndim))
    out = np.zeros_like(x)
    for idx in np.ndindex(w.shape):
        if w[idx]:
            shift = [k // 2 - i for k, i in zip(w.shape, idx)]
            out += w[idx] * np.roll(x, shift, axis=axes)
    return out


def np_band(v, diags, axis):
    """Apply a constant cyclic pentadiagonal band along ``axis``."""
    l2, l1, d, u1, u2 = diags
    r = lambda s: np.roll(v, s, axis=axis)  # noqa: E731  r(s)[i] = v[i-s]
    return l2 * r(2) + l1 * r(1) + d * v + u1 * r(-1) + u2 * r(-2)


def hyper_band(a):
    return (a, -4 * a, 1 + 6 * a, -4 * a, a)  # I + a delta^4


def diff_band(a):
    return (0.0, -a, 1 + 2 * a, -a, 0.0)  # I - a delta^2


D2 = np.array([1.0, -2.0, 1.0])
D4 = np.array([1.0, -4.0, 6.0, -4.0, 1.0])


def _d(v, w, axis):
    return np.moveaxis(np_stencil(np.moveaxis(v, axis, -1), w), -1, axis)


def _solve_hyper(r, beta, axis):
    """``(I + beta delta^4)^{-1}`` along ``axis``: diagonal in Fourier."""
    m = r.shape[axis]
    th = 2 * np.pi * np.arange(m // 2 + 1) / m
    lam = 1 + beta * (2 - 2 * np.cos(th)) ** 2
    shape = [1] * r.ndim
    shape[axis] = lam.size
    f = np.fft.rfft(r, axis=axis) / lam.reshape(shape)
    return np.fft.irfft(f, n=m, axis=axis)


def ch_reference(c0, cfg, n_steps):
    """The paper's scheme (eqs. 2-3) in float64: the bootstrap step and
    ``n_steps`` full steps; returns ``c_{n_steps + 1}``."""
    h = cfg.lx / cfg.nx
    i2, i4 = 1 / h**2, 1 / h**4
    D, g, dt = cfg.D, cfg.gamma, cfg.dt
    cross = lambda v: _d(_d(v, D2, 0), D2, 1)  # noqa: E731
    lap_cube = lambda v: _d(v**3 - v, D2, 0) + _d(v**3 - v, D2, 1)  # noqa: E731

    half, coef = 0.5 * dt, D * g * i4
    b_half = 0.5 * D * g * dt * i4
    ra = c0 + half * (-coef * (_d(c0, D4, 0) + 2 * cross(c0)) + D * i2 * lap_cube(c0))
    ch = _solve_hyper(ra, b_half, 1)
    rb = ch + half * (-coef * (_d(ch, D4, 1) + 2 * cross(ch)) + D * i2 * lap_cube(ch))
    cn, cm = _solve_hyper(rb, b_half, 0), c0

    b_full = (2 / 3) * D * g * dt * i4
    for _ in range(n_steps):
        cb = 2 * cn - cm
        bih = _d(cb, D4, 0) + _d(cb, D4, 1) + 2 * cross(cb)
        rhs = (
            -(2 / 3) * (cn - cm)
            - (2 / 3) * dt * g * D * i4 * bih
            + (2 / 3) * D * dt * i2 * lap_cube(cn)
        )
        v = _solve_hyper(_solve_hyper(rhs, b_full, 1), b_full, 0)
        cn, cm = 2 * cn - cm + v, cn
    return cn


def ch_config(n):
    """The solver at ``n``² with the library's D, gamma and domain, and
    ``dt = h^4 / (10 D gamma)``.  The bootstrap step (eq. 3) treats one
    direction's hyperdiffusion explicitly, which multiplies grid-scale
    noise by about ``8 D gamma dt / h^4`` before the nonlinear term sees
    it; the library's default ``dt = 1e-3`` makes that factor about 1e7 at
    4096², and the deep-quench run overflows in float64 as in float32."""
    from repro.core.cahn_hilliard import CHConfig

    base = CHConfig(nx=n, ny=n)
    dt = base.dx**4 / (10 * base.D * base.gamma)
    return CHConfig(nx=n, ny=n, dt=dt, dtype=F32, rhs_mode="fused",
                    backend="auto", tune="off")


# -- phases ---------------------------------------------------------------


def phase_stencils_2d(n=4096):
    """(a) periodic laplacian and biharmonic plans, two Computes with a
    Swap between them, then Destroy."""
    import jax.numpy as jnp

    import repro

    rng = np.random.default_rng(SEED)
    x64 = rng.standard_normal((n, n))
    x = jnp.asarray(x64, F32)
    for op in ("laplacian", "biharmonic"):
        plan = repro.create(op, (n, n), bc="periodic", dtype=F32, tune="off")
        compiled, secs, k = _aot(repro.compute, plan, x)
        backend = _need_kernels(f"a:{op}", k, 1)
        buf = (x, compiled(plan, x))
        cur, _ = repro.swap(buf)  # the new field becomes the input
        out = compiled(plan, cur)
        w = np.asarray(repro.get_operator(op).weights(2), np.float64)
        ref = np_stencil(np_stencil(x64, w), w)
        ms = _timed(lambda plan=plan, c=compiled: c(plan, x), 10)
        repro.destroy(plan)
        assert plan.destroyed
        _phase(f"a:{op}", shapes=(n, n), backend=backend, kernels=k,
               compile_s=secs, err=_rel(out, ref), tol=TOL_STENCIL, ms=ms)


def phase_batch1d(b=4096, m=4096):
    """(b) one radius-2 1D stencil over every row of a (B, M) stack."""
    import jax.numpy as jnp

    import repro

    rng = np.random.default_rng(SEED + 1)
    x64 = rng.standard_normal((b, m))
    x = jnp.asarray(x64, F32)
    w = np.asarray(repro.central_difference_weights(4, 2), np.float64)
    plan = repro.create(w, (b, m), mode="batch", bc="periodic", dtype=F32,
                        tune="off")
    compiled, secs, k = _aot(repro.compute, plan, x)
    backend = _need_kernels("b:batch1d", k, 1)
    out = compiled(plan, x)
    ms = _timed(lambda: compiled(plan, x), 10)
    repro.destroy(plan)
    _phase("b:batch1d", shapes=(b, m), backend=backend, kernels=k,
           compile_s=secs, err=_rel(out, np_stencil(x64, w)),
           tol=TOL_STENCIL, ms=ms)


def _adi_phase(name, op, shape, alpha, band, n_sweeps):
    import jax.numpy as jnp

    import repro

    rng = np.random.default_rng(SEED + len(shape))
    b64 = rng.standard_normal(shape)
    b = jnp.asarray(b64, F32)
    plan = repro.create(op, shape, mode="adi", alpha=alpha, bc="periodic",
                        dtype=F32, tune="off")
    compiled, secs, k = _aot(repro.compute, plan, b)
    backend = _need_kernels(name, k, n_sweeps)
    x = np.asarray(compiled(plan, b), np.float64)
    ms = _timed(lambda: compiled(plan, b), 5)
    repro.destroy(plan)
    resid = x
    for axis in range(len(shape)):
        resid = np_band(resid, band(alpha), axis)
    _phase(name, shapes=shape, backend=backend, kernels=k, compile_s=secs,
           err=_rel(resid, b64), tol=TOL_ADI, ms=ms)


def phase_adi_2d(n=4096):
    """(c) a periodic hyperdiffusion ADI solve: column and row sweeps."""
    _adi_phase("c:adi2d", "hyperdiffusion", (n, n), 1.0, hyper_band, 2)


def phase_3d(n=256):
    """(d) a 3D stencil plan and a 3D ADI step (row, plane and column
    sweeps)."""
    import jax.numpy as jnp

    import repro

    rng = np.random.default_rng(SEED + 3)
    x64 = rng.standard_normal((n, n, n))
    x = jnp.asarray(x64, F32)
    plan = repro.create("laplacian", (n, n, n), bc="periodic", dtype=F32,
                        tune="off")
    compiled, secs, k = _aot(repro.compute, plan, x)
    backend = _need_kernels("d:stencil3d", k, 1)
    out = compiled(plan, x)
    ms = _timed(lambda: compiled(plan, x), 10)
    repro.destroy(plan)
    w = np.asarray(repro.get_operator("laplacian").weights(3), np.float64)
    _phase("d:stencil3d", shapes=(n, n, n), backend=backend, kernels=k,
           compile_s=secs, err=_rel(out, np_stencil(x64, w)),
           tol=TOL_STENCIL, ms=ms)
    _adi_phase("d:adi3d", "diffusion", (n, n, n), 1.0, diff_band, 3)


def phase_cahn_hilliard(n=4096, steps=100, check_steps=2):
    """(e) the paper's CH ADI solver, fused RHS + x-sweep, through
    ``ch_evolve``: the first steps against the f64 scheme, then a long
    run checked for finiteness and mass."""
    import jax

    from repro.core.cahn_hilliard import (
        CahnHilliardADI,
        ch_evolve,
        deep_quench_ic,
    )

    cfg = ch_config(n)
    solver = CahnHilliardADI(cfg)
    c0 = deep_quench_ic(n, n, seed=SEED, dtype=F32)
    c0_64 = np.asarray(c0, np.float64)

    evolve = solver.make_evolve(steps)
    t0 = time.perf_counter()
    text = evolve.lower(c0, c0).compile().as_text()
    secs = time.perf_counter() - t0
    k = text.count(KERNEL)
    backend = _need_kernels("e:ch", k, 2)  # fused RHS+x-sweep, y-sweep

    early, _ = ch_evolve(solver, c0, check_steps)
    err = _rel(early, ch_reference(c0_64, cfg, check_steps))

    ch_evolve(solver, c0, steps)  # warm: compiles the bootstrap step
    t0 = time.perf_counter()
    final, _ = ch_evolve(solver, c0, steps)
    final = np.asarray(jax.block_until_ready(final), np.float64)
    ms = (time.perf_counter() - t0) / (steps + 1) * 1e3
    if not np.all(np.isfinite(final)):
        raise AssertionError("[e:ch] non-finite field after the long run")
    drift = abs(final.mean() - c0_64.mean())
    _phase("e:ch", shapes=(n, n), backend=backend, kernels=k,
           compile_s=secs, err=err, tol=TOL_CH, ms=ms)
    _phase("e:ch_mass", shapes=(n, n), backend=backend, kernels=k,
           compile_s=0.0, err=drift, tol=TOL_MASS, ms=ms)


def phase_serve(n=1024, per_class=2):
    """(f) ServeEngine over the serve CLI's four request classes, each
    field scaled up: bit-identical to sequential Computes, none degraded."""
    import jax.numpy as jnp

    import repro
    from repro.serve import ServeEngine
    from repro.serve.cli import build_requests, sequential_reference

    classes = [
        ("laplacian", (n, n), None, None),
        ("biharmonic", (n, n), None, None),
        ("laplacian", (n * n,), None, None),
        ("hyperdiffusion", (n, n), "adi", 0.1),
    ]
    reqs = build_requests(per_class * len(classes), SEED, 1, classes=classes)
    # the kernels dispatch picked for each class, from a sequential plan
    kernels = []
    for op, shape, mode, alpha in classes:
        shp = (1,) + shape if len(shape) == 1 else shape
        plan = repro.create(op, shp, mode="batch" if len(shape) == 1 else mode,
                            alpha=alpha, dtype=F32, tune="off")
        _, _, k = _aot(repro.compute, plan, jnp.zeros(shp, F32))
        _need_kernels(f"f:{op}{shape}", k, 1)
        kernels.append(k)
        repro.destroy(plan)

    t0 = time.perf_counter()
    with ServeEngine(backend="auto", tune="off", degrade=False,
                     max_batch=8) as engine:
        results = engine.solve_many(reqs)
        stats = engine.stats()
    wall = time.perf_counter() - t0
    if stats["degraded"] != 0:
        raise AssertionError(f"[f:serve] {stats['degraded']} degraded")
    refs = sequential_reference(reqs)
    bad = [r.tag for r, ref in zip(results, refs)
           if not np.array_equal(np.asarray(r.out), np.asarray(ref))]
    _phase("f:serve", shapes=[c[1] for c in classes], backend="pallas",
           kernels=kernels, compile_s=0.0, err=float(len(bad)), tol=0.0,
           ms=wall / len(reqs) * 1e3)
    print(f"[f:serve] degraded={stats['degraded']} "
          f"completed={stats['completed']} batches={stats['batches']}",
          flush=True)


def phase_four_chips(n=8192, steps=3):
    """Four chips: ``DistributedCahnHilliard`` on a 2x2 mesh (and one
    ``distributed_stencil_apply``) against the one-device solver."""
    import jax
    import jax.numpy as jnp

    import repro
    from repro.core.cahn_hilliard import CahnHilliardADI, deep_quench_ic
    from repro.core.dist_ch import DistributedCahnHilliard
    from repro.core.domain import DomainDecomposition, distributed_stencil_apply

    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = jax.sharding.Mesh(np.array(devices).reshape(2, 2), ("data", "model"))
    dd = DomainDecomposition(mesh=mesh)
    cfg = ch_config(n)
    dist = DistributedCahnHilliard(cfg, dd)
    single = CahnHilliardADI(cfg)

    c0 = deep_quench_ic(n, n, seed=SEED, dtype=F32)
    c1 = single.initial_step(c0)
    ref_step = jax.jit(single.step)
    cn_r, cm_r = c1, c0
    for _ in range(steps):
        cn_r, cm_r = ref_step(cn_r, cm_r)
    ref = np.asarray(cn_r, np.float64)

    sharding = dist.field_sharding()
    cn, cm = jax.device_put(c1, sharding), jax.device_put(c0, sharding)
    step = jax.jit(dist.step)
    t0 = time.perf_counter()
    compiled = step.lower(cn, cm).compile()
    secs = time.perf_counter() - t0
    for _ in range(steps):
        cn, cm = compiled(cn, cm)
    ms = _timed(lambda: compiled(cn, cm), 5)

    shards = {s.device: s.data.shape for s in cn.addressable_shards}
    if set(shards) != set(devices) or set(shards.values()) != {(n // 2, n // 2)}:
        raise AssertionError(f"[4chip:ch] shards not one per device: {shards}")
    print(f"[4chip:ch] shards={sorted((d.id, s) for d, s in shards.items())}",
          flush=True)
    _phase("4chip:ch", shapes=(n, n), backend="jnp+shardings", kernels=0,
           compile_s=secs, err=_rel(cn, ref), tol=TOL_DIST, ms=ms)

    plan = repro.create("biharmonic", (n, n), bc="periodic", dtype=F32,
                        tune="off")
    x = jax.device_put(c0, sharding)
    apply = jax.jit(lambda f: distributed_stencil_apply(plan, f, dd))
    t0 = time.perf_counter()
    compiled = apply.lower(x).compile()
    secs = time.perf_counter() - t0
    text = compiled.as_text()
    out = compiled(x)
    ms = _timed(lambda: compiled(x), 5)
    if "collective-permute" not in text:
        raise AssertionError("[4chip:halo] no collective-permute halo exchange")
    ref = np.asarray(repro.compute(plan, c0), np.float64)
    _phase("4chip:halo", shapes=(n, n), backend="shard_map+ppermute",
           kernels=text.count(KERNEL), compile_s=secs,
           err=_rel(out, ref), tol=TOL_STENCIL, ms=ms)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-mesh distributed phase")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.util import init_compile_cache

    print(f"compile cache: {init_compile_cache()}", flush=True)
    if args.chips == 4:
        phase_four_chips()
    else:
        phase_stencils_2d()
        phase_batch1d()
        phase_adi_2d()
        phase_3d()
        phase_cahn_hilliard()
        phase_serve()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
