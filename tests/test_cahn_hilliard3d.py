"""The 3D Cahn–Hilliard ADI scheme against a plain float64 reference.

The reference below is the scheme written out with nothing from the
library: ``jnp.roll`` stencils (the 7-point Laplacian, and grad^4 as that
Laplacian applied twice) and every implicit solve a dense inverse of the
circulant ``I + b delta^4`` along its axis, ``b = D gamma dt / h^4``,
inverted in float64 by numpy.  The step's factors and the bootstrap's
share that ``b``: (3/2) beta, ``beta = (2/3) D gamma dt / h^4`` being
eq. (2)'s coefficient (the solver module's doc says why).  The solver
runs through ``repro.create`` plans (two ``Stencil3D`` plans for the RHS,
an ``ADIOperator3D`` triple for the sweeps) and its ``make_evolve``
driver, on the jnp backend in float64 and on the Pallas kernels
(interpreted on the CPU) in float32, the chip's precision.

Tolerances (relative: max |solver - reference| / max |reference|):

- float64, jnp: 1e-10.  Every path is a few stencil and substitution
  passes over O(1) numbers, whose rounding stays near 1e-15; a different
  scheme would differ at O(dt) in the field, far above it.
- float32, Pallas: 2e-6.  Float32 rounding of the same passes reads
  4.2e-8 to 4.8e-7 at these sizes; the reference in bfloat16 reads
  1.3e-2 to 1.5e-2 after four steps, and without its nonlinear term
  0.17 to 0.30.

``test_the_tolerances_fail_a_dropped_nonlinear_term_and_bfloat16`` holds
both limits below what the reference itself reads with its nonlinear term
left out, or run in bfloat16, so that neither could pass.
"""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.cahn_hilliard import CahnHilliardADI, CHConfig

D, GAMMA, DT = 0.6, 0.01, 1e-3
D4 = (1.0, -4.0, 6.0, -4.0, 1.0)
TOL = {"float64": 1e-10, "float32": 2e-6}
BACKENDS = [("jnp", "float64"), ("pallas", "float32")]


# -- the plain reference ----------------------------------------------------


def _lap(v):
    return sum(jnp.roll(v, s, axis=a) for a in range(v.ndim) for s in (1, -1)) \
        - 2 * v.ndim * v


def _inverse(m: int, beta: float) -> np.ndarray:
    """Dense ``(I + beta delta^4)^{-1}`` on a periodic line of ``m``."""
    a = np.eye(m)
    for off, w in zip(range(-2, 3), D4, strict=True):
        a += beta * w * np.roll(np.eye(m), off, axis=1)
    return np.linalg.inv(a)


def _solve(v, beta, axis):
    inv = jnp.asarray(_inverse(v.shape[axis], beta), v.dtype)
    return jnp.moveaxis(jnp.tensordot(inv, v, axes=([1], [axis]),
                                      precision="highest"), 0, axis)


def _solve_all(v, beta):
    for axis in (2, 1, 0):  # x, then y, then z
        v = _solve(v, beta, axis)
    return v


def ref_bootstrap(c0, h, nonlinear=True):
    """``(I + b d_x^4)(I + b d_y^4)(I + b d_z^4)(C1 - C0)
    = dt D [-gamma grad^4 C0 + grad^2 (C0^3 - C0)]``, ``b = D gamma dt / h^4``."""
    nl = _lap(c0**3 - c0) / h**2 if nonlinear else 0.0
    rhs = DT * D * (-GAMMA * _lap(_lap(c0)) / h**4 + nl)
    return c0 + _solve_all(rhs, D * GAMMA * DT / h**4)


def ref_step(cn, cm, h, nonlinear=True):
    """Paper eq. (2) with a third implicit factor ``L_z``, every factor at
    ``b = (3/2) beta``."""
    cb = 2 * cn - cm
    rhs = (-(2 / 3) * (cn - cm)
           - (2 / 3) * DT * D * GAMMA * _lap(_lap(cb)) / h**4)
    if nonlinear:
        rhs = rhs + (2 / 3) * D * DT * _lap(cn**3 - cn) / h**2
    return cb + _solve_all(rhs, D * GAMMA * DT / h**4), cn


def ref_run(c0, h, steps, dtype="float64", nonlinear=True):
    """The bootstrap and ``steps`` full steps: ``(C^{steps+1}, C^steps)``."""
    c0 = jnp.asarray(c0, dtype)
    cn, cm = ref_bootstrap(c0, h, nonlinear), c0
    for _ in range(steps):
        cn, cm = ref_step(cn, cm, h, nonlinear)
    return cn, cm


# -- helpers ----------------------------------------------------------------


def _field(n, seed, dtype="float64"):
    rng = np.random.default_rng(seed)
    return rng.uniform(-0.1, 0.1, (n, n, n)).astype(dtype)


def _rel(a, ref):
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(a - ref)) / np.max(np.abs(ref)))


@functools.lru_cache(maxsize=None)
def _solver(n, backend, dtype):
    return CahnHilliardADI(CHConfig(
        nx=n, ny=n, nz=n, dt=DT, D=D, gamma=GAMMA, dtype=dtype,
        rhs_mode="stencil", backend=backend))


def _h(n):
    return 2 * np.pi / n


CASES = [(n, b, d) for n in (16, 32) for b, d in BACKENDS]
IDS = [f"{n}^3-{b}-{d}" for n, b, d in CASES]


# -- the solver against the reference ---------------------------------------


@pytest.mark.parametrize("n, backend, dtype", CASES, ids=IDS)
def test_bootstrap_matches_the_reference(n, backend, dtype):
    c0 = _field(n, seed=n, dtype=dtype)
    got = _solver(n, backend, dtype).initial_step(jnp.asarray(c0))
    want = ref_bootstrap(jnp.asarray(c0, jnp.float64), _h(n))
    assert _rel(got, want) < TOL[dtype]


@pytest.mark.parametrize("n, backend, dtype", CASES, ids=IDS)
def test_one_step_matches_the_reference(n, backend, dtype):
    cn, cm = _field(n, seed=1, dtype=dtype), _field(n, seed=2, dtype=dtype)
    got_n, got_m = jax.jit(_solver(n, backend, dtype).step)(
        jnp.asarray(cn), jnp.asarray(cm))
    want_n, want_m = ref_step(jnp.asarray(cn, jnp.float64),
                              jnp.asarray(cm, jnp.float64), _h(n))
    assert _rel(got_n, want_n) < TOL[dtype]
    np.testing.assert_array_equal(got_m, cn)


@pytest.mark.parametrize("n, backend, dtype", CASES, ids=IDS)
def test_make_evolve_four_steps_matches_the_reference(n, backend, dtype):
    solver = _solver(n, backend, dtype)
    c0 = jnp.asarray(_field(n, seed=3, dtype=dtype))
    c1 = solver.initial_step(c0)
    got_n, got_m = solver.make_evolve(4)(c1, jnp.array(c0))
    want_n, want_m = ref_run(np.asarray(c0, np.float64), _h(n), 4)
    assert _rel(got_n, want_n) < TOL[dtype]
    assert _rel(got_m, want_m) < TOL[dtype]


@pytest.mark.parametrize("n", [16, 32])
def test_the_tolerances_fail_a_dropped_nonlinear_term_and_bfloat16(n):
    c0 = _field(n, seed=3)
    want, _ = ref_run(c0, _h(n), 4)
    dropped, _ = ref_run(c0, _h(n), 4, nonlinear=False)
    bf16, _ = ref_run(c0, _h(n), 4, dtype="bfloat16")
    assert _rel(dropped, want) > 100 * TOL["float32"]
    assert _rel(bf16, want) > 100 * TOL["float32"]


# -- ties to the 2D scheme and to the operator registry -----------------------


def _with_factors(solver, alpha):
    """``solver``, before its first ``make_evolve``, with its step's
    factors built at ``alpha`` instead."""
    solver.op_full = api.create(
        "hyperdiffusion", solver.cfg.shape, mode="adi", alpha=alpha,
        cyclic=True, dtype=solver.cfg.dtype, backend=solver.cfg.backend)
    return solver


def test_a_z_constant_step_is_the_2d_step():
    # delta_z of a z-constant field is 0: L_z is the identity and the 3D
    # stencils reduce to the 2D ones, so the step is eq. (2) on each slice,
    # with the 2D factors at the 3D coefficient (3/2) beta
    n, nz = 16, 8
    cfg2 = CHConfig(nx=n, ny=n, dt=DT, D=D, gamma=GAMMA, rhs_mode="stencil",
                    backend="jnp")
    cfg3 = dataclasses.replace(cfg2, nz=nz)
    cn, cm = (_field(n, seed=s)[0] for s in (4, 5))
    solver2 = _with_factors(CahnHilliardADI(cfg2), D * GAMMA * DT / _h(n)**4)
    want, _ = solver2.step(jnp.asarray(cn), jnp.asarray(cm))
    stack = lambda c: jnp.broadcast_to(jnp.asarray(c), (nz, n, n))  # noqa: E731
    got, _ = CahnHilliardADI(cfg3).step(stack(cn), stack(cm))
    for k in range(nz):
        np.testing.assert_allclose(got[k], want, rtol=0, atol=1e-15)


def _growth(solver, c, steps):
    # make_evolve donates its carry: it gets copies
    carry = solver.make_evolve(steps)(jnp.array(c), jnp.array(c))
    return float(jnp.max(jnp.abs(carry[0]))) / float(jnp.max(jnp.abs(c)))


@pytest.mark.parametrize("dt_factor, grows", [(0.01, False), (0.1, True)])
def test_the_3d_step_is_stable_only_below_its_beta_bound(dt_factor, grows):
    # The bound is that of three factors at eq. (2)'s beta, and the reason
    # the library's carry (3/2) beta.  One Fourier mode with
    # 2 - 2cos(theta) = 2.77 on each axis, at an amplitude where C^3 is
    # nothing: beta = (2/3) dt_factor.  At 0.0067 every mode's growth
    # factor is below 1 either way; at 0.067, the cells' beta, this mode's
    # is about 1.23 in size at beta (the explicit cross terms
    # 2 beta (d_x d_y + d_y d_z + d_z d_x) outweigh the three factors), and
    # below 1 at (3/2) beta.  gamma = 1 keeps the Laplacian's explicit
    # antidiffusion out of the way.
    n, gamma = 16, 1.0
    h = 2 * np.pi / n
    cfg = CHConfig(nx=n, ny=n, nz=n, dt=dt_factor * h**4 / (D * gamma), D=D,
                   gamma=gamma, rhs_mode="stencil", backend="jnp")
    x = np.arange(n) * h
    mode = 1e-6 * np.cos(5 * (x[:, None, None] + x[None, :, None] + x[None, None, :]))
    c = jnp.asarray(mode)
    at_beta = _with_factors(CahnHilliardADI(cfg), (2 / 3) * dt_factor)
    growth = _growth(at_beta, c, 60)
    assert (growth > 1e3) if grows else (growth < 1), growth
    assert _growth(CahnHilliardADI(cfg), c, 60) < 1


def test_the_default_dt_is_stable_at_64_cubed():
    # dt = 1e-3 gives beta = 0.043 at 64^3, above the bound of factors at
    # beta: there the deep-quench field overflows within 30 steps, while at
    # the library's (3/2) beta it has only started to separate
    n = 64
    cfg = CHConfig(nx=n, ny=n, nz=n, dt=DT, D=D, gamma=GAMMA,
                   rhs_mode="stencil", backend="jnp")
    solver = CahnHilliardADI(cfg)
    c0 = jnp.asarray(_field(n, seed=6))
    c1 = solver.initial_step(c0)

    def peak(s):  # make_evolve donates its carry: it gets copies
        carry = s.make_evolve(30)(jnp.array(c1), jnp.array(c0))
        return float(jnp.max(jnp.abs(carry[0])))

    assert peak(solver) < 0.1
    at_beta = _with_factors(CahnHilliardADI(cfg), (2 / 3) * D * GAMMA * DT / _h(n)**4)
    assert not peak(at_beta) < 1  # far above 1, or NaN


@pytest.mark.parametrize("ndim", [2, 3])
def test_biharmonic_weights_are_the_laplacian_applied_twice(ndim):
    # the registry's weights as a periodic stencil, against the Laplacian
    # (5-point cross, 7-point in 3D) applied twice by rolls
    f = jnp.asarray(np.random.default_rng(ndim).standard_normal((8,) * ndim))
    w = np.asarray(api.get_operator("biharmonic").weights(ndim))
    assert w.shape == (5,) * ndim and np.count_nonzero(w) == {2: 13, 3: 25}[ndim]
    got = sum(w[idx] * jnp.roll(f, tuple(2 - i for i in idx), axis=tuple(range(ndim)))
              for idx in np.ndindex(w.shape))
    np.testing.assert_allclose(got, _lap(_lap(f)), rtol=0, atol=1e-12)


@pytest.mark.parametrize("mode", ["fused", "batch1d"])
def test_2d_rhs_modes_refuse_a_3d_configuration(mode):
    with pytest.raises(ValueError, match="2D only.*rhs_mode='stencil'"):
        CahnHilliardADI(CHConfig(nx=16, ny=16, nz=16, rhs_mode=mode))


# -- the 2D solver's program is what it was ----------------------------------

# sha256 of the lowered make_evolve(16) program at 64^2, without debug info
# (source locations and scope names), as the 2D solver built it before the
# 3D scheme was added.  They hold the 2D program still while the 3D scheme
# shares its code: re-record them whenever the 2D program changes on
# purpose, or the jax version does
PROGRAM_2D = {
    "fused-jnp-float64":
        "8b86012c3781abb197a65eaed4efb51726d7994c63d06ec2b015a0fed867fda5",
    "fused-pallas-float32":
        "4e0487222b91a22e2026ae0207a47e0e13d33e728e060b5383fc699da5df3d97",
    "stencil-jnp-float64":
        "daba49ced4f3182ae6d2290e66f596221c426e8305a7cdffbc2c4836301a81cf",
}


def _program_digest(rhs_mode, backend, dtype):
    solver = CahnHilliardADI(CHConfig(nx=64, ny=64, dt=DT, rhs_mode=rhs_mode,
                                      backend=backend, dtype=dtype))
    c = jax.ShapeDtypeStruct((64, 64), jnp.dtype(dtype))
    module = solver.make_evolve(16).lower(c, c).compiler_ir("stablehlo")
    text = module.operation.get_asm(enable_debug_info=False)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("key", sorted(PROGRAM_2D))
def test_the_2d_evolve_program_is_unchanged(key):
    rhs_mode, backend, dtype = key.split("-")
    assert _program_digest(rhs_mode, backend, dtype) == PROGRAM_2D[key]
