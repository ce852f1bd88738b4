"""The 3D ADI subsystem: plane-layout (batched-planes) pentadiagonal
substitution vs the dense oracle in both backends, the three transpose-free
sweeps of :class:`ADIOperator3D` (incl. round-trips against the dense
operator), the diffusion-band variant, streamed solves, and the LOD
diffusion scheme's exact discrete decay."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import check_jaxpr
from repro.core.adi import make_adi_operator_3d
from repro.kernels import ref as R
from repro.kernels.penta import (
    cyclic_penta_factor,
    cyclic_penta_solve_factored_mid,
    diffusion_diagonals,
    hyperdiffusion_diagonals,
    penta_factor,
    penta_solve_factored_mid,
)
from repro.core.stream import stream_penta_solve_mid
from repro.util import tolerance_for

TOL = tolerance_for(jnp.float64)
TOL_I = tolerance_for(jnp.float64, scale=10)  # interpret-mode recurrences


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float64)


def _solve_planes_ref(diags, rhs, *, cyclic):
    """Dense oracle for the plane layout: each (p, :, n) line one system."""
    out = [R.penta_solve_ref(*diags, rhs[p], cyclic=cyclic) for p in
           range(rhs.shape[0])]
    return jnp.stack(out)


class TestPlaneLayoutSubstitution:
    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_plain_matches_dense(self, backend):
        rng = np.random.default_rng(0)
        p, m, n = 3, 24, 16
        l2, l1, u1, u2 = (_rand(rng, (m,)) for _ in range(4))
        d = jnp.asarray(8.0 + np.abs(rng.standard_normal(m)))
        rhs = _rand(rng, (p, m, n))
        fac = penta_factor(l2, l1, d, u1, u2)
        x = penta_solve_factored_mid(fac, rhs, backend=backend, interpret=True)
        ref = _solve_planes_ref((l2, l1, d, u1, u2), rhs, cyclic=False)
        np.testing.assert_allclose(x, ref, **TOL_I)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_cyclic_matches_dense(self, backend):
        rng = np.random.default_rng(1)
        p, m, n = 4, 32, 16
        diags = hyperdiffusion_diagonals(m, 0.4)
        fac = cyclic_penta_factor(*diags)
        rhs = _rand(rng, (p, m, n))
        x = cyclic_penta_solve_factored_mid(
            fac, rhs, backend=backend, interpret=True
        )
        ref = _solve_planes_ref(diags, rhs, cyclic=True)
        np.testing.assert_allclose(x, ref, **TOL_I)

    def test_unroll_is_result_invariant(self):
        rng = np.random.default_rng(2)
        diags = hyperdiffusion_diagonals(32, 0.5)
        fac = cyclic_penta_factor(*diags)
        rhs = _rand(rng, (4, 32, 8))
        a = cyclic_penta_solve_factored_mid(fac, rhs, backend="jnp", unroll=1)
        b = cyclic_penta_solve_factored_mid(fac, rhs, backend="jnp", unroll=4)
        np.testing.assert_array_equal(a, b)

    def test_non_divisible_lane_tile_errors(self):
        fac = penta_factor(*hyperdiffusion_diagonals(16, 0.2))
        with pytest.raises(ValueError):
            penta_solve_factored_mid(
                fac, jnp.zeros((2, 16, 30)), backend="pallas", tn=16,
                interpret=True,
            )


class TestADIOperator3D:
    """x/y/z sweeps against the dense oracle + round-trips."""

    def setup_method(self):
        self.rng = np.random.default_rng(3)
        self.nz, self.ny, self.nx = 8, 12, 16
        self.rhs = _rand(self.rng, (self.nz, self.ny, self.nx))
        self.op = make_adi_operator_3d(
            self.nz, self.ny, self.nx, 0.3, cyclic=True, backend="jnp"
        )

    def test_solve_x_matches_dense(self):
        diags = hyperdiffusion_diagonals(self.nx, 0.3)
        ref = R.penta_solve_ref(
            *diags, self.rhs.reshape(-1, self.nx).T, cyclic=True
        ).T.reshape(self.rhs.shape)
        np.testing.assert_allclose(self.op.solve_x(self.rhs), ref, **TOL)

    def test_solve_y_matches_dense(self):
        diags = hyperdiffusion_diagonals(self.ny, 0.3)
        ref = _solve_planes_ref(diags, self.rhs, cyclic=True)
        np.testing.assert_allclose(self.op.solve_y(self.rhs), ref, **TOL)

    def test_solve_z_roundtrip_vs_dense(self):
        # the z-sweep ADI round-trip: applying the dense operator to the
        # solve recovers the right-hand side
        diags = hyperdiffusion_diagonals(self.nz, 0.3)
        A = R.penta_dense_cyclic(*diags)
        out = self.op.solve_z(self.rhs)
        back = (A @ out.reshape(self.nz, -1)).reshape(self.rhs.shape)
        np.testing.assert_allclose(back, self.rhs, **TOL)
        ref = R.penta_solve_ref(
            *diags, self.rhs.reshape(self.nz, -1), cyclic=True
        ).reshape(self.rhs.shape)
        np.testing.assert_allclose(out, ref, **TOL)

    def test_full_step_transpose_free(self):
        # the acceptance property: a full 3D ADI step (x, y, z implicit
        # sweeps) runs with zero transposes — reshapes of contiguous axes
        # only
        def step(c):
            return self.op.solve_z(self.op.solve_y(self.op.solve_x(c)))

        findings = check_jaxpr(
            jax.make_jaxpr(step)(self.rhs), ("no_transpose",)
        )
        assert findings == []

    def test_noncyclic_roundtrip(self):
        op = make_adi_operator_3d(
            self.nz, self.ny, self.nx, 0.3, cyclic=False, backend="jnp"
        )
        diags = hyperdiffusion_diagonals(self.ny, 0.3)
        A = R.penta_dense(*diags)
        out = op.solve_y(self.rhs)
        back = jnp.einsum("ab,pbn->pan", A, out)
        np.testing.assert_allclose(back, self.rhs, **TOL)

    def test_diffusion_operator_band(self):
        # operator='diffusion' factors I - r delta^2 (tridiagonal riding
        # the penta machinery)
        r = 0.4
        op = make_adi_operator_3d(
            self.nz, self.ny, self.nx, r, cyclic=True, backend="jnp",
            operator="diffusion",
        )
        diags = diffusion_diagonals(self.nx, r)
        A = R.penta_dense_cyclic(*diags)
        out = op.solve_x(self.rhs)
        back = jnp.einsum("ab,pnb->pna", A, out)
        np.testing.assert_allclose(back, self.rhs, **TOL)

    def test_streamed_sweeps_match_monolithic(self):
        streamed = make_adi_operator_3d(
            self.nz, self.ny, self.nx, 0.3, cyclic=True, backend="jnp",
            streams=2, max_tile_bytes=int(self.rhs.nbytes) // 4,
        )
        for name in ("solve_x", "solve_y", "solve_z"):
            np.testing.assert_allclose(
                getattr(streamed, name)(self.rhs),
                getattr(self.op, name)(self.rhs),
                err_msg=name,
                **TOL,
            )


class TestStreamedPlaneSolve:
    def test_stream_penta_solve_mid_matches(self):
        rng = np.random.default_rng(4)
        diags = hyperdiffusion_diagonals(24, 0.5)
        rhs = _rand(rng, (8, 24, 16))
        fac_c = cyclic_penta_factor(*diags)
        ref = cyclic_penta_solve_factored_mid(fac_c, rhs, backend="jnp")
        out = stream_penta_solve_mid(
            fac_c, rhs, cyclic=True, chunk_planes=2, streams=2
        )
        np.testing.assert_allclose(out, ref, **TOL)

        fac = penta_factor(*diags)
        ref = penta_solve_factored_mid(fac, rhs, backend="jnp")
        out = stream_penta_solve_mid(
            fac, rhs, cyclic=False, max_tile_bytes=int(rhs.nbytes) // 4
        )
        np.testing.assert_allclose(out, ref, **TOL)


class TestLODDiffusionScheme:
    def test_separable_mode_decays_at_exact_discrete_rate(self):
        # the example's validation, as a test: on sin(x)sin(y)sin(z) each
        # LOD backward-Euler sweep acts diagonally, so the per-step decay
        # factor is exactly prod_i (1 + 4 r sin^2(h/2))^-1
        n, steps = 16, 5
        h = 2.0 * np.pi / n
        r = 0.5 * 2e-3 / h**2
        op = make_adi_operator_3d(
            n, n, n, r, cyclic=True, backend="jnp", operator="diffusion"
        )
        x = np.arange(n) * h
        Z, Y, X = np.meshgrid(x, x, x, indexing="ij")
        c0 = jnp.asarray(np.sin(X) * np.sin(Y) * np.sin(Z))
        c = c0
        for _ in range(steps):
            c = op.solve_z(op.solve_y(op.solve_x(c)))
        g = 1.0 / (1.0 + 4.0 * r * np.sin(h / 2.0) ** 2) ** 3
        np.testing.assert_allclose(c, g**steps * c0, **TOL)
