"""Cyclic pentadiagonal Pallas backend in interpret mode.

The ADI hot path on TPU is the in-kernel ``fori_loop`` substitution of
``penta.py``; CPU CI must exercise that kernel (``backend='pallas',
interpret=True``), not just the jnp scan fallback.  These tests force the
Pallas path end-to-end: raw substitution, the Woodbury cyclic closure, the
factored ADI operator pair, and the streamed column-chunk solve."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adi import make_adi_operator
from repro.kernels import ref as R
from repro.kernels.penta import (
    cyclic_penta_factor,
    cyclic_penta_solve_factored,
    hyperdiffusion_diagonals,
    penta_factor,
    penta_solve_factored,
)
from repro.kernels.ops import penta_solve
from repro.core.stream import stream_penta_solve
from repro.util import tolerance_for

# shared helpers: base fp64 tolerance, scaled for the longer rounding
# chains of interpret-mode recurrences / random (non-SPD) bands
TOL = tolerance_for(jnp.float64, scale=10)
TOL_RAND = tolerance_for(jnp.float64, scale=1000)


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float64)


class TestCyclicPallasInterpret:
    @pytest.mark.parametrize("m,n", [(16, 8), (64, 32), (100, 16)])
    def test_cyclic_matches_dense(self, m, n):
        rng = np.random.default_rng(m * 100 + n)
        l2, l1, u1, u2 = (_rand(rng, (m,)) for _ in range(4))
        d = jnp.asarray(8.0 + np.abs(rng.standard_normal(m)))
        rhs = _rand(rng, (m, n))
        fac = cyclic_penta_factor(l2, l1, d, u1, u2)
        x = cyclic_penta_solve_factored(
            fac, rhs, backend="pallas", interpret=True
        )
        x_ref = R.penta_solve_ref(l2, l1, d, u1, u2, rhs, cyclic=True)
        np.testing.assert_allclose(x, x_ref, **TOL_RAND)

    def test_cyclic_vector_rhs(self):
        m = 64
        diags = hyperdiffusion_diagonals(m, 0.7)
        fac = cyclic_penta_factor(*diags)
        rng = np.random.default_rng(0)
        b = _rand(rng, (m,))
        x = cyclic_penta_solve_factored(
            fac, b, backend="pallas", interpret=True
        )
        assert x.shape == (m,)
        A = R.penta_dense_cyclic(*diags)
        np.testing.assert_allclose(A @ x, b, **tolerance_for(jnp.float64, scale=100))

    def test_hyperdiffusion_roundtrip_pallas(self):
        # the exact ADI operator: A x == b after a pallas-interpret solve
        m = 128
        diags = hyperdiffusion_diagonals(m, 0.4)
        fac = cyclic_penta_factor(*diags)
        rng = np.random.default_rng(1)
        x = _rand(rng, (m, 8))
        b = R.penta_dense_cyclic(*diags) @ x
        out = cyclic_penta_solve_factored(
            fac, b, backend="pallas", interpret=True
        )
        np.testing.assert_allclose(out, x, **tolerance_for(jnp.float64, scale=100))

    def test_one_shot_wrapper_pallas(self):
        m = 32
        rng = np.random.default_rng(2)
        l2, l1, u1, u2 = (_rand(rng, (m,)) for _ in range(4))
        d = jnp.asarray(9.0 + np.abs(rng.standard_normal(m)))
        rhs = _rand(rng, (m, 16))
        out = penta_solve(
            l2, l1, d, u1, u2, rhs, cyclic=True,
            backend="pallas", interpret=True,
        )
        ref = R.penta_solve_ref(l2, l1, d, u1, u2, rhs, cyclic=True)
        np.testing.assert_allclose(out, ref, **TOL_RAND)

    def test_non_divisible_batch_tile_errors(self):
        m = 16
        diags = hyperdiffusion_diagonals(m, 0.2)
        fac = penta_factor(*diags)
        rhs = jnp.zeros((m, 30))
        with pytest.raises(ValueError):
            penta_solve_factored(
                fac, rhs, backend="pallas", tn=16, interpret=True
            )

    def test_adi_operator_pallas_backend(self):
        # ADIOperator(backend='pallas') on CPU routes through the interpret
        # kernel automatically (interpret=None -> not on_tpu) — both sweeps
        rng = np.random.default_rng(3)
        rhs = _rand(rng, (64, 64))
        op_p = make_adi_operator(64, 64, 0.3, cyclic=True, backend="pallas")
        op_j = make_adi_operator(64, 64, 0.3, cyclic=True, backend="jnp")
        np.testing.assert_allclose(op_p.solve_x(rhs), op_j.solve_x(rhs), **TOL)
        np.testing.assert_allclose(op_p.solve_y(rhs), op_j.solve_y(rhs), **TOL)

    def test_streamed_chunks_through_pallas(self):
        # the streamed executor forwards backend='pallas' to every chunk
        rng = np.random.default_rng(4)
        diags = hyperdiffusion_diagonals(64, 0.5)
        fac = cyclic_penta_factor(*diags)
        rhs = _rand(rng, (64, 64))
        ref = cyclic_penta_solve_factored(fac, rhs, backend="jnp")
        out = stream_penta_solve(
            fac, rhs, cyclic=True, chunk_cols=16, streams=2,
            backend="pallas", interpret=True,
        )
        np.testing.assert_allclose(out, ref, **TOL)
