"""The fused transpose-free ADI engine (PR-3 tentpole).

Covers: row-layout pentadiagonal substitution against the dense oracle in
both backends (the Pallas one transposes lane chunks in VMEM), the fused
RHS+x-sweep kernel, the zero-transpose property of the jnp Cahn–Hilliard
step (checked on the jaxpr), streamed row-layout solves, the windowed RHS,
the alignment-padded kernel dispatch for awkward extents, and the donated
multi-step driver."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import check_jaxpr
from repro.core.adi import make_adi_operator
from repro.core.cahn_hilliard import (
    CahnHilliardADI,
    CHConfig,
    ch_evolve,
    deep_quench_ic,
)
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.penta import (
    cyclic_penta_factor,
    cyclic_penta_solve_factored,
    cyclic_penta_solve_factored_rows,
    hyperdiffusion_diagonals,
    penta_factor,
    _substitute_rows_jnp,
    _substitute_rows_pallas,
    penta_solve_factored_rows,
    tpu_sweep_problem,
)
from repro.core.stream import stream_ch_rhs_xsweep, stream_penta_solve_rows
from repro.util import tolerance_for

TOL = tolerance_for(jnp.float64)
TOL_I = tolerance_for(jnp.float64, scale=10)  # interpret-mode recurrences

CH_KW = dict(dt=1e-3, D=0.6, gamma=0.01, inv_h2=104.0, inv_h4=10900.0)


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float64)


class TestRowLayoutSubstitution:
    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_plain_matches_dense(self, backend):
        rng = np.random.default_rng(0)
        m, b = 48, 16
        l2, l1, u1, u2 = (_rand(rng, (m,)) for _ in range(4))
        d = jnp.asarray(8.0 + np.abs(rng.standard_normal(m)))
        rhs = _rand(rng, (b, m))  # row layout: each ROW one system
        fac = penta_factor(l2, l1, d, u1, u2)
        x = penta_solve_factored_rows(
            fac, rhs, backend=backend, interpret=True
        )
        ref = R.penta_solve_ref(l2, l1, d, u1, u2, rhs.T, cyclic=False).T
        np.testing.assert_allclose(x, ref, **TOL_I)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_cyclic_matches_dense(self, backend):
        rng = np.random.default_rng(1)
        m, b = 64, 32
        diags = hyperdiffusion_diagonals(m, 0.4)
        fac = cyclic_penta_factor(*diags)
        rhs = _rand(rng, (b, m))
        x = cyclic_penta_solve_factored_rows(
            fac, rhs, backend=backend, interpret=True
        )
        ref = R.penta_solve_ref(*diags, rhs.T, cyclic=True).T
        np.testing.assert_allclose(x, ref, **TOL_I)

    def test_row_and_column_layouts_agree(self):
        rng = np.random.default_rng(2)
        diags = hyperdiffusion_diagonals(96, 0.7)
        fac = cyclic_penta_factor(*diags)
        rhs = _rand(rng, (96, 40))
        col = cyclic_penta_solve_factored(fac, rhs, backend="jnp")
        row = cyclic_penta_solve_factored_rows(fac, rhs.T, backend="jnp")
        np.testing.assert_allclose(row.T, col, **TOL)

    def test_vector_rhs_squeeze(self):
        diags = hyperdiffusion_diagonals(32, 0.3)
        fac = cyclic_penta_factor(*diags)
        b = jnp.linspace(0.0, 1.0, 32)
        x_row = cyclic_penta_solve_factored_rows(fac, b)
        x_col = cyclic_penta_solve_factored(fac, b)
        assert x_row.shape == (32,)
        np.testing.assert_allclose(x_row, x_col, **TOL)

    def test_unroll_is_result_invariant(self):
        rng = np.random.default_rng(3)
        diags = hyperdiffusion_diagonals(64, 0.5)
        fac = cyclic_penta_factor(*diags)
        rhs = _rand(rng, (16, 64))
        a = cyclic_penta_solve_factored_rows(fac, rhs, backend="jnp", unroll=1)
        b = cyclic_penta_solve_factored_rows(fac, rhs, backend="jnp", unroll=4)
        np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("tb", [8, 16, 64, 128])
    @pytest.mark.parametrize("m", [128, 256, 1024])
    def test_pallas_rows_equal_jnp(self, m, tb):
        # the Pallas row sweep transposes 128-lane chunks into a VMEM
        # scratch and runs the column recurrence there: the same
        # arithmetic in the same order as the jnp lane walk
        rng = np.random.default_rng(m + tb)
        fac = penta_factor(*hyperdiffusion_diagonals(m, 0.4))
        rhs = _rand(rng, (2 * tb, m))
        out = _substitute_rows_pallas(fac, rhs, tb=tb, interpret=True)
        np.testing.assert_array_equal(out, _substitute_rows_jnp(fac, rhs))

    @pytest.mark.parametrize(
        "m, tb, problem",
        [
            (4096, 64, None),
            (256, 128, None),
            (200, 8, None),  # one chunk of all 200 lanes
            (48, 16, None),
            (12, 8, "not a multiple of 8"),
            (4096, 12, "not a multiple of 8"),
            (16384, 8, "exceed SMEM"),
        ],
    )
    def test_row_sweep_tpu_shape_rules(self, m, tb, problem):
        got = tpu_sweep_problem(m, 4 * tb, tb, np.float32, lanes=True)
        assert (got is None) if problem is None else (problem in got)

    def test_non_divisible_row_tile_errors(self):
        fac = penta_factor(*hyperdiffusion_diagonals(16, 0.2))
        with pytest.raises(ValueError):
            penta_solve_factored_rows(
                fac, jnp.zeros((30, 16)), backend="pallas", tb=16,
                interpret=True,
            )


class TestADIOperatorTransposeFree:
    def test_solve_x_matches_reference(self):
        rng = np.random.default_rng(4)
        rhs = _rand(rng, (48, 64))
        op = make_adi_operator(48, 64, 0.3, cyclic=True, backend="jnp")
        out = op.solve_x(rhs)
        diags = hyperdiffusion_diagonals(64, 0.3)
        ref = R.penta_solve_ref(*diags, rhs.T, cyclic=True).T
        np.testing.assert_allclose(out, ref, **TOL)

    def test_solve_x_jaxpr_has_no_transpose(self):
        op = make_adi_operator(32, 32, 0.3, cyclic=True, backend="jnp")
        findings = check_jaxpr(
            jax.make_jaxpr(op.solve_x)(jnp.zeros((32, 32))),
            ("no_transpose",),
        )
        assert findings == []

    def test_rectangular_domain(self):
        rng = np.random.default_rng(5)
        rhs = _rand(rng, (32, 80))
        op = make_adi_operator(32, 80, 0.2, cyclic=True, backend="jnp")
        dx = hyperdiffusion_diagonals(80, 0.2)
        dy = hyperdiffusion_diagonals(32, 0.2)
        np.testing.assert_allclose(
            op.solve_x(rhs), R.penta_solve_ref(*dx, rhs.T, cyclic=True).T,
            **TOL,
        )
        np.testing.assert_allclose(
            op.solve_y(rhs), R.penta_solve_ref(*dy, rhs, cyclic=True), **TOL
        )


class TestFusedRHSXsweep:
    def test_windowed_rhs_matches_roll_oracle(self):
        rng = np.random.default_rng(6)
        a = _rand(rng, (48, 48)) * 0.1
        b = _rand(rng, (48, 48)) * 0.1
        ref = R.ch_rhs_ref(a, b, **CH_KW)
        win = R.ch_rhs_win(a, b, **CH_KW)
        np.testing.assert_allclose(win, ref, atol=1e-13)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_xsweep_matches_composition(self, backend):
        rng = np.random.default_rng(7)
        n = 32
        a = _rand(rng, (n, n)) * 0.1
        b = _rand(rng, (n, n)) * 0.1
        fac = cyclic_penta_factor(*hyperdiffusion_diagonals(n, 0.4))
        out = ops.ch_rhs_xsweep(
            a, b, fac, **CH_KW, backend=backend, interpret=True, ty=16
        )
        ref = cyclic_penta_solve_factored_rows(
            fac, R.ch_rhs_ref(a, b, **CH_KW), backend="jnp"
        )
        np.testing.assert_allclose(out, ref, **TOL_I)

    @pytest.mark.parametrize("n", [32, 256])
    @pytest.mark.parametrize("ty", [8, 16])
    def test_pallas_xsweep_matches_jnp(self, ty, n):
        # n = 32: one transposed chunk of all 32 lanes; 256: two of 128
        rng = np.random.default_rng(ty + n)
        a = _rand(rng, (32, n)) * 0.1
        b = _rand(rng, (32, n)) * 0.1
        fac = cyclic_penta_factor(*hyperdiffusion_diagonals(n, 0.4))
        kw = dict(CH_KW, inv_h2=n * n / 10.0, inv_h4=(n * n / 10.0) ** 2)
        out = ops.ch_rhs_xsweep(
            a, b, fac, **kw, backend="pallas", interpret=True, ty=ty
        )
        ref = ops.ch_rhs_xsweep(a, b, fac, **kw, backend="jnp")
        np.testing.assert_allclose(out, ref, **TOL_I)

    def test_xsweep_tile_counts_the_transpose_scratch(self):
        # the 4096^2 cell's band: its VMEM estimate holds the (nx, ty)
        # transpose scratch, lanes padded to 128, and fits the budget,
        # so backend='auto' takes the fused kernel there with 128-row
        # bands, full vregs for the sublane recurrence
        from repro.kernels.fused_ch import (
            XSWEEP_VMEM_BUDGET,
            xsweep_tile,
            xsweep_tpu_problem,
            xsweep_vmem_bytes,
        )

        n = 4096
        ty = xsweep_tile(n, n)
        assert ty == 128
        pad = lambda v, m: -(-v // m) * m  # noqa: E731
        blocks = 2 * (2 * (ty + 16) + ty) * n
        temps = 12 * pad(ty + 4, 8) * pad(n + 4, 128)
        scratch = n * pad(ty, 128)
        assert xsweep_vmem_bytes(ty, n) == 4 * (blocks + temps + scratch)
        assert xsweep_vmem_bytes(ty, n) <= XSWEEP_VMEM_BUDGET
        assert xsweep_tpu_problem(n, n, ty, jnp.float32) is None

    def test_fused_step_has_zero_transposes(self):
        # the acceptance property: the full ADI Cahn-Hilliard step runs
        # with zero per-step transposes (both sweeps in native layout)
        s = CahnHilliardADI(
            CHConfig(nx=32, ny=32, dt=1e-3, rhs_mode="fused", backend="jnp")
        )
        c0 = deep_quench_ic(32, 32, seed=0)
        c1 = s.initial_step(c0)
        findings = check_jaxpr(
            jax.make_jaxpr(s.step)(c1, c0), ("no_transpose",)
        )
        assert findings == []

    def test_streamed_fused_step_has_zero_transposes(self):
        n = 32
        s = CahnHilliardADI(
            CHConfig(
                nx=n, ny=n, dt=1e-3, rhs_mode="fused", backend="jnp",
                streams=2, max_tile_bytes=n * n * 8 // 4,
            )
        )
        c0 = deep_quench_ic(n, n, seed=0)
        c1 = s.initial_step(c0)
        findings = check_jaxpr(
            jax.make_jaxpr(s.step)(c1, c0), ("no_transpose",)
        )
        assert findings == []

    def test_streamed_xsweep_matches_monolithic(self):
        rng = np.random.default_rng(8)
        n = 64
        a = _rand(rng, (n, n)) * 0.1
        b = _rand(rng, (n, n)) * 0.1
        fac = cyclic_penta_factor(*hyperdiffusion_diagonals(n, 0.4))
        mono = ops.ch_rhs_xsweep(a, b, fac, **CH_KW, backend="jnp")
        streamed = stream_ch_rhs_xsweep(
            a, b, fac, **CH_KW, chunk_rows=8, streams=2
        )
        np.testing.assert_allclose(streamed, mono, **TOL)


class TestStreamedRowSolve:
    def test_stream_penta_solve_rows_matches(self):
        rng = np.random.default_rng(9)
        diags = hyperdiffusion_diagonals(64, 0.5)
        rhs = _rand(rng, (96, 64))
        fac_c = cyclic_penta_factor(*diags)
        ref = cyclic_penta_solve_factored_rows(fac_c, rhs, backend="jnp")
        out = stream_penta_solve_rows(
            fac_c, rhs, cyclic=True, chunk_rows=16, streams=2
        )
        np.testing.assert_allclose(out, ref, **TOL)

        fac = penta_factor(*diags)
        ref = penta_solve_factored_rows(fac, rhs, backend="jnp")
        out = stream_penta_solve_rows(
            fac, rhs, cyclic=False, max_tile_bytes=int(rhs.nbytes) // 4
        )
        np.testing.assert_allclose(out, ref, **TOL)

    def test_adi_streamed_solve_x_transpose_free_matches(self):
        rng = np.random.default_rng(10)
        rhs = _rand(rng, (64, 64))
        mono = make_adi_operator(64, 64, 0.3, cyclic=True, backend="jnp")
        streamed = make_adi_operator(
            64, 64, 0.3, cyclic=True, backend="jnp",
            streams=2, max_tile_bytes=int(rhs.nbytes) // 4,
        )
        np.testing.assert_allclose(
            streamed.solve_x(rhs), mono.solve_x(rhs), **TOL
        )


class TestPaddedKernelDispatch:
    """pick_tile_any degradation fix: prime/odd extents pad to an aligned
    tile multiple inside the kernel wrappers instead of running one
    misaligned mega-tile (or a degenerate tile of 1)."""

    @pytest.mark.parametrize("bc", ["periodic", "np"])
    def test_2d_prime_extents(self, bc):
        rng = np.random.default_rng(11)
        data = _rand(rng, (127, 127))
        w = _rand(rng, (25,))
        init = _rand(rng, (127, 127)) if bc == "np" else None
        out = ops.stencil_apply(
            data, w, init, left=2, right=2, top=2, bottom=2, bc=bc,
            backend="pallas", interpret=True,
        )
        ref = R.stencil2d_ref(
            data, bc=bc, left=2, right=2, top=2, bottom=2, coeffs=w,
            out_init=init,
        )
        np.testing.assert_allclose(out, ref, **TOL_I)

    @pytest.mark.parametrize("bc", ["periodic", "np"])
    def test_batch1d_prime_extents(self, bc):
        rng = np.random.default_rng(12)
        data = _rand(rng, (13, 127))
        w = _rand(rng, (5,))
        init = _rand(rng, (13, 127)) if bc == "np" else None
        out = ops.stencil_apply_batch1d(
            data, w, init, left=2, right=2, bc=bc,
            backend="pallas", interpret=True,
        )
        ref = R.stencil1d_batch_ref(
            data, bc=bc, left=2, right=2, coeffs=w, out_init=init
        )
        np.testing.assert_allclose(out, ref, **TOL_I)

    def test_explicit_bad_tile_still_errors(self):
        with pytest.raises(ValueError):
            ops.stencil_apply(
                jnp.zeros((30, 30)), jnp.ones((9,)), left=1, right=1,
                top=1, bottom=1, tile=(16, 16), backend="pallas",
                interpret=True,
            )

    def test_pick_tile_padded(self):
        from repro.util import pick_tile_padded

        t, p = pick_tile_padded(128)
        assert (t, p) == (128, 128)  # clean extents untouched
        t, p = pick_tile_padded(127)
        assert p == 128 and p % t == 0 and t % 8 == 0
        t, p = pick_tile_padded(509)
        assert p >= 509 and p % t == 0 and t % 8 == 0 and t > 1
        t, p = pick_tile_padded(13)
        assert p == 16 and t == 16


class TestEvolveDriver:
    def test_ch_evolve_matches_stepwise(self):
        n = 32
        s = CahnHilliardADI(
            CHConfig(nx=n, ny=n, dt=1e-3, rhs_mode="fused", backend="jnp")
        )
        c0 = deep_quench_ic(n, n, seed=2)
        c_final, hist = ch_evolve(
            s, c0, 6, save_every=3, metrics_fn=lambda c: float(jnp.sum(c))
        )
        # reference: explicit stepping (initial step counts as step 1,
        # then n_steps scan steps — the historical run() semantics)
        cn, cm = s.initial_step(c0), c0
        for _ in range(6):
            cn, cm = s.step(cn, cm)
        np.testing.assert_allclose(c_final, cn, **TOL)
        assert len(hist) == 2

    def test_caller_buffer_survives_donation(self):
        n = 32
        s = CahnHilliardADI(
            CHConfig(nx=n, ny=n, dt=1e-3, rhs_mode="fused", backend="jnp")
        )
        c0 = deep_quench_ic(n, n, seed=3)
        total = float(jnp.sum(c0))
        ch_evolve(s, c0, 4)
        assert float(jnp.sum(c0)) == total  # c0 not invalidated

    def test_evolve_compiles_once_per_chunk(self):
        s = CahnHilliardADI(
            CHConfig(nx=32, ny=32, dt=1e-3, rhs_mode="fused", backend="jnp")
        )
        assert s.make_evolve(5) is s.make_evolve(5)
        assert s.make_evolve(5) is not s.make_evolve(7)
