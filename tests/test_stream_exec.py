"""Streamed tiled-execution subsystem: chunked executor vs the monolithic
path (allclose at fp64), chunk geometry, plan-API routing, the streamed ADI
timestep, and the shard_map multi-device chunk path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.stencil import stencil_create_1d_batch, stencil_create_2d
from repro.kernels import ops
from repro.kernels.ref import stencil1d_batch_ref, stencil2d_ref
from repro.util import tolerance_for
from repro.core.stream import (
    _effective_streams,
    choose_chunk_rows,
    n_chunks_for,
    should_stream,
    slab_bytes,
    stream_batch1d_apply,
    stream_ch_rhs,
    stream_penta_solve,
    stream_stencil_apply,
    stream_stencil_apply_dist,
)

TOL = tolerance_for(jnp.float64)  # shared fp64 equivalence tolerance


def _rand(rng, shape, dtype=jnp.float64):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# -- the executor vs the monolithic path -------------------------------------


class TestStreamedMatchesMonolithic:
    @pytest.mark.parametrize("bc", ["periodic", "np"])
    @pytest.mark.parametrize("chunk_rows", [8, 16])
    def test_xy_weighted(self, bc, chunk_rows):
        # 64 rows / 8-or-16-row chunks = 8 or 4 chunks: the domain is at
        # least 4x one chunk, the acceptance geometry.
        rng = np.random.default_rng(0)
        data = _rand(rng, (64, 48))
        w = _rand(rng, (25,))
        init = _rand(rng, (64, 48)) if bc == "np" else None
        ref = ops.stencil_apply(
            data, w, init, left=2, right=2, top=2, bottom=2, bc=bc,
            backend="jnp",
        )
        out = stream_stencil_apply(
            data, w, init, left=2, right=2, top=2, bottom=2, bc=bc,
            chunk_rows=chunk_rows, streams=2,
        )
        np.testing.assert_allclose(out, ref, **TOL)

    @pytest.mark.parametrize("bc", ["periodic", "np"])
    def test_asymmetric_extents(self, bc):
        rng = np.random.default_rng(1)
        data = _rand(rng, (48, 40))
        w = _rand(rng, (4 * 2,))  # (top+bottom+1) * (left+right+1) = 4*2
        init = _rand(rng, (48, 40)) if bc == "np" else None
        kw = dict(left=1, right=0, top=2, bottom=1, bc=bc)
        ref = stencil2d_ref(data, coeffs=w, out_init=init, **kw)
        out = stream_stencil_apply(
            data, w, init, chunk_rows=6, streams=3, **kw
        )
        np.testing.assert_allclose(out, ref, **TOL)

    def test_function_pointer_mode(self):
        # the paper's Fun variant streams too: nonlinearity inside the sweep
        def cube_fn(windows, coeffs):
            out = None
            for w, c in zip(windows, coeffs, strict=True):
                term = c * (w * w * w - w)
                out = term if out is None else out + term
            return out

        rng = np.random.default_rng(2)
        data = _rand(rng, (32, 32))
        coeffs = _rand(rng, (9,))
        kw = dict(left=1, right=1, top=1, bottom=1, bc="periodic")
        ref = stencil2d_ref(data, point_fn=cube_fn, coeffs=coeffs, **kw)
        out = stream_stencil_apply(
            data, coeffs, point_fn=cube_fn, chunk_rows=4, streams=4, **kw
        )
        np.testing.assert_allclose(out, ref, **TOL)

    def test_single_row_chunks(self):
        rng = np.random.default_rng(3)
        data = _rand(rng, (16, 24))
        w = _rand(rng, (9,))
        kw = dict(left=1, right=1, top=1, bottom=1, bc="periodic")
        ref = stencil2d_ref(data, coeffs=w, **kw)
        out = stream_stencil_apply(data, w, chunk_rows=1, **kw)
        np.testing.assert_allclose(out, ref, **TOL)

    def test_pallas_slab_compute(self):
        # each chunk through stencil2d_pallas (interpret on CPU)
        rng = np.random.default_rng(4)
        data = _rand(rng, (64, 48))
        w = _rand(rng, (25,))
        kw = dict(left=2, right=2, top=2, bottom=2, bc="periodic")
        ref = stencil2d_ref(data, coeffs=w, **kw)
        out = stream_stencil_apply(
            data, w, chunk_rows=16, streams=2, compute="pallas",
            interpret=True, **kw,
        )
        np.testing.assert_allclose(out, ref, **TOL)

    def test_np_boundary_passthrough(self):
        # global-boundary cells come from out_init even when they sit in
        # interior *chunks* (chunk edges are not domain edges)
        rng = np.random.default_rng(5)
        data = _rand(rng, (32, 32))
        init = _rand(rng, (32, 32))
        w = _rand(rng, (25,))
        out = stream_stencil_apply(
            data, w, init, left=2, right=2, top=2, bottom=2, bc="np",
            chunk_rows=4,
        )
        np.testing.assert_array_equal(out[:2, :], init[:2, :])
        np.testing.assert_array_equal(out[-2:, :], init[-2:, :])
        np.testing.assert_array_equal(out[:, :2], init[:, :2])
        np.testing.assert_array_equal(out[:, -2:], init[:, -2:])

    def test_batch1d(self):
        rng = np.random.default_rng(6)
        data = _rand(rng, (64, 40))
        w = _rand(rng, (5,))
        for bc in ("periodic", "np"):
            init = _rand(rng, (64, 40)) if bc == "np" else None
            ref = stencil1d_batch_ref(
                data, bc=bc, left=2, right=2, coeffs=w, out_init=init
            )
            out = stream_batch1d_apply(
                data, w, init, left=2, right=2, bc=bc, chunk_rows=8,
                streams=2,
            )
            np.testing.assert_allclose(out, ref, **TOL)

    def test_validation(self):
        data = jnp.zeros((16, 16))
        w = jnp.ones((9,))
        with pytest.raises(ValueError):
            stream_stencil_apply(data, w, chunk_rows=5,
                                 left=1, right=1, top=1, bottom=1)
        with pytest.raises(ValueError):
            stream_stencil_apply(data, w, bc="reflect")
        with pytest.raises(ValueError):
            stream_stencil_apply(data, w, compute="cuda")


# -- chunk geometry ----------------------------------------------------------


class TestChunkGeometry:
    def test_budget_drives_chunks(self):
        # a budget of 1/4 the field must give >= 4 chunks
        ny, nx, itemsize = 512, 512, 8
        budget = ny * nx * itemsize // 4
        rows = choose_chunk_rows(
            ny, nx, itemsize, top=2, bottom=2, left=2, right=2,
            max_tile_bytes=budget,
        )
        assert ny % rows == 0
        assert slab_bytes(rows, nx, itemsize, top=2, bottom=2,
                          left=2, right=2) <= budget
        assert ny // rows >= 4

    def test_streams_alignment_preferred(self):
        rows = choose_chunk_rows(
            60, 64, 8, max_tile_bytes=60 * 64 * 8 // 3, streams=4
        )
        assert (60 // rows) % 4 == 0

    def test_tiny_budget_falls_back_to_single_rows(self):
        assert choose_chunk_rows(64, 1 << 20, 8, max_tile_bytes=64) == 1

    def test_no_budget_means_one_chunk(self):
        assert choose_chunk_rows(64, 64, 8) == 64
        assert n_chunks_for(64, 64, 8) == 1

    def test_effective_streams(self):
        assert _effective_streams(None, 8) == 1
        assert _effective_streams(1, 8) == 1
        assert _effective_streams(2, 8) == 2
        assert _effective_streams(3, 8) == 1  # gcd fallback, no ragged tail
        assert _effective_streams(16, 8) == 8

    def test_should_stream(self):
        assert not should_stream((64, 64), 8, streams=None, max_tile_bytes=None)
        assert not should_stream((64, 64), 8, streams=1, max_tile_bytes=None)
        assert should_stream((64, 64), 8, streams=2, max_tile_bytes=None)
        assert should_stream(
            (64, 64), 8, streams=None, max_tile_bytes=64 * 64 * 8 // 2
        )
        assert not should_stream(
            (64, 64), 8, streams=None, max_tile_bytes=64 * 64 * 8 + 1
        )


# -- plan-API routing --------------------------------------------------------


class TestPlanRouting:
    def test_2d_plan_streams_when_oversized(self):
        rng = np.random.default_rng(7)
        data = _rand(rng, (64, 48))
        w = _rand(rng, (5, 5))
        mono = stencil_create_2d("xy", "periodic", weights=w, backend="jnp")
        streamed = stencil_create_2d(
            "xy", "periodic", weights=w, backend="jnp",
            streams=2, max_tile_bytes=int(data.nbytes) // 4,
        )
        np.testing.assert_allclose(
            streamed.apply(data), mono.apply(data), **TOL
        )

    def test_2d_plan_declines_when_it_fits(self):
        # within budget + single stream: the monolithic path is kept
        rng = np.random.default_rng(8)
        data = _rand(rng, (32, 32))
        w = _rand(rng, (5, 5))
        plan = stencil_create_2d(
            "xy", "periodic", weights=w, backend="jnp",
            streams=1, max_tile_bytes=int(data.nbytes) + 1,
        )
        mono = stencil_create_2d("xy", "periodic", weights=w, backend="jnp")
        np.testing.assert_allclose(plan.apply(data), mono.apply(data), **TOL)

    def test_resolve_compute_mirrors_monolithic_dispatch(self):
        from repro.kernels import ops
        from repro.core.stream import resolve_compute

        assert resolve_compute("pallas") == "pallas"
        assert resolve_compute("jnp") == "jnp"
        # auto follows on_tpu(), exactly like ops.stencil_apply's auto path
        expected = "pallas" if ops.on_tpu() else "jnp"
        assert resolve_compute("auto") == expected

    def test_batch1d_streamed_pallas_compute(self):
        # a backend='pallas' batch1d plan keeps the kernel when streamed
        rng = np.random.default_rng(15)
        data = _rand(rng, (32, 48))
        w = _rand(rng, (5,))
        ref = stencil1d_batch_ref(data, bc="periodic", left=2, right=2, coeffs=w)
        out = stream_batch1d_apply(
            data, w, left=2, right=2, bc="periodic", chunk_rows=8,
            streams=2, compute="pallas", interpret=True,
        )
        np.testing.assert_allclose(out, ref, **TOL)

    def test_batch1d_plan_streams(self):
        rng = np.random.default_rng(9)
        data = _rand(rng, (64, 32))
        w = jnp.asarray([1.0, -2.0, 1.0])
        plan = stencil_create_1d_batch(
            "np", weights=w, backend="jnp", streams=4
        )
        ref = stencil1d_batch_ref(data, bc="np", left=1, right=1, coeffs=w)
        np.testing.assert_allclose(plan.apply(data), ref, **TOL)


# -- streamed implicit half + full ADI timestep ------------------------------


class TestStreamedADI:
    def test_penta_solve_streamed(self):
        from repro.kernels.penta import (
            cyclic_penta_factor,
            cyclic_penta_solve_factored,
            hyperdiffusion_diagonals,
            penta_factor,
            penta_solve_factored,
        )

        rng = np.random.default_rng(10)
        diags = hyperdiffusion_diagonals(96, 0.4)
        rhs = _rand(rng, (96, 64))
        fac_c = cyclic_penta_factor(*diags)
        ref = cyclic_penta_solve_factored(fac_c, rhs, backend="jnp")
        out = stream_penta_solve(
            fac_c, rhs, cyclic=True, chunk_cols=16, streams=2
        )
        np.testing.assert_allclose(out, ref, **TOL)

        fac = penta_factor(*diags)
        ref = penta_solve_factored(fac, rhs, backend="jnp")
        out = stream_penta_solve(
            fac, rhs, cyclic=False, max_tile_bytes=int(rhs.nbytes) // 4
        )
        np.testing.assert_allclose(out, ref, **TOL)

    def test_adi_operator_streams(self):
        from repro.core.adi import make_adi_operator

        rng = np.random.default_rng(11)
        rhs = _rand(rng, (64, 64))
        mono = make_adi_operator(64, 64, 0.3, cyclic=True, backend="jnp")
        streamed = make_adi_operator(
            64, 64, 0.3, cyclic=True, backend="jnp",
            streams=2, max_tile_bytes=int(rhs.nbytes) // 4,
        )
        np.testing.assert_allclose(
            streamed.solve_x(rhs), mono.solve_x(rhs), **TOL
        )
        np.testing.assert_allclose(
            streamed.solve_y(rhs), mono.solve_y(rhs), **TOL
        )

    @pytest.mark.parametrize("mode", ["fused", "stencil", "batch1d"])
    def test_full_ch_timestep_streamed(self, mode):
        # the acceptance case: a full ADI Cahn-Hilliard timestep on a
        # domain 4x larger than one chunk, streamed vs monolithic
        from repro.core.cahn_hilliard import (
            CahnHilliardADI,
            CHConfig,
            deep_quench_ic,
        )

        n = 64
        budget = n * n * 8 // 4  # one chunk = 1/4 of the field
        cfg0 = CHConfig(nx=n, ny=n, dt=1e-3, backend="jnp", rhs_mode=mode)
        cfgS = CHConfig(
            nx=n, ny=n, dt=1e-3, backend="jnp", rhs_mode=mode,
            streams=2, max_tile_bytes=budget,
        )
        assert n_chunks_for(n, n, 8, halos=(2, 2, 2, 2),
                            max_tile_bytes=budget) >= 4
        c0 = deep_quench_ic(n, n, seed=3)
        s0, sS = CahnHilliardADI(cfg0), CahnHilliardADI(cfgS)
        state0, stateS = (s0.initial_step(c0), c0), (sS.initial_step(c0), c0)
        np.testing.assert_allclose(state0[0], stateS[0], **TOL)
        for _ in range(3):
            state0 = s0.step(*state0)
            stateS = sS.step(*stateS)
        np.testing.assert_allclose(state0[0], stateS[0], **TOL)

    def test_stream_ch_rhs_matches_ref(self):
        from repro.kernels.ref import ch_rhs_ref

        rng = np.random.default_rng(12)
        a, b = _rand(rng, (64, 64)), _rand(rng, (64, 64))
        kw = dict(dt=1e-3, D=0.6, gamma=0.01, inv_h2=4.1, inv_h4=16.81)
        ref = ch_rhs_ref(a, b, **kw)
        out = stream_ch_rhs(a, b, chunk_rows=8, streams=4, **kw)
        np.testing.assert_allclose(out, ref, **TOL)


# -- multi-device chunk path (shard_map over the dist mesh) ------------------


class TestStreamedDist:
    def _dd(self):
        from jax.sharding import Mesh

        from repro.core.domain import DomainDecomposition

        dev = np.array(jax.devices()[:1]).reshape(1, 1)
        return DomainDecomposition(mesh=Mesh(dev, ("data", "model")))

    @pytest.mark.parametrize("bc", ["periodic", "np"])
    def test_matches_monolithic(self, bc):
        rng = np.random.default_rng(13)
        data = _rand(rng, (64, 48))
        w = _rand(rng, (5, 5))
        init = _rand(rng, (64, 48)) if bc == "np" else None
        plan = stencil_create_2d("xy", bc, weights=w, backend="jnp")
        ref = stencil2d_ref(
            data, bc=bc, left=2, right=2, top=2, bottom=2,
            coeffs=w.ravel(), out_init=init,
        )
        out = stream_stencil_apply_dist(
            plan, data, self._dd(), init, chunk_rows=8
        )
        np.testing.assert_allclose(out, ref, **TOL)

    def test_via_distributed_solver(self):
        from repro.core.cahn_hilliard import CHConfig
        from repro.core.dist_ch import DistributedCahnHilliard

        rng = np.random.default_rng(14)
        data = _rand(rng, (32, 32))
        w = _rand(rng, (5, 5))
        cfg = CHConfig(nx=32, ny=32, backend="jnp")
        solver = DistributedCahnHilliard(cfg, self._dd())
        plan = stencil_create_2d("xy", "periodic", weights=w, backend="jnp")
        ref = stencil2d_ref(
            data, bc="periodic", left=2, right=2, top=2, bottom=2,
            coeffs=w.ravel(),
        )
        out = solver.streamed_apply(plan, data, chunk_rows=8)
        np.testing.assert_allclose(out, ref, **TOL)
