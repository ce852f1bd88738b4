"""3D stencils (paper §VI.A future work, implemented): the raw Pallas
kernel vs the oracle, the dispatcher's alignment-padded path on prime/odd
extents, the :class:`Stencil3D` plan API on the dimension-agnostic core,
and z-slab streamed execution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: deterministic sweep fallback
    from _hypothesis_fallback import given, settings, strategies as st

import repro
from repro import obs
from repro.core.stencil import (
    PlanCore,
    Stencil3D,
    laplacian3d_weights,
    stencil_compute_3d,
    stencil_create_3d,
    stencil_destroy_3d,
)
from repro.kernels import ops
from repro.kernels.ref import stencil3d_ref
from repro.kernels.stencil3d import stencil3d_pallas
from repro.core.stream import stream_stencil3d_apply
from repro.util import tolerance_for


class TestStencil3D:
    @settings(max_examples=10, deadline=None)
    @given(
        halos=st.tuples(*([st.integers(0, 2)] * 6)),
        bc=st.sampled_from(["periodic", "np"]),
        dtype=st.sampled_from([jnp.float32, jnp.float64]),
        seed=st.integers(0, 2**16),
    )
    def test_matches_ref(self, halos, bc, dtype, seed):
        if sum(halos) == 0:
            halos = (1,) + halos[1:]
        rng = np.random.default_rng(seed)
        data = jnp.asarray(rng.standard_normal((8, 16, 24)), dtype)
        n = (halos[0] + halos[1] + 1) * (halos[2] + halos[3] + 1) * (
            halos[4] + halos[5] + 1
        )
        w = jnp.asarray(rng.standard_normal(n), dtype)
        init = jnp.asarray(rng.standard_normal(data.shape), dtype) if bc == "np" else None
        kern = stencil3d_pallas(
            data, w, init, halos=halos, bc=bc, tz=4, ty=8, interpret=True
        )
        ref = stencil3d_ref(
            data, bc=bc, halos=halos, coeffs=w, out_init=init
        )
        np.testing.assert_allclose(kern, ref, **tolerance_for(dtype))

    def test_laplacian3d_exact_on_trig(self):
        n = 32
        x = np.linspace(0, 2 * np.pi, n, endpoint=False)
        Z, Y, X = np.meshgrid(x, x, x, indexing="ij")
        data = jnp.asarray(np.sin(X) * np.sin(Y) * np.sin(Z))
        h = 2 * np.pi / n
        # 7-point Laplacian as a 3x3x3 box with zeros off-axes
        w = np.zeros((3, 3, 3))
        w[1, 1, 0] = w[1, 1, 2] = w[1, 0, 1] = w[1, 2, 1] = 1.0
        w[0, 1, 1] = w[2, 1, 1] = 1.0
        w[1, 1, 1] = -6.0
        out = stencil3d_pallas(
            data, jnp.asarray(w.ravel() / h**2),
            halos=(1, 1, 1, 1, 1, 1), bc="periodic", tz=4, ty=8,
            interpret=True,
        )
        np.testing.assert_allclose(out, -3.0 * data, atol=0.15)

    def test_function_mode_3d(self):
        rng = np.random.default_rng(0)
        data = jnp.asarray(rng.standard_normal((8, 8, 16)))

        def fn(windows, coe):
            return sum(c * w * w for c, w in zip(coe, windows, strict=True))

        coe = jnp.asarray(rng.standard_normal(27))
        kern = stencil3d_pallas(
            data, coe, point_fn=fn, halos=(1, 1, 1, 1, 1, 1),
            bc="periodic", tz=4, ty=4, interpret=True,
        )
        ref = stencil3d_ref(
            data, bc="periodic", halos=(1, 1, 1, 1, 1, 1),
            point_fn=fn, coeffs=coe,
        )
        np.testing.assert_allclose(kern, ref, rtol=1e-10, atol=1e-10)


class TestDispatcher3D:
    """:func:`ops.stencil_apply_3d` — backend dispatch incl. the
    alignment-padded path for awkward (prime/odd) extents."""

    @pytest.mark.parametrize("bc", ["periodic", "np"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
    def test_prime_extents_padded_path(self, bc, dtype):
        rng = np.random.default_rng(5)
        data = jnp.asarray(rng.standard_normal((17, 19, 23)), dtype)
        w = jnp.asarray(rng.standard_normal(27), dtype)
        init = (
            jnp.asarray(rng.standard_normal(data.shape), dtype)
            if bc == "np"
            else None
        )
        out = ops.stencil_apply_3d(
            data, w, init, halos=(1, 1, 1, 1, 1, 1), bc=bc,
            backend="pallas", interpret=True,
        )
        ref = stencil3d_ref(
            data, bc=bc, halos=(1, 1, 1, 1, 1, 1), coeffs=w, out_init=init
        )
        np.testing.assert_allclose(
            out, ref, **tolerance_for(dtype, scale=10)
        )

    def test_asymmetric_halos_padded_path(self):
        rng = np.random.default_rng(6)
        data = jnp.asarray(rng.standard_normal((9, 11, 13)))
        halos = (2, 0, 1, 2, 0, 1)
        w = jnp.asarray(rng.standard_normal(3 * 4 * 2))
        out = ops.stencil_apply_3d(
            data, w, halos=halos, bc="periodic", backend="pallas",
            interpret=True,
        )
        ref = stencil3d_ref(data, bc="periodic", halos=halos, coeffs=w)
        np.testing.assert_allclose(
            out, ref, **tolerance_for(jnp.float64, scale=10)
        )

    def test_explicit_bad_tile_still_errors(self):
        with pytest.raises(ValueError):
            ops.stencil_apply_3d(
                jnp.zeros((6, 6, 8)), jnp.ones((27,)),
                halos=(1, 1, 1, 1, 1, 1), tile=(4, 4), backend="pallas",
                interpret=True,
            )

    def test_jnp_backend_off_tpu_auto(self):
        data = jnp.ones((4, 4, 8))
        w = jnp.asarray(laplacian3d_weights()).ravel()
        out = ops.stencil_apply_3d(
            data, w, halos=(1, 1, 1, 1, 1, 1), bc="periodic", backend="auto"
        )
        np.testing.assert_allclose(out, jnp.zeros_like(data), atol=1e-12)


def _single_tap_weights():
    w = np.zeros((3, 3, 3))
    w[0, 2, 1] = 0.75
    return w


# (weights box, halo, zero taps dropped)
TAP_SETS = {
    "biharmonic": (repro.get_operator("biharmonic").weights(3), 2, 100),
    "laplacian7": (laplacian3d_weights(), 1, 20),
    "single": (_single_tap_weights(), 1, 26),
}


def _sparse_counts():
    c = obs.counters()
    return c["stencil3d.sparse_applies"], c["stencil3d.taps_skipped"]


class TestTapSet3D:
    """Weighted applies whose weights are known while the program is
    traced build only their non-zero taps: the same bits as every window,
    and function-mode or traced weights keep every window."""

    @pytest.mark.parametrize("bc", ["periodic", "np"])
    @pytest.mark.parametrize("name", sorted(TAP_SETS))
    def test_tap_set_bit_matches_all_taps(self, name, bc):
        box, h, _ = TAP_SETS[name]
        halos = (h,) * 6
        rng = np.random.default_rng(13)
        data = jnp.asarray(rng.standard_normal((16, 16, 128)), jnp.float32)
        w = jnp.asarray(np.asarray(box).ravel(), jnp.float32)
        init = (
            jnp.asarray(rng.standard_normal(data.shape), jnp.float32)
            if bc == "np"
            else None
        )
        sparse = ops.stencil_apply_3d(
            data, w, init, halos=halos, bc=bc, tile=(8, 8),
            backend="pallas", interpret=True,
        )
        every = stencil3d_pallas(
            data, w, init, halos=halos, bc=bc, tz=8, ty=8, taps=None,
            interpret=True,
        )
        np.testing.assert_array_equal(sparse, every)
        ref = stencil3d_ref(data, bc=bc, halos=halos, coeffs=w, out_init=init)
        np.testing.assert_allclose(
            sparse, ref, **tolerance_for(jnp.float32, scale=10)
        )

    @pytest.mark.parametrize("name", sorted(TAP_SETS))
    def test_counts_dropped_taps(self, name):
        box, h, skipped = TAP_SETS[name]
        jax.clear_caches()  # count a fresh trace of the kernel
        before = _sparse_counts()
        ops.stencil_apply_3d(
            jnp.ones((16, 16, 128), jnp.float32),
            jnp.asarray(np.asarray(box).ravel(), jnp.float32),
            halos=(h,) * 6, bc="periodic", tile=(8, 8), backend="pallas",
            interpret=True,
        )
        after = _sparse_counts()
        assert (after[0] - before[0], after[1] - before[1]) == (1, skipped)

    def test_function_mode_keeps_every_window(self):
        rng = np.random.default_rng(14)
        data = jnp.asarray(rng.standard_normal((8, 8, 128)), jnp.float32)

        def fn(windows, coe):
            return sum(c * w * w for c, w in zip(coe, windows, strict=True))

        # zero coefficients a weighted apply would drop: fn still gets all
        coe = jnp.asarray(laplacian3d_weights().ravel(), jnp.float32)
        jax.clear_caches()
        before = _sparse_counts()
        plan = repro.create(
            fn, data.shape, mode="xyz", bc="periodic", coeffs=coe,
            extents=dict.fromkeys(
                ("left", "right", "top", "bottom", "front", "back"), 1
            ),
            backend="pallas", interpret=True,
        )
        out = plan.apply(data)
        assert _sparse_counts() == before
        ref = stencil3d_ref(
            data, bc="periodic", halos=(1,) * 6, point_fn=fn, coeffs=coe
        )
        np.testing.assert_allclose(
            out, ref, **tolerance_for(jnp.float32, scale=10)
        )

    def test_traced_weights_keep_every_window(self):
        """A plan passed into ``jit`` has traced weights: every window, and
        new weight values, even with other zeros, reuse the trace."""
        traces = []

        @jax.jit
        def f(p, x):
            traces.append(1)
            return repro.compute(p, x)

        rng = np.random.default_rng(15)
        data = jnp.asarray(rng.standard_normal((8, 8, 128)), jnp.float32)
        mk = lambda w: repro.create(  # noqa: E731
            w, data.shape, bc="periodic", dtype=jnp.float32,
            backend="pallas", interpret=True,
        )
        jax.clear_caches()
        before = _sparse_counts()
        for box in (laplacian3d_weights(), _single_tap_weights()):
            out = f(mk(box), data)
            ref = stencil3d_ref(
                data, bc="periodic", halos=(1,) * 6,
                coeffs=jnp.asarray(box.ravel(), jnp.float32),
            )
            np.testing.assert_allclose(
                out, ref, **tolerance_for(jnp.float32, scale=10)
            )
        assert len(traces) == 1, "weight-value change must not retrace"
        assert _sparse_counts() == before


class TestPlanAPI3D:
    """Stencil3D / stencil_create_3d / stencil_compute_3d on the shared
    plan core."""

    def test_shares_the_plan_core(self):
        plan = stencil_create_3d(
            "xyz", "periodic", weights=laplacian3d_weights()
        )
        assert isinstance(plan, Stencil3D) and isinstance(plan, PlanCore)
        assert plan.halos == (1, 1, 1, 1, 1, 1)
        assert plan.num_sten == 27

    def test_weighted_xyz_matches_ref(self):
        rng = np.random.default_rng(7)
        data = jnp.asarray(rng.standard_normal((8, 12, 16)))
        w = rng.standard_normal((3, 5, 3))
        plan = stencil_create_3d("xyz", "np", weights=w, backend="jnp")
        assert plan.halos == (1, 1, 2, 2, 1, 1)
        ref = stencil3d_ref(
            data, bc="np", halos=plan.halos, coeffs=jnp.asarray(w).ravel()
        )
        np.testing.assert_allclose(plan.apply(data), ref, atol=1e-12)
        np.testing.assert_array_equal(
            plan.apply(data), stencil_compute_3d(plan, data)
        )
        stencil_destroy_3d(plan)

    @pytest.mark.parametrize(
        "direction,halos",
        [
            ("x", (0, 0, 0, 0, 2, 2)),
            ("y", (0, 0, 2, 2, 0, 0)),
            ("z", (2, 2, 0, 0, 0, 0)),
        ],
    )
    def test_directional_1d_weights(self, direction, halos):
        rng = np.random.default_rng(8)
        data = jnp.asarray(rng.standard_normal((8, 8, 8)))
        w = rng.standard_normal(5)
        plan = stencil_create_3d(
            direction, "periodic", weights=w, backend="jnp"
        )
        assert plan.halos == halos
        ref = stencil3d_ref(
            data, bc="periodic", halos=halos, coeffs=jnp.asarray(w)
        )
        np.testing.assert_allclose(plan.apply(data), ref, atol=1e-12)

    def test_function_mode_through_plan(self):
        rng = np.random.default_rng(9)
        data = jnp.asarray(rng.standard_normal((4, 8, 8)))

        def fn(windows, coe):
            return coe[0] * (windows[0] - 2.0 * windows[1] + windows[2])

        plan = stencil_create_3d(
            "z", "periodic", func=fn, coeffs=jnp.asarray([2.0]),
            num_sten_front=1, num_sten_back=1, backend="jnp",
        )
        ref = stencil3d_ref(
            data, bc="periodic", halos=(1, 1, 0, 0, 0, 0),
            point_fn=fn, coeffs=jnp.asarray([2.0]),
        )
        np.testing.assert_allclose(plan.apply(data), ref, atol=1e-12)

    def test_validation_errors(self):
        with pytest.raises(ValueError):
            stencil_create_3d("w", "periodic", weights=np.ones(3))
        with pytest.raises(ValueError):
            stencil_create_3d("xyz", "nope", weights=np.ones((3, 3, 3)))
        with pytest.raises(ValueError):
            stencil_create_3d("xyz", "periodic", weights=np.ones(3))
        with pytest.raises(ValueError):
            stencil_create_3d("x", "periodic", weights=np.ones((3, 3, 3)))
        with pytest.raises(ValueError):
            stencil_create_3d("x", "periodic")  # neither weights nor func
        with pytest.raises(ValueError):
            stencil_create_3d(
                "z", "periodic", func=lambda w, c: w[0], num_sten_left=1
            )  # off-axis extent

    def test_tuned_plan_bit_matches_untuned(self, tmp_path, monkeypatch):
        # off-TPU the candidate list collapses to the single default
        # config: tuned plans are identical by construction
        from repro import tune as T

        monkeypatch.setenv(T.ENV_VAR, str(tmp_path / "cache"))
        rng = np.random.default_rng(10)
        data = jnp.asarray(rng.standard_normal((8, 8, 16)))
        w = laplacian3d_weights()
        p0 = stencil_create_3d("xyz", "periodic", weights=w, backend="jnp")
        p1 = stencil_create_3d(
            "xyz", "periodic", weights=w, backend="jnp",
            tune="cached", shape=(8, 8, 16),
        )
        np.testing.assert_array_equal(p0.apply(data), p1.apply(data))
        with pytest.raises(ValueError):
            stencil_create_3d(
                "xyz", "periodic", weights=w, tune="cached"
            )  # tune needs shape


class TestStreamed3D:
    """z-slab chunked execution (cuSten row streaming one axis up)."""

    @pytest.mark.parametrize("bc", ["periodic", "np"])
    def test_matches_monolithic(self, bc):
        rng = np.random.default_rng(11)
        data = jnp.asarray(rng.standard_normal((8, 12, 16)))
        w = jnp.asarray(rng.standard_normal(27))
        init = (
            jnp.asarray(rng.standard_normal(data.shape))
            if bc == "np"
            else None
        )
        mono = ops.stencil_apply_3d(
            data, w, init, halos=(1, 1, 1, 1, 1, 1), bc=bc, backend="jnp"
        )
        streamed = stream_stencil3d_apply(
            data, w, init, halos=(1, 1, 1, 1, 1, 1), bc=bc,
            chunk_slabs=2, streams=2,
        )
        np.testing.assert_allclose(
            streamed, mono, **tolerance_for(jnp.float64)
        )

    def test_plan_routes_through_streaming(self):
        rng = np.random.default_rng(12)
        data = jnp.asarray(rng.standard_normal((8, 12, 16)))
        w = laplacian3d_weights()
        mono = stencil_create_3d("xyz", "periodic", weights=w, backend="jnp")
        streamed = stencil_create_3d(
            "xyz", "periodic", weights=w, backend="jnp",
            streams=2, max_tile_bytes=int(data.nbytes) // 4,
        )
        np.testing.assert_allclose(
            streamed.apply(data), mono.apply(data),
            **tolerance_for(jnp.float64),
        )

    def test_bad_chunk_slabs_errors(self):
        with pytest.raises(ValueError):
            stream_stencil3d_apply(
                jnp.zeros((8, 8, 8)), jnp.ones((27,)),
                halos=(1, 1, 1, 1, 1, 1), chunk_slabs=3,
            )
