"""The loop-aware HLO cost parser that feeds §Roofline."""

import jax
import jax.numpy as jnp

from repro.analysis.cost import analyze_hlo, parse_module, execution_counts


def compile_text(fn, *sds):
    return jax.jit(fn).lower(*sds).compile()


class TestLoopFreePrograms:
    def test_matmul_matches_xla_exactly(self):
        co = compile_text(
            lambda a, b: a @ b,
            jax.ShapeDtypeStruct((128, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 64), jnp.float32),
        )
        h = analyze_hlo(co.as_text())
        ca = co.cost_analysis()
        assert h.flops == ca["flops"] == 2 * 128 * 256 * 64
        assert h.bytes == ca["bytes accessed"]

    def test_elementwise_counted(self):
        co = compile_text(
            lambda a: jnp.sum(a * a + a),
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
        )
        h = analyze_hlo(co.as_text())
        # mul + add + reduce ~ 3 * 4096
        assert 2 * 4096 <= h.flops <= 4 * 4096


class TestLoopScaling:
    def test_scan_multiplies_body_flops(self):
        def f(a, ws):
            def body(c, w):
                return c @ w, None
            out, _ = jax.lax.scan(body, a, ws)
            return out

        co = compile_text(
            f,
            jax.ShapeDtypeStruct((32, 32), jnp.float32),
            jax.ShapeDtypeStruct((12, 32, 32), jnp.float32),
        )
        h = analyze_hlo(co.as_text())
        expect = 12 * 2 * 32**3
        assert abs(h.flops - expect) / expect < 0.05
        # XLA's own analysis counts the body once — strictly less
        assert co.cost_analysis()["flops"] < h.flops

    def test_nested_scan(self):
        def f(a, ws):
            def outer(c, w):
                def inner(c2, _):
                    return c2 @ w, None
                c2, _ = jax.lax.scan(inner, c, None, length=5)
                return c2, None
            out, _ = jax.lax.scan(outer, a, ws)
            return out

        co = compile_text(
            f,
            jax.ShapeDtypeStruct((16, 16), jnp.float32),
            jax.ShapeDtypeStruct((3, 16, 16), jnp.float32),
        )
        h = analyze_hlo(co.as_text())
        expect = 3 * 5 * 2 * 16**3
        assert abs(h.flops - expect) / expect < 0.05

    def test_fori_loop_trip_count(self):
        def f(a):
            return jax.lax.fori_loop(0, 9, lambda i, c: c @ a, a)

        co = compile_text(f, jax.ShapeDtypeStruct((24, 24), jnp.float32))
        h = analyze_hlo(co.as_text())
        expect = 9 * 2 * 24**3
        assert abs(h.flops - expect) / expect < 0.05


class TestStructure:
    def test_entry_found_and_counts(self):
        def f(a, ws):
            def body(c, w):
                return jax.nn.relu(c @ w), None
            out, _ = jax.lax.scan(body, a, ws)
            return out

        co = compile_text(
            f,
            jax.ShapeDtypeStruct((8, 8), jnp.float32),
            jax.ShapeDtypeStruct((4, 8, 8), jnp.float32),
        )
        comps = parse_module(co.as_text())
        counts = execution_counts(comps)
        assert any(c.is_entry for c in comps.values())
        assert max(counts.values()) >= 4  # the body computation

    def test_dus_counted_as_slice_traffic(self):
        # in-place cache update inside a scan (the decode-cache pattern):
        # bytes must reflect per-iteration slice traffic, not trips x buffer
        def f(cache, xs):
            def body(c, x):
                return jax.lax.dynamic_update_slice(c, x[None], (5, 0)), None

            out, _ = jax.lax.scan(body, cache, xs)
            return out

        co = jax.jit(f, donate_argnums=(0,)).lower(
            jax.ShapeDtypeStruct((1024, 256), jnp.float32),
            jax.ShapeDtypeStruct((8, 256), jnp.float32),
        ).compile()
        h = analyze_hlo(co.as_text())
        whole = 1024 * 256 * 4
        # 8 iterations: without slice-accounting this would be >= 16x whole
        assert h.bytes < 4 * whole, h.bytes
