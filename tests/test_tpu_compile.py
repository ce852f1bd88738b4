"""Ahead-of-time TPU compiles of the main-path kernels at real widths.

Interpret mode runs every Pallas kernel on the CPU, but it cannot see what
the TPU compiler (Mosaic) refuses: unaligned or single-lane dynamic
accesses, float64, more VMEM or SMEM than the core has.  Each test here
compiles one kernel, through the library's own ``backend='auto'``
dispatch, for a described (not attached) TPU v5e and checks that the
compiled program holds the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import repro
from repro import obs
from repro.kernels import ops
from repro.kernels.penta import (
    CyclicPentaFactors,
    PentaFactors,
    cyclic_penta_solve_factored,
    cyclic_penta_solve_factored_mid,
    cyclic_penta_solve_factored_rows,
)

F32 = jnp.float32
CH_KW = dict(dt=1e-3, D=0.6, gamma=0.01, inv_h2=4.0e5, inv_h4=1.6e11)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no TPU library
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described device's compiles cannot be read back from a persistent
    # cache without the chip, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Make the dispatchers choose as they would with a TPU attached."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _compile(fn, sharding, *trees):
    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    args = jax.tree.map(spec, trees)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "dispatch did not pick the Pallas kernel"


def _s(*shape):
    return jax.ShapeDtypeStruct(shape, F32)


def _cyclic_factors(M):
    return CyclicPentaFactors(
        band=PentaFactors(*(_s(M) for _ in range(5))),
        z=_s(M, 4), s_inv=_s(4, 4), w=_s(M, 4),
    )


def test_stencil2d_5x5_periodic(one_chip, tpu_dispatch):
    _compile(
        lambda x, c: ops.stencil_apply(
            x, c, left=2, right=2, top=2, bottom=2, bc="periodic"
        ),
        one_chip, _s(4096, 4096), _s(25),
    )


def test_stencil1d_batch_radius2(one_chip, tpu_dispatch):
    _compile(
        lambda x, c: ops.stencil_apply_batch1d(
            x, c, left=2, right=2, bc="periodic"
        ),
        one_chip, _s(4096, 4096), _s(5),
    )


@pytest.mark.parametrize(
    "apply, shape",
    [
        # a single served 1D line: one row, padded to 8 sublanes
        (lambda x, c: ops.stencil_apply_batch1d(x, c, left=2, right=2), (1, 2**20)),
        # 1000 = 8 x 125: no 128-lane tile divides it, so x is padded
        (lambda x, c: ops.stencil_apply(x, c, left=2, right=2, top=2, bottom=2),
         (1000, 1000)),
    ],
    ids=["batch1d_one_line", "stencil2d_1000"],
)
def test_stencil_alignment_padded(one_chip, tpu_dispatch, apply, shape):
    taps = 5 if shape[0] == 1 else 25
    _compile(apply, one_chip, _s(*shape), _s(taps))


def test_stencil3d_7point(one_chip, tpu_dispatch):
    _compile(
        lambda x, c: ops.stencil_apply_3d(
            x, c, halos=(1,) * 6, bc="periodic"
        ),
        one_chip, _s(256, 256, 256), _s(27),
    )


def test_stencil3d_biharmonic_taps(one_chip, tpu_dispatch):
    # the 3D CH cell's biharmonic: concrete weights closed over, so the
    # kernel is traced with its 25 non-zero taps of 125
    w = jnp.asarray(repro.get_operator("biharmonic").weights(3).ravel(), F32)
    before = obs.counters()
    _compile(
        lambda x: ops.stencil_apply_3d(x, w, halos=(2,) * 6, bc="periodic"),
        one_chip, _s(512, 512, 512),
    )
    after = obs.counters()
    assert after["stencil3d.taps_skipped"] - before["stencil3d.taps_skipped"] == 100


def test_ch_rhs(one_chip, tpu_dispatch):
    _compile(
        lambda a, b: ops.ch_rhs(a, b, **CH_KW),
        one_chip, _s(4096, 4096), _s(4096, 4096),
    )


def test_penta_column(one_chip, tpu_dispatch):
    _compile(
        cyclic_penta_solve_factored,
        one_chip, _cyclic_factors(4096), _s(4096, 4096),
    )


def test_penta_rows(one_chip, tpu_dispatch):
    _compile(
        cyclic_penta_solve_factored_rows,
        one_chip, _cyclic_factors(4096), _s(4096, 4096),
    )


def test_penta_rows_3d(one_chip, tpu_dispatch):
    # the x-sweep of a 256^3 ADI step, reshaped to (nz*ny, nx)
    _compile(
        cyclic_penta_solve_factored_rows,
        one_chip, _cyclic_factors(256), _s(65536, 256),
    )


def test_penta_plane(one_chip, tpu_dispatch):
    _compile(
        cyclic_penta_solve_factored_mid,
        one_chip, _cyclic_factors(4096), _s(8, 4096, 256),
    )


def test_ch_rhs_xsweep(one_chip, tpu_dispatch):
    _compile(
        lambda a, b, f: ops.ch_rhs_xsweep(a, b, f, **CH_KW),
        one_chip, _s(4096, 4096), _s(4096, 4096), _cyclic_factors(4096),
    )


def test_float64_stays_off_mosaic(tpu_dispatch):
    # Mosaic has no float64: auto keeps an f64 sweep on jnp, and an
    # explicit pallas request on the chip is refused with the reason
    from repro.kernels.penta import penta_solve_factored, tpu_sweep_problem

    assert "float32" in tpu_sweep_problem(4096, 4096, 128, np.float64, lanes=False)
    assert tpu_sweep_problem(4096, 4096, 128, np.float32, lanes=False) is None

    assert ops.checked_backend("k", "no f64", "auto", None) == "jnp"
    assert ops.checked_backend("k", None, "auto", None) == "pallas"
    M = 16
    fac = PentaFactors(*(jnp.ones((M,), jnp.float64) for _ in range(5)))
    rhs = jnp.ones((M, 128), jnp.float64)
    with pytest.raises(ValueError, match="penta sweep cannot run on this TPU"):
        penta_solve_factored(fac, rhs, backend="pallas", interpret=False)


def test_ch_evolve_kernels_carry_their_sweep_stage(one_chip, tpu_dispatch):
    # every Pallas kernel of the CH hot loop is charged to its ADI sweep:
    # the fused RHS + x-sweep to custen.adi.x, the column penta to adi.y
    import re

    from repro.core.cahn_hilliard import CahnHilliardADI, CHConfig

    n = 256
    h = 2 * np.pi / n
    solver = CahnHilliardADI(CHConfig(
        nx=n, ny=n, dt=0.1 * h**4 / (0.6 * 0.01), dtype="float32",
        backend="auto"))
    c = jax.ShapeDtypeStruct((n, n), F32, sharding=one_chip)
    text = solver.make_evolve(2).lower(c, c).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    stages = [re.search(r'op_name="[^"]*?custen\.(adi\.[xy])/', k) for k in kernels]
    assert kernels and all(stages), kernels
    assert {s.group(1) for s in stages} == {"adi.x", "adi.y"}


def _outermost_stages(text):
    """The outermost ``custen.`` stage of each Pallas kernel's ``op_name``."""
    import re

    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels, "dispatch did not pick the Pallas kernels"
    return [re.search(r'op_name="[^"]*?custen\.([a-z0-9_.]+?)/', k) for k in kernels]


def test_ch3d_evolve_kernels_carry_their_stage(one_chip, tpu_dispatch):
    # the 3D CH hot loop at the benchmark's 512^3: both RHS stencil plans
    # charged to ch.rhs, the row, plane and column penta sweeps to their axis
    from repro.core.cahn_hilliard import CahnHilliardADI, CHConfig

    n = 512
    h = 2 * np.pi / n
    solver = CahnHilliardADI(CHConfig(
        nx=n, ny=n, nz=n, dt=0.1 * h**4 / (0.6 * 0.01), dtype="float32",
        rhs_mode="stencil", backend="auto"))
    c = jax.ShapeDtypeStruct((n, n, n), F32, sharding=one_chip)
    stages = _outermost_stages(solver.make_evolve(4).lower(c, c).compile().as_text())
    assert all(stages)
    assert sorted(s.group(1) for s in stages) == [
        "adi.x", "adi.y", "adi.z", "ch.rhs", "ch.rhs"]


def test_heat_lod_scan_kernels_carry_their_stage(one_chip, tpu_dispatch):
    # backward-Euler LOD heat at 256^3: c <- S_z S_y S_x c, 16 steps a scan
    import repro

    n = 256
    op = repro.create("diffusion", (n, n, n), mode="adi", alpha=1.0,
                      dtype=F32, backend="auto")

    def advance(c):
        step = lambda c, _: (repro.compute(op, c), None)  # noqa: E731
        return jax.lax.scan(step, c, None, length=16)[0]

    c = jax.ShapeDtypeStruct((n, n, n), F32, sharding=one_chip)
    text = jax.jit(advance, donate_argnums=0).lower(c).compile().as_text()
    stages = _outermost_stages(text)
    assert all(stages)
    assert sorted(s.group(1) for s in stages) == ["adi.x", "adi.y", "adi.z"]
