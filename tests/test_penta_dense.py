"""The dense-step penta recurrence (``kernels/penta.py:sweep_lines_refs``)
in interpret mode.

Each dense layout (lead: a 3D z-sweep on the field as it lies; plane: 8k
planes a block, sublane-strided steps; row: 1024k-row blocks transposed
in VMEM) gives the bits of its jnp scan, band and cyclic, and counts one
``penta.dense_sweeps``.  Shapes with no batch axis that fills the
sublanes stay on the one-sublane ``sweep_refs`` and count none."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.adi import make_adi_operator_3d
from repro.kernels.penta import (
    cyclic_penta_factor,
    cyclic_penta_solve_factored,
    cyclic_penta_solve_factored_mid,
    cyclic_penta_solve_factored_rows,
    dense_sweep_tiles,
    hyperdiffusion_diagonals,
    penta_factor,
    penta_solve_factored,
    penta_solve_factored_mid,
    penta_solve_factored_rows,
)

F32 = jnp.float32

SOLVES = {
    "lead": (penta_solve_factored, cyclic_penta_solve_factored),
    "mid": (penta_solve_factored_mid, cyclic_penta_solve_factored_mid),
    "rows": (penta_solve_factored_rows, cyclic_penta_solve_factored_rows),
}


def _dense_sweeps() -> int:
    return obs.counters()["penta.dense_sweeps"]


def _factors(M, cyclic):
    diags = hyperdiffusion_diagonals(M, 0.4, F32)
    return cyclic_penta_factor(*diags) if cyclic else penta_factor(*diags)


def _pallas_vs_jnp(layout, shape, M, cyclic):
    """The Pallas solve (interpreted) and the jnp one of a random rhs,
    and how many dense sweeps the Pallas one counted."""
    solve = SOLVES[layout][cyclic]
    fac = _factors(M, cyclic)
    rhs = jnp.asarray(np.random.default_rng(M).standard_normal(shape), F32)
    before = _dense_sweeps()
    out = solve(fac, rhs, backend="pallas", interpret=True)
    counted = _dense_sweeps() - before
    return out, solve(fac, rhs, backend="jnp"), counted


@pytest.mark.parametrize("cyclic", [False, True], ids=["band", "cyclic"])
@pytest.mark.parametrize(
    "layout, shape",
    [
        ("lead", (12, 8, 128)),  # (nz, ny, nx): one (8, 128) tile a step
        ("lead", (16, 16, 256)),  # four tiles a step
        ("lead", (10, 24, 128)),  # M need not be a multiple of 8
        ("mid", (8, 16, 128)),  # (P, M, N): plane counts 8, 16, 24
        ("mid", (16, 24, 256)),
        ("mid", (24, 16, 128)),
        ("rows", (1024, 16)),  # (B, M): 1024 and 2048 rows
        ("rows", (2048, 256)),
        ("rows", (2048, 40)),  # one lane chunk of all 40
    ],
)
def test_dense_layout_equals_jnp(layout, shape, cyclic):
    M = shape[1] if layout == "mid" else shape[-1] if layout == "rows" else shape[0]
    out, ref, counted = _pallas_vs_jnp(layout, shape, M, cyclic)
    assert counted == 1
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize(
    "layout, shape",
    [
        ("mid", (1, 16, 128)),  # one plane: the 2D column sweep
        ("mid", (12, 16, 128)),  # not a multiple of 8 planes
        ("mid", (8, 16, 64)),  # no 128-lane tile
        ("rows", (512, 16)),  # fewer than 1024 rows
        ("lead", (16, 12, 128)),  # no 8-row tile of the planes
    ],
)
def test_fallback_shapes_stay_one_sublane(layout, shape):
    M = shape[1] if layout == "mid" else shape[-1] if layout == "rows" else shape[0]
    out, ref, counted = _pallas_vs_jnp(layout, shape, M, cyclic=True)
    assert counted == 0
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize(
    "solve, shape",
    [
        (penta_solve_factored_rows, (4096, 4096)),  # 2D row sweep at M = 4096
        (penta_solve_factored, (4096, 4096)),  # 2D column sweep
    ],
    ids=["rows", "column"],
)
def test_2d_sweeps_at_long_m_stay_one_sublane(solve, shape):
    # 1024-row blocks at M = 4096 exceed VMEM, and a 2D column has one plane
    fac = penta_factor(*hyperdiffusion_diagonals(4096, 0.4, F32))
    before = _dense_sweeps()
    jax.eval_shape(
        lambda r: solve(fac, r, backend="pallas", interpret=True),
        jax.ShapeDtypeStruct(shape, F32),
    )
    assert _dense_sweeps() == before
    assert dense_sweep_tiles("rows", 4096, (4096,), F32) == 0


def test_explicit_tile_pins_the_one_sublane_sweep():
    fac = penta_factor(*hyperdiffusion_diagonals(16, 0.4, F32))
    rhs = jnp.asarray(np.random.default_rng(5).standard_normal((8, 16, 128)), F32)
    before = _dense_sweeps()
    out = penta_solve_factored_mid(
        fac, rhs, backend="pallas", tn=128, interpret=True
    )
    assert _dense_sweeps() == before
    ref = penta_solve_factored_mid(fac, rhs, backend="jnp")
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize(
    "shape, dense",
    [((64, 64, 64), 1), ((8, 128, 128), 3)],
    ids=["64^3", "8x128x128"],
)
def test_adi3d_solve_pallas_equals_jnp(shape, dense):
    # 64^3: only the x sweep (4096 rows) fills the sublanes; at 8x128x128
    # all three do
    rhs = jnp.asarray(np.random.default_rng(9).standard_normal(shape), F32)

    def ops(backend):
        return make_adi_operator_3d(*shape, 0.3, cyclic=True, dtype=F32,
                                    backend=backend)

    def step(op, c):
        return op.solve_z(op.solve_y(op.solve_x(c)))

    before = _dense_sweeps()
    out = step(ops("pallas"), rhs)
    assert _dense_sweeps() - before == dense
    np.testing.assert_array_equal(out, step(ops("jnp"), rhs))


@pytest.mark.parametrize(
    "layout, M, batch, tiles",
    [
        ("lead", 512, (512, 512), 4),  # (512, 8, 512) blocks
        ("lead", 256, (256, 256), 4),  # (256, 16, 256) blocks
        ("lead", 512, (8, 128), 1),
        ("lead", 8192, (512, 512), 0),  # exceeds VMEM even at one tile
        ("mid", 512, (512, 512), 4),
        ("mid", 256, (24, 256), 3),
        ("mid", 12, (8, 128), 0),  # M not a multiple of 8
        ("rows", 512, (262144,), 4),
        ("rows", 2048, (4096,), 1),  # two or more tiles exceed VMEM
        ("rows", 4096, (4096,), 0),
    ],
)
def test_dense_sweep_tiles(layout, M, batch, tiles):
    assert dense_sweep_tiles(layout, M, batch, F32) == tiles
    assert dense_sweep_tiles(layout, M, batch, jnp.float64) == 0
