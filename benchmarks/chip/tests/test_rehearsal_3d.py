"""The two 3D cells at 16^3 on the CPU, and their three new readers.

The rehearsal drives each cell's files, stepper, window, profiler and
check; the control and broken timed paths must come out not correct.
At 16^3 the CH cell's ``dt_factor`` would give dt = 0.39, where the
explicit nonlinear term of the scheme is unstable (at 512^3 it gives
3.8e-7): the CH cell is rehearsed at the library's dt = 1e-3 instead.

The readers ``adi_z_ms``, ``ch_rhs_ms``, ``penta3d_roofline`` and
``ch_rhs3d_roofline`` read the
recorded trace of ``test_stages.py`` (one Pallas kernel ``k.3``, 400 ns
inside the window) with its kernel put under each stage and name in turn.
"""

import contextlib

import pytest
from test_stages import HLO, _ctx, _reader, red  # noqa: F401  (fixture)

from yardstick import harness, work, work3d

ROOT = harness.HERE.parents[1]
CELLS = ["ch3d-512.evolve", "diffusion3d-256.adi"]
GRID = 16
SEED = 2**31 + 21


@pytest.fixture()
def interpret(monkeypatch):
    """Restore the library's dispatch after the rehearsal patched it."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", ops.on_tpu)
    monkeypatch.setattr(ops, "_should_interpret", ops._should_interpret)


def _tiny(workload):
    cell = harness.resolve(ROOT, workload)
    cell.config = dict(cell.config, grid=[GRID] * 3)
    if workload.startswith("ch3d"):
        h = cell.config["lx"] / GRID
        cell.config["dt_factor"] = 1e-3 * cell.config["D"] * cell.config["gamma"] / h**4
    return cell


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_runs_the_cell_and_checks_it(workload, interpret):
    from rehearse import rehearse

    cell = _tiny(workload)
    out = rehearse(workload, GRID, seconds=0.05, trace=True, seed=SEED, cell=cell)
    assert out["platform"] == "cpu"
    assert out["correct"], out
    assert out["calls"] >= 1 and out["window_spans"] == 1
    assert out["dispatch_spans"] == out["calls"]
    # the first call warms up, the second paces the window
    assert out["steps_done"] == (out["calls"] + 2) * cell.traffic["steps_per_call"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2**40 + 3])
def test_the_control_fails_the_limit(workload, seed):
    cell = _tiny(workload)
    steps = 6 * cell.traffic["steps_per_call"]
    answer = cell.stepper.control(cell.config, cell.traffic, seed, steps)
    checks = cell.stepper.check(cell.config, cell.traffic, seed, steps, answer)
    assert checks.keys() == cell.limits.keys()
    assert any(checks[k] > 3 * cell.limits[k] for k in checks), checks


def _broken(monkeypatch, workload, fault):
    """A step that returns its state unchanged, or one value altered."""
    if workload.startswith("ch3d"):
        from repro.core.cahn_hilliard import CahnHilliardADI

        step = CahnHilliardADI.step

        def altered(self, a, b):
            new, old = step(self, a, b)
            return new.at[3, 5, 7].add(0.01), old

        unchanged = lambda self, a, b: (a, b)  # noqa: E731
        monkeypatch.setattr(CahnHilliardADI, "step",
                            altered if fault == "altered" else unchanged)
    else:
        import repro

        compute = repro.compute
        altered = lambda plan, c: compute(plan, c).at[3, 5, 7].add(0.01)  # noqa: E731
        unchanged = lambda plan, c: c  # noqa: E731
        monkeypatch.setattr(repro, "compute",
                            altered if fault == "altered" else unchanged)


@pytest.mark.parametrize("workload,fault", [
    *[(w, f) for w in CELLS for f in ("unchanged", "altered")],
    ("ch3d-512.evolve", "cubic_dropped"), ("ch3d-512.evolve", "nonlinear_dropped"),
])
def test_a_broken_timed_path_is_not_correct(workload, fault, monkeypatch, interpret):
    from rehearse import rehearse

    cell = _tiny(workload)
    with contextlib.ExitStack() as stack:
        if fault in ("unchanged", "altered"):
            _broken(monkeypatch, workload, fault)
        else:
            stack.enter_context(cell.stepper.FAULTS[fault]())
        out = rehearse(workload, GRID, seconds=0.05, trace=False, seed=11, cell=cell)
    assert not out["correct"], out


@pytest.mark.parametrize("workload", CELLS)
def test_the_window_compiles_nothing(workload, monkeypatch):
    from repro import obs
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_should_interpret", lambda interpret: True)
    cell = _tiny(workload)
    start = obs.counters()["programs"]
    state = cell.stepper.build(cell.config, cell.traffic, SEED)
    calls = harness.pace(state, 0.05)
    setup = obs.counters()["programs"]
    harness.window(state, calls)
    assert obs.counters()["programs"] == setup > start
    assert harness.verify(cell, state, SEED)[1]


# -- the readers on a recorded trace ------------------------------------------

FUSED = "custen.adi.x/jit(ch_rhs_xsweep_pallas)"
KERNEL_MS = 400e-9 * 1e3 / 2  # k.3's 400 ns over the trace's 2 steps


@pytest.mark.parametrize("name, stage", [("adi_z_ms", "adi.z"), ("ch_rhs_ms", "ch.rhs")])
def test_stage_readers_read_the_kernels_of_their_stage(red, name, stage):  # noqa: F811
    hlo = HLO.replace(FUSED, f"custen.{stage}/jit(stencil3d_pallas)")
    assert _reader(name)(_ctx(red, hlo)) == pytest.approx(KERNEL_MS)


@pytest.mark.parametrize("name", ["adi_z_ms", "ch_rhs_ms"])
def test_stage_readers_are_silent_where_their_stage_is_absent(red, name):  # noqa: F811
    # the trace's kernel sits under adi.x, as in the 2D CH cell
    assert _reader(name)(_ctx(red)) is None
    # and an outer stage takes it: the bootstrap's sweeps are not the window's
    nested = HLO.replace(FUSED, "custen.ch.bootstrap/custen.adi.z/custen.ch.rhs/k")
    assert _reader(name)(_ctx(red, nested)) is None


def test_penta3d_roofline_counts_three_sweeps_over_the_sweep_kernels(red):  # noqa: F811
    ctx = _ctx(red, HLO.replace(FUSED, "custen.adi.y/jit(_substitute_mid_pallas)"))
    ctx.work = {"grid": (8, 8, 8), "itemsize": 4}
    bytes_, flops = work.penta((8, 8, 8), 4, sweeps=3)
    assert bytes_ == 6 * 512 * 4
    # 2 steps of 3 sweeps' bytes at 1000 B/s over k.3's 400 ns
    assert _reader("penta3d_roofline")(ctx) == pytest.approx(
        100 * 2 * bytes_ / 1000.0 / 400e-9)
    assert ctx.intensity["penta3d_roofline"] == pytest.approx(flops / bytes_)


def test_penta3d_roofline_is_silent_without_a_sweep_kernel(red):  # noqa: F811
    ctx = _ctx(red)  # the only kernel is the 2D fused RHS + x-sweep
    ctx.work = {"grid": (8, 8, 8), "itemsize": 4}
    assert _reader("penta3d_roofline")(ctx) is None


def test_ch_rhs3d_roofline_counts_two_applies_over_the_stencil_kernel(
        red):  # noqa: F811
    ctx = _ctx(red, HLO.replace(FUSED, "custen.ch.rhs/jit(stencil3d_pallas)"))
    ctx.work = {"grid": (8, 8, 8), "itemsize": 4}
    bytes_, flops = work3d.ch3d_rhs((8, 8, 8), 4)
    # the 25-tap biharmonic apply and the 7-tap Laplacian apply
    bih, lap = work.stencil((8, 8, 8), 25, 4), work.stencil((8, 8, 8), 7, 4)
    assert (bytes_, flops) == (bih[0] + lap[0], bih[1] + lap[1])
    # 2 steps of two applies' bytes at 1000 B/s over k.3's 400 ns
    assert _reader("ch_rhs3d_roofline")(ctx) == pytest.approx(
        100 * 2 * bytes_ / 1000.0 / 400e-9)
    assert ctx.intensity["ch_rhs3d_roofline"] == pytest.approx(flops / bytes_)


def test_ch_rhs3d_roofline_is_silent_without_a_stencil_kernel(red):  # noqa: F811
    ctx = _ctx(red)  # the only kernel is the 2D fused RHS + x-sweep
    ctx.work = {"grid": (8, 8, 8), "itemsize": 4}
    assert _reader("ch_rhs3d_roofline")(ctx) is None
