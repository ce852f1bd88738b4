"""What decides ``correct`` fails what it must, at a size a test can hold.

The control (the plain reference in bfloat16, in the program's place)
must read above each cell's limit, and a run whose timed path is broken
underneath must come out not correct: a step that returns its state
unchanged, an answer altered where it is produced, and each fault a
stepper plants in its own kernels (for CH, the nonlinear term's ``C^3``
or the whole term left out).  A one-chip cell
has no batch to halve and no exchange between chips to leave out.
"""

import contextlib

import pytest

from yardstick import harness

ROOT = harness.HERE.parents[1]
GRIDS = {"ch2d-4096.evolve": 64, "diffusion3d-256.explicit": 16}


def _tiny(workload):
    cell = harness.resolve(ROOT, workload)
    cell.config = dict(cell.config, grid=[GRIDS[workload]] * len(cell.config["grid"]))
    return cell


@pytest.mark.parametrize("workload", sorted(GRIDS))
@pytest.mark.parametrize("seed", [3, 2**31 + 3, 2**40 + 3])
def test_the_control_fails_the_limit(workload, seed):
    cell = _tiny(workload)
    steps = 6 * cell.traffic["steps_per_call"]
    answer = cell.stepper.control(cell.config, cell.traffic, seed, steps)
    checks = cell.stepper.check(cell.config, cell.traffic, seed, steps, answer)
    assert checks.keys() == cell.limits.keys()
    assert any(checks[k] > 3 * cell.limits[k] for k in checks), checks


def _unchanged(monkeypatch, workload):
    if workload.startswith("ch2d"):
        from repro.core.cahn_hilliard import CahnHilliardADI

        monkeypatch.setattr(CahnHilliardADI, "step", lambda self, a, b: (a, b))
    else:
        import repro

        monkeypatch.setattr(repro, "compute", lambda plan, c: c)


def _altered(monkeypatch, workload):
    if workload.startswith("ch2d"):
        from repro.core.cahn_hilliard import CahnHilliardADI

        step = CahnHilliardADI.step

        def bad(self, a, b):
            new, old = step(self, a, b)
            return new.at[3, 5].add(0.01), old

        monkeypatch.setattr(CahnHilliardADI, "step", bad)
    else:
        import repro

        compute = repro.compute
        monkeypatch.setattr(repro, "compute",
                            lambda plan, c: compute(plan, c).at[3, 5, 7].add(0.01))


BROKEN = {"unchanged": _unchanged, "altered": _altered}
# faults each stepper plants in its own kernels (``FAULTS``)
PLANTED = {"ch2d-4096.evolve": ["cubic_dropped", "nonlinear_dropped"]}


@pytest.mark.parametrize("workload,fault", [
    *[(w, f) for w in sorted(GRIDS) for f in BROKEN],
    *[(w, f) for w, faults in PLANTED.items() for f in faults],
])
def test_a_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    from repro.kernels import ops
    from rehearse import rehearse

    monkeypatch.setattr(ops, "on_tpu", ops.on_tpu)
    monkeypatch.setattr(ops, "_should_interpret", ops._should_interpret)
    cell = _tiny(workload)
    with contextlib.ExitStack() as stack:
        if fault in BROKEN:
            BROKEN[fault](monkeypatch, workload)
        else:
            stack.enter_context(cell.stepper.FAULTS[fault]())
        out = rehearse(workload, GRIDS[workload], seconds=0.05, trace=False, seed=11,
                       cell=cell)
    assert not out["correct"], out
