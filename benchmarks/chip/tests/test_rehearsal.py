"""The CPU rehearsal drives each cell's files, stepper, window, profiler
and check at a tiny grid; and the command refuses to run without a TPU."""

import json
import shutil
import subprocess
import sys

import pytest

from yardstick import harness

ROOT = harness.HERE.parents[1]
GRIDS = {"ch2d-4096.evolve": 64, "diffusion3d-256.explicit": 16}


@pytest.fixture()
def interpret(monkeypatch):
    """Restore the library's dispatch after the rehearsal patched it."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", ops.on_tpu)
    monkeypatch.setattr(ops, "_should_interpret", ops._should_interpret)


@pytest.mark.parametrize("workload", sorted(GRIDS))
def test_rehearsal_runs_the_cell_and_checks_it(workload, interpret):
    from rehearse import rehearse

    out = rehearse(workload, GRIDS[workload], seconds=0.05, trace=True, seed=2**31 + 9)
    assert out["platform"] == "cpu"
    assert out["correct"], out
    assert out["calls"] >= 1 and out["window_spans"] == 1
    assert out["dispatch_spans"] == out["calls"]
    steps_per_call = harness.resolve(ROOT, workload).traffic["steps_per_call"]
    # the first call warms up, the second paces the window
    assert out["steps_done"] == (out["calls"] + 2) * steps_per_call
    assert not {"step_ms", "setup_s", "peak_hbm_mib"} & set(json.dumps(out).split('"'))


def _run(cwd, workload="ch2d-4096.evolve"):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(cwd)},
    )


def test_the_command_refuses_a_machine_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_the_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
