"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 benchmarks/chip/calibrate.py --workload ch2d-4096.evolve \\
        --seconds 10 --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3 \\
        --faults cubic_dropped,nonlinear_dropped

For each seed it runs the cell as ``run.py`` does (set-up, a window of
``--seconds``, the check) and prints the numbers compared: the lower
readings.  For each control seed it also puts the control (the plain
reference one precision down) in the program's place over the same
steps, and runs the cell again with each of the stepper's ``FAULTS``
named in ``--faults`` planted in the program: the upper readings.  Each
goes through the same verdict as a run's, against the cell's limits.
The benchmark's own runs never run the control or a fault.  One JSON
line per reading.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = [f for f in args.faults.split(",") if f]

    import run

    jax = run.configure_jax()
    from yardstick import harness

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = harness.resolve(run.ROOT, args.workload)

    def program(seed, kind):
        t0 = time.perf_counter()
        state = cell.stepper.build(cell.config, cell.traffic, seed)
        calls = harness.pace(state, args.seconds)
        window_s = harness.window(state, calls)
        steps = state.steps_done
        t1 = time.perf_counter()
        checks, correct = harness.verify(cell, state, seed)
        del state
        print(json.dumps({"seed": seed, "kind": kind, "steps": steps,
                          "checks": checks, "correct": correct,
                          "step_ms": window_s * 1e3 / (calls * cell.traffic["steps_per_call"]),
                          "check_seconds": time.perf_counter() - t1,
                          "seconds": time.perf_counter() - t0}), flush=True)
        return steps

    for seed in seeds:
        steps = program(seed, "program")
        if seed not in controls:
            continue
        t0 = time.perf_counter()
        answer = cell.stepper.control(cell.config, cell.traffic, seed, steps)
        checks = cell.stepper.check(cell.config, cell.traffic, seed, steps, answer)
        print(json.dumps({"seed": seed, "kind": "control", "steps": steps,
                          "checks": checks,
                          "correct": harness.judge(checks, cell.limits),
                          "seconds": time.perf_counter() - t0}), flush=True)
        for name in faults:
            with cell.stepper.FAULTS[name]():
                program(seed, name)
    return 0


if __name__ == "__main__":
    sys.exit(main())
