"""CPU rehearsal of one cell at a tiny grid, Pallas kernels in interpret mode.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py \\
        --workload ch2d-4096.evolve --grid 64 --seconds 1 --trace 1

It runs what ``run.py`` runs (the cell's files, its stepper's set-up, the
window loop, the profiler around it, the check against the reference)
with the library's dispatch told it is on a TPU and every kernel run by
the Pallas interpreter.  It prints the calls and steps made and the
checked numbers: never a time, a rate or a share, which only a chip run
can give.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def rehearse(workload: str, grid: int, seconds: float, trace: bool, seed: int = 0,
             cell=None) -> dict:
    """Drive ``workload`` on the CPU at ``grid`` points a side."""
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    from repro.kernels import ops
    from yardstick import harness
    from yardstick import trace as _trace

    ops.on_tpu = lambda: True  # dispatch as on the chip ...
    ops._should_interpret = lambda interpret: True  # ... through the interpreter
    cell = cell or harness.resolve(ROOT, workload)
    ndim = len(cell.config["grid"])
    cell.config = dict(cell.config, grid=[grid] * ndim)

    state = cell.stepper.build(cell.config, cell.traffic, seed)
    kernels = _trace.custom_calls(state.program_text())
    with tempfile.TemporaryDirectory(prefix="rehearse_trace_") as d:
        if trace:
            jax.profiler.start_trace(d)
        try:
            calls = harness.pace(state, seconds)
            harness.window(state, calls)
        finally:
            if trace:
                jax.profiler.stop_trace()
        spans = _trace.load(d).host if trace else []
    steps_done = state.steps_done
    checks, correct = harness.verify(cell, state, seed)
    return {
        "platform": jax.devices()[0].platform,
        "correct": correct,
        "calls": calls,
        "steps_done": steps_done,
        "window_spans": sum(s.name == _trace.WINDOW_SPAN for s in spans),
        "dispatch_spans": sum(s.name == "bench.dispatch" for s in spans),
        "pallas_kernels_in_program": len(kernels),
        "checks": {k: {"value": v, "limit": cell.limits.get(k)}
                   for k, v in checks.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--grid", type=int, default=32)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    out = rehearse(args.workload, args.grid, args.seconds, bool(args.trace), args.seed)
    print(json.dumps(out))
    print(f"rehearsal done in {time.perf_counter() - t0:.0f} s (CPU, interpret "
          "mode: no device metric)", file=sys.stderr)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
