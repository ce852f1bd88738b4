"""The chip benchmark's one command: one run of one cell on a TPU.

    python3 benchmarks/chip/run.py --workload ch2d-4096.evolve \\
        --seed 7 --seconds 10 --trace 0

Run from the root of a checkout (``BENCHMARK.json`` there names the cells).
Set-up is timed from the start of this file to the first timed dispatch;
the window then runs whole calls for ``--seconds``; after it the answer is
checked against the cell's plain reference.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window.  The last line of standard output is one
JSON object; the last lines of standard error give each checked number
beside its limit.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result: there is no CPU fallback
(``rehearse.py`` is the CPU path, and prints no device metric).

float32 with x64 off; the library's ``backend='auto'`` and ``tune='off'``.
JAX's compilation cache lives in ``<checkout>/.jax_cache``, a fixed path,
so only a checkout's first run of a cell compiles.  ``LIBTPU_INIT_ARGS``
is left as the machine sets it: the benchmark needs no flag of its own.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE = ROOT / ".jax_cache"


def configure_jax():
    """x64 off and the persistent compile cache at its fixed path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    jax = configure_jax()
    from yardstick import harness

    cell = harness.resolve(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 3
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as trace_dir:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, trace_dir=Path(trace_dir))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
