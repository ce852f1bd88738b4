"""Run one benchmark cell: set-up, a timed window, the check, the result.

Everything that belongs to one cell is found by name, in files of its own:

- ``BENCHMARK.json`` (the checkout's root): the cell's configuration and
  traffic names, its chips, and the metrics it reports;
- ``configs/<config>.json``: the deployment (grid, parameters, initial
  field, precision), named by the configuration's ``file``;
- ``traffic/<traffic>.json``: the step the user drives and its
  parameters, and the ``stepper`` that runs it;
- ``steppers/<stepper>.py``: ``build`` (state on the device from the seed,
  the compiled call the window drives, already warm), ``check`` (the
  answer against the plain reference) and ``control``;
- ``cells/<cell>.json``: the limit of each number ``check`` compares;
- ``metrics/<metric>.py``: one reader per per-layer metric;
- ``peaks/<device kind>.json``: the chip's published peaks.

A stepper's ``build(config, traffic, seed)`` returns an object with
``steps_per_call``, ``steps_done``, ``work`` (shapes for the work
functions), ``setup_parts`` (seconds by set-up phase), ``call()`` (one
dispatch of the window's compiled program; returns what to wait on),
``program_text()`` (that program's compiled HLO) and ``finish()`` (the
answer as a host array, after which the program's state is freed).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import re
import sys
import time
from pathlib import Path

import jax

from yardstick import trace as _trace
from yardstick import work as _work

HERE = Path(__file__).resolve().parents[1]  # benchmarks/chip
MIB = 2**20


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a benchmark file by path (its name may hold ``-`` or ``.``)."""
    spec = importlib.util.spec_from_file_location(f"_bench_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    stepper: object
    limits: dict
    end_to_end: list
    per_layer: list


def resolve(root: Path, name: str) -> Cell:
    """Everything the cell ``name`` of ``root/BENCHMARK.json`` needs."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    per_layer = [m for m in spec["per_layer"] if name in m["workloads"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(root / cfg["file"]),
        traffic=traffic,
        stepper=load_module(HERE / "steppers" / f"{traffic['stepper']}.py"),
        limits=load_json(HERE / "cells" / f"{name}.json")["limits"],
        end_to_end=spec["end_to_end"],
        per_layer=per_layer,
    )


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown chip is an error."""
    path = HERE / "peaks" / (re.sub(r"[^A-Za-z0-9_.-]", "_", device_kind) + ".json")
    if not path.exists():
        raise KeyError(f"no peaks for device kind {device_kind!r} ({path.name})")
    return load_json(path)


@dataclasses.dataclass
class Context:
    """What a per-layer metric's ``read(ctx)`` may use."""

    red: _trace.Reduction
    kernels: dict  # instruction name -> op_name of each Pallas kernel
    steps: int  # steps completed in the traced window
    work: dict  # shapes from the stepper
    peak: dict
    intensity: dict = dataclasses.field(default_factory=dict)  # flops per byte

    def kernel_s(self, pattern: str) -> float:
        """Seconds of the kernels whose ``op_name`` matches ``pattern``."""
        return sum(self.red.op_s.get(n, 0.0) for n, op in self.kernels.items()
                   if re.search(pattern, op))

    def roofline(self, name: str, bytes_: float, flops: float, seconds: float):
        """``100 * floor / seconds`` for ``self.steps`` calls of the work,
        the floor being the bandwidth side alone (``work.floor_s``), or
        ``None`` when the trace shows no time for it."""
        if seconds <= 0:
            return None
        self.intensity[name] = flops / bytes_
        return 100.0 * _work.floor_s(bytes_ * self.steps, self.peak) / seconds


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


class Stopwatch:
    """Seconds of each named set-up phase, for the run's log."""

    def __init__(self):
        self.parts, self._t = {}, time.perf_counter()

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name], self._t = now - self._t, now


def pace(state, seconds: float) -> int:
    """Set-up's last step: time one more warm call and return how many
    calls fill ``seconds``."""
    t0 = time.perf_counter()
    jax.block_until_ready(state.call())
    return max(1, round(seconds / (time.perf_counter() - t0)))


def window(state, calls: int) -> float:
    """Dispatch ``calls`` calls back to back, as ``ch_evolve`` does, and
    wait for the last; return the seconds from the first dispatch to the
    last result.  No host wait sits between two calls: the runtime takes
    about nine ahead before a dispatch blocks."""
    span = jax.profiler.TraceAnnotation
    with span(_trace.WINDOW_SPAN):
        t0 = time.perf_counter()
        for _ in range(calls):
            with span("bench.dispatch"):
                out = state.call()
        with span("bench.block"):
            jax.block_until_ready(out)
        return time.perf_counter() - t0


def verify(cell: Cell, state, seed: int):
    """Free the program's state and compare its answer with the
    reference; return ``(checks, correct)``."""
    steps_done = state.steps_done
    answer = state.finish()
    gc.collect()
    checks = cell.stepper.check(cell.config, cell.traffic, seed, steps_done, answer)
    return checks, judge(checks, cell.limits)


def judge(checks: dict, limits: dict) -> bool:
    """Every number the cell compares is there and within its limit."""
    return set(checks) == set(limits) and all(
        checks[k] <= limits[k] for k in checks)  # NaN compares False


def per_layer(cell: Cell, ctx: Context, log=sys.stderr) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    metrics = {}
    for m in cell.per_layer:
        v = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for k, v in ctx.intensity.items():
        print(f"{k}: {v!r} flops/byte; share of the bandwidth floor", file=log)
    return metrics


def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        trace_dir: Path | None = None, log=sys.stderr) -> dict:
    """One run of ``cell`` on the chip; returns the result line as a dict."""
    devices = jax.devices()[:cell.chips]
    dev = devices[0]
    peak = peaks(dev.device_kind)
    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(lambda x: x + 1)(0.0))  # the runtime's first program
    first_program = time.perf_counter() - t0
    state = cell.stepper.build(cell.config, cell.traffic, seed)
    calls = pace(state, seconds)
    setup_s = time.perf_counter() - t_start
    parts = " ".join(f"{k}={v:.3f}" for k, v in state.setup_parts.items())
    print(f"setup_s={setup_s:.3f} parts: jax_import_and_devices="
          f"{t0 - t_start:.3f} first_program={first_program:.3f} {parts}",
          file=log, flush=True)

    kernels = _trace.custom_calls(state.program_text()) if trace else {}
    if trace:
        jax.profiler.start_trace(str(trace_dir))
    try:
        window_s = window(state, calls)
    finally:
        if trace:
            jax.profiler.stop_trace()
    steps = calls * state.steps_per_call
    peak_bytes = max(d.memory_stats()["peak_bytes_in_use"] for d in devices)
    work = state.work
    print(f"window: {calls} calls, {steps} steps, {window_s:.3f} s; "
          f"steps since the seed {state.steps_done}", file=log)
    checks, correct = verify(cell, state, seed)
    del state

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    out = {"correct": correct, "attempted": calls, "failed": 0 if correct else calls}
    if trace:
        red = _trace.reduce(_trace.load(trace_dir))
        ctx = Context(red=red, kernels=kernels, steps=steps, work=work, peak=peak)
        out["metrics"] = per_layer(cell, ctx, log)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = _trace.breakdown(red)
    else:
        values = {"step_ms": window_s * 1e3 / steps,
                  "peak_hbm_mib": peak_bytes / MIB,
                  "setup_s": setup_s}
        unknown = {m["name"] for m in cell.end_to_end} - set(values)
        if unknown:
            raise KeyError(f"no measurement for end-to-end metrics {sorted(unknown)}")
        out["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = device
    out["checks"] = {k: {"value": _finite(v), "limit": cell.limits.get(k)}
                     for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k} = {v!r} limit {cell.limits.get(k)!r}", file=log)
    return out
