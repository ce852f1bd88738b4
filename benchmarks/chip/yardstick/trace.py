"""From a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes one ``.xplane.pb`` per traced window.  On a TPU
each chip is a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line
holds one event per HLO instruction executed, named by the
instruction's text (``%name = shape opcode(...)``).  A ``while`` loop is
an event of its own that encloses its body's events; only the innermost
events count, so a loop's gaps between its body's ops stay idle time.
The host plane holds the benchmark's own spans
(``jax.profiler.TraceAnnotation`` names starting ``bench.``), on the
same clock.  The reduction here is all the benchmark reads of a trace:

- the window is the ``bench.window`` span;
- busy time is the union of the op intervals inside the window,
  averaged over the chips;
- an op's time is the sum of its events' durations inside the window,
  averaged over the chips;
- an idle gap is a stretch of the window with no op on the first chip,
  labelled by the innermost benchmark span running on the host at its
  midpoint.

Which instruction is which kernel comes from the compiled program's HLO
(:func:`custom_calls`): a Pallas kernel is a ``tpu_custom_call`` whose
``op_name`` metadata names the jitted function that issued it.
"""

from __future__ import annotations

import dataclasses
import re
from collections import Counter
from pathlib import Path

DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")  # not "... SparseCore"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "host: outside any benchmark span"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns
    end: float  # ns


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> [Span] of ops
    host: list  # [Span] of benchmark spans


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    op_s: dict  # instruction name -> seconds inside the window
    gaps: list  # [(label, seconds)], longest first
    n_devices: int


def _spans(events, keep=lambda name: True):
    return [
        Span(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
        for e in events
        if keep(e.name)
    ]


def instruction(event_name: str) -> str:
    """``%name = f32[...] opcode(...)`` -> ``name``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def innermost(spans):
    """The spans that enclose no other span: a loop's event encloses its
    body's events on the same line."""
    spans = sorted(spans, key=lambda s: (s.start, -s.end))
    parent = [False] * len(spans)
    stack = []
    for i, s in enumerate(spans):
        while stack and spans[stack[-1]].end <= s.start:
            stack.pop()
        if stack and s.end <= spans[stack[-1]].end:
            parent[stack[-1]] = True
        stack.append(i)
    return [s for s, p in zip(spans, parent) if not p]


def from_profile(profile) -> Trace:
    """The device ops and benchmark spans of a ``jax.profiler.ProfileData``."""
    devices, host = {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [Span(instruction(s.name), s.start, s.end)
                   for line in plane.lines if line.name == OP_LINE
                   for s in innermost(_spans(line.events))]
            if ops:
                devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += _spans(line.events, lambda n: n.startswith(SPAN_PREFIX))
    return Trace(devices, host)


def load(directory) -> Trace:
    """Read the newest trace the profiler wrote under ``directory``."""
    from jax.profiler import ProfileData

    files = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return from_profile(ProfileData.from_file(str(files[-1])))


def union(intervals):
    """Merge ``[(start, end)]`` into disjoint sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _label(host, t):
    inside = [s for s in host if s.name != WINDOW_SPAN and s.start <= t < s.end]
    return max(inside, key=lambda s: s.start).name if inside else NO_SPAN


def reduce(trace: Trace) -> Reduction:
    windows = [s for s in trace.host if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    if not trace.devices:
        raise ValueError("the trace holds no device op: nothing ran on a chip")
    busy, op_ns = 0.0, Counter()
    first = sorted(trace.devices)[0]
    gaps = []
    for plane, ops in trace.devices.items():
        clipped = [(max(s.start, lo), min(s.end, hi), s.name) for s in ops
                   if s.end > lo and s.start < hi]
        for a, b, name in clipped:
            op_ns[name] += b - a
        merged = union((a, b) for a, b, _ in clipped)
        busy += sum(b - a for a, b in merged)
        if plane == first:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((_label(trace.host, (a + b) / 2), (b - a) * 1e-9))
    n = len(trace.devices)
    gaps.sort(key=lambda g: -g[1])
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9 / n,
        op_s={k: v * 1e-9 / n for k, v in op_ns.items()},
        gaps=gaps,
        n_devices=n,
    )


def breakdown(red: Reduction, top: int = 10) -> dict:
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in red.gaps[:top]],
    }


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def custom_calls(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` of every Pallas kernel in a
    compiled TPU program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m, op = _INSTR.match(line), _OP_NAME.search(line)
        if m:
            out[m.group(1)] = op.group(1) if op else ""
    return out
