"""Stage scopes, host spans and compile counters: where a step's work
comes from, seen from inside the library.

Three tools, each built on what JAX already has, so a profile of a run
holds the library's names beside the device ops, on one clock:

- :func:`stage` — ``jax.named_scope("custen." + name)``.  Trace-time only:
  it adds no operation, only the ``op_name`` metadata of every HLO
  instruction traced inside it, which the device trace carries to each
  kernel and fusion (a fusion takes the scope of its root).  One stage
  per phase of a step: ``adi.x``/``adi.y``/``adi.z`` (the ADI sweeps,
  the CH fused RHS + x-sweep included), ``ch.rhs``, ``ch.update``,
  ``ch.bootstrap`` and ``stencil`` (every plan apply).  Stages nest;
  the outermost ``custen.`` name of an op is its stage.  An op with none
  is one XLA added (a copy between memory spaces) or the loop around a
  step.
- :func:`span` — ``jax.profiler.TraceAnnotation("custen." + name)`` as a
  context manager or decorator, a host-side event, recorded only while a
  profiler runs: ``create``,
  ``evolve.bootstrap``, ``evolve.chunk``, ``evolve.metrics``.
- :func:`counters` — monotone counts of the programs JAX compiled or
  fetched from its persistent cache, fed by :mod:`jax.monitoring`
  listeners registered once on import; the listeners run on a compile,
  never on a step.  Beside them, two counts the 3D stencil kernel adds
  when it is traced: ``stencil3d.sparse_applies``, the applies traced
  with a static tap set (weights known when the program was traced, some
  of them zero), and ``stencil3d.taps_skipped``, the zero taps those
  applies dropped (100 for the 5x5x5 biharmonic, 20 for the 7-point
  Laplacian).  And one the penta dispatch adds as a sweep is traced:
  ``penta.dense_sweeps``, the sweeps sent to the dense-step recurrence
  (:func:`repro.kernels.penta.sweep_lines_refs`), which fills every
  sublane with independent lines: 3 a 3D ADI step at the benchmark's
  sizes, 0 a 2D one (no batch axis there yields 8 x 128 lines in VMEM).
  Take the difference of two readings around the part of a run you want
  to count.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax

PREFIX = "custen."

#: ``jax.monitoring`` events (jax 0.9): the duration of every executable
#: obtained (compiled, or fetched from the persistent cache), and the
#: persistent cache's hits and written misses.
_PROGRAM_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

_lock = threading.Lock()
_counts = {
    "programs": 0, "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0,
    "stencil3d.sparse_applies": 0, "stencil3d.taps_skipped": 0,
    "penta.dense_sweeps": 0,
}


def stage(name: str):
    """The device-side stage ``custen.<name>``: a context manager or
    decorator around the code that emits a phase's work."""
    return jax.named_scope(PREFIX + name)


@contextlib.contextmanager
def span(name: str):
    """The host-side span ``custen.<name>`` (a profiler trace event): a
    context manager or decorator around host code."""
    with jax.profiler.TraceAnnotation(PREFIX + name):
        yield


def add(name: str, n: int) -> None:
    """Add ``n`` to the library's own count ``name`` (a trace-time count:
    callers run it while JAX traces, never on a step)."""
    with _lock:
        _counts[name] += n


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == _PROGRAM_EVENT:
        with _lock:
            _counts["programs"] += 1
            _counts["compile_s"] += seconds


def _on_event(event: str, **_) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        with _lock:
            _counts[key] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def counters() -> dict:
    """The counts since import: ``programs`` (executables obtained),
    ``compile_s`` (their seconds), ``cache_hits``/``cache_misses`` (the
    persistent compile cache), ``stencil3d.sparse_applies``/
    ``stencil3d.taps_skipped`` (3D stencil applies traced with a tap set,
    and the zero taps they dropped), ``penta.dense_sweeps`` (penta sweeps
    traced on the dense-step recurrence), and the Create-time tuner's
    counts as ``tune.*`` (read from :data:`repro.tune.stats`)."""
    from repro.tune import stats

    with _lock:
        out = dict(_counts)
    for field in dataclasses.fields(stats):
        value = getattr(stats, field.name)
        out["tune." + field.name] = len(value) if isinstance(value, list) else value
    return out
