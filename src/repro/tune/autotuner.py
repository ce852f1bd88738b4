"""Create-time autotuner: measure candidate configurations, keep the winner.

The plan layer (``stencil_create_2d``, ``stencil_create_1d_batch``,
``make_adi_operator``, ``CHConfig``) passes a ``tune`` knob through to
:func:`autotune`:

- ``'off'``     — no measurement; static heuristics (``pick_tile`` & co)
  choose the configuration, exactly the pre-tuner behaviour.
- ``'cached'``  — look the problem up in the persistent cache
  (:mod:`repro.tune.cache`); measure only on a miss and store the winner,
  so repeated plan creation is free.
- ``'force'``   — always re-measure (and refresh the cache entry).

Candidates are plain dicts of knob values; the caller supplies a
``build(config) -> callable`` factory producing a ready-to-time closure
over representative arguments (or ``None`` / raising to declare the
config infeasible).  Timing is a short median-of-repeats wall-clock
measurement with ``block_until_ready`` — crude, but these kernels differ
by integer factors, which is all Create-time selection needs.

Module-level :data:`stats` counts measurement runs and cache hits/misses
so tests (and curious users) can verify that a cached Create performs no
measurement work at all, and lists every candidate that raised.
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections.abc import Callable, Sequence

import jax

from repro.tune.cache import TuneCache, tune_key

MODES = ("off", "cached", "force")

# Escape hatch (ROADMAP "cross-host cache hygiene"): with
# REPRO_TUNE_FORCE=1 every tune='cached' Create re-measures and refreshes
# its cache entry, even on a hit — for when a shipped warm cache is
# suspect and the host fingerprint in the key was too coarse to notice.
# tune='off' stays off: the hatch forces re-measurement, never measurement.
FORCE_ENV = "REPRO_TUNE_FORCE"


def _force_requested() -> bool:
    return os.environ.get(FORCE_ENV, "").strip().lower() not in (
        "", "0", "false",
    )


def enable_force() -> None:
    """Turn the re-measurement escape hatch on for this process (what the
    CLIs' ``--retune`` flags call): every subsequent ``tune='cached'``
    Create re-measures and refreshes its cache entry."""
    os.environ[FORCE_ENV] = "1"


@dataclasses.dataclass
class TuneStats:
    """Instrumentation counters (reset with :func:`reset_stats`)."""

    measure_runs: int = 0  # individual candidate timings executed
    cache_hits: int = 0
    cache_misses: int = 0
    tuned: int = 0  # autotune() calls that produced a winner
    pruned: int = 0  # candidates skipped by the analytic cost prior
    # candidates dropped because building or running them raised:
    # (kernel, config, exception) — a kernel the compiler refuses shows
    # up here instead of vanishing from the race
    dropped: list = dataclasses.field(default_factory=list)


stats = TuneStats()


def reset_stats() -> TuneStats:
    """Zero the counters in place (the module-level object stays valid)."""
    stats.measure_runs = 0
    stats.cache_hits = 0
    stats.cache_misses = 0
    stats.tuned = 0
    stats.pruned = 0
    stats.dropped.clear()
    return stats


def check_mode(mode: str) -> str:
    if mode not in MODES:
        raise ValueError(f"tune must be one of {MODES}, got {mode!r}")
    return mode


def measure(fn: Callable, *args, warmup: int = 1, repeat: int = 3) -> float:
    """Median microseconds per call (counts toward ``stats.measure_runs``)."""
    stats.measure_runs += 1
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2]


def autotune(
    kernel: str,
    candidates: Sequence[dict],
    build: Callable[[dict], Callable | None],
    args: Sequence,
    *,
    shape,
    dtype,
    bc: str | None = None,
    backend: str | None = None,
    extra=None,
    mode: str = "cached",
    default: dict | None = None,
    cache: TuneCache | None = None,
    prior: Callable[[dict], float | None] | None = None,
) -> dict:
    """Pick the fastest candidate configuration for one kernel problem.

    Returns the winning config dict.  ``mode='off'`` (or an empty/single
    candidate list) short-circuits to ``default`` (or the first
    candidate) without any measurement.  Infeasible candidates —
    ``build`` returning ``None`` — are skipped; a candidate whose build
    or timed call raises is skipped and recorded with its exception in
    ``stats.dropped``.  If every candidate is skipped the default is
    returned.

    ``prior`` is an optional analytic scorer ``config -> predicted time
    proxy`` (see :mod:`repro.tune.prior`): candidates predicted far
    slower than the best prediction are skipped without measurement
    (counted in ``stats.pruned``).  The cache is still consulted against
    the *full* candidate list, so a previously measured winner is
    honoured even if the prior would have pruned it; a prune down to a
    single survivor returns it unmeasured (and uncached — the next
    Create re-derives it from the prior for free).
    """
    check_mode(mode)
    if mode == "cached" and _force_requested():
        mode = "force"  # $REPRO_TUNE_FORCE / --retune: re-measure on hit
    candidates = list(candidates)
    fallback = default if default is not None else (candidates[0] if candidates else {})
    if mode == "off" or len(candidates) <= 1:
        return dict(fallback)

    key = tune_key(
        kernel, shape=shape, dtype=dtype, bc=bc, backend=backend, extra=extra
    )
    cache = cache if cache is not None else TuneCache()

    if mode == "cached":
        best = cache.get(key)
        if isinstance(best, dict) and best in candidates:
            stats.cache_hits += 1
            return dict(best)
        stats.cache_misses += 1

    to_measure = candidates
    if prior is not None:
        from repro.tune.prior import prune_candidates

        to_measure, dropped = prune_candidates(candidates, prior)
        stats.pruned += len(dropped)
        if len(to_measure) == 1:
            return dict(to_measure[0])

    best, best_us = None, float("inf")
    for config in to_measure:
        try:
            fn = build(dict(config))
            if fn is None:  # declared infeasible
                continue
            us = measure(fn, *args)
        except Exception as e:  # noqa: BLE001 — fails to build/compile/run
            stats.dropped.append((kernel, dict(config), e))
            continue
        if us < best_us:
            best, best_us = dict(config), us
    if best is None:
        return dict(fallback)
    stats.tuned += 1
    cache.put(key, best, us=best_us)
    return best
