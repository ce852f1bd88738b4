"""Bucketing policy: which requests share one kernel dispatch, and how.

Batching many independent solves into one launch is the cuPentBatch
thesis (PAPERS.md, arXiv 1807.07382), and the library already has the
machinery — batched-1D plans, pytree plans that pass through ``vmap``.
This module is the policy layer that maps a drained batch of
:class:`~repro.serve.request.SolveRequest` onto it:

- **bucket key** — requests sharing ``(shape, dtype, operator, bc,
  mode, alpha, steps)`` land in one bucket; a bucket is the unit of
  dispatch.
- **rank-1 requests** (``kind='batch1d'``) stack into a ``(B, M)`` field
  and ride one :class:`~repro.core.stencil.StencilBatch1D` plan — many
  lines, one launch, bit-identical per row to a sequential ``(1, M)``
  solve (the batched-1D kernel never mixes rows).
- **rank-2/3 stencil requests** (``kind='stencil'``) stack on a new
  leading axis and run under ``jax.vmap`` of the plan's Compute — one
  launch for the whole bucket, bit-identical per member (``vmap`` of the
  explicit apply touches each member independently).
- **ADI requests** (``kind='adi'``) are *plan-multiplexed, not stacked*:
  the implicit pentadiagonal recurrences do **not** commute bitwise with
  ``vmap``/``lax.map`` re-vectorisation (measured: ~1 ulp drift), and
  the engine's contract is bit-identity with sequential
  ``repro.create``/``repro.compute`` — so ADI buckets reuse one warm
  LRU plan (skipping the expensive per-request factorisation) and
  dispatch member-by-member, exactly the sequential arithmetic.

Batch-shape quantisation: stacked buckets are zero-padded up to the next
power of two (capped at the engine's ``max_batch``) so a stream of
ragged batch sizes compiles a handful of stacked kernels instead of one
per size.  Padding rows are discarded after the launch; because every
batching family treats members independently, padding cannot perturb
real rows.
"""

from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro import api as _api
from repro.serve.request import SolveRequest

BATCH1D = "batch1d"
STENCIL = "stencil"
ADI = "adi"


def classify(req: SolveRequest) -> str:
    """The batching family a request rides: batch1d | stencil | adi."""
    if req.mode == "adi":
        return ADI
    if len(req.shape) == 1:
        return BATCH1D
    return STENCIL


def bucket_key(req: SolveRequest) -> tuple:
    """Requests with equal keys share one plan *and* one dispatch."""
    return (
        req.operator,
        req.shape,
        str(req.resolved_dtype()),
        req.bc,
        req.mode or "stencil",
        None if req.alpha is None else float(req.alpha),
        int(req.steps),
    )


def bucketize(requests) -> "OrderedDict[tuple, list]":
    """Group a drained batch into buckets, preserving arrival order both
    across buckets (first-seen order) and within each bucket."""
    buckets: OrderedDict[tuple, list] = OrderedDict()
    for item in requests:
        req = item[0] if isinstance(item, tuple) else item
        buckets.setdefault(bucket_key(req), []).append(item)
    return buckets


def plan_spec(req: SolveRequest, *, backend: str = "auto") -> tuple[str, str, dict]:
    """``(kind, key, create_kwargs)`` — how to key and build the plan.

    ``key`` is :func:`repro.api.plan_key` over the *logical* request
    shape; ``create_kwargs`` are the arguments a cache miss passes to
    :func:`repro.create`.  Rank-1 requests create their
    :class:`StencilBatch1D` plan with a ``(1, M)`` placeholder shape —
    batched-1D plans are batch-size-agnostic, so one plan serves every
    stacked ``(B, M)``.
    """
    kind = classify(req)
    dtype = req.resolved_dtype()
    mode: str | None
    if kind == BATCH1D:
        shape: tuple = (1,) + req.shape
        mode = "batch"
    else:
        shape = req.shape
        mode = req.mode
    key = _api.plan_key(
        req.operator,
        req.shape,
        dtype=dtype,
        bc=req.bc,
        mode=mode,
        alpha=req.alpha,
        extra={"backend": backend},
    )
    kwargs = dict(shape=shape, bc=req.bc, dtype=dtype, backend=backend)
    if kind == ADI:
        kwargs.update(mode="adi", alpha=req.alpha)
    elif kind == BATCH1D:
        kwargs.update(mode="batch")
    return kind, key, kwargs


def create_plan(req: SolveRequest, *, backend: str = "auto", tune: str = "off"):
    """Create the plan for one request class (the LRU-miss factory)."""
    _, _, kwargs = plan_spec(req, backend=backend)
    shape = kwargs.pop("shape")
    return _api.create(req.operator, shape, tune=tune, **kwargs)


def quantize_batch(b: int, max_batch: int) -> int:
    """Round a bucket size up to the next power of two, capped at
    ``max_batch`` — the batch-shape quantisation that bounds how many
    stacked-kernel variants ragged traffic can compile.

    >>> [quantize_batch(b, 16) for b in (1, 2, 3, 5, 9, 16)]
    [1, 2, 4, 8, 16, 16]
    """
    p = 1
    while p < b:
        p *= 2
    return min(p, max_batch) if b <= max_batch else b


# The steps run as loop iterations, not unrolled: XLA would otherwise fuse
# one step's arithmetic into the next and round differently (1 ulp on an
# AVX-512 host) from the sequential one-step Computes the engine matches.


@functools.partial(jax.jit, static_argnums=(2,))
def _run_stacked_batch1d(plan, stack, steps: int):
    """One launch for a stacked (B, M) bucket of rank-1 requests."""
    return jax.lax.fori_loop(0, steps, lambda _, s: _api.compute(plan, s), stack)


@functools.partial(jax.jit, static_argnums=(2,))
def _run_stacked_stencil(plan, stack, steps: int):
    """One vmapped launch for a stacked bucket of 2D/3D stencil requests."""

    def one(field):
        return _api.compute(plan, field)

    return jax.lax.fori_loop(0, steps, lambda _, s: jax.vmap(one)(s), stack)


def execute_bucket(plan, kind: str, fields, steps: int, *, max_batch: int = 64):
    """Solve one bucket; returns per-request outputs in input order, as
    **host** arrays (results cross the serving boundary anyway, and one
    ``device_get`` of the stacked output costs microseconds where
    per-row eager slicing costs ~80us/request in dispatch — measured to
    dominate the stacked kernel itself).

    Stacked kinds assemble the padded ``(B, ...)`` batch in numpy (one
    device upload, vs one eager ``jnp.stack`` dispatch per drain — the
    other measured dispatch hotspot), launch once, and hand back views
    of the downloaded result; ADI buckets run member-by-member on the
    shared warm plan (see the module docstring for why).
    """
    if kind == ADI:
        outs = []
        for field in fields:
            out = field
            for _ in range(steps):
                out = _api.compute(plan, out)
            outs.append(out)
        return jax.device_get(outs)

    b = len(fields)
    padded = quantize_batch(b, max_batch)
    arr = np.stack([np.asarray(f) for f in fields])
    if padded > b:
        arr = np.concatenate(
            [arr, np.zeros((padded - b,) + arr.shape[1:], arr.dtype)]
        )
    stack = jnp.asarray(arr)
    run = _run_stacked_batch1d if kind == BATCH1D else _run_stacked_stencil
    out_host = jax.device_get(run(plan, stack, steps))
    return [out_host[i] for i in range(b)]
