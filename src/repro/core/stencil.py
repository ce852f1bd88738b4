"""The plan-based stencil engine — cuSten's four-function API in JAX.

cuSten exposes ``custen{Create,Compute,Swap,Destroy}2D{X,Y,XY}{p,np}{,Fun}``
plus the batched-1D family ``custen{Create,Compute,...}1DBatch{p,np}{,Fun}``.
The public JAX equivalents are the **four-function facade** in
:mod:`repro.api` — ``repro.create`` / ``repro.compute`` / ``repro.swap`` /
``repro.destroy``, rank-dispatched over every family defined here.  This
module owns the engine underneath:

- :func:`_create_2d` & co     — Create: validate geometry, capture weights /
  function pointer / boundary mode / tiling, return an immutable plan.
- :meth:`Stencil2D.apply`     — Compute (plans are pytrees: weights are
  leaves, geometry is static aux, so plans pass through jit/vmap/donation).
- :class:`DoubleBuffer`       — Swap (functional pointer flip; under ``jit``
  with donation this is zero-copy, recovering cuSten's pointer swap).
- :func:`plan_destroy`        — Destroy (idempotent mark; JAX buffers are
  GC'd — eager freeing recorded as an intentional non-feature).

The pre-facade per-dimension names (``stencil_create_2d``,
``stencil_compute_2d``, ... — nine in all) remain importable as
one-release deprecation shims at the bottom of this module.

Direction is encoded by the halo extents: an X plan has ``left/right``, a Y
plan ``top/bottom``, an XY plan all four (the library handles the corner
halos, as in the paper).  ``bc='np'`` computes interior points only and
passes the output buffer through untouched on the boundary — the caller
applies their own boundary conditions afterwards, exactly the cuSten
semantics.

**The dimension-agnostic core.** Every plan family shares one Create/Compute
skeleton — halo bookkeeping, ``auto|pallas|jnp`` dispatch, streamed-vs-
monolithic routing, the Create-time ``tune=`` hook, Destroy semantics —
and only the geometry differs.  That skeleton lives once in
:class:`PlanCore`; :class:`Stencil2D`, :class:`StencilBatch1D` and
:class:`Stencil3D` are thin geometry wrappers declaring their kernel entry
points and halo vocabulary.  Adding a new dimensionality is a new wrapper,
not a new engine.

**Batched 1D** (:class:`StencilBatch1D`, :func:`stencil_create_1d_batch`,
:func:`stencil_compute_1d_batch`, :func:`stencil_destroy_1d_batch`): the
same Create/Compute/Destroy contract for applying one 1D stencil to every
row of a ``(B, M)`` stack independently — many 1D problems solved at once
(the cuPentBatch batching model).  On TPU the batch is tiled over the Pallas
grid with ``M`` on the lanes, so the whole batch tile advances per VPU op;
``bc='np'`` passes the ``left``/``right`` edge *columns* of every row
through from ``out_init``.  Typical uses: per-direction explicit RHS
assembly inside ADI sweeps (:mod:`repro.core.adi`), ensembles of independent
1D PDEs, Fourier-space line operators.

**3D** (:class:`Stencil3D`, :func:`stencil_create_3d`,
:func:`stencil_compute_3d`, :func:`stencil_destroy_3d`): the paper's §VI.A
extension on ``(nz, ny, nx)`` fields.  Halos are
``front/back`` (z), ``top/bottom`` (y), ``left/right`` (x); direction
``'x'|'y'|'z'`` takes 1D weights, ``'xyz'`` a full ``(sz, sy, sx)`` box.
Oversized domains stream as z-slabs through
:func:`repro.launch.stream.stream_stencil3d_apply`.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Callable
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ops
from repro.kernels.ref import weighted_point_fn
from repro.util import deprecated_shim

_DIRECTIONS = ("x", "y", "xy")
_DIRECTIONS_3D = ("x", "y", "z", "xyz")
_BCS = ("periodic", "np")
_BACKENDS = ("auto", "pallas", "jnp", "fft")


def _split_extents(n_points: int, lo: int | None, hi: int | None):
    """Resolve a stencil length into (lo, hi) extents around the centre."""
    if lo is None and hi is None:
        if n_points % 2 == 0:
            raise ValueError(
                "even stencil length needs explicit left/right split"
            )
        return n_points // 2, n_points // 2
    if lo is None or hi is None:
        raise ValueError("give both or neither of the extent pair")
    if lo + hi + 1 != n_points:
        raise ValueError(f"extents {lo}+{hi}+1 != stencil length {n_points}")
    return lo, hi


# ---------------------------------------------------------------------------
# The dimension-agnostic plan core
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, kw_only=True)
class PlanCore:
    """Shared Create/Compute machinery of every stencil plan family.

    Holds everything a Compute needs that is *not* geometry: the boundary
    mode, coefficients / function pointer, kernel tile and backend request,
    and the streaming knobs (``streams`` / ``max_tile_bytes`` mirror
    cuSten's ``nStreams`` / ``numStenTop``: when set and the field exceeds
    one tile, Compute routes through the streamed tiled executor in
    :mod:`repro.launch.stream` instead of one monolithic kernel call).

    Subclasses declare their geometry (the halo fields), the tune-cache
    kernel name, and three hooks:

    - :meth:`_halo_kwargs` — the per-family halo keyword vocabulary,
      passed verbatim to both the monolithic and streamed entry points;
    - :meth:`_mono_apply` / :meth:`_stream_apply` — the kernel entry
      points (:mod:`repro.kernels.ops` / :mod:`repro.launch.stream`);
    - :meth:`_pallas_tile_grid` — the Pallas tile candidate space the
      Create-time autotuner measures on TPU.

    Everything else — stream-vs-monolithic dispatch, the ``tune=`` hook,
    Destroy semantics — is inherited, so no plan family carries its own
    copy of the engine.
    """

    bc: str
    coeffs: jnp.ndarray  # stencil weights (weighted mode) or fn coefficients
    point_fn: Callable = weighted_point_fn
    tile: tuple[int, ...] | None = None
    backend: str = "auto"
    interpret: bool | None = None
    streams: int | None = None
    max_tile_bytes: int | None = None
    # registry provenance: set when the weights came from a named operator
    # (repro.api.get_operator) — part of the autotune cache key, so two
    # operators that happen to share a geometry cannot alias one entry
    op_name: str | None = None
    # Fourier symbol of the wrapped stencil kernel (rfftn layout), the
    # Create-time payload of the fft backend: attached when backend='fft'
    # is requested, or speculatively under backend='auto' so the tuner can
    # race fft against the direct paths.  Rides the plan as a pytree leaf.
    symbol: jnp.ndarray | None = None

    kernel_name: ClassVar[str] = "plan"

    @property
    def destroyed(self) -> bool:
        """True once :func:`plan_destroy` / ``repro.destroy`` ran on this
        plan (``repro.compute`` refuses destroyed plans)."""
        return getattr(self, "_destroyed", False)

    # -- geometry hooks (per-family) --------------------------------------
    def _halo_kwargs(self) -> dict:
        raise NotImplementedError

    def _mono_apply(self, *args, **kwargs):
        raise NotImplementedError

    def _stream_apply(self, *args, **kwargs):
        raise NotImplementedError

    def _pallas_tile_grid(self, shape):
        """Aligned Pallas tile candidates for the autotuner (TPU only)."""
        from repro.util import tile_candidates

        d0, d1 = shape[0], shape[1]
        return [
            (t0, t1)
            for t0 in tile_candidates(d0)
            for t1 in tile_candidates(d1)
        ]

    # -- the spectral (fft) backend ----------------------------------------
    def _spectral_spec(self, shape):
        """``(weights_box, los, transform_shape)`` feeding
        :func:`repro.kernels.spectral.stencil_symbol` — per family."""
        raise NotImplementedError

    def _fft_ineligible(self, shape) -> str | None:
        """Why the fft backend cannot serve this plan (None = it can)."""
        if self.bc != "periodic":
            return (
                f"bc={self.bc!r} — the symbol multiply is a *circular* "
                "convolution, so only periodic boundaries diagonalise"
            )
        if self.point_fn is not weighted_point_fn:
            return (
                "function-pointer stencils have no precomputable Fourier "
                "symbol; register explicit weights instead"
            )
        if shape is None:
            return (
                "the symbol is precomputed for one field shape at Create; "
                "pass shape=(...)"
            )
        return None

    def _with_symbol(self, shape) -> "PlanCore":
        """The plan carrying its Create-time Fourier symbol."""
        from repro.kernels import spectral

        box, los, tshape = self._spectral_spec(shape)
        sym = spectral.stencil_symbol(
            box, los, tshape, dtype=self.coeffs.dtype
        )
        return dataclasses.replace(self, symbol=sym)

    def _fft_axes(self) -> tuple[int, ...]:
        """The transformed (trailing) axes — rank read off the symbol."""
        return tuple(range(-self.symbol.ndim, 0))

    def _fft_apply(self, data: jnp.ndarray) -> jnp.ndarray:
        from repro.kernels import spectral

        if self.symbol is None:
            raise spectral.SpectralBackendError(
                "this plan carries no Fourier symbol (Create attaches one "
                "for periodic weighted plans)"
            )
        return spectral.apply_symbol(data, self.symbol, self._fft_axes())

    # -- Compute ----------------------------------------------------------
    @obs.stage("stencil")
    def apply(
        self, data: jnp.ndarray, out_init: jnp.ndarray | None = None
    ) -> jnp.ndarray:
        """Apply the stencil to ``data`` (the Compute call).

        For ``bc='np'`` the cells within the halo of the domain edge are
        copied from ``out_init`` (zeros if not given)."""
        from repro.launch import stream as _stream

        if self.backend == "fft":
            # spectral path: one symbol multiply, never streamed (the fft
            # needs the whole periodic extent; Create validated bc)
            return self._fft_apply(data)
        if _stream.should_stream(
            data.shape,
            jnp.dtype(data.dtype).itemsize,
            streams=self.streams,
            max_tile_bytes=self.max_tile_bytes,
        ):
            return self._stream_apply(
                data,
                self.coeffs,
                out_init,
                point_fn=self.point_fn,
                bc=self.bc,
                streams=self.streams,
                max_tile_bytes=self.max_tile_bytes,
                compute=_stream.resolve_compute(self.backend),
                interpret=self.interpret,
                **self._halo_kwargs(),
            )
        return self._mono_apply(
            data,
            self.coeffs,
            out_init,
            point_fn=self.point_fn,
            bc=self.bc,
            tile=self.tile,
            backend=self.backend,
            interpret=self.interpret,
            **self._halo_kwargs(),
        )

    def __call__(
        self, data: jnp.ndarray, out_init: jnp.ndarray | None = None
    ) -> jnp.ndarray:
        return self.apply(data, out_init)

    # -- Create-time autotuning (the tune= hook) ---------------------------
    def tuned(self, shape, mode: str, cache) -> "PlanCore":
        """Measure tile/backend candidates on a ``shape`` field and return
        the plan with the winning configuration baked in.

        Candidates: the plan's static-heuristic configuration plus (on TPU)
        the family's :meth:`_pallas_tile_grid`.  Off-TPU there is a single
        candidate and :func:`repro.tune.autotune` short-circuits without any
        measurement — tuned and untuned plans are then identical by
        construction (bit-match trivially holds).
        """
        from repro.tune import autotune, check_mode

        check_mode(mode)
        if mode == "off":
            return self
        if shape is None:
            raise ValueError("tune != 'off' needs shape=(...) to measure with")
        data = jnp.zeros(tuple(shape), self.coeffs.dtype)
        default = {"backend": self.backend, "tile": None}
        candidates = [default]
        # backend arbitrage: only 'auto' plans race the fft path — an
        # explicit backend= is an explicit choice, and the fp64
        # result-invariance contract (tuned == untuned bit-for-bit) only
        # holds when tuning cannot change the arithmetic
        if self.backend == "auto" and self.symbol is not None:
            candidates.append({"backend": "fft", "tile": None})
        if ops.on_tpu():
            for t in self._pallas_tile_grid(shape):
                candidates.append({"backend": "pallas", "tile": list(t)})

        halo_kwargs = self._halo_kwargs()

        def build(cfg):
            if cfg["backend"] == "fft":
                from repro.kernels import spectral

                sym, axes = self.symbol, self._fft_axes()

                def g(d):
                    return spectral.apply_symbol(d, sym, axes)

                return jax.jit(g)
            tile = tuple(cfg["tile"]) if cfg.get("tile") else None

            def f(d):
                return self._mono_apply(
                    d, self.coeffs, None, point_fn=self.point_fn,
                    bc=self.bc, tile=tile, backend=cfg["backend"],
                    interpret=self.interpret, **halo_kwargs,
                )

            return jax.jit(f)

        extra = {
            "halo": [int(h) for h in self.halo],
            "fn": getattr(self.point_fn, "__name__", "fn"),
            "op": self.op_name,
        }
        # the analytic cost prior (repro.tune.prior): rank candidates by
        # the cost model before measuring, so a backend predicted far off
        # the pace (e.g. fft for a radius-1 kernel) never races at all —
        # winner invariance is preserved by the conservative prune band
        prior = None
        if len(candidates) > 1:
            from repro.tune.prior import prior_enabled, stencil_prior

            if prior_enabled():
                import numpy as np

                taps = int(np.count_nonzero(np.asarray(self.coeffs)))
                prior = stencil_prior(
                    tuple(shape), max(taps, 1), data.dtype.itemsize
                )
        best = autotune(
            self.kernel_name, candidates, build, (data,),
            shape=shape, dtype=data.dtype, bc=self.bc, backend=self.backend,
            extra=extra, mode=mode, default=default, cache=cache,
            prior=prior,
        )
        tile = tuple(best["tile"]) if best.get("tile") else None
        return dataclasses.replace(self, tile=tile, backend=best["backend"])


def plan_destroy(plan) -> None:
    """API-parity Destroy, shared by every plan family (and by
    ``repro.destroy``).  JAX buffers are reference counted, so no memory
    is freed here; the plan is only *marked* destroyed, after which
    ``repro.compute`` refuses it.

    Idempotent by contract: destroying an already-destroyed plan, ``None``,
    or an object that cannot carry the mark (e.g. a slotted
    :class:`DoubleBuffer`) is a silent no-op — double-Destroy must never
    raise."""
    if plan is None:
        return
    try:
        # frozen dataclasses forbid normal attribute writes; plans are
        # immutable, so the destroyed mark goes in through the back door
        object.__setattr__(plan, "_destroyed", True)
    except (AttributeError, TypeError):
        pass  # slotted / exotic objects: Destroy stays a no-op for them


# ---------------------------------------------------------------------------
# Pytree registration: plans cross jit/vmap/donation boundaries
# ---------------------------------------------------------------------------


def _hashable(value):
    """Lists (e.g. a ``tile`` that round-tripped through the JSON tune
    cache) become tuples so the pytree aux data is hashable."""
    if isinstance(value, list):
        return tuple(_hashable(v) for v in value)
    return value


def _register_plan_pytree(cls) -> None:
    """Register a :class:`PlanCore` subclass as a JAX pytree.

    The array payload — ``coeffs`` (stencil weights or function-pointer
    coefficients) and the optional fft ``symbol`` — are the leaves; every
    other field (geometry, halo extents, boundary mode, backend/tile/stream
    knobs, the point function) is static aux data.  A jitted
    ``compute(plan, x)`` therefore retraces only when the aux changes —
    swapping in new weight *values* of the same shape/dtype reuses the
    trace (asserted in tests/test_api.py).
    """
    static = tuple(
        f.name
        for f in dataclasses.fields(cls)
        if f.name not in ("coeffs", "symbol")
    )

    def flatten(plan):
        # the destroyed mark travels in the aux so a jitted
        # compute(plan, x) sees it too: a destroyed plan has a different
        # treedef, forcing a retrace where compute's refusal fires
        aux = tuple(_hashable(getattr(plan, name)) for name in static)
        return (plan.coeffs, plan.symbol), aux + (plan.destroyed,)

    def unflatten(aux, leaves):
        # aux carries a trailing destroyed flag beyond the static fields
        kwargs = dict(zip(static, aux, strict=False))
        kwargs["coeffs"], kwargs["symbol"] = leaves
        plan = cls(**kwargs)
        if aux[-1]:
            object.__setattr__(plan, "_destroyed", True)
        return plan

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)


def _finish_plan(plan: PlanCore, shape, tune: str, tune_cache) -> PlanCore:
    """The shared Create tail: spectral validation / symbol attachment,
    then the ``tune=`` hook.

    ``backend='fft'`` is validated here *at Create* — non-periodic
    boundaries, function-pointer stencils and a missing ``shape=`` raise
    :class:`repro.kernels.spectral.SpectralBackendError` instead of
    silently computing wrong answers.  Under ``backend='auto'`` with
    tuning on, an eligible plan gets its symbol attached speculatively so
    :meth:`PlanCore.tuned` can race fft against the direct backends.
    """
    from repro.kernels.spectral import SpectralBackendError

    if plan.backend not in _BACKENDS:
        raise ValueError(
            f"backend must be one of {_BACKENDS}, got {plan.backend!r}"
        )
    wants_fft = plan.backend == "fft"
    arbitrage = plan.backend == "auto" and tune != "off"
    if wants_fft or arbitrage:
        reason = plan._fft_ineligible(shape)
        if reason is None:
            plan = plan._with_symbol(shape)
        elif wants_fft:
            raise SpectralBackendError(reason)
    return plan.tuned(shape, tune, tune_cache)


# ---------------------------------------------------------------------------
# 2D plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, kw_only=True)
class Stencil2D(PlanCore):
    """An immutable 2D stencil plan (the ``cuSten_t`` analogue)."""

    direction: str
    left: int
    right: int
    top: int
    bottom: int

    kernel_name: ClassVar[str] = "stencil2d"

    def _halo_kwargs(self) -> dict:
        return dict(
            left=self.left, right=self.right, top=self.top, bottom=self.bottom
        )

    def _mono_apply(self, *args, **kwargs):
        return ops.stencil_apply(*args, **kwargs)

    def _stream_apply(self, *args, **kwargs):
        from repro.launch import stream as _stream

        return _stream.stream_stencil_apply(*args, **kwargs)

    def _spectral_spec(self, shape):
        box = jnp.reshape(
            self.coeffs,
            (self.top + self.bottom + 1, self.left + self.right + 1),
        )
        return box, (self.top, self.left), tuple(shape)

    @property
    def num_sten(self) -> int:
        return (self.left + self.right + 1) * (self.top + self.bottom + 1)

    @property
    def halo(self) -> tuple[int, int, int, int]:
        return (self.left, self.right, self.top, self.bottom)

    def grid_problems(self, shape) -> list:
        """Why this plan's tile/grid cannot cover ``shape`` — empty when
        feasible (the ``pallas_grid_feasible`` audit rule's probe)."""
        ny, nx = (int(s) for s in shape)
        hx, hy = max(self.left, self.right), max(self.top, self.bottom)
        problems = []
        if hy > ny or hx > nx:
            problems.append(
                f"halo (hy={hy}, hx={hx}) exceeds the field ({ny}, {nx}); "
                "the stencil is wider than the domain"
            )
        if self.tile is not None and self.backend != "jnp":
            ty, tx = self.tile
            if not ops.pallas_grid_ok(ny, nx, ty, tx, hx, hy):
                problems.append(
                    f"explicit tile ({ty}, {tx}) cannot grid the field "
                    f"({ny}, {nx}) with halo (hy={hy}, hx={hx}): the Pallas "
                    "path needs tile|field and halo<=tile"
                )
        return problems


def _create_2d(
    direction: str,
    bc: str,
    *,
    weights=None,
    func: Callable | None = None,
    coeffs=None,
    num_sten_left: int | None = None,
    num_sten_right: int | None = None,
    num_sten_top: int | None = None,
    num_sten_bottom: int | None = None,
    tile: tuple[int, int] | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    tune: str = "off",
    shape: tuple[int, int] | None = None,
    tune_cache=None,
    op_name: str | None = None,
) -> Stencil2D:
    """Create a stencil plan (the Create call).

    Weighted mode: pass ``weights`` — 1D of length ``numSten`` for X/Y
    (with ``num_sten_left/right`` or top/bottom; symmetric split inferred for
    odd lengths), or 2D ``(sy, sx)`` for XY.

    Function mode (the paper's ``Fun`` variants): pass ``func(windows,
    coeffs)`` plus ``coeffs`` and the explicit extents.  ``windows`` is the
    row-major list of shifted views from the top-left of the stencil — the
    indexing convention of paper §V.B.

    ``streams``/``max_tile_bytes`` enable the streamed tiled executor for
    oversized domains (cuSten ``nStreams``; see :mod:`repro.launch.stream`).
    """
    if direction not in _DIRECTIONS:
        raise ValueError(f"direction must be one of {_DIRECTIONS}")
    if bc not in _BCS:
        raise ValueError(f"bc must be one of {_BCS}")
    if (weights is None) == (func is None):
        raise ValueError("exactly one of weights / func must be given")

    if weights is not None:
        w = jnp.asarray(weights)
        if direction == "x":
            if w.ndim != 1:
                raise ValueError("x stencil weights must be 1D")
            left, right = _split_extents(w.shape[0], num_sten_left, num_sten_right)
            top = bottom = 0
        elif direction == "y":
            if w.ndim != 1:
                raise ValueError("y stencil weights must be 1D")
            top, bottom = _split_extents(w.shape[0], num_sten_top, num_sten_bottom)
            left = right = 0
        else:  # xy
            if w.ndim != 2:
                raise ValueError("xy stencil weights must be 2D (sy, sx)")
            top, bottom = _split_extents(w.shape[0], num_sten_top, num_sten_bottom)
            left, right = _split_extents(w.shape[1], num_sten_left, num_sten_right)
        coeffs, point_fn = w.ravel(), weighted_point_fn
    else:
        # function-pointer mode
        left = num_sten_left or 0
        right = num_sten_right or 0
        top = num_sten_top or 0
        bottom = num_sten_bottom or 0
        if direction == "x" and (top or bottom):
            raise ValueError("x stencil cannot have top/bottom extents")
        if direction == "y" and (left or right):
            raise ValueError("y stencil cannot have left/right extents")
        if coeffs is None:
            coeffs = jnp.zeros((1,), jnp.float32)
        coeffs, point_fn = jnp.asarray(coeffs), func

    plan = Stencil2D(
        direction=direction,
        bc=bc,
        left=left,
        right=right,
        top=top,
        bottom=bottom,
        coeffs=coeffs,
        point_fn=point_fn,
        tile=tile,
        backend=backend,
        interpret=interpret,
        streams=streams,
        max_tile_bytes=max_tile_bytes,
        op_name=op_name,
    )
    return _finish_plan(plan, shape, tune, tune_cache)


# ---------------------------------------------------------------------------
# Batched-1D plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, kw_only=True)
class StencilBatch1D(PlanCore):
    """An immutable batched-1D stencil plan (cuSten's ``1DBatch`` family).

    Applies one 1D stencil (extents ``left``/``right``) along axis 1 of a
    ``(B, M)`` stack, every row independently.
    """

    left: int
    right: int

    kernel_name: ClassVar[str] = "stencil1d_batch"

    def _halo_kwargs(self) -> dict:
        return dict(left=self.left, right=self.right)

    def _mono_apply(self, *args, **kwargs):
        return ops.stencil_apply_batch1d(*args, **kwargs)

    def _stream_apply(self, *args, **kwargs):
        from repro.launch import stream as _stream

        return _stream.stream_batch1d_apply(*args, **kwargs)

    def _spectral_spec(self, shape):
        # each row of the (B, M) stack transforms independently; the 1D
        # symbol broadcasts over the batch axis
        return self.coeffs, (self.left,), (tuple(shape)[-1],)

    @property
    def num_sten(self) -> int:
        return self.left + self.right + 1

    @property
    def halo(self) -> tuple[int, int]:
        return (self.left, self.right)

    def grid_problems(self, shape) -> list:
        """Why this plan's tile/grid cannot cover the ``(B, M)`` stack —
        empty when feasible."""
        B, M = (int(s) for s in shape)
        hm = max(self.left, self.right)
        problems = []
        if hm > M:
            problems.append(
                f"line halo hm={hm} exceeds the row length M={M}; the "
                "stencil is wider than the line"
            )
        if self.tile is not None and self.backend != "jnp":
            tb, tm = self.tile
            if not ops.pallas_grid_ok_1d(B, M, tb, tm, hm):
                problems.append(
                    f"explicit tile ({tb}, {tm}) cannot grid the stack "
                    f"({B}, {M}) with halo hm={hm}: the Pallas path needs "
                    "tile|stack and halo<=tile"
                )
        return problems


def _create_1d_batch(
    bc: str,
    *,
    weights=None,
    func: Callable | None = None,
    coeffs=None,
    num_sten_left: int | None = None,
    num_sten_right: int | None = None,
    tile: tuple[int, int] | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    tune: str = "off",
    shape: tuple[int, int] | None = None,
    tune_cache=None,
    op_name: str | None = None,
) -> StencilBatch1D:
    """Create a batched-1D stencil plan (cuSten ``custenCreate1DBatch*``).

    Weighted mode: pass 1D ``weights`` of length ``numSten`` (symmetric
    split inferred for odd lengths, or give ``num_sten_left/right``).
    Function mode (``Fun`` variants): pass ``func(windows, coeffs)`` plus
    ``coeffs`` and the explicit extents; ``windows`` sweeps left→right.
    """
    if bc not in _BCS:
        raise ValueError(f"bc must be one of {_BCS}")
    if (weights is None) == (func is None):
        raise ValueError("exactly one of weights / func must be given")

    if weights is not None:
        w = jnp.asarray(weights)
        if w.ndim != 1:
            raise ValueError("batched-1D stencil weights must be 1D")
        left, right = _split_extents(
            w.shape[0], num_sten_left, num_sten_right
        )
        coeffs, point_fn = w, weighted_point_fn
    else:
        # function-pointer mode
        left = num_sten_left or 0
        right = num_sten_right or 0
        if coeffs is None:
            coeffs = jnp.zeros((1,), jnp.float32)
        coeffs, point_fn = jnp.asarray(coeffs), func

    plan = StencilBatch1D(
        bc=bc,
        left=left,
        right=right,
        coeffs=coeffs,
        point_fn=point_fn,
        tile=tile,
        backend=backend,
        interpret=interpret,
        streams=streams,
        max_tile_bytes=max_tile_bytes,
        op_name=op_name,
    )
    return _finish_plan(plan, shape, tune, tune_cache)


# ---------------------------------------------------------------------------
# 3D plans (paper §VI.A, the plan core's first new client)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, kw_only=True)
class Stencil3D(PlanCore):
    """An immutable 3D stencil plan on ``(nz, ny, nx)`` fields.

    Halos follow the :func:`repro.kernels.ref.stencil3d_ref` convention:
    ``front/back`` along z, ``top/bottom`` along y, ``left/right`` along x.
    Oversized domains stream as z-slab chunks
    (:func:`repro.launch.stream.stream_stencil3d_apply`).
    """

    direction: str
    front: int
    back: int
    top: int
    bottom: int
    left: int
    right: int

    kernel_name: ClassVar[str] = "stencil3d"

    def _halo_kwargs(self) -> dict:
        return dict(halos=self.halos)

    def _mono_apply(self, *args, **kwargs):
        return ops.stencil_apply_3d(*args, **kwargs)

    def _stream_apply(self, *args, **kwargs):
        from repro.launch import stream as _stream

        return _stream.stream_stencil3d_apply(*args, **kwargs)

    def _pallas_tile_grid(self, shape):
        # blocks carry the full x row; candidates tile (z, y) only.  z is
        # the outer (unaligned) axis so small divisors suffice; y rides the
        # sublanes and keeps the aligned candidate set.
        from repro.util import tile_candidates

        nz, ny = shape[0], shape[1]
        tzs = [t for t in (16, 8, 4) if nz % t == 0][:2] or [1]
        return [(tz, ty) for tz in tzs for ty in tile_candidates(ny)]

    def _spectral_spec(self, shape):
        box = jnp.reshape(
            self.coeffs,
            (
                self.front + self.back + 1,
                self.top + self.bottom + 1,
                self.left + self.right + 1,
            ),
        )
        return box, (self.front, self.top, self.left), tuple(shape)

    @property
    def num_sten(self) -> int:
        return (
            (self.front + self.back + 1)
            * (self.top + self.bottom + 1)
            * (self.left + self.right + 1)
        )

    @property
    def halo(self) -> tuple[int, int, int, int, int, int]:
        return self.halos

    @property
    def halos(self) -> tuple[int, int, int, int, int, int]:
        """(front, back, top, bottom, left, right) — the kernel's order."""
        return (
            self.front, self.back, self.top, self.bottom,
            self.left, self.right,
        )

    def grid_problems(self, shape) -> list:
        """Why this plan's tile/grid cannot cover the ``(nz, ny, nx)`` box
        — empty when feasible."""
        nz, ny, nx = (int(s) for s in shape)
        hz = max(self.front, self.back)
        hy = max(self.top, self.bottom)
        hx = max(self.left, self.right)
        problems = []
        if hz > nz or hy > ny or hx > nx:
            problems.append(
                f"halo (hz={hz}, hy={hy}, hx={hx}) exceeds the field "
                f"({nz}, {ny}, {nx}); the stencil is wider than the domain"
            )
        if self.tile is not None and self.backend != "jnp":
            tz, ty = self.tile
            if not ops.pallas_grid_ok_3d(nz, ny, nx, tz, ty, hz, hy, hx):
                problems.append(
                    f"explicit tile (tz={tz}, ty={ty}) cannot grid the "
                    f"field ({nz}, {ny}, {nx}) with halo (hz={hz}, hy={hy}, "
                    f"hx={hx}): the Pallas path needs tile|field and "
                    "halo<=tile"
                )
        return problems


def _create_3d(
    direction: str,
    bc: str,
    *,
    weights=None,
    func: Callable | None = None,
    coeffs=None,
    num_sten_front: int | None = None,
    num_sten_back: int | None = None,
    num_sten_top: int | None = None,
    num_sten_bottom: int | None = None,
    num_sten_left: int | None = None,
    num_sten_right: int | None = None,
    tile: tuple[int, int] | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    tune: str = "off",
    shape: tuple[int, int, int] | None = None,
    tune_cache=None,
    op_name: str | None = None,
) -> Stencil3D:
    """Create a 3D stencil plan (the §VI.A Create call).

    Weighted mode: 1D ``weights`` for directions ``'x'|'y'|'z'`` (symmetric
    split inferred for odd lengths, or the explicit extent pair), or a 3D
    ``(sz, sy, sx)`` box for ``'xyz'``.  Function mode: ``func(windows,
    coeffs)`` plus the explicit extents; windows are enumerated z-major,
    then row-major over (y, x) — the §V.B convention lifted to 3D.

    ``tile`` is the Pallas ``(tz, ty)`` block of the (z, y) grid (each
    block carries the full x row).  ``streams``/``max_tile_bytes`` stream
    oversized domains as z-slab chunks.
    """
    if direction not in _DIRECTIONS_3D:
        raise ValueError(f"direction must be one of {_DIRECTIONS_3D}")
    if bc not in _BCS:
        raise ValueError(f"bc must be one of {_BCS}")
    if (weights is None) == (func is None):
        raise ValueError("exactly one of weights / func must be given")

    front = back = top = bottom = left = right = 0
    if weights is not None:
        w = jnp.asarray(weights)
        if direction == "xyz":
            if w.ndim != 3:
                raise ValueError("xyz stencil weights must be 3D (sz, sy, sx)")
            front, back = _split_extents(w.shape[0], num_sten_front, num_sten_back)
            top, bottom = _split_extents(w.shape[1], num_sten_top, num_sten_bottom)
            left, right = _split_extents(w.shape[2], num_sten_left, num_sten_right)
        else:
            if w.ndim != 1:
                raise ValueError(f"{direction} stencil weights must be 1D")
            if direction == "x":
                left, right = _split_extents(w.shape[0], num_sten_left, num_sten_right)
            elif direction == "y":
                top, bottom = _split_extents(w.shape[0], num_sten_top, num_sten_bottom)
            else:  # z
                front, back = _split_extents(w.shape[0], num_sten_front, num_sten_back)
        coeffs, point_fn = w.ravel(), weighted_point_fn
    else:
        # function-pointer mode
        front = num_sten_front or 0
        back = num_sten_back or 0
        top = num_sten_top or 0
        bottom = num_sten_bottom or 0
        left = num_sten_left or 0
        right = num_sten_right or 0
        off_axis = {
            "x": front or back or top or bottom,
            "y": front or back or left or right,
            "z": top or bottom or left or right,
            "xyz": 0,
        }[direction]
        if off_axis:
            raise ValueError(
                f"{direction} stencil cannot have off-axis extents"
            )
        if coeffs is None:
            coeffs = jnp.zeros((1,), jnp.float32)
        coeffs, point_fn = jnp.asarray(coeffs), func

    plan = Stencil3D(
        direction=direction,
        bc=bc,
        front=front,
        back=back,
        top=top,
        bottom=bottom,
        left=left,
        right=right,
        coeffs=coeffs,
        point_fn=point_fn,
        tile=tile,
        backend=backend,
        interpret=interpret,
        streams=streams,
        max_tile_bytes=max_tile_bytes,
        op_name=op_name,
    )
    return _finish_plan(plan, shape, tune, tune_cache)


class DoubleBuffer:
    """cuSten's Swap: flip input/output fields between time steps.

    >>> buf = DoubleBuffer(c0, jnp.zeros_like(c0))
    >>> buf.new = plan.apply(buf.old); buf.swap()
    """

    __slots__ = ("old", "new")

    def __init__(self, old: jnp.ndarray, new: jnp.ndarray | None = None):
        self.old = old
        self.new = jnp.zeros_like(old) if new is None else new

    def swap(self) -> "DoubleBuffer":
        self.old, self.new = self.new, self.old
        return self


# Convenience constructors for classic schemes --------------------------------


def central_difference_weights(order: int, derivative: int, h: float = 1.0):
    """Weights of the central finite difference of given accuracy ``order``
    (even) for ``derivative`` (1 or 2), via the standard Fornberg algorithm.

    Returns a numpy array of length ``order + derivative - (derivative % 2) + 1``
    scaled by ``h**-derivative``."""
    import math as _math

    if order % 2:
        raise ValueError("order must be even for central differences")
    npts = 2 * ((order + derivative - 1) // 2) + 1
    offsets = np.arange(npts) - npts // 2
    # Solve the Vandermonde system: sum_k w_k * off_k^m = m! * delta_{m,deriv}
    A = np.vander(offsets, npts, increasing=True).T.astype(np.float64)
    b = np.zeros(npts)
    b[derivative] = _math.factorial(derivative)
    w = np.linalg.solve(A, b)
    return w / h**derivative


def laplacian3d_weights(h: float = 1.0) -> np.ndarray:
    """7-point 3D Laplacian as a ``(3, 3, 3)`` box (units ``h^-2``)."""
    w = np.zeros((3, 3, 3))
    w[1, 1, 0] = w[1, 1, 2] = 1.0
    w[1, 0, 1] = w[1, 2, 1] = 1.0
    w[0, 1, 1] = w[2, 1, 1] = 1.0
    w[1, 1, 1] = -6.0
    return w / h**2


# every plan family is a pytree: weights are leaves, geometry is static —
# plans pass *through* jit/vmap/donation instead of forcing closure capture
for _cls in (Stencil2D, StencilBatch1D, Stencil3D):
    _register_plan_pytree(_cls)
del _cls


# ---------------------------------------------------------------------------
# Deprecated per-dimension entry points (one release; use repro.api)
# ---------------------------------------------------------------------------


def _compute_impl(plan, data, out_init=None):
    return plan.apply(data, out_init)


_deprecated_shim = deprecated_shim


stencil_create_2d = _deprecated_shim("stencil_create_2d", "create", _create_2d)
stencil_compute_2d = _deprecated_shim(
    "stencil_compute_2d", "compute", _compute_impl
)
stencil_destroy_2d = _deprecated_shim(
    "stencil_destroy_2d", "destroy", plan_destroy
)
stencil_create_1d_batch = _deprecated_shim(
    "stencil_create_1d_batch", "create", _create_1d_batch
)
stencil_compute_1d_batch = _deprecated_shim(
    "stencil_compute_1d_batch", "compute", _compute_impl
)
stencil_destroy_1d_batch = _deprecated_shim(
    "stencil_destroy_1d_batch", "destroy", plan_destroy
)
stencil_create_3d = _deprecated_shim("stencil_create_3d", "create", _create_3d)
stencil_compute_3d = _deprecated_shim(
    "stencil_compute_3d", "compute", _compute_impl
)
stencil_destroy_3d = _deprecated_shim(
    "stencil_destroy_3d", "destroy", plan_destroy
)
