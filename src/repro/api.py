"""The unified four-function facade — cuSten's pitch, one entry point.

cuSten wraps "data handling, kernel calls and streaming into four easy to
use functions": Create / Compute / Swap / Destroy.  This module is the JAX
equivalent across *every* plan family the library grew — 2D, batched-1D,
and 3D stencils, plus the 2D/3D ADI operators — keyed by problem geometry
instead of one function per problem family:

- :func:`create` — infer the plan family from the rank/geometry of
  ``shape`` (and the ``mode=`` hint), build + optionally autotune the
  right plan: :class:`~repro.core.stencil.Stencil2D`,
  :class:`~repro.core.stencil.StencilBatch1D`,
  :class:`~repro.core.stencil.Stencil3D`,
  :class:`~repro.core.adi.ADIOperator` or
  :class:`~repro.core.adi.ADIOperator3D` (``mode='adi'``).
- :func:`compute` — the single apply path for any plan.
- :func:`swap` — the double-buffer pointer flip between time steps
  (tuples or :class:`~repro.core.stencil.DoubleBuffer`; under ``jit``
  with donation this is zero-copy, cuSten's pointer swap).
- :func:`destroy` — unified, idempotent teardown.

Every plan is a **JAX pytree** (arrays — stencil weights, pentadiagonal
factors, the Woodbury ``W`` — as leaves; geometry and tuning config as
static aux), so plans pass *through* ``jit`` / ``vmap`` / donation as
arguments instead of forcing closure capture, and a jitted
``compute(plan, x)`` retraces only when the static aux changes.

The **operator registry** (:func:`register_operator` /
:func:`get_operator`) is the single source of named difference operators:
each entry carries stencil ``weights`` builders (by dimensionality)
and/or ADI band ``diagonals`` builders.  Built-ins: ``"laplacian"``,
``"biharmonic"``, ``"hyperdiffusion"``, ``"diffusion"`` — and
user-registered operators participate in :func:`create` (both stencil and
``mode='adi'`` paths) exactly like the built-ins.  The operator name is
baked into autotune cache keys, so two operators sharing a geometry never
alias one tuning entry.

>>> import jax.numpy as jnp
>>> import repro
>>> field = jnp.zeros((256, 256))
>>> plan = repro.create("laplacian", (256, 256), bc="periodic")  # Create
>>> out = repro.compute(plan, field)                             # Compute
>>> field, out = repro.swap((out, field))                        # Swap
>>> repro.destroy(plan)                                          # Destroy

:func:`plan_key` gives every such plan request a canonical string
identity — the key of the serving engine's warm-plan LRU
(:mod:`repro.serve`), a sibling of the autotuner's
:func:`repro.tune.cache.tune_key`.
"""

from __future__ import annotations

import dataclasses
import json
from collections.abc import Callable

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import adi as _adi
from repro.core import stencil as _stencil
from repro.kernels.penta import (
    diffusion_diagonals,
    hyperdiffusion_diagonals,
)

__all__ = [
    "OperatorDef",
    "compute",
    "create",
    "destroy",
    "get_operator",
    "operator_names",
    "plan_key",
    "register_operator",
    "swap",
]


# ---------------------------------------------------------------------------
# The operator registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OperatorDef:
    """A named difference operator.

    ``weights(ndim, h=1.0)`` returns the explicit stencil weights for an
    ``ndim``-dimensional field (1D weights serve the batched-1D family
    and the per-direction 2D/3D plans); ``diagonals(n, alpha, dtype)``
    returns the pentadiagonal bands of the implicit per-direction
    operator for ADI plans.  Either may be ``None`` — an operator can be
    stencil-only (``"biharmonic"``) or band-only (``"diffusion"``)."""

    name: str
    weights: Callable | None = None
    diagonals: Callable | None = None
    doc: str = ""
    # declared analytic properties — what stencil-lint may verify.  None
    # means "undeclared": lint never second-guesses math it wasn't told.
    derivative: int | None = None
    symmetric: bool | None = None
    zero_sum: bool | None = None


_REGISTRY: dict[str, OperatorDef] = {}


def register_operator(
    name: str,
    *,
    weights: Callable | None = None,
    diagonals: Callable | None = None,
    doc: str = "",
    overwrite: bool = False,
    derivative: int | None = None,
    symmetric: bool | None = None,
    zero_sum: bool | None = None,
    lint: str = "warn",
) -> OperatorDef:
    """Register a named operator for :func:`create` (and the ADI band
    resolution in :mod:`repro.core.adi`).

    ``weights(ndim, h=1.0) -> array`` builds explicit stencil weights;
    ``diagonals(n, alpha, dtype) -> bands`` builds the implicit
    pentadiagonal bands (the :mod:`repro.kernels.penta` convention:
    five length-``n`` diagonals ``l2, l1, d, u1, u2``).  At least one
    must be given.  Re-registering an existing name raises unless
    ``overwrite=True`` (silent redefinition of e.g. ``"laplacian"`` would
    change numerics at a distance — and alias stale autotune entries).

    ``derivative=``/``symmetric=``/``zero_sum=`` declare analytic
    properties of the weights that stencil-lint verifies at register and
    Create time (moment/Taylor conditions, central symmetry, zero row
    sum); ``lint='off'|'warn'|'error'`` picks how register-time findings
    surface (:class:`repro.analysis.StencilLintWarning` /
    :class:`repro.analysis.LintError`).

    >>> import numpy as np
    >>> opdef = register_operator(
    ...     "doc_identity3",
    ...     weights=lambda ndim=1, h=1.0: np.array([0.0, 1.0, 0.0]),
    ...     doc="3-point identity (doctest example)",
    ...     overwrite=True,
    ... )
    >>> opdef.name
    'doc_identity3'
    >>> "doc_identity3" in operator_names()
    True
    """
    if not name or not isinstance(name, str):
        raise ValueError("operator name must be a non-empty string")
    if weights is None and diagonals is None:
        raise ValueError(
            f"operator {name!r} needs weights= and/or diagonals="
        )
    if name in _REGISTRY and not overwrite:
        raise ValueError(
            f"operator {name!r} is already registered "
            "(pass overwrite=True to replace it)"
        )
    opdef = OperatorDef(
        name=name, weights=weights, diagonals=diagonals, doc=doc,
        derivative=derivative, symmetric=symmetric, zero_sum=zero_sum,
    )
    if lint != "off" and weights is not None and (
        derivative or symmetric or zero_sum
    ):
        from repro.analysis import lint_operator, surface

        findings = []
        for ndim in (1, 2, 3):
            findings += lint_operator(opdef, ndim=ndim)
        surface(findings, lint)
    _REGISTRY[name] = opdef
    return opdef


def get_operator(name: str) -> OperatorDef:
    """Look up a registered operator; unknown names raise with the list
    of known ones.

    >>> get_operator("laplacian").derivative
    2
    >>> get_operator("no_such_op")
    Traceback (most recent call last):
        ...
    ValueError: unknown operator 'no_such_op'; registered: ...
    """
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown operator {name!r}; registered: "
            f"{sorted(_REGISTRY)} (add your own with "
            "repro.register_operator)"
        ) from None


def operator_names() -> tuple:
    """The registered operator names, sorted.

    >>> "laplacian" in operator_names() and "diffusion" in operator_names()
    True
    """
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Plan identity
# ---------------------------------------------------------------------------


def plan_key(
    operator: str,
    shape,
    *,
    dtype,
    bc: str = "periodic",
    mode: str | None = None,
    alpha: float | None = None,
    extra=None,
) -> str:
    """Canonical string identity of one plan request.

    The deterministic, order-independent key under which a *plan* (not a
    tuning result) is cached — the serving engine's warm-plan LRU
    (:class:`repro.serve.PlanLRU`) keys on exactly this, the same way the
    Create-time autotuner keys its persistent cache on
    :func:`repro.tune.cache.tune_key`.  Everything that changes the plan a
    :func:`create` call would return is part of the key: operator name,
    logical field shape, dtype, boundary condition, the ``mode`` hint, the
    ADI ``alpha``, plus an ``extra`` dict for caller-specific
    discriminators (backend request, batch quantisation, ...).  Host
    identity is deliberately *not* part of the key — unlike a tuning
    winner, a plan is portable.

    >>> import json
    >>> key = plan_key("laplacian", (64, 64), dtype="float32")
    >>> json.loads(key)["operator"]
    'laplacian'
    >>> key == plan_key("laplacian", [64, 64], dtype=jnp.float32)
    True
    >>> key == plan_key("laplacian", (64, 64), dtype="float32", bc="np")
    False
    """
    doc = {
        "schema": 1,
        "operator": str(operator),
        "shape": [int(s) for s in shape],
        "dtype": str(jnp.dtype(dtype)),
        "bc": bc,
        "mode": mode,
        "alpha": None if alpha is None else float(alpha),
        "extra": extra,
    }
    return json.dumps(doc, sort_keys=True, separators=(",", ":"))


# -- built-in operators ------------------------------------------------------

_D2 = np.array([1.0, -2.0, 1.0])  # delta (paper eq. 4a)
_D4 = np.array([1.0, -4.0, 6.0, -4.0, 1.0])  # delta^2 (paper eq. 4b)


def _laplacian_weights(ndim: int = 2, h: float = 1.0):
    """delta^2 in 1D, the 5-point cross in 2D, the 7-point box in 3D."""
    if ndim == 1:
        return _D2 / h**2
    if ndim == 2:
        w = np.zeros((3, 3))
        w[1, :] += _D2
        w[:, 1] += _D2
        return w / h**2
    if ndim == 3:
        return _stencil.laplacian3d_weights(h)
    raise ValueError(f"laplacian weights: ndim must be 1|2|3, got {ndim}")


def _biharmonic_weights(ndim: int = 2, h: float = 1.0):
    """delta^4 in 1D; in 2D and 3D the Laplacian applied twice: the 5x5
    delta_x^2 + delta_y^2 + 2 delta_x delta_y of paper eq. (4) (the
    Cahn–Hilliard hyperdiffusion stencil), and its 25-tap 5x5x5 analogue
    with the three cross terms."""
    if ndim == 1:
        return _D4 / h**4
    if ndim in (2, 3):
        lap = _laplacian_weights(ndim)
        w = np.zeros((5,) * ndim)
        for idx in np.ndindex(lap.shape):
            w[tuple(slice(i, i + 3) for i in idx)] += lap[idx] * lap
        return w / h**4
    raise ValueError(f"biharmonic weights: ndim must be 1|2|3, got {ndim}")


register_operator(
    "laplacian",
    weights=_laplacian_weights,
    doc="grad^2: 3-point / 5-point cross / 7-point box (units h^-2)",
    derivative=2,
    symmetric=True,
    zero_sum=True,
)
register_operator(
    "biharmonic",
    weights=_biharmonic_weights,
    doc="grad^4: delta^4 / the paper's 5x5 eq.-(4) stencil / its 5x5x5 "
    "analogue (units h^-4)",
    derivative=4,
    symmetric=True,
    zero_sum=True,
)
register_operator(
    "hyperdiffusion",
    weights=lambda ndim=1, h=1.0: _biharmonic_weights(ndim, h),
    diagonals=hyperdiffusion_diagonals,
    doc="implicit I + alpha delta^4 (ADI bands); explicit delta^4 weights",
    derivative=4,
    symmetric=True,
    zero_sum=True,
)
register_operator(
    "diffusion",
    weights=lambda ndim=1, h=1.0: _laplacian_weights(ndim, h),
    diagonals=diffusion_diagonals,
    doc="implicit I - alpha delta^2 (ADI bands); explicit delta^2 weights",
    derivative=2,
    symmetric=True,
    zero_sum=True,
)


# ---------------------------------------------------------------------------
# Create
# ---------------------------------------------------------------------------

_BATCH_MODES = ("batch", "batch1d", "1d_batch")
_EXTENT_KEYS = ("left", "right", "top", "bottom", "front", "back")


def _resolve_direction(rank: int, mode: str | None, wndim: int | None):
    """Plan direction from the shape rank, the mode hint, and (when
    weights are an explicit array) their dimensionality."""
    if rank == 2:
        if mode is None:
            return "xy" if wndim in (2, None) else "x"
        if mode in _stencil._DIRECTIONS:
            return mode
        raise ValueError(
            f"mode for a rank-2 shape must be one of "
            f"{_stencil._DIRECTIONS + _BATCH_MODES[:1] + ('adi',)}, "
            f"got {mode!r}"
        )
    if mode is None:
        if wndim in (3, None):
            return "xyz"
        raise ValueError(
            "1D weights on a rank-3 shape are ambiguous: pass "
            "mode='x'|'y'|'z'"
        )
    if mode in _stencil._DIRECTIONS_3D:
        return mode
    raise ValueError(
        f"mode for a rank-3 shape must be one of "
        f"{_stencil._DIRECTIONS_3D + ('adi',)}, got {mode!r}"
    )


@obs.span("create")
def create(
    weights_or_fn,
    shape,
    *,
    bc: str = "periodic",
    mode: str | None = None,
    coeffs=None,
    extents: dict | None = None,
    h: float = 1.0,
    dtype=None,
    alpha=None,
    alpha_y=None,
    alpha_z=None,
    cyclic: bool | None = None,
    tile=None,
    backend: str = "auto",
    interpret: bool | None = None,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    tune: str = "off",
    tune_cache=None,
    lint: str = "warn",
):
    """Create a plan — the one entry point for every plan family.

    ``weights_or_fn`` is an explicit weights array, a point function (the
    paper's function-pointer mode; give ``coeffs`` and ``extents``), or a
    registered operator name (``repro.get_operator``; weights are built
    for the inferred dimensionality with grid spacing ``h``).

    The family comes from the rank of ``shape`` and the ``mode`` hint:

    ========================  =========================================
    ``shape``, ``mode``       plan
    ========================  =========================================
    ``(ny, nx)``              :class:`Stencil2D` (``mode`` = direction
                              ``'x'|'y'|'xy'``; default from weights)
    ``(B, M)``, ``'batch'``   :class:`StencilBatch1D` (one 1D stencil,
                              every row of the stack)
    ``(nz, ny, nx)``          :class:`Stencil3D` (``mode`` = direction
                              ``'x'|'y'|'z'|'xyz'``)
    any, ``'adi'``            :class:`ADIOperator` / :class:`ADIOperator3D`
                              (named operator with bands + ``alpha=``)
    ========================  =========================================

    ``tune``/``streams``/``max_tile_bytes``/``backend``/``tile`` carry
    the Create-time autotuning and streaming knobs of the underlying
    family unchanged; ``shape`` doubles as the autotuner's measurement
    shape, so ``tune='cached'`` needs no extra argument here.

    ``backend`` picks the execution backend: ``'jnp'``/``'pallas'`` run
    the direct stencil/banded kernels, ``'fft'`` the spectral path —
    the operator's Fourier symbol is precomputed at Create and Compute
    is a pointwise multiply (stencils) or divide (cyclic ADI sweeps) in
    frequency space, asymptotically faster for large radii.  ``'fft'``
    needs periodic boundaries, explicit weights and a Create-time shape,
    and refuses anything else with
    :class:`repro.SpectralBackendError`.  Under the default
    ``backend='auto'`` with tuning on, the tuner *races* fft against the
    direct backends and bakes the measured winner into the plan.

    Arguments that would otherwise be silently dropped are refused:
    ``h`` scales *registry* weights only (explicit arrays and point
    functions already encode the grid spacing), and ``alpha*``/``cyclic``
    apply only to ``mode='adi'``.  For ADI plans ``bc`` picks the band
    topology (``'periodic'`` → cyclic bands + Woodbury correction,
    anything else → plain pentadiagonal); an explicit ``cyclic=``
    overrides, but contradicting ``bc='np'`` with ``cyclic=True`` is an
    error.

    ``lint='off'|'warn'|'error'`` runs Create-time stencil-lint (moment
    conditions, ADI band topology/conditioning, Pallas grid feasibility)
    and surfaces findings as :class:`repro.analysis.StencilLintWarning`
    or :class:`repro.analysis.LintError`.

    >>> plan = create("laplacian", (32, 32), bc="periodic")
    >>> type(plan).__name__
    'Stencil2D'
    >>> op = create("diffusion", (16, 16), mode="adi", alpha=0.1,
    ...             dtype="float32")
    >>> type(op).__name__
    'ADIOperator'
    >>> destroy(plan); destroy(op)
    """
    from repro.analysis import check_lint_mode

    check_lint_mode(lint)
    shape = tuple(int(s) for s in shape)
    rank = len(shape)
    if rank not in (2, 3):
        raise ValueError(
            f"shape must be rank 2 or 3, got {shape!r} "
            "(batched-1D stacks are rank-2 (B, M) with mode='batch')"
        )

    op_name = None
    opdef = None
    if isinstance(weights_or_fn, str):
        opdef = get_operator(weights_or_fn)
        op_name = opdef.name

    # -- ADI plans: named operator + alpha, rank picks 2D vs 3D ----------
    if mode == "adi":
        if opdef is None:
            raise ValueError(
                "mode='adi' takes a registered operator name (got "
                f"{type(weights_or_fn).__name__}); its diagonals build "
                "the implicit bands"
            )
        if alpha is None:
            raise ValueError("mode='adi' needs alpha= (the band coefficient)")
        if h != 1.0:
            raise ValueError(
                "h= only scales registry stencil weights; for mode='adi' "
                "fold the grid spacing into alpha= instead"
            )
        # bc= chooses the band topology: periodic -> cyclic (Woodbury),
        # np -> plain pentadiagonal.  An explicit cyclic= overrides, but
        # contradicting an explicit bc='np' is refused rather than ignored.
        if cyclic is None:
            cyclic = bc == "periodic"
        elif bc != "periodic" and cyclic:
            raise ValueError(
                f"bc={bc!r} asks for a non-cyclic operator but cyclic=True "
                "was passed; drop one of them"
            )
        if lint != "off":
            from repro.analysis import lint_adi, surface

            ax = alpha
            ay = alpha if alpha_y is None else alpha_y
            az = alpha if alpha_z is None else alpha_z
            dirs = [("x", shape[-1], ax), ("y", shape[-2], ay)]
            if rank == 3:
                dirs.append(("z", shape[-3], az))
            findings = []
            for dname, n, a in dirs:
                findings += lint_adi(
                    opdef, n, a, bc=bc, cyclic=cyclic, direction=dname,
                )
            surface(findings, lint)
        common = dict(
            cyclic=cyclic,
            dtype=jnp.float64 if dtype is None else dtype,
            backend=backend,
            streams=streams,
            max_tile_bytes=max_tile_bytes,
            tune=tune,
            tune_cache=tune_cache,
            operator=op_name,
        )
        if rank == 2:
            if alpha_z is not None:
                raise ValueError("alpha_z only applies to rank-3 shapes")
            ny, nx = shape
            return _adi._make_adi_operator(
                ny, nx, alpha, alpha_over_h4_y=alpha_y, **common
            )
        nz, ny, nx = shape
        return _adi._make_adi_operator_3d(
            nz, ny, nx, alpha, alpha_y=alpha_y, alpha_z=alpha_z, **common
        )

    # -- stencil plans ----------------------------------------------------
    for nm, val in (
        ("alpha", alpha), ("alpha_y", alpha_y), ("alpha_z", alpha_z),
        ("cyclic", cyclic),
    ):
        if val is not None:
            raise ValueError(
                f"{nm}= only applies to mode='adi' (implicit ADI plans); "
                "an explicit stencil create would silently drop it"
            )
    batch = mode in _BATCH_MODES
    if batch and rank != 2:
        raise ValueError("mode='batch' takes a rank-2 (B, M) stack")

    if opdef is None and h != 1.0:
        raise ValueError(
            "h= only scales registry-operator weights; explicit weights "
            "arrays and point functions already encode the grid spacing "
            f"(got h={h!r})"
        )
    weights = func = None
    if opdef is not None:
        if opdef.weights is None:
            raise ValueError(
                f"operator {op_name!r} defines no stencil weights "
                "(band-only); use mode='adi'"
            )
        if batch:
            wndim = 1
        else:
            direction = _resolve_direction(rank, mode, None)
            wndim = {"xy": 2, "xyz": 3}.get(direction, 1)
        weights = opdef.weights(wndim, h)
    elif callable(weights_or_fn) and not isinstance(
        weights_or_fn, (np.ndarray, jnp.ndarray)
    ):
        func = weights_or_fn
        if not batch:
            direction = _resolve_direction(rank, mode, None)
    else:
        weights = np.asarray(weights_or_fn)
        if not batch:
            direction = _resolve_direction(rank, mode, weights.ndim)

    if dtype is not None:
        if weights is not None:
            weights = jnp.asarray(weights, jnp.dtype(dtype))
        if coeffs is not None:
            coeffs = jnp.asarray(coeffs, jnp.dtype(dtype))

    ext = dict(extents or {})
    bad = sorted(set(ext) - set(_EXTENT_KEYS))
    if bad:
        raise ValueError(
            f"unknown extents keys {bad}; allowed: {list(_EXTENT_KEYS)}"
        )
    ext_kw = {f"num_sten_{k}": v for k, v in ext.items()}

    common = dict(
        weights=weights,
        func=func,
        coeffs=coeffs,
        tile=tile,
        backend=backend,
        interpret=interpret,
        streams=streams,
        max_tile_bytes=max_tile_bytes,
        tune=tune,
        shape=shape,
        tune_cache=tune_cache,
        op_name=op_name,
        **ext_kw,
    )
    if batch:
        plan = _stencil._create_1d_batch(bc, **common)
    elif rank == 2:
        plan = _stencil._create_2d(direction, bc, **common)
    else:
        plan = _stencil._create_3d(direction, bc, **common)

    if lint != "off":
        from repro.analysis import check_plan, lint_operator, surface

        findings = []
        if opdef is not None:
            wndim = 1 if batch else {"xy": 2, "xyz": 3}.get(direction, 1)
            findings += lint_operator(opdef, ndim=wndim, h=h)
        findings += check_plan(plan, shape, ("pallas_grid_feasible",))
        surface(findings, lint)
    return plan


# ---------------------------------------------------------------------------
# Compute / Swap / Destroy
# ---------------------------------------------------------------------------


def compute(plan, field, *extra):
    """Apply any plan to ``field`` — the single Compute path.

    Stencil plans take an optional ``out_init`` extra (the ``bc='np'``
    boundary passthrough buffer).  ADI plans apply the full implicit
    solve: ``L_y^{-1} L_x^{-1}`` in 2D, ``L_z^{-1} L_y^{-1} L_x^{-1}``
    in 3D — every sweep transpose-free.

    Plans are pytrees, so ``jax.jit(compute)(plan, field)`` traces the
    plan's arrays as arguments: swapping in new weight values reuses the
    compiled trace.

    >>> plan = create("laplacian", (8, 8), bc="periodic")
    >>> out = compute(plan, jnp.ones((8, 8)))   # laplacian of a constant
    >>> bool(jnp.all(out == 0.0))
    True
    >>> destroy(plan)
    """
    if getattr(plan, "_destroyed", False):
        raise ValueError(
            "plan has been destroyed (repro.destroy); create a new one"
        )
    if isinstance(plan, _stencil.PlanCore):
        return plan.apply(field, *extra)
    if isinstance(plan, (_adi.ADIOperator, _adi.ADIOperator3D)):
        if extra:
            raise TypeError("ADI compute takes no extra operands")
        out = plan.solve_y(plan.solve_x(field))
        if isinstance(plan, _adi.ADIOperator3D):
            out = plan.solve_z(out)
        return out
    raise TypeError(
        f"compute wants a stencil plan or ADI operator, got "
        f"{type(plan).__name__}"
    )


def swap(buf):
    """Flip a double buffer between time steps (cuSten's Swap).

    ``buf`` is either an ``(a, b)`` pair — returned reversed, so the
    just-computed field becomes the next step's input — or a
    :class:`~repro.core.stencil.DoubleBuffer` (flipped in place and
    returned).  Inside a jitted, donation-enabled step this is the
    zero-copy pointer swap; :func:`repro.core.cahn_hilliard.ch_evolve`
    is the same idiom at whole-chunk granularity.

    >>> swap(("old", "new"))
    ('new', 'old')
    """
    if isinstance(buf, _stencil.DoubleBuffer):
        return buf.swap()
    try:
        a, b = buf
    except (TypeError, ValueError):
        raise TypeError(
            f"swap wants an (a, b) pair or a DoubleBuffer, got "
            f"{type(buf).__name__}"
        ) from None
    return b, a


def destroy(plan) -> None:
    """Tear down any plan (cuSten's Destroy) — idempotent, unified.

    JAX buffers are reference counted, so nothing is freed eagerly; the
    plan is marked destroyed and :func:`compute` refuses it afterwards.
    Destroying ``None``, an already-destroyed plan, or a
    :class:`DoubleBuffer` is a no-op — double-Destroy never raises.

    >>> plan = create("laplacian", (8, 8))
    >>> destroy(plan); destroy(plan)    # idempotent
    >>> plan.destroyed
    True
    """
    _stencil.plan_destroy(plan)
