"""ADI (alternating-direction implicit) solve framework (paper §V, ref [15]).

Each ADI step inverts the per-direction implicit operator

    L = I + alpha * delta^4 / h^4        (pentadiagonal, constant in time)

along x and then along y.  Following cuSten/cuPentBatch, the factorisation
happens once at Create time (:class:`ADIOperator`); each Compute is a batched
banded substitution.  Both sweeps are **transpose-free**: the y-sweep runs
the column-layout substitution (systems along axis 0, batch on lanes) and
the x-sweep the row-layout variant (batch along axis 0, recurrence along
axis 1) — both factored once at Create time, so no per-step interleaving
transpose of the field passes through HBM (the Pallas row kernel
transposes 128-lane chunks in VMEM only).

The *explicit* side of each sweep is the same batched-1D picture: a purely
directional stencil applied to every grid line at once.
:func:`apply_along_x` / :func:`apply_along_y` run a
:class:`~repro.core.stencil.StencilBatch1D` plan over the rows / columns of
an ``(ny, nx)`` field, so per-direction RHS assembly never touches the
full-2D stencil machinery.

``tune='cached'|'force'`` on :func:`make_adi_operator` routes the backend /
batch-tile / unroll choice for each sweep through the Create-time
autotuner (:mod:`repro.tune`): candidates are measured once per
(shape, dtype, backend, jax version, host) and remembered on disk.

**3D** (:class:`ADIOperator3D`, :func:`make_adi_operator_3d`): the same
Create/Compute split on ``(nz, ny, nx)`` fields with *three* transpose-free
sweeps — x as a row-layout solve of the ``(nz*ny, nx)`` reshape, z as a
column-layout solve of the ``(nz, ny*nx)`` reshape, and y through the new
plane-layout substitution (recurrence along the middle axis), so a full 3D
splitting step performs zero transposes.  ``operator='diffusion'`` swaps
the hyperdiffusion band for the backward-Euler heat operator
``I - alpha delta^2`` (tridiagonal riding the pentadiagonal machinery).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.stencil import StencilBatch1D
from repro.kernels import spectral
from repro.util import deprecated_shim
from repro.kernels.penta import (
    CyclicPentaFactors,
    PentaFactors,
    cyclic_penta_factor,
    cyclic_penta_solve_factored,
    cyclic_penta_solve_factored_mid,
    cyclic_penta_solve_factored_rows,
    penta_factor,
    penta_solve_factored,
    penta_solve_factored_mid,
    penta_solve_factored_rows,
)


def _band_builder(operator: str):
    """The per-direction band builder for a named operator, resolved
    through the :mod:`repro.api` registry — the single source of operator
    definitions (``register_operator`` makes this user-extensible)."""
    from repro import api as _api

    opdef = _api.get_operator(operator)
    if opdef.diagonals is None:
        raise ValueError(
            f"operator {opdef.name!r} defines no ADI band builder "
            "(it is stencil-weights-only); register it with diagonals= "
            "via repro.register_operator to use it in ADI plans"
        )
    return opdef.diagonals


def apply_along_x(
    plan: StencilBatch1D,
    field: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Apply a batched-1D plan along the x (last) axis of an (ny, nx) field:
    the ny rows are the batch."""
    return plan.apply(field, out_init)


def apply_along_y(
    plan: StencilBatch1D,
    field: jnp.ndarray,
    out_init: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Apply a batched-1D plan along the y (first) axis of an (ny, nx)
    field: the nx columns are the batch (the explicit path still
    interleaves; the implicit sweeps do not)."""
    out_init_t = None if out_init is None else out_init.T
    return plan.apply(field.T, out_init_t).T


@dataclasses.dataclass(frozen=True)
class ADIOperator:
    """Factored per-direction operators L = I + alpha/h^4 * delta^4.

    ``streams``/``max_tile_bytes`` route the batched substitutions through
    the streamed executor (:mod:`repro.launch.stream`): the y-sweep cuts
    its independent-systems batch into column chunks
    (:func:`~repro.launch.stream.stream_penta_solve`), the x-sweep into
    row chunks (:func:`~repro.launch.stream.stream_penta_solve_rows`) —
    both transpose-free, so the implicit half of an ADI step runs on
    domains exceeding one tile.

    ``x_cfg``/``y_cfg`` are per-sweep overrides (``backend``, ``tb``/``tn``
    batch tile, jnp ``unroll``) produced by the Create-time autotuner."""

    fac_x: CyclicPentaFactors | PentaFactors  # along x (length nx)
    fac_y: CyclicPentaFactors | PentaFactors  # along y (length ny)
    cyclic: bool
    backend: str = "auto"
    streams: int | None = None
    max_tile_bytes: int | None = None
    x_cfg: dict | None = None  # tuned x-sweep config
    y_cfg: dict | None = None  # tuned y-sweep config
    operator: str = "hyperdiffusion"  # registry name the bands came from
    # band symbols (rfft eigenvalues of the cyclic penta circulants),
    # computed at Create whenever cyclic — the fft sweep divides by these
    # instead of running the recurrence + Woodbury closure.  Pytree leaves.
    sym_x: jnp.ndarray | None = None
    sym_y: jnp.ndarray | None = None

    @property
    def destroyed(self) -> bool:
        """True once ``repro.destroy`` ran on this operator."""
        return getattr(self, "_destroyed", False)

    def _cfg(self, cfg: dict | None):
        cfg = cfg or {}
        return cfg.get("backend", self.backend), cfg.get("unroll", 1), cfg

    @obs.stage("adi.x")
    def solve_x(self, rhs: jnp.ndarray) -> jnp.ndarray:
        """Solve L_x w = rhs along the x (last) axis of an (ny, nx) field —
        row layout, transpose-free."""
        from repro.launch import stream as _stream

        backend, unroll, cfg = self._cfg(self.x_cfg)
        if backend == "fft":
            return _fft_sweep(self.sym_x, rhs, axis=-1)
        if rhs.ndim == 2 and _stream.should_stream(
            rhs.shape,
            rhs.dtype.itemsize,
            streams=self.streams,
            max_tile_bytes=self.max_tile_bytes,
        ):
            return _stream.stream_penta_solve_rows(
                self.fac_x,
                rhs,
                cyclic=self.cyclic,
                streams=self.streams,
                max_tile_bytes=self.max_tile_bytes,
                backend=backend,
                unroll=unroll,
            )
        solve = (
            cyclic_penta_solve_factored_rows
            if self.cyclic
            else penta_solve_factored_rows
        )
        return solve(
            self.fac_x, rhs, backend=backend, tb=cfg.get("tb"), unroll=unroll
        )

    @obs.stage("adi.y")
    def solve_y(self, rhs: jnp.ndarray) -> jnp.ndarray:
        """Solve L_y v = rhs along the y (first) axis of an (ny, nx) field —
        column layout, native."""
        from repro.launch import stream as _stream

        backend, unroll, cfg = self._cfg(self.y_cfg)
        if backend == "fft":
            return _fft_sweep(self.sym_y, rhs, axis=0)
        if rhs.ndim == 2 and _stream.should_stream(
            rhs.shape,
            rhs.dtype.itemsize,
            streams=self.streams,
            max_tile_bytes=self.max_tile_bytes,
        ):
            return _stream.stream_penta_solve(
                self.fac_y,
                rhs,
                cyclic=self.cyclic,
                streams=self.streams,
                max_tile_bytes=self.max_tile_bytes,
                backend=backend,
                unroll=unroll,
            )
        solve = (
            cyclic_penta_solve_factored
            if self.cyclic
            else penta_solve_factored
        )
        return solve(
            self.fac_y, rhs, backend=backend, tn=cfg.get("tn"), unroll=unroll
        )

    def grid_problems(self, shape) -> list:
        """Why this operator cannot sweep an ``(ny, nx)`` field — factor
        lengths vs extents plus tuned Pallas batch-tile divisibility
        (the ``pallas_grid_feasible`` audit rule's probe)."""
        ny, nx = (int(s) for s in shape)
        problems = []
        if _fac_len(self.fac_x) != nx or _fac_len(self.fac_y) != ny:
            problems.append(
                f"factor lengths (x={_fac_len(self.fac_x)}, "
                f"y={_fac_len(self.fac_y)}) do not match the field "
                f"({ny}, {nx}); the plan was Created for another shape"
            )
        problems += _cfg_tile_problems(self.x_cfg, "x", "tb", ny, "rows ny")
        problems += _cfg_tile_problems(self.y_cfg, "y", "tn", nx, "lanes nx")
        return problems


def _fac_len(fac) -> int:
    """System length of a (cyclic) pentadiagonal factor set."""
    band = getattr(fac, "band", fac)
    return int(band.sub.shape[0])


def _fft_sweep(sym, rhs: jnp.ndarray, axis: int) -> jnp.ndarray:
    """The spectral implicit sweep: divide by the band symbol along one
    axis (:func:`repro.kernels.spectral.solve_symbol_axis`) — the
    circulant diagonalisation of the cyclic penta solve."""
    if sym is None:
        raise spectral.SpectralBackendError(
            "this ADI operator carries no band symbol (Create attaches "
            "one only for cyclic operators)"
        )
    return spectral.solve_symbol_axis(rhs, sym, axis)


def _cfg_tile_problems(cfg, sweep: str, key: str, extent: int, what: str):
    """Tuned Pallas batch tiles must divide the batch they tile."""
    cfg = cfg or {}
    t = cfg.get(key)
    if (
        t is not None
        and cfg.get("backend", "jnp") == "pallas"
        and extent % int(t) != 0
    ):
        return [
            f"{sweep}-sweep Pallas tile {key}={t} does not divide the "
            f"batch of {what}={extent}"
        ]
    return []


def _sweep_candidates(batch: int, fft: bool = False):
    """The per-sweep solve candidate space: jnp rolled/unrolled loops,
    the spectral divide when the operator is cyclic under ``backend=
    'auto'`` (``fft=True``), plus (on TPU) aligned Pallas batch tiles —
    shared by the 2D and 3D ADI tuners."""
    from repro.kernels import ops as _ops
    from repro.util import tile_candidates

    cands = [{"backend": "jnp", "unroll": 1}, {"backend": "jnp", "unroll": 4}]
    if fft:
        cands.append({"backend": "fft"})
    if _ops.on_tpu():
        for t in tile_candidates(batch):
            cands.append({"backend": "pallas", "tile": t})
    return cands


def _fft_arbitrage(op) -> bool:
    """fft joins a sweep's tuner race only for cyclic ``backend='auto'``
    operators: an explicit backend is an explicit choice, and the fp64
    tuned-equals-untuned bit-match contract must survive tuning."""
    return op.backend == "auto" and op.cyclic


def _sweep_cfg(best: dict, tile_key: str) -> dict:
    """Winning autotune config -> the per-sweep override dict solve_*
    consumes (shared by the 2D and 3D ADI tuners)."""
    cfg = {"backend": best["backend"], "unroll": best.get("unroll", 1)}
    if "tile" in best:
        cfg[tile_key] = best["tile"]
    return cfg


def _autotune_adi(op: ADIOperator, ny: int, nx: int, dtype, mode: str, cache):
    """Measure per-sweep solve configurations and attach the winners.

    Candidates run through the *operator's own* sweep dispatch (a
    per-candidate :func:`dataclasses.replace` of the sweep cfg on a
    streams-knocked-out copy), so every backend the dispatch knows —
    including the spectral divide — is measured exactly as it will run.
    """
    from repro.tune import autotune

    rhs = jnp.zeros((ny, nx), dtype)
    # the operator name is part of the cache key: registry operators with
    # coincidentally equal geometry must not alias one entry
    extra = {"cyclic": op.cyclic, "operator": op.operator}
    kw = dict(
        shape=(ny, nx), dtype=dtype, backend=op.backend, extra=extra,
        mode=mode, cache=cache,
    )
    # measure the monolithic solves (streams knocked out) — the streamed
    # executor ignores per-sweep tiles
    mono = dataclasses.replace(op, streams=None, max_tile_bytes=None)
    fft = _fft_arbitrage(op)

    def build(sweep, tile_key):
        def builder(cfg):
            op2 = dataclasses.replace(
                mono, **{sweep + "_cfg": _sweep_cfg(cfg, tile_key)}
            )
            return jax.jit(getattr(op2, "solve_" + sweep))

        return builder

    best_x = autotune(
        "adi_solve_x", _sweep_candidates(ny, fft=fft), build("x", "tb"),
        (rhs,), **kw
    )
    best_y = autotune(
        "adi_solve_y", _sweep_candidates(nx, fft=fft), build("y", "tn"),
        (rhs,), **kw
    )
    return dataclasses.replace(
        op, x_cfg=_sweep_cfg(best_x, "tb"), y_cfg=_sweep_cfg(best_y, "tn")
    )


_ADI_BACKENDS = ("auto", "jnp", "pallas", "fft")


def _check_adi_backend(backend: str, cyclic: bool) -> None:
    """Create-time backend validation shared by the 2D and 3D factories.

    ``backend='fft'`` on a non-cyclic operator raises
    :class:`repro.kernels.spectral.SpectralBackendError` — the spectral
    sweep is the circulant diagonalisation, which only exists for
    periodic (cyclic) bands."""
    if backend not in _ADI_BACKENDS:
        raise ValueError(
            f"backend must be one of {_ADI_BACKENDS}, got {backend!r}"
        )
    if backend == "fft" and not cyclic:
        raise spectral.SpectralBackendError(
            "non-cyclic ADI bands are not circulants, so they do not "
            "diagonalise under the DFT; use bc='periodic' (cyclic=True) "
            "or a direct backend"
        )


def _make_adi_operator(
    ny: int,
    nx: int,
    alpha_over_h4,
    *,
    cyclic: bool = True,
    dtype=jnp.float64,
    backend: str = "auto",
    alpha_over_h4_y: float | None = None,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    tune: str = "off",
    tune_cache=None,
    operator: str = "hyperdiffusion",
) -> ADIOperator:
    """Create (factor) the ADI operator pair.

    ``alpha_over_h4`` is the full coefficient multiplying ``delta^4``
    (e.g. ``(2/3) * D * gamma * dt / h**4`` for the paper's full scheme, or
    ``0.5 * D * gamma * dt / h**4`` for the eq. (3) initial step).
    ``operator='diffusion'`` factors ``I - alpha delta^2`` instead (the
    backward-Euler diffusion sweep; ``alpha`` is then ``D dt / h**2``).

    ``tune`` (``'off'|'cached'|'force'``) runs the Create-time autotuner
    over per-sweep backend / batch-tile / unroll candidates.
    """
    _check_adi_backend(backend, cyclic)
    diagonals = _band_builder(operator)
    ax = alpha_over_h4
    ay = alpha_over_h4 if alpha_over_h4_y is None else alpha_over_h4_y
    factor = cyclic_penta_factor if cyclic else penta_factor
    fac_x = factor(*diagonals(nx, ax, dtype))
    fac_y = factor(*diagonals(ny, ay, dtype))
    # cyclic bands are circulants: precompute their rfft eigenvalues so
    # the fft sweep (explicit or tuner-arbitraged) is a pointwise divide
    sym_x = sym_y = None
    if cyclic:
        sym_x = spectral.band_symbol(*diagonals(nx, ax, dtype), dtype=dtype)
        sym_y = spectral.band_symbol(*diagonals(ny, ay, dtype), dtype=dtype)
    op = ADIOperator(
        fac_x=fac_x, fac_y=fac_y, cyclic=cyclic, backend=backend,
        streams=streams, max_tile_bytes=max_tile_bytes, operator=operator,
        sym_x=sym_x, sym_y=sym_y,
    )
    if tune != "off":
        op = _autotune_adi(op, ny, nx, jnp.dtype(dtype), tune, tune_cache)
    return op


# ---------------------------------------------------------------------------
# 3D ADI (thesis follow-on / paper §VI.A): x/y/z sweeps on (nz, ny, nx)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ADIOperator3D:
    """Factored per-direction operators for 3D ADI sweeps, every sweep
    **transpose-free** on an ``(nz, ny, nx)`` field:

    - :meth:`solve_x` — row layout on the ``(nz*ny, nx)`` reshape (the
      batch axes are contiguous; a reshape is free, a transpose is not);
    - :meth:`solve_y` — *plane* layout
      (:func:`~repro.kernels.penta.penta_solve_factored_mid`): recurrence
      along the middle axis, batch on planes × lanes;
    - :meth:`solve_z` — column layout on the ``(nz, ny*nx)`` reshape.

    ``streams``/``max_tile_bytes`` route each sweep through the streamed
    executor: x chunks rows, y chunks z-planes, z chunks columns — the
    whole implicit half of a 3D ADI step runs on domains exceeding one
    tile.  ``x_cfg``/``y_cfg``/``z_cfg`` are per-sweep overrides produced
    by the Create-time autotuner."""

    fac_x: CyclicPentaFactors | PentaFactors  # along x (length nx)
    fac_y: CyclicPentaFactors | PentaFactors  # along y (length ny)
    fac_z: CyclicPentaFactors | PentaFactors  # along z (length nz)
    cyclic: bool
    backend: str = "auto"
    streams: int | None = None
    max_tile_bytes: int | None = None
    x_cfg: dict | None = None
    y_cfg: dict | None = None
    z_cfg: dict | None = None
    operator: str = "hyperdiffusion"  # registry name the bands came from
    # band symbols of the cyclic circulants (see ADIOperator) — the fft
    # sweep needs no reshape at all: every axis solves in place
    sym_x: jnp.ndarray | None = None
    sym_y: jnp.ndarray | None = None
    sym_z: jnp.ndarray | None = None

    @property
    def destroyed(self) -> bool:
        """True once ``repro.destroy`` ran on this operator."""
        return getattr(self, "_destroyed", False)

    def _cfg(self, cfg: dict | None):
        cfg = cfg or {}
        return cfg.get("backend", self.backend), cfg.get("unroll", 1), cfg

    def _should_stream(self, rhs) -> bool:
        from repro.launch import stream as _stream

        return _stream.should_stream(
            rhs.shape,
            rhs.dtype.itemsize,
            streams=self.streams,
            max_tile_bytes=self.max_tile_bytes,
        )

    @obs.stage("adi.x")
    def solve_x(self, rhs: jnp.ndarray) -> jnp.ndarray:
        """Solve L_x w = rhs along the x (last) axis — row layout on the
        flattened (nz*ny, nx) batch, transpose-free."""
        from repro.launch import stream as _stream

        backend, unroll, cfg = self._cfg(self.x_cfg)
        if backend == "fft":
            return _fft_sweep(self.sym_x, rhs, axis=-1)
        nz, ny, nx = rhs.shape
        flat = rhs.reshape(nz * ny, nx)
        if self._should_stream(rhs):
            out = _stream.stream_penta_solve_rows(
                self.fac_x,
                flat,
                cyclic=self.cyclic,
                streams=self.streams,
                max_tile_bytes=self.max_tile_bytes,
                backend=backend,
                unroll=unroll,
            )
        else:
            solve = (
                cyclic_penta_solve_factored_rows
                if self.cyclic
                else penta_solve_factored_rows
            )
            out = solve(
                self.fac_x, flat, backend=backend, tb=cfg.get("tb"),
                unroll=unroll,
            )
        return out.reshape(rhs.shape)

    @obs.stage("adi.y")
    def solve_y(self, rhs: jnp.ndarray) -> jnp.ndarray:
        """Solve L_y v = rhs along the y (middle) axis — plane layout,
        transpose-free."""
        from repro.launch import stream as _stream

        backend, unroll, cfg = self._cfg(self.y_cfg)
        if backend == "fft":
            return _fft_sweep(self.sym_y, rhs, axis=-2)
        if self._should_stream(rhs):
            return _stream.stream_penta_solve_mid(
                self.fac_y,
                rhs,
                cyclic=self.cyclic,
                streams=self.streams,
                max_tile_bytes=self.max_tile_bytes,
                backend=backend,
                unroll=unroll,
            )
        solve = (
            cyclic_penta_solve_factored_mid
            if self.cyclic
            else penta_solve_factored_mid
        )
        return solve(
            self.fac_y, rhs, backend=backend, tn=cfg.get("tn"), unroll=unroll
        )

    @obs.stage("adi.z")
    def solve_z(self, rhs: jnp.ndarray) -> jnp.ndarray:
        """Solve L_z u = rhs along the z (first) axis — column layout on
        the (nz, ny*nx) reshape, transpose-free."""
        from repro.launch import stream as _stream

        backend, unroll, cfg = self._cfg(self.z_cfg)
        if backend == "fft":
            return _fft_sweep(self.sym_z, rhs, axis=-3)
        nz, ny, nx = rhs.shape
        flat = rhs.reshape(nz, ny * nx)
        if self._should_stream(rhs):
            out = _stream.stream_penta_solve(
                self.fac_z,
                flat,
                cyclic=self.cyclic,
                streams=self.streams,
                max_tile_bytes=self.max_tile_bytes,
                backend=backend,
                unroll=unroll,
            )
        else:
            solve = (
                cyclic_penta_solve_factored
                if self.cyclic
                else penta_solve_factored
            )
            out = solve(
                self.fac_z, flat, backend=backend, tn=cfg.get("tn"),
                unroll=unroll,
            )
        return out.reshape(rhs.shape)

    def grid_problems(self, shape) -> list:
        """Why this operator cannot sweep an ``(nz, ny, nx)`` box — factor
        lengths vs extents plus tuned Pallas batch-tile divisibility."""
        nz, ny, nx = (int(s) for s in shape)
        problems = []
        lens = (
            _fac_len(self.fac_x), _fac_len(self.fac_y), _fac_len(self.fac_z)
        )
        if lens != (nx, ny, nz):
            problems.append(
                f"factor lengths (x={lens[0]}, y={lens[1]}, z={lens[2]}) do "
                f"not match the field ({nz}, {ny}, {nx}); the plan was "
                "Created for another shape"
            )
        problems += _cfg_tile_problems(
            self.x_cfg, "x", "tb", nz * ny, "rows nz*ny"
        )
        problems += _cfg_tile_problems(self.y_cfg, "y", "tn", nx, "lanes nx")
        problems += _cfg_tile_problems(
            self.z_cfg, "z", "tn", ny * nx, "lanes ny*nx"
        )
        return problems


def _autotune_adi3d(
    op: ADIOperator3D, nz: int, ny: int, nx: int, dtype, mode: str, cache
):
    """Measure per-sweep solve configurations and attach the winners —
    the 3D twin of :func:`_autotune_adi`, sharing its candidate space."""
    from repro.tune import autotune

    rhs = jnp.zeros((nz, ny, nx), dtype)
    extra = {"cyclic": op.cyclic, "operator": op.operator}
    kw = dict(
        shape=(nz, ny, nx), dtype=dtype, backend=op.backend, extra=extra,
        mode=mode, cache=cache,
    )

    # measure the *monolithic* solves (streams knocked out): the streamed
    # executor ignores per-sweep tiles, so routing candidates through it
    # would time the identical call per tile and cache a winner the
    # operator never applies
    mono = dataclasses.replace(op, streams=None, max_tile_bytes=None)

    def build(solve_name, tile_key):
        def builder(cfg):
            op2 = dataclasses.replace(
                mono, **{solve_name + "_cfg": _sweep_cfg(cfg, tile_key)}
            )
            return jax.jit(getattr(op2, "solve_" + solve_name))

        return builder

    fft = _fft_arbitrage(op)
    best_x = autotune(
        "adi3d_solve_x", _sweep_candidates(nz * ny, fft=fft),
        build("x", "tb"), (rhs,), **kw
    )
    best_y = autotune(
        "adi3d_solve_y", _sweep_candidates(nx, fft=fft), build("y", "tn"),
        (rhs,), **kw
    )
    best_z = autotune(
        "adi3d_solve_z", _sweep_candidates(ny * nx, fft=fft),
        build("z", "tn"), (rhs,), **kw
    )
    return dataclasses.replace(
        op,
        x_cfg=_sweep_cfg(best_x, "tb"),
        y_cfg=_sweep_cfg(best_y, "tn"),
        z_cfg=_sweep_cfg(best_z, "tn"),
    )


def _make_adi_operator_3d(
    nz: int,
    ny: int,
    nx: int,
    alpha,
    *,
    cyclic: bool = True,
    dtype=jnp.float64,
    backend: str = "auto",
    alpha_y: float | None = None,
    alpha_z: float | None = None,
    streams: int | None = None,
    max_tile_bytes: int | None = None,
    tune: str = "off",
    tune_cache=None,
    operator: str = "hyperdiffusion",
) -> ADIOperator3D:
    """Create (factor) the 3D ADI operator triple.

    ``alpha`` multiplies the per-direction difference operator:
    ``I + alpha delta^4`` for ``operator='hyperdiffusion'`` (the
    Cahn–Hilliard-style splitting), ``I - alpha delta^2`` for
    ``operator='diffusion'`` (backward-Euler heat sweeps,
    ``alpha = D dt / h^2``).  ``alpha_y``/``alpha_z`` override the x
    coefficient per direction on anisotropic grids.

    ``tune`` (``'off'|'cached'|'force'``) runs the Create-time autotuner
    over per-sweep backend / batch-tile / unroll candidates, reusing the
    2D tuner's candidate space and cache keying.
    """
    _check_adi_backend(backend, cyclic)
    diagonals = _band_builder(operator)
    ax = alpha
    ay = alpha if alpha_y is None else alpha_y
    az = alpha if alpha_z is None else alpha_z
    factor = cyclic_penta_factor if cyclic else penta_factor
    sym_x = sym_y = sym_z = None
    if cyclic:
        sym_x = spectral.band_symbol(*diagonals(nx, ax, dtype), dtype=dtype)
        sym_y = spectral.band_symbol(*diagonals(ny, ay, dtype), dtype=dtype)
        sym_z = spectral.band_symbol(*diagonals(nz, az, dtype), dtype=dtype)
    op = ADIOperator3D(
        fac_x=factor(*diagonals(nx, ax, dtype)),
        fac_y=factor(*diagonals(ny, ay, dtype)),
        fac_z=factor(*diagonals(nz, az, dtype)),
        cyclic=cyclic,
        backend=backend,
        streams=streams,
        max_tile_bytes=max_tile_bytes,
        operator=operator,
        sym_x=sym_x,
        sym_y=sym_y,
        sym_z=sym_z,
    )
    if tune != "off":
        op = _autotune_adi3d(
            op, nz, ny, nx, jnp.dtype(dtype), tune, tune_cache
        )
    return op


# ---------------------------------------------------------------------------
# Pytree registration + deprecated factories
# ---------------------------------------------------------------------------


def _freeze_cfg(cfg):
    """Tuned sweep-config dict -> hashable pytree aux (lists, which JSON
    cache round-trips produce from tuples, become tuples)."""
    if cfg is None:
        return None
    return tuple(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in sorted(cfg.items())
    )


def _thaw_cfg(frozen):
    return None if frozen is None else dict(frozen)


def _register_adi_pytree(cls, fac_fields, cfg_fields, static_fields):
    """Register an ADI operator dataclass as a JAX pytree: the factored
    bands (every array of the Create-time factorisation, including the
    cyclic Woodbury ``W``) are leaves; the solve configuration is static
    aux — so operators pass through jit/vmap/donation like any array."""

    def flatten(op):
        children = tuple(getattr(op, f) for f in fac_fields)
        aux = tuple(getattr(op, f) for f in static_fields) + tuple(
            _freeze_cfg(getattr(op, f)) for f in cfg_fields
        )
        # destroyed mark in the aux: a destroyed operator gets a new
        # treedef, so a jitted compute retraces and refuses it
        return children, aux + (getattr(op, "_destroyed", False),)

    def unflatten(aux, children):
        kwargs = dict(zip(fac_fields, children, strict=True))
        kwargs.update(zip(static_fields, aux[: len(static_fields)], strict=True))
        kwargs.update(
            (f, _thaw_cfg(v))
            for f, v in zip(cfg_fields, aux[len(static_fields):-1], strict=True)
        )
        op = cls(**kwargs)
        if aux[-1]:
            object.__setattr__(op, "_destroyed", True)
        return op

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)


_register_adi_pytree(
    ADIOperator,
    fac_fields=("fac_x", "fac_y", "sym_x", "sym_y"),
    cfg_fields=("x_cfg", "y_cfg"),
    static_fields=(
        "cyclic", "backend", "streams", "max_tile_bytes", "operator",
    ),
)
_register_adi_pytree(
    ADIOperator3D,
    fac_fields=("fac_x", "fac_y", "fac_z", "sym_x", "sym_y", "sym_z"),
    cfg_fields=("x_cfg", "y_cfg", "z_cfg"),
    static_fields=(
        "cyclic", "backend", "streams", "max_tile_bytes", "operator",
    ),
)


make_adi_operator = deprecated_shim(
    "make_adi_operator", "create(..., mode='adi')", _make_adi_operator
)
make_adi_operator_3d = deprecated_shim(
    "make_adi_operator_3d", "create(..., mode='adi')", _make_adi_operator_3d
)
