"""The Cahn–Hilliard ADI solver (paper §V, "cuCahnPentADI"), 2D and 3D.

Solves  dC/dt = D grad^2 (C^3 - C - gamma grad^2 C)  on a periodic box,
with the two-step Beam–Warming-style ADI scheme of paper eq. (2):

    L_x w = -(2/3)(C^n - C^{n-1})
            - (2/3) dt D gamma grad^4 Cbar^{n+1}
            + (2/3) D dt grad^2 (C^3 - C)^n
    L_y v = w
    C^{n+1} = Cbar^{n+1} + v,        Cbar^{n+1} = 2 C^n - C^{n-1}

with L = I + (2/3) D gamma dt d^4/dx^4 (pentadiagonal, factored once), and a
standard ADI half-step pair (paper eq. 3) to bootstrap C^1 from C^0.

In 3D (``CHConfig.nz`` set; the Gloster thesis, arXiv 2101.06550) the same
three-level step gains a third implicit factor, ``L_x w = rhs``,
``L_y u = w``, ``L_z v = u``, with grad^4 the 25-tap 5x5x5 biharmonic and
grad^2 the 7-point Laplacian.  The 3D factors carry

    L = I + (3/2) beta d^4/dx^4 = I + (D gamma dt / h^4) d^4/dx^4

and not eq. (2)'s ``beta = (2/3) D gamma dt / h^4``: three factors at
beta hold too little of the explicit cross terms
``2 beta (d_x d_y + d_y d_z + d_z d_x)``, and the linear step's growth
factor then exceeds 1 for beta above about 0.0137 (1.30 at 0.067).  With
the factors' coefficient at about 1.12 beta or more, no grid mode grows
at any beta; (3/2) beta leaves room and is the bootstrap's coefficient,
so one factored triple serves both.  The factors change by O(dt) and act
on ``v = C^{n+1} - Cbar = O(dt^2)``, so the step stays second order, as
eq. (2)'s own splitting error is.  On a field constant along z, ``L_z`` is
the identity and the 3D step is the 2D step of eq. (2) with its factors'
coefficient raised to (3/2) beta.  The bootstrap is implicit in all three
directions and first order, as eq. (3) is:

    (I + b d_x^4)(I + b d_y^4)(I + b d_z^4)(C^1 - C^0)
        = dt D [-gamma grad^4 C^0 + grad^2 (C^3 - C)^0],   b = D gamma dt / h^4

3D runs ``rhs_mode='stencil'`` only.

Three interchangeable RHS paths in 2D (validated identical in tests):

- ``rhs_mode='stencil'`` — paper-faithful: the RHS is assembled from cuSten
  plan calls: a 5x5 weighted XY plan for grad^4, and a 3x3 *function-pointer*
  plan applying the Laplacian directly to (C^3 - C) — the exact structure of
  the paper's code (§V.B).
- ``rhs_mode='batch1d'`` — the batched-1D decomposition: every directional
  piece (``delta_x^2``, ``delta_y^2``, the two ``delta`` factors of the
  cross term, and the per-direction Laplacian of ``C^3 - C``) is a
  :class:`~repro.core.stencil.StencilBatch1D` plan run over all grid lines
  at once via :func:`~repro.core.adi.apply_along_x` /
  :func:`~repro.core.adi.apply_along_y` — the explicit counterpart of the
  ADI sweeps' batched implicit solves (no full-2D stencil calls at all).
- ``rhs_mode='fused'`` — beyond-paper: one fused Pallas pass
  (:mod:`repro.kernels.fused_ch`) computing the entire explicit RHS.
"""

from __future__ import annotations

import dataclasses
import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import api as _api
from repro import obs
from repro.core import metrics as _metrics
from repro.core import stream as _stream
from repro.runtime import chaos as _chaos
from repro.core.adi import (
    apply_along_x,
    apply_along_y,
)
from repro.kernels import ops as _ops

# ---------------------------------------------------------------------------
# Stencil weight tables (paper eq. 4; §V.B stencil shapes) — sourced from
# the repro.api operator registry, the single home of named operators
# ---------------------------------------------------------------------------

_D4 = np.asarray(_api.get_operator("biharmonic").weights(1))  # eq. (4b)
_D2 = np.asarray(_api.get_operator("laplacian").weights(1))  # eq. (4a)
_LAP = np.asarray(_api.get_operator("laplacian").weights(2))
_LAP3 = np.asarray(_api.get_operator("laplacian").weights(3))


def biharmonic_weights() -> np.ndarray:
    """5x5 weights of delta_x^2 + delta_y^2 + 2 delta_x delta_y (units h^-4)
    — the registry's ``"biharmonic"`` operator at ndim=2."""
    return np.asarray(_api.get_operator("biharmonic").weights(2))


def init_explicit_weights_a() -> np.ndarray:
    """(5y x 3x) weights of 2 delta_x delta_y + delta_y^2 (eq. 3a explicit)."""
    w = np.zeros((5, 3))
    w[:, 1] += _D4
    w[1:4, :] += 2.0 * np.outer(_D2, _D2)
    return w


def init_explicit_weights_b() -> np.ndarray:
    """(3y x 5x) weights of delta_x^2 + 2 delta_x delta_y (eq. 3b explicit)."""
    w = np.zeros((3, 5))
    w[1, :] += _D4
    w[:, 1:4] += 2.0 * np.outer(_D2, _D2)
    return w


def cube_laplacian_point_fn(windows, coeffs):
    """The paper's flagship function pointer: apply Laplacian weights to
    (C^3 - C) of each window — nonlinearity inside the stencil sweep."""
    out = None
    for w, c in zip(windows, coeffs, strict=True):
        term = c * (w * w * w - w)
        out = term if out is None else out + term
    return out


# ---------------------------------------------------------------------------
# Config + solver
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CHConfig:
    """One Cahn–Hilliard run: grid, box, time step, physics and execution.

    ``nz=None`` is the paper's 2D scheme on ``(ny, nx)``; an integer ``nz``
    makes it the 3D scheme on ``(nz, ny, nx)`` (``rhs_mode='stencil'``),
    whose z side is ``nz * dx``: the grid is uniform.

    The default ``dt = 1e-3`` suits small grids only.  In 2D, with the
    deep-quench initial field, it overflows within two steps at 1024^2 and
    above, in float64 as in float32: the bootstrap (eq. 3) treats one
    direction's hyperdiffusion explicitly and multiplies grid-scale noise
    by about ``8 D gamma dt / h^4``.  The 3D bootstrap and step hold every
    direction's hyperdiffusion implicitly, and no grid mode of their
    linear part grows at any dt (module doc); at dt = 1e-3 the 3D scheme
    has been run at 64^3 (``beta = (2/3) D gamma dt / h^4`` about 0.043) and
    not at 1024 a side.  Grids of 1024 or more a side take the rule the
    benchmark's configurations use instead,
    ``dt = dt_factor * h^4 / (D gamma)``, which holds
    ``beta = (2/3) dt_factor`` fixed on every grid: ``dt_factor = 0.1``,
    beta = 0.067, in 2D and in 3D.
    """

    nx: int = 1024
    ny: int = 1024
    lx: float = 2.0 * np.pi
    ly: float = 2.0 * np.pi
    dt: float = 1e-3
    D: float = 0.6
    gamma: float = 0.01
    dtype: str = "float64"
    rhs_mode: str = "fused"  # 'fused' | 'stencil' | 'batch1d'
    backend: str = "auto"  # kernel backend for stencils & penta
    # streamed tiled execution (cuSten nStreams) for domains > one tile:
    streams: int | None = None
    max_tile_bytes: int | None = None
    # Create-time autotuning ('off' | 'cached' | 'force'): measure solve /
    # stream configurations once at Create, remember them on disk
    tune: str = "off"
    # the third axis: None keeps the paper's 2D scheme
    nz: int | None = None

    @property
    def dx(self) -> float:
        return self.lx / self.nx

    @property
    def dy(self) -> float:
        return self.ly / self.ny

    @property
    def shape(self) -> tuple[int, ...]:
        """``(ny, nx)``, or ``(nz, ny, nx)`` in 3D."""
        return (self.ny, self.nx) if self.nz is None else (self.nz, self.ny, self.nx)

    def validate(self):
        if abs(self.dx - self.dy) > 1e-12:
            raise ValueError("paper scheme assumes a uniform grid dx == dy")
        if self.nz is not None and self.rhs_mode != "stencil":
            raise ValueError(
                f"rhs_mode={self.rhs_mode!r} is 2D only: a 3D configuration "
                "(nz set) assembles its RHS from Stencil3D plans, "
                "rhs_mode='stencil'"
            )
        from repro.tune import check_mode

        check_mode(self.tune)


class CahnHilliardADI:
    """Create-once / compute-many solver object (the cuSten usage pattern)."""

    def __init__(self, cfg: CHConfig):
        cfg.validate()
        self.cfg = cfg
        dtype = jnp.dtype(cfg.dtype)
        h4 = cfg.dx**4
        h2 = cfg.dx**2
        self.inv_h2 = 1.0 / h2
        self.inv_h4 = 1.0 / h4

        # Create: factor the implicit operators once (cuPentBatch pattern).
        # With tune != 'off' the solve configuration (per-sweep backend,
        # batch tile, unroll) is *measured*; op_half shares op_full's cache
        # entry — the key is (shape, dtype, backend), not the alpha value,
        # because substitution cost does not depend on the coefficients.
        beta_full = (2.0 / 3.0) * cfg.D * cfg.gamma * cfg.dt / h4
        mk_op = functools.partial(
            _api.create, "hyperdiffusion", cfg.shape, mode="adi",
            cyclic=True, dtype=dtype, backend=cfg.backend,
            streams=cfg.streams, max_tile_bytes=cfg.max_tile_bytes,
        )
        self._evolve_cache = {}  # chunk length -> compiled donated driver
        if cfg.nz is not None:
            self._create_3d(mk_op, dtype)
            return
        self.op_full = mk_op(alpha=beta_full, tune=cfg.tune)
        beta_half = 0.5 * cfg.D * cfg.gamma * cfg.dt / h4
        self.op_half = mk_op(
            alpha=beta_half,
            tune="cached" if cfg.tune == "force" else cfg.tune,
        )
        # tuned x-sweep unroll feeds the fused RHS+sweep path too
        self._unroll = (self.op_full.x_cfg or {}).get("unroll", 1)
        self._streams_eff = cfg.streams
        self._chunk_rows_eff = None  # None -> choose_chunk_rows heuristic

        # Create: the stencil plans (paper-faithful RHS path), all through
        # the four-function facade — shape doubles as the tuning shape.
        mk = functools.partial(
            _api.create, shape=(cfg.ny, cfg.nx), mode="xy", bc="periodic",
            dtype=dtype, backend=cfg.backend, streams=cfg.streams,
            max_tile_bytes=cfg.max_tile_bytes, tune=cfg.tune,
        )
        self.plan_bih = mk("biharmonic")
        self.plan_lap_cube = mk(
            cube_laplacian_point_fn,
            coeffs=_LAP.ravel(),
            extents=dict(left=1, right=1, top=1, bottom=1),
        )
        self.plan_init_a = mk(init_explicit_weights_a())
        self.plan_init_b = mk(init_explicit_weights_b())

        # Create: the batched-1D plans (per-direction RHS path).  Each is one
        # directional factor; apply_along_{x,y} runs it over all grid lines.
        # These plans are applied in BOTH orientations ((ny, nx) rows and the
        # (nx, ny) transpose for the y direction), so a tuned tile baked for
        # one orientation would reject the other on rectangular domains —
        # tune them only when the two orientations coincide.
        tune_1d = cfg.tune if cfg.ny == cfg.nx else "off"
        mk1d = functools.partial(
            _api.create, shape=(cfg.ny, cfg.nx), mode="batch",
            bc="periodic", dtype=dtype, backend=cfg.backend,
            streams=cfg.streams, max_tile_bytes=cfg.max_tile_bytes,
            tune=tune_1d,
        )
        self.plan_d4_1d = mk1d(_D4)
        self.plan_d2_1d = mk1d(_D2)
        self.plan_lap_cube_1d = mk1d(
            cube_laplacian_point_fn,
            coeffs=_D2,
            extents=dict(left=1, right=1),
        )

        # Tune the streamed fused hot path's geometry — pipeline width
        # (chunks in flight) x chunk height (rows per slab) — when
        # streaming is on: both are properties of the host, not of the
        # PDE, and the 2D grid subsumes choose_chunk_rows' divisor
        # heuristic (ROADMAP "tuned streaming geometry").
        if cfg.tune != "off" and cfg.rhs_mode == "fused":
            if _stream.should_stream(
                (cfg.ny, cfg.nx), dtype.itemsize,
                streams=cfg.streams, max_tile_bytes=cfg.max_tile_bytes,
            ):
                self._streams_eff, self._chunk_rows_eff = (
                    self._tune_stream_geometry(dtype)
                )

    def _create_3d(self, mk_op, dtype):
        """The 3D Create: one ADIOperator3D triple at (3/2) beta for the
        step and the bootstrap (module doc), and the two Stencil3D plans of
        the RHS — the 5x5x5 biharmonic and the function-pointer Laplacian
        of (C^3 - C)."""
        cfg = self.cfg
        self.op_full = mk_op(
            alpha=cfg.D * cfg.gamma * cfg.dt * self.inv_h4, tune=cfg.tune
        )
        mk = functools.partial(
            _api.create, shape=cfg.shape, mode="xyz", bc="periodic",
            dtype=dtype, backend=cfg.backend, streams=cfg.streams,
            max_tile_bytes=cfg.max_tile_bytes, tune=cfg.tune,
        )
        self.plan_bih = mk("biharmonic")
        self.plan_lap_cube = mk(
            cube_laplacian_point_fn,
            coeffs=_LAP3.ravel(),
            extents={k: 1 for k in ("left", "right", "top", "bottom", "front", "back")},
        )

    # -- batched-1D directional assembly (rhs_mode='batch1d') ----------------
    def _cross_batch1d(self, c: jnp.ndarray) -> jnp.ndarray:
        """delta_x delta_y c — two directional 3-point factors."""
        return apply_along_x(self.plan_d2_1d, apply_along_y(self.plan_d2_1d, c))

    def _bih_batch1d(self, c: jnp.ndarray) -> jnp.ndarray:
        """delta_x^2 + delta_y^2 + 2 delta_x delta_y (units h^-4)."""
        return (
            apply_along_x(self.plan_d4_1d, c)
            + apply_along_y(self.plan_d4_1d, c)
            + 2.0 * self._cross_batch1d(c)
        )

    def _lap_cube_batch1d(self, c: jnp.ndarray) -> jnp.ndarray:
        """Laplacian of (C^3 - C) via the per-direction function-pointer
        plan: the nonlinearity is evaluated inside each 1D sweep."""
        return apply_along_x(self.plan_lap_cube_1d, c) + apply_along_y(
            self.plan_lap_cube_1d, c
        )

    # -- explicit RHS of the full scheme (eq. 2a) --------------------------
    @obs.stage("ch.rhs")
    def rhs(self, c_n: jnp.ndarray, c_nm1: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.rhs_mode == "fused":
            if _stream.should_stream(
                c_n.shape,
                c_n.dtype.itemsize,
                streams=cfg.streams,
                max_tile_bytes=cfg.max_tile_bytes,
            ):
                return _stream.stream_ch_rhs(
                    c_n,
                    c_nm1,
                    dt=cfg.dt,
                    D=cfg.D,
                    gamma=cfg.gamma,
                    inv_h2=self.inv_h2,
                    inv_h4=self.inv_h4,
                    streams=cfg.streams,
                    max_tile_bytes=cfg.max_tile_bytes,
                )
            return _ops.ch_rhs(
                c_n,
                c_nm1,
                dt=cfg.dt,
                D=cfg.D,
                gamma=cfg.gamma,
                inv_h2=self.inv_h2,
                inv_h4=self.inv_h4,
                backend=cfg.backend,
            )
        if cfg.rhs_mode in ("stencil", "batch1d"):
            bih = (
                self._bih_batch1d
                if cfg.rhs_mode == "batch1d"
                else self.plan_bih.apply
            )
            lap_cube = (
                self._lap_cube_batch1d
                if cfg.rhs_mode == "batch1d"
                else self.plan_lap_cube.apply
            )
            cbar = 2.0 * c_n - c_nm1
            lin = -(2.0 / 3.0) * (c_n - c_nm1)
            hyper = (
                -(2.0 / 3.0)
                * cfg.dt
                * cfg.gamma
                * cfg.D
                * self.inv_h4
                * bih(cbar)
            )
            nonlin = (
                (2.0 / 3.0)
                * cfg.D
                * cfg.dt
                * self.inv_h2
                * lap_cube(c_n)
            )
            return lin + hyper + nonlin
        raise ValueError(f"unknown rhs_mode {cfg.rhs_mode!r}")

    def _tune_stream_geometry(self, dtype):
        """Measure the (pipeline width x chunk height) candidate grid for
        the streamed fused sweep and return ``(streams, chunk_rows)``.

        ``chunk_rows=None`` in a candidate means "let
        :func:`~repro.core.stream.choose_chunk_rows` decide" — the
        pre-grid heuristic stays in the race as one contender among the
        measured divisor heights, so tuning can only match or beat it.
        """
        from repro.tune import autotune

        cfg = self.cfg
        c = jnp.zeros((cfg.ny, cfg.nx), dtype)

        def build(cand):
            def f(a, b):
                return _stream.stream_ch_rhs_xsweep(
                    a, b, self.op_full.fac_x,
                    dt=cfg.dt, D=cfg.D, gamma=cfg.gamma,
                    inv_h2=self.inv_h2, inv_h4=self.inv_h4,
                    streams=cand["streams"],
                    chunk_rows=cand.get("chunk_rows"),
                    max_tile_bytes=cfg.max_tile_bytes,
                    unroll=self._unroll,
                )

            return jax.jit(f)

        base = cfg.streams or 1
        widths = sorted({1, 2, 4, 8, base})
        # divisor chunk heights around the byte-budget heuristic (None) —
        # heights whose halo-padded slab would bust the user's byte budget
        # are excluded, so tuning cannot un-bound the working set
        budget = cfg.max_tile_bytes
        heights = [None] + sorted(
            {
                r
                for r in (cfg.ny // k for k in (4, 8, 16))
                if r > 0
                and cfg.ny % r == 0
                and (
                    budget is None
                    or _stream.slab_bytes(
                        r, cfg.nx, dtype.itemsize,
                        top=2, bottom=2, left=2, right=2,
                    ) <= budget
                )
            },
            reverse=True,
        )
        best = autotune(
            "ch_stream_geometry",
            [
                {"streams": s, "chunk_rows": r}
                for s in widths
                for r in heights
            ],
            build,
            (c, c),
            shape=(cfg.ny, cfg.nx),
            dtype=dtype,
            backend=cfg.backend,
            # streams is part of the key: it shapes the candidate list, so
            # differing configs must not ping-pong one cache entry
            extra={"max_tile_bytes": cfg.max_tile_bytes,
                   "streams": cfg.streams},
            mode=cfg.tune,
            default={"streams": base, "chunk_rows": None},
        )
        return best["streams"], best.get("chunk_rows")

    # -- fused explicit RHS + transpose-free x-sweep (the hot loop) ---------
    @obs.stage("adi.x")  # the x sweep, with the RHS folded in
    def _fused_xsweep(self, c_n: jnp.ndarray, c_nm1: jnp.ndarray) -> jnp.ndarray:
        """``L_x^{-1} rhs(c_n, c_nm1)`` in one fused pass — the RHS feeds
        the row-layout x-sweep in its native layout, streamed when the
        domain exceeds one tile."""
        cfg = self.cfg
        if _stream.should_stream(
            c_n.shape,
            c_n.dtype.itemsize,
            streams=cfg.streams,
            max_tile_bytes=cfg.max_tile_bytes,
        ):
            return _stream.stream_ch_rhs_xsweep(
                c_n,
                c_nm1,
                self.op_full.fac_x,
                dt=cfg.dt,
                D=cfg.D,
                gamma=cfg.gamma,
                inv_h2=self.inv_h2,
                inv_h4=self.inv_h4,
                streams=self._streams_eff,
                chunk_rows=self._chunk_rows_eff,
                max_tile_bytes=cfg.max_tile_bytes,
                backend=cfg.backend,
                unroll=self._unroll,
            )
        return _ops.ch_rhs_xsweep(
            c_n,
            c_nm1,
            self.op_full.fac_x,
            dt=cfg.dt,
            D=cfg.D,
            gamma=cfg.gamma,
            inv_h2=self.inv_h2,
            inv_h4=self.inv_h4,
            backend=cfg.backend,
            unroll=self._unroll,
        )

    # -- one full scheme step (eq. 2) ---------------------------------------
    def step(
        self, c_n: jnp.ndarray, c_nm1: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """One full-scheme step.  Transpose-free end to end: the fused path
        assembles the RHS straight into the x-sweep; both sweeps consume
        their Create-time factors in their native layout."""
        if self.cfg.rhs_mode == "fused":
            w = self._fused_xsweep(c_n, c_nm1)
        else:
            w = self.op_full.solve_x(self.rhs(c_n, c_nm1))
        v = self.op_full.solve_y(w)
        if self.cfg.nz is not None:
            v = self.op_full.solve_z(v)  # the third implicit factor
        with obs.stage("ch.update"):
            c_np1 = 2.0 * c_n - c_nm1 + v
        return c_np1, c_n

    # -- bootstrap step (eq. 3) ---------------------------------------------
    @obs.stage("ch.bootstrap")
    def initial_step(self, c0: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.nz is not None:
            # implicit in all three directions, first order (module doc)
            rhs = cfg.dt * cfg.D * (
                -cfg.gamma * self.inv_h4 * self.plan_bih.apply(c0)
                + self.inv_h2 * self.plan_lap_cube.apply(c0)
            )
            return c0 + _api.compute(self.op_full, rhs)
        half = 0.5 * cfg.dt
        coef_h = cfg.D * cfg.gamma * self.inv_h4

        if cfg.rhs_mode == "batch1d":
            # per-direction explicit operators of eq. (3), assembled from
            # the 1D plans: a = delta_y^2 + 2 dxdy, b = delta_x^2 + 2 dxdy
            expl_a = lambda c: (  # noqa: E731
                apply_along_y(self.plan_d4_1d, c) + 2.0 * self._cross_batch1d(c)
            )
            expl_b = lambda c: (  # noqa: E731
                apply_along_x(self.plan_d4_1d, c) + 2.0 * self._cross_batch1d(c)
            )
            lap_cube = self._lap_cube_batch1d
        else:
            expl_a = self.plan_init_a.apply
            expl_b = self.plan_init_b.apply
            lap_cube = self.plan_lap_cube.apply

        rhs_a = c0 + half * (
            -coef_h * expl_a(c0)
            + cfg.D * self.inv_h2 * lap_cube(c0)
        )
        c_half = self.op_half.solve_x(rhs_a)

        rhs_b = c_half + half * (
            -coef_h * expl_b(c_half)
            + cfg.D * self.inv_h2 * lap_cube(c_half)
        )
        return self.op_half.solve_y(rhs_b)

    # -- drivers -------------------------------------------------------------
    def make_scan_step(self) -> Callable:
        """A jit/scan-compatible pure step: carry = (c_n, c_nm1)."""

        def body(carry, _):
            c_n, c_nm1 = carry
            c_np1, c_n_out = self.step(c_n, c_nm1)
            return (c_np1, c_n_out), None

        return body

    def make_evolve(self, chunk: int) -> Callable:
        """A compiled ``(c_n, c_nm1) -> (c_{n+chunk}, c_{n+chunk-1})``
        multi-step driver with the scan carry *donated* through the jit
        boundary: between chunks the two field buffers are double-buffered
        in place (cuSten's pointer Swap across whole chunks of steps).
        Compiled once per chunk length and cached on the solver."""
        fn = self._evolve_cache.get(chunk)
        if fn is None:
            body = self.make_scan_step()

            def evolve(c_n, c_nm1):
                (a, b), _ = jax.lax.scan(
                    body, (c_n, c_nm1), None, length=chunk
                )
                return a, b

            fn = jax.jit(evolve, donate_argnums=(0, 1))
            self._evolve_cache[chunk] = fn
        return fn

    def run(
        self,
        c0: jnp.ndarray,
        n_steps: int,
        *,
        save_every: int = 0,
        metrics_fn: Callable | None = None,
    ):
        """Integrate ``n_steps`` of the full scheme (plus the bootstrap step).

        Returns ``(c_final, history)`` where history is a list of
        ``(step, metrics_fn(c))`` collected every ``save_every`` steps.
        Delegates to :func:`ch_evolve` (donated double-buffered carry).
        """
        return ch_evolve(
            self, c0, n_steps, save_every=save_every, metrics_fn=metrics_fn
        )


def ch_evolve(
    solver: CahnHilliardADI,
    c0: jnp.ndarray,
    n_steps: int,
    *,
    save_every: int = 0,
    metrics_fn: Callable | None = None,
):
    """Multi-step driver with a donated, double-buffered scan carry.

    Runs the bootstrap step, then advances in compiled chunks whose
    ``(c_n, c_nm1)`` carry buffers are donated across the jit boundary:
    on accelerators each chunk writes into the buffers the previous chunk
    released (the Create/Compute-era pointer swap, across whole chunks).
    ``c0`` is copied once on entry so the caller's array survives
    donation.  Returns ``(c_final, history)`` with history a list of
    ``(step, metrics_fn(c))`` every ``save_every`` steps.
    """
    with obs.span("evolve.bootstrap"):
        c0 = jnp.array(c0)  # private copy: the carry buffers get donated
        c1 = solver.initial_step(c0)
    # the Swap: the freshly computed field becomes the carry's "current"
    carry = _api.swap((c0, c1))
    chunk = save_every if save_every else n_steps
    history = []
    done = 1  # initial step counts as step 1
    while done < n_steps + 1:
        todo = min(chunk, n_steps + 1 - done)
        with obs.span("evolve.chunk"):
            # chaos hook at the chunk boundary: 'crash' kills the run here
            # (checkpoint/restart territory), 'nan' poisons the carry so the
            # chunk blows up — both consumed by runtime/resilient.py's guard
            fault = _chaos.fire("evolve.step", step=done)
            if fault is not None and fault.kind == "nan":
                carry = (carry[0].at[(0,) * carry[0].ndim].set(fault.value), carry[1])
            carry = solver.make_evolve(todo)(*carry)
        done += todo
        if metrics_fn is not None:
            with obs.span("evolve.metrics"):
                history.append((done, metrics_fn(carry[0])))
    return carry[0], history


def deep_quench_ic(
    ny: int, nx: int, *, seed: int = 0, amp: float = 0.1, dtype="float64"
) -> jnp.ndarray:
    """The paper's initial condition: uniform random values in [-amp, amp]."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(-amp, amp, (ny, nx)), jnp.dtype(dtype))


def coarsening_metrics(cfg: CHConfig):
    """metrics_fn for :meth:`CahnHilliardADI.run` returning (s, 1/k1, F, M)."""

    @jax.jit
    def fn(c):
        s = _metrics.s_metric(c, cfg.lx, cfg.ly)
        k1 = _metrics.k1_metric(c, cfg.lx, cfg.ly)
        F = _metrics.free_energy(c, cfg.gamma, cfg.lx, cfg.ly)
        m = _metrics.mass(c, cfg.lx, cfg.ly)
        return s, 1.0 / k1, F, m

    return fn
