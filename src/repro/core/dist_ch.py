"""Distributed Cahn–Hilliard ADI — the paper's solver at pod scale.

Decomposition strategy (production layout, see DESIGN.md §5):

- the explicit RHS runs on the 2D block decomposition ``P(y→data, x→model)``
  (stencil halos = neighbour collective-permutes, inserted by XLA SPMD for
  the jnp path or explicitly by :mod:`repro.core.domain`);
- the x-sweep reshards to ``P((data, model), None)`` — y fully sharded,
  x local — so the pentadiagonal recurrence runs without cross-device
  dependencies; the y-sweep reshards to ``P(None, (data, model))``.
  The two reshards are the paper's "transpose between sweeps", realised as
  all-to-alls;
- an optional ensemble axis (independent runs of the same PDE, the natural
  multi-pod workload) maps onto ``pod``.

The per-direction solves reuse the Create-time factors; substitution is the
scan-based path (the recurrence axis is local after resharding, so the scan
is collective-free).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.cahn_hilliard import CahnHilliardADI, CHConfig
from repro.core.domain import DomainDecomposition
from repro.core.stream import stream_stencil_apply_dist
from repro.kernels.penta import (
    cyclic_penta_solve_factored,
)
from repro.kernels.ref import ch_rhs_ref


@dataclasses.dataclass(frozen=True)
class DistCHLayouts:
    block: P  # 2D block decomposition for stencil work
    xsweep: P  # y fully sharded, x local
    ysweep: P  # x fully sharded, y local


def make_layouts(dd: DomainDecomposition) -> DistCHLayouts:
    ya, xa, ea = dd.y_axis, dd.x_axis, dd.ensemble_axis
    flat = tuple(a for a in (ya, xa) if a is not None)
    if ea:
        return DistCHLayouts(
            block=P(ea, ya, xa),
            xsweep=P(ea, flat, None),
            ysweep=P(ea, None, flat),
        )
    return DistCHLayouts(
        block=P(ya, xa), xsweep=P(flat, None), ysweep=P(None, flat)
    )


class DistributedCahnHilliard:
    """Create-once distributed solver: factors + layouts captured, the step
    is a pure function suitable for jit/lower on the production mesh."""

    def __init__(self, cfg: CHConfig, dd: DomainDecomposition):
        cfg.validate()
        self.cfg = cfg
        self.dd = dd
        self.layouts = make_layouts(dd)
        # Reuse the single-device Create (factors are (n,)-sized — replicated)
        self._local = CahnHilliardADI(
            dataclasses.replace(cfg, backend="jnp", rhs_mode="fused")
        )

    # -- pure step usable under jit -----------------------------------------
    def step(self, c_n: jnp.ndarray, c_nm1: jnp.ndarray):
        """One full-scheme step on (ny, nx) or ensemble (E, ny, nx) fields."""
        cfg, lay = self.cfg, self.layouts
        cons = jax.lax.with_sharding_constraint
        mesh = self.dd.mesh

        def sh(spec):
            return NamedSharding(mesh, spec)

        ens = c_n.ndim == 3

        def per_field(f):
            return f  # rank handled by vmap below

        rhs = ch_rhs_ref(
            c_n,
            c_nm1,
            dt=cfg.dt,
            D=cfg.D,
            gamma=cfg.gamma,
            inv_h2=self._local.inv_h2,
            inv_h4=self._local.inv_h4,
        )
        rhs = cons(rhs, sh(lay.block))

        fac = self._local.op_full.fac_x
        facy = self._local.op_full.fac_y

        def solve_x(r):
            return cyclic_penta_solve_factored(fac, r.T, backend="jnp").T

        def solve_y(r):
            return cyclic_penta_solve_factored(facy, r, backend="jnp")

        if ens:
            solve_x = jax.vmap(solve_x)
            solve_y = jax.vmap(solve_y)

        # "transpose between sweeps": reshard so the solve axis is local
        w = solve_x(cons(rhs, sh(lay.xsweep)))
        v = solve_y(cons(w, sh(lay.ysweep)))
        v = cons(v, sh(lay.block))
        c_np1 = 2.0 * c_n - c_nm1 + v
        return cons(c_np1, sh(lay.block)), c_n

    def multi_step(self, c_n, c_nm1, n_steps: int):
        """``n_steps`` fused into one XLA program via scan (the launch unit)."""

        def body(carry, _):
            a, b = carry
            a2, b2 = self.step(a, b)
            return (a2, b2), None

        (c_a, c_b), _ = jax.lax.scan(body, (c_n, c_nm1), None, length=n_steps)
        return c_a, c_b

    def streamed_apply(
        self,
        plan,
        field: jnp.ndarray,
        out_init: jnp.ndarray | None = None,
        *,
        streams: int | None = None,
        max_tile_bytes: int | None = None,
        chunk_rows: int | None = None,
    ) -> jnp.ndarray:
        """Apply a stencil plan to an oversized field through this solver's
        mesh: y-chunks stream sequentially (cuSten's row-chunk streams),
        each chunk's x extent is sharded over ``dd.x_axis`` inside
        ``shard_map`` with ``ppermute`` halo exchange — the §VI.B multi-GPU
        layout fused with the §III streaming machinery."""
        return stream_stencil_apply_dist(
            plan,
            field,
            self.dd,
            out_init,
            streams=streams,
            max_tile_bytes=max_tile_bytes,
            chunk_rows=chunk_rows,
        )

    def field_sharding(self) -> NamedSharding:
        return NamedSharding(self.dd.mesh, self.layouts.block)
