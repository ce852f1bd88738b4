"""The declarative invariant rule engine over jaxprs, compiled HLO text,
and plans.

cuSten's Create/Compute split means the expensive guarantees — transpose-
free ADI sweeps, fp64-stable hot paths, donated double buffers, feasible
Pallas grids — are *Create-time properties* of a plan.  Each rule here
checks one such property on a concrete artifact and returns structured
:class:`~repro.analysis.findings.Finding` records naming the offending
primitive and its enclosing computation:

====================== ========= ==========================================
rule                   kind      violated when
====================== ========= ==========================================
``no_transpose``       jaxpr     a ``transpose`` primitive appears anywhere
                                 in the traced hot path
``no_dtype_upcast``    jaxpr     ``convert_element_type`` *widens* a
                                 floating/complex array (f32→f64 creep)
``no_host_callback``   jaxpr     a host callback primitive appears
                                 (``pure_callback``, ``io_callback``, ...)
``donation_applied``   hlo       the compiled module declares no
                                 ``input_output_alias`` although donation
                                 was requested
``retrace_budget``     callable  jitted ``compute(plan, x)`` traces more
                                 than ``budget`` times across structurally
                                 identical plan arguments
``pallas_grid_feasible`` plan    the plan's tile/grid cannot cover the
                                 (padded) extents given its halo
``flops_budget``       cost      measured HLO flops exceed the family's
                                 analytic floor × calibrated factor
``bytes_budget``       cost      measured HLO bytes exceed the floor ×
                                 factor (a transpose/copy round-trip)
``peak_memory_budget`` cost      buffer-assignment peak exceeds budget
                                 (a leaked double buffer)
``no_remat``           cost      a ≥2-trip loop body's *per-trip*
                                 traffic exceeds the per-step budget
                                 (rematerialising scan)
====================== ========= ==========================================

``check_jaxpr`` / ``check_hlo`` / ``check_plan`` / ``check_cost`` run
the rules of the matching kind; :func:`repro.analysis.audit.run_audit`
and :func:`repro.analysis.audit.run_cost_audit` drive all of them over
the full operator × plan-family matrix.  The cost rules read the
measured :class:`~repro.analysis.cost.CostVector` and the analytic
:class:`~repro.analysis.cost.Expected` floor from their context.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Callable

import numpy as np

__all__ = [
    "BUDGET_FACTORS",
    "RULES",
    "Rule",
    "all_primitives",
    "check_cost",
    "check_hlo",
    "check_jaxpr",
    "check_plan",
    "iter_eqns",
    "retrace_count",
    "rule",
]

from repro.analysis.cost import input_output_aliases, parse_module
from repro.analysis.findings import ERROR, Finding

# ---------------------------------------------------------------------------
# The jaxpr walker (the single, shared replacement for the `_all_primitives`
# copies that used to live in tests/test_adi_fused.py and tests/test_adi3d.py)
# ---------------------------------------------------------------------------


def iter_eqns(closed_jaxpr):
    """Yield ``(path, eqn)`` for every equation, recursing into sub-jaxprs.

    ``path`` is the tuple of enclosing primitive names (``()`` at top
    level), so a finding can report *where* an offending primitive sits —
    e.g. ``('scan', 'pjit')``.  Sub-jaxprs are found in equation params
    both as ``ClosedJaxpr``-likes (anything with a ``.jaxpr``) and as raw
    jaxprs (anything with ``.eqns`` — e.g. a ``pallas_call`` kernel), so
    the walk is strictly deeper than the historical test walkers."""

    def walk(jaxpr, path):
        for e in jaxpr.eqns:
            yield path, e
            inner_path = path + (str(e.primitive),)
            for v in e.params.values():
                for vv in v if isinstance(v, (list, tuple)) else (v,):
                    inner = getattr(vv, "jaxpr", None)
                    if inner is None and hasattr(vv, "eqns"):
                        inner = vv
                    if inner is not None:
                        yield from walk(inner, inner_path)

    yield from walk(getattr(closed_jaxpr, "jaxpr", closed_jaxpr), ())


def all_primitives(closed_jaxpr) -> set[str]:
    """Every primitive name reachable in ``closed_jaxpr`` (recursive)."""
    return {str(e.primitive) for _, e in iter_eqns(closed_jaxpr)}


def _where(path) -> str:
    return "/".join(path) if path else "<top>"


# ---------------------------------------------------------------------------
# Rule registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Rule:
    """One declarative invariant.

    ``kind`` picks the artifact the rule inspects: ``'jaxpr'`` (a traced
    closed jaxpr), ``'hlo'`` (compiled HLO text), ``'plan'`` (a plan
    object), or ``'callable'`` (a function the check may call).  ``check``
    takes ``(target, context_dict)`` and returns a list of findings."""

    name: str
    kind: str
    doc: str
    check: Callable


RULES: dict[str, Rule] = {}


def rule(name: str, kind: str, doc: str = ""):
    """Register a rule (decorator).  User rules compose with the built-ins:
    anything registered here participates in ``check_*`` and the audit."""

    def deco(fn):
        RULES[name] = Rule(name=name, kind=kind, doc=doc, check=fn)
        return fn

    return deco


def _resolve(names, kind: str) -> list[Rule]:
    if names is None:
        return [r for r in RULES.values() if r.kind == kind]
    out = []
    for n in names:
        try:
            r = RULES[n]
        except KeyError:
            raise ValueError(
                f"unknown rule {n!r}; registered: {sorted(RULES)}"
            ) from None
        if r.kind != kind:
            raise ValueError(
                f"rule {n!r} has kind {r.kind!r}, not {kind!r}"
            )
        out.append(r)
    return out


def check_jaxpr(closed_jaxpr, rules=None, *, context=None) -> list[Finding]:
    """Run jaxpr-kind rules (all of them by default) on a closed jaxpr."""
    ctx = dict(context or {})
    findings = []
    for r in _resolve(rules, "jaxpr"):
        findings.extend(r.check(closed_jaxpr, ctx))
    return findings


def check_hlo(hlo_text: str, rules=None, *, context=None) -> list[Finding]:
    """Run hlo-kind rules on compiled (or lowered) HLO module text."""
    ctx = dict(context or {})
    findings = []
    for r in _resolve(rules, "hlo"):
        findings.extend(r.check(hlo_text, ctx))
    return findings


def check_cost(cost, rules=None, *, context=None) -> list[Finding]:
    """Run cost-kind rules on a measured
    :class:`~repro.analysis.cost.CostVector`.

    ``context`` must carry ``expected`` (the family's analytical
    :class:`~repro.analysis.cost.Expected` floor) and may override the
    per-metric ``factors`` and name the audited ``cell``."""
    ctx = dict(context or {})
    findings = []
    for r in _resolve(rules, "cost"):
        findings.extend(r.check(cost, ctx))
    return findings


def check_plan(plan, shape, rules=None, *, context=None) -> list[Finding]:
    """Run plan-kind rules on a plan object for fields of ``shape``."""
    ctx = dict(context or {})
    ctx.setdefault("shape", tuple(shape))
    findings = []
    for r in _resolve(rules, "plan"):
        findings.extend(r.check(plan, ctx))
    return findings


# ---------------------------------------------------------------------------
# jaxpr rules
# ---------------------------------------------------------------------------


@rule(
    "no_transpose",
    "jaxpr",
    "hot paths must stay transpose-free (the ADI layout contract)",
)
def _no_transpose(closed_jaxpr, ctx) -> list[Finding]:
    out = []
    for path, e in iter_eqns(closed_jaxpr):
        if str(e.primitive) == "transpose":
            perm = e.params.get("permutation")
            out.append(
                Finding(
                    rule="no_transpose",
                    severity=ERROR,
                    message=(
                        f"transpose (permutation={perm}) in a path promised "
                        "transpose-free"
                    ),
                    primitive="transpose",
                    computation=_where(path),
                )
            )
    return out


_FLOATING_KINDS = ("f", "c")  # floating + complex: the numeric hot paths


@rule(
    "no_dtype_upcast",
    "jaxpr",
    "no convert_element_type widening of floating data (fp32->fp64 creep)",
)
def _no_dtype_upcast(closed_jaxpr, ctx) -> list[Finding]:
    out = []
    for path, e in iter_eqns(closed_jaxpr):
        if str(e.primitive) != "convert_element_type":
            continue
        aval = getattr(e.invars[0], "aval", None)
        if aval is None:
            continue
        if getattr(aval, "weak_type", False):
            # weak-typed scalars (python literals) promote for free; only
            # conversions of committed array data count as upcasts
            continue
        old = np.dtype(aval.dtype)
        new = np.dtype(e.params["new_dtype"])
        if (
            old.kind in _FLOATING_KINDS
            and new.kind in _FLOATING_KINDS
            and new.itemsize > old.itemsize
        ):
            out.append(
                Finding(
                    rule="no_dtype_upcast",
                    severity=ERROR,
                    message=(
                        f"convert_element_type widens {old.name} -> "
                        f"{new.name} (shape {tuple(aval.shape)})"
                    ),
                    primitive="convert_element_type",
                    computation=_where(path),
                )
            )
    return out


_HOST_CALLBACK_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "host_callback_call",
        "outside_call",
    }
)


@rule(
    "no_host_callback",
    "jaxpr",
    "no host round-trips inside a compiled hot path",
)
def _no_host_callback(closed_jaxpr, ctx) -> list[Finding]:
    out = []
    for path, e in iter_eqns(closed_jaxpr):
        prim = str(e.primitive)
        if prim in _HOST_CALLBACK_PRIMS:
            out.append(
                Finding(
                    rule="no_host_callback",
                    severity=ERROR,
                    message=f"host callback {prim!r} in a compiled hot path",
                    primitive=prim,
                    computation=_where(path),
                )
            )
    return out


# ---------------------------------------------------------------------------
# HLO rules (parsers from repro.analysis.cost)
# ---------------------------------------------------------------------------


@rule(
    "donation_applied",
    "hlo",
    "requested buffer donation must materialise as input/output aliasing",
)
def _donation_applied(hlo_text, ctx) -> list[Finding]:
    aliases = input_output_aliases(hlo_text)
    need = int(ctx.get("min_aliased", 1))
    if len(aliases) >= need:
        return []
    try:
        comps = parse_module(hlo_text)
        entry = next(iter(comps)) if comps else None
    except Exception:  # noqa: BLE001 — attribution only, never fatal
        entry = None
    return [
        Finding(
            rule="donation_applied",
            severity=ERROR,
            message=(
                f"compiled module declares {len(aliases)} input/output "
                f"alias pair(s), expected >= {need}: donation did not "
                "materialise (double-buffer swap will copy)"
            ),
            primitive="input_output_alias",
            computation=entry,
        )
    ]


# ---------------------------------------------------------------------------
# callable rule: retrace budget
# ---------------------------------------------------------------------------


def retrace_count(fn, argsets) -> int:
    """How many times jax traces ``fn`` across ``argsets`` calls.

    Counts python executions of the wrapped function under one ``jit`` —
    the cache-hit contract of plan pytrees: calls with structurally
    identical plans (same static aux treedef) must reuse one trace."""
    import jax

    count = 0

    def counting(*args):
        nonlocal count
        count += 1
        return fn(*args)

    jitted = jax.jit(counting)
    for args in argsets:
        jax.block_until_ready(jitted(*args))
    return count


@rule(
    "retrace_budget",
    "callable",
    "jitted compute must not retrace across structurally identical plans",
)
def _retrace_budget(fn, ctx) -> list[Finding]:
    argsets = ctx["argsets"]
    budget = int(ctx.get("budget", 1))
    n = retrace_count(fn, argsets)
    if n <= budget:
        return []
    return [
        Finding(
            rule="retrace_budget",
            severity=ERROR,
            message=(
                f"{n} traces across {len(argsets)} calls with structurally "
                f"identical plan arguments (budget {budget}); the plan "
                "pytree's static aux is not retrace-stable"
            ),
            primitive="jit",
            computation="<jit cache>",
        )
    ]


# ---------------------------------------------------------------------------
# plan rule: Pallas grid feasibility
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# cost rules: fail-closed perf budgets over measured CostVectors
# ---------------------------------------------------------------------------

# Budget = analytic floor x factor.  The factors encode how far the
# *measured* program may legitimately sit above the closed-form model
# (XLA materialises intermediates the model doesn't count: the fp64
# audit cells observe bytes ~2-4x the two-field floor, peak memory a few
# live temps above in+out).  They are deliberately generous enough that
# a clean build clears every cell with >=1.5x headroom, while the
# canonical regressions — a reintroduced transpose round-trip, a leaked
# double buffer, a rematerialised scan history — overshoot them.  The
# *tight* net is the committed ANALYSIS_costs.json baseline diff (>10%);
# these absolute budgets are the backstop that works without a baseline.
BUDGET_FACTORS = {
    "flops": 12.0,
    "bytes": 8.0,
    "peak_memory": 6.0,
    "step_bytes": 8.0,
}
_NO_REMAT_MIN_TRIPS = 2  # single-trip "loops" carry no growth signal


def _budget(ctx, metric: str):
    exp = ctx["expected"]
    factors = {**BUDGET_FACTORS, **ctx.get("factors", {})}
    return getattr(exp, metric) * factors[metric], factors[metric]


def _over_budget(ctx, metric: str, measured: float, primitive: str):
    exp = ctx["expected"]
    budget, factor = _budget(ctx, metric)
    if budget <= 0 or measured <= budget:
        return []
    floor = getattr(exp, metric)
    return [
        Finding(
            rule=f"{metric}_budget",
            severity=ERROR,
            message=(
                f"measured {metric} {measured:.4g} exceeds budget "
                f"{budget:.4g} ({factor:g}x the analytic floor "
                f"{floor:.4g}; bloat {measured / floor:.2f}x)"
            ),
            primitive=primitive,
            computation=ctx.get("cell", "<cost>"),
        )
    ]


@rule(
    "flops_budget",
    "cost",
    "measured FLOPs must stay within a factor of the analytic floor",
)
def _flops_budget(cost, ctx) -> list[Finding]:
    return _over_budget(ctx, "flops", cost.flops, "flops")


@rule(
    "bytes_budget",
    "cost",
    "bytes moved must stay within a factor of the ~2-fields-plus-halo floor",
)
def _bytes_budget(cost, ctx) -> list[Finding]:
    return _over_budget(ctx, "bytes", cost.bytes, "bytes_accessed")


@rule(
    "peak_memory_budget",
    "cost",
    "peak live memory must stay within a factor of the live-field floor",
)
def _peak_memory_budget(cost, ctx) -> list[Finding]:
    return _over_budget(ctx, "peak_memory", cost.peak_memory, "buffer_assignment")


@rule(
    "no_remat",
    "cost",
    "while-body traffic must stay trip-count-linear (no rematerialised "
    "history: per-trip bytes bounded by the per-step floor)",
)
def _no_remat(cost, ctx) -> list[Finding]:
    exp = ctx["expected"]
    if exp.step_bytes <= 0:
        return []
    budget, factor = _budget(ctx, "step_bytes")
    out = []
    for lp in cost.loops:
        if lp.trips < _NO_REMAT_MIN_TRIPS or lp.per_trip_bytes <= budget:
            continue
        out.append(
            Finding(
                rule="no_remat",
                severity=ERROR,
                message=(
                    f"while body {lp.body!r} ({lp.trips} trips) moves "
                    f"{lp.per_trip_bytes:.4g} bytes per trip, over the "
                    f"per-step budget {budget:.4g} ({factor:g}x the "
                    f"analytic step floor {exp.step_bytes:.4g}): total "
                    "loop traffic grows super-linearly in the trip count "
                    "(rematerialised history / stacked carry)"
                ),
                primitive="while",
                computation=lp.body,
            )
        )
    return out


@rule(
    "pallas_grid_feasible",
    "plan",
    "tile/grid must divide the (padded) extents given the halo",
)
def _pallas_grid_feasible(plan, ctx) -> list[Finding]:
    shape = tuple(ctx["shape"])
    probe = getattr(plan, "grid_problems", None)
    if probe is None:
        return []
    return [
        Finding(
            rule="pallas_grid_feasible",
            severity=ERROR,
            message=msg,
            primitive="pallas_call",
            computation=type(plan).__name__,
        )
        for msg in probe(shape)
    ]
