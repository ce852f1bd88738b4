"""Loop-aware HLO cost extraction + the quantitative cost model.

This module is the static *cost* side of the analysis subsystem (the
invariant rules in :mod:`repro.analysis.rules` are the qualitative side).
It answers, per compiled hot path, the three roofline questions —

- how many FLOPs does one Compute execute,
- how many bytes does it move through memory,
- how much memory does it hold live at peak,

and compares each against a closed-form analytical expectation for the
plan family (a stencil apply should touch ~2 fields + halo and spend
``2*taps`` flops/point; an fft apply ``~5 n log2 n`` flops; a factored
penta solve O(1) flops/point).  The derived ratios — arithmetic
intensity and bytes/flops *bloat* over the analytic floor — are what the
budget rules (``bytes_budget``, ``flops_budget``, ``peak_memory_budget``,
``no_remat``) gate on, and what ``ANALYSIS_costs.json`` baselines.

**Why a hand parser instead of ``compiled.cost_analysis()``:** XLA's own
analysis counts each ``while`` body **once**, so a scanned multi-step
driver (``ch_evolve``), a streamed chunk pipeline, or the penta
``fori_loop`` recurrence under-reports FLOPs/bytes by the trip count.
The parser here re-derives the costs from the HLO text itself with
execution-count weighting:

1. parse the module into computations and ops;
2. build the call graph (``while`` body/condition with trip count parsed
   from the condition's comparison constant; ``fusion``/``call`` with
   multiplier 1 per invocation);
3. weight per-op costs by the computation's execution count:
   - FLOPs: ``dot`` = 2 * |out| * contracted extent (batch dims fall out
     of |out|); elementwise = |out| (transcendentals weighted like XLA,
     = 1); ``reduce``-likes = |in|;
   - bytes: per *top-level* op — operands + outputs at fusion boundaries
     (mirrors XLA's convention; fusion-internal computations are
     skipped);
   - collectives: output bytes per op, bucketed by kind.

The parser is validated against ``cost_analysis`` on loop-free programs
and against hand-counted FLOPs on scanned programs (tests/test_cost.py,
tests/test_hlo_costs.py).

Doctest — the parser on a really-compiled program:

>>> import jax, jax.numpy as jnp
>>> co = jax.jit(lambda a, b: a @ b).lower(
...     jax.ShapeDtypeStruct((8, 16), jnp.float32),
...     jax.ShapeDtypeStruct((16, 4), jnp.float32),
... ).compile()
>>> int(analyze_hlo(co.as_text()).flops) == 2 * 8 * 16 * 4
True
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict

SCHEMA_VERSION = 2  # the analysis/cost report schema

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
    "f8e4m3b11fnuz": 1, "f8e8m0fnu": 1, "f4e2m1fn": 1,
}

COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "negate", "abs", "exponential",
    "exponential-minus-one", "log", "log-plus-one", "tanh", "sqrt", "rsqrt",
    "cbrt", "power", "maximum", "minimum", "compare", "select", "and", "or",
    "xor", "not", "sign", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "cosine", "sine", "atan2", "erf", "logistic",
    "remainder", "clamp", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "is-finite", "convert",
}

_ZERO_BYTE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "add-dependency", "while", "conditional", "iota",
}


@dataclasses.dataclass
class Shape:
    dtype: str
    dims: tuple[int, ...]

    @property
    def size(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def bytes(self) -> int:
        return self.size * _DTYPE_BYTES.get(self.dtype, 4)


_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\](?:\{[^}]*\})?")


def parse_shapes(type_str: str) -> list[Shape]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        out.append(
            Shape(dt, tuple(int(d) for d in dims.split(",") if d))
        )
    return out


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    out_shapes: list[Shape]
    operands: list[str]
    attrs: str
    inner: str = ""  # raw text inside the op's parens (constants live here)


@dataclasses.dataclass
class Computation:
    name: str
    params: dict[str, list[Shape]]
    ops: dict[str, Op]
    order: list[str]
    is_entry: bool = False


def _split_header(line: str):
    """Parse a computation header line (balanced-paren aware).

    Returns (is_entry, name, params_str) or None."""
    s = line.strip()
    if not s.endswith("{") or "->" not in s or "=" in s.split("(")[0]:
        return None
    is_entry = s.startswith("ENTRY")
    if is_entry:
        s = s[len("ENTRY"):].strip()
    m = re.match(r"%?([\w.\-]+)\s*\(", s)
    if not m:
        return None
    name = m.group(1)
    i = s.index("(")
    depth = 0
    j = i
    for j in range(i, len(s)):
        if s[j] == "(":
            depth += 1
        elif s[j] == ")":
            depth -= 1
            if depth == 0:
                break
    params_str = s[i + 1 : j]
    rest = s[j + 1 :].strip()
    if not rest.startswith("->"):
        return None
    return is_entry, name, params_str


def _split_top_level(s: str):
    """Split on commas at paren/brace depth 0."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(s):
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(s[start:i])
            start = i + 1
    parts.append(s[start:])
    return [p.strip() for p in parts if p.strip()]


_SCALAR_TYPE = re.compile(r"[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?")
_OPCODE_RE = re.compile(r"([a-z][a-z0-9\-]*)\(")


def _parse_op_line(line: str):
    """Hand parser for '%name = TYPE opcode(...)...' — tuple types may
    contain '/*index=N*/' comments, so regexes over '[^=]' break."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%"):
        return None
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[:eq].lstrip("%")
    rest = s[eq + 3 :]
    if rest.startswith("("):
        depth = 0
        j = 0
        for j, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        type_str = rest[: j + 1]
        rest2 = rest[j + 1 :].strip()
    else:
        m = _SCALAR_TYPE.match(rest)
        if not m:
            return None
        type_str = m.group(0)
        rest2 = rest[m.end() :].strip()
    m = _OPCODE_RE.match(rest2)
    if not m:
        return None
    opcode = m.group(1)
    return name, type_str, opcode, rest2[m.end() :]


def _parse_operands(rest: str) -> tuple[list[str], str, str]:
    """Split the operand list (up to the matching close paren) from attrs."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                inner, attrs = rest[:i], rest[i + 1 :]
                break
    else:
        inner, attrs = rest, ""
    names = re.findall(r"%([\w.\-]+)", inner)
    return names, attrs, inner


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        if cur is None:
            h = _split_header(line)
            if h:
                is_entry, name, params_str = h
                params: dict[str, list[Shape]] = {}
                for part in _split_top_level(params_str):
                    if ":" in part:
                        pname, ptype = part.split(":", 1)
                        params[pname.strip()] = parse_shapes(ptype)
                cur = Computation(name, params, {}, [], is_entry)
            continue
        if line.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _parse_op_line(line)
        if not m:
            continue
        name, type_str, opcode, rest = m
        operands, attrs, inner = _parse_operands(rest)
        cur.ops[name] = Op(
            name, opcode, parse_shapes(type_str), operands, attrs, inner
        )
        cur.order.append(name)
    return comps


def _shape_of(comp: Computation, name: str) -> list[Shape]:
    if name in comp.ops:
        return comp.ops[name].out_shapes
    if name in comp.params:
        return comp.params[name]
    return []


def _trip_count(comps, cond_name: str) -> int:
    """Trip count from the loop condition: the constant side of the compare.

    jax scans lower to iv=0; while(iv < N): iv+=1 — N is the trip count."""
    comp = comps.get(cond_name)
    if comp is None:
        return 1
    consts = []
    for op in comp.ops.values():
        if op.opcode == "constant":
            m = re.fullmatch(r"-?\d+", op.inner.strip())
            if m:
                consts.append(int(m.group(0)))
        # descend into wrapped compare fusions
        if op.opcode == "fusion":
            cm = re.search(r"calls=%?([\w.\-]+)", op.attrs)
            if cm and cm.group(1) in comps:
                for o2 in comps[cm.group(1)].ops.values():
                    if o2.opcode == "constant":
                        m = re.fullmatch(r"-?\d+", o2.inner.strip())
                        if m:
                            consts.append(int(m.group(0)))
    pos = [c for c in consts if c > 0]
    return max(pos) if pos else 1


def execution_counts(comps: dict[str, Computation]) -> dict[str, float]:
    """How many times each computation executes per program run."""
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:  # fall back: the largest computation
        entry = max(comps.values(), key=lambda c: len(c.ops))
    counts: dict[str, float] = defaultdict(float)
    fusion_internal: set = set()

    def visit(comp: Computation, mult: float):
        counts[comp.name] += mult
        for op in comp.ops.values():
            if op.opcode == "while":
                bm = re.search(r"body=%?([\w.\-]+)", op.attrs)
                cm = re.search(r"condition=%?([\w.\-]+)", op.attrs)
                trip = _trip_count(comps, cm.group(1)) if cm else 1
                if bm and bm.group(1) in comps:
                    visit(comps[bm.group(1)], mult * trip)
                if cm and cm.group(1) in comps:
                    visit(comps[cm.group(1)], mult * (trip + 1))
            elif op.opcode in ("fusion", "call", "async-start"):
                m = re.search(r"calls=%?([\w.\-]+)", op.attrs)
                if m and m.group(1) in comps:
                    fusion_internal.add(m.group(1))
                    visit(comps[m.group(1)], mult)
            elif op.opcode == "conditional":
                for m in re.finditer(
                    r"(?:true_computation|false_computation|branch_computations=\{)([^}]*)",
                    op.attrs,
                ):
                    for name in re.findall(r"%?([\w.\-]+)", m.group(1)):
                        if name in comps:
                            visit(comps[name], mult)

    visit(entry, 1.0)
    counts["__fusion_internal__"] = 0.0
    for name in fusion_internal:
        counts.setdefault(name, 0.0)
    execution_counts.fusion_internal = fusion_internal  # type: ignore
    return counts


def _dot_flops(comp: Computation, op: Op) -> float:
    out = op.out_shapes[0] if op.out_shapes else Shape("f32", ())
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs)
    lhs_shapes = _shape_of(comp, op.operands[0]) if op.operands else []
    contracted = 1
    if m and lhs_shapes:
        for d in m.group(1).split(","):
            if d:
                contracted *= lhs_shapes[0].dims[int(d)]
    return 2.0 * out.size * contracted


def op_flops(comp: Computation, op: Op) -> float:
    oc = op.opcode
    if oc == "dot":
        return _dot_flops(comp, op)
    if oc in _ELEMENTWISE:
        return float(sum(s.size for s in op.out_shapes))
    if oc in ("reduce", "reduce-window"):
        ins = 0
        for o in op.operands[: max(1, len(op.operands) // 2)]:
            ins += sum(s.size for s in _shape_of(comp, o))
        return float(ins)
    if oc.startswith("all-reduce") or oc.startswith("reduce-scatter"):
        return float(sum(s.size for s in op.out_shapes))
    if oc == "fft":
        # XLA models an N-point transform at 5 N log2 N real flops —
        # the textbook split-radix constant the analytic model also uses
        n = sum(s.size for s in op.out_shapes)
        return 5.0 * n * max(math.log2(n), 1.0) if n else 0.0
    return 0.0


def _sliced_operand_bytes(comps, op: Op, operand_bytes):
    """For fusion ops: operands that are only *dynamic-sliced* inside the
    fused computation contribute slice-sized reads, not whole-array reads
    (the lax.scan xs pattern: param -> dynamic-slice -> bitcast).  Returns
    adjusted per-operand byte counts."""
    m = re.search(r"calls=%?([\w.\-]+)", op.attrs)
    callee = comps.get(m.group(1)) if m else None
    if callee is None:
        return operand_bytes
    # map parameter index -> param name
    param_names = {}
    for o in callee.ops.values():
        if o.opcode == "parameter":
            idx = o.inner.strip()
            if idx.isdigit():
                param_names[int(idx)] = o.name
    adjusted = list(operand_bytes)
    for i, name in param_names.items():
        if i >= len(adjusted):
            continue
        uses = [
            o for o in callee.ops.values() if name in o.operands
        ]
        if uses and all(
            u.opcode in ("dynamic-slice", "gather") for u in uses
        ):
            adjusted[i] = float(
                sum(sum(s.bytes for s in u.out_shapes) for u in uses)
            )
    return adjusted


def op_bytes(comp: Computation, op: Op, comps=None) -> float:
    if op.opcode in _ZERO_BYTE_OPS:
        return 0.0
    out_bytes = float(sum(s.bytes for s in op.out_shapes))
    operand_bytes = [
        float(sum(s.bytes for s in _shape_of(comp, o))) for o in op.operands
    ]
    if comps is not None and op.opcode == "fusion":
        operand_bytes = _sliced_operand_bytes(comps, op, operand_bytes)
    total = out_bytes + sum(operand_bytes)
    # In-place update pattern (dynamic-update-slice, scatter, and fusions
    # rooted at them): XLA updates the buffer in place — actual traffic is
    # the *slice*, not the whole operand + whole output.  Detect via an
    # operand that exactly matches the output, and count the rest only.
    blob = op.opcode + " " + op.name + " " + op.attrs
    if "dynamic-update-slice" in blob or "dynamic_update_slice" in blob or (
        op.opcode == "scatter"
    ):
        if out_bytes in operand_bytes:
            # in-place update: traffic = small operands read + region written
            small = sum(b for b in operand_bytes if b != out_bytes)
            total = 2.0 * small
    elif "dynamic-slice" in blob or "dynamic_slice" in blob:
        # dynamic-slice reads only the slice, not the whole operand —
        # without this, scan xs-slicing is charged the full stacked array
        # per iteration (quadratic inflation of the SSM cells' memory term)
        total = 2.0 * out_bytes
    elif op.opcode == "gather":
        total = 2.0 * out_bytes + 0.0
    return total


@dataclasses.dataclass
class LoopCost:
    """One ``while`` loop of a compiled module: the per-trip execution
    cost of its body (everything reachable from the body, nested loops
    already trip-weighted) and the parsed trip count.

    ``per_trip_bytes`` is the quantity the ``no_remat`` rule budgets: on
    a healthy scanned pipeline it is independent of the trip count; a
    rematerialised history (the body re-reading an O(trips) buffer each
    iteration) makes it grow with trips — quadratic total traffic."""

    body: str
    trips: int
    per_trip_flops: float
    per_trip_bytes: float


@dataclasses.dataclass
class HloCosts:
    flops: float
    bytes: float
    collectives: dict[str, dict[str, float]]
    loops: list[LoopCost] = dataclasses.field(default_factory=list)

    @property
    def collective_bytes(self) -> float:
        return sum(v["bytes"] for v in self.collectives.values())


def top_contributors(text: str, k: int = 20, *, by: str = "bytes"):
    """Top-k op contributors to bytes/flops/collectives, execution-weighted.

    Returns [(weighted_cost, opcode, op_name_metadata, shape_str, mult)] —
    the profiling view the §Perf hillclimbs read instead of guessing."""
    comps = parse_module(text)
    counts = execution_counts(comps)
    fusion_internal = getattr(execution_counts, "fusion_internal", set())
    rows = []
    for name, comp in comps.items():
        mult = counts.get(name, 0.0)
        if mult == 0.0:
            continue
        top_level = name not in fusion_internal
        for op in comp.ops.values():
            if by == "bytes":
                if not top_level:
                    continue
                cost = op_bytes(comp, op, comps)
            elif by == "flops":
                cost = op_flops(comp, op)
            else:  # collectives
                cost = (
                    float(sum(s.bytes for s in op.out_shapes))
                    if op.opcode.replace("-start", "") in COLLECTIVE_KINDS
                    else 0.0
                )
            if cost <= 0:
                continue
            meta = re.search(r'op_name="([^"]*)"', op.attrs)
            shape = ",".join(
                f"{s.dtype}[{'x'.join(map(str, s.dims))}]"
                for s in op.out_shapes[:2]
            )
            rows.append(
                (cost * mult, op.opcode, meta.group(1) if meta else op.name,
                 shape, mult)
            )
    rows.sort(reverse=True)
    return rows[:k]


def _loop_costs(comps, counts, fusion_internal) -> list[LoopCost]:
    """Per-while per-trip cost: everything reachable from the loop body,
    with *nested* loops trip-weighted but the outer trip factored out."""
    loops = []
    for comp in comps.values():
        mult = counts.get(comp.name, 0.0)
        if mult == 0.0:
            continue
        for op in comp.ops.values():
            if op.opcode != "while":
                continue
            bm = re.search(r"body=%?([\w.\-]+)", op.attrs)
            cm = re.search(r"condition=%?([\w.\-]+)", op.attrs)
            if not bm or bm.group(1) not in comps:
                continue
            trips = _trip_count(comps, cm.group(1)) if cm else 1
            # reachable-from-body sub-callgraph, one body execution
            sub_counts: dict[str, float] = defaultdict(float)
            sub_internal: set = set()

            def visit(c, m):
                sub_counts[c.name] += m
                for o in c.ops.values():
                    if o.opcode == "while":
                        b2 = re.search(r"body=%?([\w.\-]+)", o.attrs)
                        c2 = re.search(r"condition=%?([\w.\-]+)", o.attrs)
                        t2 = _trip_count(comps, c2.group(1)) if c2 else 1
                        if b2 and b2.group(1) in comps:
                            visit(comps[b2.group(1)], m * t2)
                    elif o.opcode in ("fusion", "call", "async-start"):
                        m2 = re.search(r"calls=%?([\w.\-]+)", o.attrs)
                        if m2 and m2.group(1) in comps:
                            sub_internal.add(m2.group(1))
                            visit(comps[m2.group(1)], m)

            visit(comps[bm.group(1)], 1.0)
            fl = by = 0.0
            for name2, m2 in sub_counts.items():
                c2 = comps[name2]
                internal = name2 in sub_internal or name2 in fusion_internal
                for o2 in c2.ops.values():
                    fl += m2 * op_flops(c2, o2)
                    if not internal:
                        by += m2 * op_bytes(c2, o2, comps)
            loops.append(
                LoopCost(
                    body=bm.group(1), trips=trips,
                    per_trip_flops=fl, per_trip_bytes=by,
                )
            )
    return loops


def analyze_hlo(text: str) -> HloCosts:
    comps = parse_module(text)
    counts = execution_counts(comps)
    fusion_internal = getattr(execution_counts, "fusion_internal", set())

    flops = 0.0
    bytes_ = 0.0
    colls = {k: {"count": 0.0, "bytes": 0.0} for k in COLLECTIVE_KINDS}
    for name, comp in comps.items():
        mult = counts.get(name, 0.0)
        if mult == 0.0:
            continue
        top_level = name not in fusion_internal
        for op in comp.ops.values():
            flops += mult * op_flops(comp, op)
            if top_level:
                bytes_ += mult * op_bytes(comp, op, comps)
            base = op.opcode.replace("-start", "")
            if base in colls:
                b = float(sum(s.bytes for s in op.out_shapes))
                colls[base]["count"] += mult
                colls[base]["bytes"] += mult * b
    return HloCosts(
        flops=flops, bytes=bytes_, collectives=colls,
        loops=_loop_costs(comps, counts, fusion_internal),
    )


# ---------------------------------------------------------------------------
# Measured cost vectors from compiled executables
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CostVector:
    """The measured cost of one compiled Compute: the three roofline
    inputs plus the per-loop breakdown the ``no_remat`` rule reads."""

    flops: float
    bytes: float
    peak_memory: float
    loops: list[LoopCost] = dataclasses.field(default_factory=list)

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (flops per byte moved)."""
        return self.flops / self.bytes if self.bytes else 0.0

    def to_dict(self) -> dict:
        return {
            "flops": self.flops,
            "bytes": self.bytes,
            "peak_memory": self.peak_memory,
            "intensity": self.intensity,
            "loops": [dataclasses.asdict(lp) for lp in self.loops],
        }


def memory_stats(compiled) -> dict:
    """Peak live memory of a compiled executable, from XLA's own buffer
    assignment (``memory_analysis``): arguments + outputs + temporaries,
    minus donation-aliased bytes (an aliased output reuses its argument's
    buffer, so it must not be double-counted)."""
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
    }
    mem["peak_bytes"] = (
        mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
        - mem["alias_bytes"]
    )
    return mem


# e.g.  {0}: (0, {}, may-alias)  inside the module's input_output_alias={...}
_ALIAS_PAIR_RE = re.compile(
    r"\{([0-9,\s]*)\}:\s*\((\d+),\s*\{[0-9,\s]*\},\s*(may-alias|must-alias)\)"
)


def input_output_aliases(hlo_text: str):
    """Parse the module header's ``input_output_alias={...}`` table.

    Returns a list of ``(output_index, parameter_number, kind)`` tuples —
    ``output_index`` is the (possibly empty) tuple index of the aliased
    output, ``kind`` is ``'may-alias'`` or ``'must-alias'``.  An empty list
    means the compiled module carries no donation-induced aliasing (the
    ``donation_applied`` audit rule's failure condition)."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    i = hlo_text.index("{", start)
    depth, j = 0, i
    for j in range(i, len(hlo_text)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    body = hlo_text[i : j + 1]
    return [
        (
            tuple(int(p) for p in out_idx.replace(",", " ").split()),
            int(param),
            kind,
        )
        for out_idx, param, kind in _ALIAS_PAIR_RE.findall(body)
    ]


def measure_compiled(compiled) -> CostVector:
    """The execution-count-weighted cost vector of a compiled executable."""
    h = analyze_hlo(compiled.as_text())
    mem = memory_stats(compiled)
    return CostVector(
        flops=h.flops,
        bytes=h.bytes,
        peak_memory=float(mem["peak_bytes"]),
        loops=h.loops,
    )


# ---------------------------------------------------------------------------
# Closed-form analytical expectations per plan family
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Expected:
    """The analytic floor for one Compute: what the paper's roofline
    argument says the kernel *should* cost.  Budgets are multiples of
    these (the rules' context), so a hot path that silently doubles its
    traffic trips the gate even while every qualitative rule stays green.
    """

    flops: float
    bytes: float
    peak_memory: float
    # the analytic per-step traffic of one trip of the outermost loop
    # (the no_remat budget unit); 0 when the program has no loop floor
    step_bytes: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def expected_stencil(shape, taps: int, itemsize: int, *, halo: int = 0) -> Expected:
    """A direct stencil apply: read one field + halo, write one field;
    ``2*taps`` flops per point (multiply + accumulate per tap)."""
    n = 1
    for d in shape:
        n *= int(d)
    halo_pts = halo * (n // max(int(shape[-1]), 1)) * 2 if halo else 0
    bytes_ = (2 * n + halo_pts) * itemsize
    return Expected(
        flops=2.0 * taps * n,
        bytes=float(bytes_),
        peak_memory=float(3 * n * itemsize),  # in + out + one live temp
        step_bytes=float(bytes_),
    )


def expected_fft(shape, itemsize: int, *, transforms: int = 1) -> Expected:
    """A spectral apply: forward + inverse transform plus the symbol
    multiply — ``~2 * 5 n log2 n`` flops and a handful of field-sized
    passes (real field in/out, complex spectrum in/out, symbol read)."""
    n = 1
    for d in shape:
        n *= int(d)
    logn = max(math.log2(n), 1.0)
    flops = transforms * (2 * 5.0 * n * logn + 6.0 * n)
    # real in/out + complex intermediate (2x itemsize) passes + symbol
    bytes_ = transforms * (2 * n + 3 * 2 * n + 2 * n) * itemsize
    return Expected(
        flops=flops,
        bytes=float(bytes_),
        peak_memory=float(6 * n * itemsize),
        step_bytes=float(bytes_),
    )


def expected_penta(shape, itemsize: int, *, sweeps: int = 1) -> Expected:
    """A factored (cyclic) penta solve: forward + backward substitution
    (~2 FMAs each per unknown) plus the Woodbury closure (4 broadcast
    FMAs) — O(1) flops/point, ~constant field passes per sweep."""
    n = 1
    for d in shape:
        n *= int(d)
    per_pt_flops = 2 * (2 + 2) + 2 * 4  # substitutions + Woodbury FMAs
    # rhs read + solution write + factor rows + correction passes
    bytes_ = sweeps * 6 * n * itemsize
    return Expected(
        flops=float(sweeps * per_pt_flops * n),
        bytes=float(bytes_),
        peak_memory=float(4 * n * itemsize),
        step_bytes=float(bytes_ / max(sweeps, 1)),
    )


def expected_ch_step(shape, itemsize: int) -> Expected:
    """One fused Cahn–Hilliard ADI step: the explicit RHS (a ~25-tap
    biharmonic + 9-tap nonlinear Laplacian + axpys) and two implicit
    penta sweeps."""
    rhs = expected_stencil(shape, taps=34, itemsize=itemsize)
    solve = expected_penta(shape, itemsize, sweeps=2)
    n = 1
    for d in shape:
        n *= int(d)
    step_bytes = rhs.bytes + solve.bytes
    return Expected(
        flops=rhs.flops + solve.flops + 6.0 * n,
        bytes=step_bytes,
        peak_memory=float(6 * n * itemsize),
        step_bytes=float(step_bytes),
    )


__all__ = [
    "COLLECTIVE_KINDS",
    "SCHEMA_VERSION",
    "Computation",
    "CostVector",
    "Expected",
    "HloCosts",
    "LoopCost",
    "Op",
    "Shape",
    "analyze_hlo",
    "execution_counts",
    "expected_ch_step",
    "expected_fft",
    "expected_penta",
    "expected_stencil",
    "input_output_aliases",
    "measure_compiled",
    "memory_stats",
    "op_bytes",
    "op_flops",
    "parse_module",
    "parse_shapes",
    "top_contributors",
]
