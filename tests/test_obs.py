"""Stage scopes and compile counters (:mod:`repro.obs`).

Scopes are HLO metadata: every instruction the library emits inside a
stage carries ``custen.<stage>`` in its ``op_name``, and its outermost
``custen.`` name is the stage the device trace charges it to.  What has
no library ``op_name`` at all is XLA's own (copies, hoisted constants).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import obs
from repro.core.cahn_hilliard import CahnHilliardADI, CHConfig, ch_evolve

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*\S+\s+([\w\-]+)\(")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STAGE = re.compile(r"(?:^|/)custen\.([\w.]+?)(?=/|$)")
# instructions that move or name data and do no work of their own
_STRUCTURAL = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
               "copy", "copy-start", "copy-done", "while", "conditional", "call"}


def _top_level_ops(hlo_text: str):
    """``(instruction, opcode, op_name or None)`` of every instruction
    the device runs as an op of its own: those outside fused
    computations, without the structural ones."""
    fused = {m.group(1) for line in hlo_text.splitlines() if " fusion(" in line
             for m in _CALLS.finditer(line)}
    out, computation = [], None
    for line in hlo_text.splitlines():
        head = _HEADER.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _INSTR.match(line)
        if m and computation not in fused and m.group(2) not in _STRUCTURAL:
            op = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), op.group(1) if op else None))
    return out


def _stage(op_name):
    """The outermost ``custen.`` stage of an ``op_name``, else None."""
    m = _STAGE.search(op_name or "")
    return m.group(1) if m else None


def test_stage_of_an_op_name_is_its_outermost_custen_scope():
    assert _stage("jit(f)/while/body/closed_call/custen.adi.y/"
                  "jit(_substitute_mid_pallas)/pallas_call") == "adi.y"
    assert _stage("jit(f)/custen.ch.bootstrap/custen.adi.x/add") == "ch.bootstrap"
    assert _stage("jit(f)/while/body/add") is None
    assert _stage(None) is None


def _ch_solver(rhs_mode="fused"):
    return CahnHilliardADI(CHConfig(nx=16, ny=16, dt=1e-4, dtype="float32",
                                    backend="jnp", rhs_mode=rhs_mode))


def _ch_evolve_text(rhs_mode):
    c = jnp.zeros((16, 16), jnp.float32)
    return _ch_solver(rhs_mode).make_evolve(2).lower(c, c).compile().as_text()


def _ch_bootstrap_text():
    c = jnp.zeros((16, 16), jnp.float32)
    return jax.jit(_ch_solver().initial_step).lower(c).compile().as_text()


def _adi3d_text():
    plan = repro.create("hyperdiffusion", (8, 8, 16), mode="adi", alpha=0.1,
                        dtype=jnp.float32, backend="jnp")
    return jax.jit(repro.compute).lower(
        plan, jnp.zeros((8, 8, 16), jnp.float32)).compile().as_text()


def _stencil3d_scan_text():
    plan = repro.create("laplacian", (8, 8, 16), bc="periodic",
                        dtype=jnp.float32, backend="jnp")

    def advance(c):
        step = lambda c, _: (repro.compute(plan, c), None)  # noqa: E731
        return jax.lax.scan(step, c, None, length=3)[0]

    return jax.jit(advance).lower(jnp.zeros((8, 8, 16), jnp.float32)).compile().as_text()


@pytest.mark.parametrize(
    "program, library, stages",
    [
        (lambda: _ch_evolve_text("fused"), "/closed_call/",
         {"adi.x", "adi.y", "ch.update"}),
        (lambda: _ch_evolve_text("stencil"), "/closed_call/",
         {"ch.rhs", "adi.x", "adi.y", "ch.update"}),
        (_ch_bootstrap_text, "jit(initial_step)/", {"ch.bootstrap"}),
        (_adi3d_text, "jit(compute)/", {"adi.x", "adi.y", "adi.z"}),
        (_stencil3d_scan_text, "/closed_call/", {"stencil"}),
    ],
    ids=["ch_evolve_fused", "ch_evolve_stencil", "ch_bootstrap", "adi3d_compute",
         "stencil3d_scan"],
)
def test_every_library_op_has_one_stage_from_the_expected_set(program, library,
                                                              stages):
    ops = _top_level_ops(program())
    ours = [(name, op) for name, _, op in ops if op and library in op]
    assert ours, "the program holds no op of the library"
    unstaged = [(name, op) for name, op in ours if _stage(op) is None]
    assert not unstaged, f"library ops outside any custen stage: {unstaged[:5]}"
    assert {_stage(op) for _, op in ours} == stages
    # what is left is the loop around the steps, or XLA's own (no op_name)
    for name, _, op in ops:
        if op and library not in op:
            assert re.search(r"/while/(body/add|cond/lt)$", op), (name, op)


def test_programs_counts_a_new_program_once():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.arange(5.0)
    before = obs.counters()
    f(x).block_until_ready()
    mid = obs.counters()
    f(x).block_until_ready()
    after = obs.counters()
    assert mid["programs"] == before["programs"] + 1
    assert mid["compile_s"] > before["compile_s"]
    assert after == mid


def test_a_stage_adds_no_program_to_an_eager_call():
    x = jnp.arange(7.0)
    f = jax.jit(lambda x: x - 2.0)
    f(x).block_until_ready()
    before = obs.counters()["programs"]
    with obs.stage("adi.x"):
        f(x).block_until_ready()
    assert obs.counters()["programs"] == before


def test_counters_read_the_tuner_stats_in_place():
    from repro.tune import stats

    c = obs.counters()
    assert c["tune.measure_runs"] == stats.measure_runs
    assert c["tune.dropped"] == len(stats.dropped)
    assert {"programs", "compile_s", "cache_hits", "cache_misses"} <= set(c)


def test_host_spans_reach_a_profile(tmp_path):
    from jax.profiler import ProfileData

    solver = _ch_solver()
    c0 = jnp.asarray(np.random.default_rng(0).uniform(-0.1, 0.1, (16, 16)),
                     jnp.float32)
    with jax.profiler.trace(str(tmp_path)):
        repro.create("laplacian", (16, 16), bc="periodic")
        _, history = ch_evolve(solver, c0, 2, save_every=1,
                               metrics_fn=lambda c: float(jnp.max(c)))
    assert [step for step, _ in history] == [2, 3]
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = [e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name.startswith(obs.PREFIX)]
    assert sorted(set(names)) == ["custen.create", "custen.evolve.bootstrap",
                                  "custen.evolve.chunk", "custen.evolve.metrics"]
    assert names.count("custen.evolve.chunk") == 2
