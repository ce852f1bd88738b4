"""Device time by library stage on a small recorded trace, the stage
readers ``adi_x_ms``/``adi_y_ms``, the program text without debug info,
and the programs a cell's window compiles at a tiny CPU grid.

The trace is an XSpace written out as text, as in ``test_trace.py``: one
chip runs a fusion, a Pallas kernel and a copy XLA inserted; the host
runs the benchmark's spans and one library span (``custen.``), all in
nanoseconds on one clock.
"""

import pytest
from jax.profiler import ProfileData
from test_trace import _plane

from yardstick import harness, stages
from yardstick import trace as tr

# window [100, 1100) ns.  Device: fusion.1 [100, 250), k.3 [300, 700),
# copy.2 [950, 1050): busy 150 + 400 + 100 = 650 ns.  Gaps: [250, 300)
# inside the library span custen.evolve.chunk [200, 320), itself inside
# bench.dispatch [150, 400); [700, 950) and [1050, 1100) in bench.block.
TRACE = "\n".join([
    _plane(1, "/device:TPU:0", "XLA Ops",
           [(1, 100, 150), (2, 300, 400), (3, 950, 100)],
           ["%fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(%k.3, %p), kind=kLoop",
            "%k.3 = f32[8,8]{1,0:T(8,128)} custom-call(%p)",
            "%copy.2 = f32[8,8]{1,0:T(8,128)} copy(%fusion.1)"]),
    _plane(2, "/host:CPU", "python",
           [(1, 100, 1000), (2, 150, 250), (3, 200, 120), (4, 600, 500)],
           ["bench.window", "bench.dispatch", "custen.evolve.chunk", "bench.block"]),
])

BODY = "jit(evolve)/while/body/closed_call"
HLO = f"""HloModule jit_evolve, is_scheduled=true

FileNames
1 "/src/repro/core/cahn_hilliard.py"

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}

%fc (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {{
  %a = f32[8,8]{{1,0}} parameter(0)
  %b = f32[8,8]{{1,0}} parameter(1)
  %sub.1 = f32[8,8]{{1,0}} subtract(%a, %b), metadata={{op_name="{BODY}/custen.adi.y/sub"}}
  ROOT %add.1 = f32[8,8]{{1,0}} add(%sub.1, %b), metadata={{op_name="{BODY}/custen.ch.update/add"}}
}}

ENTRY %main (p: f32[8,8]) -> f32[8,8] {{
  %p = f32[8,8]{{1,0}} parameter(0)
  %k.3 = f32[8,8]{{1,0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/custen.adi.x/jit(ch_rhs_xsweep_pallas)/pallas_call" stack_frame_id=1}}
  %fusion.1 = f32[8,8]{{1,0}} fusion(%k.3, %p), kind=kLoop, calls=%fc, metadata={{op_name="{BODY}/custen.ch.update/add"}}
  ROOT %copy.2 = f32[8,8]{{1,0}} copy(%fusion.1)
}}
"""


def _profile():
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(TRACE))


@pytest.fixture()
def red():
    return tr.reduce(tr.from_profile(_profile()))


def _ctx(red, hlo=HLO, steps=2):
    return harness.Context(red=red, kernels=tr.custom_calls(hlo), steps=steps,
                           work={"step": (1e-4, 3e-4)},
                           peak={"hbm_bytes_per_s": 1000.0})


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read


def test_op_names_reads_every_instruction_with_metadata():
    names = stages.op_names(HLO)
    assert names["k.3"] == f"{BODY}/custen.adi.x/jit(ch_rhs_xsweep_pallas)/pallas_call"
    assert names["add.1"] == f"{BODY}/custen.ch.update/add"
    assert "copy.2" not in names and "p" not in names


def test_a_fusion_takes_the_stage_of_its_root():
    # the fusion computes adi.y's subtraction too, but carries its root's name
    names = stages.op_names(HLO)
    assert stages.stage_of(names["fusion.1"]) == "ch.update"
    assert stages.stage_of(names["sub.1"]) == "adi.y"


def test_the_outermost_stage_wins():
    assert stages.stage_of("jit(f)/custen.ch.bootstrap/custen.adi.x/add") == "ch.bootstrap"
    assert stages.stage_of("jit(f)/while/body/add") is None
    assert stages.stage_of(None) is None


def test_stages_and_unscoped_time_add_up_to_busy_time(red):
    by_stage = stages.stage_seconds(red, stages.op_names(HLO))
    assert by_stage == {"adi.x": pytest.approx(400e-9),
                        "ch.update": pytest.approx(150e-9),
                        stages.UNSCOPED: pytest.approx(100e-9)}
    assert sum(by_stage.values()) == pytest.approx(red.busy_s)


def test_a_program_without_scopes_is_all_unscoped(red):
    bare = {i: op.replace("custen.", "") for i, op in stages.op_names(HLO).items()}
    assert stages.stage_seconds(red, bare) == {stages.UNSCOPED: pytest.approx(650e-9)}


def test_a_library_span_leaves_the_benchmark_gaps_as_they_were(red):
    # the benchmark labels gaps by its own spans; custen.evolve.chunk is
    # not one of them, so the gap inside it keeps bench.dispatch's name
    assert red.gaps == [("bench.block", pytest.approx(250e-9)),
                        ("bench.dispatch", pytest.approx(50e-9)),
                        ("bench.block", pytest.approx(50e-9))]


def test_adi_sweep_readers_read_the_kernels_of_their_stage(red):
    # k.3, 400 ns under custen.adi.x, over 2 steps; no kernel under adi.y
    assert _reader("adi_x_ms")(_ctx(red)) == pytest.approx(400e-9 * 1e3 / 2)
    assert _reader("adi_y_ms")(_ctx(red)) is None
    as_y = HLO.replace("custen.adi.x/", "custen.adi.y/")
    assert _reader("adi_y_ms")(_ctx(red, as_y)) == pytest.approx(400e-9 * 1e3 / 2)
    assert _reader("adi_x_ms")(_ctx(red, as_y)) is None


@pytest.mark.parametrize("name", ["adi_x_ms", "adi_y_ms"])
def test_adi_sweep_readers_are_silent_without_stages(red, name):
    assert _reader(name)(_ctx(red, HLO.replace("custen.", ""))) is None


def test_an_inner_stage_is_charged_to_the_outer_one(red):
    nested = HLO.replace("custen.adi.x/", "custen.ch.bootstrap/custen.adi.x/")
    assert _reader("adi_x_ms")(_ctx(red, nested)) is None


@pytest.mark.parametrize("name", ["idle_share", "xla_glue_share", "step_roofline",
                                  "ch_xsweep_roofline"])
def test_the_benchmark_readers_read_the_same_with_and_without_stages(red, name):
    bare = HLO.replace("custen.adi.x/", "")
    read = _reader(name)
    ctx = _ctx(red)
    ctx.work = {"step": (1e-4, 3e-4), "grid": (8, 8), "itemsize": 4}
    bare_ctx = _ctx(red, bare)
    bare_ctx.work = ctx.work
    assert read(ctx) is not None
    assert read(ctx) == pytest.approx(read(bare_ctx))


def test_without_debug_info_ignores_names_paths_and_lines():
    moved = (HLO.replace("custen.adi.x/", "")
             .replace("/src/repro", "/elsewhere/repro")
             .replace("stack_frame_id=1", "stack_frame_id=7"))
    assert stages.without_debug_info(moved) == stages.without_debug_info(HLO)
    assert "metadata" not in stages.without_debug_info(HLO)
    assert "FileNames" not in stages.without_debug_info(HLO)
    changed = HLO.replace("add(%sub.1, %b)", "multiply(%sub.1, %b)")
    assert stages.without_debug_info(changed) != stages.without_debug_info(HLO)


@pytest.fixture()
def tiny_tpu_dispatch(monkeypatch):
    """The library's dispatch told it is on a TPU, kernels interpreted."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_should_interpret", lambda interpret: True)


@pytest.mark.parametrize("workload, grid",
                         [("ch2d-4096.evolve", 64), ("diffusion3d-256.explicit", 16)])
def test_the_window_compiles_nothing(workload, grid, tiny_tpu_dispatch):
    from repro import obs

    cell = harness.resolve(harness.HERE.parents[1], workload)
    cell.config = dict(cell.config, grid=[grid] * len(cell.config["grid"]))
    start = obs.counters()["programs"]
    state = cell.stepper.build(cell.config, cell.traffic, 2**31 + 5)
    calls = harness.pace(state, 0.05)
    setup = obs.counters()["programs"]
    harness.window(state, calls)
    assert obs.counters()["programs"] == setup > start
    assert harness.verify(cell, state, 2**31 + 5)[1]
