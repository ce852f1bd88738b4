"""The trace reduction and the per-layer readers on a small recorded trace.

The trace is an XSpace written out as text: one chip whose ``XLA Ops``
line runs a Pallas kernel (``k.3``) and an XLA fusion, and the host's
benchmark spans around them, all in nanoseconds on one clock.
"""

import pytest
from jax.profiler import ProfileData

from yardstick import harness
from yardstick import trace as tr


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _plane(pid, name, line, events, names):
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for i, n in enumerate(names, 1))
    body = "\n".join(_event(*e) for e in events)
    return (f'planes {{ id: {pid} name: "{name}" '
            f'lines {{ id: 1 name: "{line}" timestamp_ns: 0 {body} }} {meta} }}')


# window [100, 1100) ns.  Device: fusion.1 [50, 250) (half before the
# window), k.3 [300, 700), k.3 [650, 900) overlapping it, fusion.1
# [1000, 1200) (half after).  Busy union inside: [100,250) + [300,900) +
# [1000,1100) = 150 + 600 + 100 = 850 ns.  Gaps: [250,300) in dispatch,
# [900,1000) in block.
# A while loop [90, 1050) encloses the k.3 events: it is no op of its own.
# Event names are the instructions' HLO text, as on the chip.
TRACE = "\n".join([
    _plane(1, "/device:TPU:0", "XLA Ops",
           [(1, 50, 200), (2, 300, 400), (2, 650, 250), (1, 1000, 200),
            (3, 90, 960)],
           ["%fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(%k.3), kind=kLoop",
            "%k.3 = f32[8,8]{1,0:T(8,128)} custom-call(%p)",
            "%while = (s32[]{:T(128)}, f32[8,8]) while(%t), body=%b"]),
    _plane(2, "/host:CPU", "python",
           [(1, 100, 1000), (2, 200, 150), (3, 350, 650), (4, 0, 50)],
           ["bench.window", "bench.dispatch", "bench.block", "other"]),
    _plane(3, "/device:TPU:0 SparseCore", "XLA Ops", [(1, 100, 1000)], ["sc.1"]),
])

HLO = """
  %k.3 = f32[8,8]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jit(my_kernel)/pallas_call"}
  %fusion.1 = f32[8,8]{1,0} fusion(%k.3), kind=kLoop, calls=%fc
  ROOT %other.2 = f32[8,8]{1,0} custom-call(%p), custom_call_target="Sharding"
"""


@pytest.fixture()
def red():
    xspace = ProfileData.text_proto_to_serialized_xspace(TRACE)
    return tr.reduce(tr.from_profile(ProfileData.from_serialized_xspace(xspace)))


def test_busy_time_is_the_union_of_ops_inside_the_window(red):
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(850e-9)


def test_op_time_is_summed_and_clipped_to_the_window(red):
    assert red.op_s["k.3"] == pytest.approx(650e-9)
    assert red.op_s["fusion.1"] == pytest.approx(250e-9)


def test_gaps_are_labelled_by_the_innermost_host_span(red):
    assert red.gaps == [("bench.block", pytest.approx(100e-9)),
                        ("bench.dispatch", pytest.approx(50e-9))]
    bd = tr.breakdown(red, top=1)
    assert bd["device_ops"] == [["k.3", pytest.approx(650e-9)]]
    assert bd["idle_gaps"] == [["bench.block", pytest.approx(100e-9)]]


def test_innermost_drops_enclosing_events():
    outer, a, b, c = (tr.Span("w", 0, 10), tr.Span("a", 1, 3), tr.Span("b", 3, 5),
                      tr.Span("c", 9, 12))
    assert tr.innermost([a, outer, c, b]) == [a, b, c]


def test_custom_calls_names_only_pallas_kernels():
    assert tr.custom_calls(HLO) == {"k.3": "jit(f)/jit(my_kernel)/pallas_call"}


def test_a_trace_without_a_window_or_a_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(tr.Trace(devices={"d": [tr.Span("x", 0, 1)]}, host=[]))
    with pytest.raises(ValueError, match="no device op"):
        tr.reduce(tr.Trace(devices={}, host=[tr.Span(tr.WINDOW_SPAN, 0, 1)]))


def _ctx(red, **work):
    # 1000 B/s makes the floors easy to count by hand
    return harness.Context(red=red, kernels=tr.custom_calls(HLO), steps=2,
                           work=work, peak={"hbm_bytes_per_s": 1000.0})


def test_readers(red):
    read = lambda name, ctx: harness.load_module(  # noqa: E731
        harness.HERE / "metrics" / f"{name}.py").read(ctx)
    ctx = _ctx(red, step=(1e-4, 3e-4))
    assert read("idle_share", ctx) == pytest.approx(15.0)
    # kernel k.3 650 ns of 850 ns busy
    assert read("xla_glue_share", ctx) == pytest.approx(100 * 200 / 850)
    # 2 steps of 1e-4 B at 1000 B/s = 2e-7 s over a 1e-6 s window
    assert read("step_roofline", ctx) == pytest.approx(20.0)
    assert ctx.intensity == {"step_roofline": pytest.approx(3.0)}


def test_kernel_roofline_sums_only_matching_kernels(red):
    ctx = _ctx(red)
    assert ctx.kernel_s(r"jit\(my_kernel\)") == pytest.approx(650e-9)
    assert ctx.kernel_s(r"jit\(stencil3d_pallas\)") == 0.0
    # no time for the kernel: the reader reports nothing, never 0
    read = harness.load_module(harness.HERE / "metrics" / "stencil3d_roofline.py").read
    assert read(_ctx(red, grid=(2, 2, 2), taps=7, itemsize=4)) is None
