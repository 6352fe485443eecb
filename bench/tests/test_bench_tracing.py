"""The trace reduction on a trace made by hand."""
import pytest

from bench.harness import tracing
from bench.harness.tracing import Event


def ev(name, s, t):
    return Event(name, float(s), float(t))


OPS = [ev("fusion.1", 0, 10), ev("custom-call", 5, 20), ev("fusion.2", 30, 40),
       ev("fusion.1", 38, 45), ev("copy", 90, 120)]


def test_union_merges_overlaps_and_keeps_gaps():
    assert tracing.merge(OPS) == [(0, 20), (30, 45), (90, 120)]


@pytest.mark.parametrize("lo,hi,busy_ns", [(0, 100, 45), (10, 35, 15), (0, 200, 65), (50, 80, 0)])
def test_busy_seconds_is_the_union_within_the_window(lo, hi, busy_ns):
    assert tracing.busy_seconds(OPS, lo, hi) == pytest.approx(busy_ns * 1e-9)


def test_idle_gaps_cover_the_rest_of_the_window():
    assert tracing.idle_gaps(OPS, 0, 100) == [(20, 30), (45, 90)]
    busy = tracing.busy_seconds(OPS, 0, 100)
    idle = sum(t - s for s, t in tracing.idle_gaps(OPS, 0, 100)) * 1e-9
    assert busy + idle == pytest.approx(100e-9)


def test_gaps_are_labelled_by_the_innermost_host_span():
    spans = [ev("bench.window", 0, 100), ev("bench.step", 40, 95)]
    got = tracing.longest_gaps(OPS, 0, 100, spans)
    assert got == [["bench.step", pytest.approx(45e-9)],
                   ["bench.window", pytest.approx(10e-9)]]
    assert tracing.label_gap((200, 300), spans) == "outside bench spans"


def test_kernel_sums_and_top_ops():
    kern = sum(e.seconds for e in OPS if tracing.matches(e.name, ("custom",)))
    assert kern == pytest.approx(15e-9)
    assert tracing.top_ops(OPS) == [["copy", pytest.approx(30e-9)],
                                    ["fusion.1", pytest.approx(17e-9)],
                                    ["custom-call", pytest.approx(15e-9)],
                                    ["fusion.2", pytest.approx(10e-9)]]
    assert [name for name, _ in tracing.top_ops(OPS, 2)] == ["copy", "fusion.1"]


def test_clip_cuts_events_to_the_window():
    assert tracing.clip(OPS, 8, 35) == [ev("fusion.1", 8, 10), ev("custom-call", 8, 20),
                                        ev("fusion.2", 30, 35)]


def test_trace_window_is_the_named_host_span():
    tr = tracing.Trace(spans=[ev("bench.step", 3, 4), ev("bench.window", 1, 9)])
    assert tr.window("bench.window") == (1, 9)
    assert tr.window("bench.other") is None


# op names as the TPU's "XLA Ops" line gives them: the whole instruction text
WHILE = "%while.4 = (s32[]{:T(128)}, f32[4,1,7168]{2,0,1:T(4,128)}) while(%tuple.1)"
KERNEL = ('%closed_call.12 = (f32[32,7,128]{2,1,0:T(8,128)}, f32[32,7,1]{2,1,0}) '
          'custom-call(s32[7,8591]{1,0} %copy-done.27), '
          'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')
COPY = "%copy.85 = bf16[1076,8,128,128]{3,2,1,0:T(8,128)(2,1)} copy(%fusion.106)"
NESTED = [ev(WHILE, 0, 100), ev(KERNEL, 10, 40), ev(COPY, 50, 60), ev(COPY, 70, 80),
          ev(KERNEL, 120, 150)]


def test_leaves_drop_the_loop_that_spans_its_body():
    assert [e.start_ns for e in tracing.leaves(NESTED)] == [10, 50, 70, 120]
    assert tracing.leaves(OPS) == sorted(OPS, key=lambda e: e.start_ns)


def test_short_names_keep_id_shape_and_custom_call_target():
    assert tracing.short_name(KERNEL) == (
        "%closed_call.12 f32[32,7,128] custom-call tpu_custom_call")
    assert tracing.short_name(COPY) == "%copy.85 bf16[1076,8,128,128]"
    assert tracing.short_name("fusion.1") == "fusion.1"


def test_top_ops_count_leaves_by_short_name():
    got = dict(tracing.top_ops(NESTED))
    assert got == {"%closed_call.12 f32[32,7,128] custom-call tpu_custom_call":
                   pytest.approx(60e-9),
                   "%copy.85 bf16[1076,8,128,128]": pytest.approx(20e-9)}


def test_the_readers_find_the_kernels_and_programs_by_the_chips_names():
    from types import SimpleNamespace

    from bench.harness import counts
    from bench.harness.device import Peaks
    from bench.harness.spec import metric_reader
    from bench.tests.test_bench_counts import cfg

    c = cfg("yi-34b-200k")
    peaks = Peaks("test", 1e14, 2e14, 1e11, 1e10, "test")
    ctx = [[1000, 3000], [1001, 3001]]
    ns = 1e6                                           # 1 ms in the trace's clock
    trace = tracing.Trace(
        ops=[ev(WHILE, 0, 9 * ns), ev(KERNEL, 1 * ns, 3 * ns), ev(COPY, 3 * ns, 4 * ns),
             ev(KERNEL, 5 * ns, 7 * ns)],
        modules=[ev("jit__unknown(105)", 0, 4 * ns), ev("jit__argmax(108)", 4 * ns, 5 * ns),
                 ev("jit__unknown(105)", 5 * ns, 9 * ns)])
    record = SimpleNamespace(cfg=c, peaks=peaks, trace=trace, trace_window=(0, 10 * ns),
                             traced_ticks=ctx)
    least = sum(counts.roofline_seconds(*counts.decode_attention(c, t), peaks) for t in ctx)
    assert metric_reader("decode_attn_roofline")(record) == pytest.approx(
        100 * 4 * least / 4e-3)
    flops = sum(counts.decode_step_flops(c, t) for t in ctx)
    assert metric_reader("decode_step_mfu")(record) == pytest.approx(
        100 * flops / 9e-3 / 1e14)
    assert metric_reader("device_idle_share")(record) == pytest.approx(10.0)
    record.trace = tracing.Trace()
    assert metric_reader("decode_attn_roofline")(record) is None
    assert metric_reader("decode_step_mfu")(record) is None
