"""The program's spans in a profiler trace: a few scheduler ticks of a tiny
paged lean engine profiled on the CPU, and the reductions on traces made by
hand."""
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.harness import program_trace, tracing
from bench.harness.tracing import Event

# span -> the spans one of which must hold it
PARENTS = {
    "sched.admit": ("sched.step",),
    "sched.prefill": ("sched.step",),
    "sched.deliver": ("sched.step",),
    "engine.tick": ("sched.step",),
    "engine.prefill_chunk": ("sched.prefill",),
    "engine.pages": ("engine.tick",),
    "engine.decode_kernel": ("engine.tick",),
    "engine.token_wait": ("engine.tick", "engine.prefill_chunk"),
    "engine.emit": ("engine.tick",),
    "engine.schedule_build": ("engine.decode_kernel", "engine.prefill_chunk"),
    "engine.inputs": ("engine.decode_kernel", "engine.prefill_chunk"),
    "engine.dispatch": ("engine.decode_kernel", "engine.prefill_chunk"),
}
# what an enabled tracer records of this run, as it did before the program
# marked the profiler's trace: (name, tick, depth)
RECORDED = [
    ("prefill_chunk", -1, 0), ("tick", 0, 0), ("prefill_chunk", 0, 0),
    ("schedule_build", 1, 2), ("decode_kernel", 1, 1), ("tick", 1, 0),
    ("schedule_build", 2, 2), ("decode_kernel", 2, 1), ("tick", 2, 0),
    ("schedule_build", 3, 2), ("decode_kernel", 3, 1), ("tick", 3, 0),
    ("tick", 4, 0), ("tick", 5, 0),
]


def ev(name, s, t):
    return Event(name, float(s), float(t))


@pytest.fixture(scope="module")
def smoke():
    from repro.configs import get_smoke_config
    from repro.models import init_params

    jax.config.update("jax_platform_name", "cpu")
    cfg = get_smoke_config("mistral-nemo-12b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("recording", [False, True])
def test_scheduler_ticks_mark_the_profile_at_every_layer(smoke, recording, tmp_path):
    from repro.obs.trace import Tracer
    from repro.serving.config import EngineConfig, ObsConfig, PagedConfig
    from repro.serving.engine import DecodeEngine
    from repro.serving.scheduler import Scheduler, SchedulerConfig

    cfg, params = smoke
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=2, cache_len=64, num_workers=4, attn_backend="lean",
        paged=PagedConfig(enabled=True, page_size=8),
        obs=ObsConfig(tracer=Tracer(enabled=recording)),
    ))
    sch = Scheduler(eng, SchedulerConfig(chunk_size=8, token_budget=16))
    rng = np.random.default_rng(0)
    for _ in range(2):
        sch.submit(rng.integers(0, cfg.vocab_size, 12), 4)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(6):
            sch.step()
    spans = program_trace.load_spans(sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1])

    names = {e.name for e in spans}
    assert set(PARENTS) | {"sched.step"} <= names, names
    assert len([e for e in spans if e.name == "sched.step"]) == 6
    for e in spans:
        if e.name in PARENTS:
            assert any(p.name in PARENTS[e.name]
                       and program_trace.within(e, p.start_ns, p.end_ns)
                       for p in spans), f"{e.name} outside {PARENTS[e.name]}"
    if recording:
        assert [(s["name"], s["tick"], s["depth"]) for s in eng.tracer.spans] == RECORDED
    else:
        assert eng.tracer.spans == []
    assert program_trace.tick_host_ms(spans, spans[0].start_ns, spans[-1].end_ns) > 0


def test_tick_host_ms_subtracts_only_the_covered_wait_and_skips_cut_ticks():
    ms = 1e6                                           # 1 ms in the trace's clock
    spans = [
        ev("sched.step", 0, 10 * ms), ev("engine.tick", 1 * ms, 9 * ms),
        ev("engine.token_wait", 5 * ms, 8 * ms),
        ev("sched.step", 12 * ms, 20 * ms),             # no wait: all host
        # cut by the window's end
        ev("sched.step", 25 * ms, 40 * ms), ev("engine.token_wait", 26 * ms, 39 * ms),
    ]
    assert program_trace.tick_host_ms(spans, 0, 30 * ms) == pytest.approx((7 + 8) / 2)
    # a wait that runs past its step counts only where the step covers it
    spans[2] = ev("engine.token_wait", 5 * ms, 11 * ms)
    assert program_trace.tick_host_ms(spans, 0, 30 * ms) == pytest.approx((5 + 8) / 2)
    assert program_trace.tick_host_ms(spans, 21 * ms, 30 * ms) is None


def test_gaps_are_labelled_by_the_innermost_program_span():
    ops = [ev("fusion.1", 0, 10), ev("custom-call", 30, 40), ev("copy", 90, 100)]
    spans = [ev("bench.window", 0, 100), ev("bench.step", 5, 95),
             ev("sched.step", 6, 94), ev("engine.tick", 8, 60),
             ev("engine.token_wait", 12, 28), ev("sched.deliver", 62, 93)]
    got = tracing.longest_gaps(ops, 0, 100, spans)
    assert [label for label, _ in got] == ["sched.deliver", "engine.token_wait"]
    assert [s for _, s in got] == [pytest.approx(50e-9), pytest.approx(20e-9)]


# a decode and a chunk-prefill Pallas call as the TPU's op line names them
DECODE = ('%lean_decode_fused.8 = (f32[32,7,128]{2,1,0:T(8,128)S(1)}, f32[32,7,1]{2,1,0:T(8,128)}) '
          'custom-call(s32[7,8895]{1,0:T(8,128)S(1)} %copy-done.27), '
          'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')
PREFILL = ('%lean_prefill_chunk.3 = (f32[9,7,256,128]{3,2,1,0}) custom-call(s32[7,12]{1,0} %p), '
           'custom_call_target="tpu_custom_call"')
UNNAMED = DECODE.replace("%lean_decode_fused.8", "%closed_call.12")
COPY = "%copy.85 = bf16[1228,8,128,128]{3,2,1,0:T(8,128)(2,1)} copy(%fusion.106)"


def test_decode_kernel_roofline_counts_only_the_named_decode_kernels():
    from types import SimpleNamespace

    from bench.harness.device import Peaks
    from bench.harness.spec import metric_reader
    from bench.tests.test_bench_counts import cfg

    ns = 1e6
    peaks = Peaks("test", 1e14, 2e14, 1e11, 1e10, "test")

    def record(*ops):
        trace = tracing.Trace(ops=[ev(name, s * ns, t * ns) for name, s, t in ops])
        return SimpleNamespace(cfg=cfg("yi-34b-200k"), peaks=peaks, trace=trace,
                               trace_window=(0, 10 * ns), traced_ticks=[[1000, 3000]])

    kernel, attn = metric_reader("decode_kernel_roofline"), metric_reader("decode_attn_roofline")
    decode_only = record((DECODE, 1, 3), (COPY, 3, 4), (DECODE, 5, 7))
    assert kernel(decode_only) == pytest.approx(attn(decode_only))
    mixed = record((DECODE, 1, 3), (PREFILL, 3, 5), (DECODE, 5, 7))
    assert kernel(mixed) == pytest.approx(attn(decode_only))
    assert attn(mixed) == pytest.approx(attn(decode_only) * 4 / 6)
    # a program whose kernels carry no name= gives nothing to read
    assert kernel(record((UNNAMED, 1, 3), (COPY, 3, 4))) is None
    assert kernel(SimpleNamespace(trace=None)) is None
