"""One run of one cell: set-up, warm-up, the measured window, the check.

The window drives the program's own entry points, ``Scheduler.submit`` and
``Scheduler.step`` over a ``DecodeEngine`` with the lean backend and the
paged pool, and reads every number from the client's side: each token is
stamped by the host clock when ``step`` hands it out.

How requests reach the server, in set-up and in each tick of the window, is
the mix's loop (:mod:`bench.loops`); this module keeps the clock, the window,
the trace and the check.
"""
from __future__ import annotations

import functools
import gc
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench.harness import check, counts, traffic, tracing
from bench.harness.device import device_info, memory_in_use, peaks_for, require_tpu
from bench.harness.spec import Cell, read_metrics
from bench.harness.stalls import StallWatch
from bench.harness.weights import make_weights

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# tracing a function to a jaxpr, and lowering it: a retrace that then finds
# its program in memory shows here and not as a compile
TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
# a tick this long has its host stack sampled and its JAX events logged
LONG_TICK_S = 0.5


@dataclass
class Record:
    """What a run saw, for the per-layer metric readers."""

    cfg: dict                                  # the configuration file
    peaks: object                              # device.Peaks
    window_compiles: int = 0                   # programs built or loaded in the window
    trace: Optional[tracing.Trace] = None
    trace_window: Optional[tuple] = None       # (start_ns, end_ns), trace clock
    traced_ticks: List[List[int]] = field(default_factory=list)  # attended ctx per tick


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models import ModelConfig

    layers = int(cfg["num_hidden_layers"])
    d, hq, hkv, hd = counts.dims(cfg)
    return ModelConfig(
        name=cfg["name"], d_model=d, n_layers=layers, n_heads=hq,
        n_kv_heads=hkv, head_dim=hd, d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]), stages=((("attn",), layers),),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )


class CompileLog:
    """Wall-clock spans of every program JAX builds or loads from its
    persistent cache (its own ``backend_compile`` events), and of every
    trace and lowering."""

    def __init__(self):
        self.spans: List[tuple] = []
        jax.monitoring.register_event_time_span_listener(self._on)

    def _on(self, event, start, end, **kw):
        if event == COMPILE_EVENT or event in TRACE_EVENTS:
            self.spans.append((start, end, event.rsplit("/", 1)[-1],
                               kw.get("fun_name", "")))

    def since(self, wall: float) -> List[str]:
        """Names of the programs built or loaded from ``wall`` on."""
        return [name for s, _, ev, name in self.spans
                if s >= wall and ev == COMPILE_EVENT.rsplit("/", 1)[-1]]

    def between(self, a: float, b: float) -> List[tuple]:
        """(event, function) of every span that overlaps ``[a, b]``."""
        return [(ev, name) for s, e, ev, name in self.spans if s <= b and e >= a]

    def close(self):
        jax.monitoring.unregister_event_time_span_listener(self._on)


class Client:
    """Stamps each token with the host clock as the scheduler hands it out."""

    def __init__(self):
        self.stamps: Dict[int, List[float]] = {}

    def on_token(self, uid, token, done):
        self.stamps.setdefault(uid, []).append(time.perf_counter())


def _server(cell: Cell, model_cfg, params, sizes: dict):
    from repro.serving.config import EngineConfig, PagedConfig
    from repro.serving.engine import DecodeEngine
    from repro.serving.scheduler import Scheduler, SchedulerConfig

    p = cell.params
    eng = DecodeEngine(model_cfg, params, config=EngineConfig(
        max_batch=sizes["max_batch"], cache_len=sizes["cache_len"],
        attn_backend="lean",
        paged=PagedConfig(enabled=True, page_size=int(p["page_size"]),
                          num_pages=sizes["num_pages"],
                          kv_dtype=p.get("kv_dtype")),
    ))
    chunk, pack = int(p["chunk_size"]), int(p["prefill_pack"])
    return Scheduler(eng, SchedulerConfig(
        chunk_size=chunk, prefill_pack=pack,
        token_budget=chunk * pack + sizes["max_batch"],
    ))


def _roofline_tick_s(cfg, peaks, ctx) -> float:
    """A decode tick can take no less than reading every weight and the
    sequences' K and V once at the chip's bandwidth."""
    return counts.decode_step_min_bytes(cfg, ctx) / peaks.hbm_bytes_per_s


def _percentile(values, q) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_chip: bool = True, peaks=None,
        log: Callable[[str], None] = lambda s: None,
        control: Optional[str] = None) -> dict:
    """One run; returns the result line's dict (``check`` last). With
    ``control`` (a reference format, ``"fp8"``) the check also reads the
    control on the same sessions (``readings``) and judges it by the same
    limits (``control_correct``, ``control_check``)."""
    from repro.compile_cache import use_compile_cache
    from repro.models import init_params

    devices = jax.devices()
    if require_chip:
        dev = require_tpu(devices, cell.chips)
    else:
        dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    peaks = peaks if peaks is not None else peaks_for(dev.device_kind)
    log(f"compile cache: {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileLog()
    try:
        return _run(cell, seed, seconds, trace, t_process, devices, peaks,
                    log, compiles, init_params, control)
    finally:
        compiles.close()


def _run(cell, seed, seconds, trace, t_process, devices, peaks, log,
         compiles, init_params, control):
    clock = time.perf_counter
    cfg = cell.config
    mcfg = model_config(cfg)
    params = make_weights(init_params, mcfg, seed)
    loop = traffic.loop(cell.traffic["loop"])
    requests = loop.generate(cell.traffic, seed, mcfg.vocab_size)
    sch = _server(cell, mcfg, params,
                  loop.server_sizes(cell.traffic, requests, int(cell.params["page_size"])))
    client = Client()
    drive = loop.Drive(sch, requests, client.on_token)
    drive.setup(seconds, functools.partial(_roofline_tick_s, cfg, peaks), log)
    log(f"set-up done: {memory_in_use(devices, cell.chips)} bytes in use "
        f"on the fullest chip")

    record = Record(cfg=cfg, peaks=peaks)
    # what set-up made lives through the window: a collection in the window
    # scans only what the window makes
    gc.collect()
    gc.freeze()
    collections = [g["collections"] for g in gc.get_stats()]
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    trace_s = min(seconds, float(cell.params.get("trace_seconds", seconds)))
    watch = StallWatch(after=LONG_TICK_S)
    watch.start()
    wall0 = time.time()
    t0 = clock()
    setup_s = t0 - t_process
    ticks = []                        # (start, end) of each window tick
    tracing_on = False
    if trace:
        jax.profiler.start_trace(trace_dir)
        tracing_on = True
        window_span = jax.profiler.TraceAnnotation("bench.window")
        window_span.__enter__()
    while True:
        if tracing_on and clock() - t0 >= trace_s:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing_on = False
        ts = clock()
        if ts - t0 >= seconds:
            break
        watch.tick(len(ticks))
        drive.before_tick()
        if tracing_on:
            record.traced_ticks.append(drive.contexts())
        with jax.profiler.TraceAnnotation("bench.step"):
            sch.step()
        ticks.append((ts, clock()))
        watch.tick(None)
    if tracing_on:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    watch.stop()
    gc.unfreeze()
    collections = [g["collections"] - c for g, c in zip(gc.get_stats(), collections)]
    built = compiles.since(wall0)
    record.window_compiles = len(built)
    device = device_info(devices, cell.chips)

    t_end = ticks[-1][1]
    gaps, tokens, failed = [], 0, 0
    due = drive.due()
    for uid in client.stamps.keys() | set(due):
        st = [t for t in client.stamps.get(uid, []) if t0 <= t <= t_end]
        tokens += len(st)
        gaps.extend(np.diff(st))
        failed += uid in due and not st       # due in the window, never served
    e2e = {
        "output_tok_s": tokens / (t_end - t0),
        "tpot_p95_ms": _percentile(gaps, 95) * 1e3,
        "setup_s": setup_s,
    }
    longest = sorted(((b - a, i) for i, (a, b) in enumerate(ticks)), reverse=True)[:3]
    in_window = compiles.between(wall0, wall0 + (t_end - t0))
    log(f"window: {len(ticks)} ticks, {tokens} tokens in {t_end - t0} s, "
        f"{len(built)} programs built or loaded {built}, {len(in_window)} JAX "
        f"compile, trace or lowering events; longest ticks (s, index) {longest}; "
        f"collections by generation {collections}")
    for i, (a, b) in enumerate(ticks):
        if b - a >= LONG_TICK_S:
            log(f"long tick {i}: {b - a} s, at {a - t0} s into the window; JAX "
                f"events {compiles.between(wall0 + a - t0, wall0 + b - t0)}; "
                f"host stacks (innermost first, looks) {watch.report(i)}; "
                f"host counters around it {watch.host(a, b)}")

    served = drive.served()
    kv_bad, kv_seen = check.kv_dtype_mismatches(sch.engine.cache, cfg["torch_dtype"])
    log(f"K/V pool dtypes {kv_seen}, configuration {cfg['torch_dtype']}")
    sch.engine.cache = None           # the KV pool goes before the reference runs
    del sch, drive
    gc.collect()

    result = {"correct": False, "attempted": len(due), "failed": failed}
    if trace:
        record.trace = tracing.load_xplane(_xplane(trace_dir))
        record.trace_window = record.trace.window("bench.window")
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = record.trace_window
        busy = tracing.busy_seconds(record.trace.ops, lo, hi)
        device["busy_s"] = busy
        device["window_s"] = (hi - lo) * 1e-9
        metrics = read_metrics(cell.per_layer, record)
        result["breakdown"] = {
            "device_ops": tracing.top_ops(tracing.clip(record.trace.ops, lo, hi)),
            "idle_gaps": tracing.longest_gaps(
                record.trace.ops, lo, hi, record.trace.spans),
        }
    else:
        metrics = {m.name: {"value": e2e[m.name], "unit": m.unit}
                   for m in cell.end_to_end}
    verdict = check.check(params, cfg, served, cell.params["check"], seed, log,
                          control=control)
    numbers = dict(verdict["numbers"])
    numbers["kv_dtype_mismatches"] = {"value": kv_bad, "limit": 0}
    result.update(correct=verdict["correct"] and kv_bad == 0 and failed == 0,
                  metrics=metrics, device=device)
    result["readings"] = verdict["stats"]
    if control is not None:
        result["control_correct"] = verdict["control_correct"]
        result["control_check"] = verdict["control_numbers"]
    result["check"] = numbers
    return result


def _xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
