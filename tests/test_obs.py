"""Observability layer: tracer spans + request timelines, metrics
registry exporters (JSON <-> Prometheus round-trip), histogram
rebucketing, the flight recorder, and the ``repro.obs`` report CLI —
including the scheduler-integration contract that a traced serving run
yields a correct per-request lifecycle timeline."""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.lean_decode import fused_iters_per_step
from repro.models import init_params
from repro.obs.flight import FlightRecorder, load_flight_dump
from repro.obs.metrics import (
    Histogram,
    MetricsRegistry,
    default_bounds,
    parse_prometheus,
)
from repro.obs.report import render_flight, render_report
from repro.obs.trace import NULL_TRACER, Tracer, load_trace
from repro.serving.config import EngineConfig, ObsConfig, PagedConfig
from repro.serving.engine import DecodeEngine
from repro.serving.scheduler import Scheduler, SchedulerConfig

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------------ tracer
def test_span_nesting_depth_and_tick_attribution():
    tr = Tracer()
    with tr.span("tick"):
        with tr.span("schedule_build", tiles=4):
            pass
        with tr.span("decode_kernel"):
            with tr.span("merge"):
                pass
    with tr.span("tick"):
        pass
    spans = tr.spans
    by_name = {s["name"]: s for s in spans}
    assert by_name["schedule_build"]["depth"] == 1
    assert by_name["merge"]["depth"] == 2
    assert by_name["schedule_build"]["tick"] == 0
    assert by_name["schedule_build"]["meta"] == {"tiles": 4}
    # two ticks, both recorded, second at index 1
    ticks = [s for s in spans if s["name"] == "tick"]
    assert [t["tick"] for t in ticks] == [0, 1]
    assert all(s["ms"] >= 0 for s in spans)


def test_annotate_targets_innermost_open_span():
    tr = Tracer()
    with tr.span("tick"):
        with tr.span("decode_kernel"):
            tr.annotate(level=0, kv_bytes=1024)
    dk = [s for s in tr.spans if s["name"] == "decode_kernel"][0]
    assert dk["meta"] == {"level": 0, "kv_bytes": 1024}
    tr.annotate(orphan=True)          # no open span: must not raise


def test_disabled_tracer_is_inert_and_falsy():
    tr = Tracer(enabled=False)
    sp = tr.span("tick")
    assert not sp                      # gates optional sync work
    with sp as s:
        s.annotate(x=1)
        s.add_sync(1.0)
    tr.request_event(0, "QUEUED")
    tr.request_token(0)
    assert tr.spans == []
    assert tr.request_uids() == []
    assert tr.request_summary(0) is None
    # the module singleton's spans only mark the profiler's trace: falsy,
    # and nothing is kept in memory
    with NULL_TRACER.span("anything") as s:
        assert not s
    assert not NULL_TRACER.span("sched.step")
    assert NULL_TRACER.spans == []


def test_layer_named_spans_only_mark_the_profile():
    tr = Tracer()
    with tr.span("tick") as tick:
        with tr.span("engine.dispatch") as sp:
            assert not sp              # never recorded, so never gated on
            tr.annotate(level=0)       # lands on the recorded tick
        assert tick
    assert [s["name"] for s in tr.spans] == ["tick"]
    assert tr.spans[0]["meta"] == {"level": 0}


def test_span_capacity_is_a_ring():
    tr = Tracer(capacity=4)
    for _ in range(10):
        with tr.span("tick"):
            pass
    assert len(tr.spans) == 4
    assert [s["tick"] for s in tr.spans] == [6, 7, 8, 9]


def test_request_timeline_summary_derivations():
    tr = Tracer()
    tr.request_event("r1", "QUEUED")
    tr.request_event("r1", "PREFILLING", slot=0)
    tr.request_event("r1", "DECODING", slot=0)
    for _ in range(4):
        tr.request_token("r1")
    tr.request_event("r1", "FINISHED", tokens=4)
    s = tr.request_summary("r1")
    assert s["tokens"] == 4
    assert s["queue_wait_s"] >= 0
    assert s["ttft_s"] >= s["queue_wait_s"]
    assert s["tpot_s"]["gaps"] == 3
    assert s["tpot_s"]["min"] <= s["tpot_s"]["mean"] <= s["tpot_s"]["max"]
    assert [e["state"] for e in s["events"]] == [
        "QUEUED", "PREFILLING", "DECODING", "FINISHED"
    ]


def test_trace_save_load_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("tick"):
        with tr.span("decode_kernel", kv_bytes=2048, flops=1e6):
            pass
    tr.request_event(0, "QUEUED")
    path = tmp_path / "t.json"
    tr.save(path, extra={"metrics": {"engine_ticks": 1}})
    doc = load_trace(path)
    assert doc["ticks"] == 1
    assert doc["meta"]["metrics"]["engine_ticks"] == 1
    assert doc["requests"]["0"]["events"][0]["state"] == "QUEUED"
    out = render_report(doc)
    assert "per-tick attribution" in out
    assert "cache & cascade effectiveness" in out


# ----------------------------------------------------------------- metrics
def test_registry_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    c = reg.counter("engine_ticks", help="ticks")
    assert reg.counter("engine_ticks") is c
    c.inc(3)
    assert reg.as_dict()["engine_ticks"] == 3
    with pytest.raises(ValueError):
        reg.gauge("engine_ticks")             # kind conflict
    with pytest.raises(ValueError):
        reg.counter("bad name")               # invalid name
    reg.gauge_fn("live", lambda: 7.0)
    with pytest.raises(ValueError):
        reg.counter("live")                   # callback/stored conflict
    assert reg.get("live") == 7.0
    assert reg.names() == ["engine_ticks", "live"]


def test_labeled_family_children():
    reg = MetricsRegistry()
    fam = reg.counter("kernel_calls", labelnames=("path",))
    fam.labels(path="fast").inc(2)
    fam.labels(path="legacy").inc()
    assert fam.labels(path="fast").value == 2
    with pytest.raises(ValueError):
        fam.labels(backend="fast")            # wrong label name
    d = reg.as_dict()["kernel_calls"]
    assert d == {"path=fast": 2, "path=legacy": 1}


def test_prometheus_roundtrip_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("ticks").inc(5)
    reg.gauge("degraded").set(2)
    fam = reg.counter("calls", labelnames=("path",))
    fam.labels(path="fast").inc(3)
    h = reg.histogram("ttft_seconds", bounds=[0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 0.5, 20.0):
        h.observe(v)
    reg.gauge_fn("pool_util", lambda: 0.25)
    text = reg.to_prometheus()
    parsed = parse_prometheus(text)
    assert parsed[("ticks", ())] == 5
    assert parsed[("degraded", ())] == 2
    assert parsed[("calls", (("path", "fast"),))] == 3
    assert parsed[("pool_util", ())] == 0.25
    # histogram series are cumulative and end at +Inf == count
    assert parsed[("ttft_seconds_bucket", (("le", "0.1"),))] == 1
    assert parsed[("ttft_seconds_bucket", (("le", "1.0"),))] == 3
    assert parsed[("ttft_seconds_bucket", (("le", "+Inf"),))] == 4
    assert parsed[("ttft_seconds_count", ())] == 4
    assert parsed[("ttft_seconds_sum", ())] == pytest.approx(21.05)


def test_histogram_merge_mismatched_bounds_raises():
    a = Histogram(bounds=[1.0, 2.0])
    b = Histogram(bounds=[1.0, 2.0, 4.0])
    a.observe(1.5)
    b.observe(3.0)
    with pytest.raises(ValueError, match="rebucket"):
        a.merge(b)
    # same bounds still merge exactly
    c = Histogram(bounds=[1.0, 2.0])
    c.observe(0.5)
    a.merge(c)
    assert a.count == 2 and a.min == 0.5 and a.max == 1.5


def test_histogram_rebucket_preserves_exact_moments():
    src = Histogram(bounds=default_bounds(1e-3, 10.0, per_decade=2))
    vals = [0.002, 0.02, 0.5, 5.0, 50.0]
    for v in vals:
        src.observe(v)
    dst = src.rebucket([0.01, 1.0, 100.0])
    assert dst.count == src.count
    assert dst.sum == pytest.approx(src.sum)
    assert dst.min == src.min and dst.max == src.max
    assert sum(dst.counts) == dst.count
    # and the rebucketed histogram merges into a same-bounds peer
    peer = Histogram([0.01, 1.0, 100.0])
    peer.observe(0.5)
    peer.merge(dst)
    assert peer.count == 6
    # empty rebucket is the empty histogram
    assert Histogram([1.0]).rebucket([2.0]).count == 0


def test_telemetry_shim_warns_and_still_exports_old_names():
    """The deprecated shim keeps the old names importable but announces
    its replacement via DeprecationWarning (once, at import)."""
    import importlib
    import sys

    sys.modules.pop("repro.serving.telemetry", None)
    with pytest.warns(DeprecationWarning, match="repro.obs.metrics"):
        telemetry = importlib.import_module("repro.serving.telemetry")
    assert telemetry.Histogram is Histogram
    h = telemetry.Histogram(bounds=telemetry.default_bounds())
    h.observe(0.01)
    assert h.as_dict()["count"] == 1


def test_no_in_repo_module_imports_telemetry_shim():
    """The deprecation is fully internalized: importing every repro module
    must never trigger the shim. Checked two ways — no source file imports
    the old path, and a fresh import sweep emits no shim warning."""
    import importlib
    import pathlib
    import pkgutil
    import sys
    import warnings

    import repro

    root = pathlib.Path(next(iter(repro.__path__)))
    for py in root.rglob("*.py"):
        if py.name == "telemetry.py" and py.parent.name == "serving":
            continue
        text = py.read_text()
        assert "serving.telemetry import" not in text, (
            f"{py} imports the deprecated repro.serving.telemetry shim"
        )

    sys.modules.pop("repro.serving.telemetry", None)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for mod in pkgutil.walk_packages(repro.__path__, "repro."):
            if mod.name == "repro.serving.telemetry":
                continue
            importlib.import_module(mod.name)
    shim = [w for w in caught
            if "repro.serving.telemetry is deprecated" in str(w.message)]
    assert not shim, f"shim triggered by an in-repo import: {shim}"


# ------------------------------------------------------------------ flight
def test_flight_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
    for i in range(20):
        fr.record("tick", tick=i)
    fr.record("fault_fire", point="nan_kv", injector_tick=20)
    events = fr.events()
    assert len(events) == 8                     # ring bound
    assert events[-1]["kind"] == "fault_fire"
    bundle = fr.dump("degrade", extra={"tick": 20, "slot": 1})
    assert bundle["reason"] == "degrade"
    assert bundle["events"][-1]["point"] == "nan_kv"
    assert fr.last_dump_path is not None
    loaded = load_flight_dump(fr.last_dump_path)
    assert loaded["context"]["slot"] == 1
    out = render_flight(loaded)
    assert "nan_kv" in out and "degrade" in out


def test_flight_dump_without_dir_returns_bundle_only(tmp_path):
    fr = FlightRecorder(capacity=4)
    fr.record("tick", tick=0)
    bundle = fr.dump("poison")
    assert fr.last_dump_path is None
    assert bundle["dump_index"] == 1
    # explicit path still writes (and creates parent dirs)
    p = tmp_path / "deep" / "f.json"
    fr.dump("poison", path=str(p))
    assert json.loads(p.read_text())["reason"] == "poison"


# ------------------------------------------- scheduler lifecycle (traced)
@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_traced_scheduler_run_timeline_matches_lifecycle(smoke, tmp_path):
    cfg, params = smoke
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=2, cache_len=64, num_workers=4, attn_backend="lean",
        paged=PagedConfig(page_size=8),
        obs=ObsConfig(tracer=Tracer()),
    ))
    sch = Scheduler(eng, SchedulerConfig(
        chunk_size=8, prefill_pack=2, token_budget=16,
    ))
    rng = np.random.default_rng(0)
    hs = [sch.submit(rng.integers(0, cfg.vocab_size, 6 + 3 * i), 3)
          for i in range(3)]
    sch.run_to_completion(max_steps=100)
    assert all(h.done for h in hs)
    order = ["QUEUED", "PREFILLING", "DECODING", "FIRST_TOKEN",
             "FINISHED"]
    for h in hs:
        s = eng.tracer.request_summary(h.uid)
        states = [e["state"] for e in s["events"]]
        # lifecycle events appear exactly once each, in order (this
        # workload has no preemptions/requeues)
        assert states == order
        # token accounting matches the stream the caller received
        assert s["tokens"] == len(h.generated) == 3
        assert s["queue_wait_s"] >= 0
        assert s["ttft_s"] >= s["queue_wait_s"]
        assert s["tpot_s"]["gaps"] == 2
    # spans cover every tick the engine ran, and decode_kernel meta
    # carries the roofline cost-model annotations
    names = {s["name"] for s in eng.tracer.spans}
    # chunked admission: prefill_chunk spans instead of blocking "admit"
    assert {"tick", "prefill_chunk", "schedule_build",
            "decode_kernel"} <= names
    dk = [s for s in eng.tracer.spans if s["name"] == "decode_kernel"]
    assert all("sync_ms" in s for s in dk)
    meta = dk[-1]["meta"]
    for key in ("path", "kv_bytes", "flops", "pred_mem_ms",
                "pred_compute_ms", "total_tiles"):
        assert key in meta
    # scheduler gauges live in the engine registry
    md = eng.metrics.as_dict()
    assert md["scheduler_queue_depth"] == 0
    assert md["scheduler_pending"] == 0
    assert md["engine_ticks"] == eng.stats.ticks > 0
    assert md["engine_ttft_seconds"]["count"] == 3
    # saved trace renders end-to-end through the report CLI path
    path = tmp_path / "trace.json"
    eng.tracer.save(path, extra={"metrics": md})
    out = render_report(load_trace(path))
    assert "FINISHED" in out
    for h in hs:
        assert str(h.uid) in out


def test_decode_records_the_fused_kernels_grid(smoke):
    """The schedule record and the ``decode_kernel`` span carry the fused
    kernel's grid, ceil((G*T + P) / n) steps of n iterations, and after a
    tick the registry's gauge holds the steps per layer."""
    cfg, params = smoke
    G = 4
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=2, cache_len=64, num_workers=G, attn_backend="lean",
        paged=PagedConfig(enabled=True, page_size=8),
        obs=ObsConfig(tracer=Tracer()),
    ))
    sch = Scheduler(eng, SchedulerConfig(chunk_size=8))
    for L in (13, 30):
        sch.submit(np.arange(L) % cfg.vocab_size, 3)
    sch.run_to_completion(max_steps=50)
    rec = eng.stats.schedules[-1]
    n = fused_iters_per_step(eng.tile, cfg.head_dim, 2)
    assert rec["iters_per_step"] == n
    assert rec["grid_steps"] == -(-(G * rec["tiles_per_worker"]
                                     + rec["pieces"]) // n)
    meta = [s for s in eng.tracer.spans if s["name"] == "decode_kernel"][-1][
        "meta"]
    assert (meta["grid_steps"], meta["iters_per_step"]) == (
        rec["grid_steps"], n)
    gauge = eng.metrics.as_dict()["engine_decode_kernel_grid_steps"]
    assert gauge["value"] == rec["grid_steps"] > 0


def test_untraced_engine_records_nothing(smoke):
    cfg, params = smoke
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=2, cache_len=32, num_workers=4, attn_backend="lean",
        paged=PagedConfig(page_size=8),
    ))
    sch = Scheduler(eng, SchedulerConfig(chunk_size=8))
    h = sch.submit(np.arange(5), 2)
    sch.run_to_completion(max_steps=50)
    assert h.done
    assert eng.tracer is NULL_TRACER or not eng.tracer.enabled
    assert eng.tracer.spans == []
    assert eng.tracer.request_uids() == []
    # metrics still work untraced — they are always-on
    assert eng.metrics.as_dict()["engine_ticks"] > 0
