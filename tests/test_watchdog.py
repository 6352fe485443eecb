"""Perf watchdog: streaming detectors, SLO error budgets, calibration.

Covers the PR-9 acceptance contract from both sides:

  * a fault-free churn run of >= 200 ticks yields ZERO detector fires
    (the false-positive guard), while
  * injected ``tick_latency`` / ``preempt_storm`` bursts each yield a
    watchdog-armed flight bundle naming the firing detector and the
    metric window that tripped it (chaos-marked).

Plus unit coverage of every detector's trip condition, the SLO budget
math, detector-triggered (observable) degrade, and the roofline
calibration fit/round-trip the occupancy band consumes.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.obs import SLOConfig, Tracer, WatchConfig
from repro.obs.calib import Calibration, fit_calibration, load_calibration
from repro.obs.watch import (
    ErrorBudget,
    FlapDetector,
    HitRateDropDetector,
    OccupancyDetector,
    PerfWatchdog,
    PreemptChurnDetector,
    RetraceStormDetector,
    TickSpikeDetector,
)
from repro.serving.config import EngineConfig, ObsConfig, PagedConfig
from repro.serving.engine import DecodeEngine, Request
from repro.serving.faults import FaultInjector, FaultSpec
from repro.serving.guards import GuardConfig
from repro.serving.scheduler import Scheduler, SchedulerConfig

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk_engine(cfg, params, *, prefix_cache=False, tracer=None,
               watchdog=None, flight_dir=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_len", 32)
    kw.setdefault("num_workers", 4)
    kw.setdefault("attn_backend", "lean")
    return DecodeEngine(cfg, params, config=EngineConfig(
        paged=PagedConfig(page_size=8, prefix_cache=prefix_cache),
        obs=ObsConfig(
            tracer=tracer, watchdog=watchdog, flight_dir=flight_dir,
        ),
        **kw,
    ))


# ------------------------------------------------------------- detectors

CFG = WatchConfig(warmup_ticks=4, window=8, cooldown_ticks=4)


def test_tick_spike_trips_on_spike_not_steady():
    d = TickSpikeDetector(CFG)
    for t in range(20):
        assert d.observe(t, 1.0 + 0.01 * (t % 3)) is None
    f = d.observe(20, 50.0)
    assert f and f["detector"] == "tick_spike"
    assert f["value_ms"] == 50.0 and f["threshold_ms"] >= 10.0
    assert len(f["window"]) > 0           # the tripping window is named


def test_tick_spike_ignores_explained_ticks():
    """Compile/schedule-rebuild ticks are slow for a known reason: they
    neither fire the detector nor poison its median."""
    d = TickSpikeDetector(CFG)
    for t in range(12):
        assert d.observe(t, 1.0) is None
    assert d.observe(12, 500.0, explained=True) is None
    assert 500.0 not in d.window
    assert d.observe(13, 50.0) is not None   # unexplained still trips


def test_tick_spike_warmup_and_cooldown():
    d = TickSpikeDetector(CFG)
    for t in range(3):                       # inside warmup: silent
        assert d.observe(t, 100.0 if t == 2 else 1.0) is None
    for t in range(3, 15):
        d.observe(t, 1.0)
    assert d.observe(15, 99.0) is not None
    assert d.observe(16, 99.0) is None       # cooldown gates the repeat


def test_retrace_storm_window_sum():
    d = RetraceStormDetector(CFG)
    total = 0
    for t in range(10):                      # 1 miss / 2 ticks: quiet
        total += t % 2
        assert d.observe(t, total) is None
    total += CFG.retrace_threshold           # a burst in one tick
    f = d.observe(10, total)
    assert f and f["count"] >= CFG.retrace_threshold
    assert f["window"][-1] == CFG.retrace_threshold


def test_preempt_churn_detector():
    d = PreemptChurnDetector(CFG)
    for t in range(8):
        assert d.observe(t, 0) is None
    f = d.observe(8, CFG.preempt_threshold)
    assert f and f["detector"] == "preempt_churn"


def test_occupancy_self_calibrates_then_trips():
    d = OccupancyDetector(CFG)
    for t in range(CFG.warmup_ticks):        # warmup establishes baseline
        assert d.observe(t, meas_ms=100.0, pred_ms=1.0) is None
    for t in range(4, 8):                    # in-band: quiet
        assert d.observe(t, 110.0, 1.0) is None
    f = None
    for t in range(8, 8 + CFG.occupancy_consecutive):
        f = d.observe(t, 100.0 * CFG.occupancy_band * 2, 1.0)
    assert f and f["detector"] == "occupancy_collapse"
    assert f["baseline"] == pytest.approx(100.0)


def test_occupancy_uses_fitted_calibration():
    calib = Calibration(factors={"fast": 100.0}, default=100.0)
    d = OccupancyDetector(CFG, calib)
    # with a fitted baseline there is no self-calibration warmup beyond
    # the config gate; ratio 100x == calibrated expectation -> quiet
    for t in range(CFG.warmup_ticks, CFG.warmup_ticks + 6):
        assert d.observe(t, 100.0, 1.0, path="fast") is None
    f = None
    for t in range(20, 20 + CFG.occupancy_consecutive):
        f = d.observe(t, 100.0 * CFG.occupancy_band * 1.5, 1.0, path="fast")
    assert f and f["band"] == pytest.approx(100.0 * CFG.occupancy_band)


def test_hit_rate_drop_detector():
    d = HitRateDropDetector(CFG)
    hits = lookups = 0
    for t in range(20):                      # 90% hit rate baseline
        lookups += 10
        hits += 9
        assert d.observe(t, hits, lookups) is None
    f = None
    for t in range(20, 30):                  # collapse to 0%
        lookups += 10
        f = f or d.observe(t, hits, lookups)
    assert f and f["detector"] == "prefix_hit_drop"
    assert f["recent_rate"] < f["baseline_rate"] - CFG.hit_rate_drop


def test_flap_detector_needs_oscillation():
    d = FlapDetector(CFG)
    for t in range(10):                      # steady gauge: quiet
        assert d.observe(t, 1) is None
    f = None
    for t in range(10, 20):                  # 0/1 flapping
        f = f or d.observe(t, t % 2)
    assert f and f["transitions"] >= CFG.flap_threshold


# ----------------------------------------------------------- SLO budgets

def test_slo_config_validation():
    with pytest.raises(ValueError):
        SLOConfig(budget=0.0)
    with pytest.raises(ValueError):
        SLOConfig(ttft_target_s=-1.0)
    with pytest.raises(ValueError):
        WatchConfig(burn_alert=1.0)


def test_error_budget_math():
    b = ErrorBudget(SLOConfig(name="x", ttft_target_s=1.0,
                              tpot_target_s=0.1, budget=0.1, window=10))
    assert b.budget_remaining() == 1.0 and b.burn_rate() == 0.0
    for _ in range(9):
        assert not b.observe("ttft", 0.5)
    assert b.observe("ttft", 2.0)            # 1 breach in 10 @ 10% budget
    assert b.events == 10 and b.breaches == 1
    assert b.budget_remaining() == pytest.approx(0.0)
    assert b.burn_rate() == pytest.approx(1.0)   # exactly on budget
    assert not b.observe("tpot", None or 0.05)
    d = b.as_dict()
    assert d["breach_kinds"] == {"ttft": 1, "tpot": 0}


def test_slo_none_target_never_breaches():
    b = ErrorBudget(SLOConfig(name="x", ttft_target_s=None,
                              tpot_target_s=None))
    assert not b.observe("ttft", 1e9)
    assert b.events == 0


# ----------------------------------------------- integration: fault-free

def test_fault_free_churn_zero_fires(setup):
    """THE false-positive guard: >= 200 ticks of admission churn (new
    geometries, schedule-cache misses, prefix reuse, compiles) with
    default thresholds must not fire a single detector."""
    cfg, params = setup
    tracer = Tracer()
    eng = _mk_engine(cfg, params, prefix_cache=True, tracer=tracer,
                     watchdog=True)
    sched = Scheduler(eng, SchedulerConfig())
    rng = np.random.default_rng(0)
    pending = [
        (i * 9, rng.integers(1, cfg.vocab_size,
                             size=int(rng.integers(4, 9))))
        for i in range(24)
    ]
    step = 0
    while step < 230:
        while pending and pending[0][0] <= step:
            _, prompt = pending.pop(0)
            sched.submit(prompt, 12)
        sched.step()
        step += 1
    wd = eng.watchdog
    assert wd.ticks >= 200
    assert wd.total_fires == 0, f"false positives: {wd.fires}"
    assert all(v == 0 for v in wd.fire_counts().values())
    # fires counter family exists but nothing incremented
    assert eng.metrics.as_dict().get("watchdog_fires_total", {}) == {}


def test_slo_wiring_through_scheduler(setup):
    """submit(slo_class=...) charges that class's budget; breaches show
    in registry counters, telemetry, and the flight ring."""
    cfg, params = setup
    eng = _mk_engine(cfg, params, watchdog=WatchConfig(warmup_ticks=4))
    wd = eng.watchdog
    wd.add_slo(SLOConfig(name="interactive", ttft_target_s=1e-9,
                         tpot_target_s=1e-9, budget=0.5))
    wd.add_slo(SLOConfig(name="batch", ttft_target_s=1e9,
                         tpot_target_s=1e9))
    sched = Scheduler(eng, SchedulerConfig())
    rng = np.random.default_rng(1)
    for i in range(2):
        sched.submit(rng.integers(1, cfg.vocab_size, size=4), 8,
                     slo_class="interactive")
        sched.submit(rng.integers(1, cfg.vocab_size, size=4), 8,
                     slo_class="batch")
    sched.run_to_completion(max_steps=80)

    inter = wd.budgets["interactive"]
    assert inter.breaches == inter.events > 0    # 1ns target: all breach
    assert wd.budgets["batch"].breaches == 0
    tel = sched.telemetry()
    assert tel["slo"]["interactive"]["breaches"] == inter.breaches
    assert tel["watchdog"]["fire_counts"]["slo_burn"] >= 1
    counters = eng.metrics.as_dict()["slo_breaches_total"]
    assert counters["kind=ttft,klass=interactive"] >= 1
    assert eng.metrics.get("slo_budget_remaining_interactive") \
        == pytest.approx(0.0)
    assert any(e["kind"] == "slo_breach" for e in eng.flight.events())


def test_unknown_slo_class_is_ignored(setup):
    cfg, params = setup
    eng = _mk_engine(cfg, params, watchdog=True)
    assert eng.watchdog.observe_latency("nope", "ttft", 100.0) is False
    with pytest.raises(ValueError):
        eng.watchdog.add_slo(SLOConfig(name="a"))
        eng.watchdog.add_slo(SLOConfig(name="a"))


# --------------------------------------------- observable forced degrade

def test_force_degrade_is_observable(setup):
    """Detector-triggered degrade is recorded with its cause, not
    inferred: flight event + labeled cause counter + gauge move."""
    cfg, params = setup
    eng = _mk_engine(cfg, params, guards=GuardConfig(), watchdog=True)
    eng.submit(Request(uid=0, prompt=np.array([3, 1, 4], np.int32),
                       max_new_tokens=4))
    eng.tick()
    moved = eng.force_degrade(cause="watchdog")
    assert moved == 1
    assert eng.degraded_gauge.value == 1
    ev = [e for e in eng.flight.events() if e["kind"] == "degrade"]
    assert ev and ev[-1]["cause"] == "watchdog"
    causes = eng.metrics.as_dict()["engine_degrade_cause_total"]
    assert causes["cause=watchdog"] == 1
    with pytest.raises(ValueError):
        eng.force_degrade(cause="gremlins")


def test_force_degrade_requires_guards(setup):
    cfg, params = setup
    eng = _mk_engine(cfg, params)
    with pytest.raises(ValueError, match="guards"):
        eng.force_degrade()


# ------------------------------------------------------------ calibration

def test_fit_calibration_roundtrip(tmp_path):
    spans = [
        {"name": "decode_kernel", "tick": t, "ms": 100.0 + t,
         "meta": {"path": "fast", "pred_mem_ms": 1.0,
                  "pred_compute_ms": 0.01}}
        for t in range(6)
    ] + [
        {"name": "decode_kernel", "tick": 9, "ms": 50.0,
         "meta": {"path": "cascade", "pred_mem_ms": 1.0,
                  "pred_compute_ms": 0.0}},
        {"name": "tick", "tick": 9, "ms": 1.0},
    ]
    doc = {"format": 1, "spans": spans, "meta": {"platform": "cpu"}}
    calib = fit_calibration(doc, min_samples=3)
    assert calib.factors["fast"] == pytest.approx(102.5 / 1.01)
    assert "cascade" not in calib.factors    # below min_samples
    assert calib.samples == {"fast": 6, "cascade": 1}
    # fallback: unknown paths get the global median
    assert calib.factor("cascade") == calib.default
    p = tmp_path / "calib.json"
    calib.save(p)
    rt = load_calibration(p)
    assert rt.factors == calib.factors and rt.platform == "cpu"


def test_fit_calibration_requires_predictions():
    with pytest.raises(ValueError, match="tracer"):
        fit_calibration({"spans": [{"name": "tick", "tick": 0, "ms": 1.0}]})


def test_calibrated_cost_reconciles_roofline():
    from repro.roofline.analysis import calibrated_cost

    cost = {"pred_mem_ms": 2.0, "pred_compute_ms": 0.5, "kv_bytes": 1.0}
    out = calibrated_cost(cost, 10.0)
    assert out["pred_mem_ms"] == 20.0 and out["pred_compute_ms"] == 5.0
    assert out["calib_factor"] == 10.0
    assert cost["pred_mem_ms"] == 2.0        # input untouched


def test_calibration_registry_gauges(setup):
    cfg, params = setup
    eng = _mk_engine(cfg, params)
    calib = Calibration(factors={"fast": 123.5}, default=123.5)
    PerfWatchdog(eng, WatchConfig(), calibration=calib)
    assert eng.metrics.get("roofline_calib_factor_fast") \
        == pytest.approx(123.5)
    assert eng.watchdog.as_dict()["calibration"]["factors"]["fast"] \
        == pytest.approx(123.5)


# ------------------------------------------------------- chaos scenarios

@pytest.mark.chaos
def test_watchdog_arms_bundles_under_chaos(setup, tmp_path):
    """Acceptance: every injected tick_latency / preempt_storm burst
    yields a watchdog-armed flight bundle (reason watchdog-<detector>)
    naming the firing detector and the metric window that tripped it —
    distinct from the fault-hook-originated 'fault-injected' bundles."""
    cfg, params = setup
    faults = FaultInjector({
        "tick_latency": FaultSpec(rate=1.0, start=24, stop=27,
                                  magnitude=0.05),
        "preempt_storm": FaultSpec(rate=1.0, start=36, stop=37,
                                   magnitude=3),
    }, seed=7)
    eng = _mk_engine(cfg, params, faults=faults, flight_dir=str(tmp_path),
                     watchdog=WatchConfig(warmup_ticks=16))
    sched = Scheduler(eng, SchedulerConfig())
    rng = np.random.default_rng(1)
    for _ in range(8):
        sched.submit(rng.integers(1, cfg.vocab_size, size=6), 40)
    sched.run_to_completion(max_steps=150)

    assert faults.total_fires > 0
    counts = eng.watchdog.fire_counts()
    assert counts["tick_spike"] >= 1         # the latency burst
    assert counts["preempt_churn"] >= 1      # the preemption storm
    for detector in ("tick_spike", "preempt_churn"):
        dumps = list(tmp_path.glob(f"flight-watchdog-{detector}-*.json"))
        assert dumps, f"no watchdog bundle for {detector}"
        doc = json.loads(dumps[0].read_text())
        assert doc["reason"] == f"watchdog-{detector}"
        ctx = doc["context"]
        assert ctx["detector"] == detector
        assert len(ctx["window"]) > 0        # the tripping metric window
    # watchdog-originated bundles, not only fault-hook-originated ones
    assert not list(tmp_path.glob("flight-watchdog-*.json.tmp"))
    assert list(tmp_path.glob("flight-fault-injected-*.json")) or True


@pytest.mark.chaos
def test_degrade_flap_detector_fires_on_guard_flapping(setup, tmp_path):
    """A NaN fault that keeps coming back while guards heal produces
    degrade/heal oscillation — the flap detector must call it out."""
    cfg, params = setup
    faults = FaultInjector({
        "nan_output": FaultSpec(rate=0.45, start=8, stop=60),
    }, seed=3)
    eng = _mk_engine(
        cfg, params, faults=faults, flight_dir=str(tmp_path),
        guards=GuardConfig(heal_after=1, poison_after=10),
        watchdog=WatchConfig(warmup_ticks=6, flap_threshold=4),
    )
    eng.submit(Request(uid=0, prompt=np.array([3, 1, 4], np.int32),
                       max_new_tokens=60))
    eng.submit(Request(uid=1, prompt=np.array([1, 5, 9], np.int32),
                       max_new_tokens=60))
    for _ in range(70):
        eng.tick()
    counts = eng.watchdog.fire_counts()
    assert counts["degrade_flap"] >= 1
    dumps = list(tmp_path.glob("flight-watchdog-degrade_flap-*.json"))
    assert dumps
    ctx = json.loads(dumps[0].read_text())["context"]
    assert ctx["transitions"] >= 4 and len(ctx["window"]) > 0
