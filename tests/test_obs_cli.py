"""`python -m repro.obs` CLI coverage: golden-render a saved trace and a
flight bundle, assert exit codes, and check the PR-9 watchdog/budget
sections appear in the report output."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.obs import SLOConfig, Tracer, WatchConfig
from repro.obs.__main__ import main
from repro.obs.watch import PerfWatchdog
from repro.serving.config import EngineConfig, ObsConfig, PagedConfig
from repro.serving.engine import DecodeEngine
from repro.serving.scheduler import Scheduler, SchedulerConfig

jax.config.update("jax_platform_name", "cpu")

REPO_FLIGHT_SAMPLE = Path(__file__).resolve().parent.parent \
    / "FLIGHT_sample.json"


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """One traced + watched scheduler run, saved as trace JSON and a
    watchdog-armed flight bundle."""
    tmp = tmp_path_factory.mktemp("obs_cli")
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tracer = Tracer()
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=4, cache_len=32, attn_backend="lean", num_workers=4,
        paged=PagedConfig(page_size=8),
        obs=ObsConfig(tracer=tracer, flight_dir=str(tmp)),
    ))
    # near-zero targets guarantee breaches -> non-empty budget table,
    # and a guaranteed slo_burn firing -> a watchdog-armed dump
    wd = PerfWatchdog(
        eng, WatchConfig(warmup_ticks=4, slo_min_events=4),
        slos=[SLOConfig(name="interactive", ttft_target_s=1e-9,
                        tpot_target_s=1e-9, budget=0.5)],
    )
    sched = Scheduler(eng, SchedulerConfig())
    rng = np.random.default_rng(0)
    for _ in range(4):
        sched.submit(rng.integers(1, cfg.vocab_size, size=5), 10,
                     slo_class="interactive")
    sched.run_to_completion(max_steps=80)

    trace_path = tmp / "trace.json"
    tracer.save(trace_path, extra={
        "metrics": eng.metrics.as_dict(),
        "watchdog": wd.as_dict(),
        "platform": "cpu-interpret",
    })
    dumps = sorted(tmp.glob("flight-watchdog-*.json"))
    assert dumps, "expected a watchdog-armed bundle from the slo burn"
    return {"tmp": tmp, "trace": trace_path, "watchdog_dump": dumps[0]}


def test_report_renders_all_sections(artifacts, capsys):
    assert main(["report", str(artifacts["trace"])]) == 0
    out = capsys.readouterr().out
    assert "== per-tick attribution" in out
    assert "== per-request timelines" in out
    assert "== cache & cascade effectiveness" in out
    # the PR-9 sections
    assert "== watchdog detector timeline ==" in out
    assert "== SLO error budgets ==" in out
    assert "slo_burn" in out
    assert "interactive" in out


def test_report_limit_elides_ticks(artifacts, capsys):
    assert main(["report", str(artifacts["trace"]), "--limit", "2"]) == 0
    assert "earlier ticks elided" in capsys.readouterr().out


def test_report_without_watchdog_meta_still_prints_sections(
        tmp_path, capsys):
    """Old traces (no meta.watchdog) must keep rendering — the new
    sections degrade to placeholders, not crashes."""
    t = Tracer()
    with t.span("tick"):
        pass
    p = tmp_path / "bare.json"
    t.save(p)
    assert main(["report", str(p)]) == 0
    out = capsys.readouterr().out
    assert "(no watchdog snapshot embedded in trace)" in out
    assert "(no SLO classes declared)" in out


def test_flight_renders_watchdog_bundle(artifacts, capsys):
    assert main(["flight", str(artifacts["watchdog_dump"])]) == 0
    out = capsys.readouterr().out
    assert "watchdog-armed postmortem" in out
    assert "detector" in out


def test_flight_renders_committed_sample(capsys):
    """The repo-root FLIGHT_sample.json (produced by the bench) stays
    renderable."""
    if not REPO_FLIGHT_SAMPLE.exists():
        pytest.skip("no committed FLIGHT_sample.json")
    assert main(["flight", str(REPO_FLIGHT_SAMPLE), "--tail", "5"]) == 0
    assert "flight dump: reason=" in capsys.readouterr().out


def test_calibrate_fits_and_report_consumes(artifacts, tmp_path, capsys):
    calib_path = tmp_path / "calib.json"
    assert main(["calibrate", str(artifacts["trace"]),
                 "--out", str(calib_path)]) == 0
    out = capsys.readouterr().out
    assert "factor" in out and calib_path.exists()
    doc = json.loads(calib_path.read_text())
    assert doc["format"] == 1 and doc["factors"]

    assert main(["report", str(artifacts["trace"]),
                 "--calib", str(calib_path)]) == 0
    out = capsys.readouterr().out
    assert "CALIBRATED" in out
    assert "matches the calibrated expectation" in out


def test_missing_file_exits_2(tmp_path, capsys):
    assert main(["report", str(tmp_path / "nope.json")]) == 2
    assert main(["flight", str(tmp_path / "nope.json")]) == 2
    assert main(["calibrate", str(tmp_path / "nope.json")]) == 2
    err = capsys.readouterr().err
    assert "error:" in err


def test_malformed_trace_exits_2(tmp_path, capsys):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"format": 999}))
    assert main(["report", str(bad)]) == 2
    assert "error:" in capsys.readouterr().err


def test_calibrate_on_prediction_free_trace_exits_2(tmp_path, capsys):
    t = Tracer()
    with t.span("tick"):
        pass
    p = tmp_path / "nopred.json"
    t.save(p)
    assert main(["calibrate", str(p)]) == 2
    assert "tracer" in capsys.readouterr().err
