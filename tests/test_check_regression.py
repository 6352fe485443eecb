"""The CI perf gate itself must be trustworthy: it passes on equal
artifacts, trips on an injected >15% regression in any suite, trips on a
silently-missing suite, and tolerates metrics the baseline predates."""
import copy
import json
import subprocess
import sys
from pathlib import Path

from benchmarks.check_regression import METRICS, check

DOC = {
    "decode_step": {"ticks_per_sec_fast": 500.0},
    "scheduler": {"chunked": {"decode_tokens_while_long_prefilling": 15}},
    "prefix": {
        "headline": {
            "decode_speedup_prefix": 1.0,
            "decode_speedup_cascade": 1.4,
        },
        "mixed_depth": {
            "headline": {
                "grouped_passes_per_tick_lcp": 2.0,
                "fused_over_two_call_speedup": 1.2,
            }
        },
    },
    "hardening": {"hardened_over_plain_throughput": 1.0},
    "observability": {"traced_over_untraced_throughput": 1.0},
    "quant": {"capacity_ratio_vs_bf16": 1.9, "token_agreement": 0.97},
    "speculative": {"spec_speedup_k4": 1.35},
}


def test_equal_artifacts_pass():
    rows, failures = check(DOC, DOC)
    assert failures == []
    assert len(rows) == len(METRICS)


def test_injected_regression_fails_every_suite():
    rows, failures = check(DOC, DOC, scale=0.8)
    assert set(failures) == set(METRICS)


def test_single_suite_regression_fails_only_that_suite():
    cur = copy.deepcopy(DOC)
    cur["prefix"]["headline"]["decode_speedup_cascade"] = 1.4 * 0.8
    rows, failures = check(cur, DOC)
    assert failures == ["prefix_cascade"]


def test_within_threshold_drift_passes():
    cur = copy.deepcopy(DOC)
    cur["scheduler"]["chunked"]["decode_tokens_while_long_prefilling"] = (
        15 * 0.9                                            # -10% < 15%
    )
    _rows, failures = check(cur, DOC)
    assert failures == []


def test_missing_suite_in_current_fails():
    cur = copy.deepcopy(DOC)
    del cur["prefix"]["mixed_depth"]
    _rows, failures = check(cur, DOC)
    assert "prefix_mixed_lcp_passes" in failures
    assert "prefix_mixed_fused" in failures


def test_missing_suite_verdict_is_distinct_from_missing_metric():
    """A whole top-level section absent (the bench never ran / silently
    skipped) must read differently from a section that ran but dropped
    the gated metric (a rename broke the contract)."""
    no_suite = copy.deepcopy(DOC)
    del no_suite["hardening"]
    rows, failures = check(no_suite, DOC)
    verdicts = {r[0]: r[4] for r in rows}
    assert "hardening" in failures
    assert verdicts["hardening"] == "FAIL (missing suite)"

    no_metric = copy.deepcopy(DOC)
    del no_metric["hardening"]["hardened_over_plain_throughput"]
    rows, failures = check(no_metric, DOC)
    verdicts = {r[0]: r[4] for r in rows}
    assert "hardening" in failures
    assert verdicts["hardening"] == "FAIL (metric missing)"


def test_hardening_gated_at_tight_threshold():
    """The hardened-vs-plain ratio has its own 3% contract: a 5% overhead
    must trip the gate even though it is far inside the default 15% noise
    bar (and a 1% wobble must not)."""
    cur = copy.deepcopy(DOC)
    cur["hardening"]["hardened_over_plain_throughput"] = 0.95
    _rows, failures = check(cur, DOC)
    assert failures == ["hardening"]
    cur["hardening"]["hardened_over_plain_throughput"] = 0.99
    _rows, failures = check(cur, DOC)
    assert failures == []


def test_speculative_floor_is_absolute():
    """spec_speedup_k4 has a hard floor at 1.0: speculative decode slower
    than plain decode must trip the gate even when the drop vs baseline
    is inside the 15% relative noise bar."""
    cur = copy.deepcopy(DOC)
    base = copy.deepcopy(DOC)
    cur["speculative"]["spec_speedup_k4"] = 0.95
    base["speculative"]["spec_speedup_k4"] = 0.96   # -1% relative: fine
    rows, failures = check(cur, base)
    assert failures == ["speculative"]
    verdicts = {r[0]: r[4] for r in rows}
    assert verdicts["speculative"].startswith("FAIL (below floor")


def test_speculative_floor_checked_without_baseline():
    """A baseline that predates the speculative suite skips the relative
    gate but the absolute floor still applies."""
    base = copy.deepcopy(DOC)
    del base["speculative"]
    _rows, failures = check(DOC, base)
    assert failures == []
    slow = copy.deepcopy(DOC)
    slow["speculative"]["spec_speedup_k4"] = 0.5
    _rows, failures = check(slow, base)
    assert failures == ["speculative"]


def test_metric_missing_from_baseline_is_skipped():
    base = copy.deepcopy(DOC)
    del base["prefix"]["mixed_depth"]
    rows, failures = check(DOC, base)
    assert failures == []
    verdicts = {r[0]: r[4] for r in rows}
    assert verdicts["prefix_mixed_fused"].startswith("skip")


def test_cli_inject_regression_exits_nonzero(tmp_path: Path):
    """End-to-end gate self-test: the exact CI invocation with
    --inject-regression 0.8 must exit 1 against a baseline of itself."""
    cur = tmp_path / "cur.json"
    base = tmp_path / "base.json"
    cur.write_text(json.dumps(DOC))
    base.write_text(json.dumps(DOC))
    repo = Path(__file__).resolve().parent.parent
    ok = subprocess.run(
        [sys.executable, "-m", "benchmarks.check_regression",
         "--current", str(cur), "--baseline", str(base)],
        cwd=repo, capture_output=True, text=True,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = subprocess.run(
        [sys.executable, "-m", "benchmarks.check_regression",
         "--current", str(cur), "--baseline", str(base),
         "--inject-regression", "0.8"],
        cwd=repo, capture_output=True, text=True,
    )
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "FAIL" in bad.stdout


# ---------------------------------------------------------------------------
# absolute-trajectory gate (BENCH_history.jsonl, like-fingerprint only)
# ---------------------------------------------------------------------------

from benchmarks.check_regression import (          # noqa: E402
    check_trajectory, update_baseline,
)

FP = {"device": "cpu", "platform": "cpu", "jax": "0.4.37",
      "git_sha": "abc1234"}
OTHER_FP = {"device": "TPU v5e", "platform": "tpu", "jax": "0.4.37",
            "git_sha": "abc1234"}


def _hist(vals, fp=FP, run_prefix="old"):
    return [
        {"format": 1, "run_id": f"{run_prefix}{i}", "fingerprint": fp,
         "metrics": {"ticks_per_sec_fast": v}}
        for i, v in enumerate(vals)
    ]


def _cur(tps=100.0, fp=FP, run_id="me"):
    return {
        "decode_step": {"ticks_per_sec_fast": tps},
        "config": {"fingerprint": fp, "run_id": run_id},
    }


def test_trajectory_passes_at_parity():
    _rows, failures = check_trajectory(_cur(100.0), _hist([99.0, 101.0, 100.0]))
    assert failures == []


def test_trajectory_fails_on_absolute_slowdown():
    rows, failures = check_trajectory(_cur(80.0), _hist([100.0, 100.0, 100.0]))
    assert failures == ["ticks_per_sec_fast"]
    assert rows[0][4] == "FAIL (regression)"


def test_trajectory_inject_regression_knob_trips():
    _rows, failures = check_trajectory(
        _cur(100.0), _hist([100.0] * 3), scale=0.8
    )
    assert failures == ["ticks_per_sec_fast"]


def test_trajectory_ignores_other_fingerprints():
    """TPU history must never gate a CPU run: a 'slowdown' vs numbers
    from different hardware is a fingerprint mismatch, not a regression."""
    rows, failures = check_trajectory(
        _cur(80.0), _hist([1000.0] * 5, fp=OTHER_FP)
    )
    assert failures == []
    assert rows[0][4] == "skip (no like-fingerprint history)"


def test_trajectory_excludes_own_run_record():
    """The bench appends its own record before the gate runs; comparing a
    run against itself would always pass, masking regressions."""
    history = _hist([100.0] * 3) + [
        {"format": 1, "run_id": "me", "fingerprint": FP,
         "metrics": {"ticks_per_sec_fast": 80.0}},
    ]
    _rows, failures = check_trajectory(_cur(80.0, run_id="me"), history)
    assert failures == ["ticks_per_sec_fast"]


def test_trajectory_median_window_resists_outliers():
    """One lucky fast record inside the window must not ratchet the bar:
    the median of the last `window` records is the comparison point."""
    history = _hist([100.0, 100.0, 100.0, 100.0, 500.0])
    _rows, failures = check_trajectory(_cur(95.0), history, window=5)
    assert failures == []


def test_trajectory_skips_without_fingerprint():
    rows, failures = check_trajectory(
        {"decode_step": {"ticks_per_sec_fast": 1.0}}, _hist([100.0])
    )
    assert failures == []
    assert rows[0][4] == "skip (no fingerprint in artifact)"


def test_trajectory_skips_with_empty_history():
    rows, failures = check_trajectory(_cur(100.0), [])
    assert failures == []
    assert rows[0][4].startswith("skip")


def test_trajectory_metric_missing_fails_when_history_exists():
    cur = _cur(100.0)
    del cur["decode_step"]["ticks_per_sec_fast"]
    _rows, failures = check_trajectory(cur, _hist([100.0] * 3))
    assert failures == ["ticks_per_sec_fast"]


def test_update_baseline_clamps_parity_ratios(tmp_path: Path):
    """--update-baseline caps the hardening/observability parity ratios
    at 1.0 (a lucky faster-than-plain draw must not ratchet the bar) and
    leaves every other metric untouched."""
    cur = copy.deepcopy(DOC)
    cur["hardening"]["hardened_over_plain_throughput"] = 1.07
    cur["observability"]["traced_over_untraced_throughput"] = 0.99
    out = tmp_path / "base.json"
    clamped = update_baseline(cur, out)
    assert clamped == ["hardening"]
    doc = json.loads(out.read_text())
    assert doc["hardening"]["hardened_over_plain_throughput"] == 1.0
    assert doc["observability"]["traced_over_untraced_throughput"] == 0.99
    assert doc["quant"]["capacity_ratio_vs_bf16"] == 1.9
    # the regenerated baseline gates cleanly against the artifact it
    # came from
    _rows, failures = check(cur, doc)
    assert failures == []


def test_cli_update_baseline_and_trajectory_end_to_end(tmp_path: Path):
    """Full CLI loop: --update-baseline writes a gateable baseline, the
    trajectory gate passes at parity with like-fingerprint history and
    exits 1 under --inject-regression."""
    cur = tmp_path / "cur.json"
    base = tmp_path / "base.json"
    hist = tmp_path / "hist.jsonl"
    doc = copy.deepcopy(DOC)
    doc["decode_step"]["ticks_per_sec_fast"] = 100.0
    doc["config"] = {"fingerprint": FP, "run_id": "me"}
    cur.write_text(json.dumps(doc))
    hist.write_text(
        "\n".join(json.dumps(r) for r in _hist([100.0, 101.0, 99.0])) + "\n"
    )
    repo = Path(__file__).resolve().parent.parent
    argv = [sys.executable, "-m", "benchmarks.check_regression",
            "--current", str(cur), "--baseline", str(base),
            "--history", str(hist)]
    upd = subprocess.run(
        argv + ["--update-baseline"], cwd=repo,
        capture_output=True, text=True,
    )
    assert upd.returncode == 0, upd.stdout + upd.stderr
    assert base.exists()
    ok = subprocess.run(argv, cwd=repo, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "trajectory" in ok.stdout
    bad = subprocess.run(
        argv + ["--inject-regression", "0.8"], cwd=repo,
        capture_output=True, text=True,
    )
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "trajectory:ticks_per_sec_fast" in bad.stdout
