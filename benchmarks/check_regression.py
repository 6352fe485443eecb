"""CI perf-regression gate over ``BENCH_decode_step.json``.

Compares the freshly-produced benchmark artifact against the committed
``BENCH_baseline.json`` and fails (exit 1) when any gated metric regresses
by more than ``--threshold`` (default 15%).

Gated metrics are *intra-run ratios and counts* — cascade-vs-baseline
decode speedup, tokens-decoded-while-prefilling, hardened-vs-plain and
traced-vs-untraced throughput — rather than absolute wall-clock
numbers, because shared CI runners make absolute timings jitter far more
than 15% while the within-run ratios stay stable (both sides of a ratio
see the same noisy host). A metric *missing* from the current artifact is
itself a failure, and the gate distinguishes the two ways that happens:
``FAIL (missing suite)`` when the suite's whole top-level section is
absent (the bench section didn't run — e.g. a crashed or silently-skipped
suite), vs ``FAIL (metric missing)`` when the section ran but no longer
reports the gated metric (a rename/refactor broke the contract). A
metric missing from the baseline is skipped with a note (new suites gate
once the baseline is refreshed).

Per-metric thresholds: ``THRESHOLDS`` overrides the CLI threshold for
metrics with a tighter contract — the hardening-overhead ratio (hardened
engine vs plain, both fault-free) is gated at 3%, the "zero overhead when
disabled" acceptance bar, not the 15% noise bar. ``FLOORS`` adds absolute
hard floors checked before the relative gate: the speculative speedup at
k=4 must stay >= 1.0x regardless of what the baseline recorded — below
parity the feature costs more than it amortizes.

**Absolute-trajectory gate**: the ratio gates above are blind to the
whole stack slowing down together, so the gate also compares the current
run's *absolute* ``ticks_per_sec_fast`` against the trajectory store
(``BENCH_history.jsonl``, see :mod:`benchmarks.trajectory`) — but only
against records whose environment fingerprint (device kind, jax
platform, jax version) matches the current artifact's, so CPU-interpret
and TPU numbers never cross-contaminate. The current run's own record
(matched by ``run_id``) is excluded, the comparison point is the median
of the last ``--trajectory-window`` like-fingerprint records, and a drop
beyond ``--threshold`` fails. No matching history → the trajectory gate
skips (first run on new hardware establishes the trajectory instead of
failing it).

``--inject-regression F`` scales every current metric by ``F`` before
comparison — the self-test knob that demonstrates the gate trips (e.g.
``--inject-regression 0.8`` must exit 1 against any baseline of itself).

``--update-baseline`` regenerates ``BENCH_baseline.json`` from the
current artifact with the clamp-to-1.0 rules applied automatically: the
parity-ratio metrics (hardening, observability) are capped at 1.0 so a
lucky faster-than-plain draw can never ratchet the bar above parity.

  PYTHONPATH=src python -m benchmarks.check_regression
  PYTHONPATH=src python -m benchmarks.check_regression --inject-regression 0.8
  PYTHONPATH=src python -m benchmarks.check_regression --update-baseline
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

from benchmarks.trajectory import fingerprint_key, load_history

# suite -> (json path, higher-is-better metric)
METRICS = {
    "scheduler": (
        "scheduler", "chunked", "decode_tokens_while_long_prefilling",
    ),
    "prefix_aliased": ("prefix", "headline", "decode_speedup_prefix"),
    "prefix_cascade": ("prefix", "headline", "decode_speedup_cascade"),
    "prefix_mixed_lcp_passes": (
        "prefix", "mixed_depth", "headline", "grouped_passes_per_tick_lcp",
    ),
    "prefix_mixed_fused": (
        "prefix", "mixed_depth", "headline", "fused_over_two_call_speedup",
    ),
    "hardening": ("hardening", "hardened_over_plain_throughput"),
    "observability": ("observability", "traced_over_untraced_throughput"),
    "quant_capacity": ("quant", "capacity_ratio_vs_bf16"),
    "quant_agreement": ("quant", "token_agreement"),
    "speculative": ("speculative", "spec_speedup_k4"),
}

# absolute hard floors, checked before the relative gate: some metrics
# carry a meaningful zero point that no amount of baseline drift may
# cross — speculative decode below 1.0x means verify sweeps cost more
# than the tokens they amortize, i.e. the feature actively hurts
FLOORS = {
    "speculative": 1.0,
}

# per-metric regression thresholds overriding the CLI default: the
# fault-flags-disabled overhead of the hardened engine is an acceptance
# contract (< 3%), not a noise bar
THRESHOLDS = {
    "hardening": 0.03,
    # same contract for tracing: an *enabled* tracer must cost < 3%
    # (disabled tracing is structurally free — a shared no-op span)
    "observability": 0.03,
    # layout math, not wall-clock: any drop means the dtype accounting
    # (page_bytes / scale sidecar) regressed, so gate it tight
    "quant_capacity": 0.01,
    # greedy decode on fixed seeds is deterministic on the CI host; a
    # real numerics regression moves agreement far more than 5%
    "quant_agreement": 0.05,
}


def _lookup(doc: dict, path):
    cur = doc
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    return float(cur) if isinstance(cur, (int, float)) else None


def check(current: dict, baseline: dict, threshold: float = 0.15,
          scale: float = 1.0):
    """Returns (rows, failures): one row per gated metric with the
    comparison verdict. ``scale`` multiplies the current value (the
    regression-injection knob)."""
    rows, failures = [], []
    for suite, path in METRICS.items():
        base = _lookup(baseline, path)
        cur = _lookup(current, path)
        if cur is not None:
            cur *= scale
        floor = FLOORS.get(suite)
        if floor is not None and cur is not None and cur < floor:
            rows.append((suite, base, cur, None,
                         f"FAIL (below floor {floor:g})"))
            failures.append(suite)
            continue
        if base is None:
            verdict = ("ok (floor only, no baseline)"
                       if floor is not None and cur is not None
                       else "skip (no baseline)")
            rows.append((suite, base, cur, None, verdict))
            continue
        if cur is None:
            # distinguish "the whole bench section never ran" from "the
            # section ran but the gated metric is gone"
            if path[0] not in current:
                verdict = "FAIL (missing suite)"
            else:
                verdict = "FAIL (metric missing)"
            rows.append((suite, base, cur, None, verdict))
            failures.append(suite)
            continue
        thr = THRESHOLDS.get(suite, threshold)
        ratio = cur / base if base else float("inf")
        if base > 0 and ratio < 1.0 - thr:
            rows.append((suite, base, cur, ratio, "FAIL (regression)"))
            failures.append(suite)
        else:
            rows.append((suite, base, cur, ratio, "ok"))
    return rows, failures


# absolute metrics gated against like-fingerprint trajectory history:
# metric name in the history record -> json path in the current artifact
TRAJECTORY_METRICS = {
    "ticks_per_sec_fast": ("decode_step", "ticks_per_sec_fast"),
}


def check_trajectory(current: dict, history: list, threshold: float = 0.15,
                     window: int = 5, scale: float = 1.0):
    """Absolute-trajectory gate: (rows, failures) like :func:`check`.

    Compares the current artifact's absolute numbers against the median
    of the last ``window`` history records with a *matching environment
    fingerprint*, excluding the current run's own record (it appends
    itself before the gate runs). No fingerprint / no comparable history
    → skip verdicts, never failures."""
    cfg = current.get("config") or {}
    fp = cfg.get("fingerprint")
    run_id = cfg.get("run_id")
    rows, failures = [], []
    if fp is None:
        for name in TRAJECTORY_METRICS:
            rows.append((name, None, None, None,
                         "skip (no fingerprint in artifact)"))
        return rows, failures
    key = fingerprint_key(fp)
    comparable = [
        r for r in history
        if fingerprint_key(r.get("fingerprint") or {}) == key
        and r.get("run_id") != run_id
    ]
    for name, path in TRAJECTORY_METRICS.items():
        cur = _lookup(current, path)
        if cur is not None:
            cur *= scale
        vals = [
            float(r["metrics"][name]) for r in comparable[-window:]
            if isinstance(r["metrics"].get(name), (int, float))
        ]
        if not vals:
            rows.append((name, None, cur,
                         None, "skip (no like-fingerprint history)"))
            continue
        base = statistics.median(vals)
        if cur is None:
            rows.append((name, base, None, None, "FAIL (metric missing)"))
            failures.append(name)
            continue
        ratio = cur / base if base else float("inf")
        if base > 0 and ratio < 1.0 - threshold:
            rows.append((name, base, cur, ratio, "FAIL (regression)"))
            failures.append(name)
        else:
            rows.append((name, base, cur, ratio, "ok"))
    return rows, failures


# suites whose gated metric is a parity ratio (hardened/plain,
# traced/untraced): clamp to 1.0 when refreshing the baseline so a lucky
# faster-than-parity draw never ratchets the bar above "no overhead"
CLAMP_SUITES = ("hardening", "observability")


def update_baseline(current: dict, out_path) -> list:
    """Regenerate the committed baseline from a bench artifact, applying
    the clamp-to-1.0 rules automatically. Returns the clamped suites.

    Top-level sections present in the existing baseline but absent from
    the current artifact are preserved — different bench entry points
    own different sections (``prefix_bench`` vs ``decode_step_bench``),
    and refreshing from one must not silently un-gate the other's
    metrics."""
    doc = json.loads(json.dumps(current))      # deep copy, JSON-clean
    out = Path(out_path)
    if out.exists():
        existing = json.loads(out.read_text())
        for section, val in existing.items():
            doc.setdefault(section, val)
    clamped = []
    for suite in CLAMP_SUITES:
        path = METRICS[suite]
        cur = doc
        for key in path[:-1]:
            if not isinstance(cur, dict) or key not in cur:
                cur = None
                break
            cur = cur[key]
        leaf = path[-1]
        if isinstance(cur, dict) and isinstance(cur.get(leaf), (int, float)):
            if cur[leaf] > 1.0:
                cur[leaf] = 1.0
                clamped.append(suite)
    Path(out_path).write_text(json.dumps(doc, indent=1))
    return clamped


def _print_rows(rows, names, header):
    w = max(len(s) for s in names)
    print(f"{header:<{w}}  {'baseline':>10}  {'current':>10}  "
          f"{'ratio':>7}  verdict")
    for suite, base, cur, ratio, verdict in rows:
        fb = f"{base:.4g}" if base is not None else "-"
        fc = f"{cur:.4g}" if cur is not None else "-"
        fr = f"{ratio:.3f}" if ratio is not None else "-"
        print(f"{suite:<{w}}  {fb:>10}  {fc:>10}  {fr:>7}  {verdict}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--current", default="BENCH_decode_step.json")
    ap.add_argument("--baseline", default="BENCH_baseline.json")
    ap.add_argument("--threshold", type=float, default=0.15)
    ap.add_argument(
        "--inject-regression", type=float, default=1.0,
        help="scale current metrics by this factor (gate self-test)",
    )
    ap.add_argument(
        "--history", default="BENCH_history.jsonl",
        help="trajectory store for the absolute gate ('' disables)",
    )
    ap.add_argument(
        "--trajectory-window", type=int, default=5,
        help="like-fingerprint records the trajectory median is over",
    )
    ap.add_argument(
        "--update-baseline", action="store_true",
        help="regenerate --baseline from --current (clamp rules applied) "
             "instead of gating",
    )
    args = ap.parse_args()

    cur_path, base_path = Path(args.current), Path(args.baseline)
    if not cur_path.exists():
        print(f"FAIL: current artifact {cur_path} not found — did the "
              "benchmarks run?")
        return 1
    current = json.loads(cur_path.read_text())

    if args.update_baseline:
        clamped = update_baseline(current, base_path)
        note = (
            f" (clamped to 1.0: {', '.join(clamped)})" if clamped else ""
        )
        print(f"regenerated {base_path} from {cur_path}{note}")
        return 0

    if not base_path.exists():
        print(f"FAIL: committed baseline {base_path} not found")
        return 1
    baseline = json.loads(base_path.read_text())
    rows, failures = check(
        current, baseline, args.threshold, args.inject_regression
    )
    _print_rows(rows, METRICS, "suite")

    if args.history:
        history = load_history(args.history)
        t_rows, t_failures = check_trajectory(
            current, history, args.threshold,
            args.trajectory_window, args.inject_regression,
        )
        print()
        _print_rows(t_rows, TRAJECTORY_METRICS, "trajectory")
        failures += [f"trajectory:{n}" for n in t_failures]

    if failures:
        print(f"\nperf gate FAILED (> {args.threshold:.0%} regression): "
              + ", ".join(failures))
        return 1
    print(f"\nperf gate passed (threshold {args.threshold:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
