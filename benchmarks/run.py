"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. "derived" is the figure's metric
(speedup ratio, occupancy, timeshare, or error vs oracle, per row name).
The ``decode_step`` suite also appends an environment-fingerprinted
absolute-throughput record to the trajectory store (``--history``, see
:mod:`benchmarks.trajectory`) that the check_regression absolute gate
compares like-fingerprint runs against.

  python -m benchmarks.run            # all
  python -m benchmarks.run --only fig7,fig10
  python -m benchmarks.run --only decode_step --history ''   # no append
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument(
        "--history", default="BENCH_history.jsonl",
        help="perf-trajectory store the decode_step suite appends its "
             "fingerprinted absolute-throughput record to ('' disables)",
    )
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    only = [s for s in args.only.split(",") if s]
    history = args.history or None

    rows: list = []
    from . import (
        attention_bench, decode_step_bench, e2e_bench, prefix_bench,
        ragged_bench,
    )

    suites = {
        "decode_step": lambda: decode_step_bench.run(
            rows, history_path=history),
        "prefix": lambda: prefix_bench.run(rows),
        "fig7": lambda: attention_bench.fig7_context_sweep(rows),
        "fig7b": lambda: attention_bench.fig7b_heads_sweep(rows),
        "fig7c": lambda: attention_bench.fig7c_batch_sweep(rows),
        "fig8": lambda: attention_bench.fig8_h100(rows),
        "fig9": lambda: attention_bench.fig9_multi_gpu(rows),
        "claims": lambda: attention_bench.paper_claim_grid(rows),
        "cpu": lambda: attention_bench.cpu_wallclock_sanity(rows),
        "fig10": lambda: ragged_bench.run(rows),
        "fig12": lambda: e2e_bench.run(rows),
        "engine": lambda: e2e_bench.run_real_engine(rows),
    }
    for name, fn in suites.items():
        if only and not any(name.startswith(o) or o.startswith(name)
                            for o in only):
            continue
        fn()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived:.6g}")


if __name__ == "__main__":
    main()
