"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics with the profiler off;
``--trace 1`` measures its per-layer metrics from a profiler trace of the
window. Progress and the numbers compared by the correctness check go to
standard error; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and ``check`` last. Exits non-zero, with no
result line, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness.device import NoChip
    from bench.harness.serve import run
    from bench.harness.spec import load_cell

    cell = load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t_process=T_PROCESS, log=log)
    except NoChip as e:
        log(str(e))
        return 1
    for name, n in result["check"].items():
        log(f"check {name}: {n['value']} (limit {n['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
