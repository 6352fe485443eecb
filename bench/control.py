"""The correctness check's control, on the chip at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10
    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10 --kv int8

For each seed, one run of the cell as ``bench/run.py`` makes it, then the
same reference computed in float8 (e4m3, one scale per tensor) read at every
position of the sessions the check compares: the token it puts first, read
under the float32 reference. The check judges the control by the same
limits as the program. Prints one JSON line per seed with both readings and
both verdicts; the control's has to be false.

With ``--kv int8`` the program runs with its own lower-precision path, the
paged pool stored in int8; its readings show what the gaps alone would let
through, and its verdict has to be false as well. The benchmark's own runs
never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# the step below the configurations' bf16: float8 e4m3
CONTROL = "fp8"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--kv", choices=("int8",), default=None)
    args = ap.parse_args(argv)

    from bench.harness.serve import run
    from bench.harness.spec import load_cell

    cell = load_cell(args.workload)
    if args.kv is not None:
        cell = dataclasses.replace(cell, params={**cell.params, "kv_dtype": args.kv})
    t_start = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run(cell, seed, args.seconds, False, t_process=t_start,
                  log=lambda m: print(m, file=sys.stderr, flush=True),
                  control=CONTROL)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": "bf16 KV" if args.kv is None else f"{args.kv} KV",
            "correct": res["correct"], "check": res["check"],
            "control": CONTROL, "control_correct": res["control_correct"],
            "control_check": res["control_check"],
            "readings": res["readings"], "metrics": res["metrics"],
        }), flush=True)
        gc.collect()
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
