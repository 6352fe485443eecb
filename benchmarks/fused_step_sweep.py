"""Time the fused paged decode kernel alone at the benchmark cells' decode
shapes, for several schedule iterations per grid step.

For each decode cell of ``BENCHMARK.json`` this builds the decode tick's schedule
as the engine would (``ScheduleCache`` over the mix's ``shape_seed``
lengths, ``--advance`` tokens into the window, 128-token pages, 16
workers), a bf16 page pool of the cell's size with a shuffled page table,
and times ``lean_decode_paged_fused`` back to back for each ``n``. One JSON
line per (cell, n): ms per call, us per grid step and per schedule
iteration, and the largest difference from the first ``n``'s output.

TPU only (device time from the host clock around ``--reps`` queued calls)::

    python -m benchmarks.fused_step_sweep --n 1,2,4,8,16
"""
from __future__ import annotations

import argparse
import inspect
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.traffic import draw_lengths
from repro.core.leantile import ScheduleCache
from repro.kernels import ops
from repro.kernels.lean_decode import lean_decode_paged_fused

ROOT = Path(__file__).resolve().parents[1]
PAGE, D, WORKERS = 128, 128, 16
CELLS = {  # cell: (mix, q heads, kv heads)
    "yi34b-decode-fewlong": ("decode-fewlong-4", 56, 8),
    "nemo12b-decode-long": ("decode-long-16-w50", 32, 8),
}


def _problem(cell: str, advance: int, seed: int):
    mix_name, hq, hkv = CELLS[cell]
    mix = json.loads(
        (ROOT / "bench" / "traffic" / f"{mix_name}.json").read_text())
    prompts = draw_lengths(mix["context"], int(mix["sessions"]),
                           np.random.default_rng(mix["shape_seed"]))
    need = [L + int(mix["max_new"]) + 2 for L in prompts]
    cache_len = -(-max(need) // PAGE) * PAGE
    num_pages = 1 + sum(-(-n // PAGE) for n in need)
    lens = [L + advance for L in prompts]
    sched = ScheduleCache().get(lens, hkv, PAGE, WORKERS, max_len=cache_len)

    rng = np.random.default_rng(seed)
    order = list(rng.permutation(np.arange(1, num_pages)))
    ptbl = np.zeros((len(lens), cache_len // PAGE), np.int32)
    for b, L in enumerate(lens):
        k = -(-L // PAGE)
        ptbl[b, :k] = [order.pop() for _ in range(k)]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = (num_pages * hkv, PAGE, D)
    k_rows = jax.random.normal(keys[0], pool, jnp.bfloat16)
    v_rows = jax.random.normal(keys[1], pool, jnp.bfloat16)
    q_seg = jax.random.normal(keys[2], (len(lens) * hkv, hq // hkv, D),
                              jnp.bfloat16)
    ctx = jnp.asarray(np.repeat(lens, hkv), jnp.int32)
    return sched, jnp.asarray(ptbl), hkv, q_seg, k_rows, v_rows, ctx


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", default="1,2,4,8,16")
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--advance", type=int, default=300)
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=2147483700)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU found: this sweep times the chip only")
    grouped = "iters_per_step" in inspect.signature(
        lean_decode_paged_fused).parameters
    for cell in args.cells.split(","):
        sched, ptbl, hkv, q_seg, k_rows, v_rows, ctx = _problem(
            cell, args.advance, args.seed)
        iters = sched.grid_iters + sched.num_pieces
        first = None
        for n in [int(x) for x in args.n.split(",")] if grouped else [1]:
            kw = {"iters_per_step": n} if grouped else {}
            route = ops._paged_route(
                sched, ptbl, hkv, True, 0, *([n] if grouped else []))
            fn = jax.jit(lambda q, k, v, c, r, kw=kw: lean_decode_paged_fused(
                q, k, v, c, r, sched, 1.0 / np.sqrt(D), **kw))
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(q_seg, k_rows, v_rows, ctx, route))
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(args.reps):
                last = fn(q_seg, k_rows, v_rows, ctx, route)
            jax.block_until_ready(last)
            ms = (time.perf_counter() - t0) / args.reps * 1e3
            o = np.asarray(out[0])
            first = o if first is None else first
            steps = -(-iters // n)
            print(json.dumps({
                "cell": cell, "grouped": grouped, "n": n, "ms_per_call": ms,
                "grid_steps": steps, "iters": iters,
                "us_per_step": ms * 1e3 / steps,
                "us_per_iter": ms * 1e3 / iters,
                "kv_bytes": int(np.asarray(ctx).sum()) * D * 2 * 2,
                "max_abs_diff_vs_first": float(np.abs(o - first).max()),
                "compile_s": compile_s,
            }), flush=True)


if __name__ == "__main__":
    main()
