"""Prefix-sharing benchmark: shared-system-prompt serving workload.

The fleet-shaped scenario the radix cache exists for: ``batch`` requests
that all start with one long shared prefix (a system prompt / few-shot
template) and differ only in a short private tail. Three engines run the
identical request stream:

  * ``baseline``   — paged lean engine, no sharing (every request
                     prefills and stores its own prefix copy);
  * ``prefix``     — radix cache on: matched prefixes skip prefill and
                     alias the cached pages (unshared schedule, bit-
                     identical decode);
  * ``cascade``    — radix cache + cascade decode: one grouped stream-K
                     pass over the shared prefix pages per tick, fused
                     with the suffix pass and the merge into a single
                     kernel.

A second, ``mixed_depth`` scenario stresses cascade v2's LCP grouping:
requests matching 1, 3, and 5 pages of ONE cached chain. The v1
identical-run grouping finds nothing to group there; LCP grouping forms
the trie passes. Reported: grouped-pass count, retrace count, and the
fused-vs-two-call tick speedup.

Reported per mode: decode ticks/sec and tokens/sec at steady state, mean
TTFT, KV pages in use, prefill tokens actually computed, and the radix
cache counters (hit rate, matched tokens, bytes saved). The section merges
into ``BENCH_decode_step.json`` next to the other serving benchmarks so
the perf trajectory stays one artifact per PR.

  PYTHONPATH=src python -m benchmarks.prefix_bench --ticks 12
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

PREFIX_PAGES = 8
PAGE = 16
TAIL = 16          # private tail length: keeps the whole measured window
                   # inside one suffix bucket (no mid-measurement retraces)
CHAIN_PAGES = 5    # mixed-depth scenario: one cached chain of 5 pages


def _build(cfg, params, *, prefix_cache, cascade, **cascade_kw):
    from repro.serving.config import (
        CascadeConfig,
        EngineConfig,
        PagedConfig,
    )
    from repro.serving.engine import DecodeEngine
    from repro.serving.scheduler import Scheduler, SchedulerConfig

    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=8, cache_len=192, attn_backend="lean", num_workers=8,
        paged=PagedConfig(page_size=PAGE, prefix_cache=prefix_cache),
        cascade=CascadeConfig(enabled=cascade, stable_ticks=1, **cascade_kw),
    ))
    sched = Scheduler(eng, SchedulerConfig(
        chunk_size=32, prefill_pack=4, token_budget=256,
    ))
    return eng, sched


def _bucket_headroom(eng, cascade: bool) -> int:
    """Decode ticks until some active slot's schedule bucket changes.

    A bucket crossing re-keys the (cascade) schedule signature and costs
    one XLA retrace — microseconds of schedule work on hardware, ~seconds
    under CPU interpret — so the measured window must stay inside one
    bucket on every slot to report kernel throughput, not compile time.
    The cascade path buckets each slot's *suffix* (ctx minus its shared
    full pages), the plain paths the whole context.
    """
    from repro.core.leantile import bucket_length

    # the engine buckets each slot's suffix by its *kept-pass* coverage
    # (seq_prefix_len of the tick's binding), which can be shorter than
    # the slot's full shared run — e.g. a 5-page match whose deeper trie
    # level collapsed to a singleton groups (and shifts) at 3 pages
    bind = eng._casc_binding if cascade else None
    left = []
    for s in range(eng.max_batch):
        if eng.slot_req[s] is None:
            continue
        plen = int(bind.seq_prefix_len[s]) if bind is not None else 0
        n = int(eng.ctx_lens[s]) + 1 - plen
        left.append(bucket_length(n, eng.tile) - n)
    return min(left, default=1 << 30)


def _measure_decode(eng, n_ticks: int, cascade: bool):
    """Warm past bucket crossings + trace, then time ``n_ticks`` decode
    ticks; returns the sorted per-tick wall times."""
    guard = 0
    while _bucket_headroom(eng, cascade) < n_ticks + 2 and guard < 64:
        eng.decode_tick()
        guard += 1
    for _ in range(2):
        eng.decode_tick()
    ticks = []
    for _ in range(n_ticks):
        t0 = time.perf_counter()
        eng.decode_tick()
        ticks.append(time.perf_counter() - t0)
    ticks.sort()
    return ticks


def _run_mode(cfg, params, prompts, shared, *, prefix_cache, cascade,
              n_ticks):
    import numpy as np

    eng, sched = _build(cfg, params, prefix_cache=prefix_cache,
                        cascade=cascade)
    if prefix_cache:
        # seed the radix cache with one donor request (the "first user" —
        # its prefill is the one copy of the shared prompt anyone pays for)
        donor = sched.submit(np.concatenate([shared, [1]]), 1)  # noqa: F841
        sched.run_to_completion(max_steps=100)
    handles = [sched.submit(p, max_new_tokens=10_000) for p in prompts]
    while any(h.state.value != "decoding" for h in handles):
        sched.step()
    ttfts = [h.first_token_time - h.arrival_time for h in handles]
    pages_in_use = eng.pool.num_allocated
    ticks = _measure_decode(eng, n_ticks, cascade)
    # best-observed per-tick: the classic noise-robust estimator — host
    # load spikes and allocator hiccups only ever ADD time
    dt = ticks[0]
    eng.pool.check()
    if eng.prefix_cache is not None:
        eng.prefix_cache.check()
    return {
        "ticks_per_sec": 1.0 / dt,
        "tokens_per_sec": len(prompts) / dt,
        "tick_ms_min": ticks[0] * 1e3,
        "tick_ms_median": ticks[len(ticks) // 2] * 1e3,
        "tick_ms_max": ticks[-1] * 1e3,
        "ttft_mean_s": sum(ttfts) / len(ttfts),
        "kv_pages_in_use": int(pages_in_use),
        "prefill_tokens_computed": int(eng.stats.prefill_tokens),
        "prefix_matched_tokens": int(eng.stats.prefix_matched_tokens),
        "cascade_ticks": int(eng.stats.cascade_ticks),
        "cascade_fused_ticks": int(eng.stats.cascade_fused_ticks),
        "cow_copies": int(eng.stats.cow_copies),
        "prefix_cache": dict(eng.stats.prefix_cache),
        "pages_saved": int(eng.pool.pages_saved),
    }


def _run_mixed_mode(cfg, params, prompts, chain, *, grouping, fused,
                    n_ticks):
    """One mixed-depth engine run: seed the chain, admit the 1/3/5-page
    matchers, measure steady-state decode + cascade grouping counters."""
    import numpy as np

    eng, sched = _build(cfg, params, prefix_cache=True, cascade=True,
                        grouping=grouping, fused=fused)
    donor = sched.submit(np.concatenate([chain, [1]]), 1)  # noqa: F841
    sched.run_to_completion(max_steps=100)
    handles = [sched.submit(p, max_new_tokens=10_000) for p in prompts]
    while any(h.state.value != "decoding" for h in handles):
        sched.step()
    ticks = _measure_decode(eng, n_ticks, cascade=True)
    eng.pool.check()
    eng.prefix_cache.check()
    s = eng.stats
    return {
        "ticks_per_sec": 1.0 / ticks[0],
        "tick_ms_min": ticks[0] * 1e3,
        "tick_ms_median": ticks[len(ticks) // 2] * 1e3,
        "cascade_ticks": int(s.cascade_ticks),
        "cascade_fused_ticks": int(s.cascade_fused_ticks),
        "grouped_passes_total": int(s.cascade_grouped_passes),
        "grouped_passes_per_tick": (
            s.cascade_grouped_passes / s.cascade_ticks
            if s.cascade_ticks else 0.0
        ),
        "grouped_slots_per_tick": (
            s.cascade_grouped_slots / s.cascade_ticks
            if s.cascade_ticks else 0.0
        ),
        "levels_max": int(s.cascade_levels_max),
        "retraces": int(s.cascade_retraces),
        "stability_skips": int(s.cascade_stability_skips),
        "last_grouping": dict(s.cascade_last),
    }


def run_mixed_depth(cfg, params, n_ticks: int) -> dict:
    """Mixed-depth LCP scenario: requests matching 1, 3, and 5 pages of
    one cached chain. Compares LCP vs identical-run grouping (grouped
    passes, retraces) and fused vs two-call cascade execution (tick
    speedup)."""
    import numpy as np

    rng = np.random.default_rng(2)
    chain = rng.integers(0, cfg.vocab_size, CHAIN_PAGES * PAGE)
    prompts = [
        np.concatenate([chain[: d * PAGE],
                        rng.integers(0, cfg.vocab_size, TAIL)])
        for d in (1, 3, 5)
    ]
    section = {
        "workload": {
            "chain_pages": CHAIN_PAGES,
            "match_depths_pages": [1, 3, 5],
            "private_tail_tokens": TAIL,
            "page_size": PAGE,
            "ticks": n_ticks,
        },
        "lcp": _run_mixed_mode(
            cfg, params, prompts, chain, grouping="lcp", fused=True,
            n_ticks=n_ticks,
        ),
        "identical": _run_mixed_mode(
            cfg, params, prompts, chain, grouping="identical", fused=True,
            n_ticks=n_ticks,
        ),
        "lcp_two_call": _run_mixed_mode(
            cfg, params, prompts, chain, grouping="lcp", fused=False,
            n_ticks=n_ticks,
        ),
    }
    lcp, ident, two = (
        section["lcp"], section["identical"], section["lcp_two_call"]
    )
    section["headline"] = {
        # the acceptance claim: LCP groups mixed-depth matches the
        # identical-run grouping cannot see at all
        "grouped_passes_per_tick_lcp": lcp["grouped_passes_per_tick"],
        "grouped_passes_per_tick_identical":
            ident["grouped_passes_per_tick"],
        "lcp_beats_identical_grouping":
            lcp["grouped_passes_per_tick"]
            > ident["grouped_passes_per_tick"],
        "retraces_lcp": lcp["retraces"],
        "fused_over_two_call_speedup":
            lcp["ticks_per_sec"] / two["ticks_per_sec"],
        "multi_level_engaged": lcp["levels_max"] >= 2,
    }
    return section


def run_prefix(n_ticks: int = 12, out_path: str = "BENCH_decode_step.json",
               rows: list | None = None) -> dict:
    import jax

    jax.config.update("jax_platform_name", "cpu")
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.models import init_params

    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, PREFIX_PAGES * PAGE)
    prompts = [
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, TAIL)])
        for _ in range(8)
    ]

    section: dict = {"workload": {
        "batch": 8, "shared_prefix_tokens": int(PREFIX_PAGES * PAGE),
        "private_tail_tokens": TAIL, "page_size": PAGE,
        "ticks": n_ticks, "platform": "cpu-interpret",
    }}
    section["baseline"] = _run_mode(
        cfg, params, prompts, shared, prefix_cache=False, cascade=False,
        n_ticks=n_ticks,
    )
    section["prefix"] = _run_mode(
        cfg, params, prompts, shared, prefix_cache=True, cascade=False,
        n_ticks=n_ticks,
    )
    section["cascade"] = _run_mode(
        cfg, params, prompts, shared, prefix_cache=True, cascade=True,
        n_ticks=n_ticks,
    )
    base, pref, casc = (
        section["baseline"], section["prefix"], section["cascade"]
    )
    section["headline"] = {
        "kv_pages_prefix_vs_baseline":
            f"{pref['kv_pages_in_use']}/{base['kv_pages_in_use']}",
        "kv_pages_strictly_below_baseline":
            pref["kv_pages_in_use"] < base["kv_pages_in_use"]
            and casc["kv_pages_in_use"] < base["kv_pages_in_use"],
        "ttft_speedup_prefix": base["ttft_mean_s"] / pref["ttft_mean_s"],
        "decode_speedup_prefix":
            pref["ticks_per_sec"] / base["ticks_per_sec"],
        "decode_speedup_cascade":
            casc["ticks_per_sec"] / base["ticks_per_sec"],
        "prefill_tokens_skipped":
            base["prefill_tokens_computed"]
            - pref["prefill_tokens_computed"],
    }
    section["mixed_depth"] = run_mixed_depth(cfg, params, n_ticks)

    # merge into the shared benchmark artifact
    out = Path(out_path)
    doc = json.loads(out.read_text()) if out.exists() else {}
    doc["prefix"] = section
    out.write_text(json.dumps(doc, indent=1))

    if rows is not None:
        h = section["headline"]
        hm = section["mixed_depth"]["headline"]
        rows.append(("prefix_decode_speedup_cascade", 0.0,
                     h["decode_speedup_cascade"]))
        rows.append(("prefix_decode_speedup_aliased", 0.0,
                     h["decode_speedup_prefix"]))
        rows.append(("prefix_ttft_speedup", 0.0, h["ttft_speedup_prefix"]))
        rows.append(("prefix_kv_pages_saved", 0.0,
                     float(base["kv_pages_in_use"]
                           - pref["kv_pages_in_use"])))
        rows.append(("prefix_mixed_lcp_passes_per_tick", 0.0,
                     hm["grouped_passes_per_tick_lcp"]))
        rows.append(("prefix_mixed_fused_speedup", 0.0,
                     hm["fused_over_two_call_speedup"]))
    return section


def run(rows: list):
    run_prefix(rows=rows)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=12)
    ap.add_argument("--out", default="BENCH_decode_step.json")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    s = run_prefix(args.ticks, args.out)
    print(json.dumps(s, indent=1))
    h = s["headline"]
    hm = s["mixed_depth"]["headline"]
    print(
        f"\nKV pages {s['prefix']['kv_pages_in_use']} (shared) vs "
        f"{s['baseline']['kv_pages_in_use']} (baseline); TTFT "
        f"{h['ttft_speedup_prefix']:.2f}x faster; decode "
        f"{h['decode_speedup_cascade']:.2f}x (cascade) / "
        f"{h['decode_speedup_prefix']:.2f}x (aliased) vs no sharing; "
        f"{h['prefill_tokens_skipped']} prefill tokens skipped"
    )
    print(
        f"mixed-depth 1/3/5: LCP {hm['grouped_passes_per_tick_lcp']:.1f} "
        f"grouped passes/tick vs identical "
        f"{hm['grouped_passes_per_tick_identical']:.1f}; "
        f"{hm['retraces_lcp']} retraces; fused vs two-call "
        f"{hm['fused_over_two_call_speedup']:.2f}x"
    )


if __name__ == "__main__":
    main()
