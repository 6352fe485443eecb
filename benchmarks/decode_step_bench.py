"""Decode-step benchmark: the engine's jitted paged decode tick,
machine-readable across PRs.

Measures, on the CPU-interpret smoke config (the CI-reproducible proxy for
the engine's launcher/host overhead):

  * ticks/sec of the decode tick (schedule cache + whole-step jit + fused
    kernel), with the ``ref`` backend's for context,
  * schedule-cache hit rate at steady state,
  * host-ms vs device-ms per tick (device = replaying the jitted step with
    fixed inputs; host = everything else the tick does).

Writes ``BENCH_decode_step.json`` (``--out``) so the perf trajectory is
diffable across PRs, and appends CSV rows for ``benchmarks.run``.

  PYTHONPATH=src python -m benchmarks.decode_step_bench --ticks 32
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path


def _mk_engine(cfg, params, backend, *, page_size=None, kv_dtype=None,
               tracer=None, **kw):
    from repro.serving.config import EngineConfig, ObsConfig, PagedConfig
    from repro.serving.engine import DecodeEngine

    return DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=4, cache_len=64, attn_backend=backend, num_workers=8,
        paged=PagedConfig(page_size=page_size, kv_dtype=kv_dtype),
        obs=ObsConfig(tracer=tracer), **kw,
    ))


def _feed(eng, cfg, n=6, seed=0):
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    for uid in range(n):
        eng.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, 8 + 5 * (uid % 3)),
            max_new_tokens=1_000_000,   # keep slots occupied: steady state
        ))


def _ticks_per_sec(eng, cfg, n_ticks, warmup=3):
    _feed(eng, cfg)
    for _ in range(warmup):
        eng.tick()
    t0 = time.perf_counter()
    for _ in range(n_ticks):
        eng.tick()
    dt = time.perf_counter() - t0
    return n_ticks / dt, dt / n_ticks


def _device_ms_per_tick(eng, n_reps=8):
    """Replay the jitted kernel step with fixed inputs: pure device time
    (trace is warm, schedule cached)."""
    import jax
    import jax.numpy as jnp

    from repro.core.leantile import fixed_split_factor

    sched = eng._tick_schedule()
    tokens = jnp.asarray(eng.next_tokens)
    ctx = jnp.asarray(eng.ctx_lens, jnp.int32)
    ptbl = jnp.asarray(eng.page_tbl)
    num_splits = fixed_split_factor(
        int(sched.seg_len.max(initial=1)), sched.num_segments, eng.tile,
        eng.num_workers,
    )

    def step():
        logits, new_cache = eng._jit_kernel_step_paged(
            eng.params, eng.cache, tokens, ctx, ptbl,
            backend=eng.attn_backend, sched=sched, num_splits=num_splits,
            fused=eng.fused, interpret=eng.interpret,
        )
        eng.cache = new_cache
        return jax.block_until_ready(logits)

    step()                                   # warm
    t0 = time.perf_counter()
    for _ in range(n_reps):
        step()
    return (time.perf_counter() - t0) * 1e3 / n_reps


def _attn_kv_bytes(eng) -> int:
    """Bytes held by global-attention KV state (the page pools),
    including any quantization scale sidecars — they are real device
    footprint."""
    total = 0
    for (pattern, reps), st_c in zip(eng.cfg.stages, eng.cache):
        for kind, lc in zip(pattern, st_c):
            if kind == "attn":
                total += lc["k"].nbytes + lc["v"].nbytes
                for key in ("k_scale", "v_scale"):
                    if key in lc:
                        total += lc[key].nbytes
    return total


def _run_paged_section(cfg, params, n_ticks: int) -> dict:
    """The page pool: throughput with the lean fused kernel, KV memory
    footprint, and the oversubscription headline — more in-flight slots
    than the same token budget could hold at full context."""
    import numpy as np

    from repro.serving.engine import DecodeEngine, Request

    # throughput + memory with the default page (the engine's tile, 64)
    eng_paged = _mk_engine(cfg, params, "lean")
    tps_paged, _ = _ticks_per_sec(eng_paged, cfg, n_ticks)

    # oversubscription demo: 8 slots backed by a pool holding only 4
    # slots' full-context token budget; lazy paging lets all 8 run
    # concurrently
    ps, pps = 16, 64 // 16
    from repro.serving.config import EngineConfig, PagedConfig

    eng_over = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=8, cache_len=64, attn_backend="ref",
        paged=PagedConfig(page_size=ps, num_pages=1 + 4 * pps),
    ))
    rng = np.random.default_rng(0)
    for uid in range(8):
        eng_over.submit(Request(
            uid=uid, prompt=rng.integers(0, cfg.vocab_size, 8),
            max_new_tokens=12,
        ))
    max_active = 0
    for _ in range(40):
        eng_over.tick()
        max_active = max(
            max_active, sum(1 for r in eng_over.slot_req if r is not None)
        )
        if not eng_over.queue and not any(eng_over.slot_req):
            break
    eng_over.pool.check()

    return {
        "ticks_per_sec_paged": tps_paged,
        "attn_kv_bytes_paged": _attn_kv_bytes(eng_paged),
        "schedule_cache_paged": eng_paged.sched_cache.stats.as_dict(),
        "pool": eng_paged.stats.kv_pool,
        "oversubscription": {
            "slots": 8,
            "dense_equivalent_slots": 4,
            "max_concurrent_slots": max_active,
            "preemptions": eng_over.stats.preemptions,
            "pool_high_water": eng_over.stats.kv_pool.get("high_water", 0),
        },
    }


def _run_scheduler_section(cfg, params) -> dict:
    """Mixed prefill+decode workload: does a long prompt stall the decode
    batch? Compares the chunked-prefill scheduler against the blocking-admit
    scheduler on identical traffic (3 steady decoders + 1 long prompt).

    The headline invariant: with chunked prefill, decode tokens keep
    flowing in the ticks where the long request is still PREFILLING
    (``decode_tokens_while_long_prefilling > 0``); with blocking admission
    the whole prompt runs inside one admission step and that count is 0 by
    construction while the wall-clock of the worst step balloons.
    """
    import time

    import numpy as np

    from repro.serving.engine import DecodeEngine
    from repro.serving.scheduler import (
        RequestState, Scheduler, SchedulerConfig,
    )

    LONG, CHUNK = 48, 8
    out: dict = {"workload": {
        "steady_decoders": 3, "long_prompt_tokens": LONG,
        "chunk_size": CHUNK, "token_budget": 16,
    }}
    from repro.serving.config import EngineConfig, PagedConfig

    for mode in ("chunked", "blocking"):
        eng = DecodeEngine(cfg, params, config=EngineConfig(
            max_batch=4, cache_len=64, attn_backend="lean", num_workers=8,
            paged=PagedConfig(page_size=16),
        ))
        sch = Scheduler(eng, SchedulerConfig(
            chunk_size=CHUNK, prefill_pack=1, token_budget=16,
            chunked=(mode == "chunked"),
        ))
        rng = np.random.default_rng(0)
        for i in range(3):
            sch.submit(rng.integers(0, cfg.vocab_size, 4), 1_000_000, uid=i)
        # warm ALL schedule signatures the measured window will touch:
        # first run the steady decoders across their last bucket boundary
        # (ctx 48 @ cache 64), then a throwaway long request so every
        # chunk/masked-decode trace is compiled before timing starts
        for _ in range(42):
            sch.step()
        warm = sch.submit(rng.integers(0, cfg.vocab_size, LONG), 2, uid=50)
        while not warm.done:
            sch.step()

        long = sch.submit(rng.integers(0, cfg.vocab_size, LONG), 2, uid=99)
        overlap_tokens = 0          # decode tokens in long-PREFILLING ticks
        overlap_ticks = 0
        step_walls = []
        while not long.done:
            t0 = time.perf_counter()
            toks = sch.step()
            step_walls.append(time.perf_counter() - t0)
            if long.state is RequestState.PREFILLING:
                overlap_ticks += 1
                overlap_tokens += len(toks)
        out[mode] = {
            "decode_tokens_while_long_prefilling": overlap_tokens,
            "long_prefilling_ticks": overlap_ticks,
            "ttft_long_s": long.first_token_time - long.arrival_time,
            "max_step_wall_s": max(step_walls),
            "mean_step_wall_s": sum(step_walls) / len(step_walls),
            "telemetry": sch.telemetry(),
        }
    out["no_stall"] = (
        out["chunked"]["decode_tokens_while_long_prefilling"] > 0
    )
    return out


def _run_hardening_section(cfg, params, n_ticks: int) -> dict:
    """Hardening overhead: the same paged lean-fused engine, plain vs
    hardened (guards configured, fault injector attached but *disabled*).
    The acceptance contract is "zero overhead when disabled": the
    throughput ratio must stay within 3% (gated by
    ``benchmarks.check_regression``). Rounds alternate plain/hardened on
    the same host and the reported ratio is the median of per-round
    ratios, so shared-runner drift hits both sides equally. Within a
    round each tick is timed individually and the round's estimate is
    the MEDIAN per-tick time: under interpret mode a bucket-boundary
    retrace (~1.5s vs ~1.3ms steady ticks) lands at the same tick index
    for both engines but its *trace* time differs between the two
    programs, so whole-round sums would measure compile noise, not the
    per-tick guard cost.
    """
    import statistics

    from repro.serving.faults import FaultInjector
    from repro.serving.guards import GuardConfig

    def mk(hardened: bool):
        kw = {}
        if hardened:
            kw["faults"] = FaultInjector({}, enabled=False)
            kw["guards"] = GuardConfig(audit_interval=32)
        return _mk_engine(cfg, params, "lean", page_size=16, **kw)

    def median_tick_s(eng, n):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            eng.tick()
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    eng_plain, eng_hard = mk(False), mk(True)
    # one warmup pass each (feeds steady-state traffic + compiles traces)
    _ticks_per_sec(eng_plain, cfg, 2)
    _ticks_per_sec(eng_hard, cfg, 2)

    # steady ticks are ~1-2 ms, so generous sampling is cheap once the
    # retrace outliers are excluded by the per-tick median
    rounds, per_round = 5, max(9, n_ticks)
    ratios, tps_p_all, tps_h_all = [], [], []
    for _ in range(rounds):
        tick_p = median_tick_s(eng_plain, per_round)
        tick_h = median_tick_s(eng_hard, per_round)
        tps_p_all.append(1.0 / tick_p)
        tps_h_all.append(1.0 / tick_h)
        ratios.append(tick_p / tick_h)

    assert eng_hard.stats.nan_ticks == 0
    assert eng_hard.stats.audit_failures == 0
    return {
        "ticks_per_sec_plain": statistics.median(tps_p_all),
        "ticks_per_sec_hardened": statistics.median(tps_h_all),
        "hardened_over_plain_throughput": statistics.median(ratios),
        "rounds": rounds,
        "ticks_per_round": per_round,
        "audits_run": eng_hard.stats.audits_run,
        "injector_fires": eng_hard.faults.total_fires,
    }


def _run_observability_section(cfg, params, n_ticks: int,
                               flight_out: str = "FLIGHT_sample.json") -> dict:
    """Tracing overhead: the same paged lean-fused engine, untraced
    (``NULL_TRACER`` default — the production setting) vs traced (an
    enabled :class:`repro.obs.trace.Tracer`, which also times a
    ``block_until_ready`` per decode span for sync attribution). The
    acceptance contract mirrors the hardening one: the traced/untraced
    throughput ratio must stay >= 0.97 (gated by
    ``benchmarks.check_regression``). The protocol tightens the
    hardening section's alternating *rounds* to alternating *ticks*:
    within a round each engine ticks in lockstep (plain, traced, plain,
    traced, ...) and the round's estimate is the per-engine median tick
    time — on a shared host, drift over a whole round (~10%) dwarfs the
    microsecond-level span cost being measured, and pairwise
    interleaving puts both engines inside the same drift window.

    Also writes ``flight_out``: a real flight-recorder postmortem bundle
    from a one-shot injected ``nan_output`` fault (CI uploads it as an
    inspectable artifact next to BENCH_decode_step.json).
    """
    import statistics

    from repro.obs.trace import Tracer
    from repro.serving.faults import FaultInjector, FaultSpec
    from repro.serving.guards import GuardConfig

    def mk(traced: bool):
        kw = {"tracer": Tracer()} if traced else {}
        return _mk_engine(cfg, params, "lean", page_size=16, **kw)

    eng_plain, eng_traced = mk(False), mk(True)
    _ticks_per_sec(eng_plain, cfg, 4)
    _ticks_per_sec(eng_traced, cfg, 4)

    rounds, per_round = 5, max(9, n_ticks)
    ratios, tps_u_all, tps_t_all = [], [], []
    for _ in range(rounds):
        tu, tt = [], []
        for _ in range(per_round):
            t0 = time.perf_counter()
            eng_plain.tick()
            tu.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            eng_traced.tick()
            tt.append(time.perf_counter() - t0)
        tick_u = statistics.median(tu)
        tick_t = statistics.median(tt)
        tps_u_all.append(1.0 / tick_u)
        tps_t_all.append(1.0 / tick_t)
        ratios.append(tick_u / tick_t)

    spans = eng_traced.tracer.spans
    dk = [s for s in spans if s["name"] == "decode_kernel"]
    sync_ms = (
        statistics.median([s.get("sync_ms", 0.0) for s in dk])
        if dk else 0.0
    )

    # sample postmortem artifact: a short hardened run with one injected
    # transient-NaN fault, dumped through the real guard path
    inj = FaultInjector(
        {"nan_output": FaultSpec(rate=1.0, start=3, max_fires=1)}, seed=1
    )
    eng_f = _mk_engine(
        cfg, params, "lean", page_size=16, faults=inj,
        guards=GuardConfig(heal_after=2),
    )
    _feed(eng_f, cfg, n=3)
    for _ in range(10):
        eng_f.tick()
    sample = eng_f.flight.dump("ci-sample", path=flight_out)

    return {
        "ticks_per_sec_untraced": statistics.median(tps_u_all),
        "ticks_per_sec_traced": statistics.median(tps_t_all),
        "traced_over_untraced_throughput": statistics.median(ratios),
        "rounds": rounds,
        "ticks_per_round": per_round,
        "spans_recorded": len(spans),
        "decode_sync_ms_median": sync_ms,
        "flight_sample": {
            "path": flight_out,
            "events": len(sample["events"]),
            "fault_fires": sum(
                1 for ev in sample["events"]
                if ev["kind"] == "fault_fire"
            ),
            "injector_fires": inj.total_fires,
        },
    }


def _run_quant_section(cfg, params, n_ticks: int) -> dict:
    """int8 page quantization: effective pool capacity per byte vs bf16
    (the headline — page_bytes straight from the pool's layout
    descriptor, scale sidecar included), device KV footprint, decode
    throughput, and a greedy-token agreement probe (quantization may
    legitimately flip a near-tie argmax, so agreement is a fraction, not
    an identity)."""
    import numpy as np

    from repro.serving.engine import DecodeEngine, Request

    def mk(**kw):
        return _mk_engine(cfg, params, "lean", page_size=16, **kw)

    eng_bf16 = mk()
    tps_bf16, _ = _ticks_per_sec(eng_bf16, cfg, n_ticks)
    eng_int8 = mk(kv_dtype="int8")
    tps_int8, _ = _ticks_per_sec(eng_int8, cfg, n_ticks)

    lay16, lay8 = eng_bf16.pool.layout, eng_int8.pool.layout
    capacity = lay16.page_bytes / lay8.page_bytes

    # token-agreement probe on fresh engines (finite requests, greedy)
    def streams(**kw):
        eng = mk(**kw)
        rng = np.random.default_rng(3)
        reqs = [
            Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 6 + 3 * i),
                    max_new_tokens=12)
            for i in range(3)
        ]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion(max_ticks=200)
        return [r.generated for r in reqs]

    base, quant = streams(), streams(kv_dtype="int8")
    agree = sum(
        x == y for a, b in zip(base, quant) for x, y in zip(a, b)
    )
    total = sum(len(a) for a in base)

    return {
        "layout_bf16": lay16.as_dict(),
        "layout_int8": lay8.as_dict(),
        "capacity_ratio_vs_bf16": capacity,
        "kv_bytes_per_token_bf16": lay16.page_bytes / lay16.page_size,
        "kv_bytes_per_token_int8": lay8.page_bytes / lay8.page_size,
        "attn_kv_bytes_bf16": _attn_kv_bytes(eng_bf16),
        "attn_kv_bytes_int8": _attn_kv_bytes(eng_int8),
        "ticks_per_sec_bf16": tps_bf16,
        "ticks_per_sec_int8": tps_int8,
        "int8_over_bf16_throughput": tps_int8 / tps_bf16,
        "token_agreement": agree / total,
        "tokens_compared": total,
    }


def _run_speculative_section(cfg, params) -> dict:
    """Draft-verify speculative decode: tokens/sec vs k with the synthetic
    100%-accept oracle proposer (replaying the non-spec greedy streams).
    Every draft verifies, so this measures the pure kernel-amortization
    ceiling — one stream-K sweep scoring k+1 rows instead of 1. Output is
    asserted token-identical to the non-spec baseline at every k (the
    safety contract is part of the bench, not just the test suite)."""
    import time as _time

    import numpy as np

    from repro.serving.config import EngineConfig, PagedConfig, SpecConfig
    from repro.serving.engine import DecodeEngine, Request
    from repro.serving.speculative import OracleProposer

    NEW = 24

    def reqs():
        rng = np.random.default_rng(7)
        return [
            Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 8 + 5 * i),
                    max_new_tokens=NEW)
            for i in range(3)
        ]

    def mk(spec=None):
        return DecodeEngine(cfg, params, config=EngineConfig(
            max_batch=4, cache_len=64, attn_backend="lean", num_workers=8,
            paged=PagedConfig(page_size=8),
            spec=spec if spec is not None else SpecConfig(),
        ))

    def timed_run(eng):
        rs = reqs()
        for r in rs:
            eng.submit(r)
        t0 = _time.perf_counter()
        eng.run_to_completion(max_ticks=600)
        dt = _time.perf_counter() - t0
        toks = sum(len(r.generated) for r in rs)
        return {r.uid: list(r.generated) for r in rs}, toks / dt

    # non-spec greedy baseline: records the oracle streams + tokens/sec.
    # one throwaway run warms the jit caches so compile time (inflated
    # ~1000x by interpret mode) stays out of every measured number.
    timed_run(mk())
    streams, tps_base = timed_run(mk())

    out: dict = {"tokens_per_sec_nonspec": tps_base, "new_tokens": NEW,
                 "accept_rate": 1.0, "by_k": {}}
    for k in (1, 2, 4, 8):
        spec = SpecConfig(enabled=True, k=k,
                          proposer=OracleProposer(streams))
        timed_run(mk(spec))                      # warm this k's traces
        eng = mk(spec)
        got, tps = timed_run(eng)
        assert got == streams, f"speculative k={k} diverged from greedy"
        out["by_k"][str(k)] = {
            "tokens_per_sec": tps,
            "speedup_vs_nonspec": tps / tps_base,
            "spec_ticks": eng.stats.spec_ticks,
            "drafted": eng.stats.spec_draft_tokens,
            "accepted": eng.stats.spec_accepted_tokens,
        }
    out["spec_speedup_k4"] = out["by_k"]["4"]["speedup_vs_nonspec"]
    return out


def run_decode_step(n_ticks: int = 24, out_path: str = "BENCH_decode_step.json",
                    rows: list | None = None,
                    history_path: str | None = "BENCH_history.jsonl") -> dict:
    import jax

    jax.config.update("jax_platform_name", "cpu")

    from repro.configs import get_smoke_config
    from repro.models import init_params

    from benchmarks.trajectory import (
        append_history,
        env_fingerprint,
        new_run_id,
    )

    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)

    # environment fingerprint + run id: the absolute-trajectory gate in
    # check_regression compares like-fingerprint history only, and uses
    # run_id to exclude this very run's freshly appended record
    fingerprint = env_fingerprint()
    run_id = new_run_id()
    result: dict = {"config": {
        "arch": "mistral-nemo-12b(smoke)", "max_batch": 4, "cache_len": 64,
        "num_workers": 8, "ticks": n_ticks, "platform": "cpu-interpret",
        "fingerprint": fingerprint, "run_id": run_id,
    }}

    # lean fused decode tick — also collect host/device split
    eng_fast = _mk_engine(cfg, params, "lean")
    tps_fast, s_per_tick = _ticks_per_sec(eng_fast, cfg, n_ticks)
    dev_ms = _device_ms_per_tick(eng_fast)
    cache_stats = eng_fast.sched_cache.stats.as_dict()

    # ref backend for context (jnp attention, same jitted tick)
    eng_ref = _mk_engine(cfg, params, "ref")
    tps_ref, _ = _ticks_per_sec(eng_ref, cfg, n_ticks)

    result["decode_step"] = {
        "ticks_per_sec_fast": tps_fast,
        "ticks_per_sec_ref_backend": tps_ref,
        "ms_per_tick_fast": s_per_tick * 1e3,
        "device_ms_per_tick": dev_ms,
        "host_ms_per_tick": max(0.0, s_per_tick * 1e3 - dev_ms),
        "schedule_cache": cache_stats,
    }
    result["paged"] = _run_paged_section(cfg, params, n_ticks)
    result["scheduler"] = _run_scheduler_section(cfg, params)
    result["hardening"] = _run_hardening_section(cfg, params, n_ticks)
    result["observability"] = _run_observability_section(
        cfg, params, n_ticks
    )
    result["quant"] = _run_quant_section(cfg, params, n_ticks)
    result["speculative"] = _run_speculative_section(cfg, params)
    Path(out_path).write_text(json.dumps(result, indent=1))
    if history_path:
        append_history(
            {
                "ticks_per_sec_fast": tps_fast,
                "ms_per_tick_fast": s_per_tick * 1e3,
                "spec_speedup_k4": result["speculative"]["spec_speedup_k4"],
            },
            fingerprint=fingerprint,
            run_id=run_id,
            wall_time=time.time(),
            path=history_path,
        )
    if rows is not None:
        d = result["decode_step"]
        p = result["paged"]
        s = result["scheduler"]
        rows.append(("decode_step_fast_us_per_tick",
                     d["ms_per_tick_fast"] * 1e3, d["ticks_per_sec_fast"]))
        rows.append(("decode_step_cache_hit_rate", 0.0,
                     cache_stats["hit_rate"]))
        rows.append(("decode_step_paged_max_concurrent", 0.0,
                     float(p["oversubscription"]["max_concurrent_slots"])))
        rows.append(("sched_decode_toks_during_long_prefill", 0.0,
                     float(s["chunked"][
                         "decode_tokens_while_long_prefilling"])))
        rows.append(("sched_ttft_long_chunked_s",
                     s["chunked"]["ttft_long_s"],
                     s["blocking"]["ttft_long_s"]))
        rows.append(("decode_step_hardened_over_plain", 0.0,
                     result["hardening"]["hardened_over_plain_throughput"]))
        rows.append(("decode_step_traced_over_untraced", 0.0,
                     result["observability"][
                         "traced_over_untraced_throughput"]))
        qn = result["quant"]
        rows.append(("decode_step_quant_capacity_ratio", 0.0,
                     qn["capacity_ratio_vs_bf16"]))
        rows.append(("decode_step_quant_token_agreement", 0.0,
                     qn["token_agreement"]))
        sp = result["speculative"]
        rows.append(("decode_step_spec_speedup_k4", 0.0,
                     sp["spec_speedup_k4"]))
    return result


def run(rows: list, history_path="BENCH_history.jsonl"):
    run_decode_step(rows=rows, history_path=history_path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=24)
    ap.add_argument("--out", default="BENCH_decode_step.json")
    ap.add_argument("--history", default="BENCH_history.jsonl",
                    help="trajectory store to append to ('' disables)")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    result = run_decode_step(args.ticks, args.out,
                             history_path=args.history or None)
    d = result["decode_step"]
    print(json.dumps(result, indent=1))
    p = result["paged"]
    print(
        f"\nlean {d['ticks_per_sec_fast']:.2f} ticks/s vs ref backend "
        f"{d['ticks_per_sec_ref_backend']:.2f} ticks/s; cache hit rate "
        f"{d['schedule_cache']['hit_rate']:.2f}; "
        f"host {d['host_ms_per_tick']:.1f}ms + device "
        f"{d['device_ms_per_tick']:.1f}ms per tick"
    )
    o = p["oversubscription"]
    print(
        f"paged {p['ticks_per_sec_paged']:.2f} ticks/s; "
        f"oversub: {o['max_concurrent_slots']}/{o['slots']} slots live on a "
        f"{o['dense_equivalent_slots']}-slot full-context budget "
        f"({o['preemptions']} preemptions)"
    )
    s = result["scheduler"]
    print(
        f"scheduler: {s['chunked']['decode_tokens_while_long_prefilling']} "
        f"decode tokens flowed during the long prefill (chunked) vs "
        f"{s['blocking']['decode_tokens_while_long_prefilling']} (blocking); "
        f"worst step {s['chunked']['max_step_wall_s']*1e3:.0f}ms vs "
        f"{s['blocking']['max_step_wall_s']*1e3:.0f}ms"
    )
    h = result["hardening"]
    print(
        f"hardening: {h['ticks_per_sec_hardened']:.2f} ticks/s hardened vs "
        f"{h['ticks_per_sec_plain']:.2f} plain "
        f"({h['hardened_over_plain_throughput']:.3f}x, gate >= 0.97)"
    )
    ob = result["observability"]
    print(
        f"observability: {ob['ticks_per_sec_traced']:.2f} ticks/s traced "
        f"vs {ob['ticks_per_sec_untraced']:.2f} untraced "
        f"({ob['traced_over_untraced_throughput']:.3f}x, gate >= 0.97); "
        f"{ob['spans_recorded']} spans, median decode sync "
        f"{ob['decode_sync_ms_median']:.2f}ms; flight sample -> "
        f"{ob['flight_sample']['path']}"
    )
    qn = result["quant"]
    print(
        f"quant: {qn['capacity_ratio_vs_bf16']:.2f}x effective pool "
        f"capacity ({qn['kv_bytes_per_token_int8']:.0f} vs "
        f"{qn['kv_bytes_per_token_bf16']:.0f} KV bytes/token); "
        f"{qn['ticks_per_sec_int8']:.2f} ticks/s int8 vs "
        f"{qn['ticks_per_sec_bf16']:.2f} bf16; token agreement "
        f"{qn['token_agreement']:.2f}"
    )
    sp = result["speculative"]
    per_k = ", ".join(
        f"k={k}: {v['speedup_vs_nonspec']:.2f}x"
        for k, v in sp["by_k"].items()
    )
    print(
        f"speculative (oracle, accept=1.0): {per_k} over "
        f"{sp['tokens_per_sec_nonspec']:.2f} tok/s non-spec "
        f"(gate: k=4 >= 1.3x; output token-identical at every k)"
    )


if __name__ == "__main__":
    main()
