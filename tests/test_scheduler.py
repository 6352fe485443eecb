"""Scheduler invariants: lifecycle, token-identity vs the blocking-admit
oracle (GQA + MQA), no decode stall during long prefills, bounded prefill
compile counts, starvation bounds under the priority policy, and clean pool
accounting after churn with chunked prefill."""
import dataclasses
import functools

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.core.leantile import bucket_length
from repro.models import init_params
from repro.serving.config import EngineConfig, PagedConfig
from repro.serving.engine import DecodeEngine, Request
from repro.serving.scheduler import (
    RequestState,
    Scheduler,
    SchedulerConfig,
)

jax.config.update("jax_platform_name", "cpu")


@functools.lru_cache(maxsize=2)
def _smoke(mqa: bool = False):
    cfg = get_smoke_config("mistral-nemo-12b")
    if mqa:
        cfg = dataclasses.replace(cfg, name="smoke-mqa", n_kv_heads=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def smoke():
    return _smoke()


def _prompts(cfg, n=4, seed=0, base=8, step=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, base + step * i) for i in range(n)]


def _paged_engine(cfg, params, backend="ref", *, page_size=16,
                  num_pages=None, prefix_cache=False, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("num_workers", 8)
    return DecodeEngine(cfg, params, config=EngineConfig(
        attn_backend=backend,
        paged=PagedConfig(
            page_size=page_size, num_pages=num_pages,
            prefix_cache=prefix_cache,
        ),
        **kw,
    ))


def _record_logits(eng, into):
    """Record every decode pass's f32 logits row as
    ``into[(uid, index of the token it picks)]``."""
    inner = eng._decode_pass

    def recording_pass(level, slots, active, exclude):
        logits = inner(level, slots, active, exclude)
        for s in slots:
            req = eng.slot_req[s]
            into[(req.uid, len(req.generated))] = np.asarray(
                logits[s], np.float32
            )
        return logits

    eng._decode_pass = recording_pass


def _run_sched(cfg, params, backend, chunked, prompts, max_new=6,
               sched_cfg=None, logits=None, **eng_kw):
    eng = _paged_engine(cfg, params, backend, **eng_kw)
    if logits is not None:
        _record_logits(eng, logits)
    sch = Scheduler(eng, sched_cfg or SchedulerConfig(
        chunk_size=8, prefill_pack=2, token_budget=16, chunked=chunked,
    ))
    streams = {}
    def cb(uid, tok, done):
        streams.setdefault(uid, []).append(tok)
    handles = [
        sch.submit(p, max_new, on_token=cb, uid=i)
        for i, p in enumerate(prompts)
    ]
    sch.run_to_completion(max_steps=400)
    return sch, handles, streams


def test_lifecycle_and_streaming(smoke):
    """QUEUED -> PREFILLING -> DECODING -> FINISHED; every token streamed
    through the callback in order; budgets honored; engine drained."""
    cfg, params = smoke
    prompts = _prompts(cfg)
    sch, handles, streams = _run_sched(cfg, params, "ref", True, prompts)
    assert sch.chunked
    for h in handles:
        assert h.state is RequestState.FINISHED and h.done
        assert len(h.generated) == 6
        assert streams[h.uid] == h.generated
        assert h.admit_step >= 0 and h.first_token_time > 0
    assert sch.stats.chunks > 0 and sch.stats.finished == len(handles)
    assert not sch.engine.queue and not any(sch.engine.slot_req)
    sch.engine.pool.check()
    with pytest.raises(ValueError, match="empty prompt"):
        sch.submit(np.zeros(0, np.int32), 3)
    # telemetry populated: one TTFT per request, TPOT for decode tokens,
    # and the per-tick prefill-vs-decode token split
    es = sch.engine.stats
    assert es.ttft.count == len(handles)
    assert es.tpot.count == es.tokens_generated
    assert sum(es.tick_prefill_tokens) == es.prefill_tokens
    assert sum(p.size for p in map(np.asarray, prompts)) == es.prefill_tokens
    assert sum(es.tick_decode_tokens) >= es.tokens_generated


def _oracle_tokens(cfg, params, prompts, max_new=6, logits=None):
    """The blocking-admit oracle: the raw engine's own tick loop."""
    eng = _paged_engine(cfg, params, "ref")
    if logits is not None:
        _record_logits(eng, logits)
    reqs = [
        Request(uid=i, prompt=p, max_new_tokens=max_new)
        for i, p in enumerate(prompts)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_ticks=200)
    return [tuple(r.generated) for r in reqs]


@pytest.fixture(scope="module")
def blocking_oracle(smoke):
    cfg, params = smoke
    logits = {}
    return _oracle_tokens(cfg, params, _prompts(cfg), logits=logits), logits


# The lean kernels accumulate attention in f32 in another order than the
# ref oracle, and each layer casts its output to bf16, so decode logits
# (|logit| < 4 here) differ by a few bf16 ulps (2**-6 in [2, 4)): 0.035 at
# most on these prompts, chunked or blocking alike. Three ulps:
LOGIT_ATOL = 0.05


@pytest.mark.parametrize(
    "backend,chunked", [("ref", False), ("ref", True), ("lean", True)]
)
def test_chunked_token_identical_to_blocking_oracle(
    smoke, blocking_oracle, backend, chunked
):
    """Acceptance: scheduler (chunked or blocking) == raw engine. The ref
    backend runs the oracle's own ops, so its tokens are identical. The
    lean stream-K kernels must match the oracle's logits within
    ``LOGIT_ATOL`` at every decode step of a common context; a greedy
    token may differ only where the oracle's top two logits are within
    ``2 * LOGIT_ATOL`` of each other (a near-tie under random weights)."""
    cfg, params = smoke
    oracle, want = blocking_oracle
    got_logits = {}
    _, handles, _ = _run_sched(
        cfg, params, backend, chunked, _prompts(cfg), logits=got_logits
    )
    got = [tuple(h.generated) for h in handles]
    if backend == "ref":
        assert got == oracle, f"ref chunked={chunked} diverged"
        return
    for uid, (a, b) in enumerate(zip(oracle, got)):
        assert len(a) == len(b) and a[0] == b[0], uid
        n = next((i for i in range(len(a)) if a[i] != b[i]), len(a))
        for i in range(1, min(n + 1, len(a))):    # same context up to n
            err = np.abs(want[(uid, i)] - got_logits[(uid, i)]).max()
            assert err <= LOGIT_ATOL, (uid, i, err)
        if n < len(a):
            top2 = np.sort(want[(uid, n)])[-2:]
            assert top2[1] - top2[0] <= 2 * LOGIT_ATOL, (uid, n, top2)


def test_chunked_parity_mqa_geometry():
    cfg, params = _smoke(mqa=True)
    prompts = _prompts(cfg, n=3)
    oracle = _oracle_tokens(cfg, params, prompts)
    _, handles, _ = _run_sched(cfg, params, "ref", True, prompts)
    assert [tuple(h.generated) for h in handles] == oracle


def test_decode_keeps_running_during_long_prefill(smoke):
    """The no-full-batch-stall property: while a long prompt streams in
    chunk by chunk, already-admitted requests keep producing decode tokens
    every tick."""
    cfg, params = smoke
    rng = np.random.default_rng(3)
    eng = _paged_engine(cfg, params, "ref", max_batch=3)
    sch = Scheduler(eng, SchedulerConfig(
        chunk_size=8, prefill_pack=1, token_budget=16, chunked=True,
    ))
    short = [sch.submit(rng.integers(0, cfg.vocab_size, 6), 20, uid=i)
             for i in range(2)]
    long = sch.submit(rng.integers(0, cfg.vocab_size, 40), 4, uid=9)
    overlap_ticks = 0
    for _ in range(60):
        out = sch.step()
        if long.state is RequestState.PREFILLING and out:
            overlap_ticks += 1
        if all(h.done for h in short + [long]):
            break
    # the 40-token prompt takes 5 chunks; decode ran alongside each
    assert overlap_ticks >= 3, f"decode stalled: {overlap_ticks} overlap ticks"
    assert long.done and all(h.done for h in short)
    eng.pool.check()


def test_prefill_compile_count_bounded(smoke):
    """Satellite acceptance: distinct prompt lengths bucket to canonical
    padded shapes, so admission prefill compiles stay O(log capacity)
    instead of one per length — and bucketing changes no tokens."""
    cfg, params = smoke
    rng = np.random.default_rng(5)
    lengths = list(range(3, 41, 3))            # 13 distinct lengths
    prompts = [rng.integers(0, cfg.vocab_size, L) for L in lengths]

    def run(bucket: bool):
        eng = DecodeEngine(cfg, params, config=EngineConfig(
            max_batch=2, cache_len=64, attn_backend="ref",
        ))
        eng.bucket_prefill = bucket
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=3))
        eng.run_to_completion(max_ticks=200)
        return eng

    eng = run(True)
    expected = {bucket_length(L, eng.tile, max_len=64) for L in lengths}
    assert eng.stats.prefill_compiles == len(expected)
    assert eng.stats.prefill_compiles <= 4     # vs 13 exact-length compiles
    cache_size = getattr(eng._jit_prefill_bucketed, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() <= len(expected)
    # exactness: bucketed admission generates the same tokens
    eng_exact = run(False)
    assert eng_exact.stats.prefill_compiles == len(lengths)
    for a, b in zip(
        sorted(eng.stats.schedules[-1]["lens"]),
        sorted(eng_exact.stats.schedules[-1]["lens"]),
    ):
        assert a == b


def test_priority_policy_and_starvation_bound(smoke):
    """Under a flood of high-priority arrivals, an old low-priority request
    is still admitted once its queue age crosses the starvation bound, and
    no admission ever passes over a starving request."""
    cfg, params = smoke
    rng = np.random.default_rng(6)
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=1, cache_len=64, attn_backend="ref",
    ))
    bound = 4
    sch = Scheduler(eng, SchedulerConfig(
        policy="priority", starvation_bound=bound, chunked=False,
    ))
    low = sch.submit(rng.integers(0, cfg.vocab_size, 4), 2, priority=0, uid=0)
    uid = 1
    for _ in range(40):
        # keep one high-priority request always waiting
        if not any(
            sr.priority > 0 and sr.state is RequestState.QUEUED
            for sr in sch.requests.values()
        ):
            sch.submit(rng.integers(0, cfg.vocab_size, 4), 2,
                       priority=10, uid=uid)
            uid += 1
        sch.step()
        if low.admit_step >= 0:
            break
    assert low.admit_step >= 0, "low-priority request starved"
    # aging admitted it within the bound plus the residency of the slot's
    # current occupant (max_new_tokens + 1 ticks)
    assert low.admit_step - low.arrival_step <= bound + 4
    assert all(
        rec["starving_passed_over"] == 0 for rec in sch.stats.admissions
    )


def test_pool_accounting_clean_after_chunked_churn(smoke):
    """An undersized pool with chunked prefill: admissions, chunk streams,
    decode growth, completions, and preemptions all interleave — the
    allocator invariants must hold throughout and the pool must drain."""
    cfg, params = smoke
    rng = np.random.default_rng(8)
    eng = _paged_engine(cfg, params, "ref", max_batch=3,
                        num_pages=1 + 6)       # 6 usable pages of 16 tokens
    sch = Scheduler(eng, SchedulerConfig(
        chunk_size=8, prefill_pack=2, token_budget=12, chunked=True,
    ))
    handles = [
        sch.submit(rng.integers(0, cfg.vocab_size, int(rng.integers(2, 30))),
                   int(rng.integers(1, 6)), uid=i)
        for i in range(6)
    ]
    for _ in range(200):
        sch.step()
        eng.pool.check()                       # invariants hold every tick
        if not sch.pending:
            break
    assert not sch.pending
    assert all(h.done for h in handles)
    # finished requests are forgotten (bounded server state, uids reusable)
    assert not sch.requests
    assert eng.pool.num_allocated == 0 and eng.pool.live_sequences == 0


def test_over_capacity_prompt_rejected(smoke):
    """A prompt beyond one slot's page-table capacity would wrap chunk
    writes onto the last page and silently corrupt KV — both admission
    paths must reject it outright."""
    cfg, params = smoke
    eng = _paged_engine(cfg, params, "ref")        # cache 64, page 16
    sch = Scheduler(eng, SchedulerConfig(chunk_size=8, chunked=True))
    sch.submit(np.arange(100) % cfg.vocab_size, 2, uid=0)
    with pytest.raises(RuntimeError, match="per-slot KV capacity"):
        sch.step()
    eng2 = _paged_engine(cfg, params, "ref")
    eng2.submit(Request(uid=0, prompt=np.arange(100) % cfg.vocab_size,
                        max_new_tokens=2))
    with pytest.raises(RuntimeError, match="per-slot KV capacity"):
        eng2.tick()


def test_double_preemption_folds_generated_once(smoke):
    """Recompute-resume must fold each generated token into the prompt
    exactly once across repeated preemptions."""
    cfg, params = smoke
    eng = _paged_engine(cfg, params, "ref", max_batch=1)
    req = Request(uid=0, prompt=np.arange(5, dtype=np.int32),
                  max_new_tokens=50)
    eng.submit(req)
    for _ in range(4):
        eng.tick()                     # prefill + a few decode tokens
    base = 5
    for round_ in range(2):
        eng.preempt_slot(0)
        assert len(req.prompt) == base + len(req.generated), (
            f"preemption {round_}: generated tokens folded more than once"
        )
        assert req.folded == len(req.generated)
        eng.tick()                     # re-admit (recompute) + decode
    eng.pool.check()


def test_pool_capacity_cut_instead_of_unservable_regrowth(smoke):
    """A context allowed to outgrow the whole pool could never be
    re-admitted after preemption (its recompute-resume prompt fails the
    pool fit check, crashing the serving loop). The engine must finish
    such sequences at the pool bound instead — with a final token, like
    the cache-capacity cut — and keep serving everyone else."""
    cfg, params = smoke
    eng = _paged_engine(cfg, params, "ref", page_size=8,
                        num_pages=1 + 4)  # 4 usable pages = 32 tokens
    sch = Scheduler(eng, SchedulerConfig(chunk_size=8, chunked=True))
    events = []
    big = sch.submit(np.arange(28, dtype=np.int32), 10_000, uid=0,
                     on_token=lambda u, t, d: events.append(d))
    other = sch.submit(np.arange(5, dtype=np.int32), 3, uid=1)
    sch.run_to_completion(max_steps=100)      # must not raise
    assert big.done and other.done
    assert len(other.generated) == 3          # not stranded
    # big was cut at the pool bound (ctx 31), terminator delivered
    assert len(big.generated) == 31 - 28 + 1
    assert events[-1] is True and all(not d for d in events[:-1])
    eng.pool.check()
    assert eng.pool.num_allocated == 0


def test_capacity_cut_fires_done_callback(smoke):
    """A request terminated by the context cap (not its token budget)
    still owes its stream a done=True terminator."""
    cfg, params = smoke
    eng = _paged_engine(cfg, params, "ref", max_batch=1, cache_len=32)
    sch = Scheduler(eng, SchedulerConfig(chunk_size=8, chunked=True))
    events = []
    h = sch.submit(np.arange(8, dtype=np.int32), 10_000,
                   on_token=lambda uid, tok, done: events.append(done))
    sch.run_to_completion(max_steps=100)
    assert h.done
    assert len(h.generated) < 10_000          # cut by capacity, not budget
    assert events[-1] is True and all(not d for d in events[:-1])


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    policy=st.sampled_from(["fcfs", "priority"]),
    num_pages=st.integers(5, 13),
    n_reqs=st.integers(3, 8),
)
def test_fuzz_arrival_churn(seed, policy, num_pages, n_reqs):
    """Slow fuzz over arrival patterns: staggered submissions with random
    priorities/lengths/budgets on an undersized pool. Asserts no
    starvation-order violations, full completion, callback streams match,
    and clean pool accounting after churn."""
    cfg, params = _smoke()
    rng = np.random.default_rng(seed)
    eng = _paged_engine(cfg, params, "ref", max_batch=3,
                        num_pages=1 + num_pages)
    sch = Scheduler(eng, SchedulerConfig(
        chunk_size=8, prefill_pack=2, token_budget=12, chunked=True,
        policy=policy, starvation_bound=6,
    ))
    streams = {}
    def cb(uid, tok, done):
        streams.setdefault(uid, []).append(tok)
    pendings = []
    for i in range(n_reqs):
        pendings.append(dict(
            at=int(rng.integers(0, 12)),
            plen=int(rng.integers(1, 30)),
            max_new=int(rng.integers(1, 7)),
            priority=int(rng.integers(0, 3)),
            uid=i,
        ))
    handles = []
    for step in range(400):
        for p in [p for p in pendings if p["at"] == step]:
            handles.append(sch.submit(
                rng.integers(0, cfg.vocab_size, p["plen"]), p["max_new"],
                priority=p["priority"], on_token=cb, uid=p["uid"],
            ))
        sch.step()
        if step > 12 and not sch.pending:
            break
    assert not sch.pending, "scheduler failed to drain"
    for h in handles:
        assert h.done and len(h.generated) == h.req.max_new_tokens
        assert streams[h.uid] == h.generated
    assert all(
        rec["starving_passed_over"] == 0 for rec in sch.stats.admissions
    )
    eng.pool.check()
    assert eng.pool.num_allocated == 0


# ------------------------------------------------------------- telemetry
def test_histogram_empty_is_guarded():
    """The empty histogram must never leak its ±inf sentinels: percentile
    and the JSON summary report zeros / a bare count, repr stays printable,
    and merging empties is a no-op."""
    from repro.obs.metrics import Histogram

    h = Histogram()
    assert h.count == 0
    assert h.mean == 0.0
    assert h.percentile(50) == 0.0 and h.percentile(99) == 0.0
    assert h.as_dict() == {"count": 0}
    assert "empty" in repr(h)
    # merge of two empties stays empty (and min/max stay sentinels only
    # internally — as_dict never exposes them)
    h.merge(Histogram())
    assert h.as_dict() == {"count": 0}
    # empty + populated merge adopts the populated side's extrema
    other = Histogram()
    other.observe(0.25)
    h.merge(other)
    d = h.as_dict()
    assert d["count"] == 1 and d["min"] == d["max"] == 0.25
    assert h.percentile(-5) == 0.25 and h.percentile(200) == 0.25


def test_scheduler_telemetry_before_any_traffic(smoke):
    """telemetry() on a fresh scheduler (all histograms empty) must be
    JSON-clean — the empty-histogram guard seen from the caller's side."""
    import json

    cfg, params = smoke
    eng = _paged_engine(cfg, params, "ref")
    sch = Scheduler(eng, SchedulerConfig(chunk_size=8, prefill_pack=2,
                                         token_budget=16))
    tel = sch.telemetry()
    assert tel["ttft"] == {"count": 0}
    assert tel["tpot"] == {"count": 0}
    json.dumps(tel)                      # no ±inf leaks into the summary


# ------------------------------------------------------ prefix admission
def test_prefix_admission_charges_only_unmatched_tokens(smoke):
    """A radix-matched admission skips the matched prompt tokens entirely:
    prefill_done starts at the match, fewer chunks run, and only unmatched
    tokens are charged to the chunk budget."""
    cfg, params = smoke
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 32)      # 2 pages of 16
    p1 = np.concatenate([shared, rng.integers(0, cfg.vocab_size, 6)])
    p2 = np.concatenate([shared, rng.integers(0, cfg.vocab_size, 9)])

    def run(prefix_cache):
        eng = _paged_engine(cfg, params, "ref", prefix_cache=prefix_cache)
        sch = Scheduler(eng, SchedulerConfig(chunk_size=8, prefill_pack=2,
                                             token_budget=16))
        h1 = sch.submit(p1, 3)
        sch.run_to_completion(max_steps=200)
        h2 = sch.submit(p2, 3)
        sch.run_to_completion(max_steps=200)
        return (tuple(h1.generated), tuple(h2.generated)), eng, sch

    toks_off, eng_off, sch_off = run(False)
    toks_on, eng_on, sch_on = run(True)
    assert toks_off == toks_on
    assert eng_on.stats.prefix_attach_count == 1
    assert eng_on.stats.prefix_matched_tokens == 32
    # the second request's prompt pushed only its unmatched tail through
    # chunked prefill
    assert eng_on.stats.prefill_tokens == eng_off.stats.prefill_tokens - 32
    assert sch_on.stats.chunks < sch_off.stats.chunks
    tel = sch_on.telemetry()
    assert tel["prefix_matched_tokens"] == 32
    assert tel["prefix_cache"]["hit_rate"] > 0
