"""Self-healing machinery, unit level: error taxonomy, fault-injector
determinism/windows, pool/trie repair, ctx-overflow warning dedupe,
invariant audits, and the scheduler's deadline/backoff/cancel paths.

Everything here is deterministic and fault-*free* at the decode level (or
drives injection points directly); the end-to-end chaos schedules that
exercise recovery under live faults are in ``tests/test_chaos.py``
(``-m chaos``).
"""
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.ops import _clamp_ctx_lens
from repro.models import init_params
from repro.serving.config import CascadeConfig, EngineConfig, PagedConfig
from repro.serving.engine import DecodeEngine, Request
from repro.serving.faults import (
    FaultInjector,
    FaultSpec,
    corrupt_trie_node,
)
from repro.serving.guards import (
    DEGRADE_LEVELS,
    FatalError,
    FatalInvariantError,
    GuardConfig,
    PoisonError,
    RetryableError,
    ServingError,
    classify,
)
from repro.serving.kvpool import KVPagePool
from repro.serving.prefix_cache import CACHE_SEQ, RadixPrefixCache
from repro.serving.scheduler import (
    RequestState,
    Scheduler,
    SchedulerConfig,
)
from repro.obs.metrics import Gauge

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ------------------------------------------------------------ error taxonomy
def test_classify_taxonomy_buckets():
    assert classify(RetryableError("pool full")) == "retryable"
    assert classify(PoisonError("never fits")) == "poison"
    assert classify(FatalError("pool corrupt")) == "fatal"
    assert classify(FatalInvariantError("audit failed")) == "fatal"
    assert classify(ValueError("plain")) == "unknown"


def test_taxonomy_preserves_runtimeerror_contract():
    """Existing fail-fast call sites catch RuntimeError; the taxonomy must
    stay inside that contract."""
    for exc in (ServingError, RetryableError, PoisonError, FatalError,
                FatalInvariantError):
        assert issubclass(exc, RuntimeError)


def test_guard_config_validation():
    GuardConfig()                             # defaults valid
    with pytest.raises(ValueError):
        GuardConfig(heal_after=0)
    with pytest.raises(ValueError):
        GuardConfig(poison_after=0)
    with pytest.raises(ValueError):
        GuardConfig(max_degrade=len(DEGRADE_LEVELS))
    with pytest.raises(ValueError):
        GuardConfig(audit_interval=-1)
    with pytest.raises(ValueError):
        GuardConfig(audit_action="explode")


# ------------------------------------------------------------- fault injector
def test_fault_spec_validation():
    FaultSpec(rate=0.5, start=2, stop=9, burst=3)
    with pytest.raises(ValueError):
        FaultSpec(rate=1.5)
    with pytest.raises(ValueError):
        FaultSpec(rate=0.5, burst=0)
    with pytest.raises(ValueError):
        FaultSpec(rate=0.5, start=5, stop=4)


def test_injector_rejects_unknown_point():
    with pytest.raises(ValueError, match="unknown injection point"):
        FaultInjector({"page_allocz": FaultSpec(rate=1.0)})


def _fire_pattern(inj, point, ticks, per_tick=3):
    pat = []
    for _ in range(ticks):
        inj.advance()
        pat.extend(inj.fire(point) for _ in range(per_tick))
    return pat


def test_injector_deterministic_replay():
    mk = lambda seed: FaultInjector(
        {"page_alloc": FaultSpec(rate=0.3)}, seed=seed
    )
    a = _fire_pattern(mk(7), "page_alloc", 40)
    b = _fire_pattern(mk(7), "page_alloc", 40)
    assert a == b and any(a)
    c = _fire_pattern(mk(8), "page_alloc", 40)
    assert a != c


def test_injector_streams_are_point_isolated():
    """Consulting (or not) point A must not perturb point B's schedule."""
    specs = {
        "page_alloc": FaultSpec(rate=0.3),
        "cow_clone": FaultSpec(rate=0.3),
    }
    solo = _fire_pattern(FaultInjector(specs, seed=3), "cow_clone", 30)
    inj = FaultInjector(specs, seed=3)
    mixed = []
    for _ in range(30):
        inj.advance()
        for _ in range(3):
            inj.fire("page_alloc")           # extra draws on another point
            mixed.append(inj.fire("cow_clone"))
    assert solo == mixed


def test_injector_window_and_max_fires():
    inj = FaultInjector(
        {"page_alloc": FaultSpec(rate=1.0, start=5, stop=8)}, seed=0
    )
    fired_at = [t for t in range(1, 13)
                if (inj.advance(), inj.fire("page_alloc"))[1]]
    assert fired_at == [5, 6, 7]             # [start, stop) in injector ticks
    inj = FaultInjector(
        {"page_alloc": FaultSpec(rate=1.0, max_fires=4)}, seed=0
    )
    assert sum(_fire_pattern(inj, "page_alloc", 10)) == 4


def test_injector_burst_continues_across_window_edge():
    """A burst triggered inside the window keeps firing its remaining
    opportunities even past ``stop`` — a storm doesn't respect the bell."""
    inj = FaultInjector(
        {"cow_clone": FaultSpec(rate=1.0, stop=2, burst=4)}, seed=0
    )
    inj.advance()                             # tick 1: in window
    assert inj.fire("cow_clone")              # trigger; burst_left = 3
    for _ in range(4):
        inj.advance()                         # well past stop
    assert [inj.fire("cow_clone") for _ in range(4)] == [
        True, True, True, False
    ]
    assert inj.fires["cow_clone"] == 4


def test_injector_disabled_is_inert():
    inj = FaultInjector(
        {"page_alloc": FaultSpec(rate=1.0)}, enabled=False
    )
    assert not any(_fire_pattern(inj, "page_alloc", 5))
    assert inj.opportunities["page_alloc"] == 0   # counters untouched
    assert inj.total_fires == 0
    inj2 = FaultInjector({"cow_clone": FaultSpec(rate=1.0, burst=8)})
    inj2.advance()
    assert inj2.fire("cow_clone")
    inj2.stop_all()                           # kills the in-flight burst too
    assert not inj2.fire("cow_clone")


def test_injector_choose_deterministic_subset():
    mk = lambda: FaultInjector({"preempt_storm": FaultSpec(rate=1.0)}, seed=5)
    cands = list(range(10))
    picks = mk().choose(cands, 3)
    assert picks == mk().choose(cands, 3)
    assert len(picks) == 3 and len(set(picks)) == 3
    assert all(p in cands for p in picks)
    assert picks == sorted(picks)             # order-stable output
    assert mk().choose(cands, 99) and len(mk().choose(cands, 99)) == 10
    assert mk().choose([], 3) == []


def test_injector_as_dict_counters():
    inj = FaultInjector({"nan_output": FaultSpec(rate=1.0, max_fires=2)})
    _fire_pattern(inj, "nan_output", 4, per_tick=1)
    d = inj.as_dict()
    assert d["total_fires"] == 2
    assert d["points"]["nan_output"]["opportunities"] == 4
    assert d["points"]["nan_output"]["fires"] == 2


# -------------------------------------------------------------------- gauge
def test_gauge_tracks_peak_and_nonzero_ticks():
    g = Gauge()
    for v in (0, 2, 5, 1, 0):
        g.set(v)
    d = g.as_dict()
    assert g.value == 0 and g.peak == 5
    assert d["updates"] == 5 and d["ticks_nonzero"] == 3


# -------------------------------------------------------------- pool repair
def test_pool_repair_fixes_refcounts_and_recovers_leaks():
    pool = KVPagePool(10, page_size=4)
    a = pool.alloc("a", 3)
    pool.share("b", a[:2])
    # corruption: wrong refcount + a leaked page (neither held nor free)
    pool._refcount[a[0]] += 2
    leaked = pool._free.pop()
    with pytest.raises(AssertionError):
        pool.check()
    fixed = pool.repair()
    assert fixed["refcount_fixes"] == 1
    assert fixed["leaked_pages"] == 1 and leaked in pool._free
    pool.check()
    assert pool.stats.repairs == 1
    # holders kept their pages through the repair
    assert pool.pages_of("a") == a and pool.pages_of("b") == a[:2]


def test_pool_repair_drops_duplicate_and_invalid_holdings():
    pool = KVPagePool(10, page_size=4)
    a = pool.alloc("a", 2)
    pool._seq_pages["a"] = a + [a[0], 0, 99]      # dup + null + out-of-range
    fixed = pool.repair()
    assert fixed["dropped_holdings"] == 3
    assert pool.pages_of("a") == a
    pool.check()


def test_pool_repair_is_noop_when_consistent():
    pool = KVPagePool(10, page_size=4)
    pool.alloc("a", 3)
    pool.share("b", pool.pages_of("a")[:1])
    before_free = list(pool._free)
    fixed = pool.repair()
    assert all(v == 0 for v in fixed.values())
    assert pool._free == before_free
    pool.check()


# ----------------------------------------------- ctx-overflow warning dedupe
def test_note_ctx_overflow_counts_all_warns_once():
    pool = KVPagePool(8, page_size=4)
    pool.alloc("s", 1)
    assert pool.note_ctx_overflow("s") is True
    assert pool.note_ctx_overflow("s") is False
    assert pool.note_ctx_overflow("s") is False
    assert pool.stats.ctx_overflows == 3
    # re-admission warns afresh
    pool.free_seq("s")
    pool.alloc("s", 1)
    assert pool.note_ctx_overflow("s") is True
    assert pool.stats.ctx_overflows == 4


def test_clamp_ctx_lens_dedupes_stuck_sequence_warning():
    pool = KVPagePool(8, page_size=4)
    pool.alloc(0, 1)
    note = pool.note_ctx_overflow
    with pytest.warns(RuntimeWarning, match="exceeds KV capacity"):
        assert _clamp_ctx_lens([7], [4], "t", note=note) == [4]
    with warnings.catch_warnings():           # same stuck seq: silent now
        warnings.simplefilter("error")
        assert _clamp_ctx_lens([8], [4], "t", note=note) == [4]
    assert pool.stats.ctx_overflows == 2
    # without a note callback the old warn-every-time behavior stands
    with pytest.warns(RuntimeWarning):
        _clamp_ctx_lens([8], [4], "t")


# ---------------------------------------------------------- trie crash-safety
def _populated_cache(n_pages=3):
    pool = KVPagePool(16, page_size=2)
    cache = RadixPrefixCache(pool)
    toks = list(range(2 * n_pages))
    pages = pool.alloc("donor", n_pages)
    assert cache.insert(toks, pages) == n_pages
    pool.free_seq("donor")
    return pool, cache, toks


def test_insert_is_all_or_nothing(monkeypatch):
    pool = KVPagePool(16, page_size=2)
    cache = RadixPrefixCache(pool)
    pages = pool.alloc("donor", 3)
    real_share, calls = pool.share, []

    def flaky_share(seq, pgs):
        calls.append(pgs)
        if len(calls) == 3:
            raise RuntimeError("injected share failure")
        return real_share(seq, pgs)

    monkeypatch.setattr(pool, "share", flaky_share)
    with pytest.raises(RuntimeError, match="injected share failure"):
        cache.insert(list(range(6)), pages)
    # the two nodes created before the crash were unwound
    assert len(cache) == 0
    assert cache.stats.aborted_inserts == 1
    assert not pool.holds(CACHE_SEQ)
    pool.free_seq("donor")
    assert pool.num_allocated == 0
    pool.check()
    cache.check()


def test_invalidate_pages_drops_node_and_subtree():
    pool, cache, toks = _populated_cache(3)
    chain = cache.match(toks).pages
    assert len(chain) == 3
    removed = cache.invalidate_pages([chain[1]])
    assert removed == 2                       # the node and its child
    assert cache.stats.invalidated_pages == 2
    m = cache.match(toks)
    assert m.pages == chain[:1]               # root child survives
    pool.check()
    cache.check()


def test_corrupt_trie_node_detected_and_repaired():
    pool, cache, toks = _populated_cache(3)
    rng = np.random.default_rng(0)
    assert corrupt_trie_node(cache, rng)
    with pytest.raises(AssertionError):
        cache.check()
    released = cache.repair()
    assert released == 3 and len(cache) == 0
    assert cache.stats.repairs == 1
    cache.check()
    pool.check()
    assert pool.num_allocated == 0            # cache refs fully released
    # an empty trie has nothing to corrupt
    assert not corrupt_trie_node(cache, rng)


# ---------------------------------------------------------- engine audits
def _guarded_engine(cfg, params, **gkw):
    return DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=2, cache_len=32, attn_backend="lean", num_workers=4,
        paged=PagedConfig(page_size=8, prefix_cache=True),
        guards=GuardConfig(audit_interval=1, **gkw),
    ))


def _submit_and_tick(eng, cfg, n_ticks=2, new=8):
    rng = np.random.default_rng(0)
    eng.submit(Request(uid=0, prompt=rng.integers(0, cfg.vocab_size, 9),
                       max_new_tokens=new))
    for _ in range(n_ticks):
        eng.tick()
    return eng


def test_audit_action_raise_surfaces_fatal_invariant(setup):
    cfg, params = setup
    eng = _submit_and_tick(_guarded_engine(cfg, params), cfg)
    assert eng.stats.audits_run >= 2 and eng.stats.audit_failures == 0
    eng.pool._refcount[eng.pool.pages_of(0)[0]] += 1
    with pytest.raises(FatalInvariantError):
        eng.tick()
    assert eng.stats.audit_failures == 1


def test_audit_action_repair_heals_pool_in_place(setup):
    cfg, params = setup
    eng = _submit_and_tick(
        _guarded_engine(cfg, params, audit_action="repair"), cfg
    )
    pages = eng.pool.pages_of(0)
    eng.pool._refcount[pages[0]] += 1
    eng.tick()                                # audit repairs, tick completes
    assert eng.stats.audit_failures == 1
    assert eng.stats.audit_repairs == 1
    assert eng.pool.pages_of(0) == pages      # holdings survived the rebuild
    eng.pool.check()
    eng.run_to_completion(max_ticks=40)
    eng.pool.check()


def test_audit_action_log_counts_and_continues(setup):
    cfg, params = setup
    eng = _submit_and_tick(
        _guarded_engine(cfg, params, audit_action="log"), cfg
    )
    eng.pool._refcount[eng.pool.pages_of(0)[0]] += 1
    with pytest.warns(RuntimeWarning, match="audit failed"):
        eng.tick()
    assert eng.stats.audit_failures >= 1 and eng.stats.audit_repairs == 0


def test_guarded_engine_tokens_identical_when_healthy(setup):
    """Guards attached but nothing failing: token streams must be
    byte-identical to the unguarded engine (the no-behavior-change half of
    the zero-overhead contract; the perf half is gated in CI)."""
    cfg, params = setup
    outs = {}
    for guarded in (False, True):
        rng = np.random.default_rng(4)
        reqs = [
            Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 6 + 5 * i),
                    max_new_tokens=5)
            for i in range(3)
        ]
        eng = DecodeEngine(cfg, params, config=EngineConfig(
            max_batch=2, cache_len=32, attn_backend="lean", num_workers=4,
            paged=PagedConfig(page_size=8),
            faults=FaultInjector({}, enabled=False) if guarded else None,
            guards=GuardConfig(audit_interval=2) if guarded else None,
        ))
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion(max_ticks=60)
        outs[guarded] = [tuple(r.generated) for r in reqs]
        if guarded:
            assert eng.stats.nan_ticks == 0
            assert eng.stats.audits_run > 0
            assert eng.degraded_gauge.peak == 0
    assert outs[True] == outs[False]


# --------------------------------------------- scheduler deadlines / backoff
def _sched(cfg, params, *, max_batch=2, num_pages=None, chunked=None, **skw):
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=max_batch, cache_len=32, attn_backend="ref",
        paged=PagedConfig(page_size=8, num_pages=num_pages),
    ))
    return Scheduler(eng, SchedulerConfig(
        chunk_size=8, prefill_pack=1, token_budget=16, chunked=chunked,
        **skw,
    )), eng


def test_deadline_miss_requeues_then_poison_fails(setup):
    cfg, params = setup
    sch, eng = _sched(cfg, params, max_batch=1,
                      deadline_steps=2, max_deadline_misses=2)
    rng = np.random.default_rng(0)
    hog = sch.submit(rng.integers(0, cfg.vocab_size, 4), 1_000_000)
    sch.step()
    late = sch.submit(rng.integers(0, cfg.vocab_size, 4), 4)
    for _ in range(30):
        sch.step()
        if late.state is RequestState.FAILED:
            break
    assert late.state is RequestState.FAILED
    assert "TTFT deadline" in late.error and "missed 2x" in late.error
    assert sch.stats.deadline_expirations == 2
    assert sch.stats.poisoned == 1
    assert late.uid not in sch.requests       # terminal: no longer tracked
    # the hog was never disturbed
    assert hog.state is RequestState.DECODING and len(hog.generated) > 5
    assert sch.cancel(hog.uid)
    eng.pool.check()


def test_deadline_expiry_preempts_prefilling_slot(setup):
    """A long prompt still PREFILLING at its deadline is pulled off its
    slot (pages released) and later poison-failed — the slot is usable by
    others, not wedged."""
    cfg, params = setup
    sch, eng = _sched(cfg, params, max_batch=1, chunked=True,
                      deadline_steps=1, max_deadline_misses=2,
                      retry_backoff=1)
    rng = np.random.default_rng(1)
    # 30-token prompt at chunk_size=8 needs 4 chunked steps > deadline 1
    long = sch.submit(rng.integers(0, cfg.vocab_size, 30), 4)
    saw_prefilling = False
    for _ in range(40):
        sch.step()
        saw_prefilling |= long.state is RequestState.PREFILLING
        if long.state is RequestState.FAILED:
            break
    assert saw_prefilling
    assert long.state is RequestState.FAILED
    assert eng.stats.preemptions >= 1
    assert not any(r is not None for r in eng.slot_req)   # slot freed
    eng.pool.check()
    assert eng.pool.num_allocated == 0


def test_generous_deadline_never_expires(setup):
    cfg, params = setup
    sch, eng = _sched(cfg, params, deadline_steps=200)
    rng = np.random.default_rng(2)
    h = sch.submit(rng.integers(0, cfg.vocab_size, 6), 4)
    sch.run_to_completion(max_steps=100)
    assert h.done and len(h.generated) == 4
    assert sch.stats.deadline_expirations == 0
    eng.pool.check()


def test_cancel_across_lifecycle_states(setup):
    cfg, params = setup
    sch, eng = _sched(cfg, params, max_batch=1)
    rng = np.random.default_rng(3)
    running = sch.submit(rng.integers(0, cfg.vocab_size, 6), 1_000_000)
    sch.step()
    queued = sch.submit(rng.integers(0, cfg.vocab_size, 6), 4)
    sch.step()
    assert queued.state is RequestState.QUEUED
    assert sch.cancel(queued.uid) and queued.state is RequestState.CANCELLED
    assert running.state is RequestState.DECODING
    assert sch.cancel(running.uid)
    assert running.state is RequestState.CANCELLED
    assert sch.cancel(running.uid) is False   # already terminal
    assert sch.cancel(12345) is False         # unknown
    assert sch.stats.cancellations == 2
    eng.pool.check()
    assert eng.pool.num_allocated == 0


def test_admit_backoff_bounded_exponential(setup):
    """Blocking admission against an exhausted pool: with retry_backoff
    configured the blocked request delays exponentially instead of
    hammering every step, and admits once capacity frees."""
    cfg, params = setup
    # pool = 2 usable pages; each request needs 2 pages (16 tokens @ ps=8)
    sch, eng = _sched(cfg, params, num_pages=3, chunked=False,
                      retry_backoff=2, retry_backoff_cap=8)
    rng = np.random.default_rng(4)
    first = sch.submit(rng.integers(0, cfg.vocab_size, 8), 6)
    blocked = sch.submit(rng.integers(0, cfg.vocab_size, 8), 4)
    sch.run_to_completion(max_steps=100)
    assert first.done and blocked.done
    assert len(blocked.generated) == 4
    assert sch.stats.admit_backoffs >= 1
    eng.pool.check()
    assert eng.pool.num_allocated == 0


def test_max_preemptions_poison_fails_thrashing_request(setup):
    cfg, params = setup
    sch, eng = _sched(cfg, params, max_batch=1, max_preemptions=1)
    rng = np.random.default_rng(5)
    h = sch.submit(rng.integers(0, cfg.vocab_size, 6), 1_000_000)
    for round_ in range(2):
        for _ in range(3):
            sch.step()
        assert h.state is RequestState.DECODING
        eng.preempt_slot(h.slot)              # forced thrash
        if h.state is RequestState.FAILED:
            break
    assert h.state is RequestState.FAILED
    assert "max_preemptions=1" in h.error
    assert sch.stats.poisoned == 1
    eng.pool.check()
    assert eng.pool.num_allocated == 0


def test_pool_exhaustion_mid_cascade_recovers_token_identical(setup):
    """Satellite: pool exhaustion while the cascade path is live. Shared-
    prefix requests group on the cascade fast path; a pool squeezed so
    decode-page allocation fails mid-flight forces preemption +
    recompute-resume *out of a cascade group* — tokens must match the
    same workload on an ample pool, with zero leaks after the drain."""
    cfg, params = setup
    rng = np.random.default_rng(6)
    shared = rng.integers(0, cfg.vocab_size, 16)
    prompts = [
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, 2 + i)])
        for i in range(3)
    ]
    outs = {}
    for tight in (False, True):
        eng = DecodeEngine(cfg, params, config=EngineConfig(
            max_batch=4, cache_len=64, attn_backend="lean", num_workers=4,
            paged=PagedConfig(
                page_size=8,
                num_pages=8 if tight else None,   # 7 usable vs ample
                prefix_cache=True,
            ),
            cascade=CascadeConfig(enabled=True, stable_ticks=1),
        ))
        sch = Scheduler(eng, SchedulerConfig(
            chunk_size=8, prefill_pack=2, token_budget=32,
        ))
        donor = sch.submit(np.concatenate([shared, [1]]), 2)
        sch.run_to_completion(max_steps=100)
        assert donor.done
        hs = [sch.submit(p, max_new_tokens=10) for p in prompts]
        sch.run_to_completion(max_steps=500)
        assert all(h.done for h in hs)
        outs[tight] = [tuple(h.generated) for h in hs]
        if tight:
            assert eng.pool.stats.failed_allocs > 0
            assert eng.stats.preemptions > 0
        else:
            assert eng.stats.cascade_ticks > 0
        eng.pool.check()
        eng.prefix_cache.check()
    assert outs[True] == outs[False]
