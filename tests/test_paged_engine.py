"""Paged serving engine: admission/free lifecycle, pool accounting, token
equivalence with a plain batch-1 model loop, and churn stress (marked
slow)."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serving.config import EngineConfig, PagedConfig
from repro.serving.engine import DecodeEngine, Request

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, n=3, seed=0, plen=lambda i: 8 + 7 * i, new=6):
    rng = np.random.default_rng(seed)
    return [
        Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, plen(i)),
            max_new_tokens=new,
        )
        for i in range(n)
    ]


def _engine(cfg, params, *, page_size=None, num_pages=None, kv_dtype=None,
            **kw):
    return DecodeEngine(cfg, params, config=EngineConfig(
        paged=PagedConfig(
            page_size=page_size, num_pages=num_pages, kv_dtype=kv_dtype,
        ),
        **kw,
    ))


def _run(cfg, params, reqs, max_ticks=80, **kw):
    eng = _engine(cfg, params, **kw)
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_ticks=max_ticks)
    return eng


@pytest.mark.parametrize(
    "arch, backend",
    [
        (arch, backend)
        for arch in ("mistral-nemo-12b", "recurrentgemma-9b", "xlstm-350m",
                     "nemotron-4-15b")
        for backend in ("ref", "lean")
    ] + [("gemma3-4b", "lean")],
)
def test_paged_engine_tokens_match_model_loop(model_loop, arch, backend):
    """Ragged continuous batching over the page pool (3 requests through 2
    slots) gives each request the tokens of its own batch-1 model loop —
    global-attention pools, window rings, recurrent and mLSTM/sLSTM state
    alike."""
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(cfg, new=5)
    _run(cfg, params, reqs, max_batch=2, cache_len=64, num_workers=4,
         attn_backend=backend, page_size=16)
    for r in reqs:
        assert list(r.generated) == model_loop(
            cfg, params, r.prompt, 5, cache_len=64
        ), r.uid


def test_paged_fresh_admit_single_token_prompt(setup, model_loop):
    """ctx==0 freshly-admitted edge at the engine level: a 1-token prompt
    admitted into an otherwise idle paged engine decodes identically to the
    batch-1 model loop from its very first tick."""
    cfg, params = setup
    reqs = _requests(cfg, n=1, plen=lambda i: 1, new=3)
    _run(cfg, params, reqs, max_batch=2, cache_len=32,
         attn_backend="ref", page_size=8)
    assert list(reqs[0].generated) == model_loop(
        cfg, params, reqs[0].prompt, 3, cache_len=32
    )


def test_pool_accounting_no_leaks_after_drain(setup):
    cfg, params = setup
    reqs = _requests(cfg, n=5, seed=3)
    eng = _run(cfg, params, reqs, max_batch=2, cache_len=64,
               attn_backend="ref", page_size=16)
    eng.pool.check()
    assert eng.pool.num_allocated == 0
    assert eng.pool.num_free == eng.pool.usable_pages
    assert eng.pool.stats.pages_allocated == eng.pool.stats.pages_freed
    assert eng.stats.kv_pool["high_water"] > 0
    assert all(len(r.generated) == 6 for r in reqs)


def test_pool_admit_evict_hooks_fire(setup):
    cfg, params = setup
    eng = _engine(cfg, params, max_batch=2, cache_len=64,
                  attn_backend="ref", page_size=16)
    events = []
    eng.pool.on_admit.append(lambda seq, pages: events.append(("+", seq, len(pages))))
    eng.pool.on_evict.append(lambda seq, pages: events.append(("-", seq, len(pages))))
    for r in _requests(cfg, n=2):
        eng.submit(r)
    eng.run_to_completion(max_ticks=60)
    admitted = sum(n for op, _, n in events if op == "+")
    evicted = sum(n for op, _, n in events if op == "-")
    assert admitted > 0 and admitted == evicted


def test_infeasible_request_fails_fast_not_livelock(setup):
    """A request whose minimum page working set exceeds the whole pool can
    never be served; admission must raise a diagnosable error instead of
    silently retrying (or prefill+preempt cycling) forever."""
    cfg, params = setup
    # 2 usable pages of 16 tokens; a 64-token prompt needs 4
    eng = _engine(cfg, params, max_batch=2, cache_len=64,
                  attn_backend="ref", page_size=16, num_pages=3)
    eng.submit(Request(uid=0, prompt=np.arange(64) % cfg.vocab_size,
                       max_new_tokens=2))
    with pytest.raises(RuntimeError, match="usable pages"):
        eng.run_to_completion(max_ticks=10)
    # prompt fits exactly but the first decode write does not: also caught
    eng2 = _engine(cfg, params, max_batch=2, cache_len=64,
                   attn_backend="ref", page_size=16, num_pages=2)
    eng2.submit(Request(uid=1, prompt=np.arange(16) % cfg.vocab_size,
                        max_new_tokens=2))
    with pytest.raises(RuntimeError, match="usable pages"):
        eng2.run_to_completion(max_ticks=10)


def test_schedule_cache_hit_rate_stays_high_under_paging(setup):
    cfg, params = setup
    reqs = _requests(cfg, n=6, seed=5, new=8)
    eng = _run(cfg, params, reqs, max_ticks=200, max_batch=2, cache_len=64,
               attn_backend="ref", page_size=16)
    st = eng.stats.schedule_cache
    assert st["hit_rate"] > 0.5, st
    assert st["hits"] >= eng.stats.ticks - st["misses"]


@pytest.mark.slow
def test_paged_lifecycle_churn_stress(setup):
    """Admit/finish/re-admit churn over many ticks against an undersized
    pool: every tick upholds the pool invariants, preemption fires and
    recovers, all requests eventually complete with their full budget, no
    pages leak, and the schedule cache keeps hitting."""
    cfg, params = setup
    rng = np.random.default_rng(42)
    eng = _engine(cfg, params, max_batch=4, cache_len=64,
                  attn_backend="ref", page_size=8,
                  num_pages=1 + 3 * 8)    # 24 usable vs 32 for every slot
    reqs = [
        Request(uid=i,
                prompt=rng.integers(0, cfg.vocab_size, int(rng.integers(1, 30))),
                max_new_tokens=int(rng.integers(6, 24)))
        for i in range(24)
    ]
    # staggered submission: three waves to force finish-then-readmit churn
    for wave in range(3):
        for r in reqs[wave * 8 : (wave + 1) * 8]:
            eng.submit(r)
        for _ in range(40):
            eng.tick()
            eng.pool.check()
            live = {s for s in range(eng.max_batch) if eng.slot_req[s]}
            assert eng.pool.live_sequences <= len(live) + 1
            if not eng.queue and not any(eng.slot_req):
                break
    eng.run_to_completion(max_ticks=2000)
    for r in reqs:
        assert len(r.generated) >= r.max_new_tokens, r.uid
    eng.pool.check()
    assert eng.pool.num_allocated == 0
    assert eng.pool.stats.pages_allocated == eng.pool.stats.pages_freed
    assert eng.stats.schedule_cache["hit_rate"] > 0.5
    # the pool really was the constraint at some point
    assert eng.stats.kv_pool["high_water"] >= int(0.75 * eng.pool.usable_pages)


@pytest.mark.parametrize(
    "budget, kv_dtype",
    [(None, "bf16"), (0, "bf16"), (None, "int8")],
    ids=["default", "no_vmem", "int8"],
)
def test_fused_decode_ticks_follow_the_vmem_gate(
    setup, monkeypatch, budget, kv_dtype
):
    """``fused_decode_ticks`` counts the lean decode ticks that ran the
    fused kernel: all of them under the default VMEM budget, none when the
    budget admits nothing. The kernel the decode steps traced agrees with
    the count, the engine gates with the pool's element width, and the
    tokens are the same either way."""
    from repro.kernels import ops

    cfg, params = setup
    traced = []
    fused_kernel = ops.lean_decode_paged_fused

    def spy(*a, **kw):
        traced.append(True)
        return fused_kernel(*a, **kw)

    monkeypatch.setattr(ops, "lean_decode_paged_fused", spy)
    if budget is not None:
        monkeypatch.setattr(ops, "FUSED_VMEM_BUDGET", budget)
    kw = dict(max_batch=2, cache_len=32, num_workers=4, attn_backend="lean",
              page_size=8, kv_dtype=kv_dtype)
    reqs = _requests(cfg, new=4)
    eng = _run(cfg, params, reqs, **kw)
    assert eng.kv_elem_bytes == eng.cache[0][0]["k"].dtype.itemsize
    assert eng.stats.ticks > 0
    want = eng.stats.ticks if budget is None else 0
    assert eng.stats.fused_decode_ticks == want
    assert bool(traced) == (want > 0)
    monkeypatch.undo()
    ref = _requests(cfg, new=4)
    _run(cfg, params, ref, **kw)
    assert [r.generated for r in reqs] == [r.generated for r in ref]
