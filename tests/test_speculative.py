"""Speculative (draft-verify) decode: greedy token-identity with plain
decode across backends/layouts, rejected-draft rollback invariants under
churn, accept-rate telemetry, and proposer unit behavior.

The identity contract is the whole safety story: because the verify sweep
scores drafts with the *target* model and keeps only the prefix it agrees
with, the emitted stream must equal non-speculative greedy decode token for
token — any divergence is a bug, not a quality trade-off.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serving.config import (
    CascadeConfig,
    EngineConfig,
    PagedConfig,
    SpecConfig,
)
from repro.serving.engine import DecodeEngine, Request
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.serving.speculative import NGramProposer, OracleProposer

jax.config.update("jax_platform_name", "cpu")


_CACHE = {}


def _setup():
    # module-level cache instead of a fixture: the hypothesis @given wrapper
    # exposes an empty signature, so fixture params can't reach it
    if "cp" not in _CACHE:
        cfg = get_smoke_config("mistral-nemo-12b")
        _CACHE["cp"] = (cfg, init_params(jax.random.PRNGKey(0), cfg))
    return _CACHE["cp"]


@pytest.fixture(scope="module")
def setup():
    return _setup()


def _requests(cfg, n=3, seed=0, new=10):
    rng = np.random.default_rng(seed)
    return [
        Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, 8 + 5 * i),
            max_new_tokens=new,
        )
        for i in range(n)
    ]


def _engine(cfg, params, *, backend="ref", spec=None, kv_dtype=None,
            cascade=False, **kw):
    return DecodeEngine(
        cfg, params,
        config=EngineConfig(
            max_batch=4, cache_len=64, attn_backend=backend, num_workers=8,
            paged=PagedConfig(
                enabled=True, page_size=8, kv_dtype=kv_dtype,
                prefix_cache=cascade,
            ),
            cascade=CascadeConfig(enabled=cascade),
            spec=spec if spec is not None else SpecConfig(),
            **kw,
        ),
    )


def _run(eng, reqs, max_ticks=300):
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_ticks=max_ticks)
    return {r.uid: list(r.generated) for r in reqs}


def _reference(cfg, params, backend="ref", kv_dtype=None, cascade=False,
               new=10):
    # memoized: greedy baselines are deterministic, and the hypothesis
    # rollback test would otherwise recompute one per drawn example
    key = (backend, kv_dtype, cascade, new)
    if key not in _CACHE:
        _CACHE[key] = _run(
            _engine(cfg, params, backend=backend, kv_dtype=kv_dtype,
                    cascade=cascade),
            _requests(cfg, new=new),
        )
    return {k: list(v) for k, v in _CACHE[key].items()}


# ------------------------------------------------------------ token identity
@pytest.mark.parametrize(
    "backend,kv_dtype",
    [("ref", None), ("lean", None), ("ref", "int8"), ("lean", "int8")],
)
def test_spec_token_identity(setup, backend, kv_dtype):
    """Greedy speculative output == non-speculative greedy, per config."""
    cfg, params = setup
    ref = _reference(cfg, params, backend=backend, kv_dtype=kv_dtype)
    spec = SpecConfig(enabled=True, k=4, proposer=OracleProposer(ref))
    eng = _engine(cfg, params, backend=backend, kv_dtype=kv_dtype, spec=spec)
    got = _run(eng, _requests(cfg))
    assert got == ref
    # 100%-accept oracle: every draft verified, far fewer ticks
    assert eng.stats.spec_accepted_tokens == eng.stats.spec_draft_tokens > 0
    assert eng.stats.spec_ticks > 0


@pytest.mark.parametrize("cascade", [False, True])
def test_spec_token_identity_cascade(setup, cascade):
    cfg, params = setup
    ref = _reference(cfg, params, backend="lean", cascade=cascade)
    spec = SpecConfig(enabled=True, k=3, proposer=OracleProposer(ref))
    got = _run(
        _engine(cfg, params, backend="lean", cascade=cascade, spec=spec),
        _requests(cfg),
    )
    assert got == ref


def test_spec_ngram_proposer_identity_and_graceful_drafts(setup):
    """The in-tree prompt-lookup proposer: identity holds at ANY accept
    rate (rejected drafts cost throughput, never correctness)."""
    cfg, params = setup
    ref = _reference(cfg, params, backend="ref")
    eng = _engine(cfg, params, spec=SpecConfig(enabled=True, k=4))
    got = _run(eng, _requests(cfg))
    assert got == ref


def test_spec_partial_accept_identity(setup):
    """Corrupted oracle (accept_rate < 1): rejection mid-block trims the
    draft tail and the stream stays identical."""
    cfg, params = setup
    ref = _reference(cfg, params, backend="ref", new=12)
    spec = SpecConfig(
        enabled=True, k=4,
        proposer=OracleProposer(ref, accept_rate=0.6, seed=7),
    )
    eng = _engine(cfg, params, spec=spec)
    got = _run(eng, _requests(cfg, new=12))
    assert got == ref
    assert 0 < eng.stats.spec_accepted_tokens < eng.stats.spec_draft_tokens


def test_spec_dense_nonspec_matches_paged_spec(setup, model_loop):
    """Cross-layout: the plain batch-1 model loop over a dense cache ==
    the paged speculative ref engine."""
    cfg, params = setup
    ref = {
        r.uid: model_loop(cfg, params, r.prompt, r.max_new_tokens, 64)
        for r in _requests(cfg)
    }
    spec = SpecConfig(enabled=True, k=4, proposer=OracleProposer(ref))
    got = _run(_engine(cfg, params, spec=spec), _requests(cfg))
    assert got == ref


# ------------------------------------------------------------- tick contract
def test_spec_tick_returns_token_lists_and_budget_width(setup):
    cfg, params = setup
    ref = _reference(cfg, params)
    eng = _engine(
        cfg, params,
        spec=SpecConfig(enabled=True, k=4, proposer=OracleProposer(ref)),
    )
    assert eng.decode_token_width() == 5
    for r in _requests(cfg):
        eng.submit(r)
    eng._admit()
    out = eng.decode_tick()
    assert out and all(isinstance(v, list) and 1 <= len(v) <= 5
                       for v in out.values())
    plain = _engine(cfg, params)
    assert plain.decode_token_width() == 1


def test_spec_scheduler_streams_every_token_once(setup):
    """Scheduler over a speculative engine: chunked prefill + variable
    accepted-tokens-per-tick, every token streamed exactly once, done=True
    only on the final one."""
    cfg, params = setup
    prompts = [np.arange(1, 9 + 3 * i) % cfg.vocab_size for i in range(3)]
    base = Scheduler(
        _engine(cfg, params, backend="lean"),
        SchedulerConfig(chunk_size=16, token_budget=32),
    )
    handles = [base.submit(p, 10) for p in prompts]
    base.run_to_completion()
    ref = {h.uid: list(h.generated) for h in handles}

    streams = {}
    spec = SpecConfig(enabled=True, k=4, proposer=OracleProposer(ref))
    sch = Scheduler(
        _engine(cfg, params, backend="lean", spec=spec),
        SchedulerConfig(chunk_size=16, token_budget=32),
    )
    hs = [
        sch.submit(
            p, 10,
            on_token=lambda uid, t, done:
                streams.setdefault(uid, []).append((t, done)),
        )
        for p in prompts
    ]
    sch.run_to_completion()
    for h in hs:
        assert list(h.generated) == ref[h.uid]
        assert [t for t, _ in streams[h.uid]] == ref[h.uid]
        flags = [d for _, d in streams[h.uid]]
        assert flags[-1] is True and not any(flags[:-1])
    tel = sch.telemetry()
    assert tel["spec_ticks"] > 0
    assert tel["spec_accept_rate"] == 1.0
    assert tel["spec_draft_tokens"] == tel["spec_accepted_tokens"] > 0


def test_spec_accept_rate_gauge(setup):
    cfg, params = setup
    ref = _reference(cfg, params)
    eng = _engine(
        cfg, params,
        spec=SpecConfig(
            enabled=True, k=4,
            proposer=OracleProposer(ref, accept_rate=0.5, seed=3),
        ),
    )
    _run(eng, _requests(cfg))
    snap = eng.metrics.as_dict()
    rate = snap["engine_spec_accept_rate"]   # callback gauge -> bare float
    assert 0.0 < rate < 1.0
    expect = eng.stats.spec_accepted_tokens / max(
        1, eng.stats.spec_draft_tokens
    )
    assert rate == pytest.approx(expect)


def test_spec_requires_chunked_prefill_machinery():
    # window rings and recurrent state have no chunk-resume plumbing
    cfg = get_smoke_config("recurrentgemma-9b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="speculative"):
        DecodeEngine(
            cfg, params,
            config=EngineConfig(spec=SpecConfig(enabled=True, k=4)),
        )


# ------------------------------------------------------- rollback invariants
class _AdversarialProposer:
    """Seeded random garbage drafts of random length — worst-case
    rejection churn for the rollback path."""

    def __init__(self, vocab, seed=0):
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)

    def propose(self, req, k):
        n = int(self.rng.integers(0, k + 1))
        return [int(t) for t in self.rng.integers(0, self.vocab, n)]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_spec_rejected_draft_rollback_pool_invariants(seed):
    """Under adversarial draft churn the pool must stay clean: rejected
    blocks roll ctx_lens back without freeing, leaking, or aliasing pages
    (pool.check() audits the full invariant set), and output stays
    identical to plain greedy decode."""
    cfg, params = _setup()
    ref = _reference(cfg, params)
    eng = _engine(
        cfg, params,
        spec=SpecConfig(
            enabled=True, k=4,
            proposer=_AdversarialProposer(cfg.vocab_size, seed),
        ),
    )
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    while (eng.queue or any(eng.slot_req)) and eng.stats.ticks < 300:
        eng.tick()
        eng.pool.check()
    assert {r.uid: list(r.generated) for r in reqs} == ref
    eng.pool.check()  # raises on any leak/alias/refcount violation


def test_spec_rollback_trims_page_table_tail(setup):
    """A rejected block leaves its pages allocated (trimmed tail, no
    scatter undo): after a full-rejection tick the slot keeps any pages
    grown for the draft block, and the next tick reuses them."""
    cfg, params = setup

    class _Reject:
        def propose(self, req, k):
            # always-colliding garbage (vocab-1 repeated) — rejects unless
            # the model actually predicts it
            return [cfg.vocab_size - 1] * k

    eng = _engine(cfg, params,
                  spec=SpecConfig(enabled=True, k=4, proposer=_Reject()))
    r = _requests(cfg, n=1)[0]
    eng.submit(r)
    eng._admit()
    slot = next(s for s in range(eng.max_batch) if eng.slot_req[s] is r)
    eng.decode_tick()
    ctx = int(eng.ctx_lens[slot])
    pages_before = eng.pool.count(slot)
    # pages cover the whole R-row block even though ctx only advanced past
    # the accepted prefix
    assert pages_before * eng.tile >= ctx
    eng.decode_tick()
    eng.pool.check()
    assert eng.pool.count(slot) >= pages_before - 1  # no mass free-on-reject


# -------------------------------------------------------------------- chaos
@pytest.mark.chaos
def test_spec_nan_during_verify_poisons_without_neighbor_damage(setup):
    """nan_output fired during verify ticks: the struck slot emits nothing
    that tick and degrades (falling back to plain decode while degraded),
    neighbors keep their exact streams, and with guards on the final output
    is still token-identical to the fault-free run."""
    from repro.serving.faults import FaultInjector, FaultSpec
    from repro.serving.guards import GuardConfig

    cfg, params = setup
    ref = _reference(cfg, params, backend="lean", new=12)
    spec = SpecConfig(enabled=True, k=4, proposer=OracleProposer(ref))
    inj = FaultInjector(
        {"nan_output": FaultSpec(rate=1.0, start=2, stop=5)}, seed=1
    )
    eng = _engine(
        cfg, params, backend="lean", spec=spec,
        faults=inj, guards=GuardConfig(heal_after=2),
    )
    got = _run(eng, _requests(cfg, new=12), max_ticks=400)
    assert got == ref
    assert inj.fires.get("nan_output", 0) > 0
    assert eng.stats.nan_ticks > 0
    assert eng.stats.poisoned_slots == 0
    eng.pool.check()


# --------------------------------------------------------------- proposers
def test_ngram_proposer_prompt_lookup():
    p = NGramProposer(n=2)
    req = Request(uid=0, prompt=np.array([1, 2, 3, 9, 1, 2]),
                  max_new_tokens=8)
    # tail bigram (1, 2) matched at the prompt head -> propose 3, 9, ...
    assert p.propose(req, 2) == [3, 9]
    assert p.propose(req, 4) == [3, 9, 1, 2]


def test_ngram_proposer_no_match_is_empty():
    p = NGramProposer(n=3, min_n=2)
    req = Request(uid=0, prompt=np.array([1, 2, 3, 4]), max_new_tokens=8)
    assert p.propose(req, 4) == []


def test_oracle_proposer_replay_and_corruption():
    stream = list(range(10, 30))
    req = Request(uid=5, prompt=np.array([1, 2]), max_new_tokens=20)
    exact = OracleProposer({5: stream})
    assert exact.propose(req, 4) == stream[:4]
    req.generated.extend(stream[:3])
    assert exact.propose(req, 4) == stream[3:7]
    noisy = OracleProposer({5: stream}, accept_rate=0.0, seed=1)
    drafts = noisy.propose(req, 4)
    assert len(drafts) == 4
    assert all(d != t for d, t in zip(drafts, stream[3:7]))
    # determinism
    assert noisy.propose(req, 4) == drafts
    # unknown uid -> no drafts
    assert exact.propose(
        Request(uid=99, prompt=np.array([1]), max_new_tokens=4), 4
    ) == []
