"""Cascade v2 (prefix-grouped) decode: numerics, bit-identity, LCP
grouping, fused single-kernel execution, engine parity.

The sharing contract has two layers, each with its own strongest-true
assertion:

  * **page aliasing is bit-neutral** — the production decode path shares
    physical pages through the page table while keeping the unshared
    stream-K schedule; output is asserted BIT-identical to the same decode
    over per-sequence duplicated pages (same schedule, same shapes, same
    values ⇒ same bits, by construction);
  * **the cascade regrouping is exact** — the grouped prefix pass(es) +
    suffix pass + merge is the associative softmax reduction re-bracketed,
    so it is asserted bit-identical under sharing vs duplicated pages
    (equal schedule + binding), and fp32-tight against the vanilla
    unshared paged decode and the dense reference oracle (a stream-K
    repartition re-associates the reduction, like any worker-count
    change). This holds on BOTH execution modes: the fused single-kernel
    flat grid and the two-call + XLA-merge fallback.

Grouping layer: ``lcp_group_passes`` walks the compressed radix trie of
the slots' shared page paths — requests matching 3 and 5 pages of one
chain group at 3, and nested subsets stack one pass per trie level.

Engine level: a cascade engine must generate token-identical streams to
the plain paged lean engine under mixed-depth prefix matches, group
collapse (fall back to vanilla decode), mid-page divergence,
admission/finish churn (hypothesis fuzz), and the grouping stability
guard.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.attention import paged_gather_kv
from repro.core.leantile import (
    ScheduleCache,
    cascade_fused_descriptors,
    make_cascade_schedule,
)
from repro.kernels.ops import (
    cascade_tables,
    cascade_uses_fused,
    lean_decode_cascade,
    lean_decode_paged,
)
from repro.kernels.ref import lean_decode_ref
from repro.serving.prefix_cache import lcp_group_passes

jax.config.update("jax_platform_name", "cpu")

GEOMS = [(4, 2, 16), (4, 1, 16), (3, 3, 8), (8, 2, 32)]   # GQA/MQA/MHA


def _shared_problem(rng, Hq, Hkv, d, ps, pp, suffixes, extra_groups=0):
    """Pool + tables where the first len(suffixes) sequences share a
    ``pp``-page prefix; optional extra singleton sequences follow."""
    B = len(suffixes) + extra_groups
    lens = [pp * ps + s for s in suffixes] + [
        ps + 3 * i for i in range(extra_groups)
    ]
    W = max(-(-L // ps) for L in lens) + 1
    total = sum(-(-L // ps) for L in lens) + pp * (len(suffixes) - 1)
    num_pages = 1 + total + 4
    k_pool = rng.standard_normal((num_pages, Hkv, ps, d)).astype(np.float32)
    v_pool = rng.standard_normal((num_pages, Hkv, ps, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, Hq, d)), jnp.float32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    shared = [int(free.pop()) for _ in range(pp)]
    ptbl = np.zeros((B, W), np.int32)
    for b, L in enumerate(lens):
        n = -(-L // ps)
        if b < len(suffixes):
            ptbl[b, :pp] = shared
            ptbl[b, pp:n] = [int(free.pop()) for _ in range(n - pp)]
        else:
            ptbl[b, :n] = [int(free.pop()) for _ in range(n)]
    groups = [list(range(len(suffixes)))] + [
        [len(suffixes) + i] for i in range(extra_groups)
    ]
    pps = [pp] + [0] * extra_groups
    return q, k_pool, v_pool, ptbl, lens, groups, pps, shared, free


def _duplicate_shared(k_pool, v_pool, ptbl, shared, free, members):
    """Unshare: give every member (past the first) its own copy of the
    shared pages — identical values on distinct physical pages."""
    k2, v2, p2 = k_pool.copy(), v_pool.copy(), ptbl.copy()
    free = list(free)
    for b in members[1:]:
        dup = [int(free.pop()) for _ in range(len(shared))]
        k2[dup] = k2[shared]
        v2[dup] = v2[shared]
        p2[b, : len(shared)] = dup
    return k2, v2, p2


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("geom", GEOMS)
def test_cascade_matches_oracle_and_paged(geom, fused):
    Hq, Hkv, d = geom
    ps, pp = 16, 3
    rng = np.random.default_rng(hash(geom) % 2**32)
    q, k_pool, v_pool, ptbl, lens, groups, pps, *_ = _shared_problem(
        rng, Hq, Hkv, d, ps, pp, suffixes=[5, 20, 33], extra_groups=1
    )
    kj, vj = jnp.asarray(k_pool), jnp.asarray(v_pool)
    ref = lean_decode_ref(
        q, paged_gather_kv(kj, jnp.asarray(ptbl)),
        paged_gather_kv(vj, jnp.asarray(ptbl)),
        ctx_lens=jnp.asarray(lens, jnp.int32),
    )
    paged = lean_decode_paged(
        q, kj, vj, ptbl, lens, num_workers=6, interpret=True
    )
    casc = lean_decode_cascade(
        q, kj, vj, ptbl, lens, groups, pps, num_workers=6, fused=fused,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(casc), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(casc), np.asarray(paged),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("geom", GEOMS)
def test_sharing_is_bit_identical_to_unshared(geom, fused):
    """THE sharing bit-identity assertions, per GQA/MQA geometry and per
    cascade execution mode:

    (a) default path — ``lean_decode_paged`` over an aliased table equals
        the same call over duplicated pages BIT-exactly (this is what the
        engine's prefix-sharing decode runs every tick);
    (b) cascade path — ``lean_decode_cascade`` under sharing equals the
        same cascade over duplicated pages BIT-exactly, on the fused
        single-kernel grid AND the two-call fallback (sharing the pass
        and the pages changes nothing vs. per-sequence copies).
    """
    Hq, Hkv, d = geom
    ps, pp = 8, 4
    rng = np.random.default_rng((hash(geom) + 7) % 2**32)
    q, k_pool, v_pool, ptbl, lens, groups, pps, shared, free = (
        _shared_problem(rng, Hq, Hkv, d, ps, pp, suffixes=[3, 9, 17, 6])
    )
    k2, v2, p2 = _duplicate_shared(k_pool, v_pool, ptbl, shared, free,
                                   members=groups[0])
    kj, vj = jnp.asarray(k_pool), jnp.asarray(v_pool)
    k2j, v2j = jnp.asarray(k2), jnp.asarray(v2)

    a1 = lean_decode_paged(q, kj, vj, ptbl, lens, num_workers=5,
                           interpret=True)
    a2 = lean_decode_paged(q, k2j, v2j, p2, lens, num_workers=5,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))

    c1 = lean_decode_cascade(q, kj, vj, ptbl, lens, groups, pps,
                             num_workers=5, fused=fused, interpret=True)
    c2 = lean_decode_cascade(q, k2j, v2j, p2, lens, groups, pps,
                             num_workers=5, fused=fused, interpret=True)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_fused_cascade_fits_budget_and_falls_back(monkeypatch):
    """The fused cascade gates on its VMEM footprint: under the default
    budget this problem runs fused; with the budget forced to zero the
    same call falls back to the two-call path and stays fp32-tight."""
    from repro.kernels import ops

    Hq, Hkv, d, ps, pp = 4, 2, 16, 16, 2
    rng = np.random.default_rng(5)
    q, k_pool, v_pool, ptbl, lens, groups, pps, *_ = _shared_problem(
        rng, Hq, Hkv, d, ps, pp, suffixes=[4, 9]
    )
    kj, vj = jnp.asarray(k_pool), jnp.asarray(v_pool)
    ref = lean_decode_ref(
        q, paged_gather_kv(kj, jnp.asarray(ptbl)),
        paged_gather_kv(vj, jnp.asarray(ptbl)),
        ctx_lens=jnp.asarray(lens, jnp.int32),
    )
    cs, _b = make_cascade_schedule(lens, groups, pps, Hkv, ps, 4)
    assert cascade_uses_fused(cs, Hq // Hkv, d)
    monkeypatch.setattr(ops, "FUSED_VMEM_BUDGET", 0)
    assert not cascade_uses_fused(cs, Hq // Hkv, d)
    out = lean_decode_cascade(q, kj, vj, ptbl, lens, groups, pps,
                              num_workers=4, fused=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_cascade_bucketed_cache_stays_exact_and_hits():
    """Cascade schedules built through the ScheduleCache bucket the suffix
    lengths; runtime masking keeps results exact, and a tick-over-tick
    length drift inside one bucket must HIT the cache."""
    Hq, Hkv, d, ps, pp = 4, 2, 16, 16, 2
    rng = np.random.default_rng(3)
    q, k_pool, v_pool, ptbl, lens, groups, pps, *_ = _shared_problem(
        rng, Hq, Hkv, d, ps, pp, suffixes=[4, 9, 13]
    )
    kj, vj = jnp.asarray(k_pool), jnp.asarray(v_pool)
    cache = ScheduleCache()
    ref = lean_decode_ref(
        q, paged_gather_kv(kj, jnp.asarray(ptbl)),
        paged_gather_kv(vj, jnp.asarray(ptbl)),
        ctx_lens=jnp.asarray(lens, jnp.int32),
    )
    out = lean_decode_cascade(q, kj, vj, ptbl, lens, groups, pps,
                              num_workers=4, schedule_cache=cache,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # +1 token on every suffix: same buckets, must hit
    lens2 = [n + 1 for n in lens]
    lean_decode_cascade(q, kj, vj, ptbl, lens2, groups, pps,
                        num_workers=4, schedule_cache=cache, interpret=True)
    assert cache.stats.hits >= 1


# --------------------------------------------------------------- grouping
def test_lcp_groups_at_longest_common_prefix():
    """Slots matching 3 and 5 pages of the same chain group at 3 — the
    old identical-run grouping would have found nothing."""
    paths = {0: (7, 8, 9), 1: (7, 8, 9, 10, 11)}
    assert lcp_group_passes(paths) == [((0, 1), 0, 3)]


def test_lcp_three_way_chain_groups_per_trie_level():
    """Three slots at depths 1/3/3 of one chain: multi-level emits the
    top-level LCP pass plus one nested pass for the deeper pair;
    single-level stops at the LCP."""
    paths = {0: (7,), 1: (7, 8, 9), 2: (7, 8, 9), 5: (20, 21)}
    assert lcp_group_passes(paths) == [((0, 1, 2), 0, 1), ((1, 2), 1, 2)]
    assert lcp_group_passes(paths, multi_level=False) == [((0, 1, 2), 0, 1)]


def test_lcp_divergence_mid_chain_groups_at_split():
    paths = {0: (7, 8, 9), 1: (7, 8, 12)}
    assert lcp_group_passes(paths) == [((0, 1), 0, 2)]


def test_lcp_singletons_emit_no_pass():
    assert lcp_group_passes({0: (1, 2), 1: (3, 4)}) == []
    assert lcp_group_passes({}) == []


@pytest.mark.parametrize("fused", [False, True])
def test_multi_level_passes_match_oracle(fused):
    """Nested trie passes (slots 0,1,2 share one page; 0,1 share two
    more) stack grouped passes per level and stay exact — the composable
    merge folds all levels plus the suffix."""
    rng = np.random.default_rng(1)
    Hq, Hkv, d, ps = 4, 2, 16, 8
    lens = [3 * ps + 5, 3 * ps + 11, ps + 7, ps + 3]
    B, W, num_pages = 4, 6, 40
    k_pool = rng.standard_normal((num_pages, Hkv, ps, d)).astype(np.float32)
    v_pool = rng.standard_normal((num_pages, Hkv, ps, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, Hq, d)), jnp.float32)
    free = list(range(1, num_pages))
    ptbl = np.zeros((B, W), np.int32)
    for b in range(B):
        for t in range(-(-lens[b] // ps)):
            ptbl[b, t] = free.pop()
    root, deep = int(ptbl[0, 0]), ptbl[0, 1:3].copy()
    ptbl[1, 0] = ptbl[2, 0] = root
    ptbl[1, 1:3] = deep
    paths = {b: tuple(int(x) for x in ptbl[b, :3]) for b in (0, 1)}
    paths[2] = (root,)
    passes = lcp_group_passes(paths)
    assert passes == [((0, 1, 2), 0, 1), ((0, 1), 1, 2)]
    kj, vj = jnp.asarray(k_pool), jnp.asarray(v_pool)
    ref = lean_decode_ref(
        q, paged_gather_kv(kj, jnp.asarray(ptbl)),
        paged_gather_kv(vj, jnp.asarray(ptbl)),
        ctx_lens=jnp.asarray(lens, jnp.int32),
    )
    casc = lean_decode_cascade(
        q, kj, vj, ptbl, lens,
        [m for m, _, _ in passes], [c for _, _, c in passes],
        page_starts=[s for _, s, _ in passes],
        num_workers=5, fused=fused, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(casc), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    n_shared=st.integers(2, 4),
    pp=st.integers(1, 3),
    n_single=st.integers(0, 2),
)
def test_cascade_fuzz_matches_oracle(seed, n_shared, pp, n_single):
    """Property fuzz over random shared-prefix problems: both cascade
    execution modes match the dense reference oracle."""
    rng = np.random.default_rng(seed)
    Hq, Hkv, d, ps = 4, 2, 8, 8
    suffixes = [int(rng.integers(1, 2 * ps)) for _ in range(n_shared)]
    q, k_pool, v_pool, ptbl, lens, groups, pps, *_ = _shared_problem(
        rng, Hq, Hkv, d, ps, pp, suffixes=suffixes, extra_groups=n_single
    )
    kj, vj = jnp.asarray(k_pool), jnp.asarray(v_pool)
    ref = lean_decode_ref(
        q, paged_gather_kv(kj, jnp.asarray(ptbl)),
        paged_gather_kv(vj, jnp.asarray(ptbl)),
        ctx_lens=jnp.asarray(lens, jnp.int32),
    )
    for fused in (False, True):
        out = lean_decode_cascade(q, kj, vj, ptbl, lens, groups, pps,
                                  num_workers=4, fused=fused, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------- schedule layer
def test_cascade_schedule_clamps_prefix_to_member_capacity():
    """A pass whose claimed prefix would swallow a member's whole context
    gets clamped so every member keeps >= 1 suffix token."""
    cs, binding = make_cascade_schedule(
        ctx_lens=[33, 64], groups=[[0, 1]], prefix_pages=[4],
        num_kv_heads=2, tile_size=16, num_workers=4,
    )
    assert binding.prefix_pages.tolist() == [2]      # (33-1)//16
    assert binding.seq_prefix_len.tolist() == [32, 32]
    desc = cascade_fused_descriptors(cs, binding)
    assert desc.shape == (7, cs.fused_grid_iters)
    # every merge target is a valid per-seq segment or the garbage row
    merge = desc[:, desc[6] == 2]
    assert merge[0].max() <= 2 * 2 and merge[0].min() >= 0


def test_cascade_schedule_drops_singletons_and_broken_nesting():
    """Single-member passes are vanilla decode (dropped); a nested pass
    whose start no longer matches its members' coverage after an upstream
    clamp is dropped rather than leaving a coverage gap."""
    cs, b = make_cascade_schedule(
        ctx_lens=[40, 40, 20], groups=[[0, 1], [2]], prefix_pages=[2, 1],
        num_kv_heads=1, tile_size=8, num_workers=2,
    )
    assert cs.num_groups == 1
    assert b.members.tolist() == [[0, 1]]
    assert b.seq_prefix_len.tolist() == [16, 16, 0]
    # nested pass at start 3 under a level-0 pass clamped to 2 pages:
    # members' coverage ends at 2, so the deep pass must be dropped
    cs2, b2 = make_cascade_schedule(
        ctx_lens=[17, 17], groups=[[0, 1], [0, 1]], prefix_pages=[3, 2],
        num_kv_heads=1, tile_size=8, num_workers=2,
        page_starts=[0, 3],
    )
    assert b2.prefix_pages.tolist() == [2]           # clamp: (17-1)//8
    assert b2.seq_prefix_len.tolist() == [16, 16]
    assert b2.num_levels == 1


def test_cascade_tables_shift_past_prefix():
    _cs, binding = make_cascade_schedule(
        ctx_lens=[40, 40, 20], groups=[[0, 1], [2]], prefix_pages=[2, 0],
        num_kv_heads=1, tile_size=8, num_workers=2,
    )
    ptbl = np.array([[5, 6, 7, 8, 9], [5, 6, 10, 11, 0],
                     [12, 13, 14, 0, 0]], np.int32)
    pt, stbl = cascade_tables(ptbl, binding)
    assert pt.shape[0] == 1                           # singleton dropped
    np.testing.assert_array_equal(pt[0, :2], [5, 6])
    np.testing.assert_array_equal(stbl[0, :3], [7, 8, 9])
    np.testing.assert_array_equal(stbl[1, :2], [10, 11])
    np.testing.assert_array_equal(stbl[2, :3], [12, 13, 14])


def test_get_cascade_keys_on_clamped_prefix():
    """Regression: two lookups with identical groups/REQUESTED prefix
    pages but different clamp outcomes must not collide in the cache
    (the second caller would silently decode with the first's longer
    prefix — negative suffix lengths, masked tails)."""
    cache = ScheduleCache()
    a, ba = cache.get_cascade([33, 33], [[0, 1]], [2], 2, 16, 4)
    b, bb = cache.get_cascade([17, 17], [[0, 1]], [2], 2, 16, 4)
    assert a is not b
    assert ba.seq_prefix_len.tolist() == [32, 32]
    assert bb.seq_prefix_len.tolist() == [16, 16]
    # equal-clamp, same-bucket lookups still share one entry
    assert cache.get_cascade([34, 34], [[0, 1]], [2], 2, 16, 4)[0] is a


def test_get_cascade_canonicalizes_equivalent_geometries():
    """Two groupings that differ only in WHICH slots group (same bucketed
    walks, same sizes) share one schedule object — membership rides in
    the binding as runtime data, so the jit trace is shared too."""
    cache = ScheduleCache()
    s1, b1 = cache.get_cascade([40, 40, 20, 20], [[0, 1]], [2], 2, 8, 4)
    s2, b2 = cache.get_cascade([20, 40, 40, 20], [[1, 2]], [2], 2, 8, 4)
    assert s1 is s2
    assert cache.stats.hits >= 1
    assert b1.members.tolist() != b2.members.tolist()
    assert b1.seq_prefix_len.tolist() == [16, 16, 0, 0]
    assert b2.seq_prefix_len.tolist() == [0, 16, 16, 0]


# ------------------------------------------------------------- engine parity
@functools.lru_cache(maxsize=1)
def _engine_setup():
    from repro.configs import get_smoke_config
    from repro.models import init_params

    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def setup():
    return _engine_setup()


def _sched_run(cfg, params, waves, *, prefix_cache, cascade,
               backend="lean", new=4, num_pages=None,
               cascade_stable_ticks=1, cascade_grouping="lcp"):
    from repro.serving.config import (
        CascadeConfig,
        EngineConfig,
        PagedConfig,
    )
    from repro.serving.engine import DecodeEngine
    from repro.serving.scheduler import Scheduler, SchedulerConfig

    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=4, cache_len=64, attn_backend=backend, num_workers=4,
        paged=PagedConfig(
            page_size=8, num_pages=num_pages, prefix_cache=prefix_cache,
        ),
        cascade=CascadeConfig(
            enabled=cascade, stable_ticks=cascade_stable_ticks,
            grouping=cascade_grouping,
        ),
    ))
    sched = Scheduler(eng, SchedulerConfig(chunk_size=8, prefill_pack=2,
                                           token_budget=32))
    out = []
    for wave in waves:
        hs = [sched.submit(p, max_new_tokens=new) for p in wave]
        sched.run_to_completion(max_steps=500)
        out.extend(tuple(h.generated) for h in hs)
    eng.pool.check()
    if eng.prefix_cache is not None:
        eng.prefix_cache.check()
    return out, eng


def _waves(cfg, seed=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, 24)
    w1 = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, 5 + 3 * i)])
          for i in range(2)]
    w2 = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, 4 + 2 * i)])
          for i in range(4)]
    return [w1, w2]


def test_engine_cascade_tokens_match_unshared_lean(setup):
    """End-to-end: the cascade engine (radix sharing + LCP-grouped fused
    decode) generates the exact token streams of the plain paged lean
    engine on the same request stream — and it actually shared (hits,
    grouped cascade ticks, fused execution, pages saved)."""
    cfg, params = setup
    waves = _waves(cfg)
    base, _ = _sched_run(cfg, params, waves, prefix_cache=False,
                         cascade=False)
    casc, eng = _sched_run(cfg, params, waves, prefix_cache=True,
                           cascade=True)
    assert base == casc
    assert eng.stats.prefix_attach_count >= 4
    assert eng.stats.prefix_matched_tokens >= 4 * 24
    assert eng.stats.cascade_ticks > 0
    assert eng.stats.cascade_grouped_slots > 0
    assert eng.stats.cascade_fused_ticks > 0


def test_engine_lcp_mixed_depth_matches_and_groups(setup):
    """Requests matching 1, 3, and 5 pages of ONE cached chain: LCP
    grouping still forms a grouped pass (the v1 identical-run grouping
    finds nothing here), multi-level stacks a deeper pass for the deeper
    pair, and token streams stay identical to the unshared engine."""
    cfg, params = setup
    rng = np.random.default_rng(21)
    chain = rng.integers(0, cfg.vocab_size, 40)       # 5 pages @ ps=8
    donor = [np.concatenate([chain, [3]])]
    mixed = [
        np.concatenate([chain[:8], rng.integers(0, cfg.vocab_size, 4)]),
        np.concatenate([chain[:24], rng.integers(0, cfg.vocab_size, 5)]),
        np.concatenate([chain[:40], rng.integers(0, cfg.vocab_size, 3)]),
    ]
    waves = [donor, mixed]
    base, _ = _sched_run(cfg, params, waves, prefix_cache=False,
                         cascade=False)
    casc, eng = _sched_run(cfg, params, waves, prefix_cache=True,
                           cascade=True)
    assert base == casc
    assert eng.stats.cascade_ticks > 0
    assert eng.stats.cascade_grouped_slots >= 2
    # the identical-run engine cannot group 1/3/5-page matches at all
    ident, eng_i = _sched_run(cfg, params, waves, prefix_cache=True,
                              cascade=True, cascade_grouping="identical")
    assert base == ident
    assert eng_i.stats.cascade_grouped_passes < eng.stats.cascade_grouped_passes


def test_engine_group_collapse_falls_back_to_paged(setup):
    """When a group collapses to a single member (its partner finished),
    the engine must leave the cascade path — no grouped pass exists — and
    keep decoding correctly on the vanilla paged path."""
    cfg, params = setup
    from repro.serving.config import (
        CascadeConfig,
        EngineConfig,
        PagedConfig,
    )
    from repro.serving.engine import DecodeEngine
    from repro.serving.scheduler import Scheduler, SchedulerConfig

    rng = np.random.default_rng(31)
    shared = rng.integers(0, cfg.vocab_size, 16)
    pair = [
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, 3)]),
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, 4)]),
    ]
    waves = [[np.concatenate([shared, [5]])], pair]
    base, _ = _sched_run(cfg, params, waves, prefix_cache=False,
                         cascade=False, new=6)
    casc, eng = _sched_run(cfg, params, waves, prefix_cache=True,
                           cascade=True, new=6)
    assert base == casc
    assert eng.stats.cascade_ticks > 0
    # collapse: one sharer runs 12 tokens, the other only 2 — once the
    # short one finishes the survivor must decode OFF the cascade path
    eng2 = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=2, cache_len=64, attn_backend="lean", num_workers=4,
        paged=PagedConfig(page_size=8, prefix_cache=True),
        cascade=CascadeConfig(enabled=True, stable_ticks=1),
    ))
    sched2 = Scheduler(eng2, SchedulerConfig(chunk_size=8, prefill_pack=2,
                                             token_budget=32))
    sched2.submit(np.concatenate([shared, [1]]), max_new_tokens=1)
    sched2.run_to_completion(max_steps=100)        # donor seeds the cache
    h_long = sched2.submit(np.concatenate([shared, [2, 3]]),
                           max_new_tokens=12)
    h_short = sched2.submit(np.concatenate([shared, [4, 5, 6]]),
                            max_new_tokens=2)
    guard = 0
    while h_short.state.value != "finished" and guard < 100:
        sched2.step()
        guard += 1
    grouped_before = eng2.stats.cascade_ticks
    assert grouped_before > 0
    sched2.run_to_completion(max_steps=200)
    assert h_long.state.value == "finished"
    assert len(h_long.generated) == 12
    # the surviving singleton never cascades again
    assert eng2.stats.cascade_ticks == grouped_before


def test_engine_divergence_mid_page_groups_at_boundary(setup):
    """Two prompts sharing 12 tokens (1.5 pages at page_size 8) diverge
    mid-page: they group at the 1-full-page boundary, the partial page is
    copy-on-written, and tokens match the unshared engine."""
    cfg, params = setup
    rng = np.random.default_rng(41)
    shared = rng.integers(0, cfg.vocab_size, 12)
    waves = [
        [np.concatenate([shared, rng.integers(0, cfg.vocab_size, 4)])],
        [np.concatenate([shared, rng.integers(0, cfg.vocab_size, 5)]),
         np.concatenate([shared, rng.integers(0, cfg.vocab_size, 6)])],
    ]
    base, _ = _sched_run(cfg, params, waves, prefix_cache=False,
                         cascade=False)
    casc, eng = _sched_run(cfg, params, waves, prefix_cache=True,
                           cascade=True)
    assert base == casc
    assert eng.stats.prefix_attach_count >= 2
    if eng.stats.cascade_ticks:
        assert eng.stats.cascade_last["passes"] >= 1


def test_engine_stability_guard_defers_cascade(setup):
    """With a large N the guard holds the cascade path back (skips are
    counted, no cascade tick fires in a short run) while token streams
    stay correct; the same run with N=1 cascades immediately."""
    cfg, params = setup
    waves = _waves(cfg, seed=51)
    base, _ = _sched_run(cfg, params, waves, prefix_cache=False,
                         cascade=False)
    guarded, eng_g = _sched_run(cfg, params, waves, prefix_cache=True,
                                cascade=True, cascade_stable_ticks=10**6)
    assert base == guarded
    assert eng_g.stats.cascade_ticks == 0
    assert eng_g.stats.cascade_stability_skips > 0
    eager, eng_e = _sched_run(cfg, params, waves, prefix_cache=True,
                              cascade=True, cascade_stable_ticks=1)
    assert base == eager
    assert eng_e.stats.cascade_ticks > 0


@settings(max_examples=2, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_engine_cascade_churn_fuzz_token_identity(seed):
    """Hypothesis fuzz (satellite): under admission/finish churn over a
    random prefix tree — mixed match depths, staggered arrivals, groups
    forming and collapsing — cascade-v2 token streams stay identical to
    the unshared engine."""
    cfg, params = _engine_setup()
    rng = np.random.default_rng(seed)
    root = rng.integers(0, cfg.vocab_size, 24)
    waves = []
    for _ in range(2):
        wave = []
        for _ in range(int(rng.integers(2, 4))):
            cut = int(rng.integers(6, len(root) + 1))
            tail = rng.integers(0, cfg.vocab_size, int(rng.integers(1, 8)))
            wave.append(np.concatenate([root[:cut], tail]))
        waves.append(wave)
    base, _ = _sched_run(cfg, params, waves, prefix_cache=False,
                         cascade=False, new=3)
    casc, eng = _sched_run(cfg, params, waves, prefix_cache=True,
                           cascade=True, new=3)
    assert base == casc


def test_engine_prefix_sharing_tokens_match_ref(setup):
    """Default (non-cascade) path: page-table aliasing over the unshared
    schedule — token streams identical with the radix cache on vs off."""
    cfg, params = setup
    waves = _waves(cfg, seed=1)
    base, _ = _sched_run(cfg, params, waves, prefix_cache=False,
                         cascade=False, backend="ref")
    on, eng = _sched_run(cfg, params, waves, prefix_cache=True,
                         cascade=False, backend="ref")
    assert base == on
    assert eng.stats.prefix_attach_count >= 4


def test_engine_cow_on_partial_page_divergence(setup):
    """A second request whose prompt exactly extends a cached sequence
    lands mid-page: its appends must copy-on-write the shared partial
    page, the original cached KV must stay pristine (a third identical
    request still matches and decodes identically), and no page is ever
    aliased between diverged requests."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    # 11 tokens: 1 full page (8) + partial page (3) at page_size 8
    base_prompt = rng.integers(0, cfg.vocab_size, 11)
    # learn the greedy continuation so the follow-ups extend the cached
    # sequence INTO its partial page (conversation-continuation pattern);
    # KV coverage of the donor is prompt + generated[:-1]
    first, _ = _sched_run(cfg, params, [[base_prompt]], prefix_cache=False,
                          cascade=False, backend="ref")
    cont = np.asarray(first[0][:3], dtype=base_prompt.dtype)
    div_a = np.concatenate([base_prompt, cont,
                            rng.integers(0, cfg.vocab_size, 6)])
    div_b = np.concatenate([base_prompt, cont,
                            rng.integers(0, cfg.vocab_size, 6)])
    waves = [[base_prompt], [div_a], [div_b]]
    off, _ = _sched_run(cfg, params, waves, prefix_cache=False,
                        cascade=False, backend="ref")
    on, eng = _sched_run(cfg, params, waves, prefix_cache=True,
                         cascade=False, backend="ref")
    assert off == on
    assert eng.stats.cow_copies >= 1, "partial-page divergence must CoW"
    assert eng.stats.prefix_matched_tokens > 0


@pytest.mark.slow
def test_engine_cascade_random_prefix_tree_churn(setup):
    """Slow fuzz: random prefix trees + request churn through an
    undersized pool with the cascade engine — token-identical to the
    unshared lean engine; pool and trie invariants hold after drain."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    roots = [rng.integers(0, cfg.vocab_size, 16) for _ in range(2)]
    waves = []
    for _ in range(4):
        wave = []
        for _ in range(int(rng.integers(2, 5))):
            root = roots[int(rng.integers(0, 2))]
            cut = int(rng.integers(4, len(root) + 1))
            tail = rng.integers(0, cfg.vocab_size, int(rng.integers(1, 12)))
            wave.append(np.concatenate([root[:cut], tail]))
        waves.append(wave)
    base, _ = _sched_run(cfg, params, waves, prefix_cache=False,
                         cascade=False, new=3)
    casc, eng = _sched_run(cfg, params, waves, prefix_cache=True,
                           cascade=True, new=3, num_pages=24)
    assert base == casc
    assert eng.pool.num_allocated == len(eng.pool.pages_of(
        "__radix_prefix_cache__"))
