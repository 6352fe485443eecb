"""Property-based invariants of the paged KV subsystem.

Three families of properties, via hypothesis:

  * schedule coverage — for random ragged length sets, the stream-K
    schedule visits every (segment, tile) pair exactly once, and the paged
    routing metadata (``LeanSchedule.iter_kv_meta``) is consistent with the
    segment decomposition;
  * allocator safety — under random alloc/free churn, no page is ever
    referenced by two live sequences and ``allocated + free == usable``
    holds at every step;
  * numerical equivalence — paged lean decode (fused and two-phase) and the
    gather-based paged reference all match the dense oracle to fp32
    tolerance on random ragged workloads with randomly permuted page tables.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.attention import paged_gather_kv
from repro.core.leantile import ScheduleCache, make_schedule
from repro.kernels.ops import lean_decode_paged
from repro.kernels.ref import lean_decode_ref
from repro.serving.kvpool import NULL_PAGE, KVPagePool

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------- strategies
ragged_lens = st.lists(st.integers(1, 200), min_size=1, max_size=5)


# ------------------------------------------------------- schedule coverage
@settings(max_examples=40)
@given(
    lens=ragged_lens,
    hkv=st.integers(1, 3),
    tile=st.sampled_from([8, 16, 32]),
    G=st.integers(1, 12),
)
def test_schedule_covers_every_segment_tile_exactly_once(lens, hkv, tile, G):
    sched = make_schedule(lens, hkv, tile, G)
    valid = sched.iter_valid == 1
    pairs = list(zip(sched.iter_seg[valid].tolist(),
                     sched.iter_tile[valid].tolist()))
    # exactly once: no duplicates, count matches the tile total
    assert len(pairs) == sched.total_tiles
    assert len(set(pairs)) == len(pairs)
    expected = {
        (s, t)
        for s in range(sched.num_segments)
        for t in range(-(-int(sched.seg_len[s]) // tile))
    }
    assert set(pairs) == expected


@settings(max_examples=40)
@given(
    lens=ragged_lens,
    hkv=st.integers(1, 3),
    G=st.integers(1, 12),
    fused=st.booleans(),
)
def test_page_routing_metadata_consistent(lens, hkv, G, fused):
    """iter_kv_meta routes every partial iteration to exactly the
    (batch, head, tile) its segment decomposes to; everything else routes
    to the null target (0, 0, 0)."""
    tile = 16
    sched = make_schedule(lens, hkv, tile, G)
    batch, head, tile_idx, ok = sched.iter_kv_meta(fused=fused)
    desc = sched.fused_descriptors() if fused else sched.packed_descriptors()
    partial = desc[6] == 1
    np.testing.assert_array_equal(ok == 1, partial)
    seg = desc[0][partial]
    np.testing.assert_array_equal(batch[partial], sched.seg_batch[seg])
    np.testing.assert_array_equal(head[partial], sched.seg_head[seg])
    np.testing.assert_array_equal(tile_idx[partial], desc[1][partial])
    assert (batch[~partial] == 0).all()
    assert (head[~partial] == 0).all()
    assert (tile_idx[~partial] == 0).all()


# --------------------------------------------------------- allocator safety
@settings(max_examples=30)
@given(
    ops=st.lists(st.integers(0, 7), min_size=1, max_size=80),
    usable=st.integers(2, 24),
)
def test_pool_churn_never_aliases_and_never_leaks(ops, usable):
    pool = KVPagePool(usable + 1, page_size=8)
    for step, key in enumerate(ops):
        if pool.count(key):
            pool.free_seq(key)
        else:
            pool.alloc(key, n=1 + (step % 3))     # may fail; pool unchanged
        pool.check()  # disjoint live sets, accounting, null page reserved
    for key in set(ops):
        if pool.holds(key):
            pool.free_seq(key)
        else:
            # unknown/already-freed sequences must fail LOUDLY now —
            # the silent 0-page return used to mask double-free bugs
            with pytest.raises(KeyError):
                pool.free_seq(key)
    pool.check()
    assert pool.num_allocated == 0
    assert pool.stats.pages_allocated == pool.stats.pages_freed


@settings(max_examples=20)
@given(n=st.integers(1, 10), usable=st.integers(2, 12))
def test_pool_alloc_is_all_or_nothing(n, usable):
    pool = KVPagePool(usable + 1, page_size=8)
    got = pool.alloc("a", n)
    if n <= usable:
        assert got is not None and len(got) == n
        assert NULL_PAGE not in got
    else:
        assert got is None
        assert pool.num_allocated == 0
        assert pool.stats.failed_allocs == 1
    pool.check()


# ---------------------------------------------------- numerical equivalence
GEOMS = [(4, 2, 16), (4, 1, 16), (3, 3, 8)]      # (Hq, Hkv, d): GQA/MQA/MHA


def _paged_problem(rng, lens, Hq, Hkv, d, ps):
    """Random pool + per-sequence page tables with *permuted* physical
    pages (the adversarial layout: logical neighbours land on scattered
    pages)."""
    B = len(lens)
    width = max(-(-L // ps) for L in lens)
    total = sum(-(-L // ps) for L in lens)
    num_pages = 1 + total
    q = jnp.asarray(rng.standard_normal((B, Hq, d)), jnp.float32)
    k_pool = jnp.asarray(
        rng.standard_normal((num_pages, Hkv, ps, d)), jnp.float32
    )
    v_pool = jnp.asarray(
        rng.standard_normal((num_pages, Hkv, ps, d)), jnp.float32
    )
    order = list(rng.permutation(np.arange(1, num_pages)))
    ptbl = np.zeros((B, width), np.int32)
    for b, L in enumerate(lens):
        n = -(-L // ps)
        ptbl[b, :n] = [order.pop() for _ in range(n)]
    return q, k_pool, v_pool, ptbl


@settings(max_examples=8)
@given(
    lens=st.lists(st.integers(1, 60), min_size=1, max_size=3),
    geom=st.sampled_from(GEOMS),
    G=st.sampled_from([1, 4, 7]),
)
def test_paged_lean_and_ref_match_dense_oracle(lens, geom, G):
    Hq, Hkv, d = geom
    ps = 16
    rng = np.random.default_rng(abs(hash((tuple(lens), geom, G))) % 2**32)
    q, k_pool, v_pool, ptbl = _paged_problem(rng, lens, Hq, Hkv, d, ps)
    k_dense = paged_gather_kv(k_pool, jnp.asarray(ptbl))
    v_dense = paged_gather_kv(v_pool, jnp.asarray(ptbl))
    ref = lean_decode_ref(
        q, k_dense, v_dense, ctx_lens=jnp.asarray(lens, jnp.int32)
    )
    for fused in (True, False):
        out = lean_decode_paged(
            q, k_pool, v_pool, ptbl, lens, num_workers=G, fused=fused,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"fused={fused} lens={lens} geom={geom} G={G}",
        )


def test_paged_scheduled_via_cache_stays_exact():
    """Bucketed (cached) schedules walk more tiles than the true lengths;
    runtime masking must keep the paged result exact — and the cache key
    must not depend on the physical page layout."""
    Hq, Hkv, d, ps = 4, 2, 16, 16
    lens = [19, 50]
    rng = np.random.default_rng(11)
    q, k_pool, v_pool, ptbl = _paged_problem(rng, lens, Hq, Hkv, d, ps)
    cache = ScheduleCache()
    ref = lean_decode_ref(
        q, paged_gather_kv(k_pool, jnp.asarray(ptbl)),
        paged_gather_kv(v_pool, jnp.asarray(ptbl)),
        ctx_lens=jnp.asarray(lens, jnp.int32),
    )
    out = lean_decode_paged(
        q, k_pool, v_pool, ptbl, lens, num_workers=5,
        schedule_cache=cache, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # permute the physical layout: same logical problem, same cache entry.
    # new_pool[perm[p]] == old_pool[p] (perm fixes the null page), so the
    # relocated table perm[ptbl] reads identical logical data.
    perm = np.concatenate([[0], np.random.default_rng(7).permutation(
        np.arange(1, k_pool.shape[0]))])
    inv = np.argsort(perm)
    out2 = lean_decode_paged(
        q, k_pool[jnp.asarray(inv)], v_pool[jnp.asarray(inv)],
        perm[ptbl].astype(np.int32), lens, num_workers=5,
        schedule_cache=cache, interpret=True,
    )
    assert cache.stats.hits >= 1, "physical relayout must not miss the cache"
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
