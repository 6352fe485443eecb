"""Quantized (int8) paged KV pool: quantizer round-trips, the single
write-chokepoint's scale monotonicity, in-kernel dequant parity against
the fp32 oracle across GQA/MQA/MHA on all four paged paths (two-phase,
fused, cascade, chunked prefill), pool scale invariants under churn, and
engine-level int8-vs-bf16 token parity + poison/scrub scale semantics.

Tolerances: symmetric int8 with per-(page, head) scales bounds the
per-element dequant error by ``scale / 2 = amax / 254``. For the
unit-normal K/V used here page amax is ~4, so attention outputs (convex
combinations of dequantized V rows) land well inside ``QUANT_TOL=0.05``
vs the full-precision oracle. Kernel-vs-dequantized-oracle checks are
fp32-tight (both read the same int8 + scales); only quant-vs-fp checks
use the loose tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.core.attention import (
    INT8_QMAX,
    mha_chunk_prefill_paged_ref,
    paged_gather_kv,
    paged_gather_kv_dequant,
    paged_scatter_tokens,
    paged_scatter_tokens_quant,
    quantize_kv_blocks,
)
from repro.core.leantile import make_chunk_schedule
from repro.kernels.ops import (
    lean_decode_cascade,
    lean_decode_paged,
    lean_prefill_chunks,
)
from repro.kernels.ref import lean_decode_ref
from repro.models import init_params
from repro.serving.config import EngineConfig, PagedConfig
from repro.serving.engine import DecodeEngine, Request
from repro.serving.kvpool import KVLayout, KVPagePool

jax.config.update("jax_platform_name", "cpu")

GEOMS = [(4, 2, 16), (4, 1, 16), (3, 3, 8)]   # (Hq, Hkv, d): GQA/MQA/MHA
QUANT_TOL = 0.05    # quant-vs-fp, unit-normal K/V (see module docstring)


# --------------------------------------------------------------- quantizer
def test_quantize_roundtrip_error_bounded_by_half_scale():
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.standard_normal((5, 3, 8, 16)), jnp.float32)
    q, s = quantize_kv_blocks(vals)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == (5, 3)
    deq = q.astype(jnp.float32) * s[..., None, None]
    err = np.abs(np.asarray(deq - vals))
    bound = np.asarray(s)[..., None, None] * 0.5 + 1e-6
    assert (err <= bound).all()
    # scales are exactly amax / 127 and the amax element survives exactly
    np.testing.assert_allclose(
        np.asarray(s),
        np.abs(np.asarray(vals)).max(axis=(-2, -1)) / INT8_QMAX,
        rtol=1e-6,
    )


def test_quantize_zero_block_gives_zero_scale_and_exact_zeros():
    q, s = quantize_kv_blocks(jnp.zeros((2, 4, 8, 4)))
    assert not np.asarray(q).any() and not np.asarray(s).any()
    deq = q.astype(jnp.float32) * s[..., None, None]
    assert not np.asarray(deq).any()          # scale 0 -> exact zeros


def test_quantize_per_page_granularity_shares_scale_across_heads():
    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.standard_normal((3, 4, 8, 16)), jnp.float32)
    _, s = quantize_kv_blocks(vals, per_head=False)
    s = np.asarray(s)
    assert (s == s[:, :1]).all()              # broadcast layout, one scale
    np.testing.assert_allclose(
        s[:, 0], np.abs(np.asarray(vals)).max(axis=(1, 2, 3)) / INT8_QMAX,
        rtol=1e-6,
    )


# ---------------------------------------------------- write chokepoint
def _chunk_problem(rng, N, W, H, ps, d, offs, lens, scale=1.0):
    tbls = np.zeros((N, W), np.int32)
    nxt = 1
    for n in range(N):
        npages = -(-int(offs[n] + lens[n]) // ps)
        tbls[n, :npages] = np.arange(nxt, nxt + npages)
        nxt += npages
    num_pages = 1 + N * W
    C = int(max(lens))
    vals = jnp.asarray(
        scale * rng.standard_normal((N, C, H, d)), jnp.float32
    )
    return jnp.asarray(tbls), vals, num_pages, C


def test_scatter_quant_matches_fp_scatter_and_scales_only_grow():
    """Two successive appends through the chokepoint — the second with
    larger-magnitude tokens into the same pages: scales grow monotonically,
    existing content is requantized (not clobbered), and the dequantized
    pool tracks the fp-scattered pool within half a scale step."""
    rng = np.random.default_rng(2)
    N, W, H, ps, d = 2, 4, 3, 8, 16
    offs1 = jnp.asarray([0, 3], jnp.int32)
    lens1 = jnp.asarray([5, 7], jnp.int32)
    tbls, vals1, num_pages, _ = _chunk_problem(
        rng, N, W, H, ps, d, [0, 3], [5, 7], scale=0.5
    )
    qpool = jnp.zeros((num_pages, H, ps, d), jnp.int8)
    scales = jnp.zeros((num_pages, H), jnp.float32)
    fpool = jnp.zeros((num_pages, H, ps, d), jnp.float32)

    qpool, scales = paged_scatter_tokens_quant(
        qpool, scales, tbls, offs1, lens1, vals1
    )
    fpool = paged_scatter_tokens(fpool, tbls, offs1, lens1, vals1)
    s1 = np.asarray(scales)
    assert (s1 >= 0).all() and np.isfinite(s1).all()

    # second append continues each chunk, 4x the magnitude: scales must grow
    offs2 = offs1 + lens1
    lens2 = jnp.asarray([6, 4], jnp.int32)
    vals2 = jnp.asarray(
        2.0 * rng.standard_normal((N, int(lens2.max()), H, d)), jnp.float32
    )
    qpool, scales = paged_scatter_tokens_quant(
        qpool, scales, tbls, offs2, lens2, vals2
    )
    fpool = paged_scatter_tokens(fpool, tbls, offs2, lens2, vals2)
    s2 = np.asarray(scales)
    assert (s2 >= s1).all()                   # monotone growth, everywhere
    assert (s2 > s1).any()                    # ... and it actually grew

    deq = np.asarray(qpool, np.float32) * s2[..., None, None]
    # requantization compounds one extra rounding step: a full scale bound
    bound = s2[..., None, None] + 1e-6
    assert (np.abs(deq - np.asarray(fpool)) <= bound).all()


def test_scatter_quant_invalid_positions_route_to_null_page():
    rng = np.random.default_rng(3)
    tbls, vals, num_pages, _ = _chunk_problem(
        rng, 1, 2, 2, 8, 4, [0], [3]
    )
    qpool = jnp.zeros((num_pages, 2, 8, 4), jnp.int8)
    scales = jnp.zeros((num_pages, 2), jnp.float32)
    qpool, scales = paged_scatter_tokens_quant(
        qpool, scales, tbls, jnp.asarray([0], jnp.int32),
        jnp.asarray([0], jnp.int32), vals,   # zero valid tokens
    )
    assert not np.asarray(qpool)[1:].any()    # only page 0 may be touched
    assert not np.asarray(scales)[1:].any()


# --------------------------------------------- paged decode kernel parity
def _paged_problem(rng, lens, Hq, Hkv, d, ps):
    """Random pool + permuted-physical-page tables (the adversarial
    layout), mirroring test_paged_invariants."""
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


@pytest.mark.parametrize("fused", [False, True], ids=["twophase", "fused"])
@pytest.mark.parametrize("geom", GEOMS, ids=["gqa", "mqa", "mha"])
def test_paged_decode_int8_matches_dequant_oracle_and_fp(geom, fused):
    Hq, Hkv, d = geom
    ps, lens = 16, [19, 50, 7]
    rng = np.random.default_rng(abs(hash((geom, fused))) % 2**32)
    q, k_pool, v_pool, ptbl = _paged_problem(rng, lens, Hq, Hkv, d, ps)
    kq, ks = quantize_kv_blocks(k_pool)
    vq, vs = quantize_kv_blocks(v_pool)
    ctx = jnp.asarray(lens, jnp.int32)
    # oracle over the SAME int8 data: kernel dequant must be fp32-tight
    deq_ref = lean_decode_ref(
        q, paged_gather_kv_dequant(kq, ks, jnp.asarray(ptbl)),
        paged_gather_kv_dequant(vq, vs, jnp.asarray(ptbl)), ctx_lens=ctx,
    )
    out = lean_decode_paged(
        q, kq, vq, ptbl, lens, num_workers=5, fused=fused,
        k_scales=ks, v_scales=vs, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(deq_ref), rtol=2e-5, atol=2e-5
    )
    # vs the full-precision pools: only quantization error remains
    fp_ref = lean_decode_ref(
        q, paged_gather_kv(k_pool, jnp.asarray(ptbl)),
        paged_gather_kv(v_pool, jnp.asarray(ptbl)), ctx_lens=ctx,
    )
    assert np.abs(np.asarray(out) - np.asarray(fp_ref)).max() < QUANT_TOL


@pytest.mark.parametrize("qdtype", [jnp.bfloat16, jnp.float16])
def test_paged_decode_int8_returns_query_dtype(qdtype):
    """Every kernel exit casts back to q.dtype — an int8 pool must not
    leak fp32 partials into a bf16/f16 activation stream."""
    Hq, Hkv, d, ps = 4, 2, 16, 16
    rng = np.random.default_rng(9)
    q, k_pool, v_pool, ptbl = _paged_problem(rng, [20, 9], Hq, Hkv, d, ps)
    kq, ks = quantize_kv_blocks(k_pool)
    vq, vs = quantize_kv_blocks(v_pool)
    for fused in (False, True):
        out = lean_decode_paged(
            q.astype(qdtype), kq, vq, ptbl, [20, 9], num_workers=4,
            fused=fused, k_scales=ks, v_scales=vs, interpret=True,
        )
        assert out.dtype == qdtype, f"fused={fused}"


# -------------------------------------------------------- cascade parity
def _shared_problem(rng, Hq, Hkv, d, ps, pp, suffixes):
    """First len(suffixes) sequences share a pp-page prefix (mirrors
    test_cascade)."""
    B = len(suffixes)
    lens = [pp * ps + s for s in suffixes]
    W = max(-(-L // ps) for L in lens) + 1
    total = sum(-(-L // ps) for L in lens) + pp * (B - 1)
    num_pages = 1 + total + 4
    k_pool = rng.standard_normal((num_pages, Hkv, ps, d)).astype(np.float32)
    v_pool = rng.standard_normal((num_pages, Hkv, ps, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, Hq, d)), jnp.float32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    shared = [int(free.pop()) for _ in range(pp)]
    ptbl = np.zeros((B, W), np.int32)
    for b, L in enumerate(lens):
        n = -(-L // ps)
        ptbl[b, :pp] = shared
        ptbl[b, pp:n] = [int(free.pop()) for _ in range(n - pp)]
    return q, jnp.asarray(k_pool), jnp.asarray(v_pool), ptbl, lens


@pytest.mark.parametrize("fused", [False, True], ids=["twocall", "fused"])
@pytest.mark.parametrize("geom", GEOMS, ids=["gqa", "mqa", "mha"])
def test_cascade_int8_matches_paged_and_fp(geom, fused):
    Hq, Hkv, d = geom
    ps, pp = 16, 3
    rng = np.random.default_rng(abs(hash(("casc", geom))) % 2**32)
    q, k_pool, v_pool, ptbl, lens = _shared_problem(
        rng, Hq, Hkv, d, ps, pp, suffixes=[5, 20, 33]
    )
    kq, ks = quantize_kv_blocks(k_pool)
    vq, vs = quantize_kv_blocks(v_pool)
    groups, pps = [[0, 1, 2]], [pp]
    casc = lean_decode_cascade(
        q, kq, vq, ptbl, lens, groups, pps, num_workers=6, fused=fused,
        k_scales=ks, v_scales=vs, interpret=True,
    )
    # re-bracketing the reduction over identical int8 data: fp32-tight
    paged = lean_decode_paged(
        q, kq, vq, ptbl, lens, num_workers=6,
        k_scales=ks, v_scales=vs, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(casc), np.asarray(paged), rtol=1e-4, atol=1e-4
    )
    fp_ref = lean_decode_ref(
        q, paged_gather_kv(k_pool, jnp.asarray(ptbl)),
        paged_gather_kv(v_pool, jnp.asarray(ptbl)),
        ctx_lens=jnp.asarray(lens, jnp.int32),
    )
    assert np.abs(np.asarray(casc) - np.asarray(fp_ref)).max() < QUANT_TOL
    assert casc.dtype == q.dtype


# ------------------------------------------------- chunked prefill parity
@pytest.mark.parametrize(
    "Hq,Hkv", [(4, 2), (4, 1), (8, 8)], ids=["gqa", "mqa", "mha"]
)
def test_chunk_prefill_int8_matches_dequant_oracle_and_fp(Hq, Hkv):
    rng = np.random.default_rng(4)
    d, ps, W = 16, 8, 6
    offs = np.array([0, 9, 3], np.int64)
    lens = np.array([5, 8, 1], np.int64)
    N = len(offs)
    num_pages = 1 + N * W
    k_pool = jnp.asarray(
        rng.standard_normal((num_pages, Hkv, ps, d)), jnp.float32
    )
    v_pool = jnp.asarray(
        rng.standard_normal((num_pages, Hkv, ps, d)), jnp.float32
    )
    tbls = np.zeros((N, W), np.int32)
    for n in range(N):
        npages = -(-int(offs[n] + lens[n]) // ps)
        tbls[n, :npages] = 1 + n * W + np.arange(npages)
    tbls = jnp.asarray(tbls)
    C = int(max(lens))
    q = jnp.asarray(rng.standard_normal((N, Hq, C, d)), jnp.float32)

    kq, ks = quantize_kv_blocks(k_pool)
    vq, vs = quantize_kv_blocks(v_pool)
    kd = kq.astype(jnp.float32) * ks[:, :, None, None]
    vd = vq.astype(jnp.float32) * vs[:, :, None, None]
    ref = mha_chunk_prefill_paged_ref(
        q, kd, vd, tbls, jnp.asarray(offs, jnp.int32)
    )
    fp_ref = mha_chunk_prefill_paged_ref(
        q, k_pool, v_pool, tbls, jnp.asarray(offs, jnp.int32)
    )
    visible = [int(o + l) for o, l in zip(offs, lens)]
    sched = make_chunk_schedule(visible, Hkv, ps, 4, max_len=W * ps)
    out = lean_prefill_chunks(
        q, kq, vq,
        jnp.asarray(np.repeat(visible, Hkv), jnp.int32),
        jnp.asarray(np.repeat(offs, Hkv), jnp.int32),
        tbls, sched, k_scales=ks, v_scales=vs, interpret=True,
    )
    assert out.dtype == q.dtype
    for n in range(N):
        L = int(lens[n])
        np.testing.assert_allclose(
            np.asarray(ref[n, :, :L]), np.asarray(out[n, :, :L]), atol=2e-5
        )
        assert (
            np.abs(np.asarray(out[n, :, :L]) - np.asarray(fp_ref[n, :, :L]))
            .max() < QUANT_TOL
        )


# ------------------------------------------------- pool scale invariants
def _quant_pool(usable=8, ps=4, Hkv=2):
    layout = KVLayout(
        kv_dtype="int8", n_kv_heads=Hkv, head_dim=8, page_size=ps,
        n_attn_layers=1,
    )
    return KVPagePool(usable + 1, page_size=ps, layout=layout)


def test_pool_check_scales_flags_nonfinite_live_pages_only():
    pool = _quant_pool()
    scales = np.zeros((pool.num_pages, 2), np.float32)
    pages = pool.alloc("a", 2)
    scales[pages] = 0.5
    pool.check(scales=[scales])               # clean live pages: fine
    # stale garbage on a FREE page is by-design invisible
    free = next(p for p in range(1, pool.num_pages) if p not in pages)
    scales[free] = np.nan
    pool.check(scales=[scales])
    # ... but NaN on a live page is corruption
    scales[pages[0]] = np.nan
    with pytest.raises(AssertionError):
        pool.check(scales=[scales])
    scales[pages[0]] = -0.1                   # amax/127 can never go negative
    with pytest.raises(AssertionError):
        pool.check(scales=[scales])
    scales[pages[0]] = 0.0
    pool.check(scales=[scales])
    with pytest.raises(AssertionError):       # short sidecar: layout bug
        pool.check(scales=[scales[:-2]])


@settings(max_examples=20)
@given(ops=st.lists(st.integers(0, 7), min_size=1, max_size=60))
def test_pool_churn_with_scale_sidecar_invariants(ops):
    """Alloc/free churn with a write-at-admit scale sidecar: the scale
    invariants hold at every step even though freed pages keep stale
    values (they are only ever overwritten on re-admit)."""
    pool = _quant_pool(usable=6)
    rng = np.random.default_rng(7)
    scales = np.zeros((pool.num_pages, 2), np.float32)
    keys = ["a", "b", "c"]
    for step, op in enumerate(ops):
        key = keys[op % 3]
        if op < 4 and not pool.holds(key):
            pages = pool.alloc(key, 1 + step % 2)
            if pages is not None:
                scales[pages] = rng.random((len(pages), 2)) + 0.01
        elif pool.holds(key):
            pool.free_seq(key)                # stale scales stay behind
        pool.check(scales=[scales])
    for key in keys:
        if pool.holds(key):
            pool.free_seq(key)
    pool.check(scales=[scales])
    assert pool.num_allocated == 0


def test_layout_page_bytes_accounts_scales_and_halves_footprint():
    mk = lambda dt: KVLayout(kv_dtype=dt, n_kv_heads=8, head_dim=128,
                             page_size=16, n_attn_layers=32)
    bf16, int8 = mk("bf16"), mk("int8")
    assert int8.quantized and int8.elem_bytes == 1
    assert int8.scale_bytes_per_page == 2 * 4 * 8 * 32
    assert int8.page_bytes == bf16.page_bytes // 2 + int8.scale_bytes_per_page
    # realistic dims: scale sidecar is noise, capacity gain is ~2x
    assert bf16.page_bytes / int8.page_bytes > 1.99


# ------------------------------------------------------------ engine level
@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, *, page_size=8, kv_dtype=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_len", 32)
    kw.setdefault("num_workers", 4)
    kw.setdefault("attn_backend", "lean")
    return DecodeEngine(cfg, params, config=EngineConfig(
        paged=PagedConfig(page_size=page_size, kv_dtype=kv_dtype), **kw,
    ))


def _streams(eng, cfg, n=3, new=10, seed=0):
    rng = np.random.default_rng(seed)
    reqs = [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 5 + 4 * i),
                max_new_tokens=new)
        for i in range(n)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_ticks=300)
    assert all(r.done for r in reqs)
    return [tuple(r.generated) for r in reqs]


def test_engine_int8_streams_consistent_and_near_bf16(setup):
    """Two int8 engines on different kernels (lean stream-K vs the dense
    gather reference) see the SAME quantized KV and agree per-step to
    fp32 tolerance, so their greedy streams stay overwhelmingly aligned
    — but neither this nor the bf16-vs-int8 comparison is bit-parity:
    a reassociated fp32 reduction (or the quantization perturbation) may
    legitimately flip a near-tie argmax, and one flip forks the stream."""
    cfg, params = setup
    base = _streams(_engine(cfg, params), cfg)
    eng = _engine(cfg, params, kv_dtype="int8")
    q = _streams(eng, cfg)
    qr = _streams(_engine(cfg, params, kv_dtype="int8",
                          attn_backend="ref"), cfg)

    def agreement(xs, ys):
        agree = sum(a == b for x, y in zip(xs, ys) for a, b in zip(x, y))
        return agree / sum(len(x) for x in xs)

    assert agreement(q, qr) >= 0.8, "int8 kernels disagree too much"
    assert agreement(base, q) >= 0.8, "int8 drifted too far from bf16"
    lay = eng.pool.layout
    assert lay.quantized and lay.elem_bytes == 1
    bf16 = KVLayout(
        kv_dtype="bf16", n_kv_heads=lay.n_kv_heads, head_dim=lay.head_dim,
        page_size=lay.page_size, n_attn_layers=lay.n_attn_layers,
    )
    assert lay.page_bytes < bf16.page_bytes
    eng.pool.check(scales=eng._kv_scale_arrays())


def test_fill_page_poisons_and_scrubs_via_scales(setup):
    """int8 content cannot hold NaN, so the guard fill rides the scale
    leaf: NaN-poison dequantizes the page to NaN (observable corruption),
    a 0.0 scrub dequantizes it to exact zeros."""
    cfg, params = setup
    eng = _engine(cfg, params, kv_dtype="int8")
    rng = np.random.default_rng(1)
    r = Request(uid=0, prompt=rng.integers(0, cfg.vocab_size, 9),
                max_new_tokens=16)        # long enough to stay live below
    eng.submit(r)
    for _ in range(2):
        eng.tick()
    page = int(eng.page_tbl[0, 0])
    assert page != 0

    def _deq_page(p):
        for (pattern, _), st_c in zip(cfg.stages, eng.cache):
            for kind, lc in zip(pattern, st_c):
                if kind == "attn":
                    tbl = jnp.asarray([[p]], jnp.int32)
                    return np.asarray(paged_gather_kv_dequant(
                        lc["k"][0], lc["k_scale"][0], tbl
                    ))
        raise AssertionError("no attn layer")

    eng.cache = eng._jit_fill_page(
        eng.cache, jnp.asarray(page, jnp.int32),
        jnp.asarray(jnp.nan, jnp.float32),
    )
    assert np.isnan(_deq_page(page)).all()    # poison is observable
    eng.cache = eng._jit_fill_page(
        eng.cache, jnp.asarray(page, jnp.int32),
        jnp.asarray(0.0, jnp.float32),
    )
    scrubbed = _deq_page(page)
    assert np.isfinite(scrubbed).all() and not scrubbed.any()
    eng.pool.check(scales=eng._kv_scale_arrays())


@pytest.mark.chaos
def test_int8_nan_kv_poison_recovers_token_identical(setup):
    """The chaos KV-corruption contract holds on a quantized pool: the
    victim is poisoned (scales scrubbed with the pages), recomputes from
    its prompt, and the drained engine matches the fault-free int8 run
    with clean scale sidecars."""
    from repro.serving.faults import FaultInjector, FaultSpec
    from repro.serving.guards import GuardConfig

    cfg, params = setup
    base = _streams(_engine(cfg, params, kv_dtype="int8"), cfg, n=4, new=12)
    inj = FaultInjector(
        {"nan_kv": FaultSpec(rate=1.0, start=3, max_fires=1)}, seed=2
    )
    eng = _engine(
        cfg, params, kv_dtype="int8", faults=inj,
        guards=GuardConfig(heal_after=2, poison_after=2),
    )
    assert _streams(eng, cfg, n=4, new=12) == base
    assert inj.fires["nan_kv"] == 1
    assert eng.stats.poisoned_slots == 1
    eng.pool.check(scales=eng._kv_scale_arrays())
    assert eng.degraded_gauge.value == 0
