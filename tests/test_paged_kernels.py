"""Paged lean-decode kernel parity: fused vs two-phase vs dense, across the
GQA (mistral-nemo-12b) and MQA (recurrentgemma-9b) head geometries.

The paged kernels re-use the dense kernel bodies and only change how K/V
tiles are fetched (page-table routing operand), so on identical logical
inputs the paged output must be *bit-identical* to the dense kernel's — not
merely allclose. The fused kernel's output is also bit-identical whatever
number ``n`` of schedule iterations one grid step runs. The broader
randomized fuzz is marked ``slow`` (dedicated CI job); a representative
slice runs in tier-1.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.attention import paged_gather_kv, quantize_kv_blocks
from repro.core.leantile import make_schedule
from repro.kernels.lean_decode import (
    MAX_ITERS_PER_STEP,
    OP_MERGE,
    OP_PAD,
    OP_PARTIAL,
    fused_iters_per_step,
    lean_decode_paged_fused,
)
from repro.kernels.ops import (
    _paged_route,
    _pool_rows,
    lean_decode,
    lean_decode_paged,
)
from repro.kernels.ref import lean_decode_ref

jax.config.update("jax_platform_name", "cpu")

# head geometries from the two assigned tiny variants
GEOMS = {
    "mistral_nemo_12b": get_smoke_config("mistral-nemo-12b"),   # GQA 4q/2kv
    "recurrentgemma_9b": get_smoke_config("recurrentgemma-9b"), # MQA 4q/1kv
}


def _paged_problem(rng, lens, Hq, Hkv, d, ps, extra_pages=0):
    B = len(lens)
    width = max(-(-L // ps) for L in lens)
    num_pages = 1 + sum(-(-L // ps) for L in lens) + extra_pages
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


# schedule iterations per grid step of the fused kernel, beside its default
STEPS = [1, 2, 3, 8]


def _fused_at(n, q, k_pool, v_pool, ptbl, lens, G, k_scales=None,
              v_scales=None):
    """The paged fused kernel with ``n`` iterations per grid step."""
    B, Hq, d = q.shape
    _, Hkv, ps, _ = k_pool.shape
    sched = make_schedule(lens, Hkv, ps, G)
    route = _paged_route(sched, jnp.asarray(ptbl), Hkv, True, 0, n)
    k_rows, v_rows, ks, vs = _pool_rows(k_pool, v_pool, k_scales, v_scales)
    o, _ = lean_decode_paged_fused(
        q.reshape(B * Hkv, Hq // Hkv, d), k_rows, v_rows,
        jnp.asarray(np.repeat(lens, Hkv), jnp.int32), route, sched,
        1.0 / np.sqrt(d), interpret=True, k_scales=ks, v_scales=vs,
        iters_per_step=n,
    )
    return np.asarray(o.reshape(B, Hq, d).astype(q.dtype))


@functools.lru_cache(maxsize=None)
def _checked_problem(lens, cfg, ps, G, seed, rtol):
    """The problem and its default fused output, once checked against the
    oracle, the two-phase kernels and the dense kernels (memoized: the
    parity cases run once per ``n``)."""
    Hq, Hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(seed)
    q, k_pool, v_pool, ptbl = _paged_problem(rng, lens, Hq, Hkv, d, ps)
    k_dense = paged_gather_kv(k_pool, jnp.asarray(ptbl))
    v_dense = paged_gather_kv(v_pool, jnp.asarray(ptbl))
    ref = lean_decode_ref(
        q, k_dense, v_dense, ctx_lens=jnp.asarray(lens, jnp.int32)
    )
    outs = {}
    for fused in (True, False):
        outs[fused] = np.asarray(lean_decode_paged(
            q, k_pool, v_pool, ptbl, lens, num_workers=G, fused=fused,
            interpret=True,
        ))
        np.testing.assert_allclose(
            outs[fused], np.asarray(ref), rtol=rtol, atol=rtol,
            err_msg=f"paged fused={fused} vs oracle, lens={lens}",
        )
        # acceptance: bit-compatible with the dense kernel on equal inputs
        dense = np.asarray(lean_decode(
            q, k_dense, v_dense, lens, num_workers=G, tile=ps, fused=fused,
            interpret=True,
        ))
        assert np.array_equal(outs[fused], dense), (
            f"paged fused={fused} not bit-identical to dense, lens={lens}"
        )
    np.testing.assert_allclose(outs[True], outs[False], rtol=rtol, atol=rtol)
    return (q, k_pool, v_pool, ptbl), outs[True]


def _check_case(lens, cfg, ps, G, seed, rtol=2e-5, n=None):
    problem, fused = _checked_problem(tuple(lens), cfg, ps, G, seed, rtol)
    if n is not None:
        # n iterations a grid step: bit-identical to the default grouping,
        # so to every other n (n = 1 included) and to the dense kernel
        got = _fused_at(n, *problem, list(lens), G)
        assert np.array_equal(got, fused), (
            f"paged fused at n={n} not bit-identical, lens={lens}"
        )


@pytest.mark.parametrize("n", STEPS)
@pytest.mark.parametrize("geom", sorted(GEOMS))
def test_fused_vs_two_phase_paged_parity(geom, n):
    cfg = GEOMS[geom]
    _check_case([40, 7, 23], cfg, ps=16, G=6, seed=hash(geom) % 2**32, n=n)


@pytest.mark.parametrize("n", STEPS)
@pytest.mark.parametrize("geom", sorted(GEOMS))
def test_paged_freshly_admitted_single_token_slot(geom, n):
    """The ctx == 0 freshly-admitted edge: a slot whose cache holds nothing
    but the token written this very step (runtime length 1, exactly one
    just-allocated page) next to a mid-stream slot."""
    cfg = GEOMS[geom]
    _check_case([1, 50], cfg, ps=16, G=4, seed=hash(geom) % 2**32 + 1, n=n)


# ragged lengths whose grouped steps straddle every boundary (below)
STRADDLE_LENS, STRADDLE_G = [47, 1, 28, 60], 5


@pytest.mark.parametrize("n", [3, 8])
def test_fused_steps_straddle_every_boundary(n):
    """At the parity tests' lengths some grid step holds a segment's first
    iteration after another's, some flushes a piece before its end, one
    holds both partial and merge iterations, and the last ends in OP_PAD."""
    cfg = GEOMS["mistral_nemo_12b"]
    sched = make_schedule(STRADDLE_LENS, cfg.n_kv_heads, 16, STRADDLE_G)
    desc = sched.fused_descriptors(n)
    assert desc.shape[1] % n == 0
    op, first, last = (desc[r].reshape(-1, n) for r in (6, 3, 4))
    part = op == OP_PARTIAL
    assert (part[:, 1:] & (first[:, 1:] == 1)).any()      # segment starts
    assert (part[:, :-1] & (last[:, :-1] == 1)).any()     # piece flushes
    assert (part.any(1) & (op == OP_MERGE).any(1)).any()  # partial -> merge
    assert op[-1, -1] == OP_PAD                             # padded tail


@functools.lru_cache(maxsize=None)
def _narrow_problem(kv):
    """A bf16 or int8 pool problem at the straddling lengths and its
    default fused output (bf16: checked bit-identical to the dense fused
    kernel). Memoized across ``n``."""
    cfg = GEOMS["mistral_nemo_12b"]
    Hq, Hkv, d, ps = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, 16
    lens, G = STRADDLE_LENS, STRADDLE_G
    rng = np.random.default_rng(11)
    q, k_pool, v_pool, ptbl = _paged_problem(rng, lens, Hq, Hkv, d, ps)
    q = q.astype(jnp.bfloat16)
    scales = {}
    if kv == "int8":
        (k_pool, ks), (v_pool, vs) = map(quantize_kv_blocks, (k_pool, v_pool))
        scales = {"k_scales": ks, "v_scales": vs}
    else:
        k_pool, v_pool = (p.astype(jnp.bfloat16) for p in (k_pool, v_pool))
    want = np.asarray(lean_decode_paged(
        q, k_pool, v_pool, ptbl, lens, num_workers=G, interpret=True, **scales
    ))
    if kv == "bf16":
        dense = lean_decode(
            q, paged_gather_kv(k_pool, jnp.asarray(ptbl)),
            paged_gather_kv(v_pool, jnp.asarray(ptbl)), lens, num_workers=G,
            tile=ps, fused=True, interpret=True,
        )
        assert np.array_equal(np.asarray(dense), want)
    return (q, k_pool, v_pool, ptbl), scales, want


@pytest.mark.parametrize("n", STEPS)
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_fused_steps_bit_identical_on_narrow_pools(kv, n):
    """bf16 and int8 pools (int8 with its per-(page, head) scales routed per
    iteration): the fused kernel at n iterations a step is bit-identical to
    its default grouping — and, for bf16, to the dense fused kernel."""
    problem, scales, want = _narrow_problem(kv)
    got = _fused_at(n, *problem, STRADDLE_LENS, STRADDLE_G, **scales)
    assert np.array_equal(got, want)


def test_iters_per_step_follow_the_tile_bytes():
    """n = 128 KiB of K and V over one iteration's bytes, within [1, 4]:
    2 for bf16 128 x 128 pages, 4 for int8, 1 for f32; smaller tiles reach
    the cap, a tile over 128 KiB runs alone."""
    assert fused_iters_per_step(128, 128, 2) == 2
    assert fused_iters_per_step(128, 128, 1) == 4
    assert fused_iters_per_step(128, 128, 4) == 1
    assert fused_iters_per_step(64, 128, 2) == 4
    assert fused_iters_per_step(16, 16, 4) == MAX_ITERS_PER_STEP == 4
    assert fused_iters_per_step(256, 256, 2) == 1


def test_paged_idle_slot_null_page_stays_finite():
    """An idle slot routed entirely to the null page (all-zero table row)
    must produce finite output and must not perturb live slots — this is
    what the engine relies on for empty batch slots."""
    cfg = GEOMS["mistral_nemo_12b"]
    Hq, Hkv, d, ps = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, 16
    rng = np.random.default_rng(3)
    q, k_pool, v_pool, ptbl = _paged_problem(rng, [33, 16], Hq, Hkv, d, ps)
    ptbl[1, :] = 0                                   # slot 1 idle: null page
    lens = [33, 1]
    ref = lean_decode_ref(
        q, paged_gather_kv(k_pool, jnp.asarray(ptbl)),
        paged_gather_kv(v_pool, jnp.asarray(ptbl)),
        ctx_lens=jnp.asarray(lens, jnp.int32),
    )
    for fused in (True, False):
        out = np.asarray(lean_decode_paged(
            q, k_pool, v_pool, ptbl, lens, page_counts=[3, 1],
            num_workers=4, fused=fused, interpret=True,
        ))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[0], np.asarray(ref)[0],
                                   rtol=2e-5, atol=2e-5)


def test_paged_overflow_clamps_with_warning():
    """Satellite fix: lengths beyond the allocated pages clamp to the
    per-sequence page capacity and WARN instead of truncating silently."""
    cfg = GEOMS["mistral_nemo_12b"]
    Hq, Hkv, d, ps = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, 16
    rng = np.random.default_rng(5)
    q, k_pool, v_pool, ptbl = _paged_problem(rng, [32, 16], Hq, Hkv, d, ps)
    ref = lean_decode_ref(
        q, paged_gather_kv(k_pool, jnp.asarray(ptbl)),
        paged_gather_kv(v_pool, jnp.asarray(ptbl)),
        ctx_lens=jnp.asarray([32, 16], jnp.int32),
    )
    with pytest.warns(RuntimeWarning, match="exceeds KV capacity"):
        out = lean_decode_paged(
            q, k_pool, v_pool, ptbl, [32, 999], num_workers=4,
            interpret=True,
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_dense_overflow_warns_too():
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((1, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 32, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 32, 16)), jnp.float32)
    with pytest.warns(RuntimeWarning, match="exceeds KV capacity"):
        lean_decode(q, k, v, [64], num_workers=2, tile=16, interpret=True)


@pytest.mark.slow
@pytest.mark.parametrize("geom", sorted(GEOMS))
def test_paged_parity_fuzz(geom):
    """Randomized sweep: ragged batches, page permutations, worker counts,
    page sizes — fused vs two-phase vs dense oracle every time."""
    cfg = GEOMS[geom]
    rng = np.random.default_rng(hash(geom) % 2**32 + 17)
    for trial in range(25):
        B = int(rng.integers(1, 5))
        ps = int(rng.choice([8, 16, 32]))
        lens = [int(rng.integers(1, 5 * ps)) for _ in range(B)]
        G = int(rng.integers(1, 13))
        _check_case(lens, cfg, ps=ps, G=G, seed=int(rng.integers(2**32)))
