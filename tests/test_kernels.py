"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.leantile import make_schedule
from repro.kernels import flash_decode, flash_prefill, lean_decode
from repro.kernels.lean_decode import lean_decode_fused
from repro.kernels.ops import _pad_kv, _to_segments
from repro.kernels.ref import flash_prefill_ref, lean_decode_ref

jax.config.update("jax_platform_name", "cpu")


def mk(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


DECODE_CASES = [
    # B, Hq, Hkv, S, d, G, tile, ragged
    (1, 1, 1, 64, 64, 4, 32, False),
    (2, 4, 2, 300, 64, 5, 64, False),
    (1, 8, 1, 777, 128, 6, 128, True),     # MQA, ragged
    (2, 8, 4, 128, 64, 16, 32, True),      # more workers than tiles
    (3, 6, 6, 95, 32, 7, 16, True),        # MHA raggged, odd sizes
    (1, 16, 2, 1024, 128, 12, 128, False), # GQA 8
    (4, 4, 4, 33, 16, 3, 8, True),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lean_decode_vs_oracle(case, dtype):
    B, Hq, Hkv, S, d, G, tile, ragged = case
    rng = np.random.default_rng(hash(case) % 2**32)
    q = mk(rng, (B, Hq, d), dtype)
    k = mk(rng, (B, Hkv, S, d), dtype)
    v = mk(rng, (B, Hkv, S, d), dtype)
    lens = list(rng.integers(1, S + 1, B)) if ragged else [S] * B
    ref = lean_decode_ref(q, k, v, ctx_lens=jnp.asarray(lens, jnp.int32))
    out = lean_decode(q, k, v, lens, num_workers=G, tile=tile,
                      interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def _dense_fused_at(n, q, k, v, lens, G, tile):
    """The dense fused kernel with ``n`` schedule iterations a grid step."""
    B, Hq, d = q.shape
    q_seg, k_seg, v_seg, _ = _to_segments(q, k, v)
    k_seg, v_seg = _pad_kv(k_seg, v_seg, tile)
    Hkv = k.shape[1]
    o, _ = lean_decode_fused(
        q_seg, k_seg, v_seg, jnp.asarray(np.repeat(lens, Hkv), jnp.int32),
        make_schedule(lens, Hkv, tile, G), 1.0 / np.sqrt(d), interpret=True,
        iters_per_step=n,
    )
    return np.asarray(o.reshape(B, Hq, d).astype(q.dtype))


@functools.lru_cache(maxsize=None)
def _checked_fused_case(case):
    """The case's problem and its fused output, once checked against the
    two-phase path and the jnp oracle (memoized: the case runs per n)."""
    B, Hq, Hkv, S, d, G, tile, ragged = case
    rng = np.random.default_rng(hash(case) % 2**32 + 1)
    q = mk(rng, (B, Hq, d), jnp.float32)
    k = mk(rng, (B, Hkv, S, d), jnp.float32)
    v = mk(rng, (B, Hkv, S, d), jnp.float32)
    lens = list(rng.integers(1, S + 1, B)) if ragged else [S] * B
    ref = lean_decode_ref(q, k, v, ctx_lens=jnp.asarray(lens, jnp.int32))
    fused = lean_decode(q, k, v, lens, num_workers=G, tile=tile,
                        fused=True, interpret=True)
    two_phase = lean_decode(q, k, v, lens, num_workers=G, tile=tile,
                            fused=False, interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two_phase),
                               rtol=1e-5, atol=1e-5)
    return q, k, v, [int(x) for x in lens], np.asarray(fused)


@pytest.mark.parametrize("n", [1, 2, 3, 8])
@pytest.mark.parametrize("case", DECODE_CASES)
def test_lean_decode_fused_vs_two_phase_vs_oracle(case, n):
    """The single-pallas_call fused partial+merge kernel must match both
    the two-phase path and the jnp oracle on ragged batches. The case list
    includes the 1-segment (B=1 MQA) and pieces>workers edge cases. At
    ``n`` schedule iterations a grid step its output is bit-identical to
    its default grouping's, so to every other ``n``."""
    q, k, v, lens, fused = _checked_fused_case(case)
    G, tile = case[5], case[6]
    assert np.array_equal(_dense_fused_at(n, q, k, v, lens, G, tile), fused)


def test_lean_decode_fused_single_segment_single_piece():
    """Degenerate 1-segment/1-worker problem: the whole context is one
    piece; the fused kernel's merge phase reduces a single partial."""
    rng = np.random.default_rng(3)
    q = mk(rng, (1, 1, 16), jnp.float32)
    k = mk(rng, (1, 1, 16, 16), jnp.float32)
    v = mk(rng, (1, 1, 16, 16), jnp.float32)
    ref = lean_decode_ref(q, k, v)
    out = lean_decode(q, k, v, num_workers=1, tile=16, fused=True,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_lean_decode_fused_lse_matches_two_phase():
    rng = np.random.default_rng(4)
    B, Hq, Hkv, S, d = 2, 4, 2, 160, 32
    q = mk(rng, (B, Hq, d), jnp.float32)
    k = mk(rng, (B, Hkv, S, d), jnp.float32)
    v = mk(rng, (B, Hkv, S, d), jnp.float32)
    lens = [150, 37]
    _, lse_f = lean_decode(q, k, v, lens, num_workers=5, tile=32,
                           fused=True, interpret=True, return_lse=True)
    _, lse_t = lean_decode(q, k, v, lens, num_workers=5, tile=32,
                           fused=False, interpret=True, return_lse=True)
    np.testing.assert_allclose(np.asarray(lse_f), np.asarray(lse_t),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", DECODE_CASES[:4])
def test_lean_decode_pallas_merge(case):
    B, Hq, Hkv, S, d, G, tile, ragged = case
    rng = np.random.default_rng(0)
    q = mk(rng, (B, Hq, d), jnp.float32)
    k = mk(rng, (B, Hkv, S, d), jnp.float32)
    v = mk(rng, (B, Hkv, S, d), jnp.float32)
    lens = list(rng.integers(1, S + 1, B)) if ragged else [S] * B
    a = lean_decode(q, k, v, lens, num_workers=G, tile=tile,
                    interpret=True, merge_impl="xla")
    b = lean_decode(q, k, v, lens, num_workers=G, tile=tile,
                    interpret=True, merge_impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("num_splits", [1, 3, 8])
def test_flash_decode_vs_oracle(case, num_splits):
    B, Hq, Hkv, S, d, G, tile, ragged = case
    rng = np.random.default_rng(hash(case) % 2**32 + num_splits)
    q = mk(rng, (B, Hq, d), jnp.float32)
    k = mk(rng, (B, Hkv, S, d), jnp.float32)
    v = mk(rng, (B, Hkv, S, d), jnp.float32)
    lens = list(rng.integers(1, S + 1, B)) if ragged else [S] * B
    ref = lean_decode_ref(q, k, v, ctx_lens=jnp.asarray(lens, jnp.int32))
    out = flash_decode(q, k, v, lens, num_splits=num_splits, tile=tile,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


PREFILL_CASES = [
    # B, Hq, Hkv, Lq, Lk, d, causal, window
    (2, 4, 2, 64, 64, 64, True, None),
    (1, 4, 4, 100, 100, 64, True, 32),
    (2, 2, 1, 37, 150, 128, False, None),
    (1, 8, 2, 128, 256, 32, True, None),   # q shorter than kv (chunked)
    (1, 2, 2, 65, 65, 16, True, 16),
]


@pytest.mark.parametrize("case", PREFILL_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_vs_oracle(case, dtype):
    B, Hq, Hkv, Lq, Lk, d, causal, window = case
    rng = np.random.default_rng(hash(case) % 2**32)
    q = mk(rng, (B, Hq, Lq, d), dtype)
    k = mk(rng, (B, Hkv, Lk, d), dtype)
    v = mk(rng, (B, Hkv, Lk, d), dtype)
    off = Lk - Lq if causal else 0
    ref = flash_prefill_ref(q, k, v, causal=causal, window=window,
                            q_offset=off)
    out = flash_prefill(q, k, v, causal=causal, window=window, q_offset=off,
                        block_q=32, block_kv=32, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_lean_decode_generalizes_fa2_and_fd():
    """Paper §IV-C: FA2 (G == segments) and FlashDecoding (G == s*segments)
    are special cases of the lean schedule — all bit-exact here."""
    rng = np.random.default_rng(7)
    B, Hq, Hkv, S, d = 2, 4, 2, 512, 64
    q = mk(rng, (B, Hq, d), jnp.float32)
    k = mk(rng, (B, Hkv, S, d), jnp.float32)
    v = mk(rng, (B, Hkv, S, d), jnp.float32)
    ref = lean_decode_ref(q, k, v)
    segs = B * Hkv
    for G in (segs, 2 * segs, 3 * segs, 5):  # FA2-like, FD-like, odd
        out = lean_decode(q, k, v, num_workers=G, tile=64, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
