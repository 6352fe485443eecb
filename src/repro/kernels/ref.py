"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth in kernel sweeps (tests/test_kernels_*.py):
each kernel output must ``assert_allclose`` against its oracle over a grid
of shapes/dtypes, including ragged context lengths. Each oracle runs its
matmuls at ``precision="highest"``: on a TPU a float32 matmul otherwise
runs in bf16 passes, and fp32 inputs would get a bf16 reference.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.core.attention import (
    fixed_split_decode,
    mha_chunk_prefill_paged_ref,
    mha_decode_ref,
    mha_prefill_ref,
)


def _highest(fn):
    @functools.wraps(fn)
    def at_highest(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return at_highest


@_highest
def lean_decode_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    ctx_lens: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """The lean kernel computes *exact* attention; oracle = standard decode."""
    return mha_decode_ref(q, k, v, ctx_lens=ctx_lens, scale=scale)


@_highest
def flash_decode_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    ctx_lens: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Fixed-split also computes exact attention; same oracle."""
    return mha_decode_ref(q, k, v, ctx_lens=ctx_lens, scale=scale)


@_highest
def flash_prefill_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
) -> jax.Array:
    return mha_prefill_ref(
        q, k, v, causal=causal, window=window, scale=scale, q_offset=q_offset
    )


@_highest
def lean_prefill_chunks_ref(
    q: jax.Array,           # (N, Hq, C, d) one prompt chunk per row
    k_pool: jax.Array,      # (num_pages, Hkv, page_size, d)
    v_pool: jax.Array,
    page_tbls: jax.Array,   # (N, W) int32
    offs: jax.Array,        # (N,) int32 absolute position of each q[0]
    scale: Optional[float] = None,
) -> jax.Array:
    """Chunked prefill through the page table: gather, then causal attention
    at each row's own depth (oracle of ``ops.lean_prefill_chunks``)."""
    return mha_chunk_prefill_paged_ref(
        q, k_pool, v_pool, page_tbls, offs, scale=scale
    )


fixed_split_decode_ref = _highest(fixed_split_decode)
