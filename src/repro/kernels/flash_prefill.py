"""FlashAttention-2 prefill kernel (Pallas/TPU): causal + sliding-window, GQA.

Used by the training / prefill path. Grid ``(B*Hq, n_q_blocks, n_kv_blocks)``
with VMEM online-softmax accumulation over the kv axis; fully-masked kv
blocks (beyond causal diagonal or outside the sliding window) are skipped via
``pl.when`` so the causal schedule does ~half the work, window schedules
O(window) work.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _prefill_kernel(
    q_ref,       # (1, bq, d)
    k_ref,       # (1, bk, d)
    v_ref,       # (1, bk, d)
    o_ref,       # (1, bq, d)
    acc_ref,     # VMEM (bq, d) f32
    m_acc_ref,   # VMEM (bq, 1)
    l_acc_ref,   # VMEM (bq, 1)
    *,
    scale: float,
    block_q: int,
    block_kv: int,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    kv_len: int,
):
    qb = pl.program_id(1)
    jb = pl.program_id(2)
    q_start = qb * block_q + q_offset          # absolute positions
    k_start = jb * block_kv

    @pl.when(jb == 0)
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_acc_ref[...] = jnp.full_like(m_acc_ref, NEG_INF)
        l_acc_ref[...] = jnp.zeros_like(l_acc_ref)

    # block-level relevance test
    relevant = k_start < kv_len
    if causal:
        relevant &= k_start <= q_start + block_q - 1
    if window is not None:
        relevant &= (k_start + block_kv - 1) > (q_start - window)

    @pl.when(relevant)
    def _work():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                       # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = kpos < kv_len
        if causal:
            ok &= kpos <= qpos
        if window is not None:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_acc_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_acc_ref[...] = alpha * l_acc_ref[...] + jnp.sum(
            p, axis=1, keepdims=True
        )
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_acc_ref[...] = m_new

    @pl.when(jb == pl.num_programs(2) - 1)
    def _flush():
        # rows with no attended keys (can't happen causally) guard: l>0
        l = jnp.maximum(l_acc_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_prefill(
    q: jax.Array,   # (B, Hq, Lq, d)
    k: jax.Array,   # (B, Hkv, Lk, d)
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """FA-2 prefill. Lq/Lk padded to block multiples internally."""
    B, Hq, Lq, d = q.shape
    _, Hkv, Lk, _ = k.shape
    g = Hq // Hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, max(8, Lq))
    block_kv = min(block_kv, max(8, Lk))
    pq = (-Lq) % block_q
    pk = (-Lk) % block_kv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v
    Lqp, Lkp = Lq + pq, Lk + pk

    qf = qp.reshape(B * Hq, Lqp, d)
    kf = kp.reshape(B * Hkv, Lkp, d)
    vf = vp.reshape(B * Hkv, Lkp, d)

    nq, nk = Lqp // block_q, Lkp // block_kv
    kernel = functools.partial(
        _prefill_kernel,
        scale=scale,
        block_q=block_q,
        block_kv=block_kv,
        causal=causal,
        window=window,
        q_offset=q_offset,
        kv_len=Lk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, qb, jb: (h, qb, 0)),
            pl.BlockSpec((1, block_kv, d), lambda h, qb, jb: (h // g, jb, 0)),
            pl.BlockSpec((1, block_kv, d), lambda h, qb, jb: (h // g, jb, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, qb, jb: (h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Lqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="flash_prefill",
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, Lqp, d)[:, :, :Lq, :]


# ------------------------------------------------------------- paged chunks
def _prefill_paged_kernel(
    tbl_ref,     # (N*W,) scalar prefetch: flattened page tables
    qoff_ref,    # (N,)   scalar prefetch: absolute position of each chunk's q[0]
    q_ref,       # (1, bq, d)
    k_ref,       # (1, page_size, d)  fetched through the page table
    v_ref,       # (1, page_size, d)
    o_ref,       # (1, bq, d)
    acc_ref,     # VMEM (bq, d) f32
    m_acc_ref,   # VMEM (bq, 1)
    l_acc_ref,   # VMEM (bq, 1)
    *,
    scale: float,
    block_q: int,
    page_size: int,
    n_heads: int,
):
    nh = pl.program_id(0)
    qb = pl.program_id(1)
    jb = pl.program_id(2)
    n = nh // n_heads
    # runtime offsets: one trace serves every chunk depth of every prompt
    q_start = qoff_ref[n] + qb * block_q
    k_start = jb * page_size

    @pl.when(jb == 0)
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_acc_ref[...] = jnp.full_like(m_acc_ref, NEG_INF)
        l_acc_ref[...] = jnp.zeros_like(l_acc_ref)

    # causal block skip doubles as the length guard: pages holding only
    # positions beyond the chunk's last query are stale/unwritten and masked
    @pl.when(k_start <= q_start + block_q - 1)
    def _work():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                       # (bq, page_size)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = kpos <= qpos
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_acc_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_acc_ref[...] = alpha * l_acc_ref[...] + jnp.sum(
            p, axis=1, keepdims=True
        )
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_acc_ref[...] = m_new

    @pl.when(jb == pl.num_programs(2) - 1)
    def _flush():
        l = jnp.maximum(l_acc_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_prefill_paged(
    q: jax.Array,           # (N, Hq, C, d) one prompt chunk per row
    k_pool: jax.Array,      # (num_pages, Hkv, page_size, d)
    v_pool: jax.Array,
    page_tbls: jax.Array,   # (N, W) int32 page table rows
    q_offsets: jax.Array,   # (N,) int32 absolute position of each chunk's q[0]
    scale: Optional[float] = None,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """FA-2 chunked prefill *through the page table* (fixed-grid baseline).

    The paged twin of :func:`flash_prefill` for the continuous-batching
    scheduler: each pack row is one prompt chunk whose KV — everything
    prefilled so far plus the chunk itself, already appended via
    :func:`repro.core.attention.paged_scatter_tokens` — lives in the global
    page pool. The kv grid axis walks the page-table width and a scalar-
    prefetch operand routes block ``j`` to flattened pool row
    ``tbl[n, j] * H_kv + head``; ``q_offsets`` is a runtime operand so one
    trace serves every chunk of every prompt (jit-stable static chunk
    geometry). Causal masking against absolute positions subsumes the
    length mask: stale data in partially-filled or unwritten pages always
    sits at key positions greater than every valid query position.

    Chunk-padding q rows produce garbage outputs the caller discards.
    """
    N, Hq, C, d = q.shape
    num_pages, Hkv, page_size, _ = k_pool.shape
    W = page_tbls.shape[1]
    g = Hq // Hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = min(block_q, max(8, C))
    pq = (-C) % block_q
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    Cp = C + pq
    qf = qp.reshape(N * Hq, Cp, d)
    k_rows = k_pool.reshape(num_pages * Hkv, page_size, d)
    v_rows = v_pool.reshape(num_pages * Hkv, page_size, d)
    nq = Cp // block_q

    def kv_map(nh, qb, jb, tbl, qoff):
        return (tbl[(nh // Hq) * W + jb] * Hkv + (nh % Hq) // g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N * Hq, nq, W),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda nh, qb, jb, *_: (nh, qb, 0)),
            pl.BlockSpec((1, page_size, d), kv_map),
            pl.BlockSpec((1, page_size, d), kv_map),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda nh, qb, jb, *_: (nh, qb, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_paged_kernel,
        scale=scale, block_q=block_q, page_size=page_size, n_heads=Hq,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N * Hq, Cp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="flash_prefill_paged",
        interpret=interpret,
    )(
        page_tbls.reshape(-1).astype(jnp.int32),
        q_offsets.astype(jnp.int32),
        qf, k_rows, v_rows,
    )
    return out.reshape(N, Hq, Cp, d)[:, :, :C, :]
