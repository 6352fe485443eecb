"""Reference decode/prefill attention paths (pure jnp) + schedule executors.

Three *schedules* from the paper, all computing bit-identical exact attention:

  * ``mha_decode_ref``        — oracle: one fused softmax over the full context.
  * ``fixed_split_decode``    — FlashDecoding: split context into ``s`` equal
                                chunks per (batch, head), merge partials.
  * ``lean_decode_jnp``       — LeanAttention: execute a
                                :class:`~repro.core.leantile.LeanSchedule`
                                (equal LeanTiles per worker, pieces merged by
                                the associative operator).

The Pallas kernels in :mod:`repro.kernels` implement the same schedules for
TPU; these jnp versions are their oracles and the CPU/dry-run execution path.

Decode shapes: ``q (B, Hq, d)``, ``k/v (B, Hkv, S, d)`` with GQA group
``g = Hq // Hkv``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .leantile import LeanSchedule
from .merge import AttnPartial, finalize, merge_n, segment_merge

NEG_INF = -1e30  # finite mask value: keeps (m, l) stats well-defined


def _length_mask(scores: jax.Array, ctx_lens: Optional[jax.Array], offset: int = 0):
    """Mask score positions >= per-batch context length. scores: (B,...,S)."""
    if ctx_lens is None:
        return scores
    S = scores.shape[-1]
    pos = jnp.arange(S) + offset
    mask = pos[None, :] < ctx_lens[:, None]            # (B, S)
    mask = mask.reshape(mask.shape[0], *([1] * (scores.ndim - 2)), S)
    return jnp.where(mask, scores, NEG_INF)


def paged_gather_kv(pool: jax.Array, page_tbl: jax.Array) -> jax.Array:
    """Materialize a dense per-sequence KV view from a paged pool.

    ``pool: (num_pages, H_kv, page_size, d)``; ``page_tbl: (B, T) int32``
    maps logical tile ``t`` of sequence ``b`` to a physical page (null-page
    entries gather masked garbage — callers mask by context length).
    Returns ``(B, H_kv, T * page_size, d)``.

    This is the oracle for the page-routed kernels and the paged execution
    path for backends without native paging (ref / fixed-split): gather then
    run the dense schedule.
    """
    g = pool[page_tbl]                       # (B, T, H, page, d)
    B, T, H, ps, d = g.shape
    return jnp.moveaxis(g, 2, 1).reshape(B, H, T * ps, d)


def paged_write_rows(
    pool: jax.Array,        # (num_pages, H, page_size, d)
    pages: jax.Array,       # (T,) int32 physical page of each token
    offsets: jax.Array,     # (T,) int32 position of each token in its page
    vals: jax.Array,        # (T, H, d) the tokens' K or V vectors
) -> jax.Array:
    """Write token vectors into a paged pool row-wise, in place.

    The pool is viewed as ``num_pages * H`` pool rows of ``(page_size, d)``
    (the rows the paged kernels read): head ``h`` of token ``t`` lands in
    row ``pages[t] * H + h`` at offset ``offsets[t]``. The scatter's only
    window dim is the minor ``d``, so the pool keeps the kernels' layout
    and is updated where it lies. With a ``(H, d)`` window XLA's TPU
    layout pass wants the heads second-minor, and copies the whole pool
    into that layout and back around every write.
    """
    num_pages, H, ps, d = pool.shape
    rows = pages[:, None] * H + jnp.arange(H)[None, :]        # (T, H)
    at = jnp.broadcast_to(offsets[:, None], rows.shape)
    out = pool.reshape(num_pages * H, ps, d).at[
        rows.reshape(-1), at.reshape(-1)
    ].set(vals.reshape(-1, d).astype(pool.dtype))
    return out.reshape(pool.shape)


def _chunk_targets(page_tbls, offs, lens, C, ps, page_base):
    """Physical page and in-page offset of each chunk position ``(N, C)``;
    positions past ``lens`` go to the null page (page ``page_base``) at 0.
    Also returns the ``(N, C)`` validity mask."""
    W = page_tbls.shape[1]
    pos = offs[:, None] + jnp.arange(C)[None, :]              # (N, C)
    valid = jnp.arange(C)[None, :] < lens[:, None]
    tile_idx = jnp.clip(pos // ps, 0, W - 1)
    pages = jnp.where(
        valid, jnp.take_along_axis(page_tbls, tile_idx, axis=1), 0
    ) + page_base
    offsets = jnp.where(valid, pos % ps, 0)
    return pages, offsets, valid


def paged_scatter_tokens(
    pool: jax.Array,        # (num_pages, H, page_size, d)
    page_tbls: jax.Array,   # (N, W) int32 page table rows
    offs: jax.Array,        # (N,) int32 first logical position of each chunk
    lens: jax.Array,        # (N,) int32 valid tokens per chunk
    vals: jax.Array,        # (N, C, H, d) new K or V rows
    page_base=0,            # pool page of the table's page 0
) -> jax.Array:
    """Scatter chunk tokens *directly* into a paged pool via the page table.

    Chunk row ``n`` writes token ``i < lens[n]`` at logical position
    ``offs[n] + i`` — physical page ``page_base + page_tbls[n, pos //
    page_size]``, offset ``pos % page_size``. Invalid positions (``i >=
    lens[n]``, e.g. chunk padding or dummy pack rows) route to the null
    page, whose contents are always masked by runtime context lengths. This
    is the chunked prefill's KV append: no dense per-slot staging cache, no
    copy-on-admit. ``page_base`` lets ``pool`` be a stack of layers' pools
    viewed as one: layer ``r``'s tables pass ``r * pages_per_layer``, and
    its null page is its own page 0.

    Live chunk rows never collide (requests hold disjoint page sets and a
    chunk's positions are distinct); only null-page writes may overlap,
    which is harmless by construction.
    """
    N, C, H, d = vals.shape
    pages, offsets, _ = _chunk_targets(
        page_tbls, offs, lens, C, pool.shape[2], page_base
    )
    return paged_write_rows(
        pool, pages.reshape(-1), offsets.reshape(-1),
        vals.reshape(N * C, H, d),
    )


# ------------------------------------------------------------ quantized KV
# Symmetric int8 page storage: pool values are round(x / scale) with one
# f32 scale per (page, kv_head) (or per page, stored broadcast across the
# head axis so the kernel-side layout never changes). Scales only ever
# GROW while a page is live — writes compute the candidate scale of the
# incoming tokens, scatter-max it into the sidecar, requantize the touched
# pages' existing int8 content by round(q * old/new), then quantize the new
# tokens at the final scale. A scale of 0 (fresh or scrubbed page)
# dequantizes to exact zeros.

INT8_QMAX = 127.0


def quantize_kv_blocks(vals: jax.Array, per_head: bool = True):
    """Quantize whole KV blocks ``(..., H, P, d)`` to int8 + f32 scales.

    Returns ``(q, scales)`` with ``q`` int8 of ``vals.shape`` and
    ``scales (..., H)`` — per (block, head) at ``per_head=True``, else one
    scale per block broadcast across the head axis (identical downstream
    layout, coarser rounding)."""
    amax = jnp.abs(vals.astype(jnp.float32)).max(axis=(-2, -1))   # (..., H)
    if not per_head:
        amax = jnp.broadcast_to(
            amax.max(axis=-1, keepdims=True), amax.shape
        )
    scales = amax / INT8_QMAX
    inv = jnp.where(scales > 0, 1.0 / jnp.maximum(scales, 1e-30), 0.0)
    q = jnp.round(vals.astype(jnp.float32) * inv[..., None, None])
    q = jnp.clip(q, -INT8_QMAX, INT8_QMAX).astype(jnp.int8)
    return q, scales.astype(jnp.float32)


def paged_gather_kv_dequant(
    pool: jax.Array,        # (num_pages, H, page_size, d) int8
    scales: jax.Array,      # (num_pages, H) f32
    page_tbl: jax.Array,    # (B, T) int32
    dtype=jnp.float32,
) -> jax.Array:
    """:func:`paged_gather_kv` for quantized pools: gather, widen, apply
    each page's per-head scale. Returns ``(B, H, T * page_size, d)``."""
    g = pool[page_tbl].astype(jnp.float32)       # (B, T, H, page, d)
    s = scales[page_tbl]                         # (B, T, H)
    g = g * s[..., None, None]
    B, T, H, ps, d = g.shape
    return jnp.moveaxis(g, 2, 1).reshape(B, H, T * ps, d).astype(dtype)


def paged_scatter_tokens_quant(
    pool: jax.Array,        # (num_pages, H, page_size, d) int8
    scales: jax.Array,      # (num_pages, H) f32 per-(page, head) scales
    page_tbls: jax.Array,   # (N, W) int32 page table rows
    offs: jax.Array,        # (N,) int32 first logical position of each chunk
    lens: jax.Array,        # (N,) int32 valid tokens per chunk
    vals: jax.Array,        # (N, C, H, d) new K or V rows (fp)
    per_head: bool = True,
    page_base=0,            # pool page of the table's page 0
):
    """Quantizing counterpart of :func:`paged_scatter_tokens`.

    The single write chokepoint for int8 pools: (1) scatter-max the
    incoming tokens' candidate scales (amax/127 per (token, head)) into
    the touched pages' scale rows — scales only grow while a page is
    live; (2) requantize the touched pages' *existing* int8 content by
    ``round(q * old/new)`` (untouched pages keep old == new and are never
    read); (3) quantize the new tokens at the final scale and scatter.
    Invalid positions route to the null page, and ``page_base`` offsets
    the table into a stack of layers' pools, exactly like the fp path.
    Returns ``(pool, scales)``.
    """
    N, C, H, d = vals.shape
    pages, offsets, valid = _chunk_targets(
        page_tbls, offs, lens, C, pool.shape[2], page_base
    )
    flat_pages = pages.reshape(-1)                            # (N*C,)

    vals_f = vals.astype(jnp.float32)
    cand = jnp.abs(vals_f).max(axis=-1) / INT8_QMAX           # (N, C, H)
    if not per_head:
        cand = jnp.broadcast_to(
            cand.max(axis=-1, keepdims=True), cand.shape
        )
    cand = jnp.where(valid[..., None], cand, 0.0)
    new_scales = scales.at[flat_pages].max(cand.reshape(N * C, H))

    # requantize what the touched pages already hold (duplicate page ids
    # write identical requantized blocks — benign)
    old_s = scales[flat_pages]                                # (N*C, H)
    new_s = new_scales[flat_pages]
    factor = jnp.where(new_s > 0, old_s / jnp.maximum(new_s, 1e-30), 0.0)
    requant = jnp.round(
        pool[flat_pages].astype(jnp.float32) * factor[..., None, None]
    )
    requant = jnp.clip(requant, -INT8_QMAX, INT8_QMAX).astype(pool.dtype)
    pool = pool.at[flat_pages].set(requant)

    # quantize the incoming tokens at the final (grown) scales
    tok_s = new_scales[pages]                                 # (N, C, H)
    inv = jnp.where(tok_s > 0, 1.0 / jnp.maximum(tok_s, 1e-30), 0.0)
    q = jnp.round(vals_f * inv[..., None])
    q = jnp.clip(q, -INT8_QMAX, INT8_QMAX).astype(pool.dtype)
    pool = paged_write_rows(
        pool, flat_pages, offsets.reshape(-1), q.reshape(N * C, H, d)
    )
    return pool, new_scales


def mha_chunk_prefill_paged_ref(
    q: jax.Array,           # (N, Hq, C, d) one prompt chunk per row
    k_pool: jax.Array,      # (num_pages, Hkv, page_size, d)
    v_pool: jax.Array,
    page_tbls: jax.Array,   # (N, W) int32
    offs: jax.Array,        # (N,) int32 absolute position of each chunk's q[0]
    scale: Optional[float] = None,
) -> jax.Array:
    """Oracle attention for one pack of prefill chunks against paged KV.

    Each chunk row gathers its dense KV view through its page table and
    attends causally with *per-row* absolute query offsets (``offs`` is a
    runtime array — rows sit at different depths of different prompts).
    Causality doubles as the length mask: stale pool data beyond
    ``offs[n] + C`` always sits at key positions greater than every valid
    query position. Rows' chunk-padding queries produce garbage outputs
    that callers discard; they never contaminate valid rows.
    """
    N, Hq, C, d = q.shape
    Hkv = k_pool.shape[1]
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    k = paged_gather_kv(k_pool, page_tbls)                    # (N, Hkv, K, d)
    v = paged_gather_kv(v_pool, page_tbls)
    K = k.shape[2]
    qg = q.reshape(N, Hkv, g, C, d)
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    qpos = offs[:, None] + jnp.arange(C)[None, :]             # (N, C)
    ok = jnp.arange(K)[None, None, :] <= qpos[..., None]      # (N, C, K)
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(N, Hq, C, d).astype(q.dtype)


def mha_decode_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    ctx_lens: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Oracle decode attention (single new token per sequence)."""
    B, Hq, d = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(B, Hkv, g, d)
    # k/v stay in cache dtype (bf16): f32 copies of a 32k-token cache would
    # double decode HBM traffic; accumulation is f32 via the einsum.
    s = jnp.einsum(
        "bhgd,bhsd->bhgs", qg, k, preferred_element_type=jnp.float32
    ) * scale
    s = _length_mask(s, ctx_lens)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhgs,bhsd->bhgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, Hq, d).astype(q.dtype)


def chunk_partial(
    q: jax.Array,
    k_chunk: jax.Array,
    v_chunk: jax.Array,
    scale: float,
    valid_len: Optional[jax.Array] = None,
) -> AttnPartial:
    """Un-scaled partial attention of q against one KV chunk (paper §IV-A).

    q: (..., g, d); k_chunk/v_chunk: (..., t, d); valid_len: scalar or
    broadcastable — tokens beyond it are masked.
    Returns AttnPartial with o: (..., g, d), m/l: (..., g).
    """
    s = jnp.einsum(
        "...gd,...td->...gt", q, k_chunk,
        preferred_element_type=jnp.float32,
    ) * scale
    if valid_len is not None:
        t = s.shape[-1]
        pos = jnp.arange(t)
        s = jnp.where(pos < valid_len, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    a = jnp.exp(s - m[..., None])
    l = jnp.sum(a, axis=-1)
    o = jnp.einsum(
        "...gt,...td->...gd", a.astype(v_chunk.dtype), v_chunk,
        preferred_element_type=jnp.float32,
    )
    return AttnPartial(o=o, m=m, l=l)


def fixed_split_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    num_splits: int,
    ctx_lens: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """FlashDecoding baseline: fixed-split along context + merge (§III-C)."""
    B, Hq, d = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    split = -(-S // num_splits)
    pad = split * num_splits - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ks = k.reshape(B, Hkv, num_splits, split, d)
    vs = v.reshape(B, Hkv, num_splits, split, d)
    qg = q.reshape(B, Hkv, 1, g, d)
    lens = ctx_lens if ctx_lens is not None else jnp.full((B,), S)
    valid = jnp.clip(
        lens[:, None] - jnp.arange(num_splits)[None, :] * split, 0, split
    )  # (B, s)
    parts = chunk_partial(
        qg,
        ks,
        vs,
        scale,
        valid_len=valid[:, None, :, None, None],
    )  # o: (B, Hkv, s, g, d)
    parts = jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0), parts)
    out = finalize(merge_n(parts))
    return out.reshape(B, Hq, d).astype(q.dtype)


def lean_decode_jnp(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    sched: LeanSchedule,
    ctx_lens: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Execute a LeanSchedule in pure jnp (vectorized over all iterations).

    Faithful to the paper's two phases: (1) every worker computes un-scaled
    partials for its equal share of LeanTiles; (2) pieces are reduced per
    segment with the associative re-scaling operator. Here phase 1 is
    expressed as a single batched gather+einsum over all G*T iterations and
    phase 2 as segment ops — the *schedule* (who computes what, and which
    partials exist) is exactly the kernel's.
    """
    B, Hq, d = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    t = sched.tile_size

    it_seg = jnp.asarray(sched.iter_seg)
    it_tile = jnp.asarray(sched.iter_tile)
    it_piece = jnp.asarray(sched.iter_piece)
    it_len = jnp.asarray(sched.iter_len)
    it_valid = jnp.asarray(sched.iter_valid)
    seg_b = jnp.asarray(sched.seg_batch)
    seg_h = jnp.asarray(sched.seg_head)

    # clamp padding iters onto segment 0 / tile 0 (masked out afterwards)
    safe_seg = jnp.where(it_valid == 1, it_seg, 0)
    b_of = seg_b[safe_seg]
    h_of = seg_h[safe_seg]

    Smax = k.shape[2]
    start = it_tile * t
    pos = start[:, None] + jnp.arange(t)[None, :]           # (I, t)
    pos_c = jnp.minimum(pos, Smax - 1)
    k_tiles = k[b_of[:, None], h_of[:, None], pos_c]        # (I, t, d)
    v_tiles = v[b_of[:, None], h_of[:, None], pos_c]
    q_tiles = q.reshape(B, Hkv, g, d)[b_of, h_of]           # (I, g, d)

    tok_valid = (pos - start[:, None]) < it_len[:, None]    # (I, t)
    sf = jnp.einsum("igd,itd->igt", q_tiles.astype(jnp.float32),
                    k_tiles.astype(jnp.float32)) * scale
    sf = jnp.where(tok_valid[:, None, :], sf, NEG_INF)
    sf = jnp.where((it_valid == 1)[:, None, None], sf, NEG_INF)
    m = jnp.max(sf, axis=-1)                                # (I, g)
    a = jnp.where(sf > NEG_INF / 2, jnp.exp(sf - m[..., None]), 0.0)
    l = jnp.sum(a, axis=-1)
    o = jnp.einsum("igt,itd->igd", a, v_tiles.astype(jnp.float32))
    m = jnp.where((it_valid == 1)[:, None], m, -jnp.inf)

    # phase 2a: iterations -> pieces (what the kernel accumulates in VMEM)
    piece = segment_merge(AttnPartial(o=o, m=m, l=l), it_piece, sched.num_pieces)
    # phase 2b: pieces -> segments (the paper's reduction / fix-up phase)
    piece_seg = jnp.asarray(sched.piece_seg)
    seg = segment_merge(piece, piece_seg, sched.num_segments)
    out = finalize(seg)                                     # (S, g, d)
    return out.reshape(B, Hkv * g, d).astype(q.dtype)


def mha_prefill_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    q_chunk: int = 512,
    unroll: bool = False,
) -> jax.Array:
    """Exact prefill attention, scanned over q chunks (flash-style memory:
    O(q_chunk * Lk) scores live at once instead of O(Lq * Lk)).

    Used as the train-path attention when ``attn_q_chunk`` is set — one of
    the §Perf memory-term optimizations. ``unroll=True`` replaces the scan
    with a python loop (flop-count mode: XLA cost analysis counts while-loop
    bodies once, so the roofline measurement needs every iteration visible).
    """
    B, Hq, Lq, d = q.shape
    _, Hkv, Lk, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    pad = (-Lq) % q_chunk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q
    nq = (Lq + pad) // q_chunk
    qc = qp.reshape(B, Hkv, g, nq, q_chunk, d)
    qc = jnp.moveaxis(qc, 3, 0)                 # (nq, B, Hkv, g, qc, d)
    kpos = jnp.arange(Lk)

    def chunk(ci, qchunk):
        s = jnp.einsum(
            "bhgqd,bhkd->bhgqk", qchunk, k,
            preferred_element_type=jnp.float32,
        ) * scale
        qpos = ci * q_chunk + jnp.arange(q_chunk) + q_offset
        ok = jnp.ones((q_chunk, Lk), dtype=bool)
        if causal:
            ok &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )

    if unroll:
        out = jnp.stack([chunk(i, qc[i]) for i in range(nq)])
    else:
        out = jax.lax.map(lambda args: chunk(*args), (jnp.arange(nq), qc))
    out = jnp.moveaxis(out, 0, 3).reshape(B, Hq, Lq + pad, d)
    return out[:, :, :Lq].astype(q.dtype)


def mha_prefill_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
) -> jax.Array:
    """Oracle prefill attention. q: (B, Hq, Lq, d), k/v: (B, Hkv, Lk, d).

    ``window``: sliding-window size (local attention); None = global.
    ``q_offset``: absolute position of q[0] (for chunked prefill).
    """
    B, Hq, Lq, d = q.shape
    _, Hkv, Lk, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(B, Hkv, g, Lq, d)
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    qpos = jnp.arange(Lq) + q_offset
    kpos = jnp.arange(Lk)
    ok = jnp.ones((Lq, Lk), dtype=bool)
    if causal:
        ok &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        ok &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, Hq, Lq, d).astype(q.dtype)
