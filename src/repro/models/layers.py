"""Shared transformer building blocks (pure-function + param-dict style).

Everything is a plain pytree of jnp arrays + pure functions, so pjit /
shard_map / scan / remat compose without a framework dependency.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attention import (
    mha_chunk_prefill_paged_ref,
    mha_decode_ref,
    mha_prefill_ref,
    paged_scatter_tokens,
    paged_scatter_tokens_quant,
)


def dense_init(rng, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(rng, shape) * scale).astype(dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(x.dtype)


# ---------------------------------------------------------------- positions
def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0):
    """Rotary embedding. x: (..., L, H, hd) or (..., H, hd) with positions
    broadcastable to the L axis. Applied over the last dim in half-split
    convention."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., L, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast over heads axis (which sits between L and hd)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def sinusoidal_pos(positions: jax.Array, d_model: int) -> jax.Array:
    half = d_model // 2
    freqs = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------- attention
def attn_init(rng, d_model, n_heads, n_kv, head_dim, qk_norm=False,
              dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads * head_dim), dtype=dtype),
        "wk": dense_init(ks[1], (d_model, n_kv * head_dim), dtype=dtype),
        "wv": dense_init(ks[2], (d_model, n_kv * head_dim), dtype=dtype),
        "wo": dense_init(
            ks[3], (n_heads * head_dim, d_model), dtype=dtype
        ),
    }
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), dtype)
        p["k_norm"] = jnp.zeros((head_dim,), dtype)
    return p


def attn_forward(
    p,
    x: jax.Array,                     # (B, L, D)
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    causal: bool = True,
    window: Optional[int] = None,
    rope_theta: Optional[float] = 10000.0,
    q_offset=0,
    kv_states: Optional[jax.Array] = None,   # cross-attn: (B, Lk, D)
    compute_dtype=jnp.bfloat16,
):
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    B, L, D = x.shape
    xc = x.astype(compute_dtype)
    src = xc if kv_states is None else kv_states.astype(compute_dtype)
    q = (xc @ p["wq"].astype(compute_dtype)).reshape(B, L, n_heads, head_dim)
    k = (src @ p["wk"].astype(compute_dtype)).reshape(
        B, src.shape[1], n_kv, head_dim
    )
    v = (src @ p["wv"].astype(compute_dtype)).reshape(
        B, src.shape[1], n_kv, head_dim
    )
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope_theta is not None and kv_states is None:
        qpos = jnp.arange(L) + q_offset
        kpos = jnp.arange(src.shape[1])
        q = rope(q, qpos, rope_theta)
        k = rope(k, kpos, rope_theta)
    # (B, H, L, hd)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    o = mha_prefill_ref(
        qh, kh, vh,
        causal=causal and kv_states is None,
        window=window,
        q_offset=q_offset if kv_states is None else 0,
    )
    o = jnp.swapaxes(o, 1, 2).reshape(B, L, n_heads * head_dim)
    out = o.astype(compute_dtype) @ p["wo"].astype(compute_dtype)
    return out.astype(x.dtype), (kh, vh)


def attn_decode(
    p,
    x: jax.Array,                 # (B, 1, D) current token
    k_cache: jax.Array,           # (B, Hkv, S, hd)
    v_cache: jax.Array,
    cur_len,                      # scalar int32 — tokens already in cache
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: Optional[float] = 10000.0,
    window: Optional[int] = None,
    compute_dtype=jnp.bfloat16,
    attn_fn=None,                 # override: f(q, k, v, ctx_lens) -> out
    ctx_lens: Optional[jax.Array] = None,   # per-slot lengths (ragged)
):
    """One decode step against the KV cache. Returns (out, k_cache, v_cache).

    ``window``: ring-buffer cache of size W (positions stored mod W, RoPE
    applied at write time with absolute positions).
    ``attn_fn``: plugs in the lean/fixed-split kernels or the mesh-level
    sequence-parallel path; default is the jnp reference.
    ``ctx_lens``: per-batch-slot context lengths for ragged serving — RoPE
    positions, cache write offsets, and masks all go per-slot.
    """
    B, _, D = x.shape
    S = k_cache.shape[2]
    xc = x.astype(compute_dtype)
    q = (xc @ p["wq"].astype(compute_dtype)).reshape(B, 1, n_heads, head_dim)
    k = (xc @ p["wk"].astype(compute_dtype)).reshape(B, 1, n_kv, head_dim)
    v = (xc @ p["wv"].astype(compute_dtype)).reshape(B, 1, n_kv, head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope_theta is not None:
        if ctx_lens is not None:
            pos = ctx_lens[:, None]                  # (B, 1) per slot
        else:
            pos = jnp.full((1,), cur_len)
        q = rope(q, pos, rope_theta)
        k = rope(k, pos, rope_theta)
    if ctx_lens is not None:
        writes = ctx_lens % S if window is not None else jnp.minimum(
            ctx_lens, S - 1
        )
        upd = lambda cache, new: jax.vmap(
            lambda c, n, w: jax.lax.dynamic_update_slice(c, n, (0, w, 0))
        )(cache, jnp.swapaxes(new, 1, 2).astype(cache.dtype), writes)
        k_cache = upd(k_cache, k)
        v_cache = upd(v_cache, v)
        ctx = jnp.minimum(ctx_lens + 1, S).astype(jnp.int32)
    else:
        write_at = cur_len % S if window is not None else cur_len
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, jnp.swapaxes(k, 1, 2).astype(k_cache.dtype),
            (0, 0, write_at, 0),
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, jnp.swapaxes(v, 1, 2).astype(v_cache.dtype),
            (0, 0, write_at, 0),
        )
        ctx = jnp.full((B,), jnp.minimum(cur_len + 1, S), dtype=jnp.int32)
    qd = q.reshape(B, n_heads, head_dim)
    # fp8 caches: reads upcast in-register (fused on TPU: HBM moves 1B/elt)
    k_eff, v_eff = k_cache, v_cache
    if k_cache.dtype not in (jnp.bfloat16, jnp.float16, jnp.float32):
        k_eff = k_cache.astype(compute_dtype)
        v_eff = v_cache.astype(compute_dtype)
    if attn_fn is not None:
        o = attn_fn(qd, k_eff, v_eff, ctx)
    else:
        o = mha_decode_ref(qd, k_eff, v_eff, ctx_lens=ctx)
    o = o.reshape(B, 1, n_heads * head_dim).astype(compute_dtype)
    out = o @ p["wo"].astype(compute_dtype)
    return out.astype(x.dtype), k_cache, v_cache


def _pool_view(pools, layer):
    """One stage's stacked pools ``(L, P, ...)`` (K, V and, for int8, their
    scales; ``None`` entries pass through) seen as single pools of
    ``L * P`` pages, which is a free reshape, and layer ``layer``'s page
    base ``layer * P``: that layer's page ``p`` is page ``layer * P + p``
    of the view. Nothing is sliced out, so nothing is copied."""
    P = pools[0].shape[1]
    flat = tuple(
        None if a is None else a.reshape((-1,) + a.shape[2:]) for a in pools
    )
    return flat, layer * P


def _write_kv(pools, page_tbls, offs, lens, k, v, page_base, per_head):
    """Append ``k``/``v`` ``(N, C, Hkv, hd)`` through the page tables into
    the viewed pools, in place; int8 pools requantize through their scales
    (:func:`paged_scatter_tokens_quant`). The writes are KV pool I/O."""
    k_pool, v_pool, k_scale, v_scale = pools
    with jax.named_scope("kv_pool_io"):
        if k_scale is not None:
            k_pool, k_scale = paged_scatter_tokens_quant(
                k_pool, k_scale, page_tbls, offs, lens, k,
                per_head=per_head, page_base=page_base,
            )
            v_pool, v_scale = paged_scatter_tokens_quant(
                v_pool, v_scale, page_tbls, offs, lens, v,
                per_head=per_head, page_base=page_base,
            )
        else:
            k_pool = paged_scatter_tokens(
                k_pool, page_tbls, offs, lens, k, page_base=page_base
            )
            v_pool = paged_scatter_tokens(
                v_pool, page_tbls, offs, lens, v, page_base=page_base
            )
    return k_pool, v_pool, k_scale, v_scale


def _read_view(k_pool, v_pool, quant, page_base, layer_pages, compute_dtype):
    """The pools attention reads and the page base to read them at. bf16,
    f16, f32 and int8 pools are read where they lie; fp8 pools are widened
    to ``compute_dtype``, this layer's pages only, and read at base 0."""
    if quant or k_pool.dtype in (jnp.bfloat16, jnp.float16, jnp.float32):
        return k_pool, v_pool, page_base
    k_eff, v_eff = (
        jax.lax.dynamic_slice_in_dim(a, page_base, layer_pages)
        .astype(compute_dtype)
        for a in (k_pool, v_pool)
    )
    return k_eff, v_eff, 0


def _stacked(pools, like):
    """Undo :func:`_pool_view`: each pool back to its stacked shape."""
    return tuple(
        None if a is None else a.reshape(b.shape) for a, b in zip(pools, like)
    )


def attn_decode_paged(
    p,
    x: jax.Array,                 # (B, 1, D) current token
    k_pool: jax.Array,            # (L, num_pages, Hkv, page_size, hd)
    v_pool: jax.Array,
    page_tbl: jax.Array,          # (B, pages_per_slot) int32
    cur_len,                      # scalar int32 (kept for API symmetry)
    *,
    layer=0,                      # which of the L stacked pools is this one's
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: Optional[float] = 10000.0,
    compute_dtype=jnp.bfloat16,
    attn_fn=None,    # override: f(q, k_pool, v_pool, ctx, page_base) -> out
    ctx_lens: Optional[jax.Array] = None,   # (B,) per-slot lengths, required
    k_scale: Optional[jax.Array] = None,    # int8 pools: (L, num_pages, Hkv)
    v_scale: Optional[jax.Array] = None,
    scale_per_head: bool = True,
):
    """Paged twin of :func:`attn_decode` for global-attention layers.

    The KV cache is a global page pool shared by every slot; ``page_tbl``
    maps each slot's logical tiles to physical pages. ``k_pool``/``v_pool``
    hold the pools of a stage's ``L`` layers stacked, and this layer's are
    ``k_pool[layer]``; they are read and written where they lie, never
    sliced out (:func:`_pool_view`): every page id is offset by the
    layer's page base. The new token's K/V go row-wise into page
    ``page_tbl[b, ctx_b // page_size]`` at offset ``ctx_b % page_size``
    (:func:`repro.core.attention.paged_write_rows`) — idle slots (``ctx ==
    0`` with an all-null table row) write the layer's reserved null page,
    whose contents are always masked. ``attn_fn`` receives the viewed
    *pools*, the visible lengths and the page base (the paged lean kernel
    consumes them natively; ref/fixed backends gather first).
    Returns (out, k_pool, v_pool), the pools in their stacked shape.

    ``k_scale``/``v_scale`` flip the pools to quantized int8 storage: the
    token write goes through :func:`paged_scatter_tokens_quant` (scales
    grow monotonically, touched pages requantize), the int8 pools pass to
    ``attn_fn`` *undequantized* together with ``k_scales=``/``v_scales=``
    keywords (the lean kernels dequantize per tile in VMEM), and the ref
    fallback gathers through :func:`paged_gather_kv_dequant`. Returns the
    5-tuple (out, k_pool, v_pool, k_scale, v_scale).
    """
    if ctx_lens is None:
        raise ValueError("paged decode requires per-slot ctx_lens")
    quant = k_scale is not None
    B, _, D = x.shape
    layer_pages, ps = k_pool.shape[1], k_pool.shape[3]
    capacity = page_tbl.shape[1] * ps
    xc = x.astype(compute_dtype)
    q = (xc @ p["wq"].astype(compute_dtype)).reshape(B, 1, n_heads, head_dim)
    k = (xc @ p["wk"].astype(compute_dtype)).reshape(B, 1, n_kv, head_dim)
    v = (xc @ p["wv"].astype(compute_dtype)).reshape(B, 1, n_kv, head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope_theta is not None:
        pos = ctx_lens[:, None]                      # (B, 1) per slot
        q = rope(q, pos, rope_theta)
        k = rope(k, pos, rope_theta)
    # the token goes into its slot's current page
    stacked = (k_pool, v_pool, k_scale, v_scale)
    pools, base = _pool_view(stacked, layer)
    write_pos = jnp.minimum(ctx_lens, capacity - 1)
    pools = _write_kv(
        pools, page_tbl, write_pos, jnp.ones((B,), jnp.int32), k, v, base,
        scale_per_head,
    )
    kp, vp, ks, vs = pools
    ctx = jnp.minimum(ctx_lens + 1, capacity).astype(jnp.int32)
    qd = q.reshape(B, n_heads, head_dim)
    k_eff, v_eff, rbase = _read_view(
        kp, vp, quant, base, layer_pages, compute_dtype
    )
    if attn_fn is not None:
        if quant:
            o = attn_fn(qd, k_eff, v_eff, ctx, rbase, k_scales=ks, v_scales=vs)
        else:
            o = attn_fn(qd, k_eff, v_eff, ctx, rbase)
    else:
        from repro.core.attention import (
            mha_decode_ref, paged_gather_kv, paged_gather_kv_dequant,
        )

        tbl = page_tbl + rbase
        if quant:
            kd = paged_gather_kv_dequant(k_eff, ks, tbl, dtype=compute_dtype)
            vd = paged_gather_kv_dequant(v_eff, vs, tbl, dtype=compute_dtype)
        else:
            kd = paged_gather_kv(k_eff, tbl)
            vd = paged_gather_kv(v_eff, tbl)
        o = mha_decode_ref(qd, kd, vd, ctx_lens=ctx)
    o = o.reshape(B, 1, n_heads * head_dim).astype(compute_dtype)
    out = o @ p["wo"].astype(compute_dtype)
    kp, vp, ks, vs = _stacked(pools, stacked)
    if quant:
        return out.astype(x.dtype), kp, vp, ks, vs
    return out.astype(x.dtype), kp, vp


def attn_prefill_chunk_paged(
    p,
    x: jax.Array,                 # (N, C, D) one prompt chunk per row
    k_pool: jax.Array,            # (L, num_pages, Hkv, page_size, hd)
    v_pool: jax.Array,
    page_tbls: jax.Array,         # (N, W) int32 page table rows
    offs: jax.Array,              # (N,) int32 absolute position of chunk[0]
    lens: jax.Array,              # (N,) int32 valid tokens per chunk
    *,
    layer=0,                      # which of the L stacked pools is this one's
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: Optional[float] = 10000.0,
    compute_dtype=jnp.bfloat16,
    attn_fn=None,  # override: f(q, k_pool, v_pool, tbls, offs, base) -> o
    k_scale: Optional[jax.Array] = None,    # int8 pools: (L, num_pages, Hkv)
    v_scale: Optional[jax.Array] = None,
    scale_per_head: bool = True,
):
    """Chunked-prefill attention for global-attention layers (paged KV).

    The prefill sibling of :func:`attn_decode_paged`, with the same stacked
    pools and page base: each batch row is one prompt *chunk* of an
    in-flight request — ``C`` positions starting at absolute offset
    ``offs[n]``, of which ``lens[n]`` are valid. The chunk's K/V append
    **directly into the page pool** through the row's page table (no dense
    staging cache), then queries attend causally over the row's visible
    prefix ``[0, offs[n] + lens[n])`` read back through the same table.
    RoPE uses absolute positions, so chunked and whole-prompt prefill
    produce the same cache contents.

    Chunk-padding positions (``i >= lens[n]``) write the null page and
    produce garbage activations confined to their own rows; callers gather
    logits only at valid positions. Returns ``(out, k_pool, v_pool)`` —
    or, with ``k_scale``/``v_scale`` (quantized int8 pools, same contract
    as :func:`attn_decode_paged`), the 5-tuple including updated scales.
    """
    quant = k_scale is not None
    N, C, D = x.shape
    xc = x.astype(compute_dtype)
    q = (xc @ p["wq"].astype(compute_dtype)).reshape(N, C, n_heads, head_dim)
    k = (xc @ p["wk"].astype(compute_dtype)).reshape(N, C, n_kv, head_dim)
    v = (xc @ p["wv"].astype(compute_dtype)).reshape(N, C, n_kv, head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope_theta is not None:
        pos = offs[:, None] + jnp.arange(C)[None, :]       # (N, C) per row
        q = rope(q, pos, rope_theta)
        k = rope(k, pos, rope_theta)
    # append the chunk's KV to the pool FIRST — queries attend their own
    # chunk (causally), so the read below must see these writes
    stacked = (k_pool, v_pool, k_scale, v_scale)
    pools, base = _pool_view(stacked, layer)
    pools = _write_kv(
        pools, page_tbls, offs, lens, k, v, base, scale_per_head
    )
    kp, vp, ks, vs = pools
    qh = jnp.swapaxes(q, 1, 2)                             # (N, Hq, C, hd)
    k_eff, v_eff, rbase = _read_view(
        kp, vp, quant, base, k_pool.shape[1], compute_dtype
    )
    if attn_fn is not None:
        if quant:
            o = attn_fn(
                qh, k_eff, v_eff, page_tbls, offs, rbase,
                k_scales=ks, v_scales=vs,
            )
        else:
            o = attn_fn(qh, k_eff, v_eff, page_tbls, offs, rbase)
    else:
        if quant:
            # reference path: dequantize the whole pool densely (tests /
            # fallback only — the kernel path never materializes this)
            k_eff = (
                kp.astype(jnp.float32) * ks[:, :, None, None]
            ).astype(compute_dtype)
            v_eff = (
                vp.astype(jnp.float32) * vs[:, :, None, None]
            ).astype(compute_dtype)
        o = mha_chunk_prefill_paged_ref(
            qh, k_eff, v_eff, page_tbls + rbase, offs
        )
    o = jnp.swapaxes(o, 1, 2).reshape(N, C, n_heads * head_dim)
    out = o.astype(compute_dtype) @ p["wo"].astype(compute_dtype)
    kp, vp, ks, vs = _stacked(pools, stacked)
    if quant:
        return out.astype(x.dtype), kp, vp, ks, vs
    return out.astype(x.dtype), kp, vp


# ---------------------------------------------------------------- FFN
def ffn_init(rng, d_model, d_ff, kind="swiglu", dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    if kind == "swiglu":
        return {
            "wg": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
            "wu": dense_init(ks[1], (d_model, d_ff), dtype=dtype),
            "wd": dense_init(ks[2], (d_ff, d_model), dtype=dtype),
        }
    return {  # gelu / squared_relu
        "wu": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
        "wd": dense_init(ks[1], (d_ff, d_model), dtype=dtype),
    }


def ffn_forward(p, x, kind="swiglu", compute_dtype=jnp.bfloat16):
    xc = x.astype(compute_dtype)
    if kind == "swiglu":
        h = jax.nn.silu(xc @ p["wg"].astype(compute_dtype)) * (
            xc @ p["wu"].astype(compute_dtype)
        )
    elif kind == "gelu":
        h = jax.nn.gelu(xc @ p["wu"].astype(compute_dtype))
    elif kind == "squared_relu":  # nemotron-4
        h = jnp.square(jax.nn.relu(xc @ p["wu"].astype(compute_dtype)))
    else:
        raise ValueError(kind)
    return (h @ p["wd"].astype(compute_dtype)).astype(x.dtype)
