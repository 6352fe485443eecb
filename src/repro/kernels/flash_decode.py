"""FlashDecoding (fixed-split) decode kernel — the paper's baseline (§III-C).

Grid ``(S_seg, n_splits, tiles_per_split)``: each (segment, split) pair
accumulates online softmax over its *fixed-size* KV range and flushes one
partial ``(o, m, l)``; a separate merge reduces the splits. This reproduces
the baseline's weakness faithfully: the split count is uniform per segment,
so when ``S_seg * n_splits`` does not tile the hardware, waves are partially
full (quantization inefficiency) — exactly what LeanAttention's stream-K
schedule removes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_decode_kernel(
    lens_ref,     # (S_seg,) scalar prefetch: context length per segment
    q_ref,        # (1, gq, d)
    k_ref,        # (1, tile, d)
    v_ref,        # (1, tile, d)
    o_ref,        # (1, 1, gq, d) partial for (segment, split)
    m_ref,        # (1, 1, gq)
    l_ref,        # (1, 1, gq)
    acc_ref,
    m_acc_ref,
    l_acc_ref,
    *,
    scale: float,
    tile: int,
    tiles_per_split: int,
):
    seg = pl.program_id(0)
    split = pl.program_id(1)
    t = pl.program_id(2)
    ctx = lens_ref[seg]
    tile_idx = split * tiles_per_split + t
    start = tile_idx * tile
    vlen = jnp.clip(ctx - start, 0, tile)

    @pl.when(t == 0)
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_acc_ref[...] = jnp.full_like(m_acc_ref, NEG_INF)
        l_acc_ref[...] = jnp.zeros_like(l_acc_ref)

    @pl.when(vlen > 0)
    def _work():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < vlen, s, NEG_INF)
        m_prev = m_acc_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(pos < vlen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_acc_ref[...] = alpha * l_acc_ref[...] + jnp.sum(
            p, axis=1, keepdims=True
        )
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_acc_ref[...] = m_new

    @pl.when(t == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0, 0] = acc_ref[...]
        m_ref[0, 0] = m_acc_ref[..., 0]
        l_ref[0, 0] = l_acc_ref[..., 0]


def flash_decode_partials(
    q_seg: jax.Array,     # (S_seg, gq, d)
    k_seg: jax.Array,     # (S_seg, S_pad, d)
    v_seg: jax.Array,
    seg_lens: jax.Array,  # (S_seg,) int32
    num_splits: int,
    tile: int,
    scale: float,
    interpret: bool = False,
):
    """Returns per-(segment, split) partials o (S, splits, gq, d), m, l."""
    S_seg, gq, d = q_seg.shape
    S_pad = k_seg.shape[1]
    total_tiles = S_pad // tile
    tps = -(-total_tiles // num_splits)
    # pad KV so every split covers tps whole tiles
    need = tps * num_splits * tile
    if need > S_pad:
        pad = need - S_pad
        k_seg = jnp.pad(k_seg, ((0, 0), (0, pad), (0, 0)))
        v_seg = jnp.pad(v_seg, ((0, 0), (0, pad), (0, 0)))

    def kv_map(s, sp, t, lens):
        return (s, sp * tps + t, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S_seg, num_splits, tps),
        in_specs=[
            pl.BlockSpec((1, gq, d), lambda s, sp, t, lens: (s, 0, 0)),
            pl.BlockSpec((1, tile, d), kv_map),
            pl.BlockSpec((1, tile, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, gq, d), lambda s, sp, t, lens: (s, sp, 0, 0)),
            pl.BlockSpec((1, 1, gq), lambda s, sp, t, lens: (s, sp, 0)),
            pl.BlockSpec((1, 1, gq), lambda s, sp, t, lens: (s, sp, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((gq, d), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _flash_decode_kernel, scale=scale, tile=tile, tiles_per_split=tps
    )
    out_shapes = [
        jax.ShapeDtypeStruct((S_seg, num_splits, gq, d), jnp.float32),
        jax.ShapeDtypeStruct((S_seg, num_splits, gq), jnp.float32),
        jax.ShapeDtypeStruct((S_seg, num_splits, gq), jnp.float32),
    ]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="flash_decode_partials",
        interpret=interpret,
    )(seg_lens.astype(jnp.int32), q_seg, k_seg, v_seg)
