"""Stream-K *chunked prefill* kernel — LeanAttention for the ragged chunk
grid of the continuous-batching scheduler.

One pack = N concurrent prompt chunks (one per in-flight request), each at a
different depth of a different prompt, all reading and appending KV through
the paged pool. The workload per segment ``(chunk, kv_head)`` is a decode
workload with a taller query block: ``g * chunk_capacity`` rows instead of
``g``. The schedule is therefore a plain :func:`repro.core.leantile
.make_schedule` over the chunks' *visible* KV lengths (``off + chunk_len``),
linearized and load-balanced exactly like decode (paper §IV-C's ragged-batch
property) — chunk packs share the decode :class:`ScheduleCache` lattice.

What differs from :mod:`repro.kernels.lean_decode` is only the tile update:
prefill queries are causal *within* the chunk, so each q row ``r`` (chunk
position ``r % chunk_capacity``) masks key positions greater than its own
absolute position ``qstart[seg] + r % chunk_capacity``. ``qstart`` rides as
an extra scalar-prefetch operand — a *runtime* array, so schedules (and the
jit traces keyed on them) stay offset-independent and keep hitting as
requests advance through their prompts.

Execution is two-phase (partials -> merge); the merge phase is byte-for-byte
the decode one (:func:`repro.core.merge.segment_merge` /
``lean_merge_pallas``) since partials carry the same ``(o, m, l)`` triple,
just with more rows. K/V fetch through the page table uses the same flat
pool-row routing operand as the paged decode kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.leantile import LeanSchedule
from .lean_decode import (
    DESC_FIRST,
    DESC_LAST,
    DESC_SEG,
    DESC_TILE,
    DESC_PIECE,
    DESC_VALID,
    NEG_INF,
    OP_PARTIAL,
    pack_descriptors,
    scale_operands,
)


def _lean_prefill_kernel(
    desc_ref,      # (7, I) scalar-prefetch descriptors
    ctx_ref,       # (S,) runtime visible KV length per segment
    qstart_ref,    # (S,) runtime absolute position of each chunk's q[0]
    route_ref,     # (I,) flattened pool row per iteration (page * Hkv + head)
    q_ref,         # (1, gq, d)    gq = g * chunk_cap query rows
    k_ref,         # (1, tile, d)  current LeanTile fetched via route
    v_ref,         # (1, tile, d)
    *refs,         # [ks_ref (1,1,1), vs_ref (1,1,1)] when quantized, then:
                   # o_ref (1, gq, d)  partial un-scaled output (piece slot)
                   # m_ref (1, gq, 1)
                   # l_ref (1, gq, 1)
                   # acc_ref   VMEM (gq, d) f32
                   # m_acc_ref VMEM (gq, 1) f32
                   # l_acc_ref VMEM (gq, 1) f32
    scale: float,
    tile_size: int,
    tiles_per_worker: int,
    chunk_cap: int,
    quantized: bool = False,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, m_acc_ref, l_acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref, m_acc_ref, l_acc_ref = refs
        ks_ref = vs_ref = None
    g = pl.program_id(0)
    t = pl.program_id(1)
    i = g * tiles_per_worker + t

    first = desc_ref[DESC_FIRST, i]
    last = desc_ref[DESC_LAST, i]
    valid = desc_ref[DESC_VALID, i]

    @pl.when(valid == OP_PARTIAL)
    def _work():
        @pl.when(first == 1)
        def _reset():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_acc_ref[...] = jnp.full_like(m_acc_ref, NEG_INF)
            l_acc_ref[...] = jnp.zeros_like(l_acc_ref)

        seg = desc_ref[DESC_SEG, i]
        kv_start = desc_ref[DESC_TILE, i] * tile_size
        # runtime length mask (bucketed schedules stay exact) ...
        vlen = jnp.clip(ctx_ref[seg] - kv_start, 0, tile_size)

        q = q_ref[0].astype(jnp.float32)                   # (gq, d)
        k = k_ref[0].astype(jnp.float32)                   # (tile, d)
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0]                              # int8 tile dequant
        if vs_ref is not None:
            v = v * vs_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (gq, tile)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        # ... plus the chunk-causal mask: q row r sits at absolute position
        # qstart + (r % chunk_cap); rows are (g, chunk) flattened chunk-minor
        qpos = qstart_ref[seg] + row % chunk_cap
        ok = (col < vlen) & (kv_start + col <= qpos)
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

        @pl.when(last == 1)
        def _flush():
            o_ref[0] = acc_ref[...]
            m_ref[0] = m_acc_ref[...]
            l_ref[0] = l_acc_ref[...]


def lean_prefill_chunk_partials(
    q_seg: jax.Array,          # (S_seg, g * chunk_cap, d)
    k_rows: jax.Array,         # (num_pages * H_kv, page_size, d) pool rows
    v_rows: jax.Array,
    seg_ctx: jax.Array,        # (S_seg,) int32 visible KV length (off + len)
    seg_qstart: jax.Array,     # (S_seg,) int32 chunk start offset
    route: jax.Array,          # (G*T,) int32 pool row per iteration
    sched: LeanSchedule,
    scale: float,
    chunk_cap: int,
    interpret: bool = False,
    k_scales: jax.Array | None = None,   # quant: (rows, 1) f32 per-row scales
    v_scales: jax.Array | None = None,
):
    """Phase 1 of the stream-K chunk pack: per-piece partials.

    Returns ``(o, m, l)`` with leading dim ``num_pieces``, f32 — the decode
    merge phase consumes them unchanged. Every q row has at least key
    position 0 visible (visible lengths are >= 1 and ``qstart >= 0``), so
    no piece-set of a segment is ever fully masked and the final divide is
    safe without an epsilon.

    ``k_scales``/``v_scales`` enable int8 pool rows: each routed tile is
    dequantized in-kernel with its per-(page, head) f32 scale before the
    fp32 online softmax, so partials merge identically to the fp path.
    """
    S_seg, gq, d = q_seg.shape
    tile = sched.tile_size
    G, T = sched.num_workers, sched.tiles_per_worker
    P = sched.num_pieces
    desc = jnp.asarray(pack_descriptors(sched))
    quant = k_scales is not None

    def q_map(g, t, desc, *_):
        i = g * T + t
        return (
            jnp.where(desc[DESC_VALID, i] == OP_PARTIAL, desc[DESC_SEG, i], 0),
            0,
            0,
        )

    def kv_map(g, t, desc, ctx, qstart, route):
        return (route[g * T + t], 0, 0)

    def out_map(g, t, desc, *_):
        return (desc[DESC_PIECE, g * T + t], 0, 0)

    in_specs = [
        pl.BlockSpec((1, gq, d), q_map),
        pl.BlockSpec((1, tile, d), kv_map),
        pl.BlockSpec((1, tile, d), kv_map),
    ]
    scale_specs, scale_inputs = scale_operands(k_scales, v_scales, kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(G, T),
        in_specs=in_specs + scale_specs,
        out_specs=[
            pl.BlockSpec((1, gq, d), out_map),
            pl.BlockSpec((1, gq, 1), out_map),
            pl.BlockSpec((1, gq, 1), out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((gq, d), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _lean_prefill_kernel,
        scale=scale, tile_size=tile, tiles_per_worker=T, chunk_cap=chunk_cap,
        quantized=quant,
    )
    out_shapes = [
        jax.ShapeDtypeStruct((P + 1, gq, d), jnp.float32),
        jax.ShapeDtypeStruct((P + 1, gq, 1), jnp.float32),
        jax.ShapeDtypeStruct((P + 1, gq, 1), jnp.float32),
    ]
    o_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="lean_prefill_chunk",
        interpret=interpret,
    )(
        desc,
        seg_ctx.astype(jnp.int32),
        seg_qstart.astype(jnp.int32),
        route.astype(jnp.int32),
        q_seg, k_rows, v_rows, *scale_inputs,
    )
    return o_p[:P], m_p[:P, :, 0], l_p[:P, :, 0]
