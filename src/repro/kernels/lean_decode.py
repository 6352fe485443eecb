"""LeanAttention decode kernel — Pallas/TPU stream-K implementation.

TPU adaptation of paper Algorithms 1+2. The grid is ``(G, T)``:

  * axis 0 (``G`` workers) is declared *parallel* — on hardware these are the
    units the Megacore/multi-chip runtime may distribute; every worker gets
    exactly ``T = ceil(total_tiles / G)`` LeanTile iterations (the stream-K
    equalized load, paper Eq. 2);
  * axis 1 (``T`` iterations per worker) is *arbitrary* (sequential): the
    online-softmax accumulation of Algorithm 1 runs in VMEM scratch across
    these steps, crossing (batch, head) segment boundaries as the schedule
    dictates.

Scalar-prefetch descriptors (built host-side by
:func:`repro.core.leantile.make_schedule`) drive the K/V BlockSpec index maps
— this is how a worker's iteration stream walks arbitrary tiles of arbitrary
segments with zero dynamic control flow on the data path.

Where the CUDA version uses a spin-lock "host block" fix-up inside one kernel
(GPU CTAs are co-resident; TPU grid steps are not), two execution modes are
offered:

  * **two-phase** (``lean_decode_partials`` + merge): each piece's un-scaled
    partial ``(o, m, l)`` goes to HBM and a second, cheap phase reduces per
    segment — XLA segment ops or the Pallas ``lean_merge`` kernel. The G
    axis stays ``parallel`` (Megacore/multi-core splittable).
  * **fused** (``lean_decode_fused``): ONE ``pallas_call`` whose flat
    schedule appends the ``P`` merge iterations after the ``G*T`` partial
    iterations (descriptor-driven, same scalar-prefetch machinery), ``n``
    consecutive iterations per grid step. Partials live in a
    VMEM scratch ring — they never round-trip HBM, single-piece segments
    reduce in-register, and there is no second kernel launch. The grid is
    fully ``arbitrary`` (sequential per core), which is the right trade for
    the decode fast-path where the whole output is a few hundred KiB.

Both modes mask with *runtime* per-segment context lengths (a second
scalar-prefetch operand), so a schedule built over bucketed lengths
(:class:`repro.core.leantile.ScheduleCache`) computes exact attention for
the true ragged lengths — trailing over-bucketed tiles contribute identity
partials.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.leantile import LeanSchedule

NEG_INF = -1e30

# descriptor row layout in the packed (7, G*T) scalar-prefetch array.
# DESC_LEN carries the SCHEDULE's tile lengths (bucketed when the schedule
# came from a ScheduleCache) — kernels mask with the runtime ctx operand
# instead and never read this row; it stays packed for layout stability and
# host-side debugging only.
DESC_SEG, DESC_TILE, DESC_PIECE, DESC_FIRST, DESC_LAST, DESC_LEN, DESC_VALID = range(7)

# DESC_VALID doubles as the opcode row: 0 = padding, 1 = partial LeanTile
# iteration, 2 = merge iteration (fused kernel only).
OP_PAD, OP_PARTIAL, OP_MERGE = 0, 1, 2


def pack_descriptors(sched: LeanSchedule) -> np.ndarray:
    """Packed (7, G*T) int32 descriptors (memoized on the schedule)."""
    return sched.packed_descriptors()


def scale_operands(k_scales, v_scales, index_map):
    """BlockSpecs and operands of the int8 dequant sidecar, or ``([], ())``
    for an fp pool. The ``(rows, 1)`` per-row scales ride as ``(rows, 1, 1)``
    so each routed ``(1, 1, 1)`` block spans the array's last two dims —
    the only block a single scalar may be on the TPU."""
    if k_scales is None:
        return [], ()
    spec = pl.BlockSpec((1, 1, 1), index_map)
    return [spec, spec], tuple(
        s.astype(jnp.float32).reshape(-1, 1, 1) for s in (k_scales, v_scales)
    )


def _online_softmax_tile(
    q, k, v, acc_ref, m_acc_ref, l_acc_ref, vlen, scale,
    k_scale=None, v_scale=None,
):
    """One LeanTile online-softmax update (Algorithm 1 lines 20-25) of the
    query group ``q`` (gq, d) against the tile ``k``, ``v`` (tile, d), as
    loaded, and the VMEM accumulators; ``vlen`` masks the tile's invalid
    tail (and the whole tile when the runtime length ends before it).

    ``k_scale``/``v_scale`` are optional f32 dequant scalars for quantized
    (int8) KV tiles: the tile is widened to f32 and multiplied *before*
    entering the dot products, so the online-softmax accumulation — and
    therefore the merge numerics — is identical to the fp path. A scale of
    0 dequantizes to exact zeros (empty or scrubbed pages)."""
    q = q.astype(jnp.float32)                              # (gq, d)
    k = k.astype(jnp.float32)                              # (tile, d)
    v = v.astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale
    if v_scale is not None:
        v = v * v_scale

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                              # (gq, tile)
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < vlen, s, NEG_INF)

    m_prev = m_acc_ref[...]                                # (gq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(pos < vlen, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_acc_ref[...] = alpha * l_acc_ref[...] + jnp.sum(
        p, axis=1, keepdims=True
    )
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_acc_ref[...] = m_new


def _lean_decode_kernel(
    desc_ref,      # (7, I) scalar-prefetch descriptors
    ctx_ref,       # (S,) scalar-prefetch runtime segment lengths
    q_ref,         # (1, gq, d)     current segment's query group
    k_ref,         # (1, tile, d)   current LeanTile of K
    v_ref,         # (1, tile, d)   current LeanTile of V
    *refs,         # [ks_ref (1,1,1), vs_ref (1,1,1)] when quantized, then:
                   # o_ref (1, gq, d)  partial un-scaled output (piece slot)
                   # m_ref (1, gq, 1)  partial row-max
                   # l_ref (1, gq, 1)  partial exp-sum
                   # acc_ref   VMEM (gq, d) f32
                   # m_acc_ref VMEM (gq, 1) f32
                   # l_acc_ref VMEM (gq, 1) f32
    scale: float,
    tile_size: int,
    tiles_per_worker: int,
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
        def _reset():  # Algorithm 1 lines 8-9
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_acc_ref[...] = jnp.full_like(m_acc_ref, NEG_INF)
            l_acc_ref[...] = jnp.zeros_like(l_acc_ref)

        # runtime ragged length — the schedule may cover bucketed (longer)
        # lengths; tiles past the true length mask to identity
        vlen = jnp.clip(
            ctx_ref[desc_ref[DESC_SEG, i]]
            - desc_ref[DESC_TILE, i] * tile_size,
            0,
            tile_size,
        )
        _online_softmax_tile(
            q_ref[0], k_ref[0], v_ref[0], acc_ref, m_acc_ref, l_acc_ref,
            vlen, scale,
            k_scale=None if ks_ref is None else ks_ref[0],
            v_scale=None if vs_ref is None else vs_ref[0],
        )

        @pl.when(last == 1)
        def _flush():  # StorePartials (Algorithm 2 lines 20-22)
            o_ref[0] = acc_ref[...]
            m_ref[0] = m_acc_ref[...]
            l_ref[0] = l_acc_ref[...]


def lean_decode_partials(
    q_seg: jax.Array,          # (S_seg, gq, d)
    k_seg: jax.Array,          # dense: (S_seg, S_pad, d), S_pad % tile == 0
    v_seg: jax.Array,          #   paged: (num_pages * H_kv, page_size, d)
    seg_ctx: jax.Array,        # (S_seg,) int32 runtime context lengths
    sched: LeanSchedule,
    scale: float,
    interpret: bool = False,
    route: Optional[jax.Array] = None,   # paged: (G*T,) int32 pool rows
    k_scales: Optional[jax.Array] = None,  # quant: (rows, 1) f32 per-row scales
    v_scales: Optional[jax.Array] = None,
):
    """Phase 1: run the stream-K grid, return per-piece partials.

    Returns (o, m, l) with leading dim ``num_pieces`` (garbage row sliced
    off), f32. ``seg_ctx`` carries the true per-segment lengths; the
    schedule's (possibly bucketed) lengths only shape the tile walk.

    ``route`` switches K/V fetching to the paged layout: tiles come from
    flattened pool rows addressed by the routing operand instead of
    contiguous (segment, tile) slices. The kernel body — and therefore the
    fp op sequence — is identical either way.

    ``k_scales``/``v_scales`` (paged only) enable quantized KV: the pool
    rows hold int8 and each tile is dequantized in-kernel with its routed
    per-(page, head) f32 scale before the fp32 online softmax.
    """
    S_seg, gq, d = q_seg.shape
    tile = sched.tile_size
    G, T = sched.num_workers, sched.tiles_per_worker
    P = sched.num_pieces
    desc = jnp.asarray(pack_descriptors(sched))
    paged = route is not None
    quant = k_scales is not None
    if quant and not paged:
        raise ValueError("quantized KV requires the paged (route) layout")

    # index maps take (*grid, *prefetch_refs); trailing *_ absorbs the
    # extra routing operand in paged mode
    def q_map(g, t, desc, *_):
        i = g * T + t
        # padded iters clamp to segment 0 (they do no work)
        return (
            jnp.where(desc[DESC_VALID, i] == OP_PARTIAL, desc[DESC_SEG, i], 0),
            0,
            0,
        )

    def kv_map_dense(g, t, desc, *_):
        i = g * T + t
        ok = desc[DESC_VALID, i] == OP_PARTIAL
        return (
            jnp.where(ok, desc[DESC_SEG, i], 0),
            jnp.where(ok, desc[DESC_TILE, i], 0),
            0,
        )

    def kv_map_paged(g, t, desc, ctx, route):
        return (route[g * T + t], 0, 0)

    kv_map = kv_map_paged if paged else kv_map_dense

    def scale_map(g, t, desc, ctx, route):
        return (route[g * T + t], 0, 0)

    def out_map(g, t, desc, *_):
        return (desc[DESC_PIECE, g * T + t], 0, 0)

    in_specs = [
        pl.BlockSpec((1, gq, d), q_map),
        pl.BlockSpec((1, tile, d), kv_map),
        pl.BlockSpec((1, tile, d), kv_map),
    ]
    scale_specs, scale_inputs = scale_operands(k_scales, v_scales, scale_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if paged else 2,
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
        _paged_partial_kernel if paged else _lean_decode_kernel,
        scale=scale, tile_size=tile, tiles_per_worker=T, quantized=quant,
    )
    out_shapes = [
        jax.ShapeDtypeStruct((P + 1, gq, d), jnp.float32),
        jax.ShapeDtypeStruct((P + 1, gq, 1), jnp.float32),
        jax.ShapeDtypeStruct((P + 1, gq, 1), jnp.float32),
    ]
    operands = (desc, seg_ctx.astype(jnp.int32))
    if paged:
        operands += (route.astype(jnp.int32),)
    o_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="lean_decode_partials",
        interpret=interpret,
    )(*operands, q_seg, k_seg, v_seg, *scale_inputs)
    return o_p[:P], m_p[:P, :, 0], l_p[:P, :, 0]


# Bytes of K and V one grid step of the fused kernel moves: a step runs
# ``n = STEP_KV_BYTES // (2 * tile * d * kv_elem_bytes)`` LeanTile
# iterations (2 for bf16 128x128 pages, 4 for int8). On a v5e the kernel
# took least time per page at 2 bf16 pages a step, 8-9% less than at 1;
# from 3 on, each page costs more again while the unrolled body and its
# compile time grow with n, hence the cap (PERF.md, section 6).
STEP_KV_BYTES = 128 * 2**10
MAX_ITERS_PER_STEP = 4


def fused_iters_per_step(tile: int, d: int, kv_elem_bytes: int) -> int:
    """LeanTile iterations per grid step of the fused kernel, from the
    bytes of one iteration's K and V tiles (:data:`STEP_KV_BYTES`),
    clamped to ``[1, MAX_ITERS_PER_STEP]``."""
    n = STEP_KV_BYTES // (2 * tile * d * kv_elem_bytes)
    return max(1, min(n, MAX_ITERS_PER_STEP))


def fused_grid(sched: LeanSchedule, d: int, kv_elem_bytes: int):
    """``(grid_steps, iters_per_step)`` of the fused kernel's call for
    ``sched``: ``ceil((G*T + P) / n)`` steps of ``n`` iterations."""
    n = fused_iters_per_step(sched.tile_size, d, kv_elem_bytes)
    return -(-(sched.grid_iters + sched.num_pieces) // n), n


def _fused_iteration(
    i, desc_ref, ctx_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
    lse_ref, acc_ref, m_acc_ref, l_acc_ref, po_ref, pm_ref, pl_ref,
    scale, tile_size,
):
    """Schedule iteration ``i`` of the fused kernel: a LeanTile update of
    the accumulators (OP_PARTIAL), a piece merged into its segment
    (OP_MERGE), or nothing (OP_PAD)."""
    op = desc_ref[DESC_VALID, i]
    seg = desc_ref[DESC_SEG, i]
    piece = desc_ref[DESC_PIECE, i]
    first = desc_ref[DESC_FIRST, i]
    last = desc_ref[DESC_LAST, i]

    @pl.when(op == OP_PARTIAL)
    def _partial():
        @pl.when(first == 1)
        def _reset():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_acc_ref[...] = jnp.full_like(m_acc_ref, NEG_INF)
            l_acc_ref[...] = jnp.zeros_like(l_acc_ref)

        vlen = jnp.clip(
            ctx_ref[seg] - desc_ref[DESC_TILE, i] * tile_size, 0, tile_size
        )
        _online_softmax_tile(
            q_ref[seg], k_ref[0], v_ref[0], acc_ref, m_acc_ref, l_acc_ref,
            vlen, scale,
            k_scale=None if ks_ref is None else ks_ref[0],
            v_scale=None if vs_ref is None else vs_ref[0],
        )

        @pl.when(last == 1)
        def _flush():  # StorePartials — into VMEM scratch, not HBM
            po_ref[pl.ds(piece, 1)] = acc_ref[...][None]
            pm_ref[pl.ds(piece, 1)] = m_acc_ref[...][None]
            pl_ref[pl.ds(piece, 1)] = l_acc_ref[...][None]

    @pl.when(op == OP_MERGE)
    def _merge():  # Algorithm 2 reduction, re-using the same accumulators
        @pl.when(first == 1)
        def _reset():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_acc_ref[...] = jnp.full_like(m_acc_ref, NEG_INF)
            l_acc_ref[...] = jnp.zeros_like(l_acc_ref)

        m_piece = pm_ref[pl.ds(piece, 1)][0]                # (gq, 1)
        l_piece = pl_ref[pl.ds(piece, 1)][0]
        o_piece = po_ref[pl.ds(piece, 1)][0]                # (gq, d)
        m_new = jnp.maximum(m_acc_ref[...], m_piece)
        a_old = jnp.exp(m_acc_ref[...] - m_new)
        a_new = jnp.exp(m_piece - m_new)
        l_acc_ref[...] = a_old * l_acc_ref[...] + a_new * l_piece
        acc_ref[...] = a_old * acc_ref[...] + a_new * o_piece
        m_acc_ref[...] = m_new

        @pl.when(last == 1)
        def _final():
            o_ref[pl.ds(seg, 1)] = (acc_ref[...] / l_acc_ref[...])[None]
            lse_ref[pl.ds(seg, 1)] = (
                m_acc_ref[...] + jnp.log(l_acc_ref[...])
            )[None]


def _lean_decode_fused_kernel(
    desc_ref,      # (7, steps * n) scalar-prefetch descriptors (+merge rows)
    ctx_ref,       # (S,) scalar-prefetch runtime segment lengths
    q_ref,         # (S, gq, d)  every segment's query group, VMEM-resident
    *refs,         # n (K, V) tile pairs, each (1, tile, d); n (K, V) scale
                   # pairs (1, 1, 1) when quantized; then:
                   # o_ref (S, gq, d)  final outputs — VMEM-resident
                   # lse_ref (S, gq, 1) final logsumexp
                   # acc_ref   VMEM (gq, d) f32  shared partial/merge acc
                   # m_acc_ref VMEM (gq, 1) f32
                   # l_acc_ref VMEM (gq, 1) f32
                   # po_ref VMEM (P+1, gq, d) f32  piece partials (VMEM only)
                   # pm_ref VMEM (P+1, gq, 1) f32
                   # pl_ref VMEM (P+1, gq, 1) f32
    scale: float,
    tile_size: int,
    iters_per_step: int,
    quantized: bool = False,
):
    n = iters_per_step
    tiles, refs = refs[: 2 * n], refs[2 * n:]
    if quantized:
        scales, refs = refs[: 2 * n], refs[2 * n:]
    else:
        scales = (None,) * (2 * n)
    for j in range(n):  # iterations s*n .. s*n + n - 1, in schedule order
        _fused_iteration(
            pl.program_id(0) * n + j, desc_ref, ctx_ref, q_ref,
            *tiles[2 * j: 2 * j + 2], *scales[2 * j: 2 * j + 2], *refs,
            scale=scale, tile_size=tile_size,
        )


def fused_vmem_bytes(
    sched: LeanSchedule, gq: int, d: int, kv_elem_bytes: int = 4
) -> int:
    """Rough VMEM footprint of the fused kernel's resident state: f32 piece
    partials + whole-output block + the resident q (at f32 width, its
    widest) + a grid step's n K and n V tiles, double-buffered, at the
    *cache dtype* width (``kv_elem_bytes``: 4 f32, 2 bf16, 1 int8/fp8 — a
    hardcoded 4 here over-triggered the two-phase fallback for narrow KV).
    Used to gate the fused path (fall back to two-phase when a schedule
    would blow the budget)."""
    P, S = sched.num_pieces, sched.num_segments
    n = fused_iters_per_step(sched.tile_size, d, kv_elem_bytes)
    per_row = gq * (d + 2)
    return (
        4 * ((P + 1) * per_row + S * gq * (d + 1))     # partials, o, lse
        + 4 * S * gq * d                                # q
        + 2 * 2 * n * kv_elem_bytes * sched.tile_size * d
    )


def lean_decode_fused(
    q_seg: jax.Array,          # (S_seg, gq, d)
    k_seg: jax.Array,          # dense: (S_seg, S_pad, d), S_pad % tile == 0
    v_seg: jax.Array,          #   paged: (num_pages * H_kv, page_size, d)
    seg_ctx: jax.Array,        # (S_seg,) int32 runtime context lengths
    sched: LeanSchedule,
    scale: float,
    interpret: bool = False,
    route: Optional[jax.Array] = None,   # paged: (steps * n,) int32 pool rows
    k_scales: Optional[jax.Array] = None,  # quant: (rows, 1) f32 per-row scales
    v_scales: Optional[jax.Array] = None,
    iters_per_step: Optional[int] = None,
):
    """Fused stream-K decode: ONE ``pallas_call`` for partials AND merge.

    The flat schedule runs the ``G*T`` LeanTile iterations followed by
    ``P`` descriptor-driven merge iterations; per-piece ``(o, m, l)`` stay
    in a VMEM scratch ring the whole time. Returns (o (S, gq, d) f32,
    lse (S, gq) f32).

    Each grid step runs ``n = iters_per_step`` consecutive iterations
    (default :func:`fused_iters_per_step` of the tile's bytes) with n K and
    n V BlockSpecs, so the pipeline fetches the next step's n pages while
    this step computes; the schedule is padded with ``OP_PAD`` iterations
    to ``steps * n``. Each iteration does what a one-iteration step does,
    in the same order, so the output does not depend on ``n``.

    The grid is sequential (``arbitrary``) — worker parallelism trades for
    zero HBM partial traffic and a single launch, the winning trade for
    decode-sized outputs. ``ops.lean_decode`` falls back to the two-phase
    path when :func:`fused_vmem_bytes` exceeds its budget.

    ``route`` switches K/V fetching to the paged pool-row layout (see
    :func:`lean_decode_partials`), one row per padded iteration
    (``sched.iter_kv_meta(fused=True, iters_per_step=n)``); merge and
    padding iterations carry null routes. ``k_scales``/``v_scales`` (paged
    only) enable int8 KV with in-kernel per-(page, head) dequant — merge
    iterations route the scales to row 0 along with the tiles, where they
    are never read.
    """
    S_seg, gq, d = q_seg.shape
    tile = sched.tile_size
    n = iters_per_step or fused_iters_per_step(
        tile, d, jnp.dtype(k_seg.dtype).itemsize
    )
    P = sched.num_pieces
    desc = jnp.asarray(sched.fused_descriptors(n))
    steps = desc.shape[1] // n
    paged = route is not None
    quant = k_scales is not None
    if quant and not paged:
        raise ValueError("quantized KV requires the paged (route) layout")
    if paged and route.shape != (steps * n,):
        raise ValueError(
            f"route has shape {route.shape}, the fused grid {steps} steps"
            f" of {n} iterations"
        )

    def kv_map_dense(j):
        def index_map(s, desc, *_):
            i = s * n + j
            ok = desc[DESC_VALID, i] == OP_PARTIAL
            return (
                jnp.where(ok, desc[DESC_SEG, i], 0),
                jnp.where(ok, desc[DESC_TILE, i], 0),
                0,
            )
        return index_map

    def route_map(j):
        return lambda s, desc, ctx, route: (route[s * n + j], 0, 0)

    kv_map = route_map if paged else kv_map_dense
    kv_specs, scale_specs, scale_inputs = [], [], ()
    for j in range(n):
        kv_specs += [pl.BlockSpec((1, tile, d), kv_map(j))] * 2
        specs, inputs = scale_operands(k_scales, v_scales, route_map(j))
        scale_specs += specs
        scale_inputs += inputs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if paged else 2,
        grid=(steps,),
        in_specs=[
            # whole q: a constant index map keeps it VMEM-resident, and
            # each iteration reads its segment's rows
            pl.BlockSpec((S_seg, gq, d), lambda s, *_: (0, 0, 0)),
            *kv_specs, *scale_specs,
        ],
        out_specs=[
            # whole-output blocks: the index maps are constant, so the
            # buffers stay VMEM-resident across the grid and flush to HBM
            # exactly once at the end — no revisit hazards
            pl.BlockSpec((S_seg, gq, d), lambda s, *_: (0, 0, 0)),
            pl.BlockSpec((S_seg, gq, 1), lambda s, *_: (0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((gq, d), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
            pltpu.VMEM((P + 1, gq, d), jnp.float32),
            pltpu.VMEM((P + 1, gq, 1), jnp.float32),
            pltpu.VMEM((P + 1, gq, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_fused_kernel if paged else _lean_decode_fused_kernel,
        scale=scale, tile_size=tile, iters_per_step=n, quantized=quant,
    )
    out_shapes = [
        jax.ShapeDtypeStruct((S_seg, gq, d), jnp.float32),
        jax.ShapeDtypeStruct((S_seg, gq, 1), jnp.float32),
    ]
    operands = (desc, seg_ctx.astype(jnp.int32))
    if paged:
        operands += (route.astype(jnp.int32),)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="lean_decode_fused",
        interpret=interpret,
    )(*operands, q_seg, *(k_seg, v_seg) * n, *scale_inputs)
    return o, lse[..., 0]


# ------------------------------------------------------------------ paged
# Page-table execution of the same stream-K schedules: K/V arrive as a
# global page pool flattened to (num_pages * H_kv, page_size, d) and a third
# scalar-prefetch operand carries, per schedule iteration, the flattened pool
# row ``page * H_kv + head`` to fetch (built in kernels.ops from
# ``LeanSchedule.iter_kv_meta`` + the runtime page table). The kernel BODIES
# are the dense ones unchanged — only the K/V BlockSpec index maps differ —
# so paged and dense execution run the identical fp op sequence and produce
# bit-identical outputs on identical inputs. Invalid, merge and padding
# iterations route to the null page's rows, whose contents are never read.


def _paged_partial_kernel(desc_ref, ctx_ref, route_ref, *refs, **kw):
    _lean_decode_kernel(desc_ref, ctx_ref, *refs, **kw)


def _paged_fused_kernel(desc_ref, ctx_ref, route_ref, *refs, **kw):
    _lean_decode_fused_kernel(desc_ref, ctx_ref, *refs, **kw)


def lean_decode_paged_partials(
    q_seg: jax.Array,          # (S_seg, gq, d)
    k_rows: jax.Array,         # (num_pages * H_kv, page_size, d) pool rows
    v_rows: jax.Array,
    seg_ctx: jax.Array,        # (S_seg,) int32 runtime context lengths
    route: jax.Array,          # (G*T,) int32 pool row per iteration
    sched: LeanSchedule,
    scale: float,
    interpret: bool = False,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
):
    """Phase 1 of the paged path: :func:`lean_decode_partials` with the
    routing operand. ``sched.tile_size`` must equal the pool's page size."""
    return lean_decode_partials(
        q_seg, k_rows, v_rows, seg_ctx, sched, scale,
        interpret=interpret, route=route,
        k_scales=k_scales, v_scales=v_scales,
    )


def lean_decode_paged_fused(
    q_seg: jax.Array,          # (S_seg, gq, d)
    k_rows: jax.Array,         # (num_pages * H_kv, page_size, d) pool rows
    v_rows: jax.Array,
    seg_ctx: jax.Array,        # (S_seg,) int32 runtime context lengths
    route: jax.Array,          # (steps * n,) int32 pool row per iteration
    sched: LeanSchedule,
    scale: float,
    interpret: bool = False,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    iters_per_step: Optional[int] = None,
):
    """Fused paged stream-K decode: :func:`lean_decode_fused` with the
    routing operand."""
    return lean_decode_fused(
        q_seg, k_rows, v_rows, seg_ctx, sched, scale,
        interpret=interpret, route=route,
        k_scales=k_scales, v_scales=v_scales, iters_per_step=iters_per_step,
    )


# ---------------------------------------------------------------- cascade
# Fused cascade decode: prefix pass + suffix pass + segment merge in ONE
# descriptor-driven flat grid. The combined grid runs the prefix phase's
# partial iterations (stacked member queries, shared pages walked once per
# grouped pass), then the suffix phase's (per-sequence private tails), then
# the merge iterations — partials for BOTH phases stay in one VMEM scratch
# ring and never round-trip HBM, exactly like ``lean_decode_fused``.
#
# Descriptor semantics are op-dependent (the array is built by
# ``repro.core.leantile.cascade_fused_descriptors`` and arrives as a
# RUNTIME operand — only its shape is schedule-static, so regroupings with
# equal geometry replay one trace):
#   OP_PARTIAL: SEG = combined q-stack segment (prefix segments first,
#     suffix segments after), TILE = kv tile, PIECE = combined piece row;
#   OP_MERGE:   SEG = target *output* segment (b * H_kv + h, garbage = S),
#     TILE = member rank r — the iteration reduces partial rows
#     [r*g, (r+1)*g) of PIECE into the target's (g, d) accumulator.


def _lean_cascade_fused_kernel(
    desc_ref,      # (7, N) scalar-prefetch descriptors (runtime values)
    ctx_ref,       # (SEG_tot,) runtime lengths: pass lens ⊗ H_kv, suffix lens
    route_ref,     # (N,) pool-row routing (consumed by the index maps)
    q_ref,         # (1, qmax, d)   current segment's stacked query block
    k_ref,         # (1, tile, d)
    v_ref,         # (1, tile, d)
    *refs,         # [ks_ref (1,1,1), vs_ref (1,1,1)] when quantized, then:
                   # o_ref (S + 1, g, d)  final outputs (+ garbage row), VMEM
                   # lse_ref (S + 1, g, 1)
                   # acc_ref   VMEM (qmax, d) f32  partial-phase accumulators
                   # m_acc_ref VMEM (qmax, 1) f32
                   # l_acc_ref VMEM (qmax, 1) f32
                   # g_acc_ref VMEM (g, d) f32     merge-phase accumulators
                   # g_m_ref   VMEM (g, 1) f32
                   # g_l_ref   VMEM (g, 1) f32
                   # po_ref VMEM (P_tot + 1, nmax, g, d) f32  piece partials
                   # pm_ref VMEM (P_tot + 1, nmax, g, 1) f32  (member-ranked)
                   # pl_ref VMEM (P_tot + 1, nmax, g, 1) f32
    scale: float,
    tile_size: int,
    gq: int,
    quantized: bool = False,
):
    if quantized:
        (ks_ref, vs_ref, o_ref, lse_ref, acc_ref, m_acc_ref, l_acc_ref,
         g_acc_ref, g_m_ref, g_l_ref, po_ref, pm_ref, pl_ref) = refs
    else:
        (o_ref, lse_ref, acc_ref, m_acc_ref, l_acc_ref,
         g_acc_ref, g_m_ref, g_l_ref, po_ref, pm_ref, pl_ref) = refs
        ks_ref = vs_ref = None
    i = pl.program_id(0)
    op = desc_ref[DESC_VALID, i]
    seg = desc_ref[DESC_SEG, i]
    piece = desc_ref[DESC_PIECE, i]
    first = desc_ref[DESC_FIRST, i]
    last = desc_ref[DESC_LAST, i]

    @pl.when(op == OP_PARTIAL)
    def _partial():
        @pl.when(first == 1)
        def _reset():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_acc_ref[...] = jnp.full_like(m_acc_ref, NEG_INF)
            l_acc_ref[...] = jnp.zeros_like(l_acc_ref)

        vlen = jnp.clip(
            ctx_ref[seg] - desc_ref[DESC_TILE, i] * tile_size, 0, tile_size
        )
        _online_softmax_tile(
            q_ref[0], k_ref[0], v_ref[0], acc_ref, m_acc_ref, l_acc_ref,
            vlen, scale,
            k_scale=None if ks_ref is None else ks_ref[0],
            v_scale=None if vs_ref is None else vs_ref[0],
        )

        @pl.when(last == 1)
        def _flush():  # split the stacked rows by member rank
            for r in range(po_ref.shape[1]):
                rows = pl.ds(r * gq, gq)
                po_ref[pl.ds(piece, 1), r] = acc_ref[rows, :][None]
                pm_ref[pl.ds(piece, 1), r] = m_acc_ref[rows, :][None]
                pl_ref[pl.ds(piece, 1), r] = l_acc_ref[rows, :][None]

    @pl.when(op == OP_MERGE)
    def _merge():
        @pl.when(first == 1)
        def _reset():
            g_acc_ref[...] = jnp.zeros_like(g_acc_ref)
            g_m_ref[...] = jnp.full_like(g_m_ref, NEG_INF)
            g_l_ref[...] = jnp.zeros_like(g_l_ref)

        rank = pl.ds(desc_ref[DESC_TILE, i], 1)  # member rank
        o_piece = po_ref[pl.ds(piece, 1), rank][0, 0]   # (g, d)
        m_piece = pm_ref[pl.ds(piece, 1), rank][0, 0]   # (g, 1)
        l_piece = pl_ref[pl.ds(piece, 1), rank][0, 0]
        m_new = jnp.maximum(g_m_ref[...], m_piece)
        a_old = jnp.exp(g_m_ref[...] - m_new)
        a_new = jnp.exp(m_piece - m_new)
        g_l_ref[...] = a_old * g_l_ref[...] + a_new * l_piece
        g_acc_ref[...] = a_old * g_acc_ref[...] + a_new * o_piece
        g_m_ref[...] = m_new

        @pl.when(last == 1)
        def _final():
            o_ref[pl.ds(seg, 1)] = (g_acc_ref[...] / g_l_ref[...])[None]
            lse_ref[pl.ds(seg, 1)] = (
                g_m_ref[...] + jnp.log(g_l_ref[...])
            )[None]


def cascade_fused_vmem_bytes(
    csched, gq: int, d: int, kv_elem_bytes: int = 4
) -> int:
    """Rough VMEM footprint of the fused cascade kernel's resident state:
    the f32 combined piece-partial ring, the whole-output block, both
    accumulator sets, and a K + V tile at the cache dtype width
    (``kv_elem_bytes`` — see :func:`fused_vmem_bytes`). Gates the fused
    path — schedules above the budget fall back to the two-call cascade."""
    qmax = csched.group_size * gq
    Ptot = csched.num_pieces_total
    S = csched.batch * csched.num_kv_heads
    return 4 * (
        (Ptot + 1) * qmax * (d + 2)
        + (S + 1) * gq * (d + 1)
        + qmax * (d + 2)
        + gq * (d + 2)
        + qmax * d
    ) + kv_elem_bytes * 2 * csched.tile_size * d


def lean_cascade_fused(
    q_stack: jax.Array,        # (SEG_tot, qmax, d) prefix then suffix blocks
    k_rows: jax.Array,         # (num_pages * H_kv, page_size, d) pool rows
    v_rows: jax.Array,
    ctx_all: jax.Array,        # (SEG_tot,) int32 runtime per-segment lengths
    route: jax.Array,          # (N,) int32 pool row per grid iteration
    desc: jax.Array,           # (7, N) int32 fused cascade descriptors
    csched,
    scale: float,
    gq: int,
    interpret: bool = False,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
):
    """Fused cascade decode: ONE ``pallas_call`` for the grouped prefix
    pass, the per-sequence suffix pass, AND the merge. Returns
    ``(o (S, g, d) f32, lse (S, g) f32)`` with the garbage row sliced off.

    All operands — including the descriptors — are runtime arrays; the
    only static inputs are the schedule-derived shapes, so every grouping
    with the same :class:`~repro.core.leantile.CascadeSchedule` geometry
    replays this trace. ``k_scales``/``v_scales`` enable int8 pool rows
    with in-kernel per-(page, head) dequant."""
    SEG_tot, qmax, d = q_stack.shape
    tile = csched.tile_size
    N = csched.fused_grid_iters
    Ptot = csched.num_pieces_total
    S = csched.batch * csched.num_kv_heads
    nmax = qmax // gq
    quant = k_scales is not None

    def q_map(i, desc, *_):
        ok = desc[DESC_VALID, i] == OP_PARTIAL
        return (jnp.where(ok, desc[DESC_SEG, i], 0), 0, 0)

    def kv_map(i, desc, ctx, route):
        return (route[i], 0, 0)

    in_specs = [
        pl.BlockSpec((1, qmax, d), q_map),
        pl.BlockSpec((1, tile, d), kv_map),
        pl.BlockSpec((1, tile, d), kv_map),
    ]
    scale_specs, scale_inputs = scale_operands(k_scales, v_scales, kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N,),
        in_specs=in_specs + scale_specs,
        out_specs=[
            pl.BlockSpec((S + 1, gq, d), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec((S + 1, gq, 1), lambda i, *_: (0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((qmax, d), jnp.float32),
            pltpu.VMEM((qmax, 1), jnp.float32),
            pltpu.VMEM((qmax, 1), jnp.float32),
            pltpu.VMEM((gq, d), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
            pltpu.VMEM((Ptot + 1, nmax, gq, d), jnp.float32),
            pltpu.VMEM((Ptot + 1, nmax, gq, 1), jnp.float32),
            pltpu.VMEM((Ptot + 1, nmax, gq, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _lean_cascade_fused_kernel, scale=scale, tile_size=tile, gq=gq,
        quantized=quant,
    )
    out_shapes = [
        jax.ShapeDtypeStruct((S + 1, gq, d), jnp.float32),
        jax.ShapeDtypeStruct((S + 1, gq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="lean_cascade_fused",
        interpret=interpret,
    )(
        desc.astype(jnp.int32), ctx_all.astype(jnp.int32),
        route.astype(jnp.int32), q_stack, k_rows, v_rows, *scale_inputs,
    )
    return o[:S], lse[:S, :, 0]


def _lean_merge_kernel(
    meta_ref,      # (2, S) scalar prefetch: piece start / piece count
    o_p_ref,       # (1, gq, d)  one piece's partial o (revisited per j)
    m_p_ref,       # (1, gq, 1)
    l_p_ref,       # (1, gq, 1)
    o_ref,         # (1, gq, d)  final output for this segment
    l_out_ref,     # (1, gq, 1)  logsumexp (for paged/backward use)
    acc_ref,       # VMEM (gq, d) f32
    m_acc_ref,     # VMEM (gq, 1)
    l_acc_ref,     # VMEM (gq, 1)
):
    s = pl.program_id(0)
    j = pl.program_id(1)
    cnt = meta_ref[1, s]

    @pl.when(j == 0)
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_acc_ref[...] = jnp.full_like(m_acc_ref, NEG_INF)
        l_acc_ref[...] = jnp.zeros_like(l_acc_ref)

    @pl.when(j < cnt)
    def _merge():  # Algorithm 2 lines 29-35: softmax re-scaling reduction
        m_piece = m_p_ref[0]
        m_new = jnp.maximum(m_acc_ref[...], m_piece)
        a_old = jnp.exp(m_acc_ref[...] - m_new)
        a_new = jnp.exp(m_piece - m_new)
        l_acc_ref[...] = a_old * l_acc_ref[...] + a_new * l_p_ref[0]
        acc_ref[...] = a_old * acc_ref[...] + a_new * o_p_ref[0].astype(
            jnp.float32
        )
        m_acc_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _final():  # Algorithm 2 lines 38-39
        o_ref[0] = acc_ref[...] / l_acc_ref[...]
        l_out_ref[0] = m_acc_ref[...] + jnp.log(l_acc_ref[...])


def lean_merge_pallas(
    o_p: jax.Array,      # (P, gq, d) f32 partials
    m_p: jax.Array,      # (P, gq)
    l_p: jax.Array,      # (P, gq)
    sched: LeanSchedule,
    interpret: bool = False,
):
    """Phase 2 as a Pallas kernel: per-segment reduction of pieces.

    Pieces are contiguous per segment (schedule invariant), so segment s owns
    piece rows [start[s], start[s]+cnt[s]). Grid (S, Pmax) revisits the
    output block while walking piece rows via scalar-prefetched offsets.
    """
    P, gq, d = o_p.shape
    S = sched.num_segments
    starts, cnts = sched.piece_ranges()
    pmax = max(1, int(cnts.max(initial=1)))
    meta = jnp.asarray(np.stack([starts, cnts]).astype(np.int32))

    def piece_map(s, j, meta):
        row = meta[0, s] + jnp.minimum(j, meta[1, s] - 1)
        return (jnp.clip(row, 0, P - 1), 0, 0)

    def seg_map(s, j, meta):
        return (s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, pmax),
        in_specs=[
            pl.BlockSpec((1, gq, d), piece_map),
            pl.BlockSpec((1, gq, 1), piece_map),
            pl.BlockSpec((1, gq, 1), piece_map),
        ],
        out_specs=[
            pl.BlockSpec((1, gq, d), seg_map),
            pl.BlockSpec((1, gq, 1), seg_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((gq, d), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
            pltpu.VMEM((gq, 1), jnp.float32),
        ],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((S, gq, d), jnp.float32),
        jax.ShapeDtypeStruct((S, gq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        _lean_merge_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="lean_merge",
        interpret=interpret,
    )(meta, o_p, m_p[..., None], l_p[..., None])
    return o, lse[..., 0]
