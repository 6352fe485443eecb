"""Public jit'd entry points for the attention kernels.

``lean_decode`` is the paper's mechanism end-to-end: host-side stream-K
schedule -> Pallas kernel(s) -> associative merge. Two split points exist:

  * ``lean_decode(q, k, v, ctx_lens)`` — the convenience API. Context
    lengths are *host* values (python ints / numpy) because the schedule is
    built on the host, exactly as in the paper where the CPU launcher picks
    the grid before kernel launch. Pass a
    :class:`~repro.core.leantile.ScheduleCache` to amortize schedule
    construction across calls.
  * ``lean_decode_from_schedule(q, k, v, seg_ctx, sched, ...)`` — the
    jit-stable fast path. The schedule is an explicit *hashable* argument
    (``LeanSchedule`` hashes by content) and the function is pure in its
    array arguments, so an outer ``jax.jit(..., static_argnames=('sched',))``
    — e.g. the serving engine's whole decode step — traces once per
    schedule signature and replays thereafter. ``seg_ctx`` carries the true
    ragged lengths at runtime; the kernels mask with it, which is what
    makes bucketed (cached) schedules exact.

``fused=True`` selects the single-``pallas_call`` partial+merge kernel
(partials never leave VMEM); ``fused=False`` keeps the two-phase path
(partials through HBM + XLA segment-op or Pallas merge) for comparison and
for schedules whose VMEM footprint exceeds the fused budget.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.leantile import (
    CascadeBinding,
    CascadeSchedule,
    LeanSchedule,
    ScheduleCache,
    cascade_fused_descriptors,
    make_cascade_schedule,
    make_schedule,
    default_tile_size,
)
from repro.core.merge import AttnPartial, finalize, merge_n, segment_merge
from .lean_decode import (
    cascade_fused_vmem_bytes,
    fused_iters_per_step,
    fused_vmem_bytes,
    lean_cascade_fused,
    lean_decode_fused,
    lean_decode_paged_fused,
    lean_decode_paged_partials,
    lean_decode_partials,
    lean_merge_pallas,
)
from .flash_decode import flash_decode_partials
from .flash_prefill import flash_prefill, flash_prefill_paged  # re-export
from .lean_prefill import lean_prefill_chunk_partials

__all__ = [
    "DecodePlan",
    "CascadeOperands",
    "decode",
    "lean_decode",
    "lean_decode_from_schedule",
    "lean_decode_paged",
    "lean_decode_paged_from_schedule",
    "lean_decode_cascade",
    "lean_decode_cascade_from_schedule",
    "cascade_tables",
    "cascade_uses_fused",
    "decode_uses_fused",
    "lean_prefill_chunks",
    "flash_decode",
    "flash_decode_from_lens",
    "flash_prefill",
    "flash_prefill_paged",
    "default_num_workers",
    "FUSED_VMEM_BUDGET",
]


def _clamp_ctx_lens(ctx_lens: Sequence[int], caps, what: str, note=None):
    """Clamp per-sequence context lengths to their capacity, *loudly*.

    ``caps`` is a scalar (dense KV capacity) or a per-sequence sequence
    (paged: allocated pages * page_size). A length beyond its capacity can
    only attend to what the backing store holds — but silently truncating
    hides bugs upstream (a scheduler admitting contexts the cache cannot
    hold), so overflow warns instead of passing unnoticed.

    ``note(i) -> bool`` (optional) is consulted once per overflowing
    sequence index: it records the occurrence wherever the caller keeps
    stats and returns whether this sequence should still be *warned*
    about. :meth:`repro.serving.kvpool.KVPagePool.note_ctx_overflow` uses
    it to dedupe a stuck sequence's warning to once per admission while
    counting every occurrence — without it a sequence pinned at its
    capacity re-warns every tick.
    """
    n = len(ctx_lens)
    caps = [int(caps)] * n if np.ndim(caps) == 0 else [int(c) for c in caps]
    clamped = [min(int(c), cap) for c, cap in zip(ctx_lens, caps)]
    over = [
        (i, int(c), cap)
        for i, (c, cap) in enumerate(zip(ctx_lens, caps))
        if int(c) > cap
    ]
    if note is not None:
        over = [item for item in over if note(item[0])]
    if over:
        warnings.warn(
            f"{what}: context length exceeds KV capacity for sequences "
            f"{[(i, c, cap) for i, c, cap in over[:8]]}"
            f"{'...' if len(over) > 8 else ''} — clamping (attention only "
            "covers the stored tokens)",
            RuntimeWarning,
            stacklevel=3,
        )
    return clamped

# fused-path resident-state budget; ~half of a TPU core's VMEM, leaving room
# for pipelined KV tiles. Schedules above this fall back to two-phase.
FUSED_VMEM_BUDGET = 8 * 2**20


def decode_uses_fused(
    sched: LeanSchedule, gq: int, d: int, kv_elem_bytes: int = 4
) -> bool:
    """Whether the fused single-kernel decode fits the VMEM budget for this
    schedule (else the two-phase kernels run). ``kv_elem_bytes`` is the KV
    element width: 2 for bf16, 1 for int8 pools."""
    return fused_vmem_bytes(
        sched, gq, d, kv_elem_bytes=kv_elem_bytes
    ) <= FUSED_VMEM_BUDGET


def default_num_workers(n_cores: int = 8, pipeline_factor: int = 2) -> int:
    """TPU analogue of paper's grid = NumSMs x MaxCTAsPerSM (Eq. 2).

    ``n_cores``: TensorCores the kernel is distributed over (Megacore=2 per
    chip; more when the op is sharded). ``pipeline_factor``: extra workers
    per core so DMA/compute phases interleave.
    """
    return n_cores * pipeline_factor


def _to_segments(q, k, v):
    """(B,Hq,d),(B,Hkv,S,d) -> segment-major views (paper's constant-stride
    (batch, heads, ctx, head_dim) layout, §IV-C)."""
    B, Hq, d = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    q_seg = q.reshape(B * Hkv, g, d)
    k_seg = k.reshape(B * Hkv, S, d)
    v_seg = v.reshape(B * Hkv, S, d)
    return q_seg, k_seg, v_seg, g


def _pad_kv(k_seg, v_seg, tile):
    S = k_seg.shape[1]
    pad = (-S) % tile
    if pad:
        k_seg = jnp.pad(k_seg, ((0, 0), (0, pad), (0, 0)))
        v_seg = jnp.pad(v_seg, ((0, 0), (0, pad), (0, 0)))
    return k_seg, v_seg


def _merge_two_phase(o_p, m_p, l_p, sched, merge_impl, interpret):
    """Phase 2 shared by the dense and paged two-phase paths: reduce the
    per-piece partials per segment. Returns (o_seg, lse)."""
    if merge_impl == "pallas":
        return lean_merge_pallas(o_p, m_p, l_p, sched, interpret=interpret)
    part = AttnPartial(o=o_p, m=m_p, l=l_p)
    seg = segment_merge(part, jnp.asarray(sched.piece_seg), sched.num_segments)
    return finalize(seg), seg.m + jnp.log(seg.l)


# ---------------------------------------------------------------- DecodePlan
_PLAN_KINDS = ("dense", "paged", "cascade", "flash", "verify")


@dataclass(frozen=True)
class DecodePlan:
    """Everything jit-static about one decode dispatch, in one hashable key.

    The ten parallel entry points this module grew (dense/paged/cascade x
    convenience/from-schedule, flash, chunk-prefill) all reduce to "which
    kernel family + which schedule + which layout flags" — a ``DecodePlan``
    names that choice once, and :func:`decode` routes it. Every public
    entry point below is now a thin wrapper that builds a plan and
    delegates, so wrapper and dispatcher are bit-identical by construction
    (pinned in ``tests/test_ops_decode.py``), and new modes land as a plan
    kind instead of an eleventh function — speculative verify
    (``kind='verify'``, ``spec_rows`` stacked query rows per sequence with
    a runtime causal offset) is the first.

    Fields mirror the jit-static arguments of the wrapped paths; a plan is
    content-hashable (``LeanSchedule``/``CascadeSchedule`` hash by
    content), so it serves directly as a ``static_argnames`` key for an
    enclosing ``jax.jit`` exactly like the bare schedule used to.

    kind:
      * ``'dense'``   — stream-K decode over dense per-slot KV
      * ``'paged'``   — stream-K decode through a page table
      * ``'cascade'`` — prefix-grouped decode (``sched`` is the
        :class:`~repro.core.leantile.CascadeSchedule`; grouped-pass
        operands arrive via :class:`CascadeOperands`)
      * ``'flash'``   — fixed-split FlashDecoding baseline
        (``num_splits``/``tile`` static, no schedule)
      * ``'verify'``  — multi-q-row paged attention: ``spec_rows`` stacked
        query rows per sequence against a chunk/spec schedule with a
        runtime ``qstart`` causal offset. Serves both chunked prefill and
        speculative draft-verify (a verify tick IS a prefill pack whose
        chunk is the draft block).
    """

    kind: str
    sched: Optional[Union[LeanSchedule, CascadeSchedule]] = None
    scale: Optional[float] = None
    fused: bool = True
    merge_impl: str = "xla"
    interpret: bool = False
    return_lse: bool = False
    num_splits: Optional[int] = None      # flash only
    tile: Optional[int] = None            # flash only
    spec_rows: int = 0                    # verify only: q rows per sequence

    def __post_init__(self):
        if self.kind not in _PLAN_KINDS:
            raise ValueError(
                f"unknown plan kind {self.kind!r} (one of {_PLAN_KINDS})"
            )
        if self.kind == "flash":
            if self.num_splits is None or self.tile is None:
                raise ValueError("flash plans need num_splits and tile")
        elif self.sched is None:
            raise ValueError(f"{self.kind!r} plans need a schedule")
        if self.kind == "verify" and self.spec_rows < 1:
            raise ValueError("verify plans need spec_rows >= 1")


class CascadeOperands(NamedTuple):
    """Runtime arrays of a cascade dispatch (everything membership-shaped —
    the schedule stays membership-free so equivalent groupings share one
    trace; see :func:`lean_decode_cascade_from_schedule`)."""

    prefix_lens: jax.Array         # (NP,) int32 true pass lengths (tokens)
    members: jax.Array             # (NP, nmax) int32 slot ids, -1 padding
    prefix_tbl: jax.Array          # (NP, Wp) int32 shared pass pages
    suffix_tbl: jax.Array          # (B, Ws) int32 private tails (shifted)
    fused_desc: jax.Array          # (7, N) int32 fused merge descriptors


def decode(
    q: jax.Array,
    kv: Tuple[jax.Array, jax.Array],
    *,
    plan: DecodePlan,
    ctx: jax.Array,
    page_tbl: Optional[jax.Array] = None,
    qstart: Optional[jax.Array] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    cascade: Optional[CascadeOperands] = None,
    page_base=0,
):
    """The one decode dispatcher: ``plan`` picks the kernel family, the
    arrays ride alongside.

    ``kv`` is ``(k, v)`` — dense per-slot KV for ``'dense'``/``'flash'``
    plans, the global page pools for ``'paged'``/``'cascade'``/``'verify'``.
    ``ctx`` carries the runtime lengths: per-segment context for decode
    kinds, visible KV (``off + len``) for ``'verify'``, suffix lengths for
    ``'cascade'``. ``qstart`` (verify only) is the per-segment causal
    offset of query row 0. ``page_base`` (paged kinds) is the pool page
    of the tables' page 0: the pools may be a stack of ``L`` layers' pools
    viewed as one of ``L * P`` pages, read in place at layer ``r``'s pages
    with ``page_base = r * P``. Pure in every array argument; ``plan`` is the
    only static key, so an enclosing ``jax.jit(...,
    static_argnames=('plan',))`` traces once per plan and replays across
    page migrations, bucket hits, and draft blocks alike.
    """
    k, v = kv
    if plan.kind == "dense":
        return _dense_decode_impl(q, k, v, ctx, plan)
    if plan.kind == "paged":
        if page_tbl is None:
            raise ValueError("paged plans need page_tbl")
        return _paged_decode_impl(
            q, k, v, ctx, page_tbl, plan, k_scales, v_scales, page_base
        )
    if plan.kind == "cascade":
        if cascade is None:
            raise ValueError("cascade plans need CascadeOperands")
        return _cascade_decode_impl(q, k, v, ctx, cascade, plan,
                                    k_scales, v_scales, page_base)
    if plan.kind == "flash":
        return _flash_decode_impl(q, k, v, ctx, plan)
    # 'verify': multi-q-row paged attention with runtime causal offset
    if page_tbl is None or qstart is None:
        raise ValueError("verify plans need page_tbl and qstart")
    return _verify_impl(
        q, k, v, ctx, qstart, page_tbl, plan, k_scales, v_scales, page_base
    )


def lean_decode_from_schedule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seg_ctx: jax.Array,            # (B*Hkv,) int32 true context lengths
    sched: LeanSchedule,
    *,
    scale: Optional[float] = None,
    fused: bool = True,
    merge_impl: str = "xla",
    interpret: bool = False,
    return_lse: bool = False,
):
    """Jit-stable LeanAttention decode against a prebuilt schedule.

    Pure in the array arguments (q, k, v, seg_ctx); ``sched`` and the
    keyword flags are hashable, so the whole function — or any caller
    enclosing it — jits with ``static_argnames=('sched', ...)`` and traces
    once per schedule signature. The schedule's tile walk must *cover* the
    true lengths (``sched.seg_len >= seg_ctx``, e.g. built from bucketed
    lengths); masking against ``seg_ctx`` keeps the result exact.

    Thin wrapper over :func:`decode` with a ``'dense'`` :class:`DecodePlan`.
    """
    plan = DecodePlan(
        kind="dense", sched=sched, scale=scale, fused=fused,
        merge_impl=merge_impl, interpret=interpret, return_lse=return_lse,
    )
    return decode(q, (k, v), plan=plan, ctx=seg_ctx)


def _dense_decode_impl(q, k, v, seg_ctx, plan: DecodePlan):
    B, Hq, d = q.shape
    sched = plan.sched
    scale = plan.scale if plan.scale is not None else 1.0 / float(np.sqrt(d))
    fused = plan.fused
    q_seg, k_seg, v_seg, _g = _to_segments(q, k, v)
    k_seg, v_seg = _pad_kv(k_seg, v_seg, sched.tile_size)
    gq = q_seg.shape[1]
    seg_ctx = seg_ctx.astype(jnp.int32)

    fused = fused and decode_uses_fused(
        sched, gq, d, kv_elem_bytes=jnp.dtype(k.dtype).itemsize
    )
    if fused:
        o_seg, lse = lean_decode_fused(
            q_seg, k_seg, v_seg, seg_ctx, sched, scale,
            interpret=plan.interpret,
        )
    else:
        o_p, m_p, l_p = lean_decode_partials(
            q_seg, k_seg, v_seg, seg_ctx, sched, scale,
            interpret=plan.interpret,
        )
        o_seg, lse = _merge_two_phase(
            o_p, m_p, l_p, sched, plan.merge_impl, plan.interpret
        )
    out = o_seg.reshape(B, Hq, d).astype(q.dtype)
    if plan.return_lse:
        return out, lse.reshape(B, Hq)
    return out


def lean_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    ctx_lens: Optional[Sequence[int]] = None,
    *,
    num_workers: Optional[int] = None,
    tile: Optional[int] = None,
    scale: Optional[float] = None,
    fused: bool = False,
    merge_impl: str = "xla",
    schedule_cache: Optional[ScheduleCache] = None,
    interpret: bool = False,
    return_lse: bool = False,
):
    """LeanAttention decode: exact attention, stream-K partitioned.

    q: (B, Hq, d); k, v: (B, Hkv, S, d); ctx_lens: host ints per batch row.
    ``schedule_cache`` buckets the lengths and memoizes the schedule;
    without one an exact schedule is built per call.
    """
    B, Hq, d = q.shape
    _, Hkv, S, _ = k.shape
    if ctx_lens is None:
        ctx_lens = [S] * B
    ctx_lens = _clamp_ctx_lens(ctx_lens, S, "lean_decode")
    tile = tile or default_tile_size(d)
    tile = min(tile, max(8, S))
    num_workers = num_workers or default_num_workers()

    if schedule_cache is not None:
        s_pad = S + ((-S) % tile)
        sched = schedule_cache.get(
            ctx_lens, Hkv, tile, num_workers, max_len=s_pad
        )
    else:
        sched = make_schedule(ctx_lens, Hkv, tile, num_workers)
    seg_ctx = jnp.asarray(np.repeat(np.asarray(ctx_lens), Hkv), jnp.int32)
    return lean_decode_from_schedule(
        q, k, v, seg_ctx, sched,
        scale=scale, fused=fused, merge_impl=merge_impl,
        interpret=interpret, return_lse=return_lse,
    )


def _paged_route(
    sched: LeanSchedule, page_tbl: jax.Array, num_kv_heads: int, fused: bool,
    page_base=0, iters_per_step: int = 1,
) -> jax.Array:
    """Per-schedule-iteration flattened pool row ``page * H_kv + head``
    (fused: padded to ``iters_per_step``, the iterations a grid step runs).

    The schedule contributes static logical routing (batch, head, tile per
    iteration — :meth:`LeanSchedule.iter_kv_meta`); the runtime page table
    contributes the physical page, offset by ``page_base`` (the page of
    the table's page 0 in a stack of layers' pools). Invalid/merge
    iterations (and tiles past the table width, which the runtime length
    always masks) route to the null page's rows, page ``page_base``.
    This is the one place a page id becomes a pool row.
    """
    batch, head, tile_idx, ok = sched.iter_kv_meta(fused, iters_per_step)
    width = page_tbl.shape[1]
    pages = page_tbl[batch, np.minimum(tile_idx, width - 1)]
    pages = jnp.where(jnp.asarray(ok) == 1, pages, 0) + page_base
    return pages.astype(jnp.int32) * num_kv_heads + jnp.asarray(head)


def lean_decode_paged_from_schedule(
    q: jax.Array,                  # (B, Hq, d)
    k_pool: jax.Array,             # (num_pages, Hkv, page_size, d)
    v_pool: jax.Array,
    seg_ctx: jax.Array,            # (B*Hkv,) int32 true context lengths
    page_tbl: jax.Array,           # (B, pages_per_seq) int32 physical pages
    sched: LeanSchedule,
    *,
    scale: Optional[float] = None,
    fused: bool = True,
    merge_impl: str = "xla",
    interpret: bool = False,
    return_lse: bool = False,
    k_scales: Optional[jax.Array] = None,   # int8 pools: (num_pages, Hkv) f32
    v_scales: Optional[jax.Array] = None,
    page_base=0,                   # pool page of the table's page 0
):
    """Jit-stable *paged* LeanAttention decode against a prebuilt schedule.

    The paged twin of :func:`lean_decode_from_schedule`: K/V live in a
    global page pool and each sequence's logical tiles resolve to physical
    pages through ``page_tbl`` (``sched.tile_size`` must equal the pool's
    page size; a lean tile IS a page). Pure in the array arguments
    (q, pools, seg_ctx, page_tbl) — ``sched`` stays the only static key, so
    schedule-cache hits keep hitting the jit trace cache no matter how
    sequences migrate across physical pages.

    Runs the identical fp op sequence as the dense path: on equal logical
    inputs the outputs are bit-identical.

    ``k_scales``/``v_scales`` (per-(page, head) f32, from a quantized int8
    pool) ride the same route operand into the kernels, which dequantize
    each KV tile in VMEM before the fp32 online softmax — merge numerics
    are unchanged and the smaller elements shrink both the HBM traffic per
    stream-K tile and the fused-path VMEM footprint.

    Thin wrapper over :func:`decode` with a ``'paged'`` :class:`DecodePlan`.
    """
    plan = DecodePlan(
        kind="paged", sched=sched, scale=scale, fused=fused,
        merge_impl=merge_impl, interpret=interpret, return_lse=return_lse,
    )
    return decode(
        q, (k_pool, v_pool), plan=plan, ctx=seg_ctx, page_tbl=page_tbl,
        k_scales=k_scales, v_scales=v_scales, page_base=page_base,
    )


def _pool_rows(k_pool, v_pool, k_scales, v_scales):
    """(page, head) flatten: a pool row is one head's page — this is a
    layout-preserving reshape (free), and it lets the paged kernels reuse
    the dense kernel bodies wholesale with a 1D routing operand."""
    num_pages, Hkv, page_size, d = k_pool.shape
    k_rows = k_pool.reshape(num_pages * Hkv, page_size, d)
    v_rows = v_pool.reshape(num_pages * Hkv, page_size, d)
    ks_rows = vs_rows = None
    if k_scales is not None:
        ks_rows = k_scales.reshape(num_pages * Hkv, 1)
        vs_rows = v_scales.reshape(num_pages * Hkv, 1)
    return k_rows, v_rows, ks_rows, vs_rows


def _paged_decode_impl(q, k_pool, v_pool, seg_ctx, page_tbl,
                       plan: DecodePlan, k_scales, v_scales, page_base):
    B, Hq, d = q.shape
    num_pages, Hkv, page_size, _ = k_pool.shape
    sched = plan.sched
    if page_size != sched.tile_size:
        raise ValueError(
            f"page_size {page_size} != schedule tile_size {sched.tile_size}"
            " — lean tiles must map 1:1 onto pages"
        )
    scale = plan.scale if plan.scale is not None else 1.0 / float(np.sqrt(d))
    fused = plan.fused
    gq = Hq // Hkv
    q_seg = q.reshape(B * Hkv, gq, d)
    seg_ctx = seg_ctx.astype(jnp.int32)
    k_rows, v_rows, ks_rows, vs_rows = _pool_rows(
        k_pool, v_pool, k_scales, v_scales
    )

    elem = jnp.dtype(k_pool.dtype).itemsize
    fused = fused and decode_uses_fused(sched, gq, d, kv_elem_bytes=elem)
    n = fused_iters_per_step(page_size, d, elem) if fused else 1
    route = _paged_route(sched, page_tbl, Hkv, fused, page_base, n)
    if fused:
        o_seg, lse = lean_decode_paged_fused(
            q_seg, k_rows, v_rows, seg_ctx, route, sched, scale,
            interpret=plan.interpret, k_scales=ks_rows, v_scales=vs_rows,
            iters_per_step=n,
        )
    else:
        o_p, m_p, l_p = lean_decode_paged_partials(
            q_seg, k_rows, v_rows, seg_ctx, route, sched, scale,
            interpret=plan.interpret, k_scales=ks_rows, v_scales=vs_rows,
        )
        o_seg, lse = _merge_two_phase(
            o_p, m_p, l_p, sched, plan.merge_impl, plan.interpret
        )
    out = o_seg.reshape(B, Hq, d).astype(q.dtype)
    if plan.return_lse:
        return out, lse.reshape(B, Hq)
    return out


def lean_decode_paged(
    q: jax.Array,                  # (B, Hq, d)
    k_pool: jax.Array,             # (num_pages, Hkv, page_size, d)
    v_pool: jax.Array,
    page_tbl,                      # (B, pages_per_seq) int32 (host or device)
    ctx_lens: Sequence[int],
    *,
    page_counts: Optional[Sequence[int]] = None,
    num_workers: Optional[int] = None,
    scale: Optional[float] = None,
    fused: bool = True,
    merge_impl: str = "xla",
    schedule_cache: Optional[ScheduleCache] = None,
    interpret: bool = False,
    return_lse: bool = False,
    pool=None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
):
    """Convenience paged decode: builds (or cache-fetches) the schedule from
    host context lengths, then runs :func:`lean_decode_paged_from_schedule`.

    Lengths clamp to each sequence's *allocated* capacity — ``page_counts``
    (pages actually held, straight from
    :meth:`repro.serving.kvpool.KVPagePool.count`) times the page size — not
    to the dense table width; overflow warns instead of truncating silently.
    When ``page_counts`` is omitted it is inferred from the table under the
    null-page convention (page 0 is never allocated, so non-null entries
    count allocated pages).

    ``pool`` (optional :class:`~repro.serving.kvpool.KVPagePool`) dedupes
    the overflow warning to once per (batch-row) sequence and counts every
    occurrence in ``pool.stats.ctx_overflows`` — a stuck sequence stops
    re-warning every tick.
    """
    B, Hq, d = q.shape
    num_pages, Hkv, page_size, _ = k_pool.shape
    ptbl_np = np.asarray(page_tbl)
    if ptbl_np.shape[0] != B:
        raise ValueError("page table rows must match the batch")
    if page_counts is None:
        page_counts = (ptbl_np != 0).sum(axis=1)
    ctx_lens = _clamp_ctx_lens(
        ctx_lens, np.asarray(page_counts) * page_size, "lean_decode_paged",
        note=None if pool is None else pool.note_ctx_overflow,
    )
    ctx_lens = [max(1, c) for c in ctx_lens]        # schedule needs >= 1
    num_workers = num_workers or default_num_workers()
    max_len = ptbl_np.shape[1] * page_size
    if schedule_cache is not None:
        sched = schedule_cache.get(
            ctx_lens, Hkv, page_size, num_workers, max_len=max_len
        )
    else:
        sched = make_schedule(ctx_lens, Hkv, page_size, num_workers)
    seg_ctx = jnp.asarray(np.repeat(np.asarray(ctx_lens), Hkv), jnp.int32)
    return lean_decode_paged_from_schedule(
        q, k_pool, v_pool, seg_ctx, jnp.asarray(ptbl_np, jnp.int32), sched,
        scale=scale, fused=fused, merge_impl=merge_impl,
        interpret=interpret, return_lse=return_lse,
        k_scales=k_scales, v_scales=v_scales,
    )


def cascade_uses_fused(
    csched: CascadeSchedule, gq: int, d: int, kv_elem_bytes: int = 4
) -> bool:
    """Whether the fused single-kernel cascade fits the VMEM budget (the
    static fallback decision callers can query for stats/bench).
    ``kv_elem_bytes`` is the pool element width — quantized int8 pools
    (1 byte) fit schedules the f32 accounting would have rejected."""
    return cascade_fused_vmem_bytes(
        csched, gq, d, kv_elem_bytes=kv_elem_bytes
    ) <= FUSED_VMEM_BUDGET


def lean_decode_cascade_from_schedule(
    q: jax.Array,                  # (B, Hq, d)
    k_pool: jax.Array,             # (num_pages, Hkv, page_size, d)
    v_pool: jax.Array,
    seg_ctx_suffix: jax.Array,     # (B*Hkv,) int32 true suffix lengths
    prefix_lens: jax.Array,        # (NP,) int32 true pass lengths (tokens)
    members: jax.Array,            # (NP, nmax) int32 slot ids, -1 padding
    prefix_tbl: jax.Array,         # (NP, Wp) int32 shared pass pages
    suffix_tbl: jax.Array,         # (B, Ws) int32 private tails (shifted)
    fused_desc: jax.Array,         # (7, N) int32 fused descriptors
    csched: CascadeSchedule,
    *,
    scale: Optional[float] = None,
    fused: bool = True,
    interpret: bool = False,
    return_lse: bool = False,
    k_scales: Optional[jax.Array] = None,   # int8 pools: (num_pages, Hkv) f32
    v_scales: Optional[jax.Array] = None,
    page_base=0,                   # pool page of the tables' page 0
):
    """Jit-stable cascade (prefix-grouped) paged LeanAttention decode.

    The grouped prefix pass(es), the per-sequence suffix pass, and the
    segment merge — executed as ONE descriptor-driven flat-grid
    ``pallas_call`` (:func:`~repro.kernels.lean_decode.lean_cascade_fused`,
    partials never leave VMEM) when the schedule fits the fused VMEM
    budget, else as the two-``pallas_call`` + XLA ``segment_merge``
    fallback.

    Pure in the array arguments; ``csched`` is the only static key — and
    it is *membership-free*, so every value that depends on which slots
    group where arrives as a runtime array: ``members`` drives the stacked
    prefix query gather and the merge targets, ``prefix_lens`` masks the
    (bucketed) pass walks, the tables route pages, and ``fused_desc``
    (built host-side by
    :func:`repro.core.leantile.cascade_fused_descriptors`; ignored on the
    two-call path) carries the merge plan. Equivalent grouping geometries
    therefore replay one trace.

    Numerics: sharing physical pages is bit-neutral (asserted in tests
    against the same cascade over duplicated pages); the *regrouping*
    itself re-associates the softmax reduction, so against the unshared
    single-walk schedule the result is exact-but-not-bitwise (fp32
    tolerance), exactly like any other stream-K repartition.

    Thin wrapper over :func:`decode` with a ``'cascade'``
    :class:`DecodePlan` (the membership-shaped arrays travel as
    :class:`CascadeOperands`).
    """
    plan = DecodePlan(
        kind="cascade", sched=csched, scale=scale, fused=fused,
        interpret=interpret, return_lse=return_lse,
    )
    ops_c = CascadeOperands(
        prefix_lens=prefix_lens, members=members, prefix_tbl=prefix_tbl,
        suffix_tbl=suffix_tbl, fused_desc=fused_desc,
    )
    return decode(
        q, (k_pool, v_pool), plan=plan, ctx=seg_ctx_suffix, cascade=ops_c,
        k_scales=k_scales, v_scales=v_scales, page_base=page_base,
    )


def _cascade_decode_impl(q, k_pool, v_pool, seg_ctx_suffix, ops_c,
                         plan: DecodePlan, k_scales, v_scales, page_base):
    csched = plan.sched
    scale, fused, interpret = plan.scale, plan.fused, plan.interpret
    return_lse = plan.return_lse
    prefix_lens, members, prefix_tbl, suffix_tbl, fused_desc = ops_c
    B, Hq, d = q.shape
    num_pages, Hkv, page_size, _ = k_pool.shape
    if page_size != csched.tile_size:
        raise ValueError(
            f"page_size {page_size} != schedule tile_size {csched.tile_size}"
            " — lean tiles must map 1:1 onto pages"
        )
    if B != csched.batch or Hkv != csched.num_kv_heads:
        raise ValueError("cascade schedule does not match the batch geometry")
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    g = Hq // Hkv
    nmax = csched.group_size
    NP = csched.num_groups
    k_rows, v_rows, ks_rows, vs_rows = _pool_rows(
        k_pool, v_pool, k_scales, v_scales
    )

    # stacked member queries: padding ranks carry member-0 copies whose
    # partial rows are dropped (or garbage-targeted) at merge
    mem = jnp.clip(jnp.asarray(members, jnp.int32), 0, None)  # (NP, nmax)
    q_r = q.reshape(B, Hkv, g, d)
    q_pref = q_r[mem]                                    # (NP, nmax, Hkv, g, d)
    q_pref = jnp.moveaxis(q_pref, 2, 1).reshape(NP * Hkv, nmax * g, d)
    seg_ctx_prefix = jnp.repeat(
        jnp.asarray(prefix_lens, jnp.int32), Hkv
    )
    route_p = _paged_route(
        csched.prefix_sched, prefix_tbl, Hkv, False, page_base
    )
    route_s = _paged_route(
        csched.suffix_sched, suffix_tbl, Hkv, False, page_base
    )
    seg_ctx_suffix = seg_ctx_suffix.astype(jnp.int32)

    if fused and not cascade_uses_fused(
        csched, g, d, kv_elem_bytes=jnp.dtype(k_pool.dtype).itemsize
    ):
        fused = False
    if fused:
        # ---- single flat grid: prefix partials + suffix partials + merge
        qmax = nmax * g
        q_suf = q.reshape(B * Hkv, g, d)
        if qmax > g:
            q_suf = jnp.pad(q_suf, ((0, 0), (0, qmax - g), (0, 0)))
        q_stack = jnp.concatenate([q_pref, q_suf], axis=0)
        ctx_all = jnp.concatenate([seg_ctx_prefix, seg_ctx_suffix])
        route = jnp.concatenate([
            route_p, route_s,
            jnp.zeros(csched.fused_merge_iters, jnp.int32),
        ])
        o_seg, lse = lean_cascade_fused(
            q_stack, k_rows, v_rows, ctx_all, route,
            jnp.asarray(fused_desc, jnp.int32), csched, scale, g,
            interpret=interpret, k_scales=ks_rows, v_scales=vs_rows,
        )
        out = o_seg.reshape(B, Hq, d).astype(q.dtype)
        if return_lse:
            return out, lse.reshape(B, Hq)
        return out

    # ---- two-call fallback: prefix pass, suffix pass, XLA segment merge
    o_p, m_p, l_p = lean_decode_paged_partials(
        q_pref, k_rows, v_rows, seg_ctx_prefix, route_p,
        csched.prefix_sched, scale, interpret=interpret,
        k_scales=ks_rows, v_scales=vs_rows,
    )
    q_suf = q.reshape(B * Hkv, g, d)
    o_s, m_s, l_s = lean_decode_paged_partials(
        q_suf, k_rows, v_rows, seg_ctx_suffix, route_s,
        csched.suffix_sched, scale, interpret=interpret,
        k_scales=ks_rows, v_scales=vs_rows,
    )
    # merge: slice prefix pieces per member, reduce with suffix pieces.
    # Targets derive from the RUNTIME members array — a prefix piece of
    # segment (pass j, head h) expands to one row per member rank, aimed
    # at sequence segment members[j, i] * Hkv + h (padding ranks aim at
    # the garbage segment B * Hkv and are dropped by segment_merge).
    Pp = csched.prefix_sched.num_pieces
    o_pe = jnp.swapaxes(o_p.reshape(Pp, nmax, g, d), 0, 1).reshape(
        nmax * Pp, g, d
    )
    m_pe = jnp.swapaxes(m_p.reshape(Pp, nmax, g), 0, 1).reshape(nmax * Pp, g)
    l_pe = jnp.swapaxes(l_p.reshape(Pp, nmax, g), 0, 1).reshape(nmax * Pp, g)
    part = AttnPartial(
        o=jnp.concatenate([o_pe, o_s]),
        m=jnp.concatenate([m_pe, m_s]),
        l=jnp.concatenate([l_pe, l_s]),
    )
    pseg = csched.prefix_sched.piece_seg.astype(np.int64)    # (Pp,) static
    grp, head = pseg // Hkv, pseg % Hkv
    mem_p = jnp.asarray(members, jnp.int32)[grp]             # (Pp, nmax)
    tgt = jnp.where(
        mem_p >= 0, mem_p * Hkv + jnp.asarray(head)[:, None], B * Hkv
    )
    ids = jnp.concatenate(
        [tgt.T.reshape(-1), jnp.asarray(csched.suffix_sched.piece_seg)]
    )
    seg = segment_merge(part, ids, B * Hkv)
    out = finalize(seg).reshape(B, Hq, d).astype(q.dtype)
    if return_lse:
        return out, (seg.m + jnp.log(seg.l)).reshape(B, Hq)
    return out


def cascade_tables(
    page_tbl: np.ndarray, binding: CascadeBinding
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side (prefix_tbl, suffix_tbl) for a cascade call.

    ``prefix_tbl[j]`` is grouped pass ``j``'s shared page run — pages
    ``[page_start, page_start + prefix_pages)`` of its first member's
    table row (all members alias the same physical pages there);
    ``suffix_tbl[b]`` is sequence ``b``'s row shifted left past its
    deepest shared coverage. Widths stay at the slot table width so
    bucketed schedule walks never index out of range.
    """
    ptbl = np.asarray(page_tbl)
    B, W = ptbl.shape
    NP = binding.members.shape[0]
    prefix_tbl = np.zeros((NP, W), dtype=np.int32)
    suffix_tbl = np.zeros((B, W), dtype=np.int32)
    for j in range(NP):
        lead = int(binding.members[j, 0])
        if lead < 0:
            continue
        s = int(binding.page_start[j])
        n = int(binding.prefix_pages[j])
        prefix_tbl[j, :n] = ptbl[lead, s : s + n]
    for b in range(B):
        n = int(binding.seq_prefix_pages[b])
        suffix_tbl[b, : W - n] = ptbl[b, n:]
    return prefix_tbl, suffix_tbl


def lean_decode_cascade(
    q: jax.Array,                  # (B, Hq, d)
    k_pool: jax.Array,             # (num_pages, Hkv, page_size, d)
    v_pool: jax.Array,
    page_tbl,                      # (B, pages_per_seq) int32
    ctx_lens: Sequence[int],
    groups: Sequence[Sequence[int]],
    prefix_pages: Sequence[int],
    *,
    page_starts: Optional[Sequence[int]] = None,
    num_workers: Optional[int] = None,
    scale: Optional[float] = None,
    fused: bool = True,
    schedule_cache: Optional[ScheduleCache] = None,
    interpret: bool = False,
    return_lse: bool = False,
    pool=None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
):
    """Convenience cascade decode: builds (or cache-fetches) the cascade
    schedule + binding from host lengths/grouping, derives the phase
    tables and fused descriptors, and runs
    :func:`lean_decode_cascade_from_schedule`.

    ``groups``/``prefix_pages``/``page_starts`` are grouped passes over
    the batch — nested (multi-level) passes allowed, singletons dropped —
    exactly the output of
    :func:`repro.serving.prefix_cache.lcp_group_passes` over a radix-cache
    admission. Lengths clamp to allocated capacity like
    :func:`lean_decode_paged` (``pool`` dedupes the warning per sequence
    and counts occurrences in the pool stats, same as there).
    """
    B, Hq, d = q.shape
    num_pages, Hkv, page_size, _ = k_pool.shape
    ptbl_np = np.asarray(page_tbl)
    if ptbl_np.shape[0] != B:
        raise ValueError("page table rows must match the batch")
    page_counts = (ptbl_np != 0).sum(axis=1)
    ctx_lens = _clamp_ctx_lens(
        ctx_lens, np.asarray(page_counts) * page_size, "lean_decode_cascade",
        note=None if pool is None else pool.note_ctx_overflow,
    )
    ctx_lens = [max(1, c) for c in ctx_lens]
    num_workers = num_workers or default_num_workers()
    max_len = ptbl_np.shape[1] * page_size
    if schedule_cache is not None:
        csched, binding = schedule_cache.get_cascade(
            ctx_lens, groups, prefix_pages, Hkv, page_size, num_workers,
            max_len=max_len, page_starts=page_starts,
        )
    else:
        csched, binding = make_cascade_schedule(
            ctx_lens, groups, prefix_pages, Hkv, page_size, num_workers,
            page_starts=page_starts, max_len=max_len,
        )
    prefix_tbl, suffix_tbl = cascade_tables(ptbl_np, binding)
    fused_desc = cascade_fused_descriptors(csched, binding)
    seg_ctx_suffix = jnp.asarray(
        np.repeat(
            np.asarray(ctx_lens) - np.asarray(binding.seq_prefix_len), Hkv
        ),
        jnp.int32,
    )
    return lean_decode_cascade_from_schedule(
        q, k_pool, v_pool, seg_ctx_suffix,
        jnp.asarray(binding.prefix_lens, jnp.int32),
        jnp.asarray(binding.members, jnp.int32),
        jnp.asarray(prefix_tbl, jnp.int32), jnp.asarray(suffix_tbl, jnp.int32),
        jnp.asarray(fused_desc, jnp.int32),
        csched, scale=scale, fused=fused, interpret=interpret,
        return_lse=return_lse, k_scales=k_scales, v_scales=v_scales,
    )


def lean_prefill_chunks(
    q: jax.Array,                  # (N, Hq, C, d) one prompt chunk per row
    k_pool: jax.Array,             # (num_pages, Hkv, page_size, d)
    v_pool: jax.Array,
    seg_ctx: jax.Array,            # (N*Hkv,) int32 visible KV (off + len)
    seg_qstart: jax.Array,         # (N*Hkv,) int32 chunk start offsets
    page_tbls: jax.Array,          # (N, W) int32 page table rows
    sched: LeanSchedule,
    *,
    scale: Optional[float] = None,
    merge_impl: str = "xla",
    interpret: bool = False,
    k_scales: Optional[jax.Array] = None,   # int8 pools: (num_pages, Hkv) f32
    v_scales: Optional[jax.Array] = None,
    page_base=0,                   # pool page of the tables' page 0
):
    """Jit-stable stream-K chunked prefill against a prebuilt chunk schedule.

    The prefill analogue of :func:`lean_decode_paged_from_schedule`: ``sched``
    comes from :func:`repro.core.leantile.make_chunk_schedule` over the pack's
    visible KV lengths and is the only static argument — ``seg_ctx``,
    ``seg_qstart``, and ``page_tbls`` are runtime arrays, so bucketed chunk
    schedules replay one trace as requests advance through their prompts and
    migrate across physical pages. Two-phase execution; the merge phase is
    the decode one (partials are the same ``(o, m, l)`` triple with
    ``g * C`` rows per segment instead of ``g``).

    Thin wrapper over :func:`decode` with a ``'verify'`` :class:`DecodePlan`
    (``spec_rows = C``): a chunked-prefill pack and a speculative verify
    tick are the same multi-q-row workload, differing only in what the
    rows hold (prompt chunk vs draft block).
    """
    N, Hq, C, d = q.shape
    plan = DecodePlan(
        kind="verify", sched=sched, scale=scale, merge_impl=merge_impl,
        interpret=interpret, spec_rows=C,
    )
    return decode(
        q, (k_pool, v_pool), plan=plan, ctx=seg_ctx, page_tbl=page_tbls,
        qstart=seg_qstart, k_scales=k_scales, v_scales=v_scales,
        page_base=page_base,
    )


def _verify_impl(q, k_pool, v_pool, seg_ctx, seg_qstart, page_tbls,
                 plan: DecodePlan, k_scales, v_scales, page_base):
    N, Hq, C, d = q.shape
    num_pages, Hkv, page_size, _ = k_pool.shape
    sched = plan.sched
    if page_size != sched.tile_size:
        raise ValueError(
            f"page_size {page_size} != schedule tile_size {sched.tile_size}"
            " — lean tiles must map 1:1 onto pages"
        )
    if C != plan.spec_rows:
        raise ValueError(
            f"q carries {C} rows per sequence, plan says {plan.spec_rows}"
        )
    scale = plan.scale if plan.scale is not None else 1.0 / float(np.sqrt(d))
    g = Hq // Hkv
    q_seg = q.reshape(N, Hkv, g, C, d).reshape(N * Hkv, g * C, d)
    k_rows, v_rows, ks_rows, vs_rows = _pool_rows(
        k_pool, v_pool, k_scales, v_scales
    )
    route = _paged_route(sched, page_tbls, Hkv, False, page_base)
    o_p, m_p, l_p = lean_prefill_chunk_partials(
        q_seg, k_rows, v_rows, seg_ctx.astype(jnp.int32),
        seg_qstart.astype(jnp.int32), route, sched, scale,
        chunk_cap=C, interpret=plan.interpret,
        k_scales=ks_rows, v_scales=vs_rows,
    )
    o_seg, _lse = _merge_two_phase(
        o_p, m_p, l_p, sched, plan.merge_impl, plan.interpret
    )
    return o_seg.reshape(N, Hq, C, d).astype(q.dtype)


def flash_decode_from_lens(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seg_ctx: jax.Array,            # (B*Hkv,) int32 true context lengths
    *,
    num_splits: int,
    tile: int,
    scale: Optional[float] = None,
    interpret: bool = False,
):
    """Jit-stable FlashDecoding baseline: lengths are a runtime array,
    ``num_splits``/``tile`` are static — the serving engine jits its whole
    decode step over this (the fixed-split analogue of
    :func:`lean_decode_from_schedule`).

    Thin wrapper over :func:`decode` with a ``'flash'`` :class:`DecodePlan`.
    """
    plan = DecodePlan(
        kind="flash", scale=scale, num_splits=num_splits, tile=tile,
        interpret=interpret,
    )
    return decode(q, (k, v), plan=plan, ctx=seg_ctx)


def _flash_decode_impl(q, k, v, seg_ctx, plan: DecodePlan):
    B, Hq, d = q.shape
    scale = plan.scale if plan.scale is not None else 1.0 / float(np.sqrt(d))
    q_seg, k_seg, v_seg, _g = _to_segments(q, k, v)
    k_seg, v_seg = _pad_kv(k_seg, v_seg, plan.tile)
    o_p, m_p, l_p = flash_decode_partials(
        q_seg, k_seg, v_seg, seg_ctx.astype(jnp.int32), plan.num_splits,
        plan.tile, scale, interpret=plan.interpret,
    )
    part = AttnPartial(
        o=jnp.moveaxis(o_p, 1, 0), m=jnp.moveaxis(m_p, 1, 0),
        l=jnp.moveaxis(l_p, 1, 0),
    )
    out = finalize(merge_n(part))
    return out.reshape(B, Hq, d).astype(q.dtype)


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    ctx_lens: Optional[Sequence[int]] = None,
    *,
    num_splits: Optional[int] = None,
    num_workers: Optional[int] = None,
    tile: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
):
    """FlashDecoding baseline: fixed-split partitioning + merge.

    ``num_splits=None`` applies FlashDecoding's heuristic: the smallest split
    factor that covers the workers (paper §III-C / Fig. 1).
    """
    B, Hq, d = q.shape
    _, Hkv, S, _ = k.shape
    if ctx_lens is None:
        ctx_lens = [S] * B
    ctx_lens = _clamp_ctx_lens(ctx_lens, S, "flash_decode")
    tile = tile or default_tile_size(d)
    tile = min(tile, max(8, S))
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    if num_splits is None:
        from repro.core.leantile import fixed_split_factor

        num_workers = num_workers or default_num_workers()
        num_splits = fixed_split_factor(max(ctx_lens), B * Hkv, tile, num_workers)

    seg_lens = jnp.asarray(np.repeat(np.asarray(ctx_lens), Hkv), jnp.int32)
    return flash_decode_from_lens(
        q, k, v, seg_lens,
        num_splits=num_splits, tile=tile, scale=scale, interpret=interpret,
    )
