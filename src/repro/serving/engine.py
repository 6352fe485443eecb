"""Continuous-batching decode engine with LeanAttention scheduling.

The engine owns a fixed pool of sequence slots (the batch), admits requests
as slots free up (Orca-style continuous batching), and runs one fused decode
step per tick. Context lengths are *heterogeneous* — exactly the ragged
regime of paper §IV-C/Fig. 6.

Decode tick:

  * the stream-K schedule comes from a :class:`ScheduleCache` — ragged
    lengths bucket to canonical shapes, so a steady-state tick performs
    ZERO numpy schedule work (cache hit) and the schedule object is
    identical tick-to-tick;
  * the whole decode step (embed -> layers -> kernel attention -> logits ->
    cache update) runs under ONE ``jax.jit`` keyed on the schedule
    signature, with the KV cache donated;
  * request admission writes the prompt's pages and the slot's per-slot
    rows under a donating jit, leaving the rest of the cache in place;
  * per-tick sampling does one device->host argmax sync for the whole
    batch, not one per slot.

Attention backends:
  * 'lean'   — the Pallas stream-K kernel (interpret=True on CPU), fused
               into a single ``pallas_call`` when its VMEM budget allows,
  * 'fixed'  — the FlashDecoding fixed-split baseline kernel,
  * 'ref'    — pure-jnp oracle (fast under jit on CPU).

All backends compute exact attention; the schedule is what differs.

The KV cache is a page pool: global-attention KV lives in pools
``(num_pages, H_kv, page_size, d)`` managed by
:class:`repro.serving.kvpool.KVPagePool`; window rings, recurrent state and
cross-attention state stay per slot in the same cache tree. Admission
allocates only the pages the prompt needs (copy-on-admit scatter), decode
grows sequences page-by-page, and finishing a request returns its pages
immediately — slot capacity decouples from worst-case context, so an
undersized pool (``num_pages``) oversubscribes slots and preempts (evict +
recompute-resume) only when the pool actually fills. The 'lean' backend
fetches KV tiles *through the page table* natively (tile == page);
'ref'/'fixed' gather to dense per-slot views first.
"""
from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.leantile import (
    CascadeSchedule,
    LeanSchedule,
    ScheduleCache,
    bucket_length,
    cascade_fused_descriptors,
    default_tile_size,
    fixed_split_factor,
    make_chunk_schedule,
    make_spec_schedule,
)
from repro.core.attention import paged_gather_kv, paged_gather_kv_dequant
from repro.kernels.lean_decode import fused_grid
from repro.kernels.ops import (
    cascade_tables,
    cascade_uses_fused,
    decode_uses_fused,
    flash_decode_from_lens,
    flash_prefill_paged,
    lean_decode_cascade_from_schedule,
    lean_decode_paged_from_schedule,
    lean_prefill_chunks,
)
from repro.models import (
    ModelConfig,
    decode_step,
    init_paged_cache,
    prefill,
    prefill_chunks,
    verify_step,
)
from repro.models import supports_chunked_prefill as _cfg_supports_chunked
from repro.serving.config import EngineConfig
from repro.serving.faults import corrupt_trie_node
from repro.serving.speculative import NGramProposer
from repro.serving.guards import (
    DEGRADE_CAUSES,
    DEGRADE_LEVELS,
    FatalInvariantError,
    PoisonError,
)
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.roofline.analysis import schedule_decode_cost
from repro.serving.kvpool import KVLayout, KVPagePool
from repro.serving.prefix_cache import RadixPrefixCache, lcp_group_passes

import contextlib


@contextlib.contextmanager
def _quiet_donation():
    """Cache donation is a no-op on CPU backends; silence that warning for
    the engine's own donating calls only (no process-wide filter)."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


def _bind(fn, **kw):
    """``functools.partial`` that keeps ``fn``'s name, so the program jitted
    from it is named after it in a profile (``jit__kernel_decode_step_paged``,
    not ``jit__unknown``)."""
    return functools.update_wrapper(functools.partial(fn, **kw), fn)


@dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (L,) int32
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    # generated tokens already folded into ``prompt`` by recompute-resume
    # preemption — keeps a second preemption from folding them twice
    folded: int = 0

    @property
    def done(self):
        return len(self.generated) >= self.max_new_tokens


# Counter-valued EngineStats fields, published to the metrics registry as
# ``engine_<name>`` counters. The attribute routing in EngineStats keeps
# every existing ``stats.<name> += 1`` / ``stats.<name> = v`` call site
# working while the registry becomes the single source of truth.
_STAT_COUNTERS = (
    "ticks",
    "tokens_generated",
    "prefills",                  # blocking whole-prompt admissions
    "chunk_prefills",            # chunked-prefill chunk executions
    "prefill_tokens",            # prompt tokens pushed through chunks
    "preemptions",
    "prefill_compiles",          # distinct bucketed prefill shapes
    "prefix_matched_tokens",     # prompt tokens served from the radix cache
    "prefix_attach_count",       # admissions that hit the radix cache
    "cow_copies",                # copy-on-write page copies
    "fused_decode_ticks",        # fast-path lean ticks on the fused kernel
    "cascade_ticks",             # decode ticks run on the cascade path
    "cascade_grouped_slots",     # cumulative slots decoded via a group
    "cascade_grouped_passes",    # cumulative grouped passes executed
    "cascade_fused_ticks",       # cascade ticks on the fused kernel
    "cascade_retraces",          # distinct cascade schedule geometries
    "cascade_stability_skips",   # groupings held back by the N-tick guard
    "cascade_levels_max",        # deepest pass nesting seen on any tick
    # self-healing / fault-injection telemetry (guards + FaultInjector)
    "nan_ticks",                 # slot-ticks quarantined (non-finite)
    "degrade_escalations",       # slot moves DOWN the fallback chain
    "degrade_heals",             # slot moves back UP toward fast path
    "poisoned_slots",            # slots preempted after exhausting it
    "donation_aborts",           # prefix-cache donations unwound
    "audits_run",                # periodic invariant audit sweeps
    "audit_failures",            # audits that caught a violation
    "audit_repairs",             # violations fixed by repair()
    # speculative (draft-verify) decode telemetry
    "spec_ticks",                # decode ticks that ran a verify sweep
    "spec_draft_tokens",         # draft tokens submitted to verify
    "spec_accepted_tokens",      # drafts the verify sweep accepted
)


class EngineStats:
    """Engine telemetry, backed by a :class:`repro.obs.metrics.
    MetricsRegistry`.

    The public attribute surface is unchanged from the old dataclass —
    counters read/assign as plain ints, the latency histograms keep
    their ``observe``/``as_dict`` API, and the snapshot dict fields
    (``kv_pool``, ``schedule_cache``, ...) are ordinary attributes — but
    counter and histogram state now lives in registry metrics named
    ``engine_*``, so ``registry.as_dict()`` / ``to_prometheus()`` export
    everything without a second bookkeeping path.

    DEPRECATED access pattern: reading hand-rolled stats dict shapes off
    this object; prefer ``engine.metrics`` (the registry) for new code.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self._counters = {
            name: self.registry.counter(f"engine_{name}")
            for name in _STAT_COUNTERS
        }
        self.cascade_last = {}       # last tick's grouping
        self.schedules = []
        self.schedule_cache = {}
        self.kv_pool = {}
        self.prefix_cache = {}
        self.degraded = {}           # degraded-mode gauge snapshot
        self.faults = {}             # injector fire counts
        # per-tick prefill-vs-decode token split (capped like the
        # schedule log)
        self.tick_prefill_tokens = []
        self.tick_decode_tokens = []
        # latency histograms (seconds) — populated by the Scheduler, which
        # is the layer that knows arrival/first-token/per-token timestamps
        self.ttft = self.registry.histogram(
            "engine_ttft_seconds", help="time to first token"
        )
        self.tpot = self.registry.histogram(
            "engine_tpot_seconds", help="inter-token latency"
        )
        self.queue_wait = self.registry.histogram(
            "engine_queue_wait_seconds", help="submit-to-admit wait"
        )

    # counters masquerade as plain int attributes: __getattr__ only fires
    # for names not in __dict__, i.e. exactly the routed counter fields
    def __getattr__(self, name):
        counters = self.__dict__.get("_counters")
        if counters is not None and name in counters:
            return counters[name].value
        raise AttributeError(name)

    def __setattr__(self, name, value):
        counters = self.__dict__.get("_counters")
        if counters is not None and name in counters:
            counters[name].value = int(value)
        else:
            object.__setattr__(self, name, value)

    def latency_dict(self) -> dict:
        return {
            "ttft": self.ttft.as_dict(),
            "tpot": self.tpot.as_dict(),
            "queue_wait": self.queue_wait.as_dict(),
        }


def _write_slot(cache, cache1, slot):
    """Write batch row 0 of ``cache1`` into row ``slot`` of ``cache``.

    One ``dynamic_update_slice`` per leaf; under jit with the destination
    donated this lowers to an in-place row write, not a tree copy. ``slot``
    is a traced scalar so every slot shares one trace.
    """
    def cp(dst, src):
        row = src[:, :1].astype(dst.dtype)
        start = (jnp.zeros((), jnp.int32),) + (slot,) + tuple(
            jnp.zeros((), jnp.int32) for _ in range(dst.ndim - 2)
        )
        return jax.lax.dynamic_update_slice(dst, row, start)

    return jax.tree.map(cp, cache, cache1)


def _pages_admit_write(pool, src, pages, page_size):
    """Copy-on-admit: scatter a freshly-prefilled slot's KV into its pages.

    ``pool: (reps, num_pages, H, page_size, hd)``; ``src`` is batch row 0 of
    the prefill cache ``(reps, 1, H, cache_len, hd)``; ``pages: (n,)`` the
    slot's physical page ids. Whole pages are written (tail padded), so any
    stale data in recycled pages is overwritten on admit.
    """
    reps, _, H, L, hd = src.shape
    n = pages.shape[0]
    need = n * page_size
    s = src[:, 0]
    if need > L:
        s = jnp.pad(s, ((0, 0), (0, 0), (0, need - L), (0, 0)))
    chunks = s[:, :, :need].reshape(reps, H, n, page_size, hd)
    chunks = jnp.moveaxis(chunks, 2, 1)          # (reps, n, H, ps, hd)
    return pool.at[:, pages].set(chunks.astype(pool.dtype))


def _pages_admit_write_quant(pool, scales, src, pages, page_size, per_head):
    """Quantizing :func:`_pages_admit_write`: whole pages are replaced, so
    each page's scale is simply *set* to the fresh content's amax/127 (no
    requantize-grow dance — there is no surviving old content)."""
    from repro.core.attention import quantize_kv_blocks

    reps, _, H, L, hd = src.shape
    n = pages.shape[0]
    need = n * page_size
    s = src[:, 0]
    if need > L:
        s = jnp.pad(s, ((0, 0), (0, 0), (0, need - L), (0, 0)))
    chunks = s[:, :, :need].reshape(reps, H, n, page_size, hd)
    chunks = jnp.moveaxis(chunks, 2, 1)          # (reps, n, H, ps, hd)
    q, sc = quantize_kv_blocks(chunks, per_head=per_head)
    return pool.at[:, pages].set(q.astype(pool.dtype)), scales.at[
        :, pages
    ].set(sc)


def _write_slot_paged(cache, cache1, pages, slot, *, cfg: ModelConfig,
                      page_size: int):
    """Paged admission write: 'attn' pools take the page scatter, everything
    else (win rings, cross-attn, recurrent state) takes the dense slot row
    write. Jitted with the destination donated, like ``_write_slot``.
    Quantized pools (``k_scale`` leaves present) quantize each admitted
    page and set its scale; the prefill source cache stays dense fp."""
    per_head = cfg.kv_scale_granularity == "page_head"
    out = []
    for (pattern, reps), st_c, st_c1 in zip(cfg.stages, cache, cache1):
        unit = []
        for kind, lc, lc1 in zip(pattern, st_c, st_c1):
            if kind == "attn":
                nc = dict(lc)
                if "k_scale" in lc:
                    nc["k"], nc["k_scale"] = _pages_admit_write_quant(
                        lc["k"], lc["k_scale"], lc1["k"], pages, page_size,
                        per_head,
                    )
                    nc["v"], nc["v_scale"] = _pages_admit_write_quant(
                        lc["v"], lc["v_scale"], lc1["v"], pages, page_size,
                        per_head,
                    )
                else:
                    nc["k"] = _pages_admit_write(
                        lc["k"], lc1["k"], pages, page_size
                    )
                    nc["v"] = _pages_admit_write(
                        lc["v"], lc1["v"], pages, page_size
                    )
                unit.append(nc)
            else:
                unit.append(_write_slot(lc, lc1, slot))
        out.append(tuple(unit))
    return out


def _kernel_decode_step_paged(
    params,
    cache,
    tokens,
    ctx_lens,
    page_tbl,
    *,
    cfg: ModelConfig,
    backend: str,
    sched: LeanSchedule,
    num_splits: int,
    fused: bool,
    interpret: bool,
):
    """One whole decode step with kernel-backed attention — pure in the
    array args; everything else is hashable and static, so the engine jits
    this end-to-end per schedule signature. The page table rides along as
    a runtime array (no retrace when sequences migrate across pages); the
    lean backend fetches tiles through it natively, the fixed-split
    baseline gathers to dense first. Each layer's call passes the page
    base of its pool in the stacked pools it receives."""

    def attn_fn(q, k_pool, v_pool, ctx, page_base, k_scales=None,
                v_scales=None):
        seg_ctx = jnp.repeat(ctx.astype(jnp.int32), cfg.n_kv_heads)
        if backend == "lean":
            return lean_decode_paged_from_schedule(
                q, k_pool, v_pool, seg_ctx, page_tbl, sched,
                fused=fused, interpret=interpret,
                k_scales=k_scales, v_scales=v_scales, page_base=page_base,
            )
        tbl = page_tbl + page_base
        if k_scales is not None:
            kd = paged_gather_kv_dequant(k_pool, k_scales, tbl)
            vd = paged_gather_kv_dequant(v_pool, v_scales, tbl)
        else:
            kd = paged_gather_kv(k_pool, tbl)
            vd = paged_gather_kv(v_pool, tbl)
        return flash_decode_from_lens(
            q, kd, vd, seg_ctx,
            num_splits=num_splits, tile=sched.tile_size, interpret=interpret,
        )

    cur = jnp.max(ctx_lens)
    return decode_step(
        params, cfg, cache, tokens, cur, attn_fn=attn_fn,
        ctx_lens=ctx_lens, page_tbl=page_tbl,
    )


def _kernel_decode_step_cascade(
    params,
    cache,
    tokens,
    ctx_lens,
    page_tbl,
    prefix_tbl,
    suffix_tbl,
    members,
    prefix_lens,
    seq_prefix_len,
    fused_desc,
    *,
    cfg: ModelConfig,
    csched: CascadeSchedule,
    fused: bool,
    interpret: bool,
):
    """Cascade (prefix-grouped) twin of ``_kernel_decode_step_paged``: the
    KV write still goes through the full per-slot ``page_tbl``; attention
    runs the grouped prefix pass(es) + per-slot suffix pass and merges —
    fused into one kernel when the VMEM budget allows. The membership-free
    schedule is the only static key; everything grouping-dependent
    (members, pass lengths, per-slot coverage, tables, merge descriptors)
    rides as runtime arrays, so equivalent geometries share this trace."""

    def attn_fn(q, k_pool, v_pool, ctx, page_base, k_scales=None,
                v_scales=None):
        suffix = jnp.maximum(
            ctx.astype(jnp.int32) - seq_prefix_len.astype(jnp.int32), 0
        )
        seg_suffix = jnp.repeat(suffix, cfg.n_kv_heads)
        return lean_decode_cascade_from_schedule(
            q, k_pool, v_pool, seg_suffix, prefix_lens, members,
            prefix_tbl, suffix_tbl, fused_desc, csched,
            fused=fused, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales, page_base=page_base,
        )

    cur = jnp.max(ctx_lens)
    return decode_step(
        params, cfg, cache, tokens, cur, attn_fn=attn_fn,
        ctx_lens=ctx_lens, page_tbl=page_tbl,
    )


def _copy_page(cache, src, dst, *, cfg: ModelConfig):
    """Copy-on-write device op: clone page ``src`` onto page ``dst`` in
    every pooled ('attn') layer. ``src``/``dst`` are traced scalars, so one
    trace serves every CoW; jitted with the cache donated."""
    out = []
    for (pattern, reps), st_c in zip(cfg.stages, cache):
        unit = []
        for kind, lc in zip(pattern, st_c):
            if kind == "attn":
                nc = dict(lc)
                keys = ("k", "v")
                if "k_scale" in lc:
                    # a CoW clone copies int8 content + its scale verbatim:
                    # exact, no requantization error
                    keys = ("k", "v", "k_scale", "v_scale")
                for key in keys:
                    pool = lc[key]
                    row = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
                    nc[key] = jax.lax.dynamic_update_slice_in_dim(
                        pool, row, dst, axis=1
                    )
                unit.append(nc)
            else:
                unit.append(lc)
        out.append(tuple(unit))
    return out


def _fill_page(cache, page, value, *, cfg: ModelConfig):
    """Overwrite page ``page`` of every pooled ('attn') layer with a
    constant. Two guard duties share this one trace (``page`` and ``value``
    are traced scalars): NaN-poisoning a victim page under fault injection,
    and zero-scrubbing a quarantined slot's private pages before they
    return to the free list — recycled pages may be read through masked
    tiles, where any *finite* garbage is harmless but NaN is not.

    Quantized pools: int8 content cannot hold NaN, so the *scale* leaf
    carries the fill value instead — ``0 * NaN = NaN`` on dequant keeps
    NaN-poisoning observable, and a 0.0 scrub dequantizes to exact zeros."""
    out = []
    for (pattern, reps), st_c in zip(cfg.stages, cache):
        unit = []
        for kind, lc in zip(pattern, st_c):
            if kind == "attn":
                nc = dict(lc)
                for key in ("k", "v"):
                    pool = lc[key]
                    fill = (
                        jnp.zeros((), pool.dtype)
                        if jnp.issubdtype(pool.dtype, jnp.integer)
                        else value
                    )
                    row = jnp.full(
                        pool.shape[:1] + (1,) + pool.shape[2:],
                        fill, pool.dtype,
                    )
                    nc[key] = jax.lax.dynamic_update_slice_in_dim(
                        pool, row, page, axis=1
                    )
                for key in ("k_scale", "v_scale"):
                    if key not in lc:
                        continue
                    sc = lc[key]
                    row = jnp.full(
                        sc.shape[:1] + (1,) + sc.shape[2:], value, sc.dtype
                    )
                    nc[key] = jax.lax.dynamic_update_slice_in_dim(
                        sc, row, page, axis=1
                    )
                unit.append(nc)
            else:
                unit.append(lc)
        out.append(tuple(unit))
    return out


def _screen_logits(logits):
    """Guarded sampling: the greedy token AND a per-slot finiteness verdict
    in one device round-trip — the NaN/Inf output guard costs one extra
    ``all(isfinite)`` reduction fused into the argmax sync, nothing more."""
    return (
        jnp.argmax(logits, axis=-1).astype(jnp.int32),
        jnp.all(jnp.isfinite(logits), axis=-1),
    )


def _chunk_attn_fn(offs, lens, *, cfg, backend, sched, interpret):
    """The multi-q-row paged attention closure shared by chunked prefill
    and the speculative verify step (``None`` selects the gather + jnp
    reference path). Rows attend causally up to ``offs + row`` via the
    schedule's runtime ``qstart``; ``page_base`` is the layer's page base
    in the stacked pools."""
    if backend == "lean":

        def attn_fn(q, k_pool, v_pool, tbls, o, page_base, k_scales=None,
                    v_scales=None):
            visible = jnp.maximum(offs + lens, 1).astype(jnp.int32)
            seg_ctx = jnp.repeat(visible, cfg.n_kv_heads)
            seg_qstart = jnp.repeat(offs.astype(jnp.int32), cfg.n_kv_heads)
            return lean_prefill_chunks(
                q, k_pool, v_pool, seg_ctx, seg_qstart, tbls, sched,
                interpret=interpret, k_scales=k_scales, v_scales=v_scales,
                page_base=page_base,
            )

        return attn_fn

    if backend == "fixed":

        def attn_fn(q, k_pool, v_pool, tbls, o, page_base, k_scales=None,
                    v_scales=None):
            if k_scales is not None:
                # fixed-split baseline has no in-kernel dequant — widen the
                # pool view first (bench/fallback path only)
                k_pool = (
                    k_pool.astype(jnp.float32) * k_scales[:, :, None, None]
                ).astype(jnp.bfloat16)
                v_pool = (
                    v_pool.astype(jnp.float32) * v_scales[:, :, None, None]
                ).astype(jnp.bfloat16)
            return flash_prefill_paged(
                q, k_pool, v_pool, tbls + page_base, o, interpret=interpret
            )

        return attn_fn

    return None


def _chunk_prefill_step(
    params,
    cache,
    tokens,          # (N, C) int32 — one prompt chunk per pack row
    offs,            # (N,) int32
    lens,            # (N,) int32
    page_tbls,       # (N, W) int32
    *,
    cfg: ModelConfig,
    backend: str,
    sched: LeanSchedule,
    interpret: bool,
):
    """One packed chunked-prefill step: pure in the array args; ``sched``
    (built over the pack's bucketed visible KV lengths) is the only static
    key, so the engine jits this end-to-end exactly like the decode step —
    one trace per (pack shape, schedule signature), replayed as requests
    advance through their prompts."""
    attn_fn = _chunk_attn_fn(
        offs, lens, cfg=cfg, backend=backend, sched=sched,
        interpret=interpret,
    )
    logits, new_cache = prefill_chunks(
        params, cfg, cache, tokens, offs, lens, page_tbls, attn_fn=attn_fn
    )
    # rows completing their prompt need only the sampled token — argmax on
    # device so the host sync moves pack_width ints, not the vocab-wide
    # logits block (mirrors the decode tick's single small argmax sync)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_cache


def _spec_verify_step(
    params,
    cache,
    tokens,          # (B, R) int32 — [last token, k drafts] per slot
    offs,            # (B,) int32 — committed context (write offset)
    lens,            # (B,) int32 — 1 + drafts actually proposed
    page_tbls,       # (B, W) int32
    *,
    cfg: ModelConfig,
    backend: str,
    sched: LeanSchedule,
    interpret: bool,
):
    """One speculative verify tick: R = k+1 stacked query rows per slot run
    through the chunked-prefill attention path (KV scattered at positions
    ``offs .. offs+lens-1``, row ``i`` attending causally through
    ``offs + i``). Returns the per-row greedy tokens ``(B, R)`` plus a
    per-slot finiteness verdict — the host sync moves B*R ints, never the
    vocab-wide logits block."""
    attn_fn = _chunk_attn_fn(
        offs, lens, cfg=cfg, backend=backend, sched=sched,
        interpret=interpret,
    )
    logits, new_cache = verify_step(
        params, cfg, cache, tokens, offs, lens, page_tbls, attn_fn=attn_fn
    )
    return (
        jnp.argmax(logits, axis=-1).astype(jnp.int32),
        jnp.all(jnp.isfinite(logits), axis=(1, 2)),
        new_cache,
    )


class DecodeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        config: Optional[EngineConfig] = None,
    ):
        """``config`` (an :class:`repro.serving.config.EngineConfig`) is
        the one configuration argument; ``None`` means ``EngineConfig()``."""
        if config is None:
            config = EngineConfig()
        self.config = config
        # unpack the nest into the names the body below grew up with
        max_batch = config.max_batch
        cache_len = config.cache_len
        attn_backend = config.attn_backend
        num_workers = config.num_workers
        fused = config.fused
        interpret = config.interpret
        schedule_cache_entries = config.schedule_cache_entries
        page_size = config.paged.page_size
        num_pages = config.paged.num_pages
        prefix_cache = config.paged.prefix_cache
        kv_dtype = config.paged.kv_dtype
        cascade = config.cascade.enabled
        cascade_fused = config.cascade.fused
        cascade_grouping = config.cascade.grouping
        cascade_multi_level = config.cascade.multi_level
        cascade_stable_ticks = config.cascade.stable_ticks
        faults = config.faults
        guards = config.guards
        tracer = config.obs.tracer
        metrics = config.obs.metrics
        flight = config.obs.flight
        flight_dir = config.obs.flight_dir
        watchdog = config.obs.watchdog
        # ``kv_dtype`` overrides the model config's KV storage dtype for
        # this engine — 'int8' turns on quantized pools (per-(page, head)
        # f32 scales, in-kernel dequant) for 2-4x effective capacity
        if kv_dtype is not None and kv_dtype != cfg.kv_cache_dtype:
            import dataclasses

            cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
        self.quant = cfg.kv_cache_dtype == "int8"
        # KV cache element width: int8/f8 caches are 1 byte, all else bf16
        self.kv_elem_bytes = 1 if cfg.kv_cache_dtype in ("int8", "f8") else 2
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.attn_backend = attn_backend
        self.num_workers = num_workers
        self.fused = fused
        self.cascade = cascade
        # cascade v2 policy knobs: fused single-kernel execution (VMEM
        # budget still gates per schedule), trie-path grouping mode
        # ('lcp' groups at longest common prefixes, optionally stacking
        # one pass per trie level; 'identical' reproduces the v1
        # equal-page-run grouping for comparison), and the stability
        # guard — the cascade path only engages once the grouping has
        # held unchanged for N consecutive ticks, so admission/finish
        # churn stops forcing a retrace per tick
        self.cascade_fused = cascade_fused
        if cascade_grouping not in ("lcp", "identical"):
            raise ValueError("cascade_grouping must be 'lcp' or 'identical'")
        self.cascade_grouping = cascade_grouping
        self.cascade_multi_level = cascade_multi_level
        self.cascade_stable_ticks = max(1, int(cascade_stable_ticks))
        self._casc_key = None           # last tick's grouping structure
        self._casc_stable = 0           # consecutive ticks it has held
        self._casc_signatures: set = set()  # schedule geometries seen
        self._casc_binding = None       # last cascade tick's binding
        # Pallas interpret mode: default on for CPU hosts (tests/bench),
        # off on real accelerators where Mosaic compiles the kernels
        self.interpret = (
            jax.default_backend() == "cpu" if interpret is None else interpret
        )

        # observability: structured tracer (NULL_TRACER is the module-wide
        # disabled instance — one falsy attribute check on the hot path),
        # unified metrics registry (EngineStats counters live in it), and
        # the always-on flight recorder (bounded ring; dumps a postmortem
        # bundle on degrade/poison/fatal/injected-fault)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.stats = EngineStats(registry=self.metrics)
        self.flight = (
            flight if flight is not None
            else FlightRecorder(dump_dir=flight_dir)
        )
        if flight is not None and flight_dir is not None:
            self.flight.dump_dir = flight_dir
        self._tick_dumped = False       # one injected-fault dump per tick
        self._fires_dumped = 0          # injector fires already dumped for
        self._sched_costs: dict = {}    # schedule -> roofline cost meta
        self._grid_steps = self.metrics.gauge(
            "engine_decode_kernel_grid_steps",
            help="grid steps per layer of the fused decode kernel in the "
                 "last tick (0 when another kernel ran)",
        )

        # fault injection + self-healing guards. Both default OFF; with
        # neither configured every hot-path hook below is a single `is None`
        # attribute test, keeping the hardened engine's fault-free tick
        # byte-for-byte the old code path (the perf gate enforces <3%).
        self.faults = faults
        if faults is not None and faults.recorder is None:
            # every injected fire logs a fault_fire event, so postmortem
            # dumps always name the injected point in their tail
            faults.recorder = self.flight
        self.guard_cfg = guards
        # per-slot position on the degraded-mode fallback chain
        # (see guards.DEGRADE_LEVELS) + consecutive bad/good tick runs
        self._slot_degrade = [0] * max_batch
        self._slot_bad = [0] * max_batch
        self._slot_good = [0] * max_batch
        self.degraded_gauge = self.metrics.gauge(
            "engine_degraded_slots", help="live slots off the fast path"
        )
        self._degrade_cause = self.metrics.counter(
            "engine_degrade_cause_total",
            help="degrade escalations by cause (see guards.DEGRADE_CAUSES)",
            labelnames=("cause",),
        )
        self._audit_clock = 0

        # perf watchdog (streaming anomaly detectors, repro.obs.watch).
        # ``watchdog`` may be True (defaults) or a WatchConfig; callers
        # needing SLO budgets or a fitted calibration construct
        # PerfWatchdog(engine, ...) themselves — it attaches here. Absent,
        # the per-tick hook is a single `is None` test.
        self.watchdog = None
        if watchdog is not None and watchdog is not False:
            from repro.obs.watch import PerfWatchdog, WatchConfig

            PerfWatchdog(
                self, WatchConfig() if watchdog is True else watchdog
            )

        # tile is fixed per engine (schedule/jit key stability); the cache
        # capacity bounds every slot's visible context. Lean tiles map 1:1
        # onto KV pages, so page_size overrides the tile.
        if page_size is not None:
            self.tile = int(page_size)
        else:
            self.tile = min(default_tile_size(cfg.head_dim), max(8, cache_len))
        self.pages_per_slot = -(-cache_len // self.tile)

        # default pool = one slot's worth of pages for every slot (+ null
        # page); pass a smaller num_pages to oversubscribe slots vs memory
        if num_pages is None:
            num_pages = 1 + max_batch * self.pages_per_slot
        n_attn = sum(
            reps for pattern, reps in cfg.stages
            for kind in pattern if kind == "attn"
        )
        layout = KVLayout(
            kv_dtype=cfg.kv_cache_dtype,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
            page_size=self.tile,
            n_attn_layers=n_attn,
            scale_granularity=cfg.kv_scale_granularity,
        )
        self.pool = KVPagePool(num_pages, self.tile, layout=layout)
        self.pool.register_metrics(self.metrics)
        self.page_tbl = np.zeros(
            (max_batch, self.pages_per_slot), dtype=np.int32
        )
        # the span holds the pools' allocation and fill, not only their
        # dispatch
        with self.tracer.span("engine.init_cache"):
            self.cache = jax.block_until_ready(init_paged_cache(
                cfg, max_batch, cache_len, num_pages, self.tile
            ))
        self.metrics.gauge(
            "engine_kv_pool_bytes",
            help="bytes of every attention layer's page pools together",
        ).set(sum(
            leaf.nbytes
            for (pattern, _), stage_c in zip(cfg.stages, self.cache)
            for kind, lc in zip(pattern, stage_c) if kind == "attn"
            for leaf in lc.values()
        ))
        self.metrics.gauge(
            "engine_kv_pages_total", help="pages in each layer's pool"
        ).set(num_pages)

        if cascade:
            if not prefix_cache:
                raise ValueError("cascade=True requires prefix_cache=True")
            if attn_backend != "lean":
                raise ValueError(
                    "cascade decode is a lean-kernel path "
                    "(attn_backend='lean')"
                )
        self.prefix_cache: Optional[RadixPrefixCache] = None
        if prefix_cache:
            # byte accounting now flows from the pool's layout descriptor
            # (the old static page_bytes knob drifted from the true dtype)
            self.prefix_cache = RadixPrefixCache(self.pool)
            self.prefix_cache.register_metrics(self.metrics)
        # per-slot prefix-sharing state: which logical tiles are shared
        # (immutable — copy-on-write before any KV write lands in one) and
        # how many *leading full* shared pages form the cascade prefix
        self._slot_shared_tiles: List[set] = [set() for _ in range(max_batch)]
        self._slot_prefix_full: List[int] = [0] * max_batch
        self.ctx_lens = np.zeros(max_batch, dtype=np.int64)   # per-slot
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.queue: List[Request] = []
        self.next_tokens = np.zeros((max_batch, 1), dtype=np.int32)

        self.sched_cache = ScheduleCache(max_entries=schedule_cache_entries)
        self.metrics.gauge_fn(
            "schedule_cache_hit_rate",
            lambda: self.sched_cache.stats.hit_rate,
            help="stream-K schedule cache hit rate",
        )

        # bucketed admission prefill: pad prompts up to canonical bucket
        # lengths so distinct prompt lengths stop costing one XLA compile
        # each (jit keys on the padded shape; true length is runtime).
        # Recurrent stages would scan pad tokens into their state — those
        # architectures keep the exact-length path.
        self.bucket_prefill = all(
            kind in ("attn", "win", "xattn")
            for pattern, _ in cfg.stages
            for kind in pattern
        )
        self._prefill_shapes: set = set()     # distinct padded lengths seen
        # a Scheduler can redirect preempted requests into its own queue
        # instead of the engine-local one
        self.preempt_sink = None

        self._jit_decode_paged = jax.jit(self._decode_fn_paged)
        self._jit_prefill_slot = jax.jit(
            self._prefill_fn, static_argnames=("plen",)
        )
        self._jit_prefill_bucketed = jax.jit(self._prefill_fn_bucketed)
        self._jit_prefill_chunks = jax.jit(
            _bind(_chunk_prefill_step, cfg=cfg),
            static_argnames=("backend", "sched", "interpret"),
            donate_argnames=("cache",),
        )
        self._jit_admit_paged = jax.jit(
            _bind(_write_slot_paged, cfg=cfg, page_size=self.tile),
            donate_argnums=(0,),
        )
        self._jit_kernel_step_paged = jax.jit(
            _bind(_kernel_decode_step_paged, cfg=cfg),
            static_argnames=("backend", "sched", "num_splits", "fused",
                             "interpret"),
            donate_argnames=("cache",),
        )
        self._jit_kernel_step_cascade = jax.jit(
            _bind(_kernel_decode_step_cascade, cfg=cfg),
            static_argnames=("csched", "fused", "interpret"),
            donate_argnames=("cache",),
        )
        self._jit_copy_page = jax.jit(
            _bind(_copy_page, cfg=cfg), donate_argnums=(0,)
        )
        self._jit_fill_page = jax.jit(
            _bind(_fill_page, cfg=cfg), donate_argnums=(0,)
        )
        self._jit_screen = jax.jit(_screen_logits)

        # speculative (draft-verify) decode: one verify sweep scores k
        # drafts per slot. Requires the chunked-prefill machinery (an
        # all-pooled-KV architecture) — the verify step IS a chunk step
        # whose "chunk" is [last token, k drafts].
        spec = config.spec
        self.spec_k = int(spec.k) if spec.enabled else 0
        self.proposer = spec.proposer
        if self.spec_k:
            if spec.k < 1:
                raise ValueError(f"SpecConfig.k must be >= 1, got {spec.k}")
            if not self.supports_chunked_prefill():
                raise ValueError(
                    "speculative decode runs the multi-row verify step "
                    "through the chunked-prefill kernels — requires "
                    "an all-'attn' architecture "
                    "(see supports_chunked_prefill)"
                )
            if self.proposer is None:
                self.proposer = NGramProposer()
            self.metrics.gauge_fn(
                "engine_spec_accept_rate",
                lambda: (
                    self.stats.spec_accepted_tokens
                    / max(1, self.stats.spec_draft_tokens)
                ),
                help="accepted / proposed draft tokens (cumulative)",
            )
        self._jit_spec_verify = jax.jit(
            _bind(_spec_verify_step, cfg=cfg),
            static_argnames=("backend", "sched", "interpret"),
            donate_argnames=("cache",),
        )

    # ------------------------------------------------------------- schedule
    def _tick_schedule(self, ctx_lens=None) -> LeanSchedule:
        """The (cached) stream-K schedule for this tick's ragged workload:
        every slot attends over its context plus the token being written,
        clamped to cache capacity. Built over ALL slots (the kernel sees the
        full batch; idle and masked-out slots contribute one masked tile)."""
        s_pad = self.cache_len + ((-self.cache_len) % self.tile)
        ctx = self.ctx_lens if ctx_lens is None else ctx_lens
        lens = np.minimum(ctx + 1, self.cache_len)
        with self.tracer.span("schedule_build") as sp:
            sched = self.sched_cache.get(
                lens.tolist(), self.cfg.n_kv_heads, self.tile,
                self.num_workers, max_len=s_pad,
            )
            if sp:
                sp.annotate(**sched.work_summary())
        return sched

    # ------------------------------------------------------------- jit fns
    def _decode_fn_paged(self, params, cache, tokens, ctx_lens, page_tbl):
        # ragged decode: per-slot context lengths drive RoPE positions,
        # cache write offsets, and attention masks
        cur = jnp.max(ctx_lens)
        logits, new_cache = decode_step(
            params, self.cfg, cache, tokens, cur, ctx_lens=ctx_lens,
            page_tbl=page_tbl,
        )
        return logits, new_cache

    def _prefill_fn(self, params, tokens, plen):
        logits, cache, cur = prefill(
            params, self.cfg, tokens, cache_len=self.cache_len
        )
        return logits, cache

    def _prefill_fn_bucketed(self, params, tokens, plen):
        # tokens padded to a canonical bucket; plen is a RUNTIME scalar —
        # the jit key is the padded shape, so compiles stay O(log cache_len)
        logits, cache, cur = prefill(
            params, self.cfg, tokens, cache_len=self.cache_len, true_len=plen
        )
        return logits, cache

    def _run_prompt_prefill(self, prompt: np.ndarray):
        """Whole-prompt prefill -> (last-position logits, 1-slot cache).
        Bucketed (padded shape + runtime length) when the architecture
        allows it; exact static-length trace otherwise."""
        plen = len(prompt)
        if not self.bucket_prefill:
            toks = jnp.asarray(np.asarray(prompt)[None, :], jnp.int32)
            self._track_prefill_shape(plen)
            return self._jit_prefill_slot(self.params, toks, plen=plen)
        pad_len = bucket_length(plen, self.tile, max_len=self.cache_len)
        toks = np.zeros((1, pad_len), dtype=np.int32)
        toks[0, :plen] = np.asarray(prompt)
        self._track_prefill_shape(pad_len)
        return self._jit_prefill_bucketed(
            self.params, jnp.asarray(toks), jnp.asarray(plen, jnp.int32)
        )

    def _track_prefill_shape(self, padded_len: int):
        self._prefill_shapes.add(int(padded_len))
        self.stats.prefill_compiles = len(self._prefill_shapes)

    # ------------------------------------------------------------- public
    def submit(self, req: Request):
        self.queue.append(req)

    def free_slots(self) -> List[int]:
        return [s for s in range(self.max_batch) if self.slot_req[s] is None]

    def _check_fits_pool(self, req: Request):
        """A request whose minimum working set (prompt pages + the first
        decode write) exceeds the whole pool can NEVER be served — failing
        fast beats the silent admit/preempt livelock waiting for pages that
        cannot materialize. Likewise a prompt beyond one slot's page-table
        capacity: chunked appends would wrap onto the last page and corrupt
        earlier KV, so it is rejected outright."""
        plen = len(req.prompt)
        if plen > self.pages_per_slot * self.tile:
            # PoisonError (a RuntimeError): the request itself can never
            # succeed — no amount of retry/backoff changes its size
            raise PoisonError(
                f"request uid={req.uid}: {plen}-token prompt exceeds the "
                f"per-slot KV capacity ({self.pages_per_slot} pages x "
                f"{self.tile} tokens) — raise cache_len or truncate"
            )
        min_pages = min(self.pages_per_slot, plen // self.tile + 1)
        if min_pages > self.pool.usable_pages:
            raise PoisonError(
                f"request uid={req.uid} needs {min_pages} KV "
                f"pages ({plen}-token prompt @ page_size "
                f"{self.tile}) but the pool holds only "
                f"{self.pool.usable_pages} usable pages — "
                "raise num_pages or shorten the prompt"
            )

    def _pool_alloc(self, seq, n: int):
        """Pool allocation with radix-cache backpressure: on exhaustion,
        evict LRU unreferenced prefix-cache leaves and retry once. Cached
        pages are *elastic* capacity — live requests always win.

        Fault point 'page_alloc': an injected failure looks exactly like
        pool exhaustion (returns None), so every caller exercises its real
        retry/preempt/backoff path, not a test-only branch."""
        if self.faults is not None and self.faults.fire("page_alloc"):
            return None
        got = self.pool.alloc(seq, n)
        if got is None and self.prefix_cache is not None:
            need = n - self.pool.num_free
            if self.prefix_cache.evict(need) > 0 or self.pool.num_free >= n:
                got = self.pool.alloc(seq, n)
        return got

    # --------------------------------------------------------- prefix sharing
    def attach_prefix(self, slot: int, prompt) -> int:
        """Map the longest cached prefix of ``prompt`` into ``slot``'s page
        table (refcount-shared, zero recompute) and return the number of
        prompt tokens it covers — the caller starts chunked prefill at that
        offset. The match is capped at ``len(prompt) - 1`` so at least one
        token always runs through the model (the first-token logits must be
        computed, not recalled). No-op (returns 0) without a prefix cache
        or on a slot that already has pages."""
        if self.prefix_cache is None:
            return 0
        if self.pool.holds(slot) or self.ctx_lens[slot] != 0:
            raise RuntimeError(
                f"attach_prefix on slot {slot} with existing pages/context"
            )
        prompt = np.asarray(prompt)
        plen = len(prompt)
        match = self.prefix_cache.match(prompt.tolist())
        matched = min(match.matched_tokens, plen - 1)
        if matched <= 0:
            return 0
        keep = -(-matched // self.tile)
        pages = match.pages[:keep]
        self.pool.share(slot, pages)
        self.page_tbl[slot, :keep] = pages
        self._slot_shared_tiles[slot] = set(range(keep))
        self._slot_prefix_full[slot] = matched // self.tile
        self.stats.prefix_matched_tokens += matched
        self.stats.prefix_attach_count += 1
        return matched

    def _cow_tile(self, slot: int, t: int) -> bool:
        """Copy-on-write logical tile ``t`` of ``slot`` before a KV write
        lands in a shared page: clone the page device-side onto a fresh one,
        swap the table entry, release the share. Returns False (state
        unchanged) when no page can be allocated right now.

        Fault point 'cow_clone': an injected failure mimics the
        alloc-failed outcome (False, nothing mutated) — the caller's
        preempt/retry handling is what gets exercised."""
        if self.faults is not None and self.faults.fire("cow_clone"):
            return False
        old = int(self.page_tbl[slot, t])
        got = self._pool_alloc(slot, 1)
        if got is None:
            return False
        new = got[0]
        with self.tracer.span("cow", slot=slot, tile=t), _quiet_donation():
            self.cache = self._jit_copy_page(
                self.cache, jnp.asarray(old, jnp.int32),
                jnp.asarray(new, jnp.int32),
            )
        self.page_tbl[slot, t] = new
        self.pool.release_pages(slot, [old])
        self._slot_shared_tiles[slot].discard(t)
        if t < self._slot_prefix_full[slot]:
            self._slot_prefix_full[slot] = t
        self.stats.cow_copies += 1
        return True

    def _cow_for_writes(self, slot: int, start: int, upto: int) -> bool:
        """CoW every shared tile that KV writes for positions
        ``[start, upto)`` would touch."""
        shared = self._slot_shared_tiles[slot]
        if not shared or upto <= start:
            return True
        for t in range(start // self.tile, (upto - 1) // self.tile + 1):
            if t in shared and not self._cow_tile(slot, t):
                return False
        return True

    def admit_blocking(self, req: Request, slot: int) -> bool:
        """Classic admission: whole-prompt prefill into ``slot``, cache row
        written, first token sampled. Returns False (engine unchanged) when
        the pool cannot currently hold the prompt. Does NOT touch the
        engine queue — callers (``_admit`` or a Scheduler) own queueing."""
        with self.tracer.span(
            "admit", uid=req.uid, prompt_tokens=len(req.prompt)
        ):
            return self._admit_blocking_inner(req, slot)

    def _admit_blocking_inner(self, req: Request, slot: int) -> bool:
        plen = len(req.prompt)
        self._check_fits_pool(req)
        # pages allocate lazily: admission takes only what the prompt
        # needs, decode grows page-by-page
        n = max(1, -(-plen // self.tile))
        pages = self._pool_alloc(slot, n)
        if pages is None:
            return False                # pool exhausted; retry next tick
        self.page_tbl[slot, :n] = pages
        self.slot_req[slot] = req
        logits, cache1 = self._run_prompt_prefill(req.prompt)
        # copy slot-0 of the fresh cache into our pages and slot rows
        with _quiet_donation():
            self.cache = self._jit_admit_paged(
                self.cache, cache1,
                jnp.asarray(pages, jnp.int32),
                jnp.asarray(slot, jnp.int32),
            )
        self.ctx_lens[slot] = plen
        nxt = int(jnp.argmax(logits[0]))
        req.generated.append(nxt)
        self.next_tokens[slot, 0] = nxt
        self.stats.prefills += 1
        return True

    def _admit(self):
        for slot in range(self.max_batch):
            if self.slot_req[slot] is None and self.queue:
                if not self.admit_blocking(self.queue[0], slot):
                    break               # pool exhausted; retry next tick
                self.queue.pop(0)

    # --------------------------------------------------------- chunked prefill
    def supports_chunked_prefill(self) -> bool:
        """Chunked prefill streams prompt pieces straight into the page
        pool (the pool + page tables ARE the staging area) — it needs an
        architecture whose whole prompt state lives in pooled
        global-attention KV."""
        return _cfg_supports_chunked(self.cfg)

    def claim_slot(self, req: Request) -> Optional[int]:
        """Reserve a free slot for ``req`` without prefilling anything —
        the entry point of the PREFILLING lifecycle state. The slot starts
        at context 0 with an all-null page table row; chunk pages allocate
        lazily per chunk (:meth:`ensure_chunk_pages`)."""
        self._check_fits_pool(req)
        for slot in range(self.max_batch):
            if self.slot_req[slot] is None:
                self.slot_req[slot] = req
                self.ctx_lens[slot] = 0
                self.page_tbl[slot, :] = 0
                self._slot_shared_tiles[slot] = set()
                self._slot_prefix_full[slot] = 0
                return slot
        return None

    def ensure_chunk_pages(
        self, slot: int, upto_tokens: int, write_from: Optional[int] = None
    ) -> bool:
        """Grow ``slot``'s page list to cover prompt positions
        ``[0, upto_tokens)``. With ``write_from`` given (the chunk's start
        offset), shared pages the chunk's KV writes would land in are
        copy-on-written first — a radix partial-page match hands the slot
        an immutable page that its own appends must not touch. Returns
        False (pool unchanged beyond failed-alloc stats) when the pool
        cannot serve it right now."""
        need = min(-(-int(upto_tokens) // self.tile), self.pages_per_slot)
        have = self.pool.count(slot)
        if have < need:
            got = self._pool_alloc(slot, need - have)
            if got is None:
                return False
            self.page_tbl[slot, have:need] = got
        if write_from is not None:
            return self._cow_for_writes(
                slot, int(write_from), int(upto_tokens)
            )
        return True

    def prefill_chunks_tick(
        self, work: List[tuple], pack_width: int, chunk_cap: int
    ) -> np.ndarray:
        """Run one packed chunked-prefill step.

        ``work``: up to ``pack_width`` tuples ``(slot, chunk_tokens, off)``
        — each one chunk (``len <= chunk_cap``) of one PREFILLING slot's
        prompt, whose pages already cover ``off + len`` tokens
        (:meth:`ensure_chunk_pages`). KV appends directly into the page
        pool through each slot's table row; no dense staging, no
        copy-on-admit. Returns the (pack_width,) greedy next-token ids at
        each row's last valid position — rows that finished their prompt
        use theirs as the request's first token (argmax runs on device;
        the host sync moves ints, not vocab-wide logits). Pack geometry is
        static (pad rows are masked), so one trace per (pack, chunk,
        schedule-signature) serves the whole run.
        """
        if not self.supports_chunked_prefill():
            raise RuntimeError(
                "chunked prefill requires an all-'attn' architecture "
                "(see supports_chunked_prefill)"
            )
        if len(work) > pack_width:
            raise ValueError(f"{len(work)} chunks > pack width {pack_width}")
        N, C = pack_width, chunk_cap
        toks = np.zeros((N, C), dtype=np.int32)
        offs = np.zeros(N, dtype=np.int32)
        lens = np.zeros(N, dtype=np.int32)
        tbls = np.zeros((N, self.pages_per_slot), dtype=np.int32)
        visible = [1] * N
        for i, (slot, chunk, off) in enumerate(work):
            chunk = np.asarray(chunk)
            if len(chunk) > C:
                raise ValueError(f"chunk of {len(chunk)} tokens > cap {C}")
            toks[i, : len(chunk)] = chunk
            offs[i] = off
            lens[i] = len(chunk)
            tbls[i] = self.page_tbl[slot]
            visible[i] = max(1, int(off) + len(chunk))
        # chunk schedules ride the same bucketed cache lattice as decode;
        # only the lean backend consumes one — keying ref/fixed on it
        # would retrace their whole chunk step per schedule signature
        n_tokens = int(lens.sum())
        sp = self.tracer.span(
            "prefill_chunk", chunks=len(work), tokens=n_tokens
        )
        with sp:
            sched = None
            if self.attn_backend == "lean":
                with self.tracer.span("engine.schedule_build"):
                    sched = make_chunk_schedule(
                        visible, self.cfg.n_kv_heads, self.tile,
                        self.num_workers,
                        max_len=self.pages_per_slot * self.tile,
                        cache=self.sched_cache,
                    )
                if sp:
                    sp.annotate(**sched.work_summary())
            with self.tracer.span("engine.inputs"):
                args = [jnp.asarray(a) for a in (toks, offs, lens, tbls)]
            with self.tracer.span("engine.dispatch"), _quiet_donation():
                next_tok, self.cache = self._jit_prefill_chunks(
                    self.params, self.cache, *args,
                    backend=self.attn_backend, sched=sched,
                    interpret=self.interpret,
                )
            if sp:
                t0 = time.perf_counter()
                jax.block_until_ready(next_tok)
                sp.add_sync(time.perf_counter() - t0)
            with self.tracer.span("engine.token_wait"):
                next_tok = np.asarray(next_tok)
        self.stats.chunk_prefills += len(work)
        self.stats.prefill_tokens += n_tokens
        self._log_tick_tokens(self.stats.tick_prefill_tokens, n_tokens)
        return next_tok

    # ------------------------------------------------------------ page mgmt
    def _ensure_decode_pages(self, active: List[int]) -> List[int]:
        """Grow each active slot's page list to cover this tick's KV write.
        A slot the pool cannot serve is preempted (pages freed, request
        requeued for recompute-resume) — the pool's analogue of running out
        of batch slots, except it only triggers when the pool is
        oversubscribed."""
        alive = []
        for s in active:
            ctx = int(self.ctx_lens[s])
            need = min(ctx // self.tile + 1, self.pages_per_slot)
            have = self.pool.count(s)
            if have < need:
                got = self._pool_alloc(s, need - have)
                if got is None:
                    self._preempt(s)
                    continue
                self.page_tbl[s, have:need] = got
            # this tick's token writes at position ctx — if that lands in a
            # shared (radix-matched) page, copy-on-write it first
            wt = min(ctx, self.pages_per_slot * self.tile - 1) // self.tile
            if wt in self._slot_shared_tiles[s] and not self._cow_tile(s, wt):
                self._preempt(s)
                continue
            alive.append(s)
        return alive

    def _preempt(self, slot: int):
        """Evict a slot: return its pages to the pool and requeue the
        request to resume by recompute (prompt extended with everything
        generated so far, so the next prefill rebuilds its exact state).
        With a ``preempt_sink`` registered (the Scheduler), the request
        goes there instead of the engine-local queue."""
        req = self.slot_req[slot]
        if self.pool.holds(slot):
            # shares release (refcount - 1); only the slot's private pages
            # actually return to the free list
            self.pool.free_seq(slot, eviction=True)
        self.page_tbl[slot, :] = 0
        self.slot_req[slot] = None
        self.ctx_lens[slot] = 0
        self._slot_shared_tiles[slot] = set()
        self._slot_prefix_full[slot] = 0
        self._reset_guard(slot)
        fresh = req.generated[req.folded :]
        req.prompt = np.concatenate(
            [np.asarray(req.prompt),
             np.asarray(fresh, dtype=np.asarray(req.prompt).dtype)]
        )
        req.folded = len(req.generated)
        if self.preempt_sink is not None:
            self.preempt_sink(req)
        else:
            self.queue.insert(0, req)
        self.stats.preemptions += 1
        self.flight.record("preempt", slot=slot, uid=req.uid,
                           tick=int(self.stats.ticks))
        self.tracer.request_event(req.uid, "PREEMPTED", slot=slot)

    def preempt_slot(self, slot: int):
        """Public eviction hook for schedulers (pool-pressure deadlock
        breaking): works for both DECODING and PREFILLING occupants —
        a mid-prefill request simply restarts its prompt on re-admission
        (its ``generated`` list is still empty)."""
        if self.slot_req[slot] is None:
            raise ValueError(f"slot {slot} is idle")
        self._preempt(slot)

    def _donate_to_prefix_cache(self, slot: int, req: Optional[Request]):
        """Offer a finishing slot's KV pages to the radix cache before its
        refs are released — cached blocks survive the release and serve
        future prompts starting with the same tokens."""
        if self.prefix_cache is None or req is None:
            return
        n_tok = int(self.ctx_lens[slot])
        if n_tok <= 0 or not self.pool.holds(slot):
            return
        fresh = req.generated[req.folded :]
        toks = np.concatenate(
            [np.asarray(req.prompt, dtype=np.int64),
             np.asarray(fresh, dtype=np.int64)]
        )[:n_tok]
        n_tok = min(n_tok, self.pool.count(slot) * self.tile)
        toks = toks[:n_tok]
        if len(toks) == 0:
            return
        pages = self.page_tbl[slot, : -(-len(toks) // self.tile)].tolist()
        # crash-consistent donation: insert() is all-or-nothing (it unwinds
        # its own partial trie growth on failure), so a mid-donation fault
        # costs only the cache entry — the finishing request still releases
        # cleanly and the trie/pool invariants hold
        try:
            self.prefix_cache.insert(toks.tolist(), pages)
        except Exception:
            self.stats.donation_aborts += 1

    def release_slot(self, slot: int):
        """Finish a slot: donate its prefix to the radix cache (when one is
        configured), release its page refs (shared pages survive under
        their other holders), and clear the slot state."""
        self._donate_to_prefix_cache(slot, self.slot_req[slot])
        self.slot_req[slot] = None
        self.ctx_lens[slot] = 0
        self._free_slot_pages(slot)
        self._reset_guard(slot)

    def _free_slot_pages(self, slot: int):
        if self.pool.holds(slot):
            self.pool.free_seq(slot)
        self.page_tbl[slot, :] = 0
        self._slot_shared_tiles[slot] = set()
        self._slot_prefix_full[slot] = 0

    def _cascade_grouping(self, active: List[int]):
        """Grouped cascade passes for this tick: the radix page *paths*
        of the active slots (their leading runs of full shared pages) are
        grouped at their longest common prefixes —
        :func:`~repro.serving.prefix_cache.lcp_group_passes` walks the
        compressed trie the paths induce, so slots matching 3 and 5 pages
        of one chain group at 3, and (multi-level) nested subsets stack
        one extra pass per trie level. ``cascade_grouping='identical'``
        keeps the v1 behavior (group only equal page runs) as the bench
        comparison baseline. Slots sharing with nobody are simply absent:
        they decode through their suffix walk alone."""
        paths = {}
        for s in active:
            npref = self._slot_prefix_full[s]
            if npref > 0:
                paths[s] = tuple(int(p) for p in self.page_tbl[s, :npref])
        if self.cascade_grouping == "identical":
            by_prefix: Dict[tuple, List[int]] = {}
            for s, p in paths.items():
                by_prefix.setdefault(p, []).append(s)
            return sorted(
                (tuple(sorted(m)), 0, len(p))
                for p, m in by_prefix.items() if len(m) >= 2
            )
        return lcp_group_passes(
            paths, multi_level=self.cascade_multi_level
        )

    def _cascade_fused_desc(self, csched, binding, fused: bool):
        """The fused merge descriptors for this tick, memoized on the
        (schedule geometry, binding content) pair — the guard keeps both
        stable across steady-state ticks, so the O(pieces x batch) host
        build runs once per regrouping, not once per tick. When the
        two-call path was selected the array is ignored by the kernel, so
        a cached zeros block of the right (static) shape rides along."""
        key = (
            fused, csched.signature, binding.members.tobytes(),
            binding.page_start.tobytes(), binding.prefix_pages.tobytes(),
        )
        cached = self.__dict__.get("_casc_desc")
        if cached is not None and cached[0] == key:
            return cached[1]
        if fused:
            desc = cascade_fused_descriptors(csched, binding)
        else:
            desc = np.zeros((7, csched.fused_grid_iters), dtype=np.int32)
        self._casc_desc = (key, desc)
        return desc

    def _cascade_schedule_for_tick(self, active: List[int], ctx_np):
        """The (schedule, binding) for this tick's cascade decode — or
        ``(None, None)`` when no grouped pass exists or the stability
        guard is still holding the path back. The guard keys on the
        grouping *structure* (membership + page ranges), not on lengths:
        a grouping must survive ``cascade_stable_ticks`` consecutive
        ticks of admission/finish churn before the engine pays the
        (possible) retrace of entering the cascade path."""
        with self.tracer.span("cascade_group") as sp:
            csched, binding = self._cascade_schedule_inner(active, ctx_np)
            if sp:
                sp.annotate(
                    engaged=csched is not None,
                    stable_ticks=self._casc_stable,
                )
        return csched, binding

    def _cascade_schedule_inner(self, active: List[int], ctx_np):
        passes = self._cascade_grouping(active)
        if not passes:
            self._casc_key = None
            self._casc_stable = 0
            return None, None
        key = tuple(passes)
        if key == self._casc_key:
            self._casc_stable += 1
        else:
            self._casc_key = key
            self._casc_stable = 1
        if self._casc_stable < self.cascade_stable_ticks:
            self.stats.cascade_stability_skips += 1
            return None, None
        s_pad = self.cache_len + ((-self.cache_len) % self.tile)
        lens = np.minimum(ctx_np + 1, self.cache_len)
        csched, binding = self.sched_cache.get_cascade(
            lens.tolist(),
            [m for m, _, _ in passes],
            [c for _, _, c in passes],
            self.cfg.n_kv_heads, self.tile, self.num_workers,
            max_len=s_pad,
            page_starts=[s for _, s, _ in passes],
        )
        return csched, binding

    def tick(self) -> Dict[int, int]:
        """Admit + one decode step for all active slots. Returns
        {uid: new_token}."""
        self._admit()
        return self.decode_tick()

    def decode_tick(self, exclude=None) -> Dict[int, int]:
        """One decode step over the active slots. Returns {uid: new_token}
        — or, with speculative decode on, {uid: [tokens...]} (1 to k+1
        tokens per slot, variable per tick; see ``decode_token_width``).

        ``exclude`` masks slots out of this tick — the Scheduler passes its
        PREFILLING slots, whose pool pages hold a *partial* prompt that the
        decode step must neither read (context forced to 0, so their
        schedule segment is fully masked) nor write (their page-table rows
        are nulled for this call, routing the garbage token write to the
        reserved null page). The excluded slots' real page tables and
        progress are untouched.

        This wrapper owns the per-tick observability: the ``tick`` trace
        span, one flight-recorder event per tick, and — when the attached
        injector fired anywhere since the last dump, *including between
        ticks* (admission-time ``page_alloc``, prefill-time ``cow_clone``)
        — a postmortem dump (deduped against dumps the guard paths
        already wrote this tick).
        """
        self._tick_dumped = False
        t0 = time.perf_counter() if self.watchdog is not None else 0.0
        with self.tracer.span("tick"):
            out = self._decode_tick_inner(exclude)
        self.flight.record(
            "tick", tick=self.stats.ticks, emitted=len(out),
            active=sum(1 for r in self.slot_req if r is not None),
            queued=len(self.queue),
        )
        if (
            self.faults is not None
            and self.faults.total_fires > self._fires_dumped
        ):
            if not self._tick_dumped:
                self._flight_dump("fault-injected")
            self._fires_dumped = self.faults.total_fires
        # watchdog runs after the fault-dump block so its own postmortems
        # (reason "watchdog-<detector>") are additional to — and
        # distinguishable from — fault-hook-originated bundles
        if self.watchdog is not None:
            self.watchdog.on_tick((time.perf_counter() - t0) * 1e3)
        return out

    def _decode_tick_inner(self, exclude=None) -> Dict[int, int]:
        exclude = set(exclude) if exclude else set()
        if self.faults is not None and self.faults.enabled:
            self._fault_tick_hooks(exclude)
        active = [
            s for s in range(self.max_batch)
            if self.slot_req[s] and s not in exclude
        ]
        with self.tracer.span("engine.pages"):
            active = self._ensure_decode_pages(active)
        if self.guard_cfg is not None:
            self._run_audits()
        if not active:
            if self.guard_cfg is not None:
                self._update_degraded_gauge()
            return {}

        # speculative slots leave the single-token passes entirely: their
        # tick is one multi-row verify sweep. Ineligible slots (degraded,
        # near capacity, or starved of pages) fall back to the normal
        # passes below — per-slot, per-tick, with no mode switch.
        guard_on = self.guard_cfg is not None and self.guard_cfg.nan_guard
        spec_slots: List[int] = []
        if self.spec_k:
            spec_slots = self._spec_select(active)
        norm = [s for s in active if s not in spec_slots]

        # partition the batch by degraded-mode level: healthy slots stay
        # on the configured fast path (one pass, the common case is the
        # whole batch), quarantined slots re-decode in separate passes
        # down the fallback chain with everyone else masked out
        if self.guard_cfg is None or not any(
            self._slot_degrade[s] for s in norm
        ):
            passes = [(0, norm)] if norm else []
        else:
            by_lvl: Dict[int, List[int]] = {}
            for s in norm:
                by_lvl.setdefault(self._effective_level(s), []).append(s)
            passes = sorted(by_lvl.items())

        results = []
        for lvl, slots in passes:
            logits = self._decode_pass(lvl, slots, active, exclude)
            # the tick's one wait for the device
            with self.tracer.span("engine.token_wait"):
                if guard_on:
                    nxt, fin = self._jit_screen(logits)
                    results.append((slots, np.asarray(nxt), np.array(fin)))
                else:
                    results.append(
                        (slots, np.asarray(jnp.argmax(logits, axis=-1)), None)
                    )

        spec = (
            self._spec_verify(spec_slots, exclude, guard_on)
            if spec_slots else None
        )

        # fault point 'nan_output': flip one victim's finiteness verdict —
        # the guard reacts exactly as to a real non-finite logit row, with
        # no device-side corruption left behind
        if (
            guard_on
            and self.faults is not None
            and self.faults.fire("nan_output")
        ):
            for v in self.faults.choose(active):
                for slots, _, fin in results:
                    if v in slots:
                        fin[v] = False
                if spec is not None and v in spec[0]:
                    spec[2][v] = False

        with self.tracer.span("engine.emit"):
            return self._emit_tokens(results, guard_on, spec=spec)

    def _decode_pass_main(self, active: List[int], ctx_np, ptbl_np):
        """The engine's configured (level-0) decode path: cascade grouping
        when eligible, else the jitted kernel step. Updates ``self.cache``
        and returns the logits."""
        csched = binding = None
        grid_steps = 0      # the fused decode kernel's, when it runs
        if self.cascade and self.attn_backend == "lean":
            csched, binding = self._cascade_schedule_for_tick(active, ctx_np)
        # benches/diagnostics read the live per-slot suffix coverage here
        self._casc_binding = binding
        if csched is not None:
            # cascade decode: shared prefix runs walked once per grouped
            # pass; the membership-free schedule is the only static key
            self._note_schedule(csched.suffix_sched, "cascade")
            prefix_tbl, suffix_tbl = cascade_tables(ptbl_np, binding)
            fused = self.cascade_fused and cascade_uses_fused(
                csched, self.cfg.n_heads // self.cfg.n_kv_heads,
                self.cfg.head_dim, kv_elem_bytes=self.kv_elem_bytes,
            )
            fused_desc = self._cascade_fused_desc(csched, binding, fused)
            if csched.signature not in self._casc_signatures:
                self._casc_signatures.add(csched.signature)
                self.stats.cascade_retraces += 1
            with self.tracer.span("engine.inputs"):
                args = [jnp.asarray(a) for a in (
                    self.next_tokens, ctx_np.astype(np.int32), ptbl_np,
                    prefix_tbl, suffix_tbl, binding.members,
                    binding.prefix_lens, binding.seq_prefix_len, fused_desc,
                )]
            with self.tracer.span("engine.dispatch"), _quiet_donation():
                logits, self.cache = self._jit_kernel_step_cascade(
                    self.params, self.cache, *args,
                    csched=csched, fused=fused, interpret=self.interpret,
                )
            grouped = np.unique(binding.members[binding.members >= 0])
            self.stats.cascade_ticks += 1
            self.stats.cascade_fused_ticks += int(fused)
            self.stats.cascade_grouped_slots += len(grouped)
            self.stats.cascade_grouped_passes += int(
                (binding.members[:, 0] >= 0).sum()
            )
            self.stats.cascade_levels_max = max(
                self.stats.cascade_levels_max, binding.num_levels
            )
            self.stats.cascade_last = {
                "passes": int((binding.members[:, 0] >= 0).sum()),
                "grouped_slots": int(len(grouped)),
                "levels": int(binding.num_levels),
                "fused": bool(fused),
            }
        else:
            # ONE schedule build (cached) serves both the stats record and
            # the kernel step — nothing is derived twice per tick
            sched = self._tick_schedule(ctx_np)
            grid = self._note_schedule(sched, "fast")
            with self.tracer.span("engine.inputs"):
                tokens = jnp.asarray(self.next_tokens)
                ctx = jnp.asarray(ctx_np, jnp.int32)
                ptbl = jnp.asarray(ptbl_np)
            dispatch = self.tracer.span("engine.dispatch")
            if self.attn_backend == "ref":
                with dispatch:
                    logits, self.cache = self._jit_decode_paged(
                        self.params, self.cache, tokens, ctx, ptbl
                    )
            else:
                num_splits = fixed_split_factor(
                    int(sched.seg_len.max(initial=1)),
                    sched.num_segments, self.tile, self.num_workers,
                )
                if self.attn_backend == "lean":
                    fused = self.fused and decode_uses_fused(
                        sched, self.cfg.n_heads // self.cfg.n_kv_heads,
                        self.cfg.head_dim, kv_elem_bytes=self.kv_elem_bytes,
                    )
                    self.stats.fused_decode_ticks += int(fused)
                    grid_steps = grid["grid_steps"] if fused else 0
                with dispatch, _quiet_donation():
                    logits, self.cache = self._jit_kernel_step_paged(
                        self.params, self.cache, tokens, ctx, ptbl,
                        backend=self.attn_backend, sched=sched,
                        num_splits=num_splits, fused=self.fused,
                        interpret=self.interpret,
                    )
        self._grid_steps.set(grid_steps)
        return logits

    def _decode_pass(self, level, slots, active, exclude):
        """One decode pass over ``slots`` at fallback-chain position
        ``level`` (see :data:`guards.DEGRADE_LEVELS`). Slots outside the
        pass are masked exactly like ``exclude`` slots — context forced to
        0, page-table rows nulled — so the kernel neither reads their KV
        nor writes anywhere real; a level-0 slot's token KV written by an
        earlier pass this tick is never re-touched by a later pass.
        Level 0 is the configured path (cascade grouping included);
        levels 1/2 are the vanilla paged lean kernel fused / two-call;
        level 3 the pure-jnp paged oracle.

        Wrapped in the ``decode_kernel`` trace span; with tracing enabled
        the pass blocks on the logits inside the span so device-sync time
        is attributed here (a disabled tracer leaves dispatch async)."""
        sp = self.tracer.span(
            "decode_kernel", level=level, slots=len(slots),
        )
        with sp:
            logits = self._decode_pass_inner(level, slots, active, exclude)
            if sp:
                t0 = time.perf_counter()
                jax.block_until_ready(logits)
                sp.add_sync(time.perf_counter() - t0)
        return logits

    def _decode_pass_inner(self, level, slots, active, exclude):
        masked = exclude | (set(active) - set(slots))
        ctx_np = self.ctx_lens.copy()
        ptbl_np = self.page_tbl
        if masked:
            ptbl_np = self.page_tbl.copy()
            for s in masked:
                ctx_np[s] = 0
                ptbl_np[s, :] = 0
        if level == 0:
            return self._decode_pass_main(slots, ctx_np, ptbl_np)
        with self.tracer.span("engine.inputs"):
            tokens = jnp.asarray(self.next_tokens)
            ctx = jnp.asarray(ctx_np, jnp.int32)
            ptbl = jnp.asarray(ptbl_np)
        if level >= 3 or self.attn_backend != "lean":
            with self.tracer.span("engine.dispatch"):
                logits, self.cache = self._jit_decode_paged(
                    self.params, self.cache, tokens, ctx, ptbl
                )
            return logits
        sched = self._tick_schedule(ctx_np)
        if self.tracer.enabled:
            # fallback passes annotate cost meta but skip the schedule
            # log — stats.schedules stays a fast-path record
            self.tracer.annotate(
                path="fallback", **self._schedule_cost(sched)
            )
        num_splits = fixed_split_factor(
            int(sched.seg_len.max(initial=1)),
            sched.num_segments, self.tile, self.num_workers,
        )
        with self.tracer.span("engine.dispatch"), _quiet_donation():
            logits, self.cache = self._jit_kernel_step_paged(
                self.params, self.cache, tokens, ctx, ptbl,
                backend="lean", sched=sched, num_splits=num_splits,
                fused=(level == 1), interpret=self.interpret,
            )
        return logits

    def _effective_level(self, s: int) -> int:
        """A slot's fallback rung for this tick. Non-lean backends have no
        intermediate lean rungs — any degradation goes straight to the
        jnp oracle."""
        lvl = self._slot_degrade[s]
        if lvl == 0:
            return 0
        if self.attn_backend != "lean":
            return 3
        return lvl

    # ------------------------------------------------------------ speculative
    def decode_token_width(self) -> int:
        """Most tokens one decode tick can emit per slot — k+1 when
        speculative decode is on, 1 otherwise. Tick composers (the
        Scheduler) charge this against their token budget."""
        return self.spec_k + 1 if self.spec_k else 1

    def _spec_select(self, active: List[int]) -> List[int]:
        """The slots running a verify sweep this tick. A slot is eligible
        when it is healthy (level 0), its context leaves room for the full
        R = k+1 block, and its pages (grown + copy-on-written here, exactly
        like a prefill chunk's) can cover the block's KV writes. Everyone
        else falls back to the single-token passes for this tick."""
        R = self.spec_k + 1
        cap = min(self.cache_len, self.pages_per_slot * self.tile)
        out = []
        for s in active:
            if self.guard_cfg is not None and self._slot_degrade[s]:
                continue
            ctx = int(self.ctx_lens[s])
            if ctx + R > cap:
                continue
            if not self.ensure_chunk_pages(s, ctx + R, write_from=ctx):
                continue          # pool pressure; plain decode this tick
            out.append(s)
        return out

    def _spec_verify(self, slots: List[int], exclude, guard_on: bool):
        """Run the verify sweep for ``slots``: one chunk-shaped forward
        whose per-slot "chunk" is ``[last emitted token, drafts...]``,
        scattered at positions ``ctx .. ctx+len-1``. Slots outside the
        sweep are masked chunk-style (offs/lens 0, page-table rows nulled)
        so their KV is neither read nor written. Returns
        ``(slots, rows (B, R), finite_or_None, drafts)`` for
        :meth:`_emit_tokens` — rejected drafts need no undo: their KV rows
        sit beyond the committed ``ctx_lens`` and are overwritten by the
        next sweep through the same trimmed page-table tail."""
        R = self.spec_k + 1
        N = self.max_batch
        toks = np.zeros((N, R), dtype=np.int32)
        offs = np.zeros(N, dtype=np.int32)
        lens = np.zeros(N, dtype=np.int32)
        drafts: Dict[int, List[int]] = {}
        for s in slots:
            req = self.slot_req[s]
            d = [int(t) for t in self.proposer.propose(req, self.spec_k)]
            d = d[: self.spec_k]
            drafts[s] = d
            ctx = int(self.ctx_lens[s])
            toks[s, 0] = self.next_tokens[s, 0]
            if d:
                toks[s, 1 : 1 + len(d)] = d
            offs[s] = ctx
            lens[s] = 1 + len(d)
        tbls = self.page_tbl.copy()
        for s in range(N):
            if s not in drafts:
                tbls[s, :] = 0
        sched = None
        if self.attn_backend == "lean":
            spec_ctx = [
                int(self.ctx_lens[s]) if s in drafts else 0
                for s in range(N)
            ]
            with self.tracer.span("engine.schedule_build"):
                sched = make_spec_schedule(
                    spec_ctx, R, self.cfg.n_kv_heads, self.tile,
                    self.num_workers,
                    max_len=self.pages_per_slot * self.tile,
                    cache=self.sched_cache,
                )
        sp = self.tracer.span("spec_verify", slots=len(slots), rows=R)
        with sp:
            with self.tracer.span("engine.inputs"):
                args = [jnp.asarray(a) for a in (toks, offs, lens, tbls)]
            with self.tracer.span("engine.dispatch"), _quiet_donation():
                rows, fin, self.cache = self._jit_spec_verify(
                    self.params, self.cache, *args,
                    backend=self.attn_backend, sched=sched,
                    interpret=self.interpret,
                )
            if sp:
                t0 = time.perf_counter()
                jax.block_until_ready(rows)
                sp.add_sync(time.perf_counter() - t0)
            with self.tracer.span("engine.token_wait"):
                rows = np.asarray(rows)
                fin = np.array(fin) if guard_on else None
        return slots, rows, fin, drafts

    def _emit_spec_tokens(self, spec, out, guard_on: bool, cap: int) -> int:
        """Acceptance-rejection + emission for this tick's verify sweep.
        Greedy accept: draft ``i+1`` stands iff row ``i``'s argmax equals
        it and every earlier draft stood — so the emitted stream is
        token-identical to plain greedy decode. Each slot emits its
        accepted drafts plus the one bonus token from the first
        disagreeing row; ``ctx_lens`` advances by exactly the emission
        count, which is the whole rollback story (pages stay allocated,
        the page-table tail past the new context is simply dead)."""
        slots, rows, finite, drafts = spec
        n_emitted = 0
        for s in slots:
            req = self.slot_req[s]
            if finite is not None and not bool(finite[s]):
                # quarantine, chunk-style: nothing emitted, context does
                # not advance — the garbage KV the sweep wrote sits beyond
                # ctx_lens, invisible to every masked read
                self._on_bad_slot(s)
                continue
            d = drafts[s]
            a = 0
            while a < len(d) and int(rows[s, a]) == d[a]:
                a += 1
            ctx = int(self.ctx_lens[s])
            rem = req.max_new_tokens - len(req.generated)
            e = min(a + 1, rem, cap - 1 - ctx)
            e = max(e, 1)
            emitted = [int(rows[s, i]) for i in range(e)]
            req.generated.extend(emitted)
            self.next_tokens[s, 0] = emitted[-1]
            self.ctx_lens[s] += e
            out[req.uid] = emitted
            n_emitted += e
            self.stats.tokens_generated += e
            self.stats.spec_draft_tokens += len(d)
            self.stats.spec_accepted_tokens += a
            if req.done or self.ctx_lens[s] >= cap - 1:
                self.release_slot(s)
        self.stats.spec_ticks += 1
        return n_emitted

    def _emit_tokens(self, results, guard_on: bool, spec=None) -> Dict[int, int]:
        """Token emission + guard bookkeeping over this tick's pass
        results (``[(slots, next_tokens, finite_or_None), ...]``), plus
        the verify sweep's when one ran. In speculative mode every value
        in the returned dict is a ``List[int]`` (single-token slots emit
        one-element lists)."""
        # context cap: the slot's capacity, and also the whole pool — a
        # context allowed past usable_pages * tile could never be
        # re-admitted after a recompute-resume preemption (its regrown
        # prompt would fail the pool fit check), so it is finished here,
        # with its final token, like any other capacity cut
        cap = min(self.cache_len, self.pool.usable_pages * self.tile)
        out = {}
        n_emitted = 0
        for slots, next_all, finite in results:
            for s in slots:
                req = self.slot_req[s]
                if finite is not None and not bool(finite[s]):
                    # quarantine: no token, context does not advance — the
                    # slot re-executes this same step next tick, one level
                    # further down the fallback chain
                    self._on_bad_slot(s)
                    continue
                if guard_on and self._slot_degrade[s]:
                    self._on_good_slot(s)
                nxt = int(next_all[s])
                req.generated.append(nxt)
                self.next_tokens[s, 0] = nxt
                self.ctx_lens[s] += 1
                out[req.uid] = [nxt] if self.spec_k else nxt
                n_emitted += 1
                self.stats.tokens_generated += 1
                if req.done or self.ctx_lens[s] >= cap - 1:
                    # finished sequences release their pages immediately
                    # (after offering their prefix to the radix cache) —
                    # this is what lets the pool admit more in-flight work
                    # than worst-case per-slot reservations could hold
                    self.release_slot(s)
        if spec is not None:
            n_emitted += self._emit_spec_tokens(spec, out, guard_on, cap)
        self.stats.ticks += 1
        self._log_tick_tokens(self.stats.tick_decode_tokens, n_emitted)
        self.stats.schedule_cache = self.sched_cache.stats.as_dict()
        self.stats.kv_pool = self.pool.as_dict()
        if self.prefix_cache is not None:
            self.stats.prefix_cache = self.prefix_cache.as_dict()
        if self.guard_cfg is not None:
            self._update_degraded_gauge()
        if self.faults is not None:
            self.stats.faults = self.faults.as_dict()
        return out

    # --------------------------------------------------------- self-healing
    def _flight_dump(self, reason: str, **extra) -> dict:
        """Snapshot the flight ring into a postmortem bundle (written to
        the recorder's ``dump_dir`` when one is configured). Marks the
        tick as dumped so the injected-fault fallback dump in
        :meth:`decode_tick` doesn't double up."""
        ctx = {
            "tick": int(self.stats.ticks),
            "degraded_slots": self.degraded_gauge.value,
            **extra,
        }
        if self.faults is not None:
            ctx["fault_fires"] = self.faults.total_fires
        self._tick_dumped = True
        return self.flight.dump(reason, extra=ctx)

    def _on_bad_slot(self, s: int):
        """A tick produced non-finite logits for slot ``s``: escalate one
        level down the fallback chain, or — once the chain is exhausted for
        ``poison_after`` consecutive ticks — poison the slot."""
        gc = self.guard_cfg
        self.stats.nan_ticks += 1
        self.flight.record("nan_tick", slot=s, tick=int(self.stats.ticks))
        self._slot_good[s] = 0
        if self._slot_degrade[s] < gc.max_degrade:
            self._slot_degrade[s] += 1
            self._slot_bad[s] = 0
            self.stats.degrade_escalations += 1
            self._degrade_cause.labels(cause="nan_guard").inc()
            self.flight.record(
                "degrade", slot=s, level=self._slot_degrade[s],
                cause="nan_guard",
                backend=DEGRADE_LEVELS[
                    min(self._slot_degrade[s], len(DEGRADE_LEVELS) - 1)
                ],
            )
            self._flight_dump("degrade", slot=s,
                              level=self._slot_degrade[s])
            return
        self._slot_bad[s] += 1
        if self._slot_bad[s] >= gc.poison_after:
            self._poison_slot(s)

    def _on_good_slot(self, s: int):
        """A degraded slot produced a finite token: after ``heal_after``
        consecutive clean ticks, step one level back toward the fast
        path."""
        self._slot_bad[s] = 0
        self._slot_good[s] += 1
        if self._slot_good[s] >= self.guard_cfg.heal_after:
            self._slot_degrade[s] -= 1
            self._slot_good[s] = 0
            self.stats.degrade_heals += 1

    def _poison_slot(self, s: int):
        """Bottom-of-chain recovery: the slot's KV is presumed corrupt.
        Scrub its private pages (zero-fill — recycled NaN pages could
        poison an innocent slot through masked-tile reads), withdraw its
        shared prefix pages from the radix cache (they are upstream of the
        corruption), and preempt: recompute-resume rebuilds clean KV from
        the prompt, which is the recovery that works when no alternate
        kernel can."""
        shared = self._slot_shared_tiles[s]
        for t in range(self.pool.count(s)):
            if t in shared:
                continue
            page = int(self.page_tbl[s, t])
            if page:
                with _quiet_donation():
                    self.cache = self._jit_fill_page(
                        self.cache, jnp.asarray(page, jnp.int32),
                        jnp.asarray(0.0, jnp.float32),
                    )
        if self.prefix_cache is not None and shared:
            self.prefix_cache.invalidate_pages(
                {int(self.page_tbl[s, t]) for t in shared}
            )
        self.stats.poisoned_slots += 1
        self.flight.record(
            "poison", slot=s, tick=int(self.stats.ticks),
            scrubbed_pages=self.pool.count(s) - len(shared),
        )
        self._preempt(s)
        self._flight_dump("poison", slot=s)

    def force_degrade(self, levels: int = 1, cause: str = "watchdog",
                      slots: Optional[List[int]] = None) -> int:
        """Explicit, *observable* degrade: push active slots ``levels``
        steps down the fallback chain, recording the cause (a
        ``guards.DEGRADE_CAUSES`` member) on the flight event and the
        ``engine_degrade_cause_total`` counter — detector-triggered
        degrade must be attributable in a postmortem, never inferred.
        Requires guards (the chain heals back via the usual
        ``heal_after`` clean-tick rule). Returns slots escalated."""
        if self.guard_cfg is None:
            raise ValueError("force_degrade requires guards=GuardConfig(...)")
        if cause not in DEGRADE_CAUSES:
            raise ValueError(
                f"unknown degrade cause {cause!r} (see DEGRADE_CAUSES)"
            )
        targets = (
            slots if slots is not None
            else [s for s in range(self.max_batch)
                  if self.slot_req[s] is not None]
        )
        moved = 0
        for s in targets:
            if self.slot_req[s] is None:
                continue
            new = min(self._slot_degrade[s] + levels,
                      self.guard_cfg.max_degrade)
            if new == self._slot_degrade[s]:
                continue
            self._slot_degrade[s] = new
            self._slot_good[s] = 0
            self.stats.degrade_escalations += 1
            self._degrade_cause.labels(cause=cause).inc()
            self.flight.record(
                "degrade", slot=s, level=new, cause=cause,
                backend=DEGRADE_LEVELS[min(new, len(DEGRADE_LEVELS) - 1)],
            )
            moved += 1
        if moved:
            self._update_degraded_gauge()
        return moved

    def _reset_guard(self, s: int):
        self._slot_degrade[s] = 0
        self._slot_bad[s] = 0
        self._slot_good[s] = 0

    def _update_degraded_gauge(self):
        n = sum(
            1 for s in range(self.max_batch)
            if self.slot_req[s] is not None and self._slot_degrade[s]
        )
        self.degraded_gauge.set(n)
        self.stats.degraded = self.degraded_gauge.as_dict()

    def _kv_scale_arrays(self):
        """Host copies of every quantized pool's per-(page, head) scale
        array — one ``(num_pages, Hkv)`` entry per attn layer rep, for the
        pool audit's scale invariants (live pages finite and >= 0)."""
        out = []
        for (pattern, reps), st_c in zip(self.cfg.stages, self.cache):
            for kind, lc in zip(pattern, st_c):
                if kind != "attn" or "k_scale" not in lc:
                    continue
                for key in ("k_scale", "v_scale"):
                    arr = np.asarray(lc[key])
                    out.extend(arr[r] for r in range(arr.shape[0]))
        return out

    def _run_audits(self):
        """Periodic invariant audits: every ``audit_interval`` decode calls
        run ``pool.check()`` then ``prefix_cache.check()``; a violation
        raises :class:`FatalInvariantError`, repairs in place, or logs,
        per ``audit_action``. The pool audits first — trie repair frees
        the cache's pages through the pool, so the pool must be sane."""
        gc = self.guard_cfg
        if gc.audit_interval <= 0:
            return
        self._audit_clock += 1
        if self._audit_clock % gc.audit_interval:
            return
        self.stats.audits_run += 1
        targets = [("kv_pool", self.pool)]
        if self.prefix_cache is not None:
            targets.append(("prefix_cache", self.prefix_cache))
        with self.tracer.span("audit", targets=len(targets)):
            for name, obj in targets:
                try:
                    if name == "kv_pool" and self.quant:
                        obj.check(scales=self._kv_scale_arrays())
                    else:
                        obj.check()
                except AssertionError as e:
                    self.stats.audit_failures += 1
                    self.flight.record(
                        "audit_failure", target=name,
                        action=gc.audit_action, error=str(e)[:200],
                    )
                    if gc.audit_action == "raise":
                        # fatal: the postmortem bundle is the last thing
                        # written before the engine goes down
                        self._flight_dump("fatal-audit", target=name)
                        raise FatalInvariantError(
                            f"{name} invariant audit failed: {e}"
                        ) from e
                    if gc.audit_action == "repair":
                        obj.repair()
                        self.stats.audit_repairs += 1
                        obj.check()  # repair must restore the invariants
                        self._flight_dump("audit-repair", target=name)
                    else:
                        warnings.warn(
                            f"{name} invariant audit failed "
                            f"(action=log): {e}",
                            RuntimeWarning,
                        )
                        self._flight_dump("audit-failure", target=name)

    # ---------------------------------------------------------- fault hooks
    def _fault_tick_hooks(self, exclude):
        """Per-tick fault points (see :mod:`repro.serving.faults`):
        wall-clock latency spikes, preemption storms, radix-trie node
        corruption, and NaN writes into live KV pages. Runs before the
        tick's active set is computed — where real faults would land."""
        inj = self.faults
        inj.advance()
        if inj.fire("tick_latency"):
            spec = inj.spec("tick_latency")
            time.sleep(spec.magnitude if spec.magnitude > 0 else 0.002)
        if inj.fire("preempt_storm"):
            spec = inj.spec("preempt_storm")
            victims = [
                s for s in range(self.max_batch)
                if self.slot_req[s] is not None
            ]
            n = max(1, int(spec.magnitude))
            for s in inj.choose(victims, n):
                self._preempt(s)
        if self.prefix_cache is not None and inj.fire("trie_corrupt"):
            corrupt_trie_node(self.prefix_cache, inj.rng("trie_corrupt"))
        if inj.fire("nan_kv"):
            self._inject_nan_kv(exclude)

    def _inject_nan_kv(self, exclude):
        """Real device-side corruption: overwrite one victim slot's
        *private*, already-written KV page with NaN. Shared (radix) pages
        are skipped here — the poison path invalidates those separately —
        and so are slots with nothing written yet."""
        cands = []
        for s in range(self.max_batch):
            if self.slot_req[s] is None or s in exclude:
                continue
            ctx = int(self.ctx_lens[s])
            if ctx <= 0:
                continue
            n_read = min(-(-ctx // self.tile), self.pages_per_slot)
            for t in range(n_read):
                if (
                    t not in self._slot_shared_tiles[s]
                    and int(self.page_tbl[s, t]) != 0
                ):
                    cands.append((s, t))
        if not cands:
            return
        s, t = self.faults.choose(cands)[0]
        page = int(self.page_tbl[s, t])
        with _quiet_donation():
            self.cache = self._jit_fill_page(
                self.cache, jnp.asarray(page, jnp.int32),
                jnp.asarray(jnp.nan, jnp.float32),
            )

    def _log_tick_tokens(self, log: List[int], n: int):
        log.append(n)
        if len(log) > self.SCHEDULE_LOG_CAP:
            del log[: -self.SCHEDULE_LOG_CAP]

    # bounded schedule log: a steady-state server ticks forever; keep the
    # benchmark/debug record from growing without limit
    SCHEDULE_LOG_CAP = 512

    def _schedule_cost(self, sched: LeanSchedule) -> dict:
        """Roofline cost meta (KV bytes / flops / predicted ms) for a
        decode schedule, memoized per schedule object — the ScheduleCache
        hands out identical instances tick-to-tick, so a steady-state tick
        does zero cost-model arithmetic here."""
        cost = self._sched_costs.get(sched)
        if cost is None:
            if len(self._sched_costs) > 128:
                self._sched_costs.clear()
            cost = schedule_decode_cost(
                sched,
                n_q_heads=self.cfg.n_heads,
                n_kv_heads=self.cfg.n_kv_heads,
                head_dim=self.cfg.head_dim,
                kv_elem_bytes=self.pool.layout.elem_bytes,
            )
            self._sched_costs[sched] = cost
        return cost

    def _note_schedule(self, sched: LeanSchedule, path: str):
        """The single per-pass schedule bookkeeping point — stats record
        plus trace annotation (execution path + roofline cost meta onto
        the enclosing ``decode_kernel`` span) — shared by the cascade and
        kernel decode paths, so the per-tick recording logic exists once.
        Returns the fused kernel's grid for ``sched``."""
        grid = self._record_schedule(sched)
        if self.tracer.enabled:
            self.tracer.annotate(
                path=path, **grid, **self._schedule_cost(sched)
            )
        return grid

    def _record_schedule(self, sched: LeanSchedule) -> dict:
        # lens come from the schedule itself (one entry per batch slot), so
        # the record is internally consistent: sum(ceil(len/tile)) * Hkv ==
        # total_tiles whether the schedule is exact or bucketed;
        # grid_steps / iters_per_step are the fused decode kernel's grid
        # for this schedule. Returns those two.
        steps, n = fused_grid(sched, self.cfg.head_dim, self.kv_elem_bytes)
        grid = {"grid_steps": steps, "iters_per_step": n}
        self.stats.schedules.append(
            {
                "lens": sched.seg_len[:: self.cfg.n_kv_heads].tolist(),
                "total_tiles": sched.total_tiles,
                "tiles_per_worker": sched.tiles_per_worker,
                "pieces": sched.num_pieces,
                **grid,
            }
        )
        if len(self.stats.schedules) > self.SCHEDULE_LOG_CAP:
            del self.stats.schedules[: -self.SCHEDULE_LOG_CAP]
        return grid

    def run_to_completion(self, max_ticks: int = 10_000):
        while (self.queue or any(self.slot_req)) and self.stats.ticks < max_ticks:
            self.tick()
        return self.stats

