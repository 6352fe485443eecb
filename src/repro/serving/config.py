"""Typed engine configuration: the one constructor argument of
:class:`repro.serving.engine.DecodeEngine`.

The engine's settings form a nested frozen-dataclass tree::

    EngineConfig(
        max_batch=8, attn_backend="lean",
        paged=PagedConfig(page_size=16, kv_dtype="int8"),
        cascade=CascadeConfig(enabled=True),
        spec=SpecConfig(enabled=True, k=4),
        obs=ObsConfig(tracer=tracer),
    )

Grouping follows the engine's own subsystem boundaries: the KV page
pool, cascade (prefix-grouped) decode, speculative draft-verify decode, and
observability sinks. Top-level fields are the knobs every engine has
regardless of mode.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

__all__ = [
    "PagedConfig",
    "CascadeConfig",
    "SpecConfig",
    "ObsConfig",
    "EngineConfig",
]


@dataclass(frozen=True)
class PagedConfig:
    """KV page pool knobs — the pool is the engine's one KV layout, so
    ``enabled`` only accepts ``True``. ``kv_dtype='int8'`` turns on
    quantized pools — per-(page, head) f32 scales with in-kernel
    dequant."""

    enabled: bool = True
    page_size: Optional[int] = None      # None -> engine tile size
    num_pages: Optional[int] = None      # None -> every slot's full pages
    prefix_cache: bool = False           # radix prompt-prefix sharing
    kv_dtype: Optional[str] = None       # None -> model config's dtype

    def __post_init__(self):
        if not self.enabled:
            raise ValueError(
                "PagedConfig(enabled=False): the engine has one KV layout, "
                "the page pool"
            )


@dataclass(frozen=True)
class CascadeConfig:
    """Prefix-grouped (cascade) decode knobs — requires
    ``PagedConfig.prefix_cache`` and the lean backend."""

    enabled: bool = False
    fused: bool = True                   # single-kernel merge when VMEM fits
    grouping: str = "lcp"                # 'lcp' | 'identical'
    multi_level: bool = True             # stack one pass per trie level
    stable_ticks: int = 2                # grouping-stability guard


@dataclass(frozen=True)
class SpecConfig:
    """Draft-verify speculative decode: one stream-K sweep scores ``k``
    draft tokens per sequence (k+1 stacked query rows through the chunked
    prefill kernels). Requires an architecture that supports chunked
    prefill. ``proposer`` is any
    :class:`repro.serving.speculative.DraftProposer`; ``None`` selects the
    in-tree prompt-lookup :class:`~repro.serving.speculative.NGramProposer`.
    """

    enabled: bool = False
    k: int = 4
    proposer: Any = None


@dataclass(frozen=True)
class ObsConfig:
    """Observability sinks: structured tracer, metrics registry, flight
    recorder (+ postmortem dump dir), perf watchdog (``True`` or a
    ``WatchConfig``)."""

    tracer: Any = None
    metrics: Any = None
    flight: Any = None
    flight_dir: Optional[str] = None
    watchdog: Any = None


@dataclass(frozen=True)
class EngineConfig:
    """The full engine configuration tree."""

    max_batch: int = 4
    cache_len: int = 256
    attn_backend: str = "ref"
    num_workers: int = 16
    fused: bool = True
    interpret: Optional[bool] = None     # None -> auto (CPU hosts interpret)
    schedule_cache_entries: int = 128
    paged: PagedConfig = field(default_factory=PagedConfig)
    cascade: CascadeConfig = field(default_factory=CascadeConfig)
    spec: SpecConfig = field(default_factory=SpecConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    faults: Any = None                   # FaultInjector
    guards: Any = None                   # GuardConfig

