"""Trip-count-corrected cost measurement.

XLA's ``cost_analysis()`` counts while-loop bodies ONCE (verified in
tests/test_roofline.py), so a scanned 60-layer model reports ~1 layer of
flops. This module measures the true per-step cost:

  for each stage (pattern, R):
      C1 = compiled cost of the model with only that stage at 1 repeat
      C2 = ... at 2 repeats, python-unrolled (every op visible to XLA)
      unit = C2 - C1           # one repeat's optimized, partitioned cost
      base = C1 - unit         # embed + loss/logits + optimizer overhead
  total = base + sum_i R_i * unit_i   (+ analytic sLSTM scan addendum)

Inner scans (q-chunked attention, chunked mLSTM, chunked CE) are unrolled
via ``cfg.unroll_scans`` in the measurement configs; the sLSTM time scan
cannot be unrolled (T python iterations) and gets a documented analytic
addendum. Costs include flops, bytes and per-kind collective bytes; the
full-depth scanned compile is still used for memory_analysis (fit proof).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict

from repro.models import ModelConfig

COLL_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


@dataclass
class CostVec:
    flops: float = 0.0
    bytes: float = 0.0
    colls: Dict[str, float] = field(
        default_factory=lambda: {k: 0.0 for k in COLL_KINDS}
    )

    def __add__(self, o):
        return CostVec(
            self.flops + o.flops,
            self.bytes + o.bytes,
            {k: self.colls[k] + o.colls[k] for k in COLL_KINDS},
        )

    def __sub__(self, o):
        return CostVec(
            self.flops - o.flops,
            self.bytes - o.bytes,
            {k: self.colls[k] - o.colls[k] for k in COLL_KINDS},
        )

    def __mul__(self, s):
        return CostVec(
            self.flops * s, self.bytes * s,
            {k: v * s for k, v in self.colls.items()},
        )

    def clamp(self):
        return CostVec(
            max(self.flops, 0.0), max(self.bytes, 0.0),
            {k: max(v, 0.0) for k, v in self.colls.items()},
        )


def cost_of(compiled, hlo_text) -> CostVec:
    from .analysis import collective_bytes

    ca = compiled.cost_analysis()
    colls = collective_bytes(hlo_text)
    return CostVec(
        float(ca.get("flops", 0.0)),
        float(ca.get("bytes accessed", 0.0)),
        {k: float(v) for k, v in colls.items()},
    )


def _kind_cfg(cfg: ModelConfig, kind: str, n: int) -> ModelConfig:
    """Model with ``n`` python-unrolled layers of a single kind; inner scans
    (q-chunk attention, chunked mLSTM, chunked CE) unrolled too."""
    return dataclasses.replace(
        cfg,
        stages=(((kind,), n),),
        n_layers=n,
        scan_layers=False,
        unroll_scans=True,
    )


def _slstm_addendum(cfg: ModelConfig, shape_spec, n_chips) -> CostVec:
    """Analytic per-device cost of ONE sLSTM layer's time scan (the scan
    over T steps stays a while loop even in count mode — T python
    iterations cannot be unrolled)."""
    if shape_spec.kind == "decode":
        return CostVec()
    B, T = shape_spec.global_batch, shape_spec.seq_len
    D = cfg.d_model
    H, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    # per step: recurrent einsum 2*B*H*hd*4hd + ~16 elementwise * B*4D
    per_step = 2.0 * B * H * hd * 4 * hd + 16.0 * B * 4 * D
    mult = 3.0 if shape_spec.kind == "train" else 1.0  # fwd + bwd(2x)
    flops = mult * per_step * (T - 1)                  # body counted once
    byts = mult * (T - 1) * (B * 4 * D + 8 * B * D) * 4.0
    return CostVec(flops / n_chips, byts / n_chips,
                   {k: 0.0 for k in COLL_KINDS})


def corrected_cost(cfg: ModelConfig, shape: str, mesh, layout: str,
                   build_fn, shape_spec, n_chips) -> CostVec:
    """``build_fn(cfg, shape) -> (lowered, compiled)`` with the same
    sharding machinery the real cell uses.

    Measures one optimized, partitioned layer of each *kind* (cost at 2
    layers minus cost at 1), then totals base + sum over stages of
    R * sum_kind count_in_pattern * unit_kind.
    """
    kinds = []
    for pattern, _ in cfg.stages:
        for k in pattern:
            if k not in kinds:
                kinds.append(k)

    unit: Dict[str, CostVec] = {}
    base = None
    for kind in kinds:
        c = {}
        for r in (1, 2):
            lowered, compiled = build_fn(_kind_cfg(cfg, kind, r), shape)
            c[r] = cost_of(compiled, compiled.as_text())
        unit[kind] = (c[2] - c[1]).clamp()
        if kind == "slstm":
            unit[kind] = unit[kind] + _slstm_addendum(cfg, shape_spec, n_chips)
        if base is None:
            base = (c[1] - unit[kind]).clamp()

    total = base or CostVec()
    for pattern, reps in cfg.stages:
        for k in pattern:
            total = total + unit[k] * reps
    return total.clamp()
