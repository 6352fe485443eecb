"""Operations and bytes that the algorithm needs, from shapes alone.

Counted from the attention problem and the model's published shapes, never
from the tiles, pages or buckets a kernel happens to touch: two kernels that
solve the same problem are charged the same work. ``cfg`` is a configuration
file's dict (Hugging Face key names). A multiply-add counts as 2 FLOPs.
"""
from __future__ import annotations

from typing import Sequence

BF16 = 2


def dims(cfg: dict):
    d = int(cfg["hidden_size"])
    hq = int(cfg["num_attention_heads"])
    hkv = int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or d // hq)
    return d, hq, hkv, hd


def layer_params(cfg: dict) -> int:
    """Weights of one attention + SwiGLU layer, norms included."""
    d, hq, hkv, hd = dims(cfg)
    f = int(cfg["intermediate_size"])
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    return attn + 3 * d * f + 2 * d


def unembed_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def step_weight_params(cfg: dict) -> int:
    """Weights a decode step reads whole: every layer plus the unembedding
    (the embedding is gathered by row)."""
    return int(cfg["num_hidden_layers"]) * layer_params(cfg) + unembed_params(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one token over every layer."""
    _, _, hkv, hd = dims(cfg)
    return 2 * int(cfg["num_hidden_layers"]) * hkv * hd * BF16


def decode_attention(cfg: dict, ctx: Sequence[int]):
    """(FLOPs, bytes) of one decode attention call, one layer: one query row
    per sequence over ``ctx[b]`` keys. Bytes: K and V of the true context,
    plus q read and out written."""
    _, hq, hkv, hd = dims(cfg)
    total = int(sum(int(c) for c in ctx))
    flops = 4 * total * hq * hd
    nbytes = 2 * total * hkv * hd * BF16 + 2 * len(ctx) * hq * hd * BF16
    return flops, nbytes


def decode_step_flops(cfg: dict, ctx: Sequence[int]) -> int:
    """Model FLOPs of one decode step over ``len(ctx)`` sequences: 2 per
    weight per token, plus attention over each sequence's true context in
    every layer."""
    flops = 2 * step_weight_params(cfg) * len(ctx)
    return flops + int(cfg["num_hidden_layers"]) * decode_attention(cfg, ctx)[0]


def decode_step_min_bytes(cfg: dict, ctx: Sequence[int]) -> int:
    """Bytes a decode step cannot avoid: every weight once, plus the K and V
    of every sequence's context."""
    return (step_weight_params(cfg) * BF16
            + int(sum(int(c) for c in ctx)) * kv_bytes_per_token(cfg))


def roofline_seconds(flops: float, nbytes: float, peaks) -> float:
    """The least time on the chip: the larger of compute and memory time."""
    return max(flops / peaks.bf16_flops_per_s, nbytes / peaks.hbm_bytes_per_s)
