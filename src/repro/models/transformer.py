"""Config-driven decoder-only model supporting all assigned architectures.

A model is a sequence of *stages*; each stage is a repeating *unit pattern*
of layer kinds scanned over its repeats (homogeneous params stack -> small
HLO, fast multi-pod compiles, natural remat boundary):

    stages = ((("rglru", "rglru", "win"), 12), (("rglru", "rglru"), 1))

Layer kinds:
  attn   global causal self-attention (+FFN/MoE)
  win    sliding-window self-attention (+FFN/MoE)
  xattn  self-attention + gated cross-attention to stub image embeddings
  rglru  Griffin RG-LRU temporal block (+FFN)
  mlstm / slstm   xLSTM blocks (self-contained, no FFN when d_ff == 0)

Three entry points per model: ``loss`` (train), ``prefill`` (build caches,
last-position logits), ``decode_step`` (one token against caches). The
decode attention implementation is pluggable via ``attn_fn`` — reference
jnp, Pallas lean kernel, or the mesh-level sequence-parallel lean path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attention import mha_prefill_chunked
from repro.distributed.hints import hint
from .layers import (
    attn_decode,
    attn_decode_paged,
    attn_forward,
    attn_prefill_chunk_paged,
    attn_init,
    dense_init,
    ffn_forward,
    ffn_init,
    rms_norm,
    rope,
    sinusoidal_pos,
)
from .moe import MoEConfig, moe_forward, moe_init
from . import recurrent as rec

ATTN_KINDS = ("attn", "win", "xattn")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    stages: Tuple[Tuple[Tuple[str, ...], int], ...]
    ffn_kind: str = "swiglu"
    moe: Optional[MoEConfig] = None
    window: int = 4096
    rope_theta: Optional[float] = 10000.0   # None -> sinusoidal absolute
    qk_norm: bool = False
    cross_kv_len: int = 0                   # >0 for 'xattn' archs
    d_rnn: int = 0                          # rglru width (0 -> d_model)
    mlstm_proj_factor: float = 2.0
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    # q-chunked (flash-style) exact attention is the default train/prefill
    # path — this IS the FlashAttention-2 baseline execution of the paper;
    # 0 selects the naive O(L^2)-memory reference (tests / ablation).
    attn_q_chunk: int = 512
    loss_chunk: int = 512                   # CE chunking (0 = full logits)
    true_n_heads: int = 0                   # pre-padding head count (6ND)
    remat: bool = True
    scan_layers: bool = True
    unroll_scans: bool = False              # flop-count mode (see roofline)
    # beyond-paper: fp8 KV cache halves decode HBM traffic & cache footprint;
    # 'int8' stores *paged* pools quantized (symmetric, per-(page, head) f32
    # scales, in-kernel dequant) for 2-4x effective pool capacity
    kv_cache_dtype: str = "bf16"            # 'bf16' | 'f8' | 'int8'
    kv_scale_granularity: str = "page_head"  # 'page_head' | 'page' (int8)

    def __post_init__(self):
        n = sum(len(pat) * reps for pat, reps in self.stages)
        assert n == self.n_layers, f"{self.name}: stages give {n} layers"

    @property
    def rnn_width(self):
        return self.d_rnn or self.d_model

    @property
    def spec_heads(self):
        return self.true_n_heads or self.n_heads


# ------------------------------------------------------------------ params
def _layer_init(rng, cfg: ModelConfig, kind: str):
    ks = jax.random.split(rng, 8)
    D = cfg.d_model
    p: dict = {"ln1": jnp.zeros((D,), jnp.float32)}
    if kind in ATTN_KINDS:
        p["attn"] = attn_init(
            ks[0], D, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qk_norm=cfg.qk_norm,
        )
        if kind == "xattn":
            p["ln_x"] = jnp.zeros((D,), jnp.float32)
            p["xattn"] = attn_init(
                ks[1], D, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                qk_norm=cfg.qk_norm,
            )
            p["xgate"] = jnp.zeros((), jnp.float32)
    elif kind == "rglru":
        p["rec"] = rec.rglru_init(ks[0], D, cfg.rnn_width)
    elif kind == "mlstm":
        p["rec"] = rec.mlstm_init(ks[0], D, cfg.n_heads, cfg.mlstm_proj_factor)
    elif kind == "slstm":
        p["rec"] = rec.slstm_init(ks[0], D, cfg.n_heads)
    else:
        raise ValueError(kind)
    if kind not in ("mlstm", "slstm") and (cfg.d_ff > 0 or cfg.moe):
        p["ln2"] = jnp.zeros((D,), jnp.float32)
        if cfg.moe is not None:
            p["moe"] = moe_init(ks[2], D, cfg.moe)
        else:
            p["ffn"] = ffn_init(ks[2], D, cfg.d_ff, cfg.ffn_kind)
    return p


def init_params(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, len(cfg.stages) + 2)
    params = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), scale=0.02),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        "stages": [],
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(
            ks[1], (cfg.d_model, cfg.vocab_size)
        )
    for si, (pattern, reps) in enumerate(cfg.stages):
        rng_s = ks[2 + si]
        unit = []
        for pi, kind in enumerate(pattern):
            reps_p = []
            for r in range(reps):
                reps_p.append(
                    _layer_init(
                        jax.random.fold_in(rng_s, pi * 1000 + r), cfg, kind
                    )
                )
            unit.append(jax.tree.map(lambda *x: jnp.stack(x), *reps_p))
        params["stages"].append(tuple(unit))
    return params


# ------------------------------------------------------------------ caches
def _layer_cache(cfg: ModelConfig, kind: str, reps: int, batch: int,
                 cache_len: int, kv_dtype):
    """Dense decode state of ``reps`` stacked layers of one kind, each leaf
    made at its stacked shape ``(reps, ...)``."""
    def zeros(*shape, dtype=jnp.float32):
        return jnp.zeros((reps,) + shape, dtype)

    D = cfg.d_model
    if kind in ATTN_KINDS:
        S = min(cache_len, cfg.window) if kind == "win" else cache_len
        kv = (batch, cfg.n_kv_heads, S, cfg.head_dim)
        c = {"k": zeros(*kv, dtype=kv_dtype), "v": zeros(*kv, dtype=kv_dtype)}
        if kind == "xattn":
            xkv = (batch, cfg.n_kv_heads, cfg.cross_kv_len, cfg.head_dim)
            c["xk"] = zeros(*xkv, dtype=kv_dtype)
            c["xv"] = zeros(*xkv, dtype=kv_dtype)
        return c
    if kind == "rglru":
        W = cfg.rnn_width
        return {"h": zeros(batch, W), "conv": zeros(batch, 3, W)}
    if kind == "mlstm":
        pd = int(D * cfg.mlstm_proj_factor)
        hd = pd // cfg.n_heads
        return {
            "C": zeros(batch, cfg.n_heads, hd, hd),
            "n": zeros(batch, cfg.n_heads, hd),
            "m": zeros(batch, cfg.n_heads),
        }
    if kind == "slstm":
        hd = D // cfg.n_heads
        return {k: zeros(batch, cfg.n_heads, hd) for k in ("c", "n", "m", "h")}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               kv_dtype=None):
    """Decode-state pytree mirroring the stage structure."""
    if kv_dtype is None:
        kv_dtype = (
            jnp.float8_e4m3fn if cfg.kv_cache_dtype == "f8" else jnp.bfloat16
        )
    return [
        tuple(_layer_cache(cfg, kind, reps, batch, cache_len, kv_dtype)
              for kind in pattern)
        for pattern, reps in cfg.stages
    ]


def init_paged_cache(
    cfg: ModelConfig,
    batch: int,
    cache_len: int,
    num_pages: int,
    page_size: int,
    kv_dtype=None,
):
    """Decode-state pytree for the *paged* engine.

    Global-attention (``attn``) layers hold a shared page pool
    ``(num_pages, H_kv, page_size, head_dim)`` instead of per-slot dense
    rows — slot capacity decouples from max context. Sliding-window caches
    stay dense rings (bounded by the window, they are not the long-context
    memory wall), and cross-attention / recurrent state stays per-slot.
    The same logical page ids index every layer's pool (one allocator, many
    pools), exactly as in paged-attention serving stacks.

    Each stage's pools are made at their stacked shape ``(reps, num_pages,
    H_kv, page_size, head_dim)`` and nothing else is made for them: no
    dense attention row exists at any point, so building the cache needs
    no memory beyond the cache itself.

    ``kv_cache_dtype == "int8"`` (or ``kv_dtype=jnp.int8``) stores the pools
    *quantized*: each attn layer additionally carries ``k_scale`` /
    ``v_scale`` leaves of shape ``(reps, num_pages, n_kv_heads)`` f32 — one
    symmetric scale per (page, kv head), 0 for untouched pages. All writes
    must then go through :func:`repro.core.attention
    .paged_scatter_tokens_quant` so scales stay consistent with content.
    """
    quant = False
    if kv_dtype is None:
        if cfg.kv_cache_dtype == "int8":
            quant = True
            kv_dtype = jnp.int8
        else:
            kv_dtype = (
                jnp.float8_e4m3fn if cfg.kv_cache_dtype == "f8"
                else jnp.bfloat16
            )
    else:
        quant = jnp.dtype(kv_dtype) == jnp.int8
    # dense sub-caches (window rings, cross-attn) stay fp — only the shared
    # page pools quantize
    dense_dtype = jnp.bfloat16 if quant else kv_dtype
    cache = []
    for pattern, reps in cfg.stages:
        unit = []
        for kind in pattern:
            if kind != "attn":
                unit.append(_layer_cache(cfg, kind, reps, batch, cache_len,
                                         dense_dtype))
                continue
            pool = (reps, num_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
            lc = {"k": jnp.zeros(pool, kv_dtype), "v": jnp.zeros(pool, kv_dtype)}
            if quant:
                scales = (reps, num_pages, cfg.n_kv_heads)
                lc["k_scale"] = jnp.zeros(scales, jnp.float32)
                lc["v_scale"] = jnp.zeros(scales, jnp.float32)
            unit.append(lc)
        cache.append(tuple(unit))
    return cache


# ------------------------------------------------------------------ forward
def _attn_full(p, x, cfg: ModelConfig, kind, img_emb, q_offset=0):
    window = cfg.window if kind == "win" else None
    if cfg.attn_q_chunk and x.shape[1] > cfg.attn_q_chunk:
        # flash-style q-chunked exact attention (memory optimization)
        B, L, D = x.shape
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        xc = xn.astype(jnp.bfloat16)
        ap = p["attn"]
        q = (xc @ ap["wq"].astype(xc.dtype)).reshape(
            B, L, cfg.n_heads, cfg.head_dim
        )
        k = (xc @ ap["wk"].astype(xc.dtype)).reshape(
            B, L, cfg.n_kv_heads, cfg.head_dim
        )
        v = (xc @ ap["wv"].astype(xc.dtype)).reshape(
            B, L, cfg.n_kv_heads, cfg.head_dim
        )
        if "q_norm" in ap:
            q = rms_norm(q, ap["q_norm"])
            k = rms_norm(k, ap["k_norm"])
        if cfg.rope_theta is not None:
            pos = jnp.arange(L) + q_offset
            q = rope(q, pos, cfg.rope_theta)
            k = rope(k, pos, cfg.rope_theta)
        q = hint(q, "dp", None, "model", None)
        k = hint(k, "dp", None, "model", None)
        v = hint(v, "dp", None, "model", None)
        o = mha_prefill_chunked(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=True, window=window,
            q_offset=q_offset, q_chunk=cfg.attn_q_chunk,
            unroll=cfg.unroll_scans,
        )
        o = jnp.swapaxes(o, 1, 2).reshape(B, L, -1).astype(xc.dtype)
        h = (o @ ap["wo"].astype(xc.dtype)).astype(x.dtype)
        kv = (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
    else:
        h, kv = attn_forward(
            p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            causal=True, window=window, rope_theta=cfg.rope_theta,
            q_offset=q_offset,
        )
    x = x + h
    xkv = None
    if kind == "xattn":
        hx, xkv = attn_forward(
            p["xattn"], rms_norm(x, p["ln_x"], cfg.norm_eps),
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            causal=False, rope_theta=None, kv_states=img_emb,
        )
        x = x + jnp.tanh(p["xgate"]) * hx
    return x, kv, xkv


@jax.named_scope("ffn")
def _ffn_part(p, x, cfg: ModelConfig):
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        h, aux = moe_forward(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg.moe)
        x = x + h
    elif "ffn" in p:
        x = x + ffn_forward(
            p["ffn"], rms_norm(x, p["ln2"], cfg.norm_eps), kind=cfg.ffn_kind
        )
    return x, aux


def _layer_forward(p, x, kind, cfg: ModelConfig, img_emb=None, q_offset=0):
    """Train-path layer. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ATTN_KINDS:
        x, _, _ = _attn_full(p, x, cfg, kind, img_emb, q_offset)
        x, aux = _ffn_part(p, x, cfg)
    elif kind == "rglru":
        h, _ = rec.rglru_forward(p["rec"], rms_norm(x, p["ln1"], cfg.norm_eps))
        x = x + h
        x, aux = _ffn_part(p, x, cfg)
    elif kind == "mlstm":
        h, _ = rec.mlstm_block_forward(
            p["rec"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg.n_heads,
            unroll=cfg.unroll_scans,
        )
        x = x + h
    elif kind == "slstm":
        h, _ = rec.slstm_forward(
            p["rec"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg.n_heads
        )
        x = x + h
    return x, aux


def _embed(params, cfg: ModelConfig, tokens, offset=0):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)
    x = x * np.sqrt(cfg.d_model)
    if cfg.rope_theta is None:  # absolute sinusoidal (musicgen)
        pos = jnp.arange(tokens.shape[-1]) + offset
        x = x + sinusoidal_pos(pos, cfg.d_model).astype(x.dtype)
    return x


@jax.named_scope("unembed")
def _unembed(params, cfg: ModelConfig, x):
    w = (
        params["embed"].T if cfg.tie_embeddings else params["unembed"]
    ).astype(jnp.bfloat16)
    return (x.astype(jnp.bfloat16) @ w).astype(jnp.float32)


def forward_hidden(params, cfg: ModelConfig, tokens, img_emb=None):
    """Backbone forward -> (hidden (B, L, D) after final norm, aux_loss)."""
    x = _embed(params, cfg, tokens)
    aux_total = jnp.zeros((), jnp.float32)
    for (pattern, reps), stage_p in zip(cfg.stages, params["stages"]):

        def unit_fn(x, unit_params):
            aux = jnp.zeros((), jnp.float32)
            for kind, lp in zip(pattern, unit_params):
                x = hint(x, "dp", None, None)
                x, a = _layer_forward(lp, x, kind, cfg, img_emb)
                aux = aux + a
            return hint(x, "dp", None, None), aux

        if cfg.remat:
            unit_fn = jax.checkpoint(unit_fn)
        if reps == 1 or not cfg.scan_layers:
            for r in range(reps):
                up = jax.tree.map(lambda a: a[r], stage_p)
                x, a = unit_fn(x, up)
                aux_total = aux_total + a
        else:
            def body(carry, up):
                x, aux = carry
                x, a = unit_fn(x, up)
                return (x, aux + a), None

            (x, aux_total), _ = jax.lax.scan(
                body, (x, aux_total), stage_p
            )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total


def forward(params, cfg: ModelConfig, tokens, img_emb=None):
    """Full forward -> (logits (B, L, V) f32, aux_loss)."""
    x, aux = forward_hidden(params, cfg, tokens, img_emb)
    return _unembed(params, cfg, x), aux


def loss_fn(params, cfg: ModelConfig, batch, loss_chunk: Optional[int] = None):
    """Next-token CE + MoE aux. batch: {'tokens': (B, L) int32, ...}.

    The CE is computed in sequence chunks with rematerialization: full
    (B, L, V) f32 logits never exist — per chunk (B, K, V_shard) only —
    and the backward recomputes each chunk's logits. This is what makes
    256k-vocab archs fit the 16 GiB/chip budget at train_4k.
    """
    if loss_chunk is None:
        loss_chunk = cfg.loss_chunk
    tokens = batch["tokens"]
    hidden, aux = forward_hidden(params, cfg, tokens, batch.get("img_emb"))
    B, L, D = hidden.shape
    Lm = L - 1
    w = (
        params["embed"].T if cfg.tie_embeddings else params["unembed"]
    )

    K = loss_chunk if loss_chunk > 0 else Lm
    n_chunks = max(1, -(-Lm // K))
    pad = n_chunks * K - Lm

    h_in = hidden[:, :Lm]
    tgt = tokens[:, 1:]
    if pad:
        h_in = jnp.pad(h_in, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
    mask = (jnp.arange(n_chunks * K) < Lm).astype(jnp.float32)

    @jax.checkpoint
    def chunk_ce(h_c, t_c, m_c):
        lg = (h_c.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)).astype(
            jnp.float32
        )
        lse = jax.nn.logsumexp(lg, axis=-1)
        true = jnp.take_along_axis(lg, t_c[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - true) * m_c[None, :])

    def body(acc, i):
        h_c = jax.lax.dynamic_slice_in_dim(h_in, i * K, K, 1)
        t_c = jax.lax.dynamic_slice_in_dim(tgt, i * K, K, 1)
        m_c = jax.lax.dynamic_slice_in_dim(mask, i * K, K, 0)
        return acc + chunk_ce(h_c, t_c, m_c), None

    if cfg.unroll_scans:
        ce_sum = jnp.zeros((), jnp.float32)
        for i in range(n_chunks):
            ce_sum, _ = body(ce_sum, i)
    else:
        ce_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                 jnp.arange(n_chunks))
    ce = ce_sum / (B * Lm)
    return ce + aux, {"ce": ce, "aux": aux}


# ------------------------------------------------------------------ prefill
def prefill(params, cfg: ModelConfig, tokens, cache_len: int, img_emb=None,
            true_len=None):
    """Forward over the prompt, building decode caches.
    Returns (last_logits (B, V), cache, cur_len).

    ``true_len`` (runtime scalar) enables *bucketed* prefill: ``tokens`` is
    the prompt padded up to a canonical bucket length, and only the first
    ``true_len`` positions are real. Causality keeps pad positions from
    contaminating real ones, logits are gathered at ``true_len - 1``, and
    sliding-window rings only admit real positions — so one trace per
    bucket serves every prompt length in it. KV rows beyond ``true_len``
    hold pad garbage that downstream ragged masking (``ctx_lens``) never
    reads. Recurrent stages scan pad tokens into their state, so bucketing
    is rejected for them.
    """
    B, L = tokens.shape
    if true_len is not None:
        bad = [
            kind
            for pattern, _ in cfg.stages
            for kind in pattern
            if kind not in ATTN_KINDS
        ]
        if bad:
            raise ValueError(
                f"bucketed prefill (true_len) unsupported for recurrent "
                f"stage kinds {sorted(set(bad))}: pad tokens would corrupt "
                "the carried state"
            )
    x = _embed(params, cfg, tokens)
    cache = []
    for (pattern, reps), stage_p in zip(cfg.stages, params["stages"]):

        def unit_fn(x, unit_params):
            caches = []
            for kind, lp in zip(pattern, unit_params):
                if kind in ATTN_KINDS:
                    x, (kh, vh), xkv = _attn_full(lp, x, cfg, kind, img_emb)
                    if kind == "win":
                        S = min(cache_len, cfg.window)
                        kc, vc = _ring_from_prefill(kh, vh, S, L, true_len)
                    else:
                        S = cache_len
                        pad = S - L
                        kc = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
                        vc = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
                    c = {"k": kc.astype(jnp.bfloat16), "v": vc.astype(jnp.bfloat16)}
                    if kind == "xattn":
                        c["xk"] = xkv[0].astype(jnp.bfloat16)
                        c["xv"] = xkv[1].astype(jnp.bfloat16)
                    x, _ = _ffn_part(lp, x, cfg)
                elif kind == "rglru":
                    h, (hT, conv) = rec.rglru_forward(
                        lp["rec"], rms_norm(x, lp["ln1"], cfg.norm_eps)
                    )
                    x = x + h
                    x, _ = _ffn_part(lp, x, cfg)
                    c = {"h": hT, "conv": conv.astype(jnp.float32)}
                elif kind == "mlstm":
                    h, (C, n, m) = rec.mlstm_block_forward(
                        lp["rec"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                        cfg.n_heads,
                    )
                    x = x + h
                    c = {"C": C, "n": n, "m": m}
                elif kind == "slstm":
                    h, (cs, ns, ms, hs) = rec.slstm_forward(
                        lp["rec"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                        cfg.n_heads,
                    )
                    x = x + h
                    c = {"c": cs, "n": ns, "m": ms, "h": hs}
                caches.append(c)
            return x, tuple(caches)

        if reps == 1 or not cfg.scan_layers:
            unit_caches = []
            for r in range(reps):
                up = jax.tree.map(lambda a: a[r], stage_p)
                x, c = unit_fn(x, up)
                unit_caches.append(c)
            stage_cache = jax.tree.map(lambda *a: jnp.stack(a), *unit_caches)
        else:
            def body(x, up):
                return unit_fn(x, up)

            x, stage_cache = jax.lax.scan(body, x, stage_p)
        cache.append(stage_cache)
    if true_len is None:
        x_last = x[:, -1]
        cur = jnp.asarray(L, jnp.int32)
    else:
        cur = jnp.asarray(true_len, jnp.int32)
        x_last = jax.lax.dynamic_index_in_dim(
            x, jnp.clip(cur - 1, 0, L - 1), axis=1, keepdims=False
        )
    x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    return _unembed(params, cfg, x_last), cache, cur


def _ring_from_prefill(kh, vh, S, L, true_len=None):
    """Place the last S prefill positions into ring-buffer slots pos % S.

    With ``true_len`` (bucketed prefill), only real positions
    ``[true_len - S, true_len)`` land in the ring; pad positions scatter
    out-of-bounds and drop, so pad garbage never displaces real KV."""
    B, H, _, hd = kh.shape
    if true_len is None:
        take = min(S, L)
        pos = jnp.arange(L - take, L)
        slots = pos % S
        kc = jnp.zeros((B, H, S, hd), kh.dtype).at[:, :, slots].set(
            kh[:, :, L - take :]
        )
        vc = jnp.zeros((B, H, S, hd), vh.dtype).at[:, :, slots].set(
            vh[:, :, L - take :]
        )
        return kc, vc
    pos = jnp.arange(L)
    valid = (pos < true_len) & (pos >= true_len - S)
    slots = jnp.where(valid, pos % S, S)            # S -> out of bounds
    kc = jnp.zeros((B, H, S, hd), kh.dtype).at[:, :, slots].set(
        kh, mode="drop"
    )
    vc = jnp.zeros((B, H, S, hd), vh.dtype).at[:, :, slots].set(
        vh, mode="drop"
    )
    return kc, vc


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill streams prompt pieces through the *paged* KV pool:
    every stage must be a global-attention layer (the pooled kind) and
    positions must be rotary (applied per-row at attention time). Window
    rings, cross-attention state, and recurrent carries would need their
    own chunk-resume plumbing — those architectures fall back to blocking
    whole-prompt admission."""
    return cfg.rope_theta is not None and all(
        kind == "attn" for pattern, _ in cfg.stages for kind in pattern
    )


def _run_stage(unit_fn, x, stage_p, stage_c, reps, cfg: ModelConfig, pooled):
    """Run ``reps`` units of one stage against its stacked cache; returns
    ``(x, stage_cache)``. ``unit_fn(x, unit_params, unit_cache, r)`` runs
    unit ``r`` and returns ``(x, new_unit_cache)``.

    ``pooled[i]`` says whether layer ``i`` of the unit holds page pools (a
    paged ``attn`` layer). Its leaves stay whole, ``(reps, num_pages,
    ...)``: the unit gets the stack with its index ``r``, writes its tokens
    into layer ``r``'s pages and reads them there, and hands the stack
    back, so no layer's pool is ever copied. Every other layer's leaves
    (window rings, cross-attention, recurrent state, dense per-slot
    caches) are sliced out for the unit and written back into the stack;
    those slices and write-backs are scoped ``kv_pool_io``, as are the
    pools' token writes, so a profile can tell them from the layers' own
    work. Under ``scan_layers`` the cache rides in the scan CARRY: as
    xs/ys XLA would double-buffer the multi-GB stack through the loop."""

    def unit(x, cache_c, up, r):
        with jax.named_scope("kv_pool_io"):
            uc = tuple(
                lc if pool else jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, r, 0, keepdims=False
                    ),
                    lc,
                )
                for lc, pool in zip(cache_c, pooled)
            )
        x, nc = unit_fn(x, up, uc, r)
        with jax.named_scope("kv_pool_io"):
            cache_c = tuple(
                new if pool else jax.tree.map(
                    lambda buf, a: jax.lax.dynamic_update_index_in_dim(
                        buf, a.astype(buf.dtype), r, 0
                    ),
                    buf_c, new,
                )
                for buf_c, new, pool in zip(cache_c, nc, pooled)
            )
        return x, cache_c

    if reps == 1 or not cfg.scan_layers:
        for r in range(reps):
            up = jax.tree.map(lambda a: a[r], stage_p)
            x, stage_c = unit(x, stage_c, up, r)
        return x, stage_c

    def body(carry, up_r):
        return unit(*carry, *up_r), None

    (x, stage_nc), _ = jax.lax.scan(
        body, (x, stage_c), (stage_p, jnp.arange(reps))
    )
    return x, stage_nc


def _chunk_forward(
    params,
    cfg: ModelConfig,
    cache,
    tokens,                 # (N, C) int32 — one token block per row
    offs,                   # (N,) int32 — tokens already in cache per row
    lens,                   # (N,) int32 — valid tokens in each block
    page_tbls,              # (N, W) int32 — page table rows of the blocks
    attn_fn: Optional[Callable] = None,
):
    """Shared body of :func:`prefill_chunks` and :func:`verify_step`: run N
    token blocks through every layer against the paged decode cache,
    appending K/V at each row's depth ``offs[n]``. Returns the full hidden
    states ``(x (N, C, D), new_cache)`` — the callers differ only in which
    positions they unembed."""
    if not supports_chunked_prefill(cfg):
        raise ValueError(
            f"{cfg.name}: chunked prefill requires all-'attn' stages and "
            "rotary positions (see supports_chunked_prefill)"
        )
    x = _embed(params, cfg, tokens)
    offs = jnp.asarray(offs, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    new_cache = []
    for (pattern, reps), stage_p, stage_c in zip(
        cfg.stages, params["stages"], cache
    ):

        def unit_fn(x, up, uc, r):
            new_cs = []
            for kind, lp, lc in zip(pattern, up, uc):
                quant = "k_scale" in lc
                with jax.named_scope("attn"):
                    out = attn_prefill_chunk_paged(
                        lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                        lc["k"], lc["v"], page_tbls, offs, lens, layer=r,
                        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                        attn_fn=attn_fn,
                        k_scale=lc["k_scale"] if quant else None,
                        v_scale=lc["v_scale"] if quant else None,
                        scale_per_head=(
                            cfg.kv_scale_granularity == "page_head"
                        ),
                    )
                if quant:
                    h, kc, vc, ks, vs = out
                    nc = {"k": kc, "v": vc, "k_scale": ks, "v_scale": vs}
                else:
                    h, kc, vc = out
                    nc = {"k": kc, "v": vc}
                x = x + h
                x, _ = _ffn_part(lp, x, cfg)
                new_cs.append(nc)
            return x, tuple(new_cs)

        x, stage_nc = _run_stage(
            unit_fn, x, stage_p, stage_c, reps, cfg, (True,) * len(pattern)
        )
        new_cache.append(stage_nc)
    return x, new_cache


def prefill_chunks(
    params,
    cfg: ModelConfig,
    cache,
    tokens,                 # (N, C) int32 — one prompt chunk per row
    offs,                   # (N,) int32 — tokens already prefilled per row
    lens,                   # (N,) int32 — valid tokens in each chunk
    page_tbls,              # (N, W) int32 — page table rows of the chunks
    attn_fn: Optional[Callable] = None,
):
    """Forward N prompt chunks against the shared paged decode cache.

    The chunked-prefill sibling of :func:`decode_step`: each row is one
    chunk of one in-flight request's prompt, at its own depth ``offs[n]``.
    K/V append directly into the page pools through ``page_tbls`` (no dense
    staging, no copy-on-admit), queries attend causally over each row's
    visible prefix, and the returned logits are each row's *last valid
    position* — the row finishing its prompt samples its first token from
    them. Shapes (N, C, W) are static: one trace serves every chunk of
    every prompt (``offs``/``lens``/``page_tbls`` are runtime arrays).

    Requires :func:`supports_chunked_prefill`. Returns
    ``(logits (N, V) f32, new_cache)``.
    """
    N, C = tokens.shape
    x, new_cache = _chunk_forward(
        params, cfg, cache, tokens, offs, lens, page_tbls, attn_fn
    )
    # each row's last valid position: the first-token logits for rows whose
    # chunk completes the prompt (other rows' logits are simply unused)
    lens = jnp.asarray(lens, jnp.int32)
    idx = jnp.clip(lens - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    return _unembed(params, cfg, x_last), new_cache


def verify_step(
    params,
    cfg: ModelConfig,
    cache,
    tokens,                 # (N, R) int32 — [last committed, k drafts]
    offs,                   # (N,) int32 — committed context per row
    lens,                   # (N,) int32 — valid rows (R, or 0 when masked)
    page_tbls,              # (N, W) int32 — page table rows
    attn_fn: Optional[Callable] = None,
):
    """Speculative verify: score a block of R = k + 1 stacked tokens per
    sequence in ONE forward and return the logits of *every* position.

    Row layout per sequence: position 0 carries the last committed (not yet
    attended) token, positions 1..k carry the draft tokens. K/V for the
    whole block append into the page pools at depths ``offs[n] ..
    offs[n] + R - 1`` exactly like a prefill chunk; logits row ``i``
    predicts the token at depth ``offs[n] + i + 1``, so greedy
    acceptance-rejection runs left to right over the returned rows and a
    rejected tail needs no scatter undo — the committed length simply never
    advances over the garbage positions (the same runtime-length masking
    that makes bucketed schedules exact).

    Mechanically this IS :func:`prefill_chunks` minus the last-position
    gather: same layer stack, same paged attention entry, same causal
    ``qstart`` mask — the composition the ROADMAP's speculative item calls
    for. Returns ``(logits (N, R, V) f32, new_cache)``.
    """
    x, new_cache = _chunk_forward(
        params, cfg, cache, tokens, offs, lens, page_tbls, attn_fn
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(params, cfg, x), new_cache


# ------------------------------------------------------------------ decode
def decode_step(
    params,
    cfg: ModelConfig,
    cache,
    tokens,                 # (B, 1) int32
    cur_len,                # scalar int32
    img_emb=None,
    attn_fn: Optional[Callable] = None,
    win_attn_fn: Optional[Callable] = None,
    ctx_lens: Optional[jax.Array] = None,   # per-slot lengths (ragged)
    page_tbl: Optional[jax.Array] = None,   # paged KV: (B, pages_per_slot)
):
    """One decode step. Returns (logits (B, V), new_cache).

    ``page_tbl`` switches global-attention layers to the paged KV path: the
    cache tree must come from :func:`init_paged_cache`, and ``attn_fn`` (if
    any) receives the page pools instead of dense per-slot KV.
    """
    x = _embed(params, cfg, tokens, offset=cur_len)
    new_cache = []
    for (pattern, reps), stage_p, stage_c in zip(
        cfg.stages, params["stages"], cache
    ):

        def unit_fn(x, up, uc, r):
            new_cs = []
            for kind, lp, lc in zip(pattern, up, uc):
                if kind in ATTN_KINDS:
                    with jax.named_scope("attn"):
                        window = cfg.window if kind == "win" else None
                        if page_tbl is not None and kind == "attn":
                            quant = "k_scale" in lc
                            out = attn_decode_paged(
                                lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                                lc["k"], lc["v"], page_tbl, cur_len,
                                layer=r,
                                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                                head_dim=cfg.head_dim,
                                rope_theta=cfg.rope_theta,
                                attn_fn=attn_fn, ctx_lens=ctx_lens,
                                k_scale=lc["k_scale"] if quant else None,
                                v_scale=lc["v_scale"] if quant else None,
                                scale_per_head=(
                                    cfg.kv_scale_granularity == "page_head"
                                ),
                            )
                            if quant:
                                h, kc, vc, ks, vs = out
                            else:
                                h, kc, vc = out
                                ks = vs = None
                        else:
                            h, kc, vc = attn_decode(
                                lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                                lc["k"], lc["v"], cur_len,
                                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                                window=window,
                                attn_fn=win_attn_fn if kind == "win" else attn_fn,
                                ctx_lens=ctx_lens,
                            )
                            ks = vs = None
                        x = x + h
                        nc = {"k": kc, "v": vc}
                        if ks is not None:
                            nc["k_scale"] = ks
                            nc["v_scale"] = vs
                        if kind == "xattn":
                            from repro.core.attention import mha_decode_ref

                            xn = rms_norm(x, lp["ln_x"], cfg.norm_eps)
                            xc_ = xn.astype(jnp.bfloat16)
                            ap = lp["xattn"]
                            qx = (xc_ @ ap["wq"].astype(xc_.dtype)).reshape(
                                x.shape[0], cfg.n_heads, cfg.head_dim
                            )
                            ox = mha_decode_ref(qx, lc["xk"], lc["xv"])
                            ox = ox.reshape(x.shape[0], 1, -1).astype(xc_.dtype)
                            hx = (ox @ ap["wo"].astype(xc_.dtype)).astype(x.dtype)
                            x = x + jnp.tanh(lp["xgate"]) * hx
                            nc["xk"] = lc["xk"]
                            nc["xv"] = lc["xv"]
                    x, _ = _ffn_part(lp, x, cfg)
                elif kind == "rglru":
                    h, hn, conv = rec.rglru_step(
                        lp["rec"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                        lc["h"], lc["conv"],
                    )
                    x = x + h
                    x, _ = _ffn_part(lp, x, cfg)
                    nc = {"h": hn, "conv": conv}
                elif kind == "mlstm":
                    h, (C, n, m) = rec.mlstm_block_step(
                        lp["rec"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                        cfg.n_heads, (lc["C"], lc["n"], lc["m"]),
                    )
                    x = x + h
                    nc = {"C": C, "n": n, "m": m}
                elif kind == "slstm":
                    h, (cs, ns, ms, hs) = rec.slstm_step(
                        lp["rec"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                        cfg.n_heads, (lc["c"], lc["n"], lc["m"], lc["h"]),
                    )
                    x = x + h
                    nc = {"c": cs, "n": ns, "m": ms, "h": hs}
                new_cs.append(nc)
            return x, tuple(new_cs)

        pooled = tuple(
            page_tbl is not None and kind == "attn" for kind in pattern
        )
        x, stage_nc = _run_stage(
            unit_fn, x, stage_p, stage_c, reps, cfg, pooled
        )
        new_cache.append(stage_nc)
    x = rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
    return _unembed(params, cfg, x), new_cache


# ------------------------------------------------------------------ counts
def count_params(cfg: ModelConfig) -> int:
    """Analytic parameter count (for 6ND model-flops accounting)."""
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    Hq, Hkv, hd = cfg.spec_heads, cfg.n_kv_heads, cfg.head_dim
    total = V * D + (0 if cfg.tie_embeddings else D * V)

    def ffn_params():
        if cfg.moe is not None:
            E, Fe = cfg.moe.num_experts, cfg.moe.d_ff_expert
            p = D * E + 3 * E * D * Fe
            if cfg.moe.d_ff_shared:
                p += 3 * D * cfg.moe.d_ff_shared
            return p
        if F == 0:
            return 0
        mult = 3 if cfg.ffn_kind == "swiglu" else 2
        return mult * D * F

    for pattern, reps in cfg.stages:
        for kind in pattern:
            if kind in ATTN_KINDS:
                p = D * Hq * hd * 2 + D * Hkv * hd * 2
                if kind == "xattn":
                    p *= 2
                p += ffn_params()
            elif kind == "rglru":
                W = cfg.rnn_width
                p = 2 * D * W + W * D + 2 * W * W + 5 * W + ffn_params()
            elif kind == "mlstm":
                pd = int(D * cfg.mlstm_proj_factor)
                p = D * 2 * pd + 3 * pd * pd + pd * 2 * cfg.n_heads + pd * D
            elif kind == "slstm":
                hd_s = D // cfg.n_heads
                p = D * 4 * D + cfg.n_heads * hd_s * 4 * hd_s + D * D
            total += p * reps
    return int(total)


def count_active_params(cfg: ModelConfig) -> int:
    """Active params per token (MoE: top_k of routed experts + shared)."""
    if cfg.moe is None:
        return count_params(cfg)
    E, K = cfg.moe.num_experts, cfg.moe.top_k
    routed = 3 * cfg.d_model * cfg.moe.d_ff_expert
    n_moe_layers = cfg.n_layers
    total = count_params(cfg)
    total -= n_moe_layers * routed * E          # remove all experts
    total += n_moe_layers * routed * K          # add back active ones
    return int(total)
