"""Plain float32 reference of a dense decoder: RMSNorm, rotary GQA attention,
SwiGLU feed-forward, untied unembedding (Mistral-NeMo-12B, Yi-34B).

Straightforward ``jax.numpy`` with every matrix product at
``Precision.HIGHEST``: no kernel, no cache, no batching. It reads the weights
the benchmark made, in the layout the program is given them, and imports
nothing of the program.

Departures from the published models, each shared with the program, whose
weights it must read: norm gains are stored as ``w`` and applied as
``1 + w``, and the token embedding is multiplied by ``sqrt(hidden_size)``.
Both are reparametrisations (a published checkpoint would load as
``w - 1`` and ``embed / sqrt(hidden_size)``), so the function computed is
the published architecture's.

``fmt="fp8"`` is the control: every matrix product's operands, and q, k
and v, rounded to float8 e4m3 with one scale per tensor (per block of
tokens in the feed-forward), accumulation in float32.

The sequence is processed whole, layer by layer; attention runs over blocks
of query rows and the feed-forward over blocks of tokens, so that a 64k
sequence fits beside the weights on one chip.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
FFN_BLOCK = 4096
SCORE_BYTES = 1 << 28         # bytes of one block of attention scores


class Dims(NamedTuple):
    d: int
    hq: int
    hkv: int
    hd: int
    f: int
    vocab: int
    theta: float
    eps: float


def dims(cfg: dict) -> Dims:
    d = int(cfg["hidden_size"])
    hq = int(cfg["num_attention_heads"])
    return Dims(
        d=d, hq=hq, hkv=int(cfg["num_key_value_heads"]),
        hd=int(cfg.get("head_dim") or d // hq),
        f=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
    )


def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _round(x, fmt):
    x = x.astype(jnp.float32)
    return _fp8(x) if fmt == "fp8" else x


def _mm(a, b, fmt):
    return jnp.matmul(_round(a, fmt), _round(b, fmt), precision=HI)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    """Half-split rotary embedding; x (T, H, hd), pos (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _q_rows(dm: Dims, t_pad: int) -> int:
    rows = max(8, SCORE_BYTES // (4 * dm.hq * t_pad))
    rows = 1 << (rows.bit_length() - 1)
    return min(rows, FFN_BLOCK, t_pad)


@functools.partial(jax.jit, static_argnames=("dm", "fmt"))
def _layer(x, stack, r, n_true, *, dm: Dims, fmt: str):
    """One layer over the whole (padded) sequence ``x`` (T, D) float32;
    ``stack`` holds the layer's weights stacked over depth, ``r`` picks one.
    Keys at or past ``n_true`` are padding and masked."""
    w = jax.tree.map(lambda a: a[r], stack)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms_norm(x, w["ln1"], dm.eps)
    at = w["attn"]
    q = _mm(h, at["wq"], fmt).reshape(T, dm.hq, dm.hd)
    k = _mm(h, at["wk"], fmt).reshape(T, dm.hkv, dm.hd)
    v = _mm(h, at["wv"], fmt).reshape(T, dm.hkv, dm.hd)
    q = _round(_rope(q, pos, dm.theta), fmt)
    k = _round(_rope(k, pos, dm.theta), fmt)
    v = _round(v, fmt)
    g = dm.hq // dm.hkv
    rows = _q_rows(dm, T)
    scale = 1.0 / np.sqrt(dm.hd)

    def attend(i):
        start = i * rows
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows).reshape(rows, dm.hkv, g, dm.hd)
        s = jnp.einsum("rkgd,tkd->kgrt", qb, k, precision=HI) * scale
        qpos = start + jnp.arange(rows)
        ok = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < n_true)
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgrt,tkd->rkgd", p, v, precision=HI)
        return o.reshape(rows, dm.hq * dm.hd)

    o = jax.lax.map(attend, jnp.arange(T // rows)).reshape(T, dm.hq * dm.hd)
    x = x + _mm(o, at["wo"], fmt)

    ff = w["ffn"]

    def ffn(xb):
        hb = _rms_norm(xb, w["ln2"], dm.eps)
        a = jax.nn.silu(_mm(hb, ff["wg"], fmt)) * _mm(hb, ff["wu"], fmt)
        return xb + _mm(a, ff["wd"], fmt)

    blk = min(FFN_BLOCK, T)
    return jax.lax.map(ffn, x.reshape(T // blk, blk, dm.d)).reshape(T, dm.d)


@functools.partial(jax.jit, static_argnames=("d",))
def _embed(embed, tokens, *, d: int):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32) * np.sqrt(d)


@functools.partial(jax.jit, static_argnames=("dm", "fmt"))
def _logits(x, final_norm, unembed, *, dm: Dims, fmt: str):
    return _mm(_rms_norm(x, final_norm, dm.eps), unembed, fmt)


def padded_length(n: int) -> int:
    """Sequences are padded to few shapes: powers of two up to 4096, then
    multiples of 4096, so repeated runs find their programs compiled."""
    if n <= FFN_BLOCK:
        return max(8, 1 << (n - 1).bit_length())
    return -(-n // FFN_BLOCK) * FFN_BLOCK


def logits_at(weights, cfg: dict, tokens, positions, fmt: str = "fp32"):
    """Float32 logits (len(positions), vocab) of the model on ``tokens``
    (T,) at each of ``positions``: the prediction for the token after."""
    dm = dims(cfg)
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    t_pad = padded_length(n)
    toks = np.zeros(t_pad, np.int32)
    toks[:n] = tokens
    x = _embed(weights["embed"], jnp.asarray(toks), d=dm.d)
    n_true = jnp.asarray(n, jnp.int32)
    for stage in weights["stages"]:
        for stack in stage:
            depth = stack["ln1"].shape[0]
            for r in range(depth):
                x = _layer(x, stack, jnp.asarray(r, jnp.int32), n_true, dm=dm, fmt=fmt)
    rows = x[jnp.asarray(np.asarray(positions, np.int32))]
    return np.asarray(
        _logits(rows, weights["final_norm"], weights["unembed"], dm=dm, fmt=fmt)
    )
