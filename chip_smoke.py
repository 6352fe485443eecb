"""Smoke run of the serving path on one TPU chip.

Mistral-NeMo-12B at its published widths (d_model 5120, 32 q / 8 kv heads,
head_dim 128, d_ff 14336, vocab 131072) with its depth cut to a few whole
layers and random weights from a seed, served through the normal path:
``Scheduler`` -> paged ``KVPagePool`` -> chunked prefill (stream-K
``lean_prefill``) -> fused stream-K paged decode. Then the lean paged decode
kernel and the chunk-prefill kernel are compared, on the chip, with the fp32
reference of ``repro.kernels.ref`` on the same bf16 inputs at these widths.

    python chip_smoke.py

Exits non-zero, with no result line, unless JAX finds a TPU or when any
phase fails. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.configs import mistral_nemo_12b  # noqa: E402
from repro.core.attention import paged_gather_kv  # noqa: E402
from repro.core.leantile import make_chunk_schedule, make_schedule  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.serving.config import EngineConfig, PagedConfig  # noqa: E402
from repro.serving.engine import DecodeEngine  # noqa: E402
from repro.serving.scheduler import Scheduler, SchedulerConfig  # noqa: E402

# 4 layers hold 9.06 GiB of f32 weights (the untied 131072 x 5120 embed and
# unembed are 5.0 GiB of it); the compiled decode and prefill-chunk steps
# then peak at 11.2 GiB of the chip's 16 GiB (memory_analysis of both
# steps compiled for a v5e).
LAYERS = 4
PAGE = 128
CHUNK = 256                       # prompts below take 2-4 chunks each
PROMPT_LENS = (300, 520, 700, 900)
MAX_NEW = 32
CACHE_LEN = 1024                  # >= longest prompt + MAX_NEW

SEED = 0
# On a v5e the kernels' f32 matmuls run at the default precision, which
# rounds the probabilities to bf16 before P @ V: at these inputs the fp32
# reference with bf16 probabilities reads 1.2e-3 (decode) and 9.1e-4
# (prefill), as the kernels do, while attention with its scores rounded to
# bf16 reads 9.8e-3 and 3.4e-3. The limit lies between the two.
REL_TOL = 2.0**-9


def require(ok, what):
    """A failed check ends the run with an error (unlike ``assert``, it
    survives ``python -O``)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def make_server(cfg, params, batch):
    """The lean paged engine under a chunked-prefill scheduler. Every decode
    pass's logits are screened for finiteness on the device; the verdicts
    are read back once per run."""
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=batch, cache_len=CACHE_LEN, attn_backend="lean",
        paged=PagedConfig(page_size=PAGE),
    ))
    eng.screens = []
    decode_pass = eng._decode_pass

    def screened_pass(level, slots, active, exclude):
        logits = decode_pass(level, slots, active, exclude)
        eng.screens.append(jnp.all(jnp.isfinite(logits[np.asarray(slots)])))
        return logits

    eng._decode_pass = screened_pass
    return Scheduler(eng, SchedulerConfig(
        chunk_size=CHUNK, prefill_pack=2, token_budget=2 * CHUNK + batch,
    ))


def serve(sch, prompts, max_new):
    """Serve ``prompts`` to drain and check what came out; returns the
    number of chunk executions it took."""
    eng = sch.engine
    eng.screens.clear()
    chunks = eng.stats.chunk_prefills
    handles = [sch.submit(p, max_new) for p in prompts]
    sch.run_to_completion()
    chunks = eng.stats.chunk_prefills - chunks
    for h in handles:
        require(h.done and len(h.generated) == max_new,
                f"request {h.uid} finished with {max_new} tokens")
    require(bool(jnp.all(jnp.stack(eng.screens))), "decode logits finite")
    require(eng.stats.degrade_escalations == 0, "no degrade escalations")
    require(chunks > len(prompts), "a prompt took more than one chunk")
    eng.pool.check()
    require(eng.pool.num_allocated == 0, "no pages allocated after drain")
    return chunks


def random_pool(rng, cfg, seq_lens):
    """bf16 K/V pools holding ``seq_lens`` tokens per sequence on shuffled
    pages, and the page tables that find them."""
    widths = [-(-n // PAGE) for n in seq_lens]
    order = rng.permutation(sum(widths)) + 1            # page 0 stays null
    tbl = np.zeros((len(seq_lens), max(widths)), np.int32)
    at = 0
    for b, w in enumerate(widths):
        tbl[b, :w] = order[at:at + w]
        at += w
    shape = (sum(widths) + 1, cfg.n_kv_heads, PAGE, cfg.head_dim)
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    return k, v, jnp.asarray(tbl)


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def bf16_values(rng, shape):
    """Queries holding bf16 values, passed as f32 so that the kernels
    return f32: the check then sees their arithmetic, not the bf16
    rounding of their output."""
    return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16).astype(
        jnp.float32
    )


def check_decode_kernel(cfg, rng, ctx_lens):
    """Lean paged decode vs the fp32 reference; returns (relative error,
    whether the fused kernel ran)."""
    k, v, tbl = random_pool(rng, cfg, ctx_lens)
    q = bf16_values(rng, (len(ctx_lens), cfg.n_heads, cfg.head_dim))
    sched = make_schedule(
        ctx_lens, cfg.n_kv_heads, PAGE, ops.default_num_workers()
    )
    fused = ops.decode_uses_fused(
        sched, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim,
        kv_elem_bytes=k.dtype.itemsize,
    )
    seg_ctx = jnp.repeat(jnp.asarray(ctx_lens, jnp.int32), cfg.n_kv_heads)
    got = jax.jit(
        ops.lean_decode_paged_from_schedule, static_argnames=("sched",)
    )(q, k, v, seg_ctx, tbl, sched=sched)
    f32 = lambda a: a.astype(jnp.float32)
    want = ref.lean_decode_ref(
        q, f32(paged_gather_kv(k, tbl)),
        f32(paged_gather_kv(v, tbl)), jnp.asarray(ctx_lens),
    )
    return rel_err(got, want), fused


def check_prefill_kernel(cfg, rng, offs):
    """Stream-K chunk prefill (one CHUNK per row at depth ``offs``) vs the
    fp32 reference; returns the relative error."""
    offs = np.asarray(offs, np.int32)
    k, v, tbl = random_pool(rng, cfg, offs + CHUNK)
    q = bf16_values(rng, (len(offs), cfg.n_heads, CHUNK, cfg.head_dim))
    sched = make_chunk_schedule(
        offs + CHUNK, cfg.n_kv_heads, PAGE, ops.default_num_workers()
    )
    rep = lambda a: jnp.repeat(jnp.asarray(a, jnp.int32), cfg.n_kv_heads)
    got = jax.jit(ops.lean_prefill_chunks, static_argnames=("sched",))(
        q, k, v, rep(offs + CHUNK), rep(offs), tbl, sched=sched
    )
    f32 = lambda a: a.astype(jnp.float32)
    want = ref.lean_prefill_chunks_ref(
        q, f32(k), f32(v), tbl, jnp.asarray(offs)
    )
    return rel_err(got, want)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU found: JAX runs on {dev.platform!r}", file=sys.stderr)
        return 1
    count = len(jax.devices())
    print(f"device: {dev.device_kind} x{count}")
    print(f"compile cache: {use_compile_cache()}")

    full = mistral_nemo_12b.config()
    cfg = dataclasses.replace(
        full, n_layers=LAYERS, stages=((("attn",), LAYERS),)
    )
    print(
        f"model: {full.name} d_model {cfg.d_model}, {cfg.n_heads} q / "
        f"{cfg.n_kv_heads} kv heads x {cfg.head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}; {cfg.n_layers} of {full.n_layers} layers"
    )

    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg
    )
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in PROMPT_LENS]
    sch = make_server(cfg, params, len(prompts))
    require(sch.engine.interpret is False, "Pallas kernels compiled, not interpreted")
    # the first run compiles every step shape the second one replays
    serve(sch, prompts, MAX_NEW)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    chunks = serve(sch, prompts, MAX_NEW)
    serve_s = time.perf_counter() - t0
    print(f"set-up + compile s: {setup_s}")
    print(
        f"serve s: {serve_s} ({len(prompts)} requests, prompts "
        f"{list(PROMPT_LENS)} in {chunks} chunks of <= {CHUNK}, {MAX_NEW} "
        "tokens each)"
    )

    # which side of the VMEM gate the engine's decode ticks took
    st = sch.engine.stats
    print(
        f"served decode kernel: fused on {st.fused_decode_ticks} of "
        f"{st.ticks} decode ticks, two-phase on the rest"
    )
    require(st.fused_decode_ticks > 0, "fused decode kernel served")
    err, fused = check_decode_kernel(cfg, rng, [4096, 2900, 1337, 130])
    print(
        f"paged decode kernel ({'fused' if fused else 'two-phase'}) vs fp32 "
        f"reference: max |err| / max |ref| = {err} (tol {REL_TOL})"
    )
    require(err <= REL_TOL, "paged decode kernel within tolerance")
    err = check_prefill_kernel(cfg, rng, [0, 700])
    print(
        f"chunk prefill kernel vs fp32 reference: max |err| / max |ref| = "
        f"{err} (tol {REL_TOL})"
    )
    require(err <= REL_TOL, "chunk prefill kernel within tolerance")

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": count},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
