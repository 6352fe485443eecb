"""The paged KV pools stay where they lie in the stacked cache: each layer
writes its tokens into its own pool in the stack, row-wise, and the
kernels read its pages there through the layer's page base.

Against a reference kept here, the layer loop that slices every layer's
pools out of the stack, runs the layer on that copy (page base 0), writes
the copy back and writes each token as one ``(H, d)`` window, the engine
serves the same tokens and leaves every cache leaf bit-identical: lean,
fixed-split, cascade and the jnp reference, bf16, int8 and fp8 pools, scanned
and unrolled stages, a stage that mixes pools with sliced window rings,
chunked prefill and speculative verify. A decode step writes each layer's
token into that layer's pool and nowhere else.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import attention
from repro.core.leantile import make_schedule
from repro.models import init_paged_cache, init_params, transformer
from repro.serving import engine
from repro.serving.config import (
    CascadeConfig,
    EngineConfig,
    PagedConfig,
    SpecConfig,
)
from repro.serving.engine import DecodeEngine
from repro.serving.scheduler import Scheduler, SchedulerConfig

jax.config.update("jax_platform_name", "cpu")


def _slicing_run_stage(unit_fn, x, stage_p, stage_c, reps, cfg, pooled):
    """The layer loop that copies: each layer's pools are sliced out of the
    stack as a stack of one (so the unit's page base is 0), the unit runs
    against that copy, and the stack takes the copy back."""
    lax = jax.lax

    def take(pool):
        if pool:
            return lambda a, r: lax.dynamic_slice_in_dim(a, r, 1)
        return lambda a, r: lax.dynamic_index_in_dim(a, r, keepdims=False)

    def put(pool):
        if pool:
            return lambda buf, a, r: lax.dynamic_update_slice_in_dim(
                buf, a, r, 0)
        return lambda buf, a, r: lax.dynamic_update_index_in_dim(
            buf, a.astype(buf.dtype), r, 0)

    def body(carry, up_r):
        x, c = carry
        up, r = up_r
        uc = tuple(jax.tree.map(lambda a: take(p)(a, r), lc)
                   for lc, p in zip(c, pooled))
        x, nc = unit_fn(x, up, uc, 0)
        c = tuple(jax.tree.map(lambda b, a: put(p)(b, a, r), bc, n)
                  for bc, n, p in zip(c, nc, pooled))
        return (x, c), None

    (x, c), _ = lax.scan(body, (x, stage_c), (stage_p, jnp.arange(reps)))
    return x, c


def _window_write(pool, pages, offsets, vals):
    """The token write as one ``(H, d)`` window per token."""
    return pool.at[pages, :, offsets].set(vals.astype(pool.dtype))


class _Drafts:
    """Always drafts, so every decode tick is a verify step."""

    def propose(self, req, k):
        return [(req.generated[-1] + i + 1) % 128 for i in range(k)]


def _cfg(kind, scan):
    cfg = get_smoke_config("mistral-nemo-12b")
    if kind == "win+attn":
        cfg = dataclasses.replace(
            cfg, name="win-attn-smoke", n_layers=4, window=8,
            stages=((("win", "attn"), 2),),
        )
    elif kind == "attn3":
        cfg = dataclasses.replace(
            cfg, name="attn3-smoke", n_layers=3, stages=((("attn",), 3),),
        )
    return dataclasses.replace(cfg, scan_layers=scan)


_PARAMS = {}


def _params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = init_params(jax.random.PRNGKey(0), cfg)
    return _PARAMS[cfg.name]


def _prompts(cfg, seed=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, 16)
    return [
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, 3 + 4 * i)])
        for i in range(3)
    ]


def _serve(cfg, *, backend, kv_dtype, cascade, spec):
    eng = DecodeEngine(cfg, _params(cfg), config=EngineConfig(
        max_batch=4, cache_len=64, attn_backend=backend, num_workers=4,
        paged=PagedConfig(enabled=True, page_size=8, kv_dtype=kv_dtype,
                          prefix_cache=cascade),
        cascade=CascadeConfig(enabled=cascade, stable_ticks=1),
        spec=SpecConfig(enabled=spec, k=2, proposer=_Drafts()),
    ))
    sch = Scheduler(eng, SchedulerConfig(chunk_size=8, prefill_pack=2,
                                         token_budget=32))
    handles = []
    for wave in (_prompts(cfg)[:1], _prompts(cfg)[1:]):
        handles += [sch.submit(p, max_new_tokens=5) for p in wave]
        sch.run_to_completion(max_steps=200)
    tokens = [list(h.generated) for h in handles]
    return tokens, jax.tree.map(np.asarray, eng.cache), eng.stats


CASES = [
    # (model, scan_layers, backend, kv_dtype, cascade, spec)
    ("attn", True, "lean", None, False, False),
    ("attn", True, "lean", "int8", False, True),
    ("attn", True, "lean", None, False, True),
    ("attn", False, "fixed", None, False, False),
    ("attn", True, "fixed", "int8", False, False),
    ("attn", True, "lean", None, True, False),
    ("attn3", False, "lean", "int8", True, False),
    ("attn", True, "ref", "int8", False, True),
    ("attn", True, "lean", "f8", False, True),
    ("win+attn", True, "lean", None, False, False),
    ("win+attn", False, "ref", None, False, False),
]


@pytest.mark.parametrize(
    "model,scan,backend,kv_dtype,cascade,spec", CASES,
    ids=["-".join(str(v) for v in c) for c in CASES],
)
def test_in_place_pools_match_the_slicing_layer_loop(
        monkeypatch, model, scan, backend, kv_dtype, cascade, spec):
    cfg = _cfg(model, scan)
    tokens, cache, stats = _serve(cfg, backend=backend, kv_dtype=kv_dtype,
                                  cascade=cascade, spec=spec)
    with monkeypatch.context() as mp:
        mp.setattr(transformer, "_run_stage", _slicing_run_stage)
        mp.setattr(attention, "paged_write_rows", _window_write)
        ref_tokens, ref_cache, _ = _serve(
            cfg, backend=backend, kv_dtype=kv_dtype, cascade=cascade,
            spec=spec)

    assert all(len(t) == 5 for t in tokens)
    assert tokens == ref_tokens
    leaves = jax.tree_util.tree_leaves_with_path(cache)
    ref_leaves = jax.tree.leaves(ref_cache)
    assert len(leaves) == len(ref_leaves)
    for (path, a), b in zip(leaves, ref_leaves):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert np.array_equal(a, b), jax.tree_util.keystr(path)
    if cascade:
        assert stats.cascade_ticks > 0
    if spec:
        assert stats.spec_draft_tokens > 0
    if model != "win+attn":
        assert stats.chunk_prefills > 0


@pytest.mark.parametrize("backend,kv_dtype", [
    ("lean", None), ("fixed", None), ("ref", None), ("lean", "int8"),
])
def test_each_layer_writes_only_its_own_pool(backend, kv_dtype):
    cfg = _cfg("attn3", True)
    params = _params(cfg)
    ps, pages = 8, 9
    tbl = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    ctx = np.array([3, 10], np.int32)
    cache = init_paged_cache(cfg, 2, 32, pages, ps,
                             kv_dtype=None if kv_dtype is None else jnp.int8)
    tokens = jnp.array([[7], [11]], jnp.int32)
    if backend == "ref":
        _, new = transformer.decode_step(
            params, cfg, cache, tokens, jnp.max(ctx),
            ctx_lens=jnp.asarray(ctx), page_tbl=jnp.asarray(tbl))
    else:
        _, new = engine._kernel_decode_step_paged(
            params, cache, tokens, jnp.asarray(ctx), jnp.asarray(tbl),
            cfg=cfg, backend=backend,
            sched=make_schedule((ctx + 1).tolist(), cfg.n_kv_heads, ps, 4),
            num_splits=2, fused=True, interpret=True)

    # (page, offset) each slot's token goes to
    written = np.zeros((pages, ps), bool)
    written[tbl[np.arange(2), ctx // ps], ctx % ps] = True
    touched_pages = written.any(axis=1)
    for key in ("k", "v"):
        # (layers, pages, heads, page_size, head_dim)
        pool = np.asarray(new[0][0][key]).astype(np.float32)
        nonzero = (pool != 0).any(axis=-1)                    # (3, P, H, ps)
        for r in range(3):
            for h in range(cfg.n_kv_heads):
                np.testing.assert_array_equal(nonzero[r, :, h], written,
                                              err_msg=f"{key} layer {r}")
        # three layers, three different projections: no layer holds another's
        rows = np.moveaxis(pool, 2, 3)[:, written]            # (3, 2, H, d)
        assert not np.array_equal(rows[0], rows[1])
        assert not np.array_equal(rows[1], rows[2])
        if kv_dtype == "int8":
            scales = np.asarray(new[0][0][f"{key}_scale"])    # (3, P, H)
            for r in range(3):
                np.testing.assert_array_equal(
                    (scales[r] != 0).any(axis=-1), touched_pages)
