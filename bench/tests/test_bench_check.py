"""The check that decides ``correct``, driven through a whole run at a size a
test can hold (CPU, Pallas interpret mode): it passes the program as it is,
and fails the control and each fault a served cell can have.

The chip's look for a TPU is skipped; everything else is the harness's own
run: set-up, warm-up, the window and the comparison with the reference.
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import check, serve, traffic
from bench.harness.device import Peaks
from bench.harness.spec import load_cell

TINY = {"name": "tiny", "reference": "dense_rope_swiglu", "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
MIX = {"loop": "closed_batch", "sessions": 3, "max_new": 600, "shape_seed": 0,
       "context": {"dist": "loguniform", "min": 40, "max": 120, "total": 240}}
LIMIT = 0.25
KNOBS = {"page_size": 16, "chunk_size": 32, "prefill_pack": 2,
         "check": {"sessions_beside_longest": 1, "widest_gap_limit": LIMIT,
                   "min_tokens_compared": 10}}
# a slow made-up chip, so that warm-up covers every tick a 1 s window runs
PEAKS = Peaks("test", 1e12, 2e12, 4e7, 1e9, "test")
SEED = 2**31 + 3


def tiny_cell():
    cell = load_cell("yi34b-decode-fewlong")
    return cell.__class__("tiny", 1, TINY, MIX, KNOBS, cell.end_to_end, cell.per_layer)


def longest_slot():
    lens = [len(s.prompt) for s in traffic.generate(MIX, SEED, TINY["vocab_size"])]
    return int(np.argmax(lens))


@pytest.fixture
def jax_config(monkeypatch, tmp_path):
    """Keep the run's compile cache out of the checkout (JAX read the
    variable at import, so no cache is placed at all), and put back the
    threshold the run sets."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def break_step(monkeypatch, fault):
    """Plant ``fault`` in the engine's jitted paged decode step."""
    import repro.serving.engine as engine

    orig = engine._kernel_decode_step_paged
    victim = longest_slot()

    def broken(params, cache, tokens, ctx_lens, page_tbl, **kw):
        if fault == "half":
            # every other slot, the longest among them, reads and writes nothing
            half = jnp.arange(page_tbl.shape[0]) % 2 == victim % 2
            page_tbl = jnp.where(half[:, None], 0, page_tbl)
        logits, new_cache = orig(params, cache, tokens, ctx_lens, page_tbl, **kw)
        if fault == "state":
            return logits, cache
        if fault == "token":
            wrong = (jnp.argmax(logits[victim]) + 1) % logits.shape[-1]
            logits = logits.at[victim, wrong].set(1e9)
        return logits, new_cache

    monkeypatch.setattr(engine, "_kernel_decode_step_paged",
                        functools.wraps(orig)(broken))


def run_tiny(knobs=KNOBS, control=None):
    cell = tiny_cell()
    cell = cell.__class__(cell.name, 1, TINY, MIX, knobs, cell.end_to_end, cell.per_layer)
    return serve.run(cell, SEED, 1.0, False, t_process=time.perf_counter(),
                     require_chip=False, peaks=PEAKS, control=control)


@pytest.mark.parametrize("fault", [None, "token", "state", "half", "int8_kv"])
def test_correct_holds_for_the_program_and_fails_each_fault(fault, monkeypatch, jax_config):
    knobs = KNOBS
    if fault == "int8_kv":
        # the program's own lower-precision pool, in place of the stated bf16
        knobs = {**KNOBS, "kv_dtype": "int8"}
    elif fault is not None:
        break_step(monkeypatch, fault)
    res = run_tiny(knobs)
    assert res["correct"] is (fault is None), res["check"]
    assert list(res)[-1] == "check"
    assert res["check"]["widest_gap"]["limit"] == LIMIT
    assert (res["check"]["kv_dtype_mismatches"]["value"] > 0) is (fault == "int8_kv")


def test_the_fp8_control_fails_the_limit(jax_config):
    res = run_tiny(control="fp8")
    assert res["correct"] is True, res["check"]
    assert res["control_correct"] is False, res["control_check"]
    assert res["control_check"]["widest_gap"]["value"] > LIMIT
    assert res["readings"]["control"]["widest"] > res["readings"]["program"]["widest"]


def test_the_check_judges_program_and_control_by_one_verdict():
    spec = KNOBS["check"]
    ok, numbers = check.verdict({"widest": LIMIT / 2}, 10, spec)
    assert ok and numbers["widest_gap"] == {"value": LIMIT / 2, "limit": LIMIT}
    assert not check.verdict({"widest": LIMIT * 2}, 10, spec)[0]
    assert not check.verdict({"widest": 0.0}, 9, spec)[0]


def test_kv_dtype_mismatches_reads_the_pools_k_and_v():
    cache = [({"k": jnp.zeros(2, jnp.bfloat16), "v": jnp.zeros(2, jnp.int8),
               "k_scale": jnp.zeros(2, jnp.float32)},)]
    assert check.kv_dtype_mismatches(cache, "bfloat16") == (1, ["bfloat16", "int8"])
