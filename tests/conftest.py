"""Test bootstrap: one hypothesis profile for the whole suite, and the
batch-1 model-loop oracle the engine tests compare against.

Interpret-mode Pallas examples compile on first use, so a per-example
deadline measures the compiler, not the property; and the example database
would write ``.hypothesis/`` into the checkout. Both are off.
"""
import pytest
from hypothesis import settings

settings.register_profile("repro", deadline=None, database=None)
settings.load_profile("repro")


def _model_loop(cfg, params, prompt, new, cache_len):
    import jax.numpy as jnp
    import numpy as np

    from repro.models import decode_step, prefill

    logits, cache, _ = prefill(
        params, cfg, jnp.asarray(np.asarray(prompt)[None], jnp.int32),
        cache_len=cache_len,
    )
    out = [int(jnp.argmax(logits[0]))]
    ctx = len(prompt)
    while len(out) < new:
        logits, cache = decode_step(
            params, cfg, cache, jnp.asarray([[out[-1]]], jnp.int32),
            jnp.asarray(ctx, jnp.int32),
            ctx_lens=jnp.asarray([ctx], jnp.int32),
        )
        out.append(int(jnp.argmax(logits[0])))
        ctx += 1
    return out


@pytest.fixture(scope="session")
def model_loop():
    """``model_loop(cfg, params, prompt, new, cache_len)``: plain batch-1
    greedy decode with the model functions alone — dense ``prefill`` then
    ``decode_step`` without a page table. The oracle the engine's pool,
    scheduling and batching must not change."""
    return _model_loop
