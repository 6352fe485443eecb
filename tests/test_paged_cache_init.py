"""The paged cache is built pool-first: each attention layer's page pools
are made at their stacked shape ``(reps, num_pages, H_kv, page_size, hd)``,
and no dense per-slot attention row ``(batch, H_kv, cache_len, hd)`` and no
one-layer pool to broadcast exists while it is built. Building it then
needs no memory beyond the pools themselves.

Shapes keep Mistral-NeMo-12B's proportions at a size a test can hold: the
query width (8 x 16) differs from d_model, 4 query heads share each KV
head, and the slots' dense rows would outweigh the pool.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, init_paged_cache

jax.config.update("jax_platform_name", "cpu")

NEMO_LIKE = ModelConfig(
    name="nemo-like", d_model=160, n_layers=4, n_heads=8, n_kv_heads=2,
    head_dim=16, d_ff=448, vocab_size=512, stages=((("attn",), 4),),
    tie_embeddings=False,
)
BATCH, CACHE_LEN, PAGES, PAGE = 4, 96, 13, 8
DENSE_ROW = (BATCH, NEMO_LIKE.n_kv_heads, CACHE_LEN, NEMO_LIKE.head_dim)
POOL = (PAGES, NEMO_LIKE.n_kv_heads, PAGE, NEMO_LIKE.head_dim)
STACKED_POOL = (4,) + POOL


def _shapes(jaxpr):
    """The shape of every value a jaxpr makes, its sub-jaxprs' included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8, jnp.float8_e4m3fn])
def test_paged_cache_is_built_pool_first(kv_dtype):
    build = functools.partial(init_paged_cache, NEMO_LIKE, BATCH, CACHE_LEN,
                              PAGES, PAGE, kv_dtype=kv_dtype)
    shapes = list(_shapes(jax.make_jaxpr(build)().jaxpr))
    assert STACKED_POOL in shapes
    assert not [s for s in shapes if s[-4:] == DENSE_ROW], "dense attention row"
    assert not [s for s in shapes if s[-4:] == POOL and s != STACKED_POOL], \
        "a pool made at another shape than its stack's"

    leaves = jax.tree.leaves(jax.eval_shape(build))
    pools = [leaf for leaf in leaves if leaf.shape == STACKED_POOL]
    assert len(pools) == 2 and all(p.dtype == kv_dtype for p in pools)
    out_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
    mem = jax.jit(build).lower().compile().memory_analysis()
    page_bytes = int(np.prod(POOL[1:])) * jnp.dtype(kv_dtype).itemsize
    assert mem.temp_size_in_bytes < page_bytes
    # the outputs are the leaves, beside the output tuple's table of pointers
    assert 0 <= mem.output_size_in_bytes - out_bytes <= 8 * len(leaves)
