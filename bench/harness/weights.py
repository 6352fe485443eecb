"""Random weights on the device, from the run seed, in the served dtype.

The tree's structure and shapes are the program's own (``jax.eval_shape`` of
its ``init_params``); every value is the benchmark's: one jitted call draws
each tensor from the seed and casts it to bf16 where it is made, so no whole
float32 copy of the model ever exists.

Scales (chosen by the benchmark, and the reference reads the same arrays):
token embedding 0.02; q and k projections 2/sqrt(fan_in), so that attention
scores spread with a standard deviation of about 4 and a query attends to a
few keys rather than to an even average of a long context; every other
matrix 1/sqrt(fan_in); norm gains 1 + N(0, 0.1) in the program's ``1 + w``
form.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

QK_SCALE = 2.0
NORM_STD = 0.1
EMBED_STD = 0.02


def run_key(seed: int):
    """A key that tells apart every seed up to 64 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _init_leaf(name: str, shape, key, dtype):
    if len(shape) == 0:
        return jnp.zeros(shape, dtype)
    normal = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        return (normal * EMBED_STD).astype(dtype)
    if "norm" in name or name.startswith("ln"):
        return (normal * NORM_STD).astype(dtype)
    if len(shape) < 2:
        return jnp.zeros(shape, dtype)
    scale = (QK_SCALE if name in ("wq", "wk") else 1.0) / (shape[-2] ** 0.5)
    return (normal * scale).astype(dtype)


def make_weights(init_params, model_cfg, seed: int, dtype=jnp.bfloat16):
    """Weights of ``model_cfg`` in ``dtype``, on the default device, drawn
    from ``seed``. ``init_params(rng, cfg)`` gives the tree's structure."""
    shapes = jax.eval_shape(lambda k: init_params(k, model_cfg), jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = [
            _init_leaf(_leaf_name(path), sds.shape, jax.random.fold_in(key, i), dtype)
            for i, (path, sds) in enumerate(leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(run_key(seed))
