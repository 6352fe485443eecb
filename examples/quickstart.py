"""Quickstart: LeanAttention in four acts.

  PYTHONPATH=src python examples/quickstart.py

1. The associative softmax re-scaling merge (the paper's theorem).
2. A stream-K LeanSchedule over a ragged decode batch.
3. The Pallas lean kernel vs the oracle (interpret mode on CPU).
4. FA2 / FlashDecoding recovered as special cases of the lean schedule.
"""
import jax.numpy as jnp
import numpy as np

from repro.core import (
    chunk_partial, finalize, make_schedule, merge, mha_decode_ref,
)
from repro.compile_cache import use_compile_cache
from repro.kernels import lean_decode

use_compile_cache()

rng = np.random.default_rng(0)
B, Hq, Hkv, S, d = 2, 8, 4, 1000, 64
q = jnp.asarray(rng.standard_normal((B, Hq, d)), jnp.float32)
k = jnp.asarray(rng.standard_normal((B, Hkv, S, d)), jnp.float32)
v = jnp.asarray(rng.standard_normal((B, Hkv, S, d)), jnp.float32)

# --- 1. unequal chunks merge to exact attention -------------------------
scale = 1.0 / np.sqrt(d)
qg = q.reshape(B, Hkv, 2, d)
a = chunk_partial(qg, k[:, :, :137], v[:, :, :137], scale)
b = chunk_partial(qg, k[:, :, 137:], v[:, :, 137:], scale)
merged = finalize(merge(a, b)).reshape(B, Hq, d)
ref = mha_decode_ref(q, k, v)
print("1. unequal-chunk merge err:", float(jnp.max(jnp.abs(merged - ref))))

# --- 2. a ragged stream-K schedule ---------------------------------------
lens = [1000, 300]
sched = make_schedule(lens, Hkv, tile_size=128, num_workers=6)
print(f"2. ragged schedule: {sched.total_tiles} LeanTiles over "
      f"{sched.num_workers} workers x {sched.tiles_per_worker} tiles, "
      f"{sched.num_pieces} pieces to merge")

# --- 3. the Pallas stream-K kernel ---------------------------------------
out = lean_decode(q, k, v, lens, num_workers=6, tile=128, interpret=True)
ref_r = mha_decode_ref(q, k, v, ctx_lens=jnp.asarray(lens, jnp.int32))
print("3. lean kernel vs oracle err:", float(jnp.max(jnp.abs(out - ref_r))))

# --- 4. FA2 / FlashDecoding as special cases ------------------------------
segs = B * Hkv
for name, G in [("FA2-like (G=segments)", segs),
                ("FlashDecoding-like (G=2*segments)", 2 * segs),
                ("lean (G=hardware width)", 13)]:
    o = lean_decode(q, k, v, num_workers=G, tile=128, interpret=True)
    print(f"4. {name}: err={float(jnp.max(jnp.abs(o - ref))):.2e}")
