"""The main-path stream-K kernels compile for a TPU v5e at Mistral-NeMo-12B
attention widths (32 q / 8 kv heads, head_dim 128, bf16 or int8 KV, 8
sequences of 4k context in 128-token pages), and the fused decode kernel
compiles at the largest batch its VMEM gate admits. The engine's paged
decode and chunked-prefill steps, at Yi-34B widths, compile to programs
that never copy a layer's KV pool or the stack of them.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
*described* v5e chip, which refuses what Mosaic cannot lower (block shapes
off the (8, 128) tiling, unsupported in-kernel gathers and slices) that
interpret mode on the CPU accepts. The topology is described inside the
fixture, never at import, so every test worker collects the same tests and
only the one running this file loads the TPU compiler.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.leantile import (
    ScheduleCache,
    cascade_fused_descriptors,
    make_cascade_schedule,
    make_chunk_schedule,
    make_schedule,
)
from repro.kernels.lean_decode import (
    fused_grid,
    lean_cascade_fused,
    lean_decode_fused,
    lean_decode_paged_fused,
    lean_decode_paged_partials,
    lean_decode_partials,
    lean_merge_pallas,
)
from repro.kernels.lean_prefill import lean_prefill_chunk_partials
from repro.kernels.ops import decode_uses_fused
from repro.models import ModelConfig, init_paged_cache, init_params
from repro.serving import engine

B, HQ, HKV, D, CTX, PAGE, WORKERS = 8, 32, 8, 128, 4096, 128, 16
GQ = HQ // HKV
SEG = B * HKV
ROWS = (B * CTX // PAGE + 1) * HKV          # pool rows: (page, kv head)
SCALE = 1.0 / np.sqrt(D)
CHUNK, PACK = 256, 4                        # chunk-prefill pack geometry


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            jax.config.update("jax_enable_compilation_cache", cache_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


SCHED = make_schedule([CTX] * B, HKV, PAGE, WORKERS)
Q = ((SEG, GQ, D), jnp.bfloat16)
KV_DENSE = ((SEG, CTX, D), jnp.bfloat16)
KV_ROWS = ((ROWS, PAGE, D), jnp.bfloat16)
CTX_ARG = ((SEG,), jnp.int32)
ROWS8 = ((ROWS, PAGE, D), jnp.int8)
SCALES = ((ROWS, 1), jnp.float32)


def test_dense_two_phase_decode(one_chip):
    _compile(
        one_chip,
        lambda q, k, v, c: lean_decode_partials(q, k, v, c, SCHED, SCALE),
        Q, KV_DENSE, KV_DENSE, CTX_ARG,
    )


def test_dense_fused_decode(one_chip):
    _compile(
        one_chip,
        lambda q, k, v, c: lean_decode_fused(q, k, v, c, SCHED, SCALE),
        Q, KV_DENSE, KV_DENSE, CTX_ARG,
    )


def _fused_routes(sched, kv_elem_bytes):
    """Route operand of the fused kernel: one pool row per iteration of
    its padded grid, ``steps * n``."""
    steps, n = fused_grid(sched, D, kv_elem_bytes)
    return ((steps * n,), jnp.int32)


def test_paged_fused_decode(one_chip):
    _compile(
        one_chip,
        lambda q, k, v, c, r: lean_decode_paged_fused(
            q, k, v, c, r, SCHED, SCALE
        ),
        Q, KV_ROWS, KV_ROWS, CTX_ARG, _fused_routes(SCHED, 2),
    )


# The benchmark cells' decode shapes, 300 tokens into the window: their
# mixes' shape_seed 0 context lengths, pools and widths (PERF.md section 4)
CELLS = {
    "yi34b-decode-fewlong": (
        [53240, 32002, 23303, 22527], 7, 1228, 59648),
    "nemo12b-decode-long": (
        [10116, 3655, 1938, 1811, 16493, 21732, 9300, 13075, 7810, 23120,
         16612, 1743, 18640, 1899, 13081, 2815], 4, 1802, 27264),
}


@pytest.mark.parametrize("n", [2, 8])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_paged_fused_decode_at_the_cells_shapes(one_chip, cell, n):
    """The fused kernel at the cells' decode shapes (S_seg 32 at gq 7, and
    128 at gq 4) over bf16 pools, at its default 2 iterations a grid step
    and at 8, is under the VMEM gate and compiles."""
    lens, gq, pages, cache_len = CELLS[cell]
    sched = ScheduleCache().get(
        [L + 300 for L in lens], HKV, PAGE, WORKERS, max_len=cache_len)
    assert fused_grid(sched, D, 2)[1] == 2
    assert decode_uses_fused(sched, gq, D, kv_elem_bytes=2)
    seg, rows = len(lens) * HKV, ((pages * HKV, PAGE, D), jnp.bfloat16)
    _compile(
        one_chip,
        lambda q, k, v, c, r: lean_decode_paged_fused(
            q, k, v, c, r, sched, SCALE, iters_per_step=n
        ),
        ((seg, gq, D), jnp.bfloat16), rows, rows, ((seg,), jnp.int32),
        ((len(sched.fused_descriptors(n)[0]),), jnp.int32),
    )


def test_int8_paged_two_phase_decode(one_chip):
    _compile(
        one_chip,
        lambda q, k, v, c, r, ks, vs: lean_decode_paged_partials(
            q, k, v, c, r, SCHED, SCALE, k_scales=ks, v_scales=vs
        ),
        Q, ROWS8, ROWS8, CTX_ARG, ((SCHED.grid_iters,), jnp.int32),
        SCALES, SCALES,
    )


def test_int8_paged_fused_decode(one_chip):
    _compile(
        one_chip,
        lambda q, k, v, c, r, ks, vs: lean_decode_paged_fused(
            q, k, v, c, r, SCHED, SCALE, k_scales=ks, v_scales=vs
        ),
        Q, ROWS8, ROWS8, CTX_ARG, _fused_routes(SCHED, 1), SCALES, SCALES,
    )


# Short contexts keep the scalar-prefetched descriptors inside SMEM, so
# the batch reaches the VMEM gate: at 512 tokens it admits 250 sequences.
GATE_CTX = 512


def _gate_schedule(batch):
    return make_schedule([GATE_CTX] * batch, HKV, PAGE, WORKERS)


def _largest_fused_batch():
    b = 1
    while decode_uses_fused(_gate_schedule(b + 1), GQ, D, kv_elem_bytes=2):
        b += 1
    return b


@pytest.mark.parametrize("above", [False, True], ids=["at_gate", "above_gate"])
def test_paged_decode_at_the_vmem_gate(one_chip, above):
    """At the largest batch the gate admits, the fused kernel compiles; one
    sequence more, the gate picks two-phase, which compiles there."""
    batch = _largest_fused_batch() + above
    sched = _gate_schedule(batch)
    assert decode_uses_fused(sched, GQ, D, kv_elem_bytes=2) is not above
    seg = batch * HKV
    rows = ((batch * GATE_CTX // PAGE + 1) * HKV, PAGE, D)
    kernel = lean_decode_paged_partials if above else lean_decode_paged_fused
    routes = (
        ((sched.grid_iters,), jnp.int32) if above
        else _fused_routes(sched, 2)
    )
    _compile(
        one_chip,
        lambda q, k, v, c, r: kernel(q, k, v, c, r, sched, SCALE),
        ((seg, GQ, D), jnp.bfloat16), (rows, jnp.bfloat16),
        (rows, jnp.bfloat16), ((seg,), jnp.int32), routes,
    )


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_chunk_prefill(one_chip, kv):
    sched = make_chunk_schedule([4 * CHUNK] * PACK, HKV, PAGE, WORKERS)
    seg = ((PACK * HKV,), jnp.int32)
    pool, scales = (KV_ROWS, ()) if kv == "bf16" else (ROWS8, (SCALES,) * 2)
    _compile(
        one_chip,
        lambda q, k, v, c, qs, r, *sc: lean_prefill_chunk_partials(
            q, k, v, c, qs, r, sched, SCALE, chunk_cap=CHUNK,
            k_scales=sc[0] if sc else None, v_scales=sc[1] if sc else None,
        ),
        ((PACK * HKV, GQ * CHUNK, D), jnp.bfloat16), pool, pool,
        seg, seg, ((sched.grid_iters,), jnp.int32), *scales,
    )


def test_merge_pallas(one_chip):
    P = SCHED.num_pieces
    _compile(
        one_chip,
        lambda o, m, l: lean_merge_pallas(o, m, l, SCHED),
        ((P, GQ, D), jnp.float32), ((P, GQ), jnp.float32),
        ((P, GQ), jnp.float32),
    )


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_cascade_fused_decode(one_chip, kv):
    # all 8 sequences share a 16-page system prompt
    cs, binding = make_cascade_schedule(
        [CTX] * B, [list(range(B))], [16], HKV, PAGE, WORKERS, max_len=CTX
    )
    desc = cascade_fused_descriptors(cs, binding)
    seg_tot = (cs.num_groups + B) * HKV
    pool, scales = (KV_ROWS, ()) if kv == "bf16" else (ROWS8, (SCALES,) * 2)
    _compile(
        one_chip,
        lambda q, k, v, c, r, de, *sc: lean_cascade_fused(
            q, k, v, c, r, de, cs, SCALE, GQ,
            k_scales=sc[0] if sc else None, v_scales=sc[1] if sc else None,
        ),
        ((seg_tot, cs.group_size * GQ, D), jnp.bfloat16), pool, pool,
        ((seg_tot,), jnp.int32), ((cs.fused_grid_iters,), jnp.int32),
        (desc.shape, jnp.int32), *scales,
    )


# Yi-34B's attention and FFN widths, 4 layers, 4 sequences, and the pool of
# the yi34b-decode-fewlong benchmark cell: 1228 pages of 128 tokens
YI = ModelConfig(
    name="yi-34b-4l", d_model=7168, n_layers=4, n_heads=56, n_kv_heads=HKV,
    head_dim=D, d_ff=20480, vocab_size=64000, stages=((("attn",), 4),),
    rope_theta=5e6, norm_eps=1e-5, tie_embeddings=False,
)
YI_BATCH, YI_PAGES, YI_WIDTH = 4, 1228, 420     # width: pages per sequence
POOL_ELEMS = YI_PAGES * HKV * PAGE * D          # one layer's K or V pool
WHOLE_POOL_OPS = ("copy", "dynamic-slice", "dynamic-update-slice")


def _pool_copies(hlo):
    """Instructions of an optimized HLO module that copy, slice or update a
    whole pool, one layer's or the stack's: ops of those kinds, and fusions
    named after them, whose result has as many elements."""
    found = []
    for m in re.finditer(
            r"%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(", hlo):
        name, dims, op = m.groups()
        elems = int(np.prod([int(x) for x in dims.split(",") if x]))
        if elems not in (POOL_ELEMS, YI.n_layers * POOL_ELEMS):
            continue
        if op in WHOLE_POOL_OPS or (
                op == "fusion" and any(k in name for k in WHOLE_POOL_OPS)):
            found.append(f"{name} = {op}[{dims}]")
    return found


@pytest.mark.parametrize("step", ["decode", "chunk_prefill"])
def test_model_step_keeps_the_pool_in_place(one_chip, step):
    """Every layer writes its tokens into its own pool in the stacked cache
    and the kernel reads its pages there: the compiled step holds no op
    that copies, slices out or writes back a whole pool."""
    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, jnp.bfloat16 if a.dtype == jnp.float32 else a.dtype,
                sharding=one_chip),
            tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), YI)))
    cache = sds(jax.eval_shape(lambda: init_paged_cache(
        YI, YI_BATCH, YI_WIDTH * PAGE, YI_PAGES, PAGE)))
    if step == "decode":
        sched = make_schedule([22528, 30000, 40000, 53248], HKV, PAGE, WORKERS)
        fn = functools.partial(
            engine._kernel_decode_step_paged, cfg=YI, backend="lean",
            sched=sched, num_splits=1, fused=True, interpret=False)
        args = (i32(YI_BATCH, 1), i32(YI_BATCH), i32(YI_BATCH, YI_WIDTH))
    else:
        sched = make_chunk_schedule([16 * CHUNK], HKV, PAGE, WORKERS)
        fn = functools.partial(
            engine._chunk_prefill_step, cfg=YI, backend="lean", sched=sched,
            interpret=False)
        args = (i32(1, CHUNK), i32(1), i32(1), i32(1, YI_WIDTH))
    hlo = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _pool_copies(hlo) == []
