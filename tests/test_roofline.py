"""Roofline machinery: HLO collective parsing, the scan-counted-once fact
that motivates the corrected measurement, CostVec algebra, model flops."""
import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config
from repro.models import count_active_params
from repro.roofline.analysis import (
    analyze,
    collective_bytes,
    model_flops_for,
    _shape_bytes,
)
from repro.roofline.measure import COLL_KINDS, CostVec

jax.config.update("jax_platform_name", "cpu")


def test_shape_bytes():
    assert _shape_bytes("bf16[256,4096]") == 256 * 4096 * 2
    assert _shape_bytes("f32[8]") == 32
    assert _shape_bytes("(f32[2,2], bf16[4])") == 16 + 8
    assert _shape_bytes("pred[]") == 1


def test_collective_parsing():
    hlo = """
  %all-reduce.1 = f32[128,128]{1,0} all-reduce(%dot), channel_id=1
  %ag = bf16[64,32]{1,0} all-gather(%x), dimensions={0}
  %ag2 = bf16[64,32]{1,0} all-gather-start(%x), dimensions={0}
  %p = f32[16]{0} collective-permute(%y), source_target_pairs={{0,1}}
  %nothing = f32[2,2]{1,0} add(%a, %b)
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 128 * 128 * 4
    assert got["all-gather"] == 2 * 64 * 32 * 2
    assert got["collective-permute"] == 64
    assert got["all-to-all"] == 0


def test_xla_counts_scan_body_once():
    """The documented XLA behavior the corrected measurement exists for."""
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)

    def scanned(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

    flops = jax.jit(scanned).lower(x, ws).compile().cost_analysis()["flops"]
    one = 2 * 64**3
    assert flops < 2 * one, "XLA started multiplying loop bodies: simplify!"


def test_costvec_algebra():
    a = CostVec(10, 100, {k: 1.0 for k in COLL_KINDS})
    b = CostVec(4, 40, {k: 0.5 for k in COLL_KINDS})
    c = (a - b) * 2 + b
    assert c.flops == 16 and c.bytes == 160
    assert all(v == 1.5 for v in c.colls.values())
    assert (b - a).clamp().flops == 0


def test_analyze_terms_and_bottleneck():
    rf = analyze(
        arch="x", shape="train_4k", mesh_name="single", n_chips=256,
        flops=197e12, byts=819e9 * 2, colls={"all-reduce": 50e9},
        model_flops=197e12 * 256 * 0.5,
    )
    assert abs(rf.compute_s - 1.0) < 1e-6
    assert abs(rf.memory_s - 2.0) < 1e-6
    assert abs(rf.collective_s - 1.0) < 1e-6
    assert rf.bottleneck == "memory"
    assert abs(rf.roofline_frac - 0.25) < 1e-6


def test_model_flops_scaling():
    cfg = get_config("mistral-nemo-12b")
    n = count_active_params(cfg)
    t = model_flops_for(cfg, SHAPES["train_4k"], n)
    p = model_flops_for(cfg, SHAPES["prefill_32k"], n)
    d = model_flops_for(cfg, SHAPES["decode_32k"], n)
    tokens_train = 256 * 4096
    assert t > 6.0 * n * tokens_train          # fwd+bwd + attention term
    assert p > 2.0 * n * 32 * 32768
    assert d > 2.0 * n * 128                   # one token per sequence
    assert d < t
