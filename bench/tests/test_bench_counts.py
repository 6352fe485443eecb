"""FLOP and byte counts against hand counts at both configurations."""
import json
from pathlib import Path

import pytest

from bench.harness import counts
from bench.harness.device import peaks_for

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# by hand: attention q,k,v,o + three SwiGLU matrices + two norms
@pytest.mark.parametrize("name,layer,unembed,kv_token", [
    ("mistral-nemo-12b",
     5120 * 4096 + 2 * 5120 * 1024 + 4096 * 5120 + 3 * 5120 * 14336 + 2 * 5120,
     5120 * 131072, 2 * 8 * 8 * 128 * 2),
    ("yi-34b-200k",
     7168 * 7168 + 2 * 7168 * 1024 + 7168 * 7168 + 3 * 7168 * 20480 + 2 * 7168,
     7168 * 64000, 2 * 4 * 8 * 128 * 2),
])
def test_params_and_kv_bytes(name, layer, unembed, kv_token):
    c = cfg(name)
    assert counts.layer_params(c) == layer
    assert counts.unembed_params(c) == unembed
    assert counts.kv_bytes_per_token(c) == kv_token
    assert counts.step_weight_params(c) == c["num_hidden_layers"] * layer + unembed


def test_layer_totals_match_the_published_sizes():
    assert counts.layer_params(cfg("mistral-nemo-12b")) * 8 == 2_181_120_000
    assert counts.layer_params(cfg("yi-34b-200k")) * 4 == 2_231_427_072


@pytest.mark.parametrize("name,hq,hkv", [("mistral-nemo-12b", 32, 8), ("yi-34b-200k", 56, 8)])
def test_decode_attention_counts_the_true_context(name, hq, hkv):
    c = cfg(name)
    flops, nbytes = counts.decode_attention(c, [10, 20])
    assert flops == 4 * 30 * hq * 128
    assert nbytes == 2 * 30 * hkv * 128 * 2 + 2 * 2 * hq * 128 * 2


def test_decode_step_flops_and_bytes():
    c = cfg("mistral-nemo-12b")
    ctx = [1000, 3000]
    w = counts.step_weight_params(c)
    assert counts.decode_step_flops(c, ctx) == 2 * w * 2 + 8 * 4 * 4000 * 32 * 128
    assert counts.decode_step_min_bytes(c, ctx) == 2 * w + 4000 * 32768


def test_roofline_takes_the_larger_bound():
    p = peaks_for("TPU v5 lite")
    assert counts.roofline_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert counts.roofline_seconds(1.0, 819e9, p) == pytest.approx(1.0)
