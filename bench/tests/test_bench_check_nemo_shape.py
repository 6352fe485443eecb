"""The check that decides ``correct``, on Mistral-NeMo-12B's shape at a size
a test can hold: the query width (4 heads x 32) is not d_model (64), 4 query
heads share one KV head, and the unembedding is untied. As on the tiny
configuration of :mod:`bench.tests.test_bench_check`, the whole run passes
the program as it is and fails each fault a served cell can have."""
import time

import pytest

from bench.harness import serve
from bench.tests.test_bench_check import (  # noqa: F401  (jax_config is a fixture)
    KNOBS,
    LIMIT,
    MIX,
    PEAKS,
    SEED,
    TINY,
    break_step,
    jax_config,
    tiny_cell,
)

NEMO_SHAPED = {**TINY, "name": "nemo-shaped", "num_attention_heads": 4,
               "num_key_value_heads": 1, "head_dim": 32}


@pytest.mark.parametrize("fault", [None, "token", "state", "half", "int8_kv"])
def test_correct_holds_on_the_nemo_shape_and_fails_each_fault(fault, monkeypatch, jax_config):
    knobs = KNOBS
    if fault == "int8_kv":
        knobs = {**KNOBS, "kv_dtype": "int8"}
    elif fault is not None:
        break_step(monkeypatch, fault)
    cell = tiny_cell()
    cell = cell.__class__(cell.name, 1, NEMO_SHAPED, MIX, knobs, cell.end_to_end,
                          cell.per_layer)
    res = serve.run(cell, SEED, 1.0, False, t_process=time.perf_counter(),
                    require_chip=False, peaks=PEAKS)
    assert res["correct"] is (fault is None), res["check"]
    assert res["check"]["widest_gap"]["limit"] == LIMIT
    assert (res["check"]["kv_dtype_mismatches"]["value"] > 0) is (fault == "int8_kv")
