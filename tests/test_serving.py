"""Serving engine: continuous batching, ragged lean scheduling, backend
equivalence (lean kernel / fixed-split kernel / reference all produce the
same tokens — exact attention everywhere, only the schedule differs)."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serving.config import EngineConfig
from repro.serving.engine import DecodeEngine, Request

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, n=3, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, 8 + 7 * i),
            max_new_tokens=6,
        )
        for i in range(n)
    ]


def test_engine_generates_and_drains(setup):
    cfg, params = setup
    eng = DecodeEngine(
        cfg, params, config=EngineConfig(max_batch=2, cache_len=64)
    )
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    stats = eng.run_to_completion(max_ticks=50)
    # every request got its full budget (1 from prefill + rest from ticks)
    assert all(len(r.generated) == 6 for r in reqs)
    assert stats.prefills == 3
    assert not eng.queue and not any(eng.slot_req)


def test_engine_backends_token_identical(setup):
    cfg, params = setup
    outs = {}
    for backend in ("ref", "lean", "fixed"):
        eng = DecodeEngine(cfg, params, config=EngineConfig(
            max_batch=2, cache_len=64, attn_backend=backend, num_workers=8,
        ))
        reqs = _requests(cfg)
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion(max_ticks=50)
        outs[backend] = [tuple(r.generated) for r in reqs]
    assert outs["ref"] == outs["lean"], "lean backend diverged"
    assert outs["ref"] == outs["fixed"], "fixed-split backend diverged"


def test_fast_path_steady_state_zero_schedule_builds(setup):
    """Acceptance: a steady-state decode tick with the lean backend does no
    numpy schedule work — every tick after warmup is a schedule-cache hit
    (the jitted step replays under the same schedule signature)."""
    cfg, params = setup
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=2, cache_len=64, attn_backend="lean", num_workers=8,
    ))
    for r in _requests(cfg):
        eng.submit(r)
    eng.run_to_completion(max_ticks=50)
    st = eng.stats.schedule_cache
    assert st["misses"] <= 2           # admission-shape warmup only
    assert st["hits"] >= eng.stats.ticks - st["misses"]
    assert st["hit_rate"] > 0.5


def test_fused_and_two_phase_engine_tokens_identical(setup):
    cfg, params = setup
    outs = {}
    for fused in (True, False):
        eng = DecodeEngine(cfg, params, config=EngineConfig(
            max_batch=2, cache_len=64, attn_backend="lean", num_workers=8,
            fused=fused,
        ))
        reqs = _requests(cfg)
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion(max_ticks=50)
        outs[fused] = [tuple(r.generated) for r in reqs]
    assert outs[True] == outs[False]


def test_ragged_schedules_are_balanced(setup):
    """Every tick's lean schedule gives each worker the same tile count
    (the paper's Fig. 6 property) despite ragged slot lengths."""
    cfg, params = setup
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=3, cache_len=64, num_workers=8,
    ))
    for r in _requests(cfg):
        eng.submit(r)
    eng.run_to_completion(max_ticks=50)
    assert eng.stats.schedules
    for s in eng.stats.schedules:
        # stream-K invariant: workers hold at most tiles_per_worker, and
        # the total matches the ragged workload exactly
        assert s["total_tiles"] <= 8 * s["tiles_per_worker"]
        assert s["pieces"] >= len(s["lens"])  # >= one piece per segment
