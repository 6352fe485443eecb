"""End-to-end system behaviour: the full train->checkpoint->restart->serve
lifecycle on a reduced config, exercising the public API surface."""
import shutil
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, batch_at
from repro.models import init_params
from repro.serving.config import EngineConfig
from repro.serving.engine import DecodeEngine, Request
from repro.training.checkpoint import restore_checkpoint, save_checkpoint
from repro.training.optimizer import OptConfig, adamw_init
from repro.training.train_loop import make_train_step

jax.config.update("jax_platform_name", "cpu")


def test_train_checkpoint_restart_serve_lifecycle():
    cfg = get_smoke_config("gemma3-4b")
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, OptConfig(lr=3e-3, warmup_steps=2)))

    losses = []
    for i in range(12):
        b = {k: jnp.asarray(v) for k, v in batch_at(dcfg, i).items()}
        params, opt, m = step(params, opt, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses

    tmp = Path(tempfile.mkdtemp())
    try:
        save_checkpoint(tmp, 12, {"params": params, "opt": opt},
                        extra={"data_step": 12})
        # "crash": restore into fresh trees and keep training
        fresh_p = init_params(jax.random.PRNGKey(99), cfg)
        state, extra = restore_checkpoint(
            tmp, {"params": fresh_p, "opt": adamw_init(fresh_p)}
        )
        assert extra["data_step"] == 12
        params2 = state["params"]
        b = {k: jnp.asarray(v) for k, v in batch_at(dcfg, 12).items()}
        _, _, m2 = step(params2, state["opt"], b)
        assert np.isfinite(float(m2["loss"]))

        # serve the trained weights
        eng = DecodeEngine(
            cfg, params2, config=EngineConfig(max_batch=2, cache_len=64)
        )
        reqs = [Request(uid=i, prompt=np.arange(5 + i) % cfg.vocab_size,
                        max_new_tokens=4) for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion(max_ticks=30)
        assert all(len(r.generated) == 4 for r in reqs)
    finally:
        shutil.rmtree(tmp)
