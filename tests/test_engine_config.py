"""EngineConfig: the engine's one constructor argument. Its defaults build
a paged ``ref`` engine, its sinks thread through, and the page pool is the
only KV layout it accepts.
"""
import jax
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serving.config import EngineConfig, ObsConfig, PagedConfig
from repro.serving.engine import DecodeEngine

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_config_defaults_are_paged_ref_engine(setup):
    cfg, params = setup
    eng = DecodeEngine(cfg, params, config=EngineConfig())
    assert eng.pool is not None and eng.spec_k == 0 and not eng.cascade
    assert eng.attn_backend == "ref" and eng.prefix_cache is None
    # default pool: every slot's full page run plus the null page
    assert eng.pool.num_pages == 1 + eng.max_batch * eng.pages_per_slot
    assert eng.config == EngineConfig()


def test_paged_config_disabled_is_rejected():
    with pytest.raises(ValueError, match="one KV layout"):
        PagedConfig(enabled=False)
    assert PagedConfig(enabled=True) == PagedConfig()


def test_obs_config_threads_sinks(setup):
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import Tracer

    cfg, params = setup
    tracer, metrics = Tracer(), MetricsRegistry()
    eng = DecodeEngine(
        cfg, params,
        config=EngineConfig(obs=ObsConfig(tracer=tracer, metrics=metrics)),
    )
    assert eng.tracer is tracer and eng.metrics is metrics
