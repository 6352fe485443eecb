"""The names a profile finds the program's device work by: every Pallas
kernel passes ``name=``, and each jitted engine step compiles to a module
named after its function, with the model step's regions scoped
(``kv_pool_io``, ``attn``, ``ffn``, ``unembed``) in the op metadata."""
import ast
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.kernels
from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serving.config import EngineConfig, PagedConfig, SpecConfig
from repro.serving.engine import DecodeEngine
from repro.serving.scheduler import Scheduler, SchedulerConfig

jax.config.update("jax_platform_name", "cpu")

SCOPES = ("kv_pool_io", "attn", "ffn", "unembed")


def _pallas_calls():
    for path in sorted(Path(repro.kernels.__file__).parent.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                yield path.name, node


def test_every_pallas_call_passes_a_name():
    names = {}
    for fname, node in _pallas_calls():
        kw = {k.arg: k.value for k in node.keywords}
        assert "name" in kw, f"{fname}:{node.lineno} pallas_call has no name="
        names[f"{fname}:{node.lineno}"] = ast.literal_eval(kw["name"])
    assert len(names) == 8
    assert len(set(names.values())) == len(names), names


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("mistral-nemo-12b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _last_call(smoke, attr, spec):
    """Serve two requests through the scheduler and return the engine's
    ``attr`` jitted step with the shapes of its last call."""
    cfg, params = smoke
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=2, cache_len=64, num_workers=4, attn_backend="lean",
        paged=PagedConfig(enabled=True, page_size=8),
        spec=SpecConfig(enabled=spec, k=2),
    ))
    step, calls = getattr(eng, attr), []

    def spy(*args, **kw):
        calls.append((args, kw))
        return step(*args, **kw)

    setattr(eng, attr, spy)
    sch = Scheduler(eng, SchedulerConfig(chunk_size=8, token_budget=16))
    rng = np.random.default_rng(0)
    for _ in range(2):
        sch.submit(rng.integers(0, cfg.vocab_size, 12), 4)
    sch.run_to_completion(max_steps=40)
    assert calls, f"{attr} never ran"
    args, kw = calls[-1]
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args
    )
    return step, shapes, kw


@pytest.mark.parametrize("attr,module,spec", [
    ("_jit_kernel_step_paged", "jit__kernel_decode_step_paged", False),
    ("_jit_prefill_chunks", "jit__chunk_prefill_step", False),
    ("_jit_spec_verify", "jit__spec_verify_step", True),
])
def test_engine_steps_compile_to_named_modules_with_scoped_regions(
        smoke, attr, module, spec):
    step, shapes, kw = _last_call(smoke, attr, spec)
    hlo = step.lower(*shapes, **kw).compile().as_text()
    assert f"HloModule {module}" in hlo
    assert "jit__unknown" not in hlo
    for scope in SCOPES:
        assert f"/{scope}/" in hlo, f"no op in scope {scope}"


def test_engine_marks_its_cache_build_and_publishes_the_pool_size(smoke, tmp_path):
    from jax.profiler import ProfileData

    cfg, params = smoke
    with jax.profiler.trace(str(tmp_path)):
        eng = DecodeEngine(cfg, params, config=EngineConfig(
            max_batch=2, cache_len=64, attn_backend="lean",
            paged=PagedConfig(enabled=True, page_size=8, num_pages=11),
        ))
    xplane = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    marks = {ev.name for plane in ProfileData.from_file(str(xplane)).planes
             if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events}
    assert "engine.init_cache" in marks
    pools = [leaf for pattern_c in eng.cache for lc in pattern_c
             for key, leaf in lc.items() if key in ("k", "v")]
    assert pools and all(p.shape[1] == 11 for p in pools)
    md = eng.metrics.as_dict()
    assert md["engine_kv_pool_bytes"]["value"] == sum(p.nbytes for p in pools)
    assert md["engine_kv_pages_total"]["value"] == 11
