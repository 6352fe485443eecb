"""Distributed correctness on an 8-CPU-device host mesh (subprocess — the
device-count flag must be set before jax initializes; the main pytest
process stays single-device)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path


ROOT = Path(__file__).resolve().parent.parent


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sp_decode_all_plans_match_reference():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import sp_decode_attention
        from repro.core.attention import mha_decode_ref
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        B, Hq, Hkv, S, d = 8, 4, 2, 64, 16
        q = jnp.asarray(rng.standard_normal((B, Hq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, Hkv, S, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, Hkv, S, d)), jnp.float32)
        ctx = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
        ref = mha_decode_ref(q, k, v, ctx_lens=ctx)
        for kw in (
            dict(seq_axis=("model",), batch_axis="data"),
            dict(seq_axis=("data",), batch_axis=None),
            dict(seq_axis=("data", "model"), batch_axis=None),
        ):
            out = sp_decode_attention(q, k, v, mesh, head_axis="model",
                                      ctx_len=ctx, **kw)
            err = float(jnp.max(jnp.abs(out - ref)))
            assert err < 1e-5, (kw, err)
        print("ok")
    """)


def test_sharded_train_step_matches_single_device():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.models import ModelConfig, init_params
        from repro.training.optimizer import OptConfig, adamw_init
        from repro.training.train_loop import make_train_step
        from repro.distributed.sharding import param_specs, batch_specs, to_named
        from repro.distributed.hints import activation_mesh
        from repro.launch.mesh import make_host_mesh

        cfg = ModelConfig(name="t", d_model=32, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
            stages=((("attn",), 2),), attn_q_chunk=0, loss_chunk=0)
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        batch = {"tokens": toks}
        step = make_train_step(cfg, OptConfig(lr=1e-2, warmup_steps=1))

        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        mesh = make_host_mesh((4, 2), ("data", "model"))
        pspec = param_specs(params, mesh, cfg)
        put = lambda t, s: jax.device_put(t, NamedSharding(mesh, s))
        params_s = jax.tree.map(put, params, pspec,
            is_leaf=lambda x: isinstance(x, P))
        opt_s = {"m": jax.tree.map(put, opt["m"], pspec,
                    is_leaf=lambda x: isinstance(x, P)),
                 "v": jax.tree.map(put, opt["v"], pspec,
                    is_leaf=lambda x: isinstance(x, P)),
                 "step": opt["step"]}
        batch_s = {"tokens": jax.device_put(
            toks, NamedSharding(mesh, P("data", None)))}
        with activation_mesh(mesh):
            p2, o2, m2 = jax.jit(step)(params_s, opt_s, batch_s)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-3
        # bf16 reduction-order noise can flip near-ties in Adam updates;
        # bound the bulk of the parameters instead of every element
        deltas = [float(jnp.mean(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
                  for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2))]
        assert max(deltas) < 5e-3, max(deltas)
        print("ok")
    """)


def test_elastic_checkpoint_restore_across_mesh_shapes():
    run_sub("""
        import tempfile, shutil
        from pathlib import Path
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.models import ModelConfig, init_params
        from repro.training.checkpoint import save_checkpoint, restore_checkpoint
        from repro.distributed.sharding import param_specs, to_named

        cfg = ModelConfig(name="t", d_model=32, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
            stages=((("attn",), 2),))
        params = init_params(jax.random.PRNGKey(0), cfg)
        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        spec = param_specs(params, mesh_a, cfg)
        put = lambda t, s: jax.device_put(t, NamedSharding(mesh_a, s))
        params_a = jax.tree.map(put, params, spec,
            is_leaf=lambda x: isinstance(x, P))

        tmp = Path(tempfile.mkdtemp())
        try:
            save_checkpoint(tmp, 1, params_a)
            # restore onto a DIFFERENT mesh shape (elastic rescale)
            mesh_b = jax.make_mesh((2, 4), ("data", "model"))
            spec_b = param_specs(params, mesh_b, cfg)
            sh_b = to_named(spec_b, mesh_b)
            restored, _ = restore_checkpoint(tmp, params, shardings=sh_b)
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        finally:
            shutil.rmtree(tmp)
        print("ok")
    """)


def test_pipeline_parallel_matches_sequential():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_forward, bubble_fraction
        mesh = jax.make_mesh((4, 2), ("pod", "data"))
        n_stages, M, mb, L, D = 4, 6, 2, 8, 16
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.standard_normal((n_stages, D, D)) * 0.1, jnp.float32)
        x = jnp.asarray(rng.standard_normal((M, mb, L, D)), jnp.float32)

        def fn_stage(w, x, stage_idx):
            return jnp.tanh(x @ w)

        out = pipeline_forward(fn_stage, ws, x, mesh, axis="pod")
        ref = x
        for s in range(n_stages):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        assert 0 < bubble_fraction(4, 6) < 1
        print("ok")
    """)
