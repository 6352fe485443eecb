"""Mini dry-run in a subprocess: lower+compile one small cell on an 8-device
host mesh exercising exactly the production build path (the full 512-device
matrix runs via ``python -m repro.launch.dryrun``; this is its fast guard)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_build_cell_small_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.launch.dryrun import build_cell
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.hints import activation_mesh
        from repro.distributed.sharding import choose_layout, dp_axes
        from repro.configs import get_smoke_config, SHAPES
        import repro.configs.shapes as shp
        import dataclasses

        # a reduced decode cell on a (4,2) mesh: same code path as the
        # production 16x16 dry-run
        mesh = make_host_mesh((4, 2), ("data", "model"))
        cfg = get_smoke_config("mistral-nemo-12b")
        cfg = dataclasses.replace(cfg, n_heads=4, n_kv_heads=2)
        shp.SHAPES = dict(shp.SHAPES)
        shp.SHAPES["tiny_decode"] = shp.ShapeSpec("tiny_decode", "decode", 64, 8)
        import repro.launch.dryrun as dr
        dr.SHAPES = shp.SHAPES
        layout = "2d"
        with activation_mesh(mesh, dp=dp_axes(mesh, layout)):
            lowered = build_cell(cfg, "tiny_decode", mesh, layout)
            compiled = lowered.compile()
        ca = compiled.cost_analysis()
        assert ca["flops"] > 0
        ma = compiled.memory_analysis()
        assert ma.argument_size_in_bytes > 0
        print("ok")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "ok" in r.stdout
