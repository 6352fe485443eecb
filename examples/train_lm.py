"""Train a ~small LM for a few hundred steps on CPU (reduced mistral-nemo
config family), with checkpoint/restart demonstrated mid-run.

  PYTHONPATH=src python examples/train_lm.py
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CKPT = Path("/tmp/repro_train_lm_ckpt")

if CKPT.exists():
    shutil.rmtree(CKPT)

base = [sys.executable, "-m", "repro.launch.train", "--arch",
        "mistral-nemo-12b", "--smoke", "--batch", "8", "--seq", "64",
        "--ckpt-dir", str(CKPT), "--ckpt-every", "50", "--log-every", "20"]
env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin"}
if "JAX_COMPILATION_CACHE_DIR" in os.environ:       # the children's cache
    env["JAX_COMPILATION_CACHE_DIR"] = os.environ["JAX_COMPILATION_CACHE_DIR"]

print("== phase 1: 100 steps ==")
subprocess.run(base + ["--steps", "100"], check=True, env=env)
print("== phase 2: resume (simulated restart) + 100 steps ==")
subprocess.run(base + ["--steps", "100"], check=True, env=env)
print("training with restart complete; checkpoints in", CKPT)
