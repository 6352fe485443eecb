"""Traffic: a mix's data file and a seed, turned into requests.

A mix file (``bench/traffic/<name>.json``) holds parameters only. Its
``loop`` names how its requests reach the server: the module
``bench/loops/<loop>.py`` (see :mod:`bench.loops`), which draws the requests
from the mix and the seed, sizes the server and drives the window. A new mix
is a new data file; a new way of arriving is a new loop module.
"""
from __future__ import annotations

import importlib
import re
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

LOOPS_DIR = Path(__file__).resolve().parents[1] / "loops"


@dataclass(frozen=True)
class Request:
    prompt: np.ndarray          # (L,) int32 token ids
    max_new: int


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> List[int]:
    """``n`` lengths from ``spec`` (``dist`` ``loguniform`` over
    ``[min, max]``); with ``total`` they are then rescaled to sum to
    exactly ``total``."""
    if spec["dist"] != "loguniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.exp(rng.uniform(np.log(spec["min"]), np.log(spec["max"]), n))
    if "total" in spec:
        x = x * (spec["total"] / x.sum())
    lens = np.maximum(np.rint(x).astype(np.int64), 1)
    if "total" in spec:
        lens[np.argmax(lens)] += int(spec["total"]) - int(lens.sum())
    return [int(v) for v in lens]


def loop(name: str):
    """The loop module ``bench/loops/<name>.py``."""
    known = sorted(p.stem for p in LOOPS_DIR.glob("*.py") if p.stem != "__init__")
    if not re.fullmatch(r"[A-Za-z0-9_]+", str(name)) or name not in known:
        raise ValueError(f"unknown loop {name!r}; known: {known}")
    return importlib.import_module(f"bench.loops.{name}")


def generate(mix: dict, seed: int, vocab_size: int) -> List[Request]:
    """The mix's requests for run seed ``seed``, in submission order."""
    return loop(mix["loop"]).generate(mix, seed, vocab_size)
