"""Whether what the timed path served is correct: a comparison with the
plain reference.

Once the window has closed and the engine is freed, a sample of the served
sessions, drawn from the seed and always holding the longest, is run through
the reference once each: its prompt followed by every token the program
served for it. At each served position the gap is the reference's best logit
minus the reference's logit of the token the program served; a greedy
program that computes what the model computes serves its best token up to
rounding, so the widest gap stays small. The cell's file gives the limit.

The control puts the same reference, computed in a lower precision, in the
program's place: at each of the same positions its own best token is read
under the float32 reference the same way.
"""
from __future__ import annotations

import importlib
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np


def reference_module(cfg: dict):
    """The plain reference the configuration names, from ``bench/reference``."""
    return importlib.import_module(f"bench.reference.{cfg['reference']}")


def sample(served: Sequence[Tuple[np.ndarray, List[int]]], others: int, seed: int):
    """Indices of the sessions to check: the longest (prompt plus served
    tokens) and ``others`` more drawn from ``seed``."""
    total = [len(p) + len(g) for p, g in served]
    longest = int(np.argmax(total))
    rest = [i for i in range(len(served)) if i != longest and served[i][1]]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    pick = rng.choice(len(rest), size=min(others, len(rest)), replace=False)
    return [longest] + sorted(rest[int(i)] for i in pick)


def gaps(ref_logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """Best logit minus the logit of each token, row by row."""
    ref_logits = np.asarray(ref_logits, np.float64)
    got = ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    return ref_logits.max(axis=1) - got


def session_readings(weights, cfg: dict, prompt, generated,
                     control: Optional[str] = None) -> dict:
    """Gaps of one session: the program's served tokens, and with
    ``control`` the tokens that reference in that format puts first."""
    ref = reference_module(cfg)
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(generated[:-1], np.int32)])
    pos = np.arange(len(prompt) - 1, len(seq))
    want = ref.logits_at(weights, cfg, seq, pos)
    out = {"served": gaps(want, generated)}
    if control is not None:
        low = ref.logits_at(weights, cfg, seq, pos, fmt=control)
        out["control"] = gaps(want, low.argmax(axis=1))
    return out


def summary(gaps_: np.ndarray) -> dict:
    """Readings of one set of gaps: the widest, the mean, the 99th
    percentile and the share of tokens that are not the reference's best."""
    g = np.asarray(gaps_, np.float64)
    return {"widest": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.percentile(g, 99)), "not_best": float(np.mean(g > 0))}


def verdict(stats: dict, n_tokens: int, spec: dict):
    """Whether one set of readings (:func:`summary`) over ``n_tokens``
    compared tokens passes the cell's limits, and each number compared
    beside its limit. The program and the control are judged by this one
    function."""
    numbers = {"widest_gap": {"value": stats["widest"],
                              "limit": float(spec["widest_gap_limit"])},
               "tokens_compared": {"value": n_tokens,
                                   "limit": int(spec["min_tokens_compared"])}}
    ok = (numbers["widest_gap"]["value"] <= numbers["widest_gap"]["limit"]
          and n_tokens >= numbers["tokens_compared"]["limit"])
    return ok, numbers


def kv_dtype_mismatches(cache, dtype: str):
    """K and V leaves of the program's cache (dict keys ``k`` and ``v``)
    whose dtype is not ``dtype``, the configuration's: a pool stored in a
    lower precision serves the model in another format than the one stated.
    Returns the count and every dtype seen."""
    import jax

    bad, seen = 0, set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if getattr(path[-1], "key", None) in ("k", "v"):
            seen.add(str(leaf.dtype))
            bad += str(leaf.dtype) != dtype
    return bad, sorted(seen)


def check(weights, cfg: dict, served, spec: dict, seed: int,
          log: Callable[[str], None] = lambda s: None,
          control: Optional[str] = None) -> dict:
    """Compare the sample with the reference. ``correct`` and ``numbers``
    (each number compared beside its limit) judge the program; with
    ``control``, ``control_correct`` and ``control_numbers`` judge the
    control by the same :func:`verdict`."""
    idx = sample(served, int(spec["sessions_beside_longest"]), seed)
    served_gaps, control_gaps, n_tokens = [], [], 0
    for i in idx:
        prompt, gen = served[i]
        r = session_readings(weights, cfg, prompt, gen, control)
        served_gaps.append(r["served"])
        n_tokens += len(gen)
        if control is not None:
            control_gaps.append(r["control"])
        log(f"check: session {i}, {len(prompt)} prompt + {len(gen)} served "
            f"tokens, widest gap {float(r['served'].max())}")
    stats = {"program": summary(np.concatenate(served_gaps))}
    out = {"sessions": idx, "stats": stats}
    out["correct"], out["numbers"] = verdict(stats["program"], n_tokens, spec)
    if control is not None:
        stats["control"] = summary(np.concatenate(control_gaps))
        out["control_correct"], out["control_numbers"] = verdict(
            stats["control"], n_tokens, spec)
    log(f"check readings: {stats}")
    return out
