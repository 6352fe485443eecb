"""closed_batch: every request is submitted at once and decodes to the end.

Mix parameters::

    {"loop": "closed_batch", "sessions": 16,
     "context": {"dist": "loguniform", "min": 2048, "max": 32768, "total": 163840},
     "max_new": 1024, "shape_seed": 0}

Sizes come from ``shape_seed``, which the mix fixes: every run seed serves
the same lengths in the same order, so every seed does the same work and
compiles the same programs. The run's ``--seed`` draws the token ids.

Set-up serves the sessions until all are decoding (chunked prefill through
the paged pool), then warms every decode program the window can reach; the
window decodes only, one token for every session each tick.
"""
from __future__ import annotations

from typing import Callable, List, Sequence

import numpy as np

from bench.harness.traffic import Request, draw_lengths


def generate(mix: dict, seed: int, vocab_size: int) -> List[Request]:
    n = int(mix["sessions"])
    lens = draw_lengths(mix["context"], n, np.random.default_rng(mix["shape_seed"]))
    rng = np.random.default_rng(seed)
    return [
        Request(rng.integers(0, vocab_size, L, dtype=np.int32), int(mix["max_new"]))
        for L in lens
    ]


def server_sizes(mix: dict, requests: Sequence[Request], page_size: int) -> dict:
    """One slot per session, and pages for every session's whole length."""
    need = [len(r.prompt) + r.max_new + 2 for r in requests]
    return {
        "max_batch": len(requests),
        "cache_len": -(-max(need) // page_size) * page_size,
        "num_pages": 1 + sum(-(-n // page_size) for n in need),
    }


def _bucketed(ctx, tile, cap):
    """The key a decode tick's schedule is built for: the engine buckets
    ``min(ctx + 1, cache_len)``."""
    from repro.core.leantile import bucket_ctx_lens

    lens = np.minimum(np.asarray(ctx) + 1, cap)
    return tuple(bucket_ctx_lens(lens.tolist(), tile, cap))


def warm_decode(sch, handles, max_ticks: int) -> int:
    """Compile every decode program the next ``max_ticks`` ticks can use.

    In a closed batch each session grows by one token per tick, so the
    schedule key of tick ``t`` is known in advance. For each new key one
    tick runs with the slots' lengths set ``t`` tokens ahead, through the
    engine's own ``decode_tick``; then lengths, next tokens and token lists
    are put back. The token it wrote at each slot's future position is
    rewritten by the real tick there before anything reads it. Returns the
    number of keys warmed."""
    eng = sch.engine
    slots = [h.slot for h in handles]
    base = eng.ctx_lens.copy()
    toks = eng.next_tokens.copy()
    seen = set()
    for t in range(max_ticks + 1):
        ctx = base.copy()
        ctx[slots] += t
        key = _bucketed(ctx, eng.tile, eng.cache_len)
        if key in seen:
            continue
        seen.add(key)
        eng.ctx_lens[:] = ctx
        eng.decode_tick()
        eng.ctx_lens[:] = base
        eng.next_tokens[:] = toks
        for h in handles:
            h.req.generated.pop()
    return len(seen)


class Drive:
    def __init__(self, sch, requests: Sequence[Request], on_token: Callable):
        self.sch = sch
        self.requests = list(requests)
        self.on_token = on_token
        self.handles: list = []

    def setup(self, seconds: float, tick_floor_s: Callable[[List[int]], float],
              log: Callable[[str], None]) -> None:
        """Submit every session, serve until all decode, and warm every
        decode program of the ticks that a window of ``seconds`` could run
        if each tick took no more than ``tick_floor_s`` of the contexts."""
        from repro.serving.scheduler import RequestState

        self.handles = [self.sch.submit(r.prompt, r.max_new, on_token=self.on_token)
                        for r in self.requests]
        steps = 0
        while not all(h.state is RequestState.DECODING for h in self.handles):
            self.sch.step()
            steps += 1
        max_ticks = int(seconds / tick_floor_s(self.contexts())) + 1
        served = max(len(h.generated) for h in self.handles)
        if served + max_ticks + 1 >= min(r.max_new for r in self.requests):
            raise ValueError(
                f"max_new of the mix is too small: {served} tokens served in "
                f"set-up and up to {max_ticks} in a {seconds} s window")
        warmed = warm_decode(self.sch, self.handles, max_ticks)
        log(f"set-up: {steps} steps, {warmed} decode programs warmed "
            f"for up to {max_ticks} ticks")

    def before_tick(self) -> None:
        """A closed batch submits nothing in the window."""

    def contexts(self) -> List[int]:
        return [len(r.prompt) + len(h.generated)
                for r, h in zip(self.requests, self.handles)]

    def due(self) -> List[int]:
        """Every session decodes in every tick of the window."""
        return [h.uid for h in self.handles]

    def served(self):
        return [(r.prompt, list(h.generated))
                for r, h in zip(self.requests, self.handles)]
