"""Serve continuously-batched requests through the scheduler: chunked
stream-K prefill into the paged KV pool + fused lean decode ticks, with
per-token streaming callbacks and TTFT/TPOT telemetry. Compares all three
attention backends (token streams must be identical — exact attention
everywhere, only the schedule differs).

  PYTHONPATH=src python examples/serve_decode.py
"""
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serving.config import EngineConfig, PagedConfig, SpecConfig
from repro.serving.engine import DecodeEngine
from repro.serving.scheduler import Scheduler, SchedulerConfig

use_compile_cache()
cfg = get_smoke_config("mistral-nemo-12b")
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, 10 + 3 * uid) for uid in range(6)]

streams = {}
for backend in ("ref", "lean", "fixed"):
    eng = DecodeEngine(cfg, params, config=EngineConfig(
        max_batch=3, cache_len=96, attn_backend=backend, num_workers=8,
        paged=PagedConfig(page_size=16),
    ))
    sch = Scheduler(eng, SchedulerConfig(
        chunk_size=8, prefill_pack=2, token_budget=16, policy="fcfs",
    ))

    tokens_seen = {}
    def on_token(uid, tok, done, _acc=tokens_seen):
        _acc.setdefault(uid, []).append(tok)

    t0 = time.perf_counter()
    handles = [
        sch.submit(p, max_new_tokens=6, on_token=on_token, uid=uid)
        for uid, p in enumerate(prompts)
    ]
    sch.run_to_completion(max_steps=200)
    dt = time.perf_counter() - t0
    streams[backend] = [tuple(h.generated) for h in handles]

    tel = sch.telemetry()
    print(f"{backend:6s}: {tel['tokens_generated']} decode tokens + "
          f"{tel['admitted']} first tokens in {tel['steps']} steps "
          f"({dt:.2f}s); {tel['chunks']} prefill chunks "
          f"({tel['prefill_tokens']} prompt tokens streamed into the pool)")
    print(f"        TTFT p50={tel['ttft']['p50']*1e3:.1f}ms "
          f"p99={tel['ttft']['p99']*1e3:.1f}ms | "
          f"TPOT p50={tel['tpot']['p50']*1e3:.1f}ms | "
          f"queue wait p99={tel['queue_wait']['p99']*1e3:.1f}ms")
    assert all(tokens_seen[h.uid] == h.generated for h in handles)

assert streams["ref"] == streams["lean"] == streams["fixed"], \
    "backends diverged"
print("\nall backends token-identical; streaming callbacks matched handles")

# speculative decode: the prompt-lookup proposer drafts k tokens, ONE
# stream-K verify sweep scores all of them, and the accepted prefix lands
# in a single tick — output stays token-identical to plain greedy decode
eng = DecodeEngine(cfg, params, config=EngineConfig(
    max_batch=3, cache_len=96, attn_backend="lean", num_workers=8,
    paged=PagedConfig(page_size=16),
    spec=SpecConfig(enabled=True, k=4),
))
sch = Scheduler(eng, SchedulerConfig(
    chunk_size=8, prefill_pack=2, token_budget=16, policy="fcfs",
))
handles = [sch.submit(p, max_new_tokens=6, uid=uid)
           for uid, p in enumerate(prompts)]
sch.run_to_completion(max_steps=200)
assert [tuple(h.generated) for h in handles] == streams["lean"], \
    "speculative decode diverged from greedy"
tel = sch.telemetry()
print(f"spec  : identical stream in {tel['spec_ticks']} verify ticks; "
      f"{tel['spec_accepted_tokens']}/{tel['spec_draft_tokens']} drafts "
      f"accepted (rate {tel['spec_accept_rate']:.2f})")
