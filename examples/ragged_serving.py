"""Ragged continuous batching, live (paper Fig. 6/10 in motion): wildly
different prompt lengths arrive together; the scheduler streams each prompt
into the paged pool chunk by chunk while every admitted sequence keeps
decoding — watch the per-tick prefill/decode token composition and the
stream-K schedule keep workers balanced as context lengths diverge.

  PYTHONPATH=src python examples/ragged_serving.py
"""
import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serving.config import EngineConfig, PagedConfig
from repro.serving.engine import DecodeEngine
from repro.serving.scheduler import RequestState, Scheduler, SchedulerConfig

use_compile_cache()
cfg = get_smoke_config("mistral-nemo-12b")
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)

eng = DecodeEngine(cfg, params, config=EngineConfig(
    max_batch=4, cache_len=128, attn_backend="lean", num_workers=8,
    paged=PagedConfig(page_size=16),
))
sch = Scheduler(eng, SchedulerConfig(
    chunk_size=16, prefill_pack=2, token_budget=32, policy="priority",
    starvation_bound=16,
))

# a ragged burst: one long prompt among short ones (the decode batch must
# not stall behind the 96-token prefill), plus a late high-priority arrival
lens = [96, 9, 17, 33, 12]
handles = [
    sch.submit(rng.integers(0, cfg.vocab_size, L), max_new_tokens=10, uid=i)
    for i, L in enumerate(lens)
]
late = None

print(f"{'tick':>4} {'queue':>5} {'prefilling':>10} {'decoding':>8} "
      f"{'chunk toks':>10} {'decode toks':>11}")
for step in range(200):
    if step == 6:
        late = sch.submit(rng.integers(0, cfg.vocab_size, 7),
                          max_new_tokens=5, priority=5, uid=99)
        handles.append(late)
    pre = sum(1 for sr in sch.requests.values()
              if sr.state is RequestState.PREFILLING)
    dec = sum(1 for sr in sch.requests.values()
              if sr.state is RequestState.DECODING)
    chunk_before = sch.engine.stats.prefill_tokens
    out = sch.step()
    chunk_toks = sch.engine.stats.prefill_tokens - chunk_before
    if step < 14:
        print(f"{step:>4} {len(sch.queue):>5} {pre:>10} {dec:>8} "
              f"{chunk_toks:>10} {len(out):>11}")
    if not sch.pending:
        break

assert all(h.done for h in handles)
print(f"\ndrained in {sch.stats.steps} ticks: "
      f"{sch.stats.chunks} chunks, {sch.engine.stats.tokens_generated} "
      f"decode tokens, {sch.engine.stats.preemptions} preemptions")
if eng.stats.schedules:
    s = eng.stats.schedules[-1]
    print(f"last stream-K schedule: lens={s['lens']} "
          f"tiles={s['total_tiles']} over 8 workers x "
          f"{s['tiles_per_worker']} tiles/worker (pieces={s['pieces']})")
tel = sch.telemetry()
print(f"TTFT p50={tel['ttft']['p50']*1e3:.1f}ms  "
      f"p99={tel['ttft']['p99']*1e3:.1f}ms  "
      f"(high-priority late arrival waited "
      f"{(late.admit_step - late.arrival_step)} ticks in queue)")
eng.pool.check()
