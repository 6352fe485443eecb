"""Continuous-batching scheduler: chunked stream-K prefill + decode ticks.

The :class:`repro.serving.engine.DecodeEngine` provides the *mechanisms* —
a fused decode tick, a paged KV pool, blocking whole-prompt admission, and
(new) a packed chunked-prefill step. This module provides the *policy*
layer that turns those into a server:

  * a request lifecycle ``QUEUED -> PREFILLING -> DECODING -> FINISHED``
    (preemption folds back to ``QUEUED`` for recompute-resume);
  * a token-budget **tick composer**: each :meth:`Scheduler.step` packs up
    to ``prefill_pack`` prompt chunks (each at most ``chunk_size`` tokens,
    all together at most ``token_budget`` minus the decode batch) *plus*
    the decode batch — so a 32k-token prompt streams into the paged pool a
    chunk per tick while every in-flight sequence keeps decoding, instead
    of stalling the whole batch behind one blocking prefill;
  * admission **policies** (``fcfs`` | ``priority``) with a hard
    *starvation bound*: any request queued for more than
    ``starvation_bound`` scheduler steps outranks every younger request
    regardless of priority (FIFO among the starving);
  * **streaming**: an ``on_token(uid, token, done)`` callback fires for
    every generated token, including the first one sampled off the final
    prefill chunk;
  * **telemetry**: TTFT / TPOT / queue-wait histograms (recorded into the
    engine's :class:`~repro.serving.engine.EngineStats`, which now lives
    on the :class:`repro.obs.metrics.MetricsRegistry`), queue-depth and
    per-tick prefill-vs-decode token logs. Request lifecycle transitions
    additionally stream into the engine's :class:`repro.obs.trace.Tracer`
    (``request_event`` / ``request_token``), so a traced run yields
    per-uid QUEUED -> PREFILLING -> DECODING -> FINISHED timelines with
    TTFT/TPOT derived independently of the histograms.

Chunked prefill requires an all-global-attention architecture
(``engine.supports_chunked_prefill()``); otherwise the scheduler
transparently falls back to blocking admission — same lifecycle,
same telemetry, same token streams. The blocking path doubles as the
*oracle* for the chunked path: both must generate token-identical output
(``tests/test_scheduler.py``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.serving.engine import DecodeEngine, Request

__all__ = ["RequestState", "SchedulerConfig", "ScheduledRequest", "Scheduler"]


class RequestState(Enum):
    QUEUED = "queued"
    PREFILLING = "prefilling"
    DECODING = "decoding"
    FINISHED = "finished"
    FAILED = "failed"          # poisoned: deadline/preemption budget spent
    CANCELLED = "cancelled"    # caller withdrew the request


@dataclass
class SchedulerConfig:
    """Tick-composition and policy knobs.

    ``token_budget`` is the per-tick token *target*: decode tokens (one per
    DECODING slot) are latency-critical and always run; prefill chunks fill
    the remainder. ``chunk_size`` trades TTFT for decode interference (see
    EXPERIMENTS.md); ``prefill_pack`` bounds how many requests prefill
    concurrently in one packed kernel call (its value is a static jit
    shape — keep it fixed per scheduler).
    """

    chunk_size: int = 32
    prefill_pack: int = 2
    token_budget: int = 64
    chunked: Optional[bool] = None        # None -> auto-detect from engine
    policy: str = "fcfs"                  # 'fcfs' | 'priority'
    starvation_bound: int = 64            # scheduler steps
    # --- robustness knobs (all default-off / unbounded = old behavior) ---
    # TTFT deadline in scheduler steps: a request still first-token-less
    # this many steps after (re-)queueing expires — it is requeued with
    # backoff, and after ``max_deadline_misses`` expiries poison-failed
    deadline_steps: Optional[int] = None
    max_deadline_misses: int = 3
    # bounded exponential backoff for failed admissions (pool pressure):
    # 0 disables (head-of-line blocks exactly as before); > 0 delays the
    # failed request ``min(cap, base << (failures-1))`` steps and lets
    # younger requests admit past it meanwhile
    retry_backoff: int = 0
    retry_backoff_cap: int = 64
    # a request preempted more than this many times is poison-failed
    # (None = never — the old unbounded recompute-resume behavior)
    max_preemptions: Optional[int] = None

    def __post_init__(self):
        if self.policy not in ("fcfs", "priority"):
            raise ValueError(f"unknown policy {self.policy!r}")
        if self.chunk_size <= 0 or self.prefill_pack <= 0:
            raise ValueError("chunk_size and prefill_pack must be positive")
        if self.starvation_bound <= 0:
            raise ValueError("starvation_bound must be positive")
        if self.deadline_steps is not None and self.deadline_steps <= 0:
            raise ValueError("deadline_steps must be positive (or None)")
        if self.max_deadline_misses < 1:
            raise ValueError("max_deadline_misses must be >= 1")
        if self.retry_backoff < 0 or self.retry_backoff_cap < 1:
            raise ValueError("retry_backoff >= 0, retry_backoff_cap >= 1")
        if self.max_preemptions is not None and self.max_preemptions < 1:
            raise ValueError("max_preemptions must be >= 1 (or None)")


@dataclass
class ScheduledRequest:
    """A submitted request plus its lifecycle/telemetry state — the handle
    :meth:`Scheduler.submit` returns (token stream in ``req.generated``)."""

    req: Request
    priority: int = 0
    on_token: Optional[Callable[[int, int, bool], None]] = None
    slo_class: str = "default"            # SLO budget class (watchdog)
    state: RequestState = RequestState.QUEUED
    slot: int = -1
    prefill_done: int = 0                 # prompt tokens already chunked in
    arrival_seq: int = 0                  # submission order (FCFS tiebreak)
    arrival_step: int = 0
    arrival_time: float = 0.0
    enqueue_time: float = 0.0             # last (re-)queue time: wait metric
    admit_step: int = -1
    first_token_time: float = -1.0
    last_token_time: float = -1.0
    preemptions: int = 0
    deadline_at: int = -1                 # step the TTFT deadline expires
    deadline_window: int = -1             # the deadline's length in steps
    deadline_misses: int = 0
    not_before: int = 0                   # admission backoff: skip until
    admit_failures: int = 0               # consecutive failed admissions
    error: Optional[str] = None           # set when state is FAILED

    @property
    def uid(self) -> int:
        return self.req.uid

    @property
    def done(self) -> bool:
        return self.state is RequestState.FINISHED

    @property
    def generated(self) -> List[int]:
        return self.req.generated

    def queue_age(self, now_step: int) -> int:
        return now_step - self.arrival_step


@dataclass
class SchedulerStats:
    steps: int = 0
    admitted: int = 0
    finished: int = 0
    chunks: int = 0
    stalled_chunk_ticks: int = 0          # ticks where page pressure held
    deadlock_preemptions: int = 0         # chunks back entirely
    deadline_expirations: int = 0         # TTFT deadline misses (each one)
    cancellations: int = 0                # caller-cancelled requests
    poisoned: int = 0                     # requests poison-failed
    admit_backoffs: int = 0               # failed admissions that backed off
    queue_depth: List[int] = field(default_factory=list)
    # admission audit trail for the starvation-bound invariant: one record
    # per admission (step, uid, age, #starving requests passed over)
    admissions: List[dict] = field(default_factory=list)

    LOG_CAP = 4096

    def log_depth(self, d: int):
        self.queue_depth.append(d)
        if len(self.queue_depth) > self.LOG_CAP:
            del self.queue_depth[: -self.LOG_CAP]


class Scheduler:
    """Continuous-batching policy layer over a :class:`DecodeEngine`."""

    def __init__(self, engine: DecodeEngine, config: Optional[SchedulerConfig] = None):
        self.engine = engine
        self.config = config or SchedulerConfig()
        if self.config.chunked is None:
            self.chunked = engine.supports_chunked_prefill()
        else:
            self.chunked = self.config.chunked
            if self.chunked and not engine.supports_chunked_prefill():
                raise ValueError(
                    "chunked prefill requires an all-'attn' architecture "
                    "(engine.supports_chunked_prefill() is False)"
                )
        self.queue: List[ScheduledRequest] = []
        self.requests: Dict[int, ScheduledRequest] = {}
        self._slot_sr: Dict[int, ScheduledRequest] = {}
        self._next_uid = 0
        self._arrival_seq = 0
        self.stats = SchedulerStats()
        # engine preemptions (pool pressure mid-decode) fold back into OUR
        # queue, keeping their arrival time so aging continues
        engine.preempt_sink = self._on_preempt
        # observability: lifecycle events flow into the engine's tracer
        # (a NULL_TRACER no-ops them) and queue-depth gauges into its
        # metrics registry, next to the engine/kvpool/cache families
        self.tracer = engine.tracer
        engine.metrics.gauge_fn(
            "scheduler_queue_depth", lambda: len(self.queue),
            help="requests waiting for admission",
        )
        engine.metrics.gauge_fn(
            "scheduler_active_slots", lambda: len(self._slot_sr),
            help="slots holding a PREFILLING or DECODING request",
        )
        engine.metrics.gauge_fn(
            "scheduler_pending", lambda: self.pending,
            help="queued + in-flight requests",
        )

    # ---------------------------------------------------------------- submit
    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        priority: int = 0,
        on_token: Optional[Callable[[int, int, bool], None]] = None,
        uid: Optional[int] = None,
        deadline_steps: Optional[int] = None,
        slo_class: str = "default",
    ) -> ScheduledRequest:
        """Enqueue a request; returns its handle immediately. Tokens stream
        through ``on_token(uid, token, done)`` as :meth:`step` produces
        them and accumulate in ``handle.generated``. ``deadline_steps``
        overrides the config-level TTFT deadline for this request.
        ``slo_class`` names the request's SLO budget class: when the
        engine carries a perf watchdog with a matching
        :class:`~repro.obs.watch.SLOConfig`, this request's TTFT/TPOT
        observations are charged against that class's error budget."""
        prompt = np.asarray(prompt, dtype=np.int32)
        if prompt.size == 0:
            raise ValueError("empty prompt (nothing to prefill)")
        if uid is None:
            uid = self._next_uid
        self._next_uid = max(self._next_uid, uid + 1)
        if uid in self.requests:
            raise ValueError(f"duplicate request uid {uid}")
        now = time.perf_counter()
        sr = ScheduledRequest(
            req=Request(
                uid=uid,
                prompt=prompt,
                max_new_tokens=max_new_tokens,
            ),
            priority=priority,
            on_token=on_token,
            slo_class=slo_class,
            arrival_seq=self._arrival_seq,
            arrival_step=self.stats.steps,
            arrival_time=now,
            enqueue_time=now,
        )
        self._arrival_seq += 1
        ttft_deadline = (
            deadline_steps if deadline_steps is not None
            else self.config.deadline_steps
        )
        if ttft_deadline is not None:
            sr.deadline_window = int(ttft_deadline)
            sr.deadline_at = sr.arrival_step + sr.deadline_window
        self.requests[uid] = sr
        self.queue.append(sr)
        self.tracer.request_event(
            uid, "QUEUED", prompt_tokens=int(prompt.size),
            priority=priority,
        )
        return sr

    def _on_preempt(self, req: Request):
        sr = self.requests.get(req.uid)
        if sr is None or sr.req is not req:
            # a request admitted through the raw engine API on the same
            # engine is not ours — preserve the engine's own requeue
            # semantics instead of corrupting scheduler state
            self.engine.queue.insert(0, req)
            return
        if sr.slot >= 0:
            self._slot_sr.pop(sr.slot, None)
        sr.state = RequestState.QUEUED
        sr.slot = -1
        sr.prefill_done = 0           # recompute-resume restarts the prompt
        sr.preemptions += 1
        cfg = self.config
        if (
            cfg.max_preemptions is not None
            and sr.preemptions > cfg.max_preemptions
        ):
            # a request thrashed off its slot this many times is poison:
            # under sustained pressure its recompute-resume work would
            # starve everyone else forever
            self._fail(sr, f"preempted {sr.preemptions}x "
                           f"(max_preemptions={cfg.max_preemptions})")
            return
        sr.enqueue_time = time.perf_counter()
        self.queue.insert(0, sr)
        # (the engine already emitted PREEMPTED for this uid)
        self.tracer.request_event(
            sr.uid, "QUEUED", requeue=True, preemptions=sr.preemptions
        )

    # ---------------------------------------------------------------- policy
    def _starving(self, sr: ScheduledRequest) -> bool:
        return sr.queue_age(self.stats.steps) > self.config.starvation_bound

    def _order_queue(self):
        """Admission order. FCFS: arrival. Priority: higher ``priority``
        first — EXCEPT that requests older than the starvation bound
        outrank everything, FIFO among themselves. Sort is stable, so
        equal keys keep arrival order."""
        if self.config.policy == "fcfs":
            self.queue.sort(key=lambda sr: sr.arrival_seq)
        else:
            self.queue.sort(
                key=lambda sr: (
                    0 if self._starving(sr) else 1,
                    -sr.priority if not self._starving(sr) else 0,
                    sr.arrival_seq,
                )
            )

    # ------------------------------------------------------------- admission
    def _record_admission(self, sr: ScheduledRequest):
        # audit, not logic: admission always takes the ordered queue head,
        # so this stays 0 unless a future change starts skipping past
        # blocked heads — the fuzz suite pins the invariant either way
        passed_over = sum(
            1 for other in self.queue if self._starving(other)
            and not self._starving(sr)
        )
        self.stats.admitted += 1
        sr.admit_step = self.stats.steps
        self.stats.admissions.append(
            {
                "step": self.stats.steps,
                "uid": sr.uid,
                "age": sr.queue_age(self.stats.steps),
                "starving_passed_over": passed_over,
            }
        )
        if len(self.stats.admissions) > SchedulerStats.LOG_CAP:
            del self.stats.admissions[: -SchedulerStats.LOG_CAP]
        # wait since the LAST enqueue: a preempted request's decode
        # residency must not be booked as queue wait on re-admission
        self.engine.stats.queue_wait.observe(
            time.perf_counter() - sr.enqueue_time
        )
        self.tracer.request_event(
            sr.uid, "PREFILLING", slot=sr.slot,
            prefix_matched=sr.prefill_done,
        )

    def _admit_backoff(self, sr: ScheduledRequest):
        """A failed admission (pool pressure): with ``retry_backoff``
        configured, delay this request's next attempt exponentially (so
        younger requests can admit past the blocked head meanwhile);
        without it, the old head-of-line semantics apply unchanged."""
        cfg = self.config
        if cfg.retry_backoff <= 0:
            return
        sr.admit_failures += 1
        delay = min(
            cfg.retry_backoff_cap,
            cfg.retry_backoff << (sr.admit_failures - 1),
        )
        sr.not_before = self.stats.steps + delay
        self.stats.admit_backoffs += 1

    def _admit(self):
        if not self.queue:
            return
        self._order_queue()
        i = 0
        while i < len(self.queue) and self.engine.free_slots():
            sr = self.queue[i]
            if sr.not_before > self.stats.steps:
                i += 1                    # backing off; try the next request
                continue
            if self.chunked:
                slot = self.engine.claim_slot(sr.req)
                if slot is None:
                    self._admit_backoff(sr)
                    break
                sr.state = RequestState.PREFILLING
                # radix prefix cache: matched prompt tokens map their cached
                # KV pages straight into the slot's table — prefill starts
                # past them, and only the unmatched tail is ever charged to
                # the chunk token budget
                sr.prefill_done = self.engine.attach_prefix(
                    slot, sr.req.prompt
                )
            else:
                slot = self.engine.free_slots()[0]
                if not self.engine.admit_blocking(sr.req, slot):
                    # pool exhausted; retry next step (with backoff when
                    # configured — capacity pressure is global, so stop
                    # scanning either way)
                    self._admit_backoff(sr)
                    break
            self.queue.pop(i)
            sr.not_before = 0
            sr.admit_failures = 0
            sr.slot = slot
            self._slot_sr[slot] = sr
            self._record_admission(sr)
            if not self.chunked:
                # blocking admission already sampled the first token
                self.tracer.request_event(sr.uid, "DECODING", slot=slot)
                self._emit_first_token(sr)

    # --------------------------------------------------------------- prefill
    def _prefill_slots(self) -> List[ScheduledRequest]:
        srs = [
            sr for sr in self._slot_sr.values()
            if sr.state is RequestState.PREFILLING
        ]
        srs.sort(key=lambda sr: sr.arrival_seq)     # oldest first
        return srs

    def _decoding_slots(self) -> List[int]:
        return [
            s for s, sr in self._slot_sr.items()
            if sr.state is RequestState.DECODING
        ]

    def _compose_chunks(self) -> List[tuple]:
        """Pick this tick's prefill chunks under the token budget. Returns
        ``[(sr, slot, chunk_tokens, off), ...]`` (at most ``prefill_pack``).
        """
        cfg = self.config
        # decode slots are charged at the engine's token width — with
        # speculative decode on, every DECODING slot may emit up to k+1
        # tokens this tick, and prefill only gets what is left
        budget = max(
            0,
            cfg.token_budget
            - len(self._decoding_slots()) * self.engine.decode_token_width(),
        )
        if budget == 0:
            # liveness floor: a saturated decode batch must not starve
            # prefill forever — grant one token of prefill progress
            budget = 1
        work = []
        pressure = False
        for sr in self._prefill_slots():
            if len(work) >= cfg.prefill_pack or budget <= 0:
                break
            plen = len(sr.req.prompt)
            clen = min(cfg.chunk_size, plen - sr.prefill_done, budget)
            if clen <= 0:
                continue
            if not self.engine.ensure_chunk_pages(
                sr.slot, sr.prefill_done + clen, write_from=sr.prefill_done
            ):
                pressure = True
                continue                  # pool pressure; retry next tick
            chunk = sr.req.prompt[sr.prefill_done : sr.prefill_done + clen]
            work.append((sr, sr.slot, chunk, sr.prefill_done))
            budget -= clen
        if pressure and not work:
            self.stats.stalled_chunk_ticks += 1
            self._break_page_deadlock()
        return work

    def _break_page_deadlock(self):
        """Nothing could prefill for want of pages. If decode is running,
        completions will free pages — wait. If NOT, the pool is wedged by
        half-prefilled requests: evict the youngest PREFILLING slot so the
        oldest can make progress (recompute-resume on re-admission)."""
        if self._decoding_slots():
            return
        srs = self._prefill_slots()
        if len(srs) < 2:
            return                        # single occupant always fits
        victim = srs[-1]
        self.engine.preempt_slot(victim.slot)   # routes to _on_preempt
        self.stats.deadlock_preemptions += 1

    def _run_prefill(self):
        work = self._compose_chunks()
        if not work:
            return
        first_toks = self.engine.prefill_chunks_tick(
            [(slot, chunk, off) for _, slot, chunk, off in work],
            pack_width=self.config.prefill_pack,
            chunk_cap=self.config.chunk_size,
        )
        self.stats.chunks += len(work)
        for i, (sr, slot, chunk, off) in enumerate(work):
            sr.prefill_done = off + len(chunk)
            if sr.prefill_done == len(sr.req.prompt):
                # prompt complete: this row's sampled token IS the first
                # token — the request joins the decode batch next tick
                nxt = int(first_toks[i])
                sr.req.generated.append(nxt)
                self.engine.next_tokens[slot, 0] = nxt
                self.engine.ctx_lens[slot] = len(sr.req.prompt)
                sr.state = RequestState.DECODING
                self.tracer.request_event(sr.uid, "DECODING", slot=slot)
                self._emit_first_token(sr)

    # ---------------------------------------------------------------- tokens
    def _emit_first_token(self, sr: ScheduledRequest):
        now = time.perf_counter()
        if sr.first_token_time < 0:
            # a preempted-and-resumed request re-enters here; TTFT is the
            # time to its FIRST first-token only
            sr.first_token_time = now
            ttft = now - sr.arrival_time
            self.engine.stats.ttft.observe(ttft)
            if self.engine.watchdog is not None:
                self.engine.watchdog.observe_latency(
                    sr.slo_class, "ttft", ttft
                )
            self.tracer.request_event(sr.uid, "FIRST_TOKEN")
        sr.last_token_time = now
        self.tracer.request_token(sr.uid)
        tok = sr.req.generated[-1]
        done = sr.req.done
        if sr.on_token:
            sr.on_token(sr.uid, tok, done)
        if done:
            self._finish(sr, free_engine_slot=True)

    def _emit_decode_token(self, sr: ScheduledRequest, tok: int, done: bool):
        now = time.perf_counter()
        if sr.last_token_time >= 0:
            tpot = now - sr.last_token_time
            self.engine.stats.tpot.observe(tpot)
            if self.engine.watchdog is not None:
                self.engine.watchdog.observe_latency(
                    sr.slo_class, "tpot", tpot
                )
        sr.last_token_time = now
        self.tracer.request_token(sr.uid)
        if sr.on_token:
            sr.on_token(sr.uid, tok, done)

    def _fail(self, sr: ScheduledRequest, msg: str):
        """Poison-fail a request: terminal FAILED state, never retried.
        The caller is responsible for having detached it from the queue
        and any slot first."""
        if sr.slot >= 0:
            self._slot_sr.pop(sr.slot, None)
            sr.slot = -1
        sr.state = RequestState.FAILED
        sr.error = msg
        self.stats.poisoned += 1
        self.tracer.request_event(sr.uid, "FAILED", error=msg)
        self.requests.pop(sr.uid, None)

    def cancel(self, uid: int) -> bool:
        """Cancel a request wherever it is in the lifecycle: QUEUED leaves
        the queue; PREFILLING/DECODING frees the slot (pages released, a
        finished-enough prefix still donated to the radix cache). Returns
        False for unknown / already-terminal uids."""
        sr = self.requests.get(uid)
        if sr is None:
            return False
        if sr in self.queue:
            self.queue.remove(sr)
        if sr.slot >= 0:
            slot = sr.slot
            self._slot_sr.pop(slot, None)
            sr.slot = -1
            self.engine.release_slot(slot)
        sr.state = RequestState.CANCELLED
        self.stats.cancellations += 1
        self.tracer.request_event(uid, "CANCELLED")
        self.requests.pop(uid, None)
        return True

    def _check_deadlines(self):
        """TTFT deadline sweep (runs before admission each step): a request
        past its deadline with no first token yet is pulled back — a
        PREFILLING occupant frees its slot and pool pages — and requeued
        with exponential backoff and a fresh deadline window; a repeat
        offender (``max_deadline_misses``) is poison-failed instead of
        wedging a slot forever."""
        cfg = self.config
        now = self.stats.steps
        expired = [
            sr for sr in list(self.requests.values())
            if sr.deadline_at >= 0
            and now > sr.deadline_at
            and sr.first_token_time < 0
            and sr.state in (RequestState.QUEUED, RequestState.PREFILLING)
        ]
        for sr in expired:
            sr.deadline_misses += 1
            self.stats.deadline_expirations += 1
            if sr.state is RequestState.PREFILLING:
                # routes through _on_preempt: state -> QUEUED, queue front
                # (and the preemption budget check, which may fail it)
                self.engine.preempt_slot(sr.slot)
                if sr.state is RequestState.FAILED:
                    continue
            if sr.deadline_misses >= cfg.max_deadline_misses:
                if sr in self.queue:
                    self.queue.remove(sr)
                self._fail(
                    sr, f"TTFT deadline ({sr.deadline_window} steps) "
                        f"missed {sr.deadline_misses}x"
                )
                continue
            base = max(1, cfg.retry_backoff)
            delay = min(
                cfg.retry_backoff_cap, base << (sr.deadline_misses - 1)
            )
            sr.not_before = now + delay
            sr.deadline_at = sr.not_before + max(1, sr.deadline_window)

    def _finish(self, sr: ScheduledRequest, free_engine_slot: bool = False):
        slot = sr.slot
        if free_engine_slot and slot >= 0:
            # the engine frees slots itself after decode ticks; this path
            # covers requests whose budget was exhausted by the first token
            # (release_slot also donates the finished prefix to the radix
            # cache before letting the page refs go)
            self.engine.release_slot(slot)
        self._slot_sr.pop(slot, None)
        sr.slot = -1
        sr.state = RequestState.FINISHED
        self.stats.finished += 1
        self.tracer.request_event(
            sr.uid, "FINISHED", tokens=len(sr.req.generated)
        )
        # a steady-state server must not grow per-request state forever:
        # the handle stays with the caller, the scheduler forgets it (and
        # its uid becomes reusable)
        self.requests.pop(sr.uid, None)

    # ------------------------------------------------------------------ step
    def step(self) -> Dict[int, int]:
        """One scheduler tick: admit, pack prefill chunks, decode.
        Returns {uid: token} for decode-produced tokens — token lists with
        speculative decode on (first tokens stream via callbacks and
        ``handle.generated``)."""
        with self.tracer.span("sched.step"):
            return self._step()

    def _step(self) -> Dict[int, int]:
        self.stats.steps += 1
        self.stats.log_depth(len(self.queue))
        self._check_deadlines()
        with self.tracer.span("sched.admit"):
            self._admit()
        if self.chunked:
            with self.tracer.span("sched.prefill"):
                self._run_prefill()
        prefilling = [
            s for s, sr in self._slot_sr.items()
            if sr.state is RequestState.PREFILLING
        ]
        out = self.engine.decode_tick(exclude=prefilling)
        with self.tracer.span("sched.deliver"):
            for uid, tok in out.items():
                sr = self.requests[uid]
                # the engine frees the slot when the budget is spent OR the
                # context cap is hit — either way this request is terminal,
                # and the stream contract owes its consumer a done=True token
                finished = self.engine.slot_req[sr.slot] is not sr.req
                # speculative ticks emit token *lists* (1..k+1 per slot); the
                # stream contract is per-token either way, done only on the
                # last
                toks = tok if isinstance(tok, list) else [tok]
                for j, t in enumerate(toks):
                    self._emit_decode_token(
                        sr, t, done=finished and j == len(toks) - 1
                    )
                if finished:
                    self._finish(sr)
        return out

    # -------------------------------------------------------------- draining
    @property
    def pending(self) -> int:
        return len(self.queue) + len(self._slot_sr)

    def run_to_completion(self, max_steps: int = 10_000) -> SchedulerStats:
        while self.pending and self.stats.steps < max_steps:
            self.step()
        return self.stats

    def telemetry(self) -> dict:
        """JSON-friendly snapshot: scheduler counters + engine latency
        histograms + per-tick token split (for BENCH_decode_step.json)."""
        es = self.engine.stats
        return {
            "steps": self.stats.steps,
            "admitted": self.stats.admitted,
            "finished": self.stats.finished,
            "chunks": self.stats.chunks,
            "chunked": self.chunked,
            "policy": self.config.policy,
            "stalled_chunk_ticks": self.stats.stalled_chunk_ticks,
            "deadlock_preemptions": self.stats.deadlock_preemptions,
            "deadline_expirations": self.stats.deadline_expirations,
            "cancellations": self.stats.cancellations,
            "poisoned": self.stats.poisoned,
            "admit_backoffs": self.stats.admit_backoffs,
            "queue_depth_max": max(self.stats.queue_depth, default=0),
            "prefill_tokens": es.prefill_tokens,
            "tokens_generated": es.tokens_generated,
            "prefix_matched_tokens": es.prefix_matched_tokens,
            "prefix_attach_count": es.prefix_attach_count,
            "cow_copies": es.cow_copies,
            "fused_decode_ticks": es.fused_decode_ticks,
            "cascade_ticks": es.cascade_ticks,
            "cascade_fused_ticks": es.cascade_fused_ticks,
            "cascade_grouped_passes": es.cascade_grouped_passes,
            "cascade_retraces": es.cascade_retraces,
            "cascade_stability_skips": es.cascade_stability_skips,
            "cascade_levels_max": es.cascade_levels_max,
            "prefix_cache": dict(es.prefix_cache),
            # speculative decode telemetry (engine-side)
            "spec_ticks": es.spec_ticks,
            "spec_draft_tokens": es.spec_draft_tokens,
            "spec_accepted_tokens": es.spec_accepted_tokens,
            "spec_accept_rate": (
                es.spec_accepted_tokens / max(1, es.spec_draft_tokens)
            ),
            # self-healing / fault telemetry (engine-side)
            "nan_ticks": es.nan_ticks,
            "degrade_escalations": es.degrade_escalations,
            "degrade_heals": es.degrade_heals,
            "poisoned_slots": es.poisoned_slots,
            "donation_aborts": es.donation_aborts,
            "audits_run": es.audits_run,
            "audit_failures": es.audit_failures,
            "audit_repairs": es.audit_repairs,
            "degraded": dict(es.degraded),
            "faults": dict(es.faults),
            **es.latency_dict(),
            **self._watchdog_telemetry(),
        }

    def _watchdog_telemetry(self) -> dict:
        """Watchdog fire counts + per-class SLO budget state, when the
        engine carries a perf watchdog (empty otherwise so older telemetry
        consumers see an unchanged dict)."""
        wd = self.engine.watchdog
        if wd is None:
            return {}
        return {
            "watchdog": {
                "ticks": wd.ticks,
                "total_fires": wd.total_fires,
                "fire_counts": wd.fire_counts(),
            },
            "slo": {k: b.as_dict() for k, b in wd.budgets.items()},
        }
