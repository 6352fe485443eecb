"""Chaos suite (``-m chaos``): fixed-seed fault schedules against the
self-healing engine, asserting the three recovery contracts end-to-end:

  1. requests untouched by a fault generate **token-identical** streams to
     the same workload on a fault-free engine (and, because recovery is
     recompute-resume + an argmax-exact degraded chain, so do the victims);
  2. **zero page leaks** after recovery — ``pool.check()`` passes and the
     pool drains to empty once all requests finish;
  3. the **degraded gauge returns to 0** after the faults stop (slots heal
     back up the chain; nothing stays quarantined).

Every schedule is deterministic (``FaultInjector`` seeds + greedy argmax
decode), so failures replay exactly. The randomized fault-schedule fuzz
at the bottom is ``@slow`` (the long-suite CI job), not ``chaos``.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.obs.flight import load_flight_dump
from repro.serving.config import EngineConfig, ObsConfig, PagedConfig
from repro.serving.engine import DecodeEngine, Request
from repro.serving.faults import FaultInjector, FaultSpec
from repro.serving.guards import GuardConfig
from repro.serving.scheduler import Scheduler, SchedulerConfig

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk_engine(cfg, params, *, faults=None, guards=None,
               prefix_cache=False, flight_dir=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_len", 32)
    kw.setdefault("num_workers", 4)
    return DecodeEngine(cfg, params, config=EngineConfig(
        attn_backend="lean",
        paged=PagedConfig(page_size=8, prefix_cache=prefix_cache),
        obs=ObsConfig(flight_dir=flight_dir),
        faults=faults, guards=guards, **kw,
    ))


def _requests(cfg, n=4, new=12, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 5 + 4 * i),
                max_new_tokens=new)
        for i in range(n)
    ]


def _run(eng, cfg, *, n=4, new=12, seed=0, max_ticks=400):
    reqs = _requests(cfg, n=n, new=new, seed=seed)
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_ticks=max_ticks)
    assert all(r.done for r in reqs), "requests wedged under faults"
    return [tuple(r.generated) for r in reqs]


def _quiesce(eng, ticks=8):
    """Stop all faults and idle-tick the engine so the *periodic* audit
    gets its post-storm pass — a corruption injected after the final
    in-flight audit is healed here, exactly as a live service would heal
    it on the next audit interval."""
    if eng.faults is not None:
        eng.faults.stop_all()
    for _ in range(ticks):
        eng.tick()


def _assert_recovered(eng):
    """The three post-recovery contracts shared by every schedule."""
    assert eng.pool is not None
    eng.pool.check()                              # zero leaks / no corruption
    assert eng.pool.num_allocated == (
        len(eng.prefix_cache._pages) if eng.prefix_cache is not None else 0
    )
    assert eng.degraded_gauge.value == 0          # gauge back to zero
    if eng.prefix_cache is not None:
        eng.prefix_cache.check()


@pytest.mark.chaos
def test_nan_output_quarantine_degrade_heal_token_identical(setup):
    """Transient non-finite logits: the victim is quarantined (no token,
    no ctx advance), walks down the degraded chain, and heals back to the
    fast path once the window closes — the full stream stays identical to
    the fault-free run because the re-executed steps are argmax-exact."""
    cfg, params = setup
    base = _run(_mk_engine(cfg, params), cfg)
    guards = GuardConfig(heal_after=2, audit_interval=4,
                         audit_action="repair")
    inj = FaultInjector(
        {"nan_output": FaultSpec(rate=1.0, start=4, stop=7)}, seed=1
    )
    eng = _mk_engine(cfg, params, faults=inj, guards=guards)
    assert _run(eng, cfg) == base
    assert inj.fires["nan_output"] == 3
    assert eng.stats.nan_ticks >= 3
    assert eng.stats.degrade_escalations >= 1
    assert eng.stats.degrade_heals >= 1
    assert eng.degraded_gauge.peak >= 1
    assert eng.stats.poisoned_slots == 0          # transient ≠ poison
    _assert_recovered(eng)


@pytest.mark.chaos
def test_nan_kv_corruption_poisons_and_recomputes(setup, tmp_path):
    """Real device-side KV corruption: no alternate kernel can make NaN
    attention finite, so the victim rides the chain to the bottom, is
    poisoned (pages scrubbed + freed), and recomputes from its prompt —
    finishing with the exact fault-free stream. Scrubbing matters: a NaN
    page recycled un-zeroed would poison whichever innocent slot got it.

    The flight recorder must leave a postmortem trail: the degrade and
    poison dumps' trailing events name the injected ``nan_kv`` point, so
    the fault is attributable from the JSON artifacts alone."""
    cfg, params = setup
    base = _run(_mk_engine(cfg, params), cfg)
    guards = GuardConfig(heal_after=2, poison_after=2)
    inj = FaultInjector(
        {"nan_kv": FaultSpec(rate=1.0, start=3, max_fires=1)}, seed=2
    )
    eng = _mk_engine(cfg, params, faults=inj, guards=guards,
                     flight_dir=str(tmp_path))
    assert _run(eng, cfg) == base
    assert inj.fires["nan_kv"] == 1
    assert eng.stats.poisoned_slots == 1
    assert eng.stats.degrade_escalations >= 3     # rode the chain down
    assert eng.stats.preemptions >= 1             # recompute-resume
    _assert_recovered(eng)
    # postmortem bundles on disk: degrade + poison paths both dumped, and
    # each bundle's recent events identify the injected fault point
    files = sorted(tmp_path.glob("flight-*.json"))
    assert files, "no flight dumps written"
    reasons = set()
    for f in files:
        doc = load_flight_dump(f)
        reasons.add(doc["reason"])
        fires = [ev for ev in doc["events"]
                 if ev["kind"] == "fault_fire"]
        assert fires and all(ev["point"] == "nan_kv" for ev in fires)
    assert "poison" in reasons
    assert "degrade" in reasons
    assert eng.flight.dumps == len(files)


@pytest.mark.chaos
def test_alloc_and_cow_storm_under_scheduler(setup):
    """Allocation storm (bursty page_alloc + cow_clone failures) against
    the scheduler with backoff + deadlines: blocked admissions back off,
    preempted slots recompute-resume, and the drained system matches the
    fault-free token streams with an empty pool."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, 12)
    tails = [rng.integers(0, cfg.vocab_size, 2 + i) for i in range(4)]

    def run(inj):
        eng = _mk_engine(
            cfg, params, max_batch=2, prefix_cache=True, faults=inj,
            guards=GuardConfig(audit_interval=4, audit_action="repair"),
        )
        sch = Scheduler(eng, SchedulerConfig(
            chunk_size=8, prefill_pack=1, token_budget=16,
            retry_backoff=1, deadline_steps=100, max_preemptions=20,
        ))
        donor = sch.submit(np.concatenate([shared, [1]]), 2)
        sch.run_to_completion(max_steps=200)
        assert donor.done
        # Exact continuations of the donated 15-token chain (not
        # page-aligned): attaching its partial tail page puts a *shared*
        # half-full page in every slot, so the first prefill write must
        # copy-on-write — the only path that consults the cow_clone hook.
        chain = np.concatenate([shared, [1], donor.generated])
        prompts = [np.concatenate([chain, t]) for t in tails]
        hs = [sch.submit(p, max_new_tokens=8) for p in prompts]
        sch.run_to_completion(max_steps=800)
        assert all(h.done for h in hs)
        return [tuple(h.generated) for h in hs], eng, sch

    base, _, _ = run(None)
    inj = FaultInjector({
        "page_alloc": FaultSpec(rate=0.4, start=2, stop=30, burst=2),
        "cow_clone": FaultSpec(rate=0.5, start=2, stop=30),
    }, seed=3)
    got, eng, sch = run(inj)
    assert got == base
    assert inj.total_fires > 0
    assert sch.stats.poisoned == 0                # pressure, not poison
    _assert_recovered(eng)


@pytest.mark.chaos
def test_preempt_storm_and_latency_spikes(setup):
    """Forced preemption storms + tick-latency spikes: every request still
    drains to its fault-free stream (recompute-resume is exact) and the
    pool comes back empty."""
    cfg, params = setup
    base = _run(_mk_engine(cfg, params), cfg)
    inj = FaultInjector({
        "preempt_storm": FaultSpec(rate=0.3, start=3, stop=20,
                                   magnitude=2),
        "tick_latency": FaultSpec(rate=0.2, stop=20, magnitude=0.001),
    }, seed=4)
    eng = _mk_engine(cfg, params, faults=inj,
                     guards=GuardConfig(audit_interval=3,
                                        audit_action="repair"))
    assert _run(eng, cfg) == base
    assert inj.fires["preempt_storm"] >= 1
    assert eng.stats.preemptions >= 1
    _assert_recovered(eng)


@pytest.mark.chaos
def test_trie_corruption_caught_by_audit_and_repaired(setup):
    """Host-memory corruption of the radix trie: the periodic audit
    detects it (``prefix_cache.check()``), the repair action resets the
    trie from the pool's records, and decoding continues token-identical —
    sharing is a performance layer, never a correctness dependency."""
    cfg, params = setup
    rng = np.random.default_rng(9)
    shared = rng.integers(0, cfg.vocab_size, 16)
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab_size, 3 + i)])
               for i in range(3)]

    def run(inj):
        eng = _mk_engine(
            cfg, params, prefix_cache=True, faults=inj,
            guards=GuardConfig(audit_interval=2, audit_action="repair"),
        )
        sch = Scheduler(eng, SchedulerConfig(
            chunk_size=8, prefill_pack=2, token_budget=32,
        ))
        donor = sch.submit(np.concatenate([shared, [1]]), 2)
        sch.run_to_completion(max_steps=200)
        hs = [sch.submit(p, max_new_tokens=8) for p in prompts]
        sch.run_to_completion(max_steps=400)
        assert donor.done and all(h.done for h in hs)
        return [tuple(h.generated) for h in hs], eng

    base, _ = run(None)
    inj = FaultInjector(
        {"trie_corrupt": FaultSpec(rate=0.6, start=2, stop=12)}, seed=5
    )
    got, eng = run(inj)
    assert got == base
    assert inj.fires["trie_corrupt"] >= 1
    assert eng.stats.audit_failures >= 1
    assert eng.stats.audit_repairs >= 1
    _quiesce(eng)
    _assert_recovered(eng)


FAULT_MATRIX = [
    # (point, spec kwargs) — one cell per injection point; EXPERIMENTS.md
    # tabulates the measured outcomes of this exact sweep. The fault-free
    # run is short (donor done by injector tick ~3, main wave decoding
    # ticks ~4-11), so windows sit inside that span and lean on rate=1.0
    # for the points that must fire deterministically.
    ("page_alloc", dict(rate=0.5, start=2, stop=40, burst=2)),
    ("cow_clone", dict(rate=0.7, start=2, stop=40)),
    ("nan_output", dict(rate=1.0, start=6, stop=8)),
    ("nan_kv", dict(rate=1.0, start=6, max_fires=1)),
    ("trie_corrupt", dict(rate=0.5, start=2, stop=40)),
    ("preempt_storm", dict(rate=1.0, start=5, max_fires=1, magnitude=2)),
    ("tick_latency", dict(rate=1.0, start=2, stop=5, magnitude=0.001)),
]


@pytest.mark.chaos
@pytest.mark.parametrize("point,spec", FAULT_MATRIX,
                         ids=[p for p, _ in FAULT_MATRIX])
def test_fault_matrix_every_point_recovers(setup, point, spec, tmp_path):
    """One cell per injection point: whatever the failure mode, the system
    drains every request, leaks nothing, and ends with the gauge at 0.
    (The point-specific recovery *paths* are asserted by the dedicated
    tests above; this sweep pins the blanket survival contract.) Every
    cell must also leave a flight-recorder postmortem whose trailing
    events name the injected point — including points that fire *between*
    decode ticks (admission-time ``page_alloc``, prefill ``cow_clone``)."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, 12)
    tails = [rng.integers(0, cfg.vocab_size, 2 + i) for i in range(4)]
    inj = FaultInjector({point: FaultSpec(**spec)}, seed=6)
    eng = _mk_engine(
        cfg, params, prefix_cache=True, faults=inj,
        guards=GuardConfig(heal_after=2, audit_interval=3,
                           audit_action="repair"),
        flight_dir=str(tmp_path),
    )
    sch = Scheduler(eng, SchedulerConfig(
        chunk_size=8, prefill_pack=2, token_budget=32,
        retry_backoff=1, deadline_steps=150, max_preemptions=30,
    ))
    # donor wave populates the radix cache so sharing-dependent points
    # (cow_clone writes into shared tails, trie_corrupt needs trie nodes)
    # have real opportunities during the main wave
    donor = sch.submit(np.concatenate([shared, [1]]), 2)
    sch.run_to_completion(max_steps=200)
    assert donor.done
    # exact continuations of the donated (non-page-aligned) chain attach
    # its partial tail page shared, so prefill writes must CoW
    chain = np.concatenate([shared, [1], donor.generated])
    hs = [sch.submit(np.concatenate([chain, t]), max_new_tokens=8)
          for t in tails]
    sch.run_to_completion(max_steps=800)
    assert all(h.done for h in hs)
    assert inj.total_fires >= 1, f"{point} schedule never fired"
    _quiesce(eng)
    _assert_recovered(eng)
    # the postmortem contract: >= 1 dump on disk, and at least one
    # bundle's trailing events contain a fault_fire naming this point
    files = sorted(tmp_path.glob("flight-*.json"))
    assert files, f"{point}: faults fired but no flight dump written"
    assert any(
        ev["kind"] == "fault_fire" and ev["point"] == point
        for f in files for ev in load_flight_dump(f)["events"][-64:]
    ), f"{point}: no dump's trailing events identify the fault point"


@pytest.mark.slow
@given(
    seed=st.integers(0, 2**16),
    points=st.lists(
        st.sampled_from([
            "page_alloc", "cow_clone", "nan_output", "nan_kv",
            "preempt_storm", "trie_corrupt",
        ]),
        min_size=1, max_size=3,
    ),
    rate_pct=st.integers(5, 60),
)
@settings(max_examples=12, deadline=None)
def test_random_fault_schedules_never_leak_or_wedge(seed, points, rate_pct):
    """Randomized fault-schedule fuzz: any mix of points/rates inside a
    bounded window must leave a drainable system — every request reaches a
    terminal state, the pool is leak-free, and the gauge returns to 0."""
    cfg = get_smoke_config("mistral-nemo-12b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    inj = FaultInjector(
        {p: FaultSpec(rate=rate_pct / 100, start=2, stop=18)
         for p in set(points)},
        seed=seed,
    )
    eng = _mk_engine(
        cfg, params, prefix_cache=True, faults=inj,
        guards=GuardConfig(heal_after=2, audit_interval=3,
                           audit_action="repair"),
    )
    sch = Scheduler(eng, SchedulerConfig(
        chunk_size=8, prefill_pack=2, token_budget=32,
        retry_backoff=1, deadline_steps=150, max_preemptions=30,
    ))
    rng = np.random.default_rng(seed)
    hs = [sch.submit(rng.integers(0, cfg.vocab_size, 4 + 3 * i), 6)
          for i in range(4)]
    sch.run_to_completion(max_steps=1000)
    assert all(h.done or h.error is not None for h in hs)
    _assert_recovered(eng)
