"""Deterministic simulation suite for the continuous-batching scheduler.

Everything here runs on a :class:`VirtualClock` — scripted arrival traces
(bursty, uniform, adversarial mixed prompt lengths), zero wall-clock sleeps.
The load-bearing assertions:

* batching decisions — occupancy follows the trace (bursty fills all slots,
  uniform trickles in, completions free slots for the backlog);
* slot lifecycle — every admitted request's slot is freed, no leaks, slots
  are reused across requests;
* FIFO fairness within a bucket — admission order == arrival order;
* byte-identical generation — the coalesced, bucket-padded scheduler output
  equals sequential unbatched `generate()` token-for-token;
* bucket-ladder properties (hypothesis) — smallest-rung-≥-length, padding
  invariance of real-position logits, and PlanRegistry round-trips (a warm
  mixed trace reports misses == 0);
* the hoisted-jit regression — repeated `generate()` calls do not retrace.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, reduced
from repro.core.engine import (
    Engine,
    PlanRegistry,
    bucket_for,
    plan_cache_for,
    reset_plan_caches,
)
from repro.core.template import TemplateConfig, Template, default_template
from repro.launch.scheduler import (
    Request,
    SamplingParams,
    SchedulerConfig,
    ServeScheduler,
    TRACE_COUNTS,
    VirtualClock,
    compiled_steps,
    replay_trace,
    synthetic_trace,
)
from repro.launch.serve import generate
from repro.models import transformer as T

LADDER = (8, 16, 24)
MAX_NEW = 6


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen2-0.5b"))
    tpl = default_template()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params, tpl


def make_sched(setup, *, slots=3, ladder=LADDER, max_new=MAX_NEW, **kw):
    cfg, params, tpl = setup
    return ServeScheduler(
        cfg, params, tpl=tpl, clock=VirtualClock(),
        sched=SchedulerConfig(ladder=ladder, slots=slots,
                              max_new_limit=max_new, **kw),
    )


def prompts_of(lengths, vocab=128, seed=7):
    rng = np.random.default_rng(seed)
    return [tuple(int(t) for t in rng.integers(0, vocab, size=n)) for n in lengths]


# ---------------------------------------------------------------------------
# batching decisions
# ---------------------------------------------------------------------------


def test_bursty_trace_fills_all_slots(setup):
    sched = make_sched(setup, slots=3)
    trace = [Request(prompt=p, max_new=4, arrival=0.0)
             for p in prompts_of([5, 9, 3, 17, 8, 12])]
    replay_trace(sched, trace, tick=1.0)
    # burst: first step admits slots-many, the backlog waits for completions
    occ = [e["decoded"] for e in sched.history if e["decoded"]]
    assert occ[0] == 3, f"burst must fill every slot, got occupancy {occ[0]}"
    assert max(occ) == 3
    assert sched.counters["completed"] == 6
    assert sched.counters["admitted"] == 6
    # coalescing: strictly fewer decode steps than sequential serving would do
    sequential_steps = sum(3 for _ in trace)  # max_new - 1 each
    assert sched.counters["decode_steps"] < sequential_steps


def test_uniform_trace_trickles(setup):
    sched = make_sched(setup, slots=4)
    trace = [Request(prompt=p, max_new=3, arrival=float(4 * i))
             for i, p in enumerate(prompts_of([6, 6, 6, 6]))]
    replay_trace(sched, trace, tick=1.0)
    # spaced arrivals: each request runs alone (completes before the next)
    assert all(e["decoded"] <= 1 for e in sched.history)
    assert sched.counters["completed"] == 4


def test_adversarial_mixed_lengths(setup):
    """Every bucket sees traffic; over-long prompts are refused up front."""
    sched = make_sched(setup, slots=3)
    lengths = [1, 8, 9, 16, 17, 24, 2, 23]
    trace = [Request(prompt=p, max_new=3, arrival=float(i % 3))
             for i, p in enumerate(prompts_of(lengths))]
    too_long = Request(prompt=prompts_of([25])[0], max_new=3, arrival=0.0)
    stats = replay_trace(sched, trace + [too_long], tick=1.0)
    assert sched.counters["completed"] == len(trace)
    assert sched.counters["rejected"] == 1
    assert too_long.state == "rejected"
    by_bucket = stats["buckets"]
    assert by_bucket[8]["admitted"] == 3   # lengths 1, 8, 2
    assert by_bucket[16]["admitted"] == 2  # lengths 9, 16
    assert by_bucket[24]["admitted"] == 3  # lengths 17, 24, 23
    assert sum(b["admitted"] for b in by_bucket.values()) == len(trace)


def test_unsupported_families_rejected_at_construction(setup):
    """Padding is unsound for recurrent/SSM state and for sliding-window
    rings shorter than a bucket — those configs must be refused up front."""
    cfg, params, tpl = setup
    for name in ("mamba2-1.3b", "recurrentgemma-9b", "whisper-medium"):
        bad_cfg = reduced(get_config(name))
        with pytest.raises(ValueError):
            ServeScheduler(bad_cfg, None, tpl=tpl, clock=VirtualClock())
    import dataclasses

    # all-local hybrid: the window-sized ring (8 < bucket rungs) is refused
    windowed = dataclasses.replace(cfg, family="hybrid", pattern=("attn",),
                                   window=8)
    assert all(p.mixer == "local" for p in T.plan_pattern(windowed))
    with pytest.raises(ValueError):
        ServeScheduler(windowed, params, tpl=tpl, clock=VirtualClock())


def test_admission_control_queue_cap(setup):
    sched = make_sched(setup, slots=1, max_queue=2)
    trace = [Request(prompt=p, max_new=2, arrival=0.0)
             for p in prompts_of([4, 4, 4, 4, 4])]
    for r in trace:
        sched.submit(r)
    assert sched.counters["rejected"] == 3  # queue holds 2, rest refused
    sched.drain(tick=1.0)
    assert sched.counters["completed"] == 2


# ---------------------------------------------------------------------------
# slot lifecycle
# ---------------------------------------------------------------------------


def test_slot_lifecycle_no_leak_and_reuse(setup):
    sched = make_sched(setup, slots=2)
    trace = [Request(prompt=p, max_new=3, arrival=0.0)
             for p in prompts_of([4, 6, 8, 5, 7])]
    replay_trace(sched, trace, tick=1.0)
    # no leak: every slot freed, nothing active, every request completed
    assert sched._free == [0, 1]
    assert sched.active == {}
    assert all(r.state == "completed" and r.slot is None for r in trace)
    # every admitted request held exactly one slot per admission
    for r in trace:
        assert len(r.slot_history) == 1 + r.preemptions
    # reuse: 5 requests through 2 slots must revisit slots
    used = [s for r in trace for s in r.slot_history]
    assert len(used) == 5 and set(used) == {0, 1}


def test_eos_frees_slot_early(setup):
    cfg, params, tpl = setup
    sched = make_sched(setup, slots=1)
    prompt = prompts_of([6])[0]
    # oracle: what greedy decode will emit, so eos triggers on token 2 of 5
    ref = np.asarray(generate(cfg, params, jnp.asarray([prompt], jnp.int32),
                              gen=5, tpl=tpl))[0]
    eos = int(ref[1])
    req = Request(prompt=prompt, max_new=5, eos_id=eos)
    replay_trace(sched, [req], tick=1.0)
    assert req.finish_reason == "eos"
    stop = next(i for i, t in enumerate(ref.tolist()) if t == eos)
    assert req.generated == ref[: stop + 1].tolist()
    assert sched._free == [0]


def test_preemption_requeues_and_completes(setup):
    cfg, params, tpl = setup
    sched = make_sched(setup, slots=1, preempt_after=2.0)
    a = Request(prompt=prompts_of([4])[0], max_new=6, arrival=0.0)
    b = Request(prompt=prompts_of([5], seed=9)[0], max_new=2, arrival=1.0)
    replay_trace(sched, [a, b], tick=1.0)
    assert sched.counters["preempted"] == 1
    assert a.preemptions == 1
    assert len(a.slot_history) == 2  # admitted, preempted, re-admitted
    assert a.state == b.state == "completed"
    assert len(a.generated) == 6 and len(b.generated) == 2
    assert sched._free == [0]
    # parity must survive the re-prefill of prompt+generated: the preempted
    # request's tokens still match the unbatched path end to end
    for r in (a, b):
        ref = np.asarray(generate(cfg, params, jnp.asarray([r.prompt], jnp.int32),
                                  gen=r.max_new, tpl=tpl))[0]
        assert r.generated == ref.tolist()


# ---------------------------------------------------------------------------
# FIFO fairness within a bucket
# ---------------------------------------------------------------------------


def test_fifo_within_bucket(setup):
    sched = make_sched(setup, slots=1)  # serialize admissions
    trace = [Request(prompt=p, max_new=2, arrival=float(i) * 0.25)
             for i, p in enumerate(prompts_of([6, 5, 7, 6, 4]))]  # all bucket 8
    replay_trace(sched, trace, tick=1.0)
    admitted_order = [rid for e in sched.history for rid in e["admitted"]]
    assert admitted_order == [r.rid for r in trace]
    # completion timestamps are monotone in arrival order too
    times = [sched.results[r.rid].completed_at for r in trace]
    assert times == sorted(times)


# ---------------------------------------------------------------------------
# byte-identical generation vs the unbatched path
# ---------------------------------------------------------------------------


def test_batched_tokens_byte_identical_to_unbatched(setup):
    cfg, params, tpl = setup
    sched = make_sched(setup, slots=3)
    lengths = [5, 9, 3, 17, 8, 24, 2]
    trace = [Request(prompt=p, max_new=MAX_NEW, arrival=float(i % 2))
             for i, p in enumerate(prompts_of(lengths))]
    replay_trace(sched, trace, tick=1.0)
    for r in trace:
        ref = np.asarray(generate(cfg, params, jnp.asarray([r.prompt], jnp.int32),
                                  gen=r.max_new, tpl=tpl))[0]
        got = np.asarray(sched.results[r.rid].generated)
        assert got.tolist() == ref.tolist(), (
            f"rid {r.rid} (len {len(r.prompt)}): scheduler {got.tolist()} "
            f"!= unbatched {ref.tolist()}"
        )


def test_q16_scheduler_decode_determinism():
    """The PR 4 mixed trace replayed under NumericsPolicy('q16') yields
    byte-identical tokens to the unbatched q16 `generate()`, with an int16
    slot-indexed KV cache, and the warm registry replay reports zero new DSE
    searches (DESIGN.md §8)."""
    from repro.core.quantization import NumericsPolicy

    cfg = reduced(get_config("qwen2-0.5b"))
    tpl = default_template("q16")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cal = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0, cfg.vocab)
    policy = T.calibrate_policy(tpl, cfg, params, cal)
    sched = ServeScheduler(
        cfg, params, tpl=tpl, clock=VirtualClock(), policy=policy,
        sched=SchedulerConfig(ladder=LADDER, slots=3, max_new_limit=MAX_NEW),
    )
    sched.warmup()
    assert jax.tree.leaves(sched.cache or {}) == []  # cache built on admit
    m0 = sched.registry.misses
    lengths = [5, 9, 3, 17, 8, 24, 2]  # the PR 4 mixed trace
    trace = [Request(prompt=p, max_new=MAX_NEW, arrival=float(i % 2))
             for i, p in enumerate(prompts_of(lengths))]
    replay_trace(sched, trace, tick=1.0)
    assert sched.counters["completed"] == len(trace)
    assert sched.registry.misses == m0, (
        "warm q16 registry replay must report zero new DSE searches")
    assert sched.cache["blocks"][0]["attn"]["k"].dtype == jnp.int16
    for r in trace:
        ref = np.asarray(generate(cfg, params, jnp.asarray([r.prompt], jnp.int32),
                                  gen=r.max_new, tpl=tpl, policy=policy))[0]
        got = sched.results[r.rid].generated
        assert got == ref.tolist(), (
            f"rid {r.rid} (len {len(r.prompt)}): q16 scheduler {got} "
            f"!= unbatched q16 {ref.tolist()}"
        )


def test_scheduler_rejects_unsupported_policy_combos(setup):
    """--backend/--policy mismatches fail at construction with clear errors
    instead of silently serving the wrong numerics."""
    from repro.core.quantization import NumericsPolicy

    cfg, params, tpl = setup  # tpl is the float (xla) template
    with pytest.raises(ValueError, match="requires the 'q16' backend"):
        ServeScheduler(cfg, params, tpl=tpl, clock=VirtualClock(),
                       policy=NumericsPolicy("q16"))


# ---------------------------------------------------------------------------
# bucket-ladder properties (hypothesis)
# ---------------------------------------------------------------------------


@given(st.integers(0, 4096))
@settings(max_examples=40, deadline=None)
def test_bucket_is_smallest_rung_geq_length(length):
    ladder = (8, 16, 64, 256, 1024)
    b = bucket_for(length, ladder)
    fitting = [r for r in ladder if r >= length]
    assert b == (min(fitting) if fitting else None)
    if b is not None:
        assert b >= length
        assert all(r < length or r >= b for r in ladder)


_PAD_ENV = {}


@given(st.integers(1, 16))
@settings(max_examples=6, deadline=None)
def test_padding_never_changes_real_position_logits(s):
    if not _PAD_ENV:
        cfg = reduced(get_config("qwen2-0.5b"))
        _PAD_ENV["cfg"] = cfg
        _PAD_ENV["tpl"] = default_template()
        _PAD_ENV["params"] = T.init_params(jax.random.PRNGKey(0), cfg)
    cfg, tpl, params = _PAD_ENV["cfg"], _PAD_ENV["tpl"], _PAD_ENV["params"]
    toks = jax.random.randint(jax.random.PRNGKey(s), (1, s), 0, cfg.vocab)
    bucket = 16
    padded = jnp.pad(toks, ((0, 0), (0, bucket - s)))
    lg_exact, _ = T.prefill(tpl, cfg, params, toks, cache_len=32)
    lg_padded, _ = T.prefill(tpl, cfg, params, padded, cache_len=32,
                             last_pos=jnp.int32(s - 1))
    np.testing.assert_allclose(np.asarray(lg_padded), np.asarray(lg_exact),
                               atol=1e-5, rtol=1e-5)


def test_bucket_ladder_round_trips_plan_registry(tmp_path):
    """Every rung's plan persists through the store and replans with 0 misses."""
    reg = PlanRegistry()
    eng = Engine(TemplateConfig(backend="pallas"), plan_cache=reg)
    ladder = (8, 32, 128)
    plans = eng.plan_gemm_ladder(ladder, 96, 64)
    assert sorted(plans) == sorted(ladder)
    assert reg.misses == len(ladder)
    path = str(tmp_path / "ladder_store.json")
    reg.save(path)
    warm = PlanRegistry()
    warm.load(path)
    eng2 = Engine(TemplateConfig(backend="pallas"), plan_cache=warm)
    plans2 = eng2.plan_gemm_ladder(ladder, 96, 64)
    assert warm.misses == 0 and warm.hits == len(ladder)
    assert plans2 == plans


def test_warm_mixed_trace_zero_misses():
    """After warmup, a mixed trace replays against the registry with 0 misses
    (pallas backend: every GEMM consults the PlanRegistry at trace time)."""
    reset_plan_caches()
    cfg = reduced(get_config("qwen2-0.5b"))
    tpl = default_template("pallas")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    sched = ServeScheduler(
        cfg, params, tpl=tpl, clock=VirtualClock(),
        sched=SchedulerConfig(ladder=(8, 16), slots=2, max_new_limit=3),
    )
    per_bucket = sched.warmup()
    assert all(b["misses"] > 0 for b in per_bucket.values()), (
        "cold warmup must run the DSE for every bucket")
    reg = sched.registry
    h0, m0 = reg.hits, reg.misses
    trace = synthetic_trace(5, seed=1, vocab=cfg.vocab, ladder=(8, 16), max_new=3)
    stats = replay_trace(sched, trace, tick=1.0)
    assert sched.counters["completed"] == 5
    assert reg.misses == m0, (
        f"mixed trace against a warm registry must report zero new DSE "
        f"searches, got {reg.misses - m0}")
    assert stats["registry"]["misses"] == m0
    reset_plan_caches()


# ---------------------------------------------------------------------------
# hoisted-jit regression: repeated generate()/scheduler calls don't retrace
# ---------------------------------------------------------------------------


def test_generate_does_not_retrace(setup):
    cfg, params, tpl = setup
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0, cfg.vocab)
    generate(cfg, params, toks, gen=3, tpl=tpl)  # may trace (cold)
    before = dict(TRACE_COUNTS)
    for _ in range(3):
        generate(cfg, params, toks, gen=3, tpl=tpl)
    assert dict(TRACE_COUNTS) == before, (
        f"repeated generate() retraced: {dict(TRACE_COUNTS)} vs {before}")


def test_scheduler_steps_do_not_retrace(setup):
    cfg, params, tpl = setup
    sched = make_sched(setup, slots=2)
    sched.warmup()
    trace = [Request(prompt=p, max_new=3, arrival=0.0)
             for p in prompts_of([4, 9, 17])]
    replay_trace(sched, trace, tick=1.0)
    before = dict(TRACE_COUNTS)
    replay_trace(sched, [Request(prompt=p, max_new=3, arrival=0.0)
                         for p in prompts_of([6, 12, 20], seed=11)], tick=1.0)
    assert dict(TRACE_COUNTS) == before, "steady-state scheduler retraced"


def test_compiled_steps_memoized(setup):
    cfg, params, tpl = setup
    a = compiled_steps(tpl, cfg, 48)
    b = compiled_steps(tpl, cfg, 48)
    assert a[0] is b[0] and a[1] is b[1]
    c = compiled_steps(tpl, cfg, 64)
    assert c[0] is not a[0]


# ---------------------------------------------------------------------------
# coalesced (B, L) bucket prefill
# ---------------------------------------------------------------------------


_BATCH_ENV = {}


@given(st.lists(st.integers(1, 16), min_size=2, max_size=4), st.integers(0, 9))
@settings(max_examples=8, deadline=None)
def test_batched_prefill_rows_bitwise_equal_single(lengths, seed):
    """A coalesced (B, L) prefill over mixed-length right-padded prompts is
    byte-identical per row to B separate (1, L) prefills — the property that
    makes one-launch-per-rung admission parity-free."""
    if not _BATCH_ENV:
        cfg = reduced(get_config("qwen2-0.5b"))
        _BATCH_ENV["cfg"] = cfg
        _BATCH_ENV["tpl"] = default_template()
        _BATCH_ENV["params"] = T.init_params(jax.random.PRNGKey(0), cfg)
    cfg, tpl, params = _BATCH_ENV["cfg"], _BATCH_ENV["tpl"], _BATCH_ENV["params"]
    fns = compiled_steps(tpl, cfg, 24)
    bucket = 16
    rng = np.random.default_rng(seed)
    toks = np.zeros((len(lengths), bucket), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = rng.integers(0, cfg.vocab, size=n)
    last = np.asarray([n - 1 for n in lengths], np.int32)
    lg_batch = np.asarray(
        fns.prefill(params, jnp.asarray(toks), None, jnp.asarray(last))[0])
    for i in range(len(lengths)):
        lg_one = np.asarray(
            fns.prefill(params, jnp.asarray(toks[i: i + 1]), None,
                        jnp.asarray(last[i: i + 1]))[0])[0]
        assert np.array_equal(lg_batch[i], lg_one), (
            f"row {i} (len {lengths[i]}) of the batched prefill diverged "
            f"bitwise from its (1, L) launch")


def test_batched_mode_matches_sequential_mode(setup):
    """The coalesced launches change only the launch count, never a token:
    batched vs sequential prefill_mode agree byte-for-byte on the PR 4
    mixed trace, with strictly fewer prefill launches."""
    lengths = [5, 9, 3, 17, 8, 24, 2]
    outs, launches = [], []
    for mode in ("batched", "sequential"):
        sched = make_sched(setup, slots=3, prefill_mode=mode)
        trace = [Request(prompt=p, max_new=4, arrival=0.0)
                 for p in prompts_of(lengths)]
        replay_trace(sched, trace, tick=1.0)
        assert sched.counters["completed"] == len(trace)
        outs.append([sched.results[r.rid].generated for r in trace])
        launches.append(sched.counters["prefill_launches"])
    assert outs[0] == outs[1], "prefill coalescing changed generated tokens"
    assert launches[0] < launches[1], (
        f"batched mode must issue fewer prefill launches "
        f"({launches[0]} vs sequential {launches[1]})")
    assert launches[1] == len(lengths)  # sequential: one launch per admission


def test_prefill_launches_bounded_by_occupied_rungs(setup):
    """Per tick, prefill launches <= #distinct buckets admitted that tick —
    the acceptance bar for the coalesced admission path."""
    sched = make_sched(setup, slots=3)
    lengths = [5, 9, 3, 17, 8, 24, 2]
    trace = [Request(prompt=p, max_new=MAX_NEW, arrival=float(i % 2))
             for i, p in enumerate(prompts_of(lengths))]
    stats = replay_trace(sched, trace, tick=1.0)
    by_rid = {r.rid: r for r in trace}
    for ev in sched.history:
        rungs = {by_rid[rid].bucket for rid in ev["admitted"]}
        assert ev["prefill_launches"] <= len(rungs), (
            f"tick at {ev['now']}: {ev['prefill_launches']} prefill launches "
            f"for {len(rungs)} occupied rungs")
    assert stats["prefill_coalescing"] >= 1.0
    assert stats["counters"]["prefill_launches"] < len(lengths)
    assert stats["ttft"]["n"] == len(lengths)
    assert stats["ttft"]["p50"] <= stats["ttft"]["p99"]


# ---------------------------------------------------------------------------
# chunked prefill / decode interleaving
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_unbatched(setup):
    """Streaming long prompts chunk-by-chunk (interleaved with decode) still
    yields byte-identical tokens to the unbatched `generate()`."""
    cfg, params, tpl = setup
    sched = make_sched(setup, slots=3, prefill_chunk=8)
    lengths = [5, 9, 3, 17, 8, 24, 2]
    trace = [Request(prompt=p, max_new=MAX_NEW, arrival=float(i % 2))
             for i, p in enumerate(prompts_of(lengths))]
    replay_trace(sched, trace, tick=1.0)
    assert sched.counters["completed"] == len(trace)
    assert sched.counters["chunk_steps"] > 0, "no chunked prefill happened"
    # the chunk path really interleaved: some tick ran both chunk and decode
    assert any(e["chunk_rows"] and e["decoded"] for e in sched.history), (
        "chunk launches never overlapped a decode step")
    for r in trace:
        ref = np.asarray(generate(cfg, params, jnp.asarray([r.prompt], jnp.int32),
                                  gen=r.max_new, tpl=tpl))[0]
        got = sched.results[r.rid].generated
        assert got == ref.tolist(), (
            f"rid {r.rid} (len {len(r.prompt)}): chunked {got} "
            f"!= unbatched {ref.tolist()}")


def test_prefill_chunk_step_equivalence(setup):
    """Driving prefill_chunk_step over a prompt reproduces the whole-prompt
    prefill: same cache validity, same next-token choice, logits to 1e-5."""
    cfg, params, tpl = setup
    cache_len = 24
    s = 13
    chunk = 5
    toks = np.asarray(prompts_of([s], seed=3)[0], np.int32)[None]
    lg_ref, _ = T.prefill(tpl, cfg, params, jnp.asarray(toks),
                          cache_len=cache_len)
    cache = T.init_cache(cfg, 2, cache_len, per_slot=True)
    logits = None
    for t0 in range(0, s, chunk):
        n = min(chunk, s - t0)
        blk = np.zeros((2, chunk), np.int32)
        blk[0, :n] = toks[0, t0: t0 + n]
        tvec = np.asarray([t0, -1], np.int32)  # row 1 stays inactive
        nv = np.asarray([n, 0], np.int32)
        logits, cache = T.prefill_chunk_step(
            tpl, cfg, params, jnp.asarray(blk), jnp.asarray(tvec),
            jnp.asarray(nv), cache)
    np.testing.assert_allclose(np.asarray(logits)[0], np.asarray(lg_ref)[0],
                               atol=1e-5, rtol=1e-5)
    assert int(jnp.argmax(logits[0])) == int(jnp.argmax(lg_ref[0]))
    # the inactive lane's cache row stayed fully invalid
    pos = np.asarray(cache["blocks"][0]["attn"]["pos"])
    assert (pos[:, 1] == -1).all(), "gated-off lane's cache row moved"
    assert (np.sort(pos[0, 0][pos[0, 0] >= 0]) == np.arange(s)).all()


# ---------------------------------------------------------------------------
# sampled decode lanes (per-slot RNG)
# ---------------------------------------------------------------------------


def _sampled_run(setup, seed, lengths=(5, 9, 3, 17, 8, 24, 2), **kw):
    cfg, params, tpl = setup
    sched = ServeScheduler(
        cfg, params, tpl=tpl, clock=VirtualClock(),
        sampling=SamplingParams(temperature=0.8, top_k=20, seed=seed),
        sched=SchedulerConfig(ladder=LADDER, slots=3, max_new_limit=MAX_NEW,
                              **kw),
    )
    trace = [Request(prompt=p, max_new=MAX_NEW, arrival=float(i % 2))
             for i, p in enumerate(prompts_of(list(lengths)))]
    replay_trace(sched, trace, tick=1.0)
    assert sched.counters["completed"] == len(trace)
    return [sched.results[r.rid].generated for r in trace]


def test_sampled_decode_deterministic_per_seed(setup):
    """Two replay_trace runs with the same SamplingParams.seed emit identical
    token streams (per-slot RNG lanes keyed by (seed, slot, position) under
    the VirtualClock); a different seed diverges."""
    a = _sampled_run(setup, seed=17)
    b = _sampled_run(setup, seed=17)
    assert a == b, "same-seed sampled replays diverged"
    c = _sampled_run(setup, seed=18)
    assert a != c, "distinct seeds produced identical sampled streams"
    # chunked prefill keeps per-seed determinism too
    d = _sampled_run(setup, seed=17, prefill_chunk=8)
    e = _sampled_run(setup, seed=17, prefill_chunk=8)
    assert d == e, "same-seed chunked sampled replays diverged"
