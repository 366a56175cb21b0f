"""Continuous-batching slot-scheduler tests (trlx_tpu/serve/slots):
step-level harvest + immediate slot reuse mid-decode (the acceptance
e2e) with parity against one-shot ``generate()``, zero steady-state
recompiles, the ``serve_admit`` chaos containment paths, sampling, the
HTTP surface, and the slow-marked mixed-length soak (zero recompiles,
zero slot leaks). The device primitives' parity and the prefix cache
get their own pass in test_paged.py.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.generation import (
    _segments_of,
    generate,
    init_page_pool,
    init_slot_state,
    prefill_into_slots,
)
from trlx_tpu.serve import InferenceEngine, InferenceServer, ServeConfig
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.supervisor import RunSupervisor, chaos
from test_serve import tiny_config_dict

SERVE_SLOTS = ServeConfig(
    buckets=[[2, 8, 8], [4, 8, 8], [4, 16, 8]],
    max_queue=64,
    request_timeout=30.0,
    slots=4,
    page_size=4,  # divides every bucket's prompt and prompt + gen
)


@pytest.fixture(scope="module")
def engine():
    telemetry.start()
    cfg = TRLConfig.from_dict(tiny_config_dict())
    return InferenceEngine(cfg, serve=SERVE_SLOTS)


@pytest.fixture()
def fresh_registry():
    session = telemetry.start()
    yield session.registry
    telemetry.start()


@pytest.fixture()
def scheduler(engine, fresh_registry):
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    yield s
    s.stop()


def direct_generate(engine, rows, bucket, gen_size=8):
    """One-shot generate() at the same bucket — the parity oracle."""
    tokens, mask = engine.pad_batch(rows, bucket)
    gen_cfg = engine._gen_base._replace(gen_size=gen_size)
    return jax.jit(
        lambda b, e, lf, t, m, r: generate(
            engine.spec, b, e, lf, t, m, r, gen_cfg,
            compute_dtype=jnp.float32,
        )
    )(engine.blocks, engine.embed, engine.ln_f, tokens, mask,
      jax.random.PRNGKey(0))


# --------------------------------------------------------------------- #
# device primitives: parity with one-shot generate()
# --------------------------------------------------------------------- #


def test_prefill_drop_sentinel_touches_nothing(engine):
    """An all-sentinel prefill (what warmup runs: sentinel slot ids AND
    sentinel page tables) must leave pages and lanes byte-identical —
    the mode='drop' contract. The pages start non-zero, so a write that
    landed anywhere would show."""
    spec = engine.spec
    _, seg_sizes = _segments_of(engine.blocks)
    S, ps, max_pages, num_pages = 2, 4, 4, 8
    pool = jax.tree_util.tree_map(
        lambda x: x + 1, init_page_pool(spec, seg_sizes, num_pages, ps)
    )
    state = init_slot_state(S, max_pages * ps, spec.vocab_size, max_pages)
    tokens = np.zeros((2, 8), np.int32)
    mask = np.ones((2, 8), np.int32)
    new_pool, new_state = jax.jit(
        lambda pool, st, t, m, sid, mn, pt: prefill_into_slots(
            spec, engine.blocks, engine.embed, engine.ln_f, pool, st,
            t, m, sid, mn, pt, ps, compute_dtype=jnp.float32,
        )
    )(pool, state, tokens, mask, np.full((2,), S, np.int32),
      np.ones((2,), np.int32),
      np.full((2, max_pages), num_pages, np.int32))
    for a, b in zip(jax.tree_util.tree_leaves((pool, state)),
                    jax.tree_util.tree_leaves((new_pool, new_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# scheduler: the acceptance e2e
# --------------------------------------------------------------------- #


def test_mixed_length_parity_and_slot_reuse_e2e(engine, fresh_registry):
    """The tentpole acceptance scenario: concurrent mixed-length
    requests return token-identical output to one-shot generate() at the
    same bucket with zero steady-state recompiles, and a short request
    demonstrably completes (slot freed + reused by a queued request)
    while a long request is still decoding."""
    s = SlotScheduler(engine, slots=2)  # force contention on a tiny pool
    s.warmup()
    assert fresh_registry.counters.get("compile/recompiles", 0.0) == 0.0
    # submit BEFORE starting the worker so the first admission
    # deterministically takes [long, short] and the third starves
    long = s.submit([1, 2, 3, 4], max_new_tokens=8)
    short = s.submit([9, 8], max_new_tokens=1)
    third = s.submit([5, 5, 5], max_new_tokens=2)
    s.start()
    try:
        for r in (long, short, third):
            r.wait(timeout=60.0)

        # token parity per row against the (4, 8, 8) bucket oracle
        rows = [long.tokens, short.tokens, third.tokens]
        oracle = direct_generate(engine, rows, (4, 8, 8))
        for i, (req, mn) in enumerate(
            zip((long, short, third), (8, 1, 2))
        ):
            assert req.result == engine.depad_row(oracle, i, mn)

        # the step-level scheduling proof, from the event log: short's
        # slot is freed and REUSED by the third request strictly before
        # the long request finishes
        events = list(s.events)
        free_short = events.index(("free", short_slot(events, short), short))
        admit_third = next(
            i for i, ev in enumerate(events)
            if ev[0] == "admit" and ev[2] is third
        )
        free_long = next(
            i for i, ev in enumerate(events)
            if ev[0] == "free" and ev[2] is long
        )
        assert free_short < admit_third < free_long
        assert events[admit_third][1] == events[free_short][1], (
            "the third request must reuse the short request's freed slot"
        )

        # the third request waited for a slot while decode kept stepping
        assert fresh_registry.counters["serve/preempted_steps"] >= 1.0
        assert fresh_registry.counters["serve/admissions"] == 3.0
        assert fresh_registry.counters["serve/evictions"] == 3.0
        assert fresh_registry.gauges["serve/slot_occupancy"] == 0.0
        assert fresh_registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert s.free_slots() == 2  # no slot leaked
    finally:
        s.stop()


def short_slot(events, req):
    for kind, slot, r in events:
        if kind == "admit" and r is req:
            return slot
    raise AssertionError("request was never admitted")


def test_per_request_max_new_bounds_latency(engine, fresh_registry,
                                            scheduler):
    """Requests terminate at THEIR OWN max_new_tokens, not the bucket
    gen extent — the step-level scheduling win the static path cannot
    express."""
    reqs = [
        scheduler.submit([i + 1, 2, 3], max_new_tokens=n)
        for i, n in enumerate((1, 3, 5, 8, 2, 7))
    ]
    for r in reqs:
        r.wait(timeout=60.0)
    eos = engine._gen_base.eos_token_id
    for r in reqs:
        assert len(r.result) <= r.max_new_tokens
        if len(r.result) < r.max_new_tokens:  # early only via eos
            assert r.result[-1] == eos
    assert fresh_registry.counters.get("compile/recompiles", 0.0) == 0.0
    assert scheduler.free_slots() == scheduler.runtime.num_slots


def test_prompt_class_rounding_and_validation(engine, scheduler):
    with pytest.raises(ValueError, match="empty prompt"):
        scheduler.submit([], max_new_tokens=2)
    with pytest.raises(ValueError, match="must be >= 1"):
        scheduler.submit([1], max_new_tokens=0)
    with pytest.raises(ValueError, match="fits no serve bucket"):
        scheduler.submit([1], max_new_tokens=99)
    long_prompt = list(range(1, 13))  # rounds to the (16, 8) class
    req = scheduler.submit(long_prompt, max_new_tokens=2)
    req.wait(timeout=60.0)
    assert req.shape == (16, 8)
    oracle = direct_generate(engine, [long_prompt], (4, 16, 8))
    assert req.result == engine.depad_row(oracle, 0, 2)


def test_queue_overflow_rejected(engine, fresh_registry):
    from trlx_tpu.serve import QueueFull

    s = SlotScheduler(engine, max_queue=2)  # not started: nothing drains
    s.submit([1], max_new_tokens=1)
    s.submit([2], max_new_tokens=1)
    with pytest.raises(QueueFull, match="retry with backoff"):
        s.submit([3], max_new_tokens=1)
    assert fresh_registry.counters["serve/rejected"] == 1.0
    s.stop()  # pending requests are failed, not stranded


def test_stopped_scheduler_fails_pending(engine):
    s = SlotScheduler(engine)
    req = s.submit([1, 2], max_new_tokens=2)
    s.stop()
    with pytest.raises(RuntimeError, match="scheduler stopped"):
        req.wait(timeout=1.0)


# --------------------------------------------------------------------- #
# serve_admit chaos containment
# --------------------------------------------------------------------- #


def test_chaos_admit_hang_is_attributable_stall(engine, fresh_registry):
    """serve_admit:hang wedges the admission phase; the watchdog must
    attribute the stall to 'serve_admit' (not silence, not a misnamed
    phase), and releasing the hang replays the batch (crash-only
    recovery) while the loop keeps serving."""
    exit_codes = []
    sup = RunSupervisor(
        stall_timeout=0.3, stall_first_timeout=0.3,
        stall_grace=10_000.0, exit_fn=exit_codes.append,
    )
    chaos.configure("serve_admit:hang=60@1")
    s = SlotScheduler(engine, run_supervisor=sup)
    s.warmup()
    s.start()
    try:
        req = s.submit([1, 2, 3], max_new_tokens=2)
        deadline = time.monotonic() + 15.0
        while sup.stalls == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sup.stalls >= 1, "watchdog never flagged the hung admission"
        assert sup.stalled_phase == "serve_admit"
        assert fresh_registry.counters["fault/stalls"] >= 1.0
        chaos.reset()  # releases the hang as ChaosHang in the worker
        # the released hang is an admission fault: the batch is
        # RE-QUEUED for replay and completes once the seam is clear
        assert req.wait(timeout=15.0).result is not None
        assert req.replays == 1
        assert fresh_registry.counters["serve/replays"] >= 1.0
        # the loop survived: a fresh request is admitted and decoded
        ok = s.submit([4, 5], max_new_tokens=2)
        assert ok.wait(timeout=30.0).result is not None
        assert not exit_codes  # grace was huge: no escalation
    finally:
        chaos.reset()
        s.stop()


def test_chaos_admit_exc_replays_batch_not_loop(engine, fresh_registry,
                                                scheduler):
    """A poisoned admission (serve_admit:exc) RE-QUEUES its batch for
    replay instead of failing it (crash-only serving): the request
    completes on the retried admission, bit-identical."""
    chaos.configure("serve_admit:exc@1")
    try:
        req = scheduler.submit([1, 2], max_new_tokens=2)
        assert req.wait(timeout=30.0).result is not None
        oracle = direct_generate(engine, [[1, 2]], (2, 8, 8))
        assert req.result == engine.depad_row(oracle, 0, 2)
        assert req.replays == 1
        assert fresh_registry.counters["serve/replays"] >= 1.0
        assert scheduler.free_slots() == scheduler.runtime.num_slots
        ok = scheduler.submit([3, 4], max_new_tokens=2)
        assert ok.wait(timeout=30.0).result is not None
    finally:
        chaos.reset()


def test_poisoned_step_replays_live_and_recovers(engine, fresh_registry,
                                                 scheduler):
    """A decode-step failure (serve_decode:exc) resets the lanes and
    RE-QUEUES the in-flight requests instead of failing them — the
    replayed request finishes with output bit-identical to an
    uninterrupted run (the greedy-parity invariant makes replay safe),
    and the loop keeps serving."""
    chaos.configure("serve_decode:exc@1")
    try:
        req = scheduler.submit([1, 2], max_new_tokens=4)
        assert req.wait(timeout=30.0).result is not None
        oracle = direct_generate(engine, [[1, 2]], (2, 8, 8))
        assert req.result == engine.depad_row(oracle, 0, 4)
        assert req.replays == 1
        assert fresh_registry.counters["serve/replays"] >= 1.0
        assert fresh_registry.counters.get("serve/request_errors", 0) == 0
        assert scheduler.free_slots() == scheduler.runtime.num_slots
        ok = scheduler.submit([3, 4], max_new_tokens=2)
        assert ok.wait(timeout=30.0).result is not None
    finally:
        chaos.reset()


def test_replay_budget_exhaustion_is_typed_503(engine, fresh_registry,
                                               scheduler):
    """Every step poisoned (serve_decode:exc@*): the request burns its
    full ``serve.max_replays`` budget and completes with the typed
    ReplayExhausted (HTTP 503 + reason), not a raw ChaosError — and the
    engine still serves once the fault clears."""
    from trlx_tpu.serve.admission import ReplayExhausted

    chaos.configure("serve_decode:exc@*")
    try:
        req = scheduler.submit([1, 2], max_new_tokens=2)
        with pytest.raises(ReplayExhausted, match="max_replays"):
            req.wait(timeout=30.0)
        assert req.replays == engine.serve.max_replays + 1
    finally:
        chaos.reset()
    assert scheduler.free_slots() == scheduler.runtime.num_slots
    ok = scheduler.submit([3, 4], max_new_tokens=2)
    assert ok.wait(timeout=30.0).result is not None


def test_replay_double_fault_falls_back_to_fail(engine, fresh_registry,
                                                scheduler):
    """A fault INSIDE recovery itself (serve_replay:exc) is a double
    fault: replay is abandoned and the batch fails like pre-replay
    containment — never a wedged loop."""
    chaos.configure("serve_decode:exc@1;serve_replay:exc@1")
    try:
        req = scheduler.submit([1, 2], max_new_tokens=4)
        with pytest.raises(chaos.ChaosError):
            req.wait(timeout=30.0)
        assert scheduler.free_slots() == scheduler.runtime.num_slots
        ok = scheduler.submit([3, 4], max_new_tokens=2)
        assert ok.wait(timeout=30.0).result is not None
    finally:
        chaos.reset()


# --------------------------------------------------------------------- #
# HTTP surface
# --------------------------------------------------------------------- #


def _post(port, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, json.loads(resp.read())


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=60
    ) as resp:
        return resp.status, json.loads(resp.read())


def test_http_endpoint_on_slots_scheduler(engine, fresh_registry):
    server = InferenceServer(engine, port=0).start(warmup=True)
    try:
        status, health = _get(server.port, "/healthz")
        assert status == 200 and health["status"] == "ok"
        assert health["scheduler"] == "slots" and health["warmed"]
        assert health["slots"] == 4 and health["free_slots"] == 4

        prompts = ["a", "bc", "def", "ghij"]
        results = [None] * len(prompts)

        def call(i):
            _, results[i] = _post(
                server.port, {"prompt": prompts[i], "max_new_tokens": 8}
            )

        threads = [
            threading.Thread(target=call, args=(i,))
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert all(r is not None for r in results)

        rows = [engine.encode_prompt(p) for p in prompts]
        oracle = direct_generate(engine, rows, (4, 8, 8))
        for i in range(len(prompts)):
            assert results[i]["tokens"] == engine.depad_row(oracle, i, 8)

        _, metrics = _get(server.port, "/metrics")
        assert metrics["counters"]["compile/recompiles"] == 0
        assert metrics["counters"]["serve/admissions"] >= 4
        assert metrics["counters"]["serve/evictions"] >= 4
        assert "serve/preempted_steps" in metrics["counters"]  # predeclared
        assert "serve/slot_occupancy" in metrics["gauges"]
        assert any(
            k.startswith("time/serve/prefill_b") for k in metrics["timings"]
        )
        assert "serve/slot_step" in {
            k.removeprefix("time/") for k in metrics["timings"]
        }
    finally:
        server.stop()


# --------------------------------------------------------------------- #
# sampling (do_sample: true): the per-step stream
# --------------------------------------------------------------------- #

SAMPLED = [([3, 1, 4, 1, 5], 8), ([9, 2], 1), ([5, 3, 5, 8, 9, 7], 3),
           ([2, 7, 1, 8], 8), ([6], 5), ([1, 6, 1, 8, 0, 3], 2)]


@pytest.fixture(scope="module")
def sampling_engine():
    telemetry.start()
    cfg = TRLConfig.from_dict(tiny_config_dict(do_sample=True))
    return InferenceEngine(cfg, serve=SERVE_SLOTS)


def sample_all(engine):
    """Every SAMPLED request through a fresh 2-slot scheduler, queued
    BEFORE the worker starts: admission order and the step each request
    rides are then the queue's alone."""
    s = SlotScheduler(engine, slots=2)
    s.warmup()
    reqs = [s.submit(toks, max_new_tokens=n) for toks, n in SAMPLED]
    s.start()
    try:
        return [r.wait(timeout=60.0).result for r in reqs]
    finally:
        s.stop()


def test_sampling_honours_vocab_budget_and_compiles_nothing(
    sampling_engine, fresh_registry
):
    eos = sampling_engine.tokenizer.eos_token_id
    results = sample_all(sampling_engine)
    for (toks, max_new), out in zip(SAMPLED, results):
        assert out and all(
            0 <= t < sampling_engine.spec.vocab_size for t in out
        )
        assert len(out) == max_new or (len(out) < max_new
                                       and out[-1] == eos)
        assert eos not in out[:-1]
    # sampled, not the greedy stream under another name
    oracle = direct_generate(
        sampling_engine, [t for t, _ in SAMPLED[:4]], (4, 8, 8)
    )
    greedy = [sampling_engine.depad_row(oracle, i, n)
              for i, (_, n) in enumerate(SAMPLED[:4])]
    assert results[:4] != greedy
    assert fresh_registry.counters.get("compile/recompiles", 0.0) == 0.0
    assert fresh_registry.counters["serve/responses"] == len(SAMPLED)


def test_sampling_is_reproducible_for_one_arrival_order(sampling_engine):
    """The stream is per STEP (``serve.seed`` + the step's counter): the
    same requests in the same order through a fresh scheduler ride the
    same steps and draw the same tokens."""
    assert sample_all(sampling_engine) == sample_all(sampling_engine)


# --------------------------------------------------------------------- #
# soak: zero recompiles, zero slot leaks at scale
# --------------------------------------------------------------------- #


@pytest.mark.slow
def test_soak_mixed_lengths_no_recompiles_no_leaks(engine, fresh_registry):
    """Hundreds of mixed-length requests through the slot scheduler:
    every compiled program stays warm (compile/recompiles == 0), every
    slot returns to the free pool, every completion respects its own
    max_new_tokens."""
    rng = np.random.default_rng(0)
    s = SlotScheduler(engine, max_queue=1024)
    s.warmup()
    s.start()
    try:
        reqs = []
        for i in range(300):
            plen = int(rng.integers(1, 16))
            tokens = [int(t) for t in rng.integers(0, 250, size=plen)]
            mn = int(rng.integers(1, 9))
            reqs.append(s.submit(tokens, max_new_tokens=mn))
        for r in reqs:
            r.wait(timeout=300.0)
        assert all(len(r.result) <= r.max_new_tokens for r in reqs)
        assert s.queue_depth() == 0
        assert s.free_slots() == s.runtime.num_slots, "slot leak"
        assert not s._speculators, "leaked per-slot speculator state"
        assert fresh_registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert fresh_registry.counters["serve/admissions"] == 300.0
        assert fresh_registry.counters["serve/evictions"] == 300.0
        assert fresh_registry.counters.get("serve/request_errors", 0.0) == 0.0
    finally:
        s.stop()


# --------------------------------------------------------------------- #
# two classes of page: the window class through the scheduler
# --------------------------------------------------------------------- #


def _window_blocks_needed(pos, window=16, page_size=8):
    """Logical pages the query at ``pos`` still reads, and the one it
    writes: from the page of ``pos - window + 1`` on."""
    return list(range(max((pos - window + 1) // page_size, 0),
                      pos // page_size + 1))


def test_a_window_page_is_released_at_the_step_that_passes_it():
    from test_cohere2_moe import build, prompt_of

    sched = build()
    req = sched.submit(prompt_of(13), max_new_tokens=16)  # one-shot class
    sched._admit()
    (slot, live), = sched._live.items()
    rt, ring = sched.runtime, sched.runtime.ring_pages
    freed, steps = [sched.cache.window_pages_freed], 0
    while sched._live:
        pos = 13 + steps  # the position this step writes
        sched._step()
        steps += 1
        freed.append(sched.cache.window_pages_freed)
        if sched._live:
            # exactly the pages the step's window reached stay mapped ...
            assert sorted(live.wmap) == _window_blocks_needed(pos)
            # ... and the ring shows them and nothing else
            row = sched._wtable[slot]
            assert {int(p) for p in row if p < rt.num_window_pages} == \
                set(live.wmap.values())
            assert all(row[b % ring] == p for b, p in live.wmap.items())
    # 13 + 16 positions, window 16, pages of 8: page 0 goes when position
    # 23 is written (its last key, 7, is 16 behind), never earlier
    assert freed[23 - 13] == 0 and freed[23 - 13 + 1] == 1
    assert req.result and sched.cache.window_reserved == 0


def test_a_sharers_window_keeps_a_page_the_owner_has_passed():
    from test_cohere2_moe import SPEC, build, prompt_of, reference_logits

    sched = build()
    doc = prompt_of(32)  # 4 whole pages, committed by the first request
    first = sched.submit(doc + [5], max_new_tokens=16)
    sched._admit()
    second = sched.submit(doc + [6, 7], max_new_tokens=4)
    sched._admit()
    assert second.trace.prefix_blocks_hit == 4
    a, b = (sched._live[s] for s in sorted(sched._live, key=lambda s: sched._live[s].request.seq))
    shared = b.wmap[3]  # page 3 of the document, from the trie
    assert a.wmap[3] == shared
    assert sched.cache.window_allocator.refcount(shared) == 2
    while not second.done.is_set():
        sched._step()
    # the sharer is gone; the owner still reads page 3 (position 33 + 5)
    assert sched.cache.window_allocator.refcount(shared) == 1
    while sched._live:
        sched._step()
    assert 3 not in a.wmap and sched.cache.window_allocator.refcount(shared) == 0
    assert shared in sched.cache._node_of_wpage  # the trie keeps it cached
    for req, prompt in ((first, doc + [5]), (second, doc + [6, 7])):
        ref = reference_logits(SPEC, prompt, req.result)
        assert req.result == [int(t) for t in ref.argmax(-1)]
    cache = sched.cache
    assert not any(cache.allocator._ref) and not any(cache.window_allocator._ref)
    assert cache.window_reserved == 0


@pytest.mark.parametrize("short_class", ["window", "full"])
def test_exhaustion_of_either_class_queues(short_class):
    from test_cohere2_moe import build, prompt_of

    # one request of 16 + 16 tokens takes 4 pages of each class
    sizes = {"pages": 64, "window_pages": 24}
    sizes["window_pages" if short_class == "window" else "pages"] = 6
    sched = build(**sizes)
    reqs = [sched.submit(prompt_of(16, seed=i), max_new_tokens=16)
            for i in range(2)]
    sched._admit()
    assert len(sched._live) == 1 and sched.queue_depth() == 1
    assert sched._starved and reqs[1].trace.queue_reentries == 1
    while not reqs[0].done.is_set():
        sched._admit()
        sched._step()
    sched._admit()  # the first one's pages came back: the second is admitted
    assert len(sched._live) == 1 and sched.queue_depth() == 0
    while sched._live:
        sched._step()
    assert all(len(r.result) == 16 for r in reqs)
    assert sched.cache.window_reserved == 0


def test_flight_record_and_counters_of_a_two_class_model(fresh_registry):
    from test_cohere2_moe import build, prompt_of

    sched = build()
    req = sched.submit(prompt_of(40), max_new_tokens=12)
    sched._admit()
    while sched._live:
        sched._step()
    sched._record_step(0.0, 1.0)
    rec = sched.flight.snapshot()[-1]
    assert {"pairs_here", "experts_hit", "pages_full", "pages_window",
            "window_freed", "moe_load"} <= set(rec)
    counters = fresh_registry.counters
    assert rec["pairs_here"] == counters["serve/moe/pairs_here"] > 0
    assert rec["window_freed"] == counters["serve/window_pages_freed"] >= 3
    assert counters["serve/prefill_chunks"] == 2  # 40 = 16 + 16 + a rest of 8
    gauges = fresh_registry.gauges
    assert gauges["serve/pages_in_use{class=window}"] == rec["pages_window"]
    assert gauges["serve/moe/load_max_over_mean"] >= 1.0
    assert len(req.result) == 12
