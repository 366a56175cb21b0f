"""Speculative-decoding tests (trlx_tpu/serve/speculate, the
``verify_step`` device primitive in models/generation, and the
SlotScheduler's propose -> verify -> accept loop): n-gram index
semantics (longest-gram-first lookup, the no-self-match cursor, the LRU
key bound), the radix cache's read-only ``peek_continuation``, the
pinned greedy bit-parity sweep speculation on vs off across
page_size x kv_dtype x staggered admission with zero steady-state
recompiles, the effective-tokens-per-step speedup floor on a
repetitive trace, the ``serve_speculate`` chaos drills (exc -> clean
fallback to plain decode; hang -> watchdog-attributable serve_decode
stall), replay-after-poisoned-step speculation-state reset, the
injected-draft tier, and the slow speculation soak (no leaks, no
recompiles, the per-slot speculator map drains)."""

import time

import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.serve import InferenceEngine, ServeConfig
from trlx_tpu.serve.paged import RadixCache
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.serve.speculate import DraftProposer, NgramIndex, SlotSpeculator
from trlx_tpu.supervisor import RunSupervisor, chaos
from test_serve import tiny_config_dict
from test_slots import direct_generate


def build_engine(**overrides):
    telemetry.start()
    serve = ServeConfig(**{
        "buckets": [[2, 8, 8], [4, 8, 8]], "max_queue": 64,
        "request_timeout": 30.0, "slots": 4,
        "page_size": 4,
        "speculation": "lookup", "spec_k": 4, **overrides,
    })
    return InferenceEngine(TRLConfig.from_dict(tiny_config_dict()),
                           serve=serve)


@pytest.fixture()
def fresh_registry():
    session = telemetry.start()
    yield session.registry
    telemetry.start()


# --------------------------------------------------------------------- #
# proposal tier: n-gram index + speculator + radix peek
# --------------------------------------------------------------------- #


def test_ngram_index_longest_gram_wins():
    idx = NgramIndex(ngram_max=3, max_keys=64)
    idx.extend([1, 2, 3, 9, 1, 2, 3, 7])
    # suffix [2, 3] could continue with 9 (first occurrence) or 7
    # (latest) — the trigram [1, 2, 3]'s LATEST continuation wins
    assert idx.lookup([5, 1, 2, 3]) == 7
    # a suffix only the early occurrence matches falls back to shorter
    # grams, which also resolve to the latest continuation
    assert idx.lookup([4, 4, 3]) == 7


def test_ngram_index_never_self_matches():
    idx = NgramIndex(ngram_max=2, max_keys=64)
    h = [1, 2, 3]
    idx.extend(h)
    # the history's own tail gram [2, 3] has NO continuation token yet;
    # proposing from it would replay stale text. [3] alone likewise.
    assert idx.lookup(h) is None
    h.append(4)
    idx.extend(h)
    # now [2, 3] -> 4 is real (continuation exists); the new tail [3, 4]
    # still is not indexed
    assert idx.lookup([9, 2, 3]) == 3  # history[3] == 4
    assert idx.lookup(h) is None


def test_ngram_index_lru_bound_holds():
    idx = NgramIndex(ngram_max=2, max_keys=8)
    idx.extend(list(range(100)))
    assert len(idx) <= 8
    # recent grams survive; ancient ones were evicted
    assert idx.lookup([97, 98]) == 99
    assert idx.lookup([1, 2]) is None


def test_slot_speculator_proposes_from_own_history():
    sp = SlotSpeculator([1, 2, 3, 1, 2], spec_k=3)
    # suffix [1, 2] matched at position 3 -> proposes history[2:5]
    assert sp.propose() == [3, 1, 2]
    sp.append([9])
    # novel token: no gram ends in 9 anywhere
    assert sp.propose() == []


def test_radix_peek_continuation_is_read_only():
    c = RadixCache(8, 2)
    pages = c.alloc(3)
    c.commit([1, 2, 3, 4, 5, 6], pages)
    c.release_all(pages)
    free_before = c.free_pages()
    # full-block walk + follow child chain
    assert c.peek_continuation([1, 2], 4) == [3, 4, 5, 6]
    # partial tail completes from the prefix-matching child block
    assert c.peek_continuation([1, 2, 3], 2) == [4, 5]
    # miss: unknown tail
    assert c.peek_continuation([9, 9], 4) == []
    # read-only: no refcount was taken, nothing became un-evictable
    assert c.free_pages() == free_before
    assert all(c.allocator.refcount(p) == 0 for p in pages)


# --------------------------------------------------------------------- #
# the pinned parity sweep: speculation on == off, bit-identical
# --------------------------------------------------------------------- #

ROWS = [
    [3, 1, 4, 1, 5],
    [3, 1, 4, 1, 5, 9, 2, 6],  # shares a 5-token prefix with row 0
    [9, 2, 6],
    [3, 1, 4, 1, 5, 9, 2, 6],  # full repeat of row 1
]


def _run_staggered(s, rows, max_new=8):
    first = [s.submit(r, max_new_tokens=max_new) for r in rows[:2]]
    for r in first:
        r.wait(timeout=60.0)
    second = [s.submit(r, max_new_tokens=max_new) for r in rows[2:]]
    for r in second:
        r.wait(timeout=60.0)
    out = []
    for req in first + second:
        if req.error is not None:
            raise req.error
        out.append(req.result)
    return out


@pytest.mark.parametrize("page_size", [3, 8, 24])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_greedy_parity_sweep_spec_on_vs_off(page_size, kv_dtype):
    """The acceptance invariant: greedy output with speculation: lookup
    is BIT-IDENTICAL to speculation: off across page sizes (unaligned 3,
    mid 8, bucket_max 24), both KV tiers, and staggered shared-prefix
    admission — with compile/recompiles == 0 on the speculating
    engine (verify_step is one more executable, not a signature
    drift)."""
    engine = build_engine(page_size=page_size, kv_dtype=kv_dtype)
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        spec_out = _run_staggered(s, ROWS)
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert registry.counters.get("serve/spec_proposed", 0.0) > 0
        assert s.free_slots() == s.runtime.num_slots
        assert not s._speculators
    finally:
        s.stop()
    engine_off = build_engine(page_size=page_size, kv_dtype=kv_dtype,
                              speculation="off")
    s_off = SlotScheduler(engine_off)
    s_off.warmup()
    s_off.start()
    try:
        plain_out = _run_staggered(s_off, ROWS)
    finally:
        s_off.stop()
    assert spec_out == plain_out, (
        f"speculation changed greedy output at page_size={page_size}, "
        f"kv_dtype={kv_dtype}"
    )
    if kv_dtype == "bf16":
        # bf16 KV is also pinned against the one-shot generate() oracle
        oracle = direct_generate(engine, ROWS, (4, 8, 8))
        for i, out in enumerate(spec_out):
            assert out == engine.depad_row(oracle, i, 8), (
                f"row {i} diverged from the generate() oracle"
            )


def test_spec_effective_tokens_per_step_floor(fresh_registry):
    """The CPU smoke proxy for the bench speedup claim: on a repetitive
    trace (the prompt-lookup ideal case) each verify pass accepts
    multiple tokens, so effective tokens per target step clears 1.5x —
    the shared-prefix/RLHF-shaped trace's acceptance-rate floor."""
    engine = build_engine(buckets=[[2, 8, 16], [4, 8, 16]])
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        rows = [[1, 2, 3, 1, 2, 3, 1], [7, 8, 7, 8, 7, 8]]
        reqs = [s.submit(r, max_new_tokens=16) for r in rows]
        for r in reqs:
            r.wait(timeout=60.0)
            assert r.error is None
        generated = sum(len(r.result) for r in reqs)
        steps = s._step_counter
        effective = generated / max(steps, 1)
        assert effective >= 1.5, (
            f"{generated} tokens over {steps} steps = "
            f"{effective:.2f} effective tokens/step (< 1.5)"
        )
        reg = telemetry.current().registry
        accepted = reg.counters.get("serve/spec_accepted", 0.0)
        proposed = reg.counters.get("serve/spec_proposed", 0.0)
        assert accepted > 0 and proposed >= accepted
        assert reg.counters.get("serve/spec_steps_saved") == accepted
        assert reg.gauges["serve/spec_acceptance_rate"] > 0.0
        assert reg.counters.get("compile/recompiles", 0.0) == 0.0
    finally:
        s.stop()


# --------------------------------------------------------------------- #
# serve_speculate chaos drills
# --------------------------------------------------------------------- #


def test_chaos_speculate_exc_falls_back_to_plain_decode(fresh_registry):
    """serve_speculate:exc poisons proposal gathering BEFORE anything is
    dispatched: the step completes as a plain decode (nothing
    half-committed, no replay consumed), serve/spec_fallbacks counts the
    event, and the output stays bit-identical."""
    engine = build_engine()
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    chaos.configure("serve_speculate:exc@1-2")
    try:
        req = s.submit([1, 2, 3, 1, 2, 3, 1], max_new_tokens=8)
        assert req.wait(timeout=30.0).result is not None
        assert req.replays == 0  # a proposal fault is NOT a step fault
        assert registry.counters["serve/spec_fallbacks"] >= 1.0
        oracle = direct_generate(engine, [[1, 2, 3, 1, 2, 3, 1]],
                                 (4, 8, 8))
        assert req.result == engine.depad_row(oracle, 0, 8)
        assert s.free_slots() == s.runtime.num_slots
    finally:
        chaos.reset()
        s.stop()


def test_chaos_speculate_hang_is_attributable_stall(fresh_registry):
    """serve_speculate:hang wedges proposal gathering inside the
    supervised serve_decode phase: the watchdog must attribute the
    stall to 'serve_decode'; releasing the hang lands as a caught
    proposal fault (fallback, not replay) and the request completes."""
    exit_codes = []
    sup = RunSupervisor(
        stall_timeout=0.3, stall_first_timeout=0.3,
        stall_grace=10_000.0, exit_fn=exit_codes.append,
    )
    engine = build_engine()
    registry = telemetry.current().registry
    chaos.configure("serve_speculate:hang=60@1")
    s = SlotScheduler(engine, run_supervisor=sup)
    s.warmup()
    s.start()
    try:
        req = s.submit([1, 2, 3, 1, 2], max_new_tokens=4)
        deadline = time.monotonic() + 15.0
        while sup.stalls == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sup.stalls >= 1, "watchdog never flagged the hung proposal"
        assert sup.stalled_phase == "serve_decode"
        assert registry.counters["fault/stalls"] >= 1.0
        chaos.reset()  # raises ChaosHang inside _gather_proposals
        assert req.wait(timeout=15.0).result is not None
        assert req.replays == 0  # caught -> fallback, not a poisoned step
        assert registry.counters["serve/spec_fallbacks"] >= 1.0
        assert not exit_codes
    finally:
        chaos.reset()
        s.stop()


# --------------------------------------------------------------------- #
# crash-only recovery: speculation state resets with the lanes
# --------------------------------------------------------------------- #


def test_poisoned_step_resets_speculation_state(fresh_registry):
    """A poisoned decode step under speculation re-queues the request
    AND drops every per-slot speculator; replay re-admission rebuilds
    them from the journaled history and the result stays bit-identical
    to the unspeculated oracle — speculation state can never survive a
    reset it should not."""
    engine = build_engine()
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    chaos.configure("serve_decode:exc@2")
    try:
        req = s.submit([1, 2, 3, 1, 2, 3, 1], max_new_tokens=8)
        assert req.wait(timeout=30.0).result is not None
        chaos.reset()
        assert req.replays == 1
        assert registry.counters["serve/replays"] >= 1.0
        oracle = direct_generate(engine, [[1, 2, 3, 1, 2, 3, 1]],
                                 (4, 8, 8))
        assert req.result == engine.depad_row(oracle, 0, 8)
        # the replayed request still speculated after re-admission
        assert registry.counters.get("serve/spec_proposed", 0) > 0
        assert not s._speculators
        assert s.free_slots() == s.runtime.num_slots
        assert s.pool_stats()["pages_free"] \
            + s.pool_stats()["pages_cached"] == s.runtime.num_pages
    finally:
        chaos.reset()
        s.stop()


def test_flight_recorder_carries_spec_columns(fresh_registry):
    """Every flight-recorder record on a speculating engine carries the
    per-step spec_proposed/spec_accepted deltas — a speculation
    regression must be visible in a stall dump."""
    engine = build_engine()
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        req = s.submit([1, 2, 3, 1, 2, 3, 1], max_new_tokens=8)
        req.wait(timeout=30.0)
        recs = s.flight.snapshot()
        assert recs, "no flight records landed"
        assert all("spec_proposed" in r and "spec_accepted" in r
                   for r in recs)
        assert sum(r["spec_accepted"] for r in recs) > 0
        assert s.pressure()["spec_acceptance_rate"] > 0.0
        dbg = s.debug_state()["speculation"]
        assert dbg["mode"] == "lookup" and dbg["k"] == 4
        assert dbg["accepted"] > 0
        assert 0.0 < dbg["acceptance_rate"] <= 1.0
    finally:
        s.stop()


# --------------------------------------------------------------------- #
# the draft tier (injected draft == the target itself: 100% acceptance)
# --------------------------------------------------------------------- #


def test_draft_tier_parity_and_full_acceptance(fresh_registry):
    """speculation: draft with the SERVING engine injected as its own
    draft: proposals are the target's exact greedy continuations, so
    every budget-feasible proposal is accepted and the output is
    bit-identical to the generate() oracle."""
    engine = build_engine(speculation="draft",
                          spec_draft_checkpoint="unused-injected")
    draft = DraftProposer(engine, spec_k=4,
                          batch=engine.slot_count())
    s = SlotScheduler(engine, draft=draft)
    s.warmup()
    s.start()
    try:
        rows = [[5, 6, 7], [9, 9, 2, 6]]
        reqs = [s.submit(r, max_new_tokens=8) for r in rows]
        for r in reqs:
            r.wait(timeout=60.0)
            assert r.error is None
        oracle = direct_generate(engine, rows, (4, 8, 8))
        for i, req in enumerate(reqs):
            assert req.result == engine.depad_row(oracle, i, 8)
        reg = telemetry.current().registry
        proposed = reg.counters.get("serve/spec_proposed", 0.0)
        accepted = reg.counters.get("serve/spec_accepted", 0.0)
        assert proposed > 0
        # self-draft greedy == target greedy: everything shipped accepts
        assert accepted == proposed
        assert reg.counters.get("compile/recompiles", 0.0) == 0.0
        assert s.free_slots() == s.runtime.num_slots
    finally:
        s.stop()


# --------------------------------------------------------------------- #
# config/CLI gating
# --------------------------------------------------------------------- #


def test_draft_requires_checkpoint():
    with pytest.raises(ValueError, match="spec_draft_checkpoint"):
        build_engine(speculation="draft")


def test_speculation_requires_greedy():
    telemetry.start()
    serve = ServeConfig(buckets=[[2, 8, 8]], slots=4,
                        page_size=4,
                        speculation="lookup")
    with pytest.raises(ValueError, match="greedy"):
        InferenceEngine(
            TRLConfig.from_dict(tiny_config_dict(do_sample=True)),
            serve=serve,
        )


def test_spec_knob_validation():
    with pytest.raises(ValueError, match="spec_k"):
        build_engine(spec_k=0)
    with pytest.raises(ValueError, match="speculation"):
        build_engine(speculation="banana")
    with pytest.raises(ValueError, match="spec_index_max_keys"):
        build_engine(spec_index_max_keys=0)


def test_cli_speculation_flags():
    from trlx_tpu.serve.__main__ import (
        build_parser,
        serve_config_from_args,
    )

    args = build_parser().parse_args([
        "--checkpoint", "ckpt", "--speculation", "lookup",
        "--spec-k", "6", "--spec-draft-checkpoint", "draft-ckpt",
    ])
    cfg = serve_config_from_args(args)
    assert cfg.speculation == "lookup"
    assert cfg.spec_k == 6
    assert cfg.spec_draft_checkpoint == "draft-ckpt"


# --------------------------------------------------------------------- #
# soak: no leaks, no recompiles, the speculator map drains
# --------------------------------------------------------------------- #


@pytest.mark.slow
def test_soak_speculation_no_recompiles_no_leaks(fresh_registry):
    """300 mixed repetitive/novel requests through the speculating
    engine: zero lost requests, zero recompiles, zero slot/page leaks,
    and the per-slot speculator map (the bounded n-gram indexes) drains
    to empty — the host-memory leak-accounting assertion."""
    engine = build_engine(buckets=[[2, 8, 8], [4, 8, 8]])
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        rng = np.random.RandomState(0)
        pending = []
        for i in range(300):
            if i % 3 == 0:
                row = [1, 2, 3, 1, 2, 3, 1]  # lookup's ideal case
            else:
                row = rng.randint(1, 250, size=rng.randint(2, 8)).tolist()
            pending.append(s.submit(row, max_new_tokens=int(
                rng.randint(1, 8)
            )))
            if len(pending) >= 16:
                for r in pending:
                    r.wait(timeout=60.0)
                    assert r.error is None and r.result is not None
                pending = []
        for r in pending:
            r.wait(timeout=60.0)
            assert r.error is None and r.result is not None
        assert s.free_slots() == s.runtime.num_slots
        assert not s._speculators
        assert s.pool_stats()["pages_free"] \
            + s.pool_stats()["pages_cached"] == s.runtime.num_pages
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert registry.counters["serve/admissions"] >= 300.0
        assert registry.counters["serve/responses"] == 300.0
        assert registry.counters.get("serve/request_errors", 0.0) \
            == 0.0
    finally:
        s.stop()
