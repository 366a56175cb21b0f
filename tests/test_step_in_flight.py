"""The serve loop keeps one decode step in flight (trlx_tpu/serve/slots):
step n+1 is dispatched before step n is fetched and harvested. Every serve
family decodes, token for token, what the synchronous loop (admit, step,
harvest) decodes; a slot admitted again while the step in flight still
carries its old occupant hands that occupant's lane to nobody; window pages
go at the step they go in the synchronous loop; arrivals within a step are
admitted in the batches the synchronous loop makes; and a drain's deadline
and a hot swap complete the step in flight first (a poisoned step with one
in flight: tests/test_slots.py).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_cohere2_moe as C
import test_ouro as O
import test_sarvam_mla as M
from test_serve import tiny_config_dict
from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.serve import InferenceEngine, ServeConfig
from trlx_tpu.serve import slots as S
from trlx_tpu.serve.admission import DrainTimeout
from trlx_tpu.supervisor import monotonic


@pytest.fixture()
def registry():
    session = telemetry.start()
    yield session.registry
    telemetry.start()


def dense(**serve):
    """The tiny dense model on the jnp path, its pages in float32."""
    cfg = dict(buckets=[[2, 8, 8], [4, 8, 8]], max_queue=64,
               request_timeout=30.0, slots=3, page_size=4,
               flight_recorder_steps=256)
    engine = InferenceEngine(TRLConfig.from_dict(tiny_config_dict()),
                             serve=ServeConfig(**{**cfg, **serve}))
    sched = S.SlotScheduler(engine)
    rt = sched.runtime
    rt.pool = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                     rt.pool)
    sched.warmup()
    return sched


# (scheduler, the longest prompt and answer it takes) of each serve family
FAMILIES = {
    "dense": (dense, 8, 8),
    "window": (C.build, 16, 16),
    "latent": (M.build, 16, 16),
    "looped": (O.build, 16, 24),
}


def request(i, longest_prompt, longest_answer, first=2):
    """The i-th request of the parity tests: (prompt, max_new_tokens), its
    token ids from ``first`` up (from 2, none ends on EOS in any family)."""
    n = [5, 12, 3, 9, 14, 7, 2, 11, 4, 8][i % 10]
    answer = [3, 9, 1, 6, 12, 2, 8, 5, 10, 4][i % 10]
    return ([t % 250 + first
             for t in C.prompt_of(min(n, longest_prompt), seed=i)],
            min(answer, longest_answer))


def backlog(longest_prompt, longest_answer, first=2):
    """Seven requests for three slots, all queued at once: a queue behind
    every slot."""
    return [[request(i, longest_prompt, longest_answer, first)]
            for i in range(7)]


def zero_think(longest_prompt, longest_answer):
    """Five closed-loop callers of three requests each, for three slots:
    each sends its next request the moment its last one is answered, so a
    queue stands behind the slots and an answer's successor is there
    before the next admission."""
    return [[request(3 * c + j, longest_prompt, longest_answer)
             for j in range(3)] for c in range(5)]


def always_ahead(sched):
    """The loop with every step sent ahead while some slot has a token left
    after the step in flight: what a slot that the step in flight ends on
    EOS looks like to the host (it cannot see that end coming)."""
    sched._may_go_ahead = lambda: any(
        live.left() > 0 for live in sched._live.values())


def serve(sched, callers, order):
    """Every request of ``callers`` (one list of (prompt, max_new_tokens) a
    caller, each request sent when the caller's last one is harvested)
    through ``sched``, driven by hand in ``order``: the worker loop's own
    passes ("loop" or "always", see :func:`always_ahead`) or the
    synchronous order ("sync"). Returns the answers, the step each
    request's prefill ran ahead of, and the lanes a step handed back for a
    slot that held another request by then."""
    sched.runtime.reset_lanes()
    sched._reset_cache()
    base, stale, sent, ahead_of = sched._step_counter, [], [], {}
    harvest, prefill_batch = sched._harvest, sched._prefill_batch

    def send(c, j):
        prompt, n = callers[c][j]
        sent.append(((c, j), sched.submit(prompt, max_new_tokens=n)))

    def harvested(cand, counts, finished, lanes, span):
        stale.extend(s for s, live in lanes.items()
                     if s in sched._live and sched._live[s] is not live)
        harvest(cand, counts, finished, lanes, span)
        for (c, j), req in list(sent):
            if req.done.is_set() and j + 1 < len(callers[c]) and \
                    (c, j + 1) not in dict(sent):
                send(c, j + 1)

    def admitted(batch, *args):
        for r in batch:
            ahead_of[r.seq] = sched._step_counter + 1 - base
        return prefill_batch(batch, *args)

    sched._harvest, sched._prefill_batch = harvested, admitted
    if order == "always":
        always_ahead(sched)
    for c in range(len(callers)):
        send(c, 0)
    while not all(r.done.is_set() for _, r in sent):
        if order == "sync":
            sched._admit()
            if sched._live:
                sched._step()
        else:
            sched._iterate()
    sched._settle()
    for name in ("_harvest", "_prefill_batch", "_may_go_ahead"):
        sched.__dict__.pop(name, None)
    sent.sort(key=lambda kv: kv[0])
    for _, r in sent:
        assert r.error is None
    return ([r.result for _, r in sent],
            [ahead_of[r.seq] for _, r in sent], stale)


def served_by_every_order(sched, callers, registry):
    """The answers and prefill steps of the synchronous order, the loop's
    and the always-ahead loop's, with the steps each sent ahead."""
    out, ahead = {}, {}
    for order in ("sync", "loop", "always"):
        before = registry.counters.get("serve/steps_ahead", 0)
        out[order] = serve(sched, callers, order)
        ahead[order] = registry.counters.get("serve/steps_ahead", 0) - before
    assert sched.free_slots() == sched.runtime.num_slots
    cache = sched.cache
    assert not any(cache.allocator._ref)
    if sched.runtime.two_class:
        assert not any(cache.window_allocator._ref)
        assert cache.window_reserved == 0
    return out, ahead


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_steps_in_flight_decode_what_the_synchronous_loop_decodes(
        family, registry):
    """A queue behind every slot: the loop decodes every request token for
    token as the synchronous order does, and prefills each ahead of the
    same step (a slot whose budget the step in flight spends is harvested
    and admitted into before the next step goes). Sent ahead regardless,
    a slot freed at step n and admitted again before step n + 1's result
    came hands that step's lane, its old occupant's, to nobody."""
    build, longest_prompt, longest_answer = FAMILIES[family]
    sched = build()
    out, ahead = served_by_every_order(
        sched, backlog(longest_prompt, longest_answer), registry)
    (tokens, steps, stale), loop, always = \
        out["sync"], out["loop"], out["always"]
    assert ahead["sync"] == 0 and not stale
    for answer, [(_, n)] in zip(tokens, backlog(longest_prompt,
                                                longest_answer)):
        assert len(answer) == n  # nothing ends on EOS here
    assert loop == (tokens, steps, [])
    assert ahead["loop"] > 0
    assert always[0] == tokens and always[2]
    assert any(a > s for a, s in zip(always[1], steps))


def test_a_slot_ended_on_eos_is_admitted_into_one_step_later(registry):
    """The one admission the loop makes later than the synchronous order:
    into a slot that the step in flight ends on EOS (here request 3, at its
    first token), which the host cannot foresee. The request behind it is
    prefilled one step later; every other request at the same step."""
    sched = C.build()
    work = backlog(16, 16, first=1)
    (tokens, steps, _), (loop_tokens, loop_steps, _) = (
        serve(sched, work, order) for order in ("sync", "loop"))
    eos = sched.engine._gen_base.eos_token_id
    assert tokens[3] == [eos] and work[3][0][1] > 1
    assert loop_tokens == tokens
    assert [a - s for a, s in zip(loop_steps, steps)] == [0, 0, 0, 0, 1, 0, 0]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_zero_think_callers_behind_a_backlog_are_prefilled_at_the_synchronous_step(
        family, registry):
    """Callers that send their next request the moment the last is
    answered, more of them than slots: each request's prefill runs ahead
    of the step it runs ahead of in the synchronous order, never later."""
    build, longest_prompt, longest_answer = FAMILIES[family]
    sched = build()
    callers = zero_think(longest_prompt, longest_answer)
    out, ahead = served_by_every_order(sched, callers, registry)
    tokens, steps, _ = out["sync"]
    assert [len(t) for t in tokens] == [n for c in callers for _, n in c]
    assert out["loop"] == (tokens, steps, [])
    assert ahead["loop"] > 0
    # sent ahead regardless, an answer's successor rides a step later
    assert out["always"][0] == tokens
    assert any(a > s for a, s in zip(out["always"][1], steps))


def test_window_pages_go_at_the_step_they_go_in_the_synchronous_loop(
        registry):
    sched = C.build()
    work = [(C.prompt_of(13), 16), (C.prompt_of(9, seed=1), 16),
            (C.prompt_of(16, seed=2), 14)]

    def freed_by_step(order):
        sched.runtime.reset_lanes()
        sched._reset_cache()
        if order == "always":
            always_ahead(sched)
        base, freed = sched._step_counter, {}
        reqs = [sched.submit(p, max_new_tokens=n) for p, n in work]
        while not all(r.done.is_set() for r in reqs):
            if order == "sync":
                sched._admit()
                sched._step()
            else:
                sched._iterate()
            freed[sched._step_counter - base] = \
                sched.cache.window_pages_freed
        sched._settle()
        sched.__dict__.pop("_may_go_ahead", None)
        assert sched.cache.window_reserved == 0
        return [r.result for r in reqs], freed

    tokens, freed = freed_by_step("sync")
    assert freed[len(freed)] > 0
    assert freed_by_step("loop") == (tokens, freed)
    # every step goes ahead but the first, step 15 (step 14 spends a
    # budget) and step 16 (a slot is free, and nothing arrives)
    assert registry.counters["serve/steps_ahead"] == len(freed) - 3
    # sent ahead over the slots the step in flight ends, too: those get
    # no window page for a step that writes nothing for them
    assert freed_by_step("always") == (tokens, freed)


def slow_device(sched, monkeypatch, seconds):
    """A device whose decode step takes ``seconds``: programs run in the
    order they were dispatched, a fetch waits for its step's end, and a
    step in flight is ready then."""
    rt, due, last = sched.runtime, {}, [0.0]
    step, fetch = rt.step, rt.fetch_step

    def timed_step(*args):
        out = step(*args)
        last[0] = due[id(out[0])] = max(monotonic(), last[0]) + seconds
        return out

    def timed_fetch(out):
        time.sleep(max(due[id(out[0])] - monotonic(), 0.0))
        return fetch(out)

    monkeypatch.setattr(rt, "step", timed_step)
    monkeypatch.setattr(rt, "fetch_step", timed_fetch)
    monkeypatch.setattr(S._Step, "ready", lambda self: (
        self.host is not None or monotonic() >= due[id(self.out[0])]))


CLIENTS = [  # three closed-loop callers, three requests each
    [([1, 2, 3, 4, 5], 3), ([6, 7], 2), ([8, 9, 10], 4)],
    [([11, 12, 13], 2), ([14, 15, 16, 17, 18, 19], 3), ([20], 2)],
    [([21, 22], 4), ([23, 24, 25, 26], 2), ([27, 28, 29], 3)],
]


def closed_loop(sched, think_s=0.02):
    """Each client sends its next request ``think_s`` after its last one was
    answered (the first ones are queued before the worker starts). Returns
    the answers and each prefill call: (steps dispatched before it, its
    bucket, its rows)."""
    rt, calls = sched.runtime, []
    prefill = rt.prefill

    def logged(bucket, tokens, mask, slot_ids, *args, **kwargs):
        rows = int((np.asarray(slot_ids) < rt.num_slots).sum())
        calls.append((sched._step_counter, tuple(bucket), rows))
        return prefill(bucket, tokens, mask, slot_ids, *args, **kwargs)

    rt.prefill = logged
    answers = [[None] * len(c) for c in CLIENTS]
    first = [sched.submit(c[0][0], max_new_tokens=c[0][1]) for c in CLIENTS]

    def client(i):
        req = first[i]
        for j, (prompt, n) in enumerate(CLIENTS[i]):
            if j:
                time.sleep(think_s)
                req = sched.submit(prompt, max_new_tokens=n)
            answers[i][j] = req.wait(timeout=60.0).result

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(CLIENTS))]
    sched.start()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
    finally:
        sched.stop()
    return answers, calls


def test_arrivals_within_a_step_are_admitted_as_the_synchronous_loop_admits_them(
        registry, monkeypatch):
    """Closed-loop clients that resubmit within a step: the step in flight
    waits for them, and every prefill carries the bucket and rows it
    carries in the synchronous order, ahead of the same step. When nothing
    more arrives, the wait ends on the step's result and is counted."""
    sched = dense()
    slow_device(sched, monkeypatch, seconds=0.15)
    monkeypatch.setattr(sched, "_may_go_ahead", lambda: False)
    synchronous = closed_loop(sched)
    assert not registry.counters.get("serve/steps_ahead")

    sched = dense()
    slow_device(sched, monkeypatch, seconds=0.15)
    answers, calls = closed_loop(sched)
    assert (answers, calls) == synchronous
    assert any(rows > 1 for _, _, rows in calls)
    assert registry.counters["serve/steps_ahead"] > 0
    # the last requests' slots stay free: those waits end on the step
    assert registry.counters["serve/admit_waits_expired"] >= 1


def test_with_nothing_to_admit_the_loop_keeps_the_synchronous_order(
        registry):
    sched = dense()
    req = sched.submit([3, 1, 4, 1, 5], max_new_tokens=6)
    while not req.done.is_set():
        sched._iterate()
    assert len(req.result) == 6
    # two slots stay free and nothing arrives: every step completes before
    # the next one goes, and each wait that ended so is counted
    assert not registry.counters.get("serve/steps_ahead")
    assert registry.counters["serve/admit_waits_expired"] == 5
    records = sched.flight.snapshot()
    assert [r["ahead"] for r in records] == [0] * 6


def test_a_drain_deadline_answers_what_the_step_in_flight_finished(
        registry):
    sched = dense(slots=2)
    short = sched.submit([1, 2, 3], max_new_tokens=2)
    long = sched.submit([4, 5, 6, 7], max_new_tokens=8)
    sched._iterate()  # both admitted, step 1 dispatched
    sched._iterate()  # step 2 goes ahead, step 1 is harvested
    assert sched._inflight is not None and sched._inflight.ahead
    assert not short.done.is_set()
    sched._drain_expire()
    assert short.error is None and len(short.result) == 2
    with pytest.raises(DrainTimeout):
        long.wait(timeout=1.0)
    assert sched._inflight is None and not sched._live
    assert sched.free_slots() == 2


def test_a_hot_swap_completes_the_step_in_flight_and_goes_synchronous(
        registry):
    sched = dense(slots=2)
    reqs = [sched.submit([1, 2, 3], max_new_tokens=6),
            sched.submit([4, 5], max_new_tokens=3)]
    sched._iterate()
    sched._iterate()
    assert sched._inflight is not None and sched._inflight.ahead
    box = {"params": sched.engine._init_params(), "label": "swap",
           "done": threading.Event(), "result": None}
    with sched._cond:
        sched._pending_swap = box
    seen = len(sched.flight.snapshot())
    while not box["done"].is_set():
        sched._iterate()
    assert box["result"]["reloaded"], box["result"]
    assert [len(r.result) for r in reqs] == [6, 3]
    records = sched.flight.snapshot()[seen:]
    # the step dispatched ahead before the swap was asked completes first;
    # every step after it completes before the next one goes
    assert records[0]["ahead"] == 1
    assert records[1:] and not any(r["ahead"] for r in records[1:])
    assert sched._inflight is None and not sched._live


def test_many_callers_against_steps_in_flight_lose_nothing(registry):
    """More callers than cores and slots, the interpreter switching threads
    often: every request is answered with its own length, and the loop
    leaks no slot and no page."""
    import sys

    sched = dense()
    errors = []

    def caller(i):
        try:
            for j in range(4):
                n = 1 + (i + j) % 6
                req = sched.submit([1 + (i * 7 + j) % 200, 2, 3], max_new_tokens=n)
                out = req.wait(timeout=60.0).result
                eos = sched.engine._gen_base.eos_token_id
                assert len(out) == n or (len(out) < n and out[-1] == eos)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=caller, args=(i,)) for i in range(24)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    sched.start()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        # a request's done event is set in the harvest before its slot is
        # freed: give the last harvest its moment, then look (before
        # stop() frees every slot)
        deadline = monotonic() + 10.0
        while (sched.free_slots() != sched.runtime.num_slots
               and monotonic() < deadline):
            time.sleep(0.01)
        free = sched.free_slots()
    finally:
        sched.stop()
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert registry.counters["serve/responses"] == 24 * 4
    assert registry.counters["serve/steps_ahead"] > 0
    assert free == sched.runtime.num_slots
    assert not any(sched.cache.allocator._ref)
