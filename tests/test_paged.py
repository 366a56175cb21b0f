"""Paged KV-cache + radix prefix-cache tests (trlx_tpu/serve/paged, the
paged halves of models/generation + transformer.block_apply, and the
SlotScheduler's paged admission): allocator semantics (exhaustion ->
queue-not-crash, refcounts never negative, LRU evicts only refcount-0
leaves), device-level paged prefill/decode parity against one-shot
``generate()``, the greedy-parity sweep across page sizes and staggered
shared-prefix admission, prefix hits skipping prefill tokens, the
``serve_prefix_match`` chaos drill, pool health on /healthz + /metrics,
and the buffer-reusing ``reset_lanes``.
"""

import json
import time
import urllib.request

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.generation import (
    _segments_of,
    decode_step,
    init_page_pool,
    init_slot_state,
    prefill_into_slots,
    verify_step,
)
from trlx_tpu.serve import InferenceEngine, InferenceServer, ServeConfig
from trlx_tpu.serve.paged import PageAllocator, RadixCache
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.supervisor import RunSupervisor, chaos
from test_serve import tiny_config_dict
from test_slots import direct_generate

SERVE_PAGED = ServeConfig(
    buckets=[[2, 8, 8], [4, 8, 8], [4, 16, 8]],
    max_queue=64,
    request_timeout=30.0,
    slots=4,
    page_size=4,
)


def build_engine(**overrides):
    telemetry.start()
    serve = ServeConfig(**{
        "buckets": [[2, 8, 8]], "max_queue": 64, "request_timeout": 30.0,
        "slots": 4,
        "page_size": 4, **overrides,
    })
    return InferenceEngine(TRLConfig.from_dict(tiny_config_dict()),
                           serve=serve)


@pytest.fixture(scope="module")
def engine():
    telemetry.start()
    cfg = TRLConfig.from_dict(tiny_config_dict())
    return InferenceEngine(cfg, serve=SERVE_PAGED)


@pytest.fixture()
def fresh_registry():
    session = telemetry.start()
    yield session.registry
    telemetry.start()


# --------------------------------------------------------------------- #
# allocator: free list + refcounts
# --------------------------------------------------------------------- #


def test_allocator_alloc_free_exhaustion():
    a = PageAllocator(4)
    pages = a.alloc(3)
    assert len(set(pages)) == 3 and a.free_count() == 1
    # exhaustion returns None (queue-not-crash contract) and consumes
    # NOTHING partially
    assert a.alloc(2) is None
    assert a.free_count() == 1
    (extra,) = a.alloc(1)
    for p in pages + [extra]:
        assert a.release(p) == 0
        a.free_page(p)
    assert a.free_count() == 4


def test_allocator_refcount_never_negative():
    a = PageAllocator(2)
    (p,) = a.alloc(1)
    a.retain(p)
    assert a.release(p) == 1
    assert a.release(p) == 0
    with pytest.raises(RuntimeError, match="double free"):
        a.release(p)
    with pytest.raises(RuntimeError, match="refcount"):
        a.free_page(a.alloc(1)[0])  # still referenced: not freeable


# --------------------------------------------------------------------- #
# radix tree: match cap, commit dedup, LRU eviction
# --------------------------------------------------------------------- #


def test_radix_match_caps_one_token_short():
    c = RadixCache(8, 2)
    pages = c.alloc(2)
    assert c.commit([1, 2, 3, 4], pages) == pages
    # the full prompt matches ONE block only: >= 1 suffix token must
    # remain to produce the first-step logits
    m = c.match([1, 2, 3, 4])
    assert m == pages[:1]
    c.release_all(m)
    m = c.match([1, 2, 3, 4, 9])  # one token longer: both blocks hit
    assert m == pages
    c.release_all(m)
    c.release_all(pages)
    assert c.free_pages() == 8 - 2  # committed pages stay cached
    assert c.cached_pages() == 2


def test_radix_commit_keeps_existing_nodes():
    c = RadixCache(8, 2)
    first = c.alloc(2)
    c.commit([1, 2, 3, 4], first)
    dup = c.alloc(2)
    # racing duplicate: blocks already present -> nothing inserted, the
    # duplicate pages free at release instead of shadowing the cache
    assert c.commit([1, 2, 3, 4], dup) == []
    c.release_all(dup)
    c.release_all(first)
    assert c.free_pages() == 8 - 2


def test_radix_lru_evicts_only_refcount_zero_leaves():
    c = RadixCache(4, 2)
    held = c.alloc(2)
    c.commit([1, 2, 3, 4], held)  # stays referenced throughout
    idle = c.alloc(2)
    c.commit([5, 6, 7, 8], idle)
    c.release_all(idle)  # refcount 0, cached -> evictable
    assert c.free_pages() == 0
    # pool dry: alloc must evict from the idle chain, leaf-first
    got = c.alloc(1)
    assert got is not None and c.evicted_pages == 1
    assert c.alloc(1) is not None and c.evicted_pages == 2
    # the referenced chain was never touched
    assert all(c.allocator.refcount(p) == 1 for p in held)
    # nothing evictable remains: the held pages block further allocation
    c.release_all(got)
    assert c.alloc(3) is None


def test_radix_rollback_detaches_pending_nodes():
    c = RadixCache(8, 2)
    pages = c.alloc(2)
    inserted = c.commit([1, 2, 3, 4], pages)
    c.rollback(inserted)
    c.release_all(pages)  # no longer cached: pages return to the free list
    assert c.free_pages() == 8
    assert c.match([1, 2, 3, 4, 5]) == []


# --------------------------------------------------------------------- #
# device primitives: paged parity with one-shot generate()
# --------------------------------------------------------------------- #


def test_paged_primitives_parity_with_staggered_admission(engine):
    """Greedy paged decode must emit tokens bit-identical to one-shot
    generate() per row — page tables hand-built, slots admitted out of
    order, one row admitted MID-DECODE, plus a drop-sentinel filler."""
    spec = engine.spec
    cfg = engine._gen_base._replace(gen_size=8)
    _, seg_sizes = _segments_of(engine.blocks)
    S, ps, max_pages, Np = 3, 4, 4, 12
    pool = init_page_pool(spec, seg_sizes, Np, ps)
    state = init_slot_state(S, max_pages * ps, spec.vocab_size,
                            max_pages=max_pages)

    pf = jax.jit(
        lambda pool, st, t, m, sid, mn, pt, start: prefill_into_slots(
            spec, engine.blocks, engine.embed, engine.ln_f, pool, st,
            t, m, sid, mn, compute_dtype=jnp.float32,
            page_tables=pt, page_size=ps, start=start,
        )
    )
    sf = jax.jit(
        lambda pool, st, seed: decode_step(
            spec, engine.blocks, engine.embed, engine.ln_f, pool, st,
            seed, cfg, compute_dtype=jnp.float32,
        )
    )

    def right_pad(rows, P):
        t = np.zeros((len(rows), P), np.int32)
        m = np.zeros((len(rows), P), np.int32)
        for i, row in enumerate(rows):
            t[i, :len(row)] = row
            m[i, :len(row)] = 1
        return t, m

    rows = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9, 7, 9, 3]]
    tables = {0: [0, 1, 2, 3], 1: [4, 5, 6, 7], 2: [8, 9, 10, 11]}
    t2, m2 = right_pad(rows[:2] + rows[:1], 8)
    pool, state = pf(
        pool, state, t2, m2,
        np.array([2, 0, S], np.int32), np.array([8, 8, 1], np.int32),
        np.array([tables[2], tables[0], [Np] * 4], np.int32),
        np.zeros((3,), np.int32),
    )
    got = {0: [], 1: [], 2: []}
    for step in range(3):
        pool, state, tok, em, _ = sf(pool, state, np.int32(step))
        tok, em = np.asarray(tok), np.asarray(em)
        for s in (2, 0):
            if em[s]:
                got[s].append(int(tok[s]))
    # admit row 3 into slot 1 while the others are mid-decode
    t3, m3 = right_pad(rows[2:] + rows[2:], 8)
    pool, state = pf(
        pool, state, t3, m3, np.array([1, S], np.int32),
        np.array([8, 1], np.int32),
        np.array([tables[1], [Np] * 4], np.int32),
        np.zeros((2,), np.int32),
    )
    for step in range(3, 14):
        pool, state, tok, em, _ = sf(pool, state, np.int32(step))
        tok, em = np.asarray(tok), np.asarray(em)
        for s in (2, 0, 1):
            if em[s]:
                got[s].append(int(tok[s]))

    oracle = direct_generate(engine, rows, (4, 8, 8))
    for i, slot in enumerate((2, 0, 1)):
        assert got[slot] == engine.depad_row(oracle, i, 8), (
            f"slot {slot} (row {i}) diverged from one-shot generate()"
        )


# --------------------------------------------------------------------- #
# the pool is per-layer leaves, written in place: structure of the programs
# --------------------------------------------------------------------- #


def _is_var(v):  # an equation's operand is a variable or a literal
    return isinstance(v, jax.extend.core.Var)


def _flat_eqns(jaxpr, env):
    """(primitive, inputs, outputs) of every equation, nested ``jit``
    calls (jnp.where, clip, ...) inlined so that a variable keeps ONE
    identity from the program's arguments to its results. ``env`` maps a
    variable to the outer variable it stands for."""
    resolve = lambda v: env.get(v, v) if _is_var(v) else v
    for eqn in jaxpr.eqns:
        ins = [resolve(v) for v in eqn.invars]
        inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
        inner = getattr(inner, "jaxpr", inner)
        if inner is not None and len(inner.invars) == len(ins):
            sub = dict(zip(inner.invars, ins))
            yield from _flat_eqns(inner, sub)
            for outer, v in zip(eqn.outvars, inner.outvars):
                env[outer] = sub.get(v, v) if _is_var(v) else v
        else:
            yield eqn.primitive.name, ins, list(eqn.outvars)


@pytest.mark.parametrize("kv_dtype,program", [
    ("bfloat16", "decode_step"),
    ("bfloat16", "verify_step"),
    ("bfloat16", "prefill_suffix"),
    ("bfloat16", "prefill"),
    ("int8", "decode_step"),
    ("int8", "verify_step"),
    ("int8", "prefill_suffix"),
    ("int8", "prefill"),
])
def test_pool_leaves_are_written_in_place(engine, kv_dtype, program):
    """Every pool leaf enters a serve program, is consumed by exactly ONE
    scatter (block_apply's write of the fresh rows, or the local
    prefill's block-scatter), and that scatter's output — read only by
    gathers — is the leaf the program returns.
    No slice / dynamic_update_slice / concatenate touches anything as
    large as a leaf. That is what lets XLA alias each donated leaf to
    its output; a stacked [L, ...] pool (sliced per layer, written back
    with ``.at[i].set``) fails every clause, and cost 46% of a gpt-j-6B
    decode step on the chip."""
    spec = engine.spec
    cfg = engine._gen_base
    _, seg_sizes = _segments_of(engine.blocks)
    S, ps, max_pages, B, P, K = 3, 4, 4, 2, 8, 2
    weights = jax.tree_util.tree_leaves(
        (engine.blocks, engine.embed, engine.ln_f)
    )
    # a pool whose SMALLEST leaf (an int8 scale plane) outgrows every
    # other array of the program, so "as large as a leaf" names the pool
    rows = max(x.size for x in weights) // (ps * spec.kv_heads) + 1
    pool = jax.eval_shape(lambda: init_page_pool(
        spec, seg_sizes, rows, ps,
        cache_dtype=jnp.int8 if kv_dtype == "int8" else jnp.bfloat16,
    ))
    state = jax.eval_shape(lambda: init_slot_state(
        S, max_pages * ps, spec.vocab_size, max_pages=max_pages,
    ))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    model = (spec, engine.blocks, engine.embed, engine.ln_f)
    if program == "decode_step":
        fn = lambda pool, st: decode_step(
            *model, pool, st, jnp.int32(0), cfg,
            compute_dtype=jnp.float32,
        )[0]
        args = ()
    elif program == "verify_step":
        fn = lambda pool, st, prop, n: verify_step(
            *model, pool, st, jnp.int32(0), prop, n, cfg,
            compute_dtype=jnp.float32,
        )[0]
        args = (i32(S, K), i32(S))
    else:
        fn = lambda pool, st, t, m, sid, mn, pt, start: prefill_into_slots(
            *model, pool, st, t, m, sid, mn, pt, ps,
            compute_dtype=jnp.float32, start=start,
            prefix_context=program == "prefill_suffix",
        )[0]
        args = (i32(B, P), i32(B, P), i32(B), i32(B), i32(B, max_pages),
                i32(B))
    closed = jax.make_jaxpr(fn)(pool, state, *args)
    leaves_in = closed.jaxpr.invars[:len(jax.tree_util.tree_leaves(pool))]
    eqns = list(_flat_eqns(closed.jaxpr, {}))
    leaf_size = min(v.aval.size for v in leaves_in)

    for leaf, returned in zip(leaves_in, closed.jaxpr.outvars):
        readers = [e for e in eqns if any(v is leaf for v in e[1])]
        assert [e[0] for e in readers] == ["scatter"], (
            f"{program}: a pool leaf is read by {[e[0] for e in readers]}"
            f", not by one scatter alone"
        )
        _, ins, (written,) = readers[0]
        assert ins[0] is leaf  # the scatter's operand, not its updates
        assert returned is written, (
            f"{program}: a returned pool leaf is not its scatter's output"
        )
        after = [e[0] for e in eqns if any(v is written for v in e[1])]
        assert set(after) <= {"gather"}, after
        if program in ("decode_step", "verify_step", "prefill_suffix"):
            assert after, "attention never reads the written leaf"
    big = lambda v: _is_var(v) and v.aval.size >= leaf_size
    moved = [
        name for name, ins, outs in eqns
        if name in ("slice", "dynamic_slice", "dynamic_update_slice",
                    "concatenate")
        and any(big(v) for v in ins + outs)
    ]
    assert not moved, f"{program}: {moved} move something of a leaf's size"


def test_block_apply_refuses_per_row_writes_without_a_page_table(engine):
    """Per-row cache writes exist only through a page table: a contiguous
    cache (generate()'s own) is written at the one ``cache_offset``. The
    combination that used to be the contiguous slot pool is refused, not
    taken for a cache_offset of None."""
    from trlx_tpu.models.transformer import (
        ArchFlags,
        block_apply,
        init_kv_cache,
    )

    spec = engine.spec
    B, T = 2, 8
    layer = jax.tree_util.tree_map(
        lambda x: x[0], _segments_of(engine.blocks)[0][-1]
    )
    cache = jax.tree_util.tree_map(
        lambda x: x[0], init_kv_cache(spec, 1, B, T, jnp.float32)
    )
    args = dict(
        h=jnp.zeros((B, 1, spec.d_model), jnp.float32),
        mask_bias=jnp.zeros((B, 1, 1, T), jnp.float32),
        positions=jnp.zeros((B, 1), jnp.int32), kv_cache=cache,
    )
    with pytest.raises(ValueError, match="cache_row_offsets.*page_table"):
        block_apply(spec, ArchFlags.for_spec(spec), layer,
                    cache_row_offsets=jnp.zeros((B,), jnp.int32), **args)
    out, new_cache = block_apply(spec, ArchFlags.for_spec(spec), layer,
                                 cache_offset=jnp.int32(3), **args)
    assert out.shape == (B, 1, spec.d_model)
    assert new_cache[0].shape == cache[0].shape


# --------------------------------------------------------------------- #
# scheduler: greedy-parity sweep + prefix caching e2e
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("page_size", [3, 8, 24])
def test_greedy_parity_sweep_page_sizes(page_size, fresh_registry):
    """Greedy outputs pinned bit-identical to one-shot generate() across
    page sizes (unaligned 3, mid 8, bucket_max 24 — a single page per
    slot) with staggered shared-prefix admission and zero steady-state
    recompiles."""
    engine = build_engine(page_size=page_size,
                          buckets=[[2, 8, 8], [4, 8, 8]])
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        rows = [
            [3, 1, 4, 1, 5],
            [3, 1, 4, 1, 5, 9, 2, 6],  # shares a 5-token prefix with row 0
            [9, 2, 6],
            [3, 1, 4, 1, 5, 9, 2, 6],  # full repeat of row 1
        ]
        first = [s.submit(r, max_new_tokens=8) for r in rows[:2]]
        for r in first:
            r.wait(timeout=60.0)
        second = [s.submit(r, max_new_tokens=8) for r in rows[2:]]
        for r in second:
            r.wait(timeout=60.0)
        oracle = direct_generate(engine, rows, (4, 8, 8))
        for i, req in enumerate(first + second):
            assert req.result == engine.depad_row(oracle, i, 8), (
                f"row {i} diverged at page_size={page_size}"
            )
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        if page_size < 8:  # whole blocks shared -> prefix hits must fire
            assert registry.counters["serve/prefix_tokens_saved"] > 0
        assert s.free_slots() == s.runtime.num_slots
    finally:
        s.stop()


def test_prefix_hit_skips_prefill_tokens(engine, fresh_registry):
    """An admitted prompt matching a committed prefix prefills only the
    suffix: serve/prefix_tokens_saved counts the skipped tokens and the
    result stays bit-identical to the full-prefill oracle."""
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        prompt = [7, 7, 7, 7, 5, 5, 5, 5, 1, 2, 3, 4]  # (16, 8) class
        a = s.submit(prompt, max_new_tokens=4)
        a.wait(timeout=60.0)
        assert fresh_registry.counters.get(
            "serve/prefix_tokens_saved", 0.0
        ) == 0.0
        b = s.submit(prompt, max_new_tokens=4)  # 2 of 3 blocks hit
        b.wait(timeout=60.0)
        assert fresh_registry.counters["serve/prefix_tokens_saved"] == 8.0
        oracle = direct_generate(engine, [prompt, prompt], (4, 16, 8))
        assert a.result == engine.depad_row(oracle, 0, 4)
        assert b.result == engine.depad_row(oracle, 1, 4)
        assert fresh_registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert fresh_registry.gauges["serve/prefix_hit_rate"] > 0.0
        stats = s.pool_stats()
        assert stats["prefix_tokens_saved"] == 8
        assert stats["pages_cached"] > 0
    finally:
        s.stop()


def test_page_exhaustion_queues_not_crash(fresh_registry):
    """A pool holding ~1.5 requests' pages serves a 6-request burst by
    QUEUEING behind page availability (preempted steps, LRU evictions)
    — every request completes, nothing errors, all pages come back."""
    engine = build_engine(pages=6, slots=4)
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        reqs = [
            s.submit([10 + i, 20 + i, 30 + i, 40 + i, 50 + i],
                     max_new_tokens=8)
            for i in range(6)
        ]
        for r in reqs:
            r.wait(timeout=120.0)
        assert all(r.error is None for r in reqs)
        assert all(len(r.result) <= 8 for r in reqs)
        assert registry.counters["serve/admissions"] == 6.0
        assert registry.counters.get("serve/request_errors", 0.0) == 0.0
        # distinct prompts at 6 pages: later admissions must evict the
        # earlier requests' cached prefixes
        assert registry.counters["serve/evicted_pages"] >= 1.0
        stats = s.pool_stats()
        assert stats["pages_free"] + stats["pages_cached"] == 6
        assert s.free_slots() == s.runtime.num_slots
    finally:
        s.stop()


def test_impossible_request_rejected_up_front():
    engine = build_engine(pages=2)
    s = SlotScheduler(engine)
    with pytest.raises(ValueError, match="KV pages"):
        s.submit([1, 2, 3, 4, 5], max_new_tokens=8)  # needs 4 > 2 pages
    s.stop()


# --------------------------------------------------------------------- #
# containment: chaos drill + buffer-reusing reset
# --------------------------------------------------------------------- #


def test_chaos_prefix_match_hang_is_attributable_stall(engine,
                                                      fresh_registry):
    """serve_prefix_match:hang wedges the radix walk inside admission;
    the watchdog must attribute the stall to 'serve_admit', and the loop
    must keep serving once released."""
    exit_codes = []
    sup = RunSupervisor(
        stall_timeout=0.3, stall_first_timeout=0.3,
        stall_grace=10_000.0, exit_fn=exit_codes.append,
    )
    chaos.configure("serve_prefix_match:hang=60@1")
    s = SlotScheduler(engine, run_supervisor=sup)
    s.warmup()
    s.start()
    try:
        req = s.submit([1, 2, 3], max_new_tokens=2)
        deadline = time.monotonic() + 15.0
        while sup.stalls == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sup.stalls >= 1, "watchdog never flagged the hung match"
        assert sup.stalled_phase == "serve_admit"
        assert fresh_registry.counters["fault/stalls"] >= 1.0
        chaos.reset()  # releases the hang as ChaosHang in the worker
        # an admission fault now RE-QUEUES the batch for replay; the
        # request completes once the seam is clear
        assert req.wait(timeout=15.0).result is not None
        assert req.replays == 1
        ok = s.submit([4, 5], max_new_tokens=2)
        assert ok.wait(timeout=30.0).result is not None
        assert not exit_codes
    finally:
        chaos.reset()
        s.stop()


def test_reset_lanes_reuses_pool_buffers(engine):
    """The poisoned-step reset must keep the (undamaged) pool arrays —
    no transient 2x pool HBM — while handing back fresh lanes."""
    s = SlotScheduler(engine)
    before = [id(x) for x in jax.tree_util.tree_leaves(s.runtime.pool)]
    s.runtime.reset_lanes()
    after = [id(x) for x in jax.tree_util.tree_leaves(s.runtime.pool)]
    assert before == after, "pool buffers were reallocated on reset"
    assert not bool(np.asarray(s.runtime.state.active).any())
    assert int(np.asarray(s.runtime.state.pages).min()) >= s.runtime.num_pages
    s.stop()


def test_poisoned_step_resets_prefix_cache_and_replays(engine,
                                                       fresh_registry):
    """serve_decode:exc on the paged pool resets lanes AND the radix
    cache (its content can't be trusted), then RE-QUEUES the in-flight
    request — the replay re-prefills from the cold cache (zero prefix
    hits on re-admission) and completes bit-identical; a repeat of the
    previously-cached prompt then re-caches and serves correctly."""
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        warmup_req = s.submit([1, 2, 3, 4, 5, 6], max_new_tokens=2)
        warmup_req.wait(timeout=30.0)
        assert s.pool_stats()["pages_cached"] > 0
        chaos.configure("serve_decode:exc@1")
        bad = s.submit([1, 2, 3, 4, 5, 6], max_new_tokens=4)
        assert bad.wait(timeout=30.0).result is not None
        chaos.reset()
        oracle = direct_generate(engine, [[1, 2, 3, 4, 5, 6]], (4, 8, 8))
        assert bad.result == engine.depad_row(oracle, 0, 4)
        assert bad.replays == 1
        # the poisoned reset wiped the cache, so bad's REPLAY admission
        # found no prefix to reuse — despite the warmed-cache hit its
        # first admission got
        assert bad.trace.prefix_blocks_hit == 0
        assert fresh_registry.counters["serve/replays"] >= 1.0
        ok = s.submit([1, 2, 3, 4, 5, 6], max_new_tokens=2)
        ok.wait(timeout=30.0)
        assert ok.result == engine.depad_row(oracle, 0, 2)
        assert s.free_slots() == s.runtime.num_slots
        # zero page leaks across fault + replay + repeat
        assert s.pool_stats()["pages_free"] \
            + s.pool_stats()["pages_cached"] == s.runtime.num_pages
    finally:
        chaos.reset()
        s.stop()


# --------------------------------------------------------------------- #
# surfaces: /healthz + /metrics
# --------------------------------------------------------------------- #


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=60
    ) as resp:
        return resp.status, json.loads(resp.read())


def _post(port, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, json.loads(resp.read())


def test_healthz_and_metrics_report_pool_health(engine, fresh_registry):
    server = InferenceServer(engine, port=0).start(warmup=True)
    try:
        status, health = _get(server.port, "/healthz")
        assert status == 200 and health["status"] == "ok"
        kv = health["kv"]
        assert kv["page_size"] == 4
        assert kv["pages_total"] == kv["pages_free"] == 24
        assert kv["prefix_hit_rate"] == 0.0

        for _ in range(2):  # identical prompts -> the second hits
            _post(server.port, {"tokens": [1, 2, 3, 4, 5, 6, 7],
                                "max_new_tokens": 2})
        _, health = _get(server.port, "/healthz")
        assert health["kv"]["prefix_tokens_saved"] == 4
        assert health["kv"]["pages_cached"] > 0

        _, metrics = _get(server.port, "/metrics")
        assert metrics["counters"]["serve/prefix_tokens_saved"] == 4
        assert "serve/evicted_pages" in metrics["counters"]  # predeclared
        assert "serve/pages_free" in metrics["gauges"]
        assert "serve/prefix_hit_rate" in metrics["gauges"]
        assert "serve/pages_per_request_p95" in metrics["gauges"]
        assert "serve/pages_per_request" in metrics["timings"]
        assert metrics["counters"]["compile/recompiles"] == 0
    finally:
        server.stop()


@pytest.mark.slow
def test_soak_paged_no_recompiles_no_page_leaks(fresh_registry):
    """Hundreds of mixed-length requests (a third sharing prefixes)
    through the paged pool: zero steady-state recompiles, every page
    accounted for at the end (free + cached == total, no refcount
    leaks), every completion within its own max_new_tokens."""
    engine = build_engine(buckets=[[2, 8, 8], [4, 8, 8], [4, 16, 8]],
                          max_queue=1024)
    registry = telemetry.current().registry
    rng = np.random.default_rng(0)
    shared = [int(t) for t in rng.integers(1, 250, size=8)]
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        reqs = []
        for i in range(300):
            if i % 3 == 0:  # shared-prefix cohort: radix hits + evictions
                tokens = shared[:rng.integers(4, 9)] + [
                    int(t) for t in rng.integers(0, 250,
                                                 size=rng.integers(1, 8))
                ]
            else:
                tokens = [int(t) for t in rng.integers(
                    0, 250, size=rng.integers(1, 16))]
            mn = int(rng.integers(1, 9))
            reqs.append(s.submit(tokens, max_new_tokens=mn))
        for r in reqs:
            r.wait(timeout=300.0)
        assert all(len(r.result) <= r.max_new_tokens for r in reqs)
        assert s.queue_depth() == 0
        assert s.free_slots() == s.runtime.num_slots, "slot leak"
        assert not s._speculators, "leaked per-slot speculator state"
        stats = s.pool_stats()
        assert stats["pages_free"] + stats["pages_cached"] == \
            stats["pages_total"], "page leak"
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert registry.counters["serve/admissions"] == 300.0
        assert registry.counters["serve/prefix_tokens_saved"] > 0.0
        assert registry.counters.get("serve/request_errors", 0.0) == 0.0
    finally:
        s.stop()


# --------------------------------------------------------------------- #
# two classes of page (a model with window layers)
# --------------------------------------------------------------------- #


def two_class_cache(pages=32, window_pages=12, page_size=4, window=8):
    return RadixCache(pages, page_size, window_pages=window_pages,
                      window=window)


def commit_with_windows(cache, tokens):
    """What admission + prefill do for one prompt: full-class pages for
    every block, a window-class page attached to every whole block."""
    n = -(-len(tokens) // cache.page_size)
    pages = cache.alloc(n)
    cache.commit(tokens, pages)
    wpages = cache.alloc_window(len(tokens) // cache.page_size)
    for page, wpage in zip(pages, wpages):
        assert cache.attach_window(page, wpage)
    return pages, wpages


def test_two_class_match_retains_the_window_pages_before_its_end():
    cache = two_class_cache()
    toks = list(range(100, 124))  # 6 blocks of 4; window = 2 blocks
    pages, wpages = commit_with_windows(cache, toks)
    cache.release_all(pages)
    cache.release_window(wpages)
    got, wmap = cache.match_classes(toks + [1, 2])
    assert got == pages and wmap == {4: wpages[4], 5: wpages[5]}
    assert cache.window_allocator.refcount(wpages[5]) == 1
    assert cache.window_allocator.refcount(wpages[3]) == 0


def test_two_class_match_is_cut_back_where_window_pages_are_gone():
    cache = two_class_cache()
    toks = list(range(100, 124))
    pages, wpages = commit_with_windows(cache, toks)
    cache.release_all(pages)
    cache.release_window(wpages)
    node_of = cache._node_of_wpage
    cache._drop_wpage(node_of[wpages[4]])  # block 4's window page is gone
    got, wmap = cache.match_classes(toks + [1, 2])
    # blocks 0..3 are the longest prefix whose last 2 window pages are kept
    assert got == pages[:4] and sorted(wmap) == [2, 3]
    cache.release_all(got)
    cache.release_window(list(wmap.values()))
    for wpage in wpages[:4]:
        cache._drop_wpage(node_of[wpage])
    assert cache.match_classes(toks + [1, 2]) == ([], {})  # or to nothing


def test_window_pages_deep_inside_a_prefix_are_evicted_first():
    cache = two_class_cache(window_pages=8)
    toks = list(range(100, 124))  # 6 blocks, window 2 blocks
    pages, wpages = commit_with_windows(cache, toks)
    cache.release_all(pages)
    cache.release_window(wpages)
    assert cache.window_free_pages() == 2
    assert cache.evict_window(4) == 4
    # blocks 0..3 have 2 or more committed blocks below them: no match
    # that reads their window pages can end there
    assert sorted(cache._node_of_wpage) == sorted(wpages[4:])
    got, wmap = cache.match_classes(toks + [1])
    assert len(got) == 6 and sorted(wmap) == [4, 5]  # the whole match survives


def test_a_question_below_a_document_does_not_make_its_last_pages_deep():
    cache = two_class_cache(window_pages=10)
    doc = list(range(100, 124))  # 6 blocks, window 2 blocks
    pages, wpages = commit_with_windows(cache, doc)
    cache.release_all(pages)
    cache.release_window(wpages)
    # a turn: the document (a hit) and a question of two whole blocks below it
    got, wmap = cache.match_classes(doc + list(range(8)) + [1])
    fresh = cache.alloc(2)
    cache.commit(doc + list(range(8)), got + fresh)
    qw = cache.alloc_window(2)
    for page, wpage in zip(fresh, qw):
        assert cache.attach_window(page, wpage)
    cache.release_all(got + fresh)
    cache.release_window(list(wmap.values()) + qw)
    # the document's last block has two blocks below it now, but prompts END
    # on it: blocks 4 and 5 stay; blocks 0..3 go first
    assert cache.evict_window(4) == 4
    assert sorted(cache._node_of_wpage) == sorted(wpages[4:] + qw)
    got, wmap = cache.match_classes(doc + [9, 9, 9, 9, 9])  # the next question
    assert len(got) == 6 and sorted(wmap) == [4, 5]


def test_window_class_exhaustion_refuses_and_reservations_hold():
    cache = two_class_cache(window_pages=6)
    assert cache.alloc_window(0, reserve=4) == []
    assert cache.window_available_pages() == 2
    assert cache.alloc_window(3) is None  # a newcomer cannot eat the reserve
    mine = cache.alloc_window(3, reserved=True)  # its holder can
    assert len(mine) == 3 and cache.window_reserved == 1
    cache.release_window(mine[:1], behind=True, back_to_reserve=True)
    assert cache.window_reserved == 2 and cache.window_pages_freed == 1
    assert cache.window_free_pages() == 4 and cache.window_available_pages() == 2


def test_a_shared_window_page_outlives_the_first_release():
    cache = two_class_cache()
    toks = list(range(100, 116))
    pages, wpages = commit_with_windows(cache, toks)  # the owner's refs
    got, wmap = cache.match_classes(toks + [9])  # a sharer maps the tail
    assert sorted(wmap) == [2, 3]
    cache.release_window([wpages[3]], behind=True)  # the owner passes it
    assert cache.window_allocator.refcount(wpages[3]) == 1  # the sharer's
    assert wpages[3] in cache._node_of_wpage
    cache.release_window([wmap[3]])
    assert cache.window_allocator.refcount(wpages[3]) == 0
    assert wpages[3] in cache._node_of_wpage  # cached: the trie keeps it


def test_evicting_a_block_frees_both_of_its_pages():
    cache = two_class_cache(pages=4, window_pages=4)
    toks = list(range(100, 116))
    pages, wpages = commit_with_windows(cache, toks)
    cache.release_all(pages)
    cache.release_window(wpages)
    assert cache.free_pages() == 0 and cache.window_free_pages() == 0
    assert cache.alloc(1) is not None  # evicts a batch of leaves
    assert cache.window_free_pages() == cache.allocator.free_count() + 1
    assert cache.cached_pages() == len(cache._node_of_wpage)


def test_one_class_eviction_order_is_least_recently_used_leaf_first():
    """The heap keeps the order a fresh scan per victim gave: leaves by
    ``last_used``, a parent once its last child is gone."""
    cache = RadixCache(8, 2)
    a = cache.alloc(3)
    cache.commit([1, 2, 3, 4, 5, 6], a)  # chain a0 - a1 - a2
    b = cache.alloc(2)
    cache.commit([1, 2, 9, 9, 8, 8], [a[0]] + b)  # a0 - b0 - b1
    cache.release_all(a + b)
    cache.match([1, 2, 3, 4, 5, 6, 0])  # touch the a-chain
    cache.release_all(a)
    assert cache.evict(3) == 3  # b1, b0 (now a leaf), then a2
    assert sorted(cache._node_of_page) == sorted(a[:2])
