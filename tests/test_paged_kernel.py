"""Fused paged-attention decode kernel + int8 KV/weight tiers
(trlx_tpu/ops/paged_attention, the quantized halves of
models/transformer + models/generation, serve.attention/kv_dtype/
weights_dtype): kernel-vs-jnp numerics with sentinel pages and GQA,
end-to-end greedy parity of the ``serve.attention: pallas`` engine
against the one-shot generate() oracle across page sizes with shared
prefixes and staggered admission, the int8 tier's quantize/dequantize
round-trip bound, int8 greedy parity + logit tolerance, prefix-cache
content-addressability under quantized pages, replay-after-poisoned-step
parity with int8 pages, and the serve-only int8 weight views (boot,
decode, hot-swap validation, shrunk model_gb). All device code runs the
kernel through the Pallas interpreter on CPU (``make kernels``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.models.generation import (
    _segments_of,
    decode_step,
    init_page_pool,
    init_slot_state,
    prefill_into_slots,
)
from trlx_tpu.models.transformer import dequantize_kv, quantize_kv
from trlx_tpu.ops.paged_attention import (
    make_paged_decode_fn,
    paged_decode_attention,
)
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.supervisor import chaos
from test_paged import build_engine
from test_slots import direct_generate

NEG_INF = -1e9


@pytest.fixture()
def fresh_registry():
    session = telemetry.start()
    yield session.registry
    telemetry.start()


# --------------------------------------------------------------------- #
# kernel numerics vs the jnp gather+score reference
# --------------------------------------------------------------------- #


def _jnp_paged_reference(q, k_pool, v_pool, pt, bias):
    """The exact arithmetic of block_apply's paged mode for one decode
    row: clamp-gather pages to logical order, GQA-grouped scores in f32,
    softmax, weighted sum."""
    S, H, hd = q.shape
    num_pages, page_size, Hkv, _ = k_pool.shape
    T = pt.shape[1] * page_size
    ctx = jnp.clip(pt, 0, num_pages - 1)
    k_ctx = k_pool[ctx].reshape(S, T, Hkv, hd)
    v_ctx = v_pool[ctx].reshape(S, T, Hkv, hd)
    G = H // Hkv
    qg = q.reshape(S, 1, Hkv, G, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_ctx).astype(jnp.float32)
    scores = scores * jax.lax.rsqrt(jnp.float32(hd)) \
        + bias[:, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(v_ctx.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_ctx)
    return out.reshape(S, 1, H, hd)[:, 0]


def _kernel_case(seed=0):
    rng = np.random.default_rng(seed)
    S, H, Hkv, hd = 3, 4, 2, 16
    num_pages, page_size, max_pages = 10, 4, 3
    T = max_pages * page_size
    q = jnp.asarray(rng.standard_normal((S, H, hd)), jnp.float32)
    k = jnp.asarray(
        rng.standard_normal((num_pages, page_size, Hkv, hd)), jnp.float32
    )
    v = jnp.asarray(
        rng.standard_normal((num_pages, page_size, Hkv, hd)), jnp.float32
    )
    sent = 2**30  # the host allocator's out-of-pool sentinel
    pt = jnp.asarray(
        [[1, 3, sent], [0, sent, sent], [5, 6, 7]], jnp.int32
    )
    lengths = jnp.asarray([6, 3, 12], jnp.int32)
    bias = jnp.where(
        jnp.arange(T)[None, :] < lengths[:, None], 0.0, NEG_INF
    ).astype(jnp.float32)
    return q, k, v, pt, bias


def test_kernel_matches_jnp_reference_with_sentinel_pages():
    """Online-softmax kernel output matches the gather+softmax reference
    to float tolerance — GQA grouping, varying lengths, sentinel pages
    (clamped DMA + exact-zero mask) all in play, under jit."""
    q, k, v, pt, bias = _kernel_case()
    ref = _jnp_paged_reference(q, k, v, pt, bias)
    out = jax.jit(
        lambda *a: paged_decode_attention(*a)
    )(q, k, v, pt, bias)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5
    )


def test_kernel_int8_matches_dequantized_reference():
    """The fused in-kernel dequant is numerically the same computation
    as dequantize-then-score: parity against the reference run on
    explicitly dequantized pools."""
    q, k, v, pt, bias = _kernel_case(seed=1)
    k_codes, k_scales = quantize_kv(k)
    v_codes, v_scales = quantize_kv(v)
    ref = _jnp_paged_reference(
        q,
        dequantize_kv(k_codes, k_scales, jnp.float32),
        dequantize_kv(v_codes, v_scales, jnp.float32),
        pt, bias,
    )
    out = jax.jit(
        lambda *a: paged_decode_attention(*a)
    )(q, (k_codes, k_scales), (v_codes, v_scales), pt, bias)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5
    )


def test_make_paged_decode_fn_single_device_is_direct_call():
    q, k, v, pt, bias = _kernel_case(seed=2)
    fn = make_paged_decode_fn(mesh=None)
    out = fn(q, k, v, pt, bias)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_jnp_paged_reference(q, k, v, pt, bias)),
        atol=1e-5,
    )


# -- the block walk: P pages a step, (page_size, Hkv) folded into keys -- #

SENT = 2**30  # the host allocator's out-of-pool sentinel

#: per case: (table rows, visible positions per row), at page_size 4 over
#: tables of 7 walked 3 pages a block (padded to 9); pages are pool ids
BLOCK_CASES = {
    # every entry live: the third block holds one real entry and two pads
    "table-not-a-multiple-of-P": (
        [[1, 3, 5, 7, 9, 11, 13], [0, 2, 4, 6, 8, 10, 12]],
        [range(0, 28), range(0, 26)],
    ),
    # extents of 4 and 5 entries end inside the second block; one of 1
    "extent-ends-inside-a-block": (
        [[1, 3, 5, 7, SENT, SENT, SENT], [0, 2, 4, 6, 8, SENT, SENT],
         [9, SENT, SENT, SENT, SENT, SENT, SENT]],
        [range(0, 14), range(0, 20), range(0, 2)],
    ),
    # a sentinel entry between live ones (its positions masked)
    "sentinel-inside-a-live-block": (
        [[1, SENT, 5, 7, 9, SENT, SENT], [0, 2, 4, 6, SENT, 10, 12]],
        [[*range(0, 4), *range(8, 19)], [*range(0, 16), *range(20, 27)]],
    ),
    # a free slot between two busy ones: all sentinel, nothing visible
    "a-row-with-nothing-live": (
        [[1, 3, 5, 7, 9, 11, 13], [SENT] * 7, [0, 2, 4, 6, SENT, SENT, SENT]],
        [range(0, 28), [], range(3, 15)],
    ),
    # a ring written past its end: live entries on both sides of a hole
    # of stale pages, the newest page and the oldest both partly seen
    "wrapped-ring": (
        [[1, 3, 5, 7, 9, 11, 13], [0, 2, 4, 6, 8, 10, 12]],
        [[*range(0, 6), *range(21, 28)], [*range(0, 3), *range(14, 28)]],
    ),
}


@pytest.mark.parametrize("tier", ["compute-dtype", "int8"])
@pytest.mark.parametrize("G", [1, 4], ids=["G=1", "G=4"])
@pytest.mark.parametrize("case", list(BLOCK_CASES))
def test_kernel_matches_jnp_reference_over_blocks(case, G, tier,
                                                  monkeypatch):
    """What walking a table in blocks brings, each against the gather +
    softmax reference: 8 kv heads of 128 (the narrowest pool the block
    kernel takes; the int8 tier walks the same tables a page a step). A
    row the bias lets nothing through for reads zeros (the reference
    averages every masked key there, which no caller reads)."""
    from trlx_tpu.ops import paged_attention as pa

    table, visible = BLOCK_CASES[case]
    S, Hkv, hd, page_size, num_pages = len(table), 8, 128, 4, 14
    max_pages = len(table[0])
    pool = (num_pages, page_size, Hkv, hd)
    # 3 pages a block, whatever the budget's default
    monkeypatch.setattr(pa, "BLOCK_VMEM_BYTES",
                        4 * 3 * page_size * Hkv * hd * 4)
    rng = np.random.default_rng(len(case) + G)
    q = jnp.asarray(rng.standard_normal((S, Hkv * G, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    v = jnp.asarray(rng.standard_normal(pool), jnp.float32)
    bias = np.full((S, max_pages * page_size), NEG_INF, np.float32)
    for row, seen in enumerate(visible):
        bias[row, list(seen)] = 0.0
    pt, bias = jnp.asarray(table, jnp.int32), jnp.asarray(bias)
    if tier == "int8":
        assert pa.block_plan(pool, jnp.int8, max_pages) == (1, max_pages)
        k_in, v_in = quantize_kv(k), quantize_kv(v)
        k, v = (dequantize_kv(*k_in, jnp.float32),
                dequantize_kv(*v_in, jnp.float32))
    else:
        assert pa.block_plan(pool, k.dtype, max_pages) == (3, 3)
        k_in, v_in = k, v
    out = np.asarray(jax.jit(paged_decode_attention)(q, k_in, v_in, pt, bias))
    ref = np.asarray(_jnp_paged_reference(q, k, v, pt, bias))
    for row, seen in enumerate(visible):
        if len(seen):
            np.testing.assert_allclose(out[row], ref[row], atol=2e-5)
        else:
            assert not out[row].any()


def test_block_plan_follows_the_pool_it_is_given():
    """Pages a block from the page's bytes against one VMEM budget, evened
    over the table; the pools Mosaic cannot slice by page walk pages."""
    from trlx_tpu.ops.paged_attention import block_plan

    cell3 = (7168, 64, 8, 128)  # 128 KiB a page and operand in bfloat16
    assert block_plan(cell3, jnp.bfloat16, 454) == (8, 57)
    assert block_plan((2560, *cell3[1:]), jnp.bfloat16, 66) == (8, 9)
    assert block_plan(cell3, jnp.bfloat16, 4) == (4, 1)  # at most the table
    assert block_plan((96, 64, 16, 256), jnp.bfloat16, 6) == (2, 3)  # gpt-j
    assert block_plan((96, 64, 16, 256), jnp.int8, 6) == (1, 6)
    assert block_plan((512, 64, 25, 64), jnp.bfloat16, 8) == (1, 8)  # gpt2-xl


# --------------------------------------------------------------------- #
# e2e: serve.attention pallas — greedy parity vs one-shot generate()
# --------------------------------------------------------------------- #

#: the standard parity trace: shared 5-token prefix, a full repeat, a
#: cold row — staggered over two admission waves
ROWS = [
    [3, 1, 4, 1, 5],
    [3, 1, 4, 1, 5, 9, 2, 6],
    [9, 2, 6],
    [3, 1, 4, 1, 5, 9, 2, 6],
]


def _run_staggered(s, max_new=8):
    first = [s.submit(list(r), max_new_tokens=max_new) for r in ROWS[:2]]
    for r in first:
        r.wait(timeout=60.0)
    second = [s.submit(list(r), max_new_tokens=max_new) for r in ROWS[2:]]
    for r in second:
        r.wait(timeout=60.0)
    return first + second


@pytest.mark.parametrize("page_size", [3, 8, 24])
def test_pallas_engine_greedy_parity_sweep(page_size, fresh_registry):
    """The kernel engine's greedy outputs are pinned to the one-shot
    generate() oracle across page sizes (unaligned 3, mid 8, whole-
    buffer 24) with shared prefixes, staggered admission, and zero
    steady-state recompiles — same contract the jnp path carries."""
    engine = build_engine(attention="pallas", page_size=page_size,
                          buckets=[[2, 8, 8], [4, 8, 8]])
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        reqs = _run_staggered(s)
        oracle = direct_generate(engine, ROWS, (4, 8, 8))
        for i, req in enumerate(reqs):
            assert req.result == engine.depad_row(oracle, i, 8), (
                f"row {i} diverged from generate() at "
                f"page_size={page_size} under the pallas kernel"
            )
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        # warm-up named what the kernel chose for this pool: 4 heads of
        # 16 fill no tile, so a page a grid step
        rt = s.runtime
        assert registry.gauges[
            "serve/paged_attn_pages_per_block{class=full}"] == 1.0
        assert registry.gauges["serve/paged_attn_grid_steps{class=full}"] \
            == rt.num_slots * rt.max_pages
        if page_size < 8:
            assert registry.counters["serve/prefix_tokens_saved"] > 0
        assert s.free_slots() == s.runtime.num_slots
    finally:
        s.stop()


# --------------------------------------------------------------------- #
# int8 KV tier
# --------------------------------------------------------------------- #


def test_int8_roundtrip_error_bound_per_page():
    """|x - dq(q(x))| <= scale / 2 elementwise, i.e. amax/254 per
    (token, head) — the quantize_kv contract the logit tolerance rests
    on; exercised on page-shaped data including an all-zero page (fresh
    pool rows must survive the eps floor)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(
        rng.standard_normal((6, 4, 2, 16)) * 3.0, jnp.float32
    )
    x = x.at[2].set(0.0)  # an untouched (all-zero) pool page
    codes, scale = quantize_kv(x)
    dq = dequantize_kv(codes, scale, jnp.float32)
    err = np.abs(np.asarray(x - dq))
    bound = np.asarray(scale)[..., None] / 2.0 + 1e-6
    assert (err <= bound).all()
    amax = np.abs(np.asarray(x)).max(axis=-1)
    assert (err.max(axis=-1) <= amax / 254.0 + 1e-6).all()
    # determinism = content-addressability: same content, same bits
    codes2, scale2 = quantize_kv(x)
    assert (np.asarray(codes) == np.asarray(codes2)).all()
    assert (np.asarray(scale) == np.asarray(scale2)).all()


def test_int8_pool_logit_tolerance_vs_bf16():
    """Prefill + decode over an int8 pool tracks the bf16-pool logits
    within a small absolute tolerance — the 'tested logit tolerance'
    half of the int8 parity contract, at the primitives level."""
    engine = build_engine()
    spec = engine.spec
    cfg = engine._gen_base._replace(gen_size=8)
    _, seg_sizes = _segments_of(engine.blocks)
    S, ps, max_pages, Np = 2, 4, 4, 8
    rows = [[3, 1, 4, 1, 5], [9, 2, 6]]
    t = np.zeros((S, 8), np.int32)
    m = np.zeros((S, 8), np.int32)
    for i, row in enumerate(rows):
        t[i, :len(row)] = row
        m[i, :len(row)] = 1
    tables = np.array([[0, 1, 2, 3], [4, 5, 6, 7]], np.int32)

    logit_trace = {}
    for tier in ("bf16", "int8"):
        dtype = jnp.int8 if tier == "int8" else jnp.bfloat16
        pool = init_page_pool(spec, seg_sizes, Np, ps, cache_dtype=dtype)
        state = init_slot_state(S, max_pages * ps, spec.vocab_size,
                                max_pages=max_pages)
        pool, state = jax.jit(
            lambda pool, st, pt: prefill_into_slots(
                spec, engine.blocks, engine.embed, engine.ln_f, pool,
                st, t, m, np.arange(S, dtype=np.int32),
                np.full((S,), 8, np.int32), compute_dtype=jnp.float32,
                page_tables=pt, page_size=ps,
                start=np.zeros((S,), np.int32),
            )
        )(pool, state, tables)
        trace = [np.asarray(state.logits)]
        sf = jax.jit(
            lambda pool, st, seed: decode_step(
                spec, engine.blocks, engine.embed, engine.ln_f, pool,
                st, seed, cfg, compute_dtype=jnp.float32,
            )
        )
        for step in range(4):
            pool, state, _, _, _ = sf(pool, state, np.int32(step))
            trace.append(np.asarray(state.logits))
        logit_trace[tier] = trace

    for a, b in zip(logit_trace["bf16"], logit_trace["int8"]):
        assert np.abs(a - b).max() < 0.1, (
            "int8 KV logits drifted past the pinned tolerance"
        )


@pytest.mark.parametrize("page_size", [3, 8, 24])
def test_int8_engine_greedy_parity_sweep(page_size, fresh_registry):
    """Greedy parity on the standard traces under int8 KV pages: same
    rows, staggered admission, shared prefixes — outputs must match the
    full-precision one-shot oracle on these traces, with zero
    recompiles (quantization changes pool dtypes at build time, never
    shapes at step time)."""
    engine = build_engine(kv_dtype="int8", page_size=page_size,
                          buckets=[[2, 8, 8], [4, 8, 8]])
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        reqs = _run_staggered(s)
        oracle = direct_generate(engine, ROWS, (4, 8, 8))
        for i, req in enumerate(reqs):
            assert req.result == engine.depad_row(oracle, i, 8), (
                f"row {i} diverged from generate() at "
                f"page_size={page_size} under int8 KV"
            )
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert s.free_slots() == s.runtime.num_slots
    finally:
        s.stop()


def test_int8_with_pallas_kernel_matches_int8_jnp_engine(fresh_registry):
    """The fully-fused tier (int8 pages + in-kernel dequant) emits the
    same greedy tokens as the int8 jnp path — the kernel A/B holds at
    both KV tiers."""
    results = {}
    for attention in ("jnp", "pallas"):
        engine = build_engine(kv_dtype="int8", attention=attention,
                              buckets=[[2, 8, 8], [4, 8, 8]])
        s = SlotScheduler(engine)
        s.warmup()
        s.start()
        try:
            results[attention] = [r.result for r in _run_staggered(s)]
        finally:
            s.stop()
    assert results["pallas"] == results["jnp"]


def test_int8_prefix_pages_remain_content_addressable(fresh_registry):
    """Quantized pages dedupe identically: a repeat of a committed
    prompt hits the radix cache (skipping its prefill) and still decodes
    bit-identical to the cold run — quantize_kv is a pure function of
    token content, so shared pages carry the same codes either way."""
    engine = build_engine(kv_dtype="int8", buckets=[[2, 16, 8]],
                          page_size=4)
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        prompt = [7, 7, 7, 7, 5, 5, 5, 5, 1, 2, 3, 4]
        a = s.submit(prompt, max_new_tokens=4)
        a.wait(timeout=60.0)
        b = s.submit(prompt, max_new_tokens=4)  # 2 of 3 blocks hit
        b.wait(timeout=60.0)
        saved = telemetry.current().registry.counters[
            "serve/prefix_tokens_saved"
        ]
        assert saved == 8.0, "repeat prompt did not hit quantized pages"
        assert a.result == b.result
        stats = s.pool_stats()
        assert stats["kv_dtype"] == "int8"
        assert stats["pages_cached"] > 0
    finally:
        s.stop()


def test_int8_replay_after_poisoned_step_parity(fresh_registry):
    """Crash-only recovery holds on quantized pools: a poisoned decode
    step resets lanes + cache and replays the in-flight request, whose
    output must match the same engine's uninterrupted run (re-prefilled
    pages re-quantize to the same codes)."""
    engine = build_engine(kv_dtype="int8")
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        clean = s.submit([1, 2, 3, 4, 5, 6], max_new_tokens=4)
        clean.wait(timeout=30.0)
        assert clean.result is not None
        chaos.configure("serve_decode:exc@1")
        bad = s.submit([1, 2, 3, 4, 5, 6], max_new_tokens=4)
        assert bad.wait(timeout=30.0).result is not None
        chaos.reset()
        assert bad.replays == 1
        assert bad.result == clean.result, (
            "replayed int8 decode diverged from the uninterrupted run"
        )
        stats = s.pool_stats()
        assert stats["pages_free"] + stats["pages_cached"] \
            == s.runtime.num_pages
    finally:
        chaos.reset()
        s.stop()


# --------------------------------------------------------------------- #
# serve-only int8 weights
# --------------------------------------------------------------------- #


def test_weights_int8_engine_boots_decodes_and_validates_swap(
    fresh_registry,
):
    """serve.weights_dtype: int8 — the engine boots with quantized block
    views (model_gb shrinks vs bf16), decodes finite tokens with zero
    recompiles, and a strip_for_serve'd hot-swap candidate (quantized
    through the same seam) passes validate_swap leaf-for-leaf."""
    bf16 = build_engine()
    bf16_gb = telemetry.current().registry.gauges["serve/model_gb"]
    engine = build_engine(weights_dtype="int8")
    registry = telemetry.current().registry
    assert registry.gauges["serve/model_gb"] < bf16_gb
    # block matrices really are (codes, scale) pairs now
    leaves = jax.tree_util.tree_leaves(engine.blocks)
    assert any(leaf.dtype == jnp.int8 for leaf in leaves)
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        req = s.submit([3, 1, 4, 1, 5], max_new_tokens=6)
        req.wait(timeout=60.0)
        assert req.error is None
        assert 0 < len(req.result) <= 6
        assert all(0 <= t < engine.spec.vocab_size for t in req.result)
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
    finally:
        s.stop()
    views = engine.strip_for_serve(engine._init_params())
    engine.validate_swap(views)  # must not raise: same quantized layout


def test_weights_int8_tracks_bf16_logits():
    """Per-channel int8 weights stay close to the bf16 engine's greedy
    choices on a short trace — the weight tier's parity smoke (exact
    bit-parity is NOT pinned for weights; closeness is)."""
    results = {}
    for tier in ("bf16", "int8"):
        engine = build_engine(weights_dtype=tier)
        s = SlotScheduler(engine)
        s.warmup()
        s.start()
        try:
            req = s.submit([3, 1, 4], max_new_tokens=4)
            req.wait(timeout=60.0)
            results[tier] = req.result
        finally:
            s.stop()
    assert len(results["int8"]) == len(results["bf16"])
