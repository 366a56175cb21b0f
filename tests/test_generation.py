"""Decode-engine tests: cache consistency, eos handling, warpers, padding."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.configs import ModelSpec
from trlx_tpu.models.generation import GenerationConfig, generate
from trlx_tpu.models.policy import HydraPolicy
from trlx_tpu.ops.sampling import (
    SamplingParams,
    warp_logits,
    warp_top_k,
    warp_top_p,
)


@functools.lru_cache(maxsize=None)
def setup(arch="gpt2"):
    kw = dict(vocab_size=97, n_layer=3, n_head=4, d_model=64, n_positions=64)
    if arch == "gptj":
        kw.update(rotary_dim=8, tie_lm_head=False)
    if arch == "llama":
        kw.update(tie_lm_head=False, n_kv_heads=2)  # GQA decode cache
    spec = ModelSpec(arch=arch, **kw)
    policy = HydraPolicy(spec=spec, num_layers_unfrozen=1, compute_dtype=jnp.float32)
    params = policy.init(jax.random.PRNGKey(0))
    blocks = policy.all_blocks(params)
    embed, ln_f = policy.head_params_for_decode(params)
    return spec, policy, params, blocks, embed, ln_f


def run_generate(arch, prompt, mask, cfg, seed=0):
    spec, policy, params, blocks, embed, ln_f = setup(arch)
    fn = jax.jit(
        lambda blocks, embed, ln_f, p, m, rng: generate(
            spec, blocks, embed, ln_f, p, m, rng, cfg, compute_dtype=jnp.float32,
            cache_dtype=jnp.float32,
        )
    )
    return fn(blocks, embed, ln_f, prompt, mask, jax.random.PRNGKey(seed))


GREEDY = GenerationConfig(gen_size=6, sampling=SamplingParams(do_sample=False))


@pytest.mark.parametrize("arch", ["gpt2", "gptj", "gptneox", "llama"])
def test_greedy_decode_matches_teacher_forcing(arch):
    """Cache-based decode must agree with a full no-cache forward: feeding
    the generated sequence back through the model, argmax at each position
    must reproduce the next generated token."""
    spec, policy, params, *_ = setup(arch)
    B, P = 2, 5
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, P), 1, 97)
    mask = jnp.ones((B, P), jnp.int32)
    out = run_generate(arch, prompt, mask, GREEDY)

    logits, _, _ = policy.jit_forward()(
        params, out.sequences, jnp.ones_like(out.sequences)
    )
    # position P-1+t predicts generated token t
    for t in range(GREEDY.gen_size):
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(logits[:, P - 1 + t], axis=-1)),
            np.asarray(out.gen_tokens[:, t]),
            err_msg=f"mismatch at step {t}",
        )


def test_left_padding_same_continuation():
    """A left-padded prompt must generate the same greedy continuation."""
    B, P, pad = 1, 4, 3
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, P), 1, 97)
    mask = jnp.ones((B, P), jnp.int32)
    out = run_generate("gpt2", prompt, mask, GREEDY)

    prompt_p = jnp.concatenate([jnp.zeros((B, pad), prompt.dtype), prompt], axis=1)
    mask_p = jnp.concatenate([jnp.zeros((B, pad), jnp.int32), mask], axis=1)
    out_p = run_generate("gpt2", prompt_p, mask_p, GREEDY)
    np.testing.assert_array_equal(
        np.asarray(out.gen_tokens), np.asarray(out_p.gen_tokens)
    )


def test_eos_masks_rest():
    """After a row emits eos, tokens become pad and gen_mask goes 0."""
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 4), 1, 97)
    mask = jnp.ones((1, 4), jnp.int32)
    # discover the first greedy token, then declare it to be "eos"
    free = run_generate("gpt2", prompt, mask, GREEDY)
    eos = int(free.gen_tokens[0, 0])
    cfg = GenerationConfig(
        gen_size=6,
        sampling=SamplingParams(do_sample=False),
        eos_token_id=eos,
        pad_token_id=0,
    )
    out = run_generate("gpt2", prompt, mask, cfg)
    gen = np.asarray(out.gen_tokens[0])
    gmask = np.asarray(out.gen_mask[0])
    assert gen[0] == eos
    assert gmask[0] == 1  # eos token itself counts
    assert (gen[1:] == 0).all()
    assert (gmask[1:] == 0).all()


def test_sampling_deterministic_per_seed():
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 4), 1, 97)
    mask = jnp.ones((2, 4), jnp.int32)
    cfg = GenerationConfig(
        gen_size=5, sampling=SamplingParams(do_sample=True, temperature=0.9)
    )
    a = run_generate("gpt2", prompt, mask, cfg, seed=7)
    b = run_generate("gpt2", prompt, mask, cfg, seed=7)
    c = run_generate("gpt2", prompt, mask, cfg, seed=8)
    np.testing.assert_array_equal(np.asarray(a.gen_tokens), np.asarray(b.gen_tokens))
    assert not np.array_equal(np.asarray(a.gen_tokens), np.asarray(c.gen_tokens))


def test_gen_logprobs_match_forward():
    """Stored logprobs must equal log-softmax of the model's logits at the
    emitting position (greedy => warped == unwarped argmax distribution)."""
    spec, policy, params, *_ = setup("gpt2")
    B, P = 2, 5
    prompt = jax.random.randint(jax.random.PRNGKey(5), (B, P), 1, 97)
    out = run_generate("gpt2", prompt, jnp.ones((B, P), jnp.int32), GREEDY)
    logits, _, _ = policy.jit_forward()(
        params, out.sequences, jnp.ones_like(out.sequences)
    )
    lp = jax.nn.log_softmax(logits, axis=-1)
    for t in range(GREEDY.gen_size):
        expect = np.asarray(
            jnp.take_along_axis(
                lp[:, P - 1 + t], out.gen_tokens[:, t][:, None], axis=-1
            )[:, 0]
        )
        np.testing.assert_allclose(
            np.asarray(out.gen_logprobs[:, t]), expect, rtol=1e-4, atol=1e-5
        )


def test_top_k_warper():
    logits = jnp.array([[1.0, 4.0, 2.0, 3.0]])
    out = np.asarray(warp_top_k(logits, 2))
    assert out[0, 1] == 4.0 and out[0, 3] == 3.0
    assert out[0, 0] < -1e8 and out[0, 2] < -1e8


def test_top_p_warper():
    # probs ~ [0.64, 0.23, 0.086, 0.032, ...]: top_p=0.8 keeps the top two
    logits = jnp.log(jnp.array([[0.64, 0.235, 0.086, 0.032, 0.007]]))
    out = np.asarray(warp_top_p(logits, 0.8))
    assert np.isfinite(out[0, 0]) and np.isfinite(out[0, 1])
    assert (out[0, 2:] < -1e8).all()
    # top-1 always survives even with tiny top_p
    out2 = np.asarray(warp_top_p(logits, 1e-9))
    assert np.isfinite(out2[0, 0]) and (out2[0, 1:] < -1e8).all()


def test_warp_order_matches_hf():
    p = SamplingParams(temperature=0.5, top_k=3, top_p=0.9)
    logits = jnp.array([[0.1, 0.5, 0.4, 0.2, 0.05]])
    out = warp_logits(logits, p)
    assert np.isfinite(np.asarray(out)).any()


@pytest.mark.parametrize("arch", ["gpt2", "llama"])
def test_fori_decode_path_matches_unrolled(arch, monkeypatch):
    """Deep models (> _UNROLL_MAX_LAYERS) decode through a fori_loop with
    the stacked cache carried whole; its outputs must bit-match the
    unrolled per-layer-carry path that shallow models take."""
    import trlx_tpu.models.generation as gen_mod

    spec, policy, params, blocks, embed, ln_f = setup(arch)
    B, P = 2, 5
    prompt = jax.random.randint(jax.random.PRNGKey(3), (B, P), 1, 97)
    mask = jnp.ones((B, P), jnp.int32)
    cfg = GenerationConfig(
        gen_size=6, sampling=SamplingParams(do_sample=True), eos_token_id=7,
        pad_token_id=0,
    )

    def run():
        fn = jax.jit(
            lambda blocks, embed, ln_f, p, m, rng: generate(
                spec, blocks, embed, ln_f, p, m, rng, cfg,
                compute_dtype=jnp.float32, cache_dtype=jnp.float32,
            )
        )
        return fn(blocks, embed, ln_f, prompt, mask, jax.random.PRNGKey(9))

    unrolled = run()
    monkeypatch.setattr(gen_mod, "_UNROLL_MAX_LAYERS", 0)
    fori = run()
    for a, b in zip(unrolled, fori):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_from_gen_kwargs_honors_max_new_tokens():
    """HF-style max_new_tokens (what serving clients pass) overrides
    gen_size; exceeding the compiled ceiling raises instead of being
    silently ignored (the pre-serving behavior)."""
    cfg = GenerationConfig.from_gen_kwargs(16, {"max_new_tokens": 8})
    assert cfg.gen_size == 8
    # fixed-length configs keep min_new == (overridden) gen_size
    cfg = GenerationConfig.from_gen_kwargs(
        16, {"max_new_tokens": 8, "min_length": 24, "max_length": 24}
    )
    assert cfg.gen_size == 8 and cfg.min_new_tokens == 8
    with pytest.raises(ValueError, match="exceeds the compiled"):
        GenerationConfig.from_gen_kwargs(8, {"max_new_tokens": 9})
    with pytest.raises(ValueError, match="must be >= 1"):
        GenerationConfig.from_gen_kwargs(8, {"max_new_tokens": 0})
    # absent key: unchanged behavior
    assert GenerationConfig.from_gen_kwargs(8, {}).gen_size == 8


def test_greedy_skips_warps_unchanged():
    """do_sample=False skips temperature/top-k/top-p entirely — all are
    argmax-invariant — so greedy output must match the old warped-argmax
    path exactly (the regression the fast path must not break)."""
    from trlx_tpu.ops.sampling import sample_token

    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, 97))
    for p in (
        SamplingParams(do_sample=False),
        SamplingParams(do_sample=False, temperature=0.37),
        SamplingParams(do_sample=False, top_k=5),
        SamplingParams(do_sample=False, top_p=0.42),
        SamplingParams(do_sample=False, temperature=2.0, top_k=3,
                       top_p=0.9),
    ):
        got = np.asarray(sample_token(rng, logits, p))
        warped_argmax = np.asarray(
            jnp.argmax(warp_logits(logits, p), axis=-1)
        )
        np.testing.assert_array_equal(got, warped_argmax)
        np.testing.assert_array_equal(
            got, np.asarray(jnp.argmax(logits, axis=-1))
        )


def _eos_hungry_extras(eos, fallback=1):
    """extras_fn that replaces the model's logits with a fixed
    distribution whose argmax is ALWAYS eos (fallback token second) —
    the construction the min_new_tokens window tests need, independent
    of what the random model would sample."""

    def extras(h_normed, logits, prev_tok):
        fixed = jnp.full_like(logits, -5.0)
        fixed = fixed.at[:, fallback].set(5.0)
        return fixed.at[:, eos].set(10.0)

    return extras


@pytest.mark.parametrize("min_new", [1, 3])
def test_min_new_tokens_suppression_window(min_new):
    """A row whose argmax is eos from step 0 must emit exactly
    ``min_new_tokens`` real (non-eos) tokens, then the eos — eos is
    NEG_INF-masked strictly inside the window and free at its boundary."""
    spec, policy, params, blocks, embed, ln_f = setup("gpt2")
    eos, fallback, G = 7, 1, 6
    prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 4), 1, 97)
    mask = jnp.ones((2, 4), jnp.int32)
    cfg = GenerationConfig(
        gen_size=G, sampling=SamplingParams(do_sample=False),
        eos_token_id=eos, pad_token_id=0, min_new_tokens=min_new,
    )
    fn = jax.jit(
        lambda b, e, lf, p, m, rng: generate(
            spec, b, e, lf, p, m, rng, cfg, compute_dtype=jnp.float32,
            cache_dtype=jnp.float32,
            extras_fn=_eos_hungry_extras(eos, fallback),
        )
    )
    out = fn(blocks, embed, ln_f, prompt, mask, jax.random.PRNGKey(0))
    gen = np.asarray(out.gen_tokens)
    gmask = np.asarray(out.gen_mask)
    for row in range(2):
        # min_new real tokens (the suppressed-eos fallback), then eos
        np.testing.assert_array_equal(gen[row, :min_new], fallback)
        assert gen[row, min_new] == eos
        np.testing.assert_array_equal(gen[row, min_new + 1:], 0)
        assert gmask[row].sum() == min_new + 1  # eos token counts


def test_min_new_equals_gen_size_suppresses_eos_fully():
    """The fixed-length pin (min_length == max_length ->
    min_new_tokens == gen_size): eos stays suppressed at EVERY step, so
    an eos-hungry model still emits gen_size real tokens and gen_mask
    never drops."""
    spec, policy, params, blocks, embed, ln_f = setup("gpt2")
    eos, G = 7, 5
    prompt = jax.random.randint(jax.random.PRNGKey(8), (1, 4), 1, 97)
    mask = jnp.ones((1, 4), jnp.int32)
    cfg = GenerationConfig(
        gen_size=G, sampling=SamplingParams(do_sample=False),
        eos_token_id=eos, pad_token_id=0, min_new_tokens=G,
    )
    fn = jax.jit(
        lambda b, e, lf, p, m, rng: generate(
            spec, b, e, lf, p, m, rng, cfg, compute_dtype=jnp.float32,
            cache_dtype=jnp.float32, extras_fn=_eos_hungry_extras(eos),
        )
    )
    out = fn(blocks, embed, ln_f, prompt, mask, jax.random.PRNGKey(0))
    gen = np.asarray(out.gen_tokens[0])
    assert eos not in gen
    assert np.asarray(out.gen_mask[0]).sum() == G


def test_from_gen_kwargs_min_length_boundary_pin():
    """min_length == max_length must map to FULL suppression
    (min_new_tokens == gen_size) exactly at the boundary; one below the
    pin leaves a one-token eos window."""
    cfg = GenerationConfig.from_gen_kwargs(
        8, {"min_length": 12, "max_length": 12}, prompt_len=4
    )
    assert cfg.min_new_tokens == cfg.gen_size == 8
    cfg = GenerationConfig.from_gen_kwargs(
        8, {"min_length": 11, "max_length": 12}, prompt_len=4
    )
    assert cfg.min_new_tokens == 7 < cfg.gen_size


def test_eos_early_exit_parity_with_plain_scan(monkeypatch):
    """The lax.cond early-exit guard (all rows finished -> cheap no-op
    step) must be invisible in the outputs: tokens and gen_mask
    bit-match the plain-scan path on a batch that terminates early."""
    import trlx_tpu.models.generation as gen_mod

    spec, policy, params, blocks, embed, ln_f = setup("gpt2")
    eos = 7
    prompt = jax.random.randint(jax.random.PRNGKey(9), (3, 4), 1, 97)
    mask = jnp.ones((3, 4), jnp.int32)
    cfg = GenerationConfig(
        gen_size=8, sampling=SamplingParams(do_sample=False),
        eos_token_id=eos, pad_token_id=0, min_new_tokens=2,
    )

    def run():
        fn = jax.jit(
            lambda b, e, lf, p, m, rng: generate(
                spec, b, e, lf, p, m, rng, cfg, compute_dtype=jnp.float32,
                cache_dtype=jnp.float32, extras_fn=_eos_hungry_extras(eos),
            )
        )
        return fn(blocks, embed, ln_f, prompt, mask, jax.random.PRNGKey(0))

    guarded = run()
    # every row terminates at step 2 (min_new=2 window + eos): the guard
    # really fires for steps 3..7
    assert np.asarray(guarded.gen_mask).sum(axis=1).tolist() == [3, 3, 3]
    monkeypatch.setattr(gen_mod, "_EOS_EARLY_EXIT", False)
    plain = run()
    np.testing.assert_array_equal(
        np.asarray(guarded.gen_tokens), np.asarray(plain.gen_tokens)
    )
    np.testing.assert_array_equal(
        np.asarray(guarded.gen_mask), np.asarray(plain.gen_mask)
    )
    np.testing.assert_allclose(
        np.asarray(guarded.gen_logprobs), np.asarray(plain.gen_logprobs)
    )


def test_sampling_key_accepts_raw_rbg_data():
    """ADVICE r04: raw 4-word uint32 key data is already rbg-shaped — it
    must wrap as-is (tiling to 8 words raises inside wrap_key_data), and
    2-word threefry-style data still tiles to 4. Unknown widths pass
    through untouched."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.generation import _sampling_key

    raw4 = jnp.arange(4, dtype=jnp.uint32)
    k4 = _sampling_key(raw4)
    assert str(jax.random.key_impl(k4)) == "rbg"
    jax.random.uniform(k4)  # usable

    raw2 = jnp.arange(2, dtype=jnp.uint32)
    k2 = _sampling_key(raw2)
    assert str(jax.random.key_impl(k2)) == "rbg"
    jax.random.uniform(k2)

    raw3 = jnp.arange(3, dtype=jnp.uint32)
    assert _sampling_key(raw3) is raw3

    # typed non-threefry keys pass through with their stream intact
    rbg_key = jax.random.key(0, impl="rbg")
    assert _sampling_key(rbg_key) is rbg_key


def test_per_device_nbytes_eager_vs_tracer():
    """Eager arrays report a real per-device footprint (replicated ==
    global); jit tracers are uninspectable and return None so the decode
    unroll decision falls back to the depth ceiling (ADVICE r04)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.generation import _per_device_nbytes

    x = jnp.ones((8, 4), jnp.float32)
    assert _per_device_nbytes([x]) == 8 * 4 * 4

    seen = {}

    @jax.jit
    def f(y):
        seen["val"] = _per_device_nbytes([y])
        return y

    f(x)
    assert seen["val"] is None


def test_decide_unroll_eager_and_env_override(monkeypatch):
    """Trainers decide the decode unroll EAGERLY (code-review r05: inside
    the jitted rollout the weights are tracers, so generate()'s own
    per-device backoff can't engage) and pass it through; the env override
    still governs the eager decision."""
    import jax.numpy as jnp

    from trlx_tpu.data.configs import ModelSpec
    from trlx_tpu.models.generation import decide_unroll

    spec = ModelSpec(vocab_size=97, n_layer=2, n_head=2, d_model=32,
                     n_positions=64)
    params = {"w": jnp.ones((4, 4), jnp.float32)}
    assert decide_unroll(spec, params, batch_size=4, seq_len=16) is True
    monkeypatch.setenv("TRLX_TPU_DECODE_UNROLL_MAX", "0")
    assert decide_unroll(spec, params, batch_size=4, seq_len=16) is False


# -- the decode step's bounded read of the contiguous cache ---------------- #


def _bounded_and_whole(arch, prompt, mask, cfg, block, monkeypatch,
                       extras_fn=None):
    """generate() twice on the same inputs: with the decode step reading
    only the written slots in extents of ``block`` slots (the last one
    the whole buffer), and with the plan empty (the whole buffer read and
    masked every step)."""
    import trlx_tpu.models.generation as gen_mod

    spec, policy, params, blocks, embed, ln_f = setup(arch)
    S = prompt.shape[1] + cfg.gen_size
    plans = []

    def run(plan):
        def fake_plan(spec_, batch, buffer_len, cache_dtype):
            assert buffer_len == S
            plans.append(plan)
            return plan

        monkeypatch.setattr(gen_mod, "decode_read_plan", fake_plan)
        fn = jax.jit(
            lambda b, e, lf, p, m, rng: generate(
                spec, b, e, lf, p, m, rng, cfg, compute_dtype=jnp.float32,
                cache_dtype=jnp.float32, extras_fn=extras_fn,
            )
        )
        return fn(blocks, embed, ln_f, prompt, mask, jax.random.PRNGKey(4))

    plan = tuple(range(block, S, block)) + (S,)
    assert len(plan) > 1
    bounded, whole = run(plan), run(())
    assert plans == [plan, ()]
    return bounded, whole


@pytest.mark.parametrize(
    "arch, P, G, block, layers",
    [
        ("gpt2", 5, 6, 4, "unrolled"),  # S = 11, not a multiple of 4
        ("gpt2", 4, 8, 4, "unrolled"),  # S = 12, a multiple of 4
        ("gpt2", 5, 6, 1, "unrolled"),  # a slot an extent
        ("llama", 5, 6, 4, "unrolled"),  # GQA: 4 query heads over 2
        ("gpt2", 5, 6, 4, "fori"),
        ("llama", 4, 8, 3, "fori"),
    ],
)
def test_bounded_cache_read_matches_the_whole_buffer(
        arch, P, G, block, layers, monkeypatch):
    """The decode step's attention over the written slots alone (a switch
    over the plan's extents) gives the greedy tokens, the log-probabilities
    and the emitted mask of the whole buffer read under its mask: the
    slots it leaves out carry probability nought there, so only rounding
    may differ (float32 here; the chip's bfloat16 within its own
    rounding). Both layer paths, MHA and GQA, a buffer a multiple of the
    extent and one not."""
    import trlx_tpu.models.generation as gen_mod

    if layers == "fori":
        monkeypatch.setattr(gen_mod, "_UNROLL_MAX_LAYERS", 0)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, P), 1, 97)
    mask = jnp.ones((2, P), jnp.int32)
    cfg = GenerationConfig(gen_size=G, sampling=SamplingParams(do_sample=False))
    bounded, whole = _bounded_and_whole(arch, prompt, mask, cfg, block,
                                        monkeypatch)
    np.testing.assert_array_equal(np.asarray(bounded.gen_tokens),
                                  np.asarray(whole.gen_tokens))
    np.testing.assert_array_equal(np.asarray(bounded.gen_mask),
                                  np.asarray(whole.gen_mask))
    np.testing.assert_allclose(np.asarray(bounded.gen_logprobs),
                               np.asarray(whole.gen_logprobs),
                               rtol=1e-5, atol=1e-5)


def test_bounded_cache_read_under_eos_exit_and_left_padding(monkeypatch):
    """The same over left-padded prompts (pad slots inside the first
    extent, masked as before) and the EOS early-exit path: the rows end
    at step 2 and the guard skips the steps after it, in both reads."""
    eos = 7
    prompt = jax.random.randint(jax.random.PRNGKey(9), (3, 5), 1, 97)
    mask = jnp.array([[0, 0, 1, 1, 1], [1, 1, 1, 1, 1], [0, 1, 1, 1, 1]],
                     jnp.int32)
    prompt = prompt * mask
    cfg = GenerationConfig(
        gen_size=8, sampling=SamplingParams(do_sample=False),
        eos_token_id=eos, pad_token_id=0, min_new_tokens=2,
    )
    bounded, whole = _bounded_and_whole(
        "gpt2", prompt, mask, cfg, 4, monkeypatch,
        extras_fn=_eos_hungry_extras(eos),
    )
    assert np.asarray(bounded.gen_mask).sum(axis=1).tolist() == [3, 3, 3]
    for a, b in zip(bounded, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_decode_read_plan_from_the_cache_shape(monkeypatch):
    """The plan is read off the buffer length, the rows and the head
    shape. gpt2-xl's PPO rollout (128 rows, 4 + 48 slots, 25 heads of 64,
    bfloat16 on one chip): extents of 16 slots, 6.5 MB of K a layer each,
    the last the whole 52, so the loop reads 0.673 of what the whole
    buffer every step would. Whole (no plan): a buffer within one extent,
    rows that fill no whole lane tile on some device, heads as wide as a
    lane tile (their cache is laid out head-last already). Split over
    devices, an extent counts one device's rows."""
    from trlx_tpu.models.generation import decode_read_plan, decode_read_share

    monkeypatch.setattr(jax, "device_count", lambda: 1)
    xl = ModelSpec(arch="gpt2", vocab_size=50257, n_layer=48, n_head=25,
                   d_model=1600, n_positions=1024)
    plan = decode_read_plan(xl, 128, 52, jnp.bfloat16)
    assert plan == (16, 32, 48, 52)
    assert decode_read_share(plan, 4, 48) == pytest.approx(1680 / 2496)
    assert decode_read_share((), 4, 48) == 1.0
    assert decode_read_plan(xl, 128, 16, jnp.bfloat16) == ()  # one extent
    assert decode_read_plan(xl, 64, 52, jnp.bfloat16) == ()
    assert decode_read_plan(xl, 128, 52, jnp.float32) == (8, 16, 24, 32,
                                                          40, 48, 52)
    gptj = ModelSpec(arch="gptj", vocab_size=50400, n_layer=28, n_head=16,
                     d_model=4096, rotary_dim=64, n_positions=2048)
    assert decode_read_plan(gptj, 128, 52, jnp.bfloat16) == ()
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert decode_read_plan(xl, 128, 52, jnp.bfloat16) == ()  # 32 a chip
    assert decode_read_plan(xl, 512, 52, jnp.bfloat16) == (16, 32, 48, 52)
