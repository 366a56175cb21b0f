"""Jitted autoregressive generation with a static-shape KV cache.

Replaces HF `generate` (reference: trlx/model/accelerate_base_model.py:119-123)
and the ILQL hand-rolled KV-cache loop (reference:
trlx/model/nn/ilql_models.py:216-260) with one compiled program:

- prefill: one full forward over the (left-padded) prompt filling the cache;
- decode: `lax.scan` over `gen_size` steps, each a single-token forward
  against the cache — static shapes, no host round-trips, pjit-shardable;
- fixed-length generation with eos masking (the reference configs pin
  min_length == max_length, reference: configs/ppo_config.yml:48-49): after
  a row emits eos, it keeps emitting pad tokens and `gen_mask` goes 0.

An optional `extras_fn(h_normed, logits) -> logits` hook lets ILQL shift
logits by beta * (Q - V) at each step without a second implementation.

The decode loop's KV cache lives in the scan *carry* as per-layer leaves
(layer loop unrolled in the body) rather than as stacked xs/ys of an inner
layer scan: scan xs/ys buffers are re-materialized every step, so a stacked
cache costs ~4x its size in HBM traffic per decoded token (read-in + update
copy + attention read + write-out), which measured ~1.4 ms/step of pure
cache traffic at gpt2-124M [B=128, S=52] on v5e where the attention-read
floor is ~0.3 ms. Carry leaves are aliased in place by XLA; the same decode
measured 2.83 -> 1.58 ms/step. Deep models (> _UNROLL_MAX_LAYERS) switch to
a fori_loop over layers with the stacked cache carried whole (same in-place
property, O(1) program size; ~14% slower at 12 layers).
"""

import functools
import math
import os
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import ModelSpec
from trlx_tpu.models.transformer import (
    ArchFlags,
    NEG_INF,
    _mixed_layers,
    require_supported,
    apply_blocks_with_cache,
    attention_scores,
    block_apply,
    causal_mask_bias,
    embed_tokens,
    init_kv_cache,
    init_paged_kv_cache,
    layer_norm,
    quantize_kv,
    positions_from_mask,
    project_logits,
    rope_of,
)
from trlx_tpu.ops.sampling import SamplingParams, sample_token
from trlx_tpu.utils import tree_bytes

Params = Dict[str, Any]

# EOS early-exit fast path in generate(): once every row has finished,
# each remaining scan step runs a cheap predicated no-op instead of a full
# forward (lax.cond on finished.all()). Module-level so tests can A/B the
# guarded path against the plain scan (token/gen_mask parity).
_EOS_EARLY_EXIT = True

# Depth ceiling for the unrolled decode body. What makes the unrolled path
# fast is the per-layer TUPLE cache leaves in the scan carry (measured:
# gpt2-xl 48L 9.7-11.8 ms/step unrolled vs 14.7-15.7 for every
# stacked-carry variant, including group-chunked unrolls —
# dynamic_update_index on a stacked cache costs the same as fori). The
# unrolled body extends buffer live ranges, which OOMed the fused rollout
# at gpt2-xl while the scoring forward still materialized [B, T, V] logits;
# chunked scoring removed that peak, and the re-measured fused cycle now
# WINS unrolled at 48 layers (61.3 -> 71.5 samples/s on v5e — see
# docs/source/performance.rst). Default: unroll up to 48 layers, backing
# off to fori when the runtime reports insufficient HBM headroom for the
# cache's extended live range; TRLX_TPU_DECODE_UNROLL_MAX overrides both.
_UNROLL_MAX_LAYERS = 48


def _per_device_nbytes(leaves) -> "int | None":
    """Best-effort PER-DEVICE footprint of concrete arrays, via their
    shardings' shard shapes. None when any leaf is not inspectable (jit
    tracers carry global shapes and no committed sharding) — callers fall
    back to depth-only heuristics then."""
    total = 0
    for x in leaves:
        sharding = getattr(x, "sharding", None)
        shard_shape = getattr(sharding, "shard_shape", None)
        if shard_shape is None:
            return None
        try:
            total += math.prod(shard_shape(x.shape)) * x.dtype.itemsize
        except Exception:
            return None
    return total


def _use_unrolled_layers(
    n_layers: int, static_bytes: int, bytes_are_per_device: bool = True
) -> bool:
    """Whether the decode body unrolls the layer loop.

    `static_bytes`: weights + 2x KV cache, computed from shapes at trace
    time — deliberately STATIC so the decision is deterministic for a
    given (config, device type). Consulting live allocator state here
    would bake whatever happened to be resident at first trace into the
    compiled program: non-reproducible perf, and under multi-host SPMD
    two hosts could compile different programs (different collective
    sequences -> hang). bytes_limit is a hardware constant, identical
    across same-generation hosts, so comparing the static estimate
    against it is multi-host safe; the CPU backend reports no stats and
    just uses the depth ceiling.

    `bytes_are_per_device`: False when the caller could only compute a
    GLOBAL estimate under a multi-device mesh (jit tracers hide the
    param sharding) — then the comparison against per-device bytes_limit
    would wrongly force fori for models that fit fine per chip, so the
    depth ceiling governs. Callers that CAN resolve per-device bytes
    (eager arrays — including pure-dp replication, where per-device
    equals global) keep the HBM-headroom backoff."""
    env = os.environ.get("TRLX_TPU_DECODE_UNROLL_MAX")
    if env is not None:
        return n_layers <= int(env)
    if n_layers > _UNROLL_MAX_LAYERS:
        return False
    if jax.device_count() > 1 and not bytes_are_per_device:
        return True
    limit = (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit")
    return not (limit and static_bytes > 0.9 * limit)


def decide_unroll(spec: ModelSpec, weight_params, batch_size: int,
                  seq_len: int, cache_dtype=jnp.bfloat16) -> bool:
    """Decode-unroll decision computed EAGERLY, for callers that jit
    generate(): inside jit the weights are tracers with global shapes and
    no shardings, so the per-device HBM backoff cannot engage at trace
    time. Trainers call this once at build time on the concrete param
    tree and pass the result through ``generate(..., unroll_layers=...)``.

    `weight_params` may be the whole param tree — including branches
    decode never touches (ref branch, value heads) — the slight
    overestimate only errs toward the safer fori fallback. The cache
    estimate stays global (unscaled by batch sharding): same direction."""
    leaves = [
        x for x in jax.tree_util.tree_leaves(weight_params)
        if hasattr(x, "dtype")
    ]
    cache_bytes = (
        2 * spec.n_layer * batch_size * seq_len * spec.kv_heads
        * spec.head_dim * jnp.dtype(cache_dtype).itemsize
    )
    per_device = _per_device_nbytes(leaves)
    if per_device is not None:
        return _use_unrolled_layers(spec.n_layer,
                                    per_device + 2 * cache_bytes)
    return _use_unrolled_layers(
        spec.n_layer, tree_bytes(leaves) + 2 * cache_bytes,
        bytes_are_per_device=jax.device_count() == 1,
    )


def _sampling_key(rng: jax.Array) -> jax.Array:
    """The caller's PRNG key converted to the `rbg` implementation for the
    decode loop's per-step draws.

    XLA lowers rbg to the TPU's hardware RngBitGenerator; threefry runs as
    software kernels whose [B, V] gumbel bits measurably tax every step
    (v5e, gpt2-124M [B=128, V=50257]: 1.37 -> 1.22 ms/step), and rbg also
    partitions cleanly under pjit where threefry forms a bottleneck. The
    same seed produces a DIFFERENT stream than threefry would — the
    sampling stream was never a stability contract (determinism per seed
    is preserved); the sampled distribution is identical."""
    if jnp.issubdtype(rng.dtype, jnp.unsignedinteger):
        data = rng  # raw uint32 key data (jax.random.PRNGKey style)
    else:
        if str(jax.random.key_impl(rng)) != "threefry2x32":
            return rng  # already rbg/custom — respect the caller's choice
        data = jax.random.key_data(rng)
    # rbg keys are 4 uint32 words; threefry keys are 2. Raw 4-word data is
    # already rbg-shaped — wrap as-is (tiling it to 8 would make
    # wrap_key_data raise). Any other width is not a key we know how to
    # convert; leave the sampling stream to the caller's implementation.
    if data.shape[-1] == 4:
        return jax.random.wrap_key_data(data, impl="rbg")
    if data.shape[-1] != 2:
        return rng
    return jax.random.wrap_key_data(jnp.tile(data, 2), impl="rbg")


class GenerationConfig(NamedTuple):
    """Static generation settings (hashable, jit-cache friendly).

    Mirrors the reference gen_kwargs contract
    (reference: trlx/data/method_configs.py:74 `gen_kwargs`):
    fixed `gen_size` new tokens; sampling per SamplingParams; eos handling.
    """

    gen_size: int
    sampling: SamplingParams = SamplingParams()
    eos_token_id: int = -1  # -1 disables eos termination
    pad_token_id: int = 0
    min_new_tokens: int = 0  # eos suppressed before this many tokens

    @classmethod
    def from_gen_kwargs(cls, gen_size: int, gen_kwargs: dict, eos_token_id=-1,
                        pad_token_id=0, prompt_len: int = 0) -> "GenerationConfig":
        """Translate reference-style gen_kwargs (max_length/min_length/top_k/
        top_p/do_sample/temperature) into a GenerationConfig.

        HF's min_length counts prompt + generated tokens, so min_new_tokens
        = min_length - prompt_len. The reference configs pin min_length ==
        max_length (configs/ppo_config.yml:48-49), which means fixed-length
        generation — translated as min_new_tokens == gen_size (eos fully
        suppressed).

        An explicit HF-style ``max_new_tokens`` (what serving clients
        pass) overrides ``gen_size`` — the `gen_size` argument then acts
        as the compiled ceiling (the trainer's configured length / the
        serve bucket's gen extent), and exceeding it raises instead of
        silently truncating or recompiling."""
        max_new = gen_kwargs.get("max_new_tokens")
        if max_new is not None:
            max_new = int(max_new)
            if max_new <= 0:
                raise ValueError(
                    f"gen_kwargs max_new_tokens={max_new} must be >= 1"
                )
            if max_new > gen_size:
                raise ValueError(
                    f"gen_kwargs max_new_tokens={max_new} exceeds the "
                    f"compiled generation length (gen_size / serve bucket "
                    f"gen extent) of {gen_size}; raise train.gen_size or "
                    f"add a larger serve bucket instead of asking one "
                    f"program for more tokens than it was compiled for"
                )
            gen_size = max_new
        min_len = int(gen_kwargs.get("min_length", 0) or 0)
        max_len = int(gen_kwargs.get("max_length", 0) or 0)
        if min_len and min_len >= max_len:
            min_new = gen_size
        else:
            min_new = max(0, min(min_len - prompt_len, gen_size))
        return cls(
            gen_size=gen_size,
            sampling=SamplingParams(
                temperature=float(gen_kwargs.get("temperature", 1.0)),
                top_k=int(gen_kwargs.get("top_k", 0) or 0),
                top_p=float(gen_kwargs.get("top_p", 1.0)),
                do_sample=bool(gen_kwargs.get("do_sample", True)),
            ),
            eos_token_id=eos_token_id,
            pad_token_id=pad_token_id,
            min_new_tokens=min_new,
        )


class GenerationOutput(NamedTuple):
    sequences: jnp.ndarray  # [B, P+G] prompt ++ generated (pads after eos)
    gen_tokens: jnp.ndarray  # [B, G]
    gen_logprobs: jnp.ndarray  # [B, G] logprob of emitted token (unwarped dist)
    gen_mask: jnp.ndarray  # [B, G] 1 while not finished (includes eos token)
    attention_mask: jnp.ndarray  # [B, P+G] prompt mask ++ ones


def generate(
    spec: ModelSpec,
    blocks: Params,
    embed: Params,
    ln_f: Params,
    prompt_tokens: jnp.ndarray,
    prompt_mask: jnp.ndarray,
    rng: jax.Array,
    config: GenerationConfig,
    compute_dtype=jnp.bfloat16,
    cache_dtype=jnp.bfloat16,
    extras_fn: Optional[Callable] = None,
    attention_fn=attention_scores,
    logit_mask: Optional[jnp.ndarray] = None,
    unroll_layers: Optional[bool] = None,
) -> GenerationOutput:
    """Sample `config.gen_size` tokens per row from a left-padded prompt.

    blocks: stacked [L, ...] live-policy blocks — either ONE stacked tree
    or a tuple/list of stacked SEGMENTS run in order (the hydra policies
    pass (frozen bottom, trainable top): concatenating them into one
    stack inside a jitted program materializes a full copy of the trunk
    as an HLO temp — ~10 GB at gpt-j-6B, the difference between fitting
    and OOMing on one chip). embed/ln_f: head params.
    Everything inside is static-shape; wrap in jit (or pjit via the trainer).

    `logit_mask`: optional [V] (or [B, V]) boolean array; False entries are
    excluded from sampling at every step. For the reference's per-previous-
    token edge restriction ([V, V], examples/ilql_randomwalks.py:72) use
    `extras_fn`, which receives (h_normed [B, D], logits [B, V],
    prev_token [B]) and returns adjusted logits.
    """
    B, P = prompt_tokens.shape
    G = config.gen_size
    S = P + G
    if _mixed_layers(spec):
        raise NotImplementedError(
            f"generate() runs every layer over one contiguous cache; arch "
            f"'{spec.arch}' mixes {spec.layer_pattern} layers and is served "
            f"through the paged slot pool (trlx_tpu.serve.slots)"
        )
    require_supported(spec, rollout_cache="contiguous")
    if S > spec.n_positions:
        raise ValueError(
            f"prompt ({P}) + gen_size ({G}) = {S} exceeds the model's "
            f"n_positions ({spec.n_positions})"
        )
    segments = tuple(blocks) if isinstance(blocks, (list, tuple)) \
        else (blocks,)
    seg_sizes = [
        jax.tree_util.tree_leaves(s)[0].shape[0] for s in segments
    ]
    n_layers = sum(seg_sizes)

    rng = _sampling_key(rng)
    prompt_mask = prompt_mask.astype(jnp.int32)
    real_len = prompt_mask.sum(axis=-1)  # [B]

    # --- prefill ---------------------------------------------------------
    # the KV cache is a LIST of per-segment stacked (k, v) buffers —
    # never one concatenated [L, ...] stack: re-assembling segment slices
    # costs a full cache copy in HLO temps per program (~2 GB at gpt2-xl
    # b128), for buffers only this function ever reads
    cache_segs = [
        init_kv_cache(spec, size, B, S, cache_dtype) for size in seg_sizes
    ]
    positions = positions_from_mask(prompt_mask)
    h = embed_tokens(embed, spec, prompt_tokens, positions, compute_dtype)
    # [B, 1, P, S] bias: causal over prompt slots, pad keys excluded, future
    # (generation) slots excluded.
    prefill_bias = jnp.concatenate(
        [
            causal_mask_bias(prompt_mask),
            jnp.full((B, 1, P, G), NEG_INF, jnp.float32),
        ],
        axis=-1,
    )
    for i, seg in enumerate(segments):
        h, cache_segs[i] = apply_blocks_with_cache(
            seg, cache_segs[i], spec, h, prefill_bias, positions,
            cache_offset=jnp.int32(0), attention_fn=attention_fn,
        )
    h_last = layer_norm(ln_f, h[:, -1:], spec.layer_norm_epsilon)
    logits0 = project_logits(embed, spec, h_last)[:, 0]  # [B, V]

    buffer_mask = jnp.concatenate(
        [prompt_mask, jnp.ones((B, G), jnp.int32)], axis=-1
    )  # [B, S] validity of each cache slot once written
    slot_idx = jnp.arange(S)

    # -- decode scan ------------------------------------------------------
    flags = ArchFlags.for_spec(spec)
    cache_bytes = (
        2 * n_layers * B * S * spec.kv_heads * spec.head_dim
        * jnp.dtype(cache_dtype).itemsize
    )
    # `unroll_layers` not passed: decide here. Callers that jit this
    # function should pass decide_unroll's eager verdict instead — under a
    # jit trace the weights below are tracers and the per-device branch
    # can't engage.
    if unroll_layers is None:
        weight_leaves = jax.tree_util.tree_leaves((blocks, embed))
        per_device_weights = _per_device_nbytes(weight_leaves)
        if per_device_weights is not None:
            # Eager arrays: real per-device weight footprint (replicated
            # params — e.g. pure dp — come out equal to global, so
            # near-limit models still back off to fori). The cache is
            # created inside this program and inherits the batch sharding;
            # scale its estimate by the prompt's per-device batch fraction
            # when that too is inspectable.
            batch_scale = 1.0
            per_device_prompt = _per_device_nbytes([prompt_tokens])
            if per_device_prompt is not None and prompt_tokens.size:
                batch_scale = per_device_prompt / (
                    prompt_tokens.size * prompt_tokens.dtype.itemsize
                )
            unroll_layers = _use_unrolled_layers(
                n_layers,
                per_device_weights + 2 * int(cache_bytes * batch_scale),
            )
        else:
            unroll_layers = _use_unrolled_layers(
                n_layers, tree_bytes(weight_leaves) + 2 * cache_bytes,
                bytes_are_per_device=jax.device_count() == 1,
            )

    def run_layers(cache, h, bias, pos, offset):
        """One token through all blocks with IN-PLACE cache updates.

        `cache` is either a tuple of per-layer (k, v) pairs (unrolled path)
        or the stacked (k, v) buffers (fori path) — both are scan-carry
        leaves, so XLA aliases the update instead of re-materializing."""
        if unroll_layers:
            # cache: flat tuple of per-layer (k, v) pairs (scan-carry
            # leaves, aliased in place)
            new_cache = []
            layer = 0
            for seg, size in zip(segments, seg_sizes):
                for i in range(size):
                    p_i = jax.tree_util.tree_map(lambda x: x[i], seg)
                    h, kv = block_apply(
                        spec, flags, p_i, h, bias, pos,
                        kv_cache=cache[layer], cache_offset=offset,
                        attention_fn=attention_fn,
                    )
                    new_cache.append(kv)
                    layer += 1
            return tuple(new_cache), h

        # fori path: cache is a tuple of per-segment stacked (k, v)
        # buffers; one fori_loop per segment (usually 1-2) with LOCAL
        # indices on its own buffers
        new_cache = []
        for seg, size, (k_c, v_c) in zip(segments, seg_sizes, cache):

            def layer_body(i, state, seg=seg):
                h, k_c, v_c = state
                p_i = jax.tree_util.tree_map(
                    lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, False),
                    seg,
                )
                h, (k_new, v_new) = block_apply(
                    spec, flags, p_i, h, bias, pos,
                    kv_cache=(k_c[i], v_c[i]), cache_offset=offset,
                    attention_fn=attention_fn,
                )
                k_c = jax.lax.dynamic_update_index_in_dim(k_c, k_new, i, 0)
                v_c = jax.lax.dynamic_update_index_in_dim(v_c, v_new, i, 0)
                return (h, k_c, v_c)

            h, k_c, v_c = jax.lax.fori_loop(
                0, size, layer_body, (h, k_c, v_c)
            )
            new_cache.append((k_c, v_c))
        return tuple(new_cache), h

    def live_step(carry, step):
        cache, logits, h_prev_normed, prev_tok, finished, rng = carry
        rng, key = jax.random.split(rng)
        step_logits = logits
        if extras_fn is not None:
            step_logits = extras_fn(h_prev_normed, step_logits, prev_tok)
        if logit_mask is not None:
            step_logits = jnp.where(logit_mask, step_logits, NEG_INF)
        if config.eos_token_id >= 0 and config.min_new_tokens > 0:
            suppress = step < config.min_new_tokens
            eos_col = step_logits[:, config.eos_token_id]
            step_logits = step_logits.at[:, config.eos_token_id].set(
                jnp.where(suppress, NEG_INF, eos_col)
            )
        # the normalized [B, V] distribution is never materialized: every
        # warper and categorical() itself is invariant to the per-row
        # logsumexp shift, so the draw runs on the raw logits and the
        # recorded (unwarped) logprob is gather(logits, tok) - logsumexp —
        # a fused reduction instead of a full-vocab log_softmax write+read
        logz = jax.nn.logsumexp(step_logits, axis=-1)
        tok = sample_token(key, step_logits, config.sampling)
        logprob = jnp.take_along_axis(
            step_logits, tok[:, None], axis=-1
        )[:, 0] - logz
        tok = jnp.where(finished, jnp.int32(config.pad_token_id), tok)
        logprob = jnp.where(finished, 0.0, logprob)
        emitted_mask = ~finished
        if config.eos_token_id >= 0:
            finished = finished | (tok == config.eos_token_id)

        # one-token forward against the cache
        offset = P + step
        pos = (real_len + step)[:, None]  # [B, 1] logical position
        h = embed_tokens(embed, spec, tok[:, None], pos, compute_dtype)
        key_valid = (slot_idx[None, :] <= offset) & (buffer_mask > 0)
        bias = jnp.where(key_valid, 0.0, NEG_INF)[:, None, None, :].astype(
            jnp.float32
        )
        cache, h = run_layers(cache, h, bias, pos, offset)
        h_normed = layer_norm(ln_f, h, spec.layer_norm_epsilon)
        next_logits = project_logits(embed, spec, h_normed)[:, 0]
        carry = (cache, next_logits, h_normed[:, 0], tok, finished, rng)
        return carry, (tok, logprob, emitted_mask)

    # EOS early-exit: when termination is possible before gen_size (eos
    # enabled and not fully suppressed), guard the heavy body with a
    # scalar cond on finished.all() — a batch that has fully terminated
    # pays a cheap pass-through step instead of a full forward. The
    # fixed-length training configs (min_new_tokens == gen_size) keep the
    # plain scan: the guard could never fire before the last step.
    early_exit = (
        _EOS_EARLY_EXIT
        and config.eos_token_id >= 0
        and config.min_new_tokens < G
    )

    def decode_body(carry, step):
        if not early_exit:
            return live_step(carry, step)

        def dead(args):
            carry, _ = args
            pad = jnp.full((B,), config.pad_token_id, jnp.int32)
            return carry, (
                pad, jnp.zeros((B,), jnp.float32), jnp.zeros((B,), bool)
            )

        def live(args):
            return live_step(*args)

        return jax.lax.cond(carry[4].all(), dead, live, (carry, step))

    if unroll_layers:
        # stacked per-segment prefill buffers -> flat per-layer carry
        # leaves
        decode_cache = tuple(
            (k[i], v[i])
            for (k, v), size in zip(cache_segs, seg_sizes)
            for i in range(size)
        )
    else:
        decode_cache = tuple(cache_segs)
    h0_normed = h_last[:, 0]
    finished0 = jnp.zeros((B,), bool)
    # last real prompt token per row (left padding aware)
    last_prompt_tok = jnp.take_along_axis(
        prompt_tokens, jnp.maximum(real_len - 1, 0)[:, None], axis=1
    )[:, 0]
    carry0 = (decode_cache, logits0, h0_normed, last_prompt_tok, finished0, rng)
    _, (gen_tokens, gen_logprobs, gen_mask) = jax.lax.scan(
        decode_body, carry0, jnp.arange(G)
    )
    gen_tokens = gen_tokens.T  # [B, G]
    gen_logprobs = gen_logprobs.T
    gen_mask = gen_mask.T.astype(jnp.int32)

    sequences = jnp.concatenate([prompt_tokens, gen_tokens], axis=-1)
    return GenerationOutput(
        sequences=sequences,
        gen_tokens=gen_tokens,
        gen_logprobs=gen_logprobs,
        gen_mask=gen_mask,
        attention_mask=buffer_mask,
    )


# ---------------------------------------------------------------------------
# Slot-pool decode primitives (continuous batching)
# ---------------------------------------------------------------------------
#
# generate() above is REQUEST-TO-COMPLETION: one program owns its KV cache
# from prefill through all gen_size steps, so a batch admits nothing until
# every row is done and a finished row keeps paying full steps. The two
# primitives below split that monolith for iteration-level scheduling
# (Orca, Yu et al., OSDI '22) over a PERSISTENT device-resident page pool
# (the static-shape rebuild of vLLM's PagedAttention allocator, Kwon et
# al., SOSP '23):
#
# - ``prefill_into_slots``: one prompt-bucket forward writing each row's
#   prompt KV into the pages its table names (scatter, ``mode="drop"`` so
#   filler rows aimed at the out-of-bounds sentinel vanish) plus its
#   first-step logits and per-slot lanes;
# - ``decode_step``: ONE token for all S slots — per-slot cache offsets,
#   logical positions, finished/active lanes, per-request max_new caps —
#   returning the emitted tokens to the host scheduler
#   (trlx_tpu.serve.slots), which harvests finished rows and re-admits
#   queued requests into the freed slots at every step boundary.
#
# Both are meant to be AOT-compiled once per shape (the pool/state shapes
# are static; ``prefill`` per (batch, prompt_len) bucket, ``decode_step``
# once) with the pool+state donated, so steady state is two executables
# and zero recompiles. Numerics match generate() exactly for a row decoded
# in isolation: masked (invalid) pool positions contribute exact zeros to
# the attention softmax, so emitted tokens are bit-identical under greedy
# decode — the parity contract tests/test_paged.py pins, over a sweep of
# page sizes and prefix splits.
#
# The pool is PAGED (init_page_pool + SlotState.pages page tables,
# block_apply's paged mode): KV lives in fixed-size pages shared across
# slots, a slot's logical position p maps through its table to (page,
# offset), and prefill can start at a nonzero page-aligned offset with
# the committed prefix gathered as attention context
# (prefix_context=True — the radix-prefix-cache path,
# trlx_tpu.serve.paged). Page tables are DATA, not shape, so they cost no
# executable and no recompile.


class SlotState(NamedTuple):
    """Per-slot decode lanes riding next to the KV pool (all leading-S).

    ``valid`` [S, T] marks which pool positions hold real keys (prompt
    pads and never-written tail stay 0 — the attention mask source);
    ``offset`` is the next cache write position, ``pos`` the next rotary/
    logical position (= real tokens so far), ``generated`` the emitted
    count against the per-request ``max_new`` cap. ``active`` is host
    occupancy (False = free slot), ``finished`` terminal-for-decode;
    ``logits`` [S, V] carries each slot's next-token distribution between
    programs (written by prefill, advanced by every step).

    ``pages`` [S, max_pages] int32 is the per-slot page table: entry j
    names the physical pool page holding the slot's logical positions
    [j * page_size, (j+1) * page_size); unallocated entries carry the
    out-of-bounds :data:`PAGE_SENTINEL` so device scatters drop them.
    """

    valid: jnp.ndarray  # [S, T] int32
    offset: jnp.ndarray  # [S] int32
    pos: jnp.ndarray  # [S] int32
    generated: jnp.ndarray  # [S] int32
    max_new: jnp.ndarray  # [S] int32
    active: jnp.ndarray  # [S] bool
    finished: jnp.ndarray  # [S] bool
    logits: jnp.ndarray  # [S, V] float32
    pages: jnp.ndarray  # [S, max_pages] int32


#: page-table entry meaning "no page here": comfortably past any real
#: pool's page count, so every mode="drop" scatter through it vanishes
#: and every read gather clamps into masked garbage
PAGE_SENTINEL = 2**30


def init_slot_state(num_slots: int, buffer_len: int, vocab_size: int,
                    max_pages: int) -> SlotState:
    """An all-free pool state: nothing active, everything finished (so a
    decode step over an empty pool emits nothing), every page-table
    entry at the drop sentinel."""
    S = num_slots
    return SlotState(
        valid=jnp.zeros((S, buffer_len), jnp.int32),
        offset=jnp.zeros((S,), jnp.int32),
        pos=jnp.zeros((S,), jnp.int32),
        generated=jnp.zeros((S,), jnp.int32),
        max_new=jnp.zeros((S,), jnp.int32),
        active=jnp.zeros((S,), bool),
        finished=jnp.ones((S,), bool),
        logits=jnp.zeros((S, vocab_size), jnp.float32),
        pages=jnp.full((S, max_pages), PAGE_SENTINEL, jnp.int32),
    )


# The pool is PER-LAYER LEAVES, never a stacked [L, ...] array: per
# segment (the structure generate() keeps, so hydra policies never
# concatenate their trunk) a tuple of L_seg (k, v) entries. Each leaf is
# its own donated buffer and block_apply's scatter is the only write a
# program makes to it, so XLA aliases input to output and a step writes
# its fresh rows in place. A layer sliced out of a stacked donated array
# is a NEW buffer: the stacked pool made every step slice each layer out,
# scatter into the slice and copy it back (measured on v5e: 46% of a
# gpt-j-6B decode step). tests/test_paged.py pins the structure.


def init_page_pool(spec: ModelSpec, seg_sizes, num_pages,
                   page_size: int, cache_dtype=jnp.bfloat16):
    """PAGE pool: per segment, per layer, (k, v) pages [num_pages,
    page_size, Hkv, hd]: HBM is sized in pages shared by all slots, not
    slots x worst-case length. The int8 tier makes each of k/v a ``(codes,
    scales)`` pair (transformer.init_paged_kv_cache). A latent-attention
    model's entry is ONE array of latent pages [num_pages, page_size,
    latent_page_width]: there is no V buffer.

    ``num_pages`` is one count, or ``{class: count}`` for a model whose
    layers differ: every layer of a kind keeps ``num_pages[kind]`` pages,
    and all layers of a kind are addressed through one table (a page id
    of the window class names that page in every window layer)."""
    def pages_of(layer):
        if isinstance(num_pages, dict):
            return num_pages[spec.layer_kind(layer)]
        return num_pages

    first = [sum(seg_sizes[:i]) for i in range(len(seg_sizes))]
    return tuple(
        tuple(
            init_paged_kv_cache(spec, pages_of(lo + i), page_size,
                                cache_dtype)
            for i in range(size)
        )
        for lo, size in zip(first, seg_sizes)
    )


def _segments_of(blocks):
    segments = tuple(blocks) if isinstance(blocks, (list, tuple)) \
        else (blocks,)
    seg_sizes = [
        jax.tree_util.tree_leaves(s)[0].shape[0] for s in segments
    ]
    return segments, seg_sizes


def _pool_page_geometry(pool):
    """(num_pages, page_size) of a page pool in either KV tier."""
    pages = jax.tree_util.tree_leaves(pool)[0]  # layer 0's k pages/codes
    return pages.shape[0], pages.shape[1]


#: the short name of a layer's kind in a named scope (``layer3.full/attn``)
KIND_TAG = {"full": "full", "window": "win"}


def _apply_layers_with_pool(spec, segments, seg_sizes, pool, h,
                            by_kind=None, **block_kw):
    """The serve programs' unrolled layer loop: layer ``n`` reads and
    writes its own pool leaves ``pool[seg][i]`` through block_apply
    (whose ``kv_cache`` is one layer's (k, v)) and nothing else touches
    them. Returns (new_pool, h).

    ``by_kind`` ({"full": kwargs, "window": kwargs}) gives each layer the
    arguments of ITS kind (its class of page table, its mask or reader)
    over the shared ``block_kw``; the layer's kind then also stands in
    its scope (``layer2.win``), and whether it rotates follows
    ``ModelSpec.rope_kinds``. Without it every layer is the same, as the
    dense families' are."""
    flags = ArchFlags.for_spec(spec)
    new_pool, layer = [], 0
    for seg, size, seg_pool in zip(segments, seg_sizes, pool):
        new_seg = []
        for i in range(size):
            scope, kw = f"layer{layer}", block_kw
            if by_kind is not None:
                kind = spec.layer_kind(layer)
                scope = f"{scope}.{KIND_TAG[kind]}"
                kw = {**block_kw, **by_kind[kind],
                      "use_rope": rope_of(spec, flags, layer)}
            with jax.named_scope(scope):
                p_i = jax.tree_util.tree_map(lambda x, i=i: x[i], seg)
                h, kv = block_apply(
                    spec, flags, p_i, h, kv_cache=seg_pool[i], **kw
                )
            new_seg.append(kv)
            layer += 1
        new_pool.append(tuple(new_seg))
    return tuple(new_pool), h


def paged_context_attention(q, k_pages, v_pages, table, page_base, q_pos,
                            window: int, page_size: int,
                            pages_per_block: int = 8):
    """Attention of q [B, P, H, hd] (logical positions ``q_pos`` [B, P])
    against the keys a page table holds, in blocks of ``pages_per_block``
    pages with a running softmax: the float32 scores of a 1,024-token
    chunk against a 29k context are 15 GB and are never formed; a block's
    are [B, H, P, pages_per_block * page_size]. Table entry ``i`` of row
    ``b`` holds logical page ``page_base[b] + i`` (``page_base`` None: 0).
    Key position ``kp`` is seen by query position ``qp`` iff ``kp <= qp``
    and, under ``window`` > 0, ``kp > qp - window``; an entry at the
    sentinel is seen by nobody. The loop stops at the last block any
    query reaches, so a chunk early in a long prompt pays for the context
    it has. Grouped-query heads run against the compact K/V."""
    B, P, H, hd = q.shape
    num_pages, ps, Hkv, _ = k_pages.shape
    if ps != page_size:
        raise ValueError(f"pool page size {ps} != page_size {page_size}")
    G = H // Hkv
    ppb = pages_per_block
    n_entries = table.shape[1]
    n_blocks = -(-n_entries // ppb)
    table = jnp.pad(table, ((0, 0), (0, n_blocks * ppb - n_entries)),
                    constant_values=num_pages)
    base = jnp.zeros((B,), jnp.int32) if page_base is None else page_base
    q5 = q.reshape(B, P, Hkv, G, hd)
    scale = jax.lax.rsqrt(jnp.float32(hd))
    # the last table entry any query reaches, in blocks
    reach = jnp.max(q_pos // page_size - base[:, None]) + 1
    hi = jnp.clip(-(-reach // ppb), 1, n_blocks)
    qp = q_pos[:, None, None, :, None]  # [B, 1, 1, P, 1]

    def body(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, i * ppb, ppb, axis=1)
        live = ids < num_pages  # [B, ppb]
        ids = jnp.where(live, ids, 0)
        k = k_pages[ids].reshape(B, ppb * ps, Hkv, hd).astype(q.dtype)
        v = v_pages[ids].reshape(B, ppb * ps, Hkv, hd).astype(q.dtype)
        page = base[:, None] + i * ppb + jnp.arange(ppb)[None, :]
        kp = (page[:, :, None] * ps + jnp.arange(ps)[None, None, :])
        kp = kp.reshape(B, 1, 1, 1, ppb * ps)
        seen = (kp <= qp) & jnp.repeat(live, ps, axis=1)[:, None, None, None]
        if window > 0:
            seen = seen & (kp > qp - window)
        s = jnp.einsum("bphgd,bkhd->bhgpk", q5, k).astype(jnp.float32)
        s = jnp.where(seen, s * scale, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        probs = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        l = alpha * l + probs.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgpk,bkhd->bhgpd", probs.astype(v.dtype), v
        ).astype(jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((B, Hkv, G, P), 2.0 * NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, P), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, P, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, Hkv, G, P, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, P, H, hd).astype(q.dtype)


def moe_stats_array(stats):
    """[L, 4] float32 (pairs_here, experts_hit, load_max, load_mean) from
    the per-layer tuples block_apply collected; None for a dense model."""
    if not stats:
        return None
    return jnp.stack([jnp.stack([jnp.asarray(x, jnp.float32) for x in s])
                      for s in stats])


def prefill_into_slots(
    spec: ModelSpec,
    blocks: Params,
    embed: Params,
    ln_f: Params,
    pool,
    state: SlotState,
    prompt_tokens: jnp.ndarray,  # [Bp, P] RIGHT-padded
    prompt_mask: jnp.ndarray,  # [Bp, P]
    slot_ids: jnp.ndarray,  # [Bp] int32; == num_slots -> dropped filler
    max_new: jnp.ndarray,  # [Bp] int32 per-request cap
    page_tables: jnp.ndarray,  # [Bp, max_pages] int32
    page_size: int,
    compute_dtype=jnp.bfloat16,
    attention_fn=attention_scores,
    start: Optional[jnp.ndarray] = None,  # [Bp] int32 page-aligned prefix
    prefix_context: bool = False,
    window_tables: Optional[jnp.ndarray] = None,  # [Bp, Rp] int32
    window_base: Optional[jnp.ndarray] = None,  # [Bp] int32 logical page
):
    """Write a prompt bucket's KV + first-step logits into the page pool:
    suffix forward + block-scatter through per-row ``page_tables``; state
    rows (valid/offset/pos/pages/logits) scattered to ``slot_ids``.

    Filler rows carry ``slot_ids == num_slots`` (one past the end) and
    sentinel page tables: every scatter here uses ``mode="drop"``, so
    they compile the bucket shape without touching any real slot or page
    — which is also how warmup compiles each bucket against the live
    pool for free.

    ``prompt_tokens`` / ``prompt_mask`` are RIGHT-padded — under right
    padding a slot's buffer position equals its logical token position,
    so two requests sharing a token prefix share identical page CONTENT,
    which is what makes radix prefix caching content-addressable (KV of
    a causal model depends only on the tokens before it, not on pad
    placement; masked pad positions contribute exactly zero either way,
    so greedy outputs stay bit-identical to one-shot left-padded
    ``generate()``).

    ``start`` ([Bp] int32, page-aligned, default zeros) is each row's
    already-committed prefix length: the tokens passed in are only the
    UNMATCHED SUFFIX (right-padded into the bucket's [Bp, P] shape) and
    are written at logical positions ``start + j``. With
    ``prefix_context=True`` the suffix attends to the committed prefix
    pages gathered from the pool (the ``prefill_suffix`` executable — a
    prefix hit skips the matched tokens' forward entirely); with
    ``False`` (all-zero ``start``) attention stays local to the prompt
    (the exact prefill generate() runs, in a local [Bp, P] cache buffer
    at offset 0), which is cheaper.

    A model with window layers keeps two classes of page and is always
    prefilled with ``prefix_context=True``: ``window_tables`` [Bp, Rp]
    maps, for the window class, logical pages ``window_base[b] + i``
    (the pages the suffix writes and the ``window`` positions before it),
    and attention runs in blocks against the pool
    (:func:`paged_context_attention`), the window cut in its mask. One
    such call is also one CHUNK of a long prompt: a chunk that is not the
    last carries the sentinel in ``slot_ids`` and leaves only its pages
    behind. Returns ``(pool, state, moe_stats [L, 4])`` for such a model.
    """
    B, P = prompt_tokens.shape
    T = state.valid.shape[1]
    if P > T:
        raise ValueError(
            f"prefill prompt_len {P} exceeds the slot buffer length {T}"
        )
    segments, seg_sizes = _segments_of(blocks)
    prompt_mask = prompt_mask.astype(jnp.int32)
    if page_size <= 0:
        raise ValueError(f"prefill needs a page_size >= 1, got {page_size}")
    max_pages = page_tables.shape[1]
    if max_pages * page_size != T:
        raise ValueError(
            f"page table extent {max_pages} x {page_size} != slot buffer "
            f"length {T}"
        )
    suffix_len = prompt_mask.sum(axis=-1)  # [Bp] real (unmatched) tokens
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    start = start.astype(jnp.int32)
    real_len = start + suffix_len  # [Bp] total committed positions after
    # right padding: suffix token j sits at logical position start + j
    positions = start[:, None] + jnp.arange(P)[None, :]
    h = embed_tokens(embed, spec, prompt_tokens, positions, compute_dtype)

    # by the first leaf, not by pool[0][0]: a segment may hold no layer
    # (every block trainable leaves the frozen trunk empty)
    pool_dtype = jax.tree_util.tree_leaves(pool)[0].dtype
    quantized = pool_dtype == jnp.int8
    moe_stats = []
    if _mixed_layers(spec):
        # two classes of page: each kind of layer writes through its own
        # table and reads it back in blocks, the window cut in the mask
        if not prefix_context or window_tables is None:
            raise ValueError(
                "a model with window layers is prefilled through the "
                "prefix-context program with its window-class tables"
            )
        attend = functools.partial(
            paged_context_attention, q_pos=positions, page_size=page_size
        )
        new_pool, h = _apply_layers_with_pool(
            spec, segments, seg_sizes, pool, h,
            by_kind={
                "full": dict(
                    page_table=page_tables,
                    paged_attend_fn=functools.partial(
                        attend, table=page_tables, page_base=None, window=0
                    ),
                ),
                "window": dict(
                    page_table=window_tables, page_base=window_base,
                    paged_attend_fn=functools.partial(
                        attend, table=window_tables,
                        page_base=window_base, window=spec.window,
                    ),
                ),
            },
            mask_bias=None, positions=positions, cache_row_offsets=start,
            page_size=page_size, attention_fn=attention_fn,
            token_mask=prompt_mask > 0, moe_stats=moe_stats,
        )
    elif spec.kv_lora_rank:
        # latent pages: every layer writes its latents through the one
        # table and reads them back in blocks (latent.attend_pages), in
        # the order the static chunk length picks
        if not prefix_context:
            raise ValueError(
                "a latent-attention model is prefilled through the "
                "prefix-context program (there is no local K/V buffer)"
            )
        new_pool, h = _apply_layers_with_pool(
            spec, segments, seg_sizes, pool, h,
            mask_bias=None, positions=positions, cache_row_offsets=start,
            page_table=page_tables, page_size=page_size,
            token_mask=prompt_mask > 0, moe_stats=moe_stats,
        )
    elif not prefix_context:
        # no committed prefix: local causal prefill (the exact ops
        # generate() runs), then one block-scatter into the pages.
        # int8 tier: the LOCAL buffer stays full-precision in the compute
        # dtype and quantization happens once at the scatter — the same
        # source dtype block_apply's decode-time quantize sees, so page
        # content stays a pure function of token content (radix dedupe).
        cache_dtype = compute_dtype if quantized else pool_dtype
        cache_segs = [
            init_kv_cache(spec, size, B, P, cache_dtype)
            for size in seg_sizes
        ]
        bias = causal_mask_bias(prompt_mask)
        for i, seg in enumerate(segments):
            h, cache_segs[i] = apply_blocks_with_cache(
                seg, cache_segs[i], spec, h, bias, positions,
                cache_offset=jnp.int32(0), attention_fn=attention_fn,
            )
        pos_buf = jnp.arange(P)
        pids = page_tables[:, pos_buf // page_size]  # [Bp, P]
        ioff = pos_buf % page_size  # [P], broadcasts against pids

        with jax.named_scope("kv_write"):
            new_pool = []
            for seg_pool, fresh in zip(pool, cache_segs):
                if quantized:
                    # (codes [L,Bp,P,Hkv,hd], scales [L,Bp,P,Hkv]) for
                    # each of k, v: the nesting of a pool entry
                    fresh = tuple(quantize_kv(x) for x in fresh)
                # one scatter per layer of that layer's fresh rows
                new_pool.append(tuple(
                    jax.tree_util.tree_map(
                        lambda pages, x, i=i: pages.at[pids, ioff].set(
                            x[i], mode="drop"
                        ),
                        entry, fresh,
                    )
                    for i, entry in enumerate(seg_pool)
                ))
            new_pool = tuple(new_pool)
    else:
        # prefix-suffix prefill: each suffix token attends to the
        # committed prefix pages (gathered inside block_apply's paged
        # mode) plus the suffix tokens written before it — causality over
        # LOGICAL positions: key position p is visible to suffix token j
        # of row b iff p <= start[b] + j. Prefix positions (< start) are
        # whole committed pages, so no extra validity lane is needed;
        # positions past the row's own writes are masked by causality.
        allowed = jnp.arange(T)[None, None, :] <= positions[:, :, None]
        bias = jnp.where(allowed, 0.0, NEG_INF).astype(
            jnp.float32
        )[:, None]  # [Bp, 1, P, T]
        new_pool, h = _apply_layers_with_pool(
            spec, segments, seg_sizes, pool, h,
            mask_bias=bias, positions=positions, cache_row_offsets=start,
            page_table=page_tables, page_size=page_size,
            attention_fn=attention_fn,
        )

    # first-step logits from the last REAL suffix token (right padding:
    # per-row gather, not the shared last column)
    with jax.named_scope("head"):
        last_idx = jnp.maximum(suffix_len - 1, 0)
        h_last = h[jnp.arange(B), last_idx]  # [Bp, D]
        h_normed = layer_norm(ln_f, h_last, spec.layer_norm_epsilon)
        logits0 = project_logits(embed, spec, h_normed)  # [Bp, V]

    rows = slot_ids.astype(jnp.int32)
    valid_rows = (
        jnp.arange(T)[None, :] < real_len[:, None]
    ).astype(jnp.int32)
    new_state = SlotState(
        valid=state.valid.at[rows].set(valid_rows, mode="drop"),
        offset=state.offset.at[rows].set(real_len, mode="drop"),
        pos=state.pos.at[rows].set(real_len, mode="drop"),
        generated=state.generated.at[rows].set(0, mode="drop"),
        max_new=state.max_new.at[rows].set(
            jnp.clip(max_new.astype(jnp.int32), 0, T - real_len),
            mode="drop",
        ),
        active=state.active.at[rows].set(True, mode="drop"),
        finished=state.finished.at[rows].set(False, mode="drop"),
        logits=state.logits.at[rows].set(logits0, mode="drop"),
        pages=state.pages.at[rows].set(
            page_tables.astype(jnp.int32), mode="drop"
        ),
    )
    if moe_stats:
        return new_pool, new_state, moe_stats_array(moe_stats)
    return new_pool, new_state


def verify_step(
    spec: ModelSpec,
    blocks: Params,
    embed: Params,
    ln_f: Params,
    pool,
    state: SlotState,
    seed: jnp.ndarray,  # scalar int32 (per-step sampling stream)
    proposals: jnp.ndarray,  # [S, K] int32 speculated continuation tokens
    n_proposed: jnp.ndarray,  # [S] int32 how many of each row are real
    config: GenerationConfig,
    compute_dtype=jnp.bfloat16,
    attention_fn=attention_scores,
):
    """Speculative-decoding verification: score K proposed tokens per
    slot in ONE batched pass and emit the longest greedy-matching prefix
    plus the free token — ``prefill_suffix`` generalized to the decode
    loop (the speculation tentpole; host side in trlx_tpu.serve.slots).

    Per slot the candidate row is ``[t0, proposals...]`` where ``t0`` is
    the token the slot's CARRIED logits emit (exactly what
    :func:`decode_step` would produce this step — the always-free
    token). All K+1 candidates are forwarded together at logical
    positions ``pos + j``, attending over the committed pool positions
    (``state.valid``) plus the candidates before them — the same
    logical-causality bias the prefix-suffix prefill builds, so the
    per-position logits are bit-identical to K+1 sequential
    ``decode_step`` calls under greedy decode. Proposal ``j`` is
    accepted iff it equals the argmax of the distribution following
    candidate ``j-1`` and every earlier proposal was accepted; the
    emitted run is ``cand[:count]`` (eos truncates it and finishes the
    slot, as does the per-slot ``max_new`` budget).

    Rejected candidates need no KV copy-back: their pool writes landed
    through the slot's OWN reserved pages (never radix-shared — the trie
    only holds whole committed prompt blocks), and the final ``valid``
    lanes mark exactly the accepted positions, so rejected garbage is
    masked now and overwritten when the slot actually reaches those
    positions. Page tables are data, not shape: K is static
    (``serve.spec_k``) and this is ONE executable next to
    ``decode_step``, so ``compile/recompiles == 0`` survives.

    The candidate window may run past the slot buffer for rows near
    their budget end, so the write path runs through a sentinel-extended
    page table — overflow positions drop instead of clamping into the
    last real page. Greedy
    sampling only (the host gates speculation on ``do_sample=False``);
    the jnp attention path only (the pallas decode kernel is T==1).

    Returns ``(pool, state, cand [S, K+1], counts [S], finished [S])``:
    the host appends ``cand[s, :counts[s]]`` per live slot; plain steps
    are the ``counts <= 1`` degenerate case of the same harvest shape.
    """
    S, K = proposals.shape
    Tc = K + 1  # candidates forwarded: the free token + K proposals
    T = state.valid.shape[1]
    segments, seg_sizes = _segments_of(blocks)

    emitting = state.active & ~state.finished
    # clamp proposals to the per-slot budget: t0 spends one token, so at
    # most remaining-1 proposals can ever be accepted
    remaining = jnp.maximum(state.max_new - state.generated, 0)
    n = jnp.minimum(
        jnp.clip(n_proposed.astype(jnp.int32), 0, K),
        jnp.maximum(remaining - 1, 0),
    )
    n = jnp.where(emitting, n, 0)

    # the free token: exactly decode_step's emission from the carried
    # logits (eos suppression mirrored; greedy => argmax either way)
    step_logits = state.logits
    if config.eos_token_id >= 0 and config.min_new_tokens > 0:
        suppress = state.generated < config.min_new_tokens
        eos_col = step_logits[:, config.eos_token_id]
        step_logits = step_logits.at[:, config.eos_token_id].set(
            jnp.where(suppress, NEG_INF, eos_col)
        )
    key = _sampling_key(jax.random.PRNGKey(seed))
    t0 = sample_token(key, step_logits, config.sampling)
    cand = jnp.concatenate(
        [t0[:, None], proposals.astype(jnp.int32)], axis=1
    )  # [S, Tc]
    cand = jnp.where(
        emitting[:, None], cand, jnp.int32(config.pad_token_id)
    ).astype(jnp.int32)

    # logical causality over the buffer: candidate j of row s sees the
    # committed positions (valid lanes) plus candidates 0..j — the
    # prefix-suffix prefill bias with the committed prefix read from
    # valid instead of recomputed from start offsets
    buf = jnp.arange(T)[None, None, :]
    j_idx = jnp.arange(Tc)[None, :, None]
    off = state.offset[:, None, None]
    cand_vis = (
        (buf >= off) & (buf <= off + j_idx)
        & emitting[:, None, None]
    )
    allowed = (state.valid[:, None, :] > 0) | cand_vis
    num_pages, page_size = _pool_page_geometry(pool)
    # sentinel-extend the table so overflow candidate positions (a row
    # near its budget end still WRITES all Tc candidates) drop instead
    # of clamping into the row's last real page; the extra key columns
    # are masked below
    extra = -(-Tc // page_size)
    pt_step = jnp.where(
        emitting[:, None], state.pages, jnp.int32(num_pages)
    )
    pt_v = jnp.concatenate(
        [pt_step, jnp.full((S, extra), num_pages, jnp.int32)], axis=1
    )
    bias = jnp.concatenate(
        [
            jnp.where(allowed, 0.0, NEG_INF),
            jnp.full((S, Tc, extra * page_size), NEG_INF),
        ],
        axis=-1,
    ).astype(jnp.float32)[:, None]  # [S, 1, Tc, T + extra*ps]

    positions = state.pos[:, None] + jnp.arange(Tc)[None, :]  # [S, Tc]
    h = embed_tokens(embed, spec, cand, positions, compute_dtype)
    new_pool, h = _apply_layers_with_pool(
        spec, segments, seg_sizes, pool, h,
        mask_bias=bias, positions=positions,
        cache_row_offsets=state.offset,
        page_table=pt_v, page_size=page_size, attention_fn=attention_fn,
    )
    with jax.named_scope("head"):
        h_normed = layer_norm(ln_f, h, spec.layer_norm_epsilon)
        L = project_logits(embed, spec, h_normed)  # [S, Tc, V]

    # acceptance: proposal j (emitted index j, 1-based over proposals)
    # survives iff it matches the greedy token of the distribution after
    # candidate j-1 AND every earlier proposal survived
    jpos = jnp.arange(1, K + 1)[None, :]  # [1, K] emitted index of prop j
    Lm = L[:, :K]  # [S, K, V]: dist following cand_0..cand_{K-1}
    if config.eos_token_id >= 0 and config.min_new_tokens > 0:
        sup = (state.generated[:, None] + jpos) < config.min_new_tokens
        eos_col = Lm[:, :, config.eos_token_id]
        Lm = Lm.at[:, :, config.eos_token_id].set(
            jnp.where(sup, NEG_INF, eos_col)
        )
    greedy = jnp.argmax(Lm, axis=-1).astype(jnp.int32)  # [S, K]
    match = (proposals.astype(jnp.int32) == greedy) & (jpos <= n[:, None])
    m = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)  # [S]

    # emitted run: cand_0..cand_m, truncated at (and including) the
    # first eos among them; counts gate everything downstream
    i_idx = jnp.arange(Tc)[None, :]
    within = i_idx <= m[:, None]
    if config.eos_token_id >= 0:
        is_eos = (cand == config.eos_token_id) & within
    else:
        is_eos = jnp.zeros_like(within)
    eos_before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) \
        - is_eos.astype(jnp.int32)  # exclusive cumsum: eos count BEFORE i
    emit_mask = within & (eos_before == 0) & emitting[:, None]
    counts = emit_mask.sum(axis=1).astype(jnp.int32)  # [S] in 0..K+1

    finished = state.finished
    if config.eos_token_id >= 0:
        finished = finished | (emitting & (is_eos & emit_mask).any(axis=1))
    generated = state.generated + counts
    finished = finished | (state.active & (generated >= state.max_new))

    # the valid-lane rollback: exactly the accepted candidate positions
    # become valid; rejected writes stay masked and are overwritten when
    # the slot genuinely reaches them
    rows2 = jnp.arange(S)[:, None]
    cols = state.offset[:, None] + jnp.arange(Tc)[None, :]
    valid = state.valid.at[rows2, cols].set(
        emit_mask.astype(jnp.int32), mode="drop"
    )

    # carried logits advance to the distribution after the LAST emitted
    # token — L[s, counts-1] is conditioned on exactly the greedy prefix,
    # so the next step (plain or speculative) resumes bit-identically
    last = jnp.maximum(counts - 1, 0)
    next_logits = L[jnp.arange(S), last]  # [S, V]
    next_logits = jnp.where(
        emitting[:, None], next_logits, state.logits
    )

    new_state = SlotState(
        valid=valid,
        offset=state.offset + counts,
        pos=state.pos + counts,
        generated=generated,
        max_new=state.max_new,
        active=state.active,
        finished=finished,
        logits=next_logits,
        pages=state.pages,
    )
    return new_pool, new_state, cand, counts, finished


def decode_step(
    spec: ModelSpec,
    blocks: Params,
    embed: Params,
    ln_f: Params,
    pool,
    state: SlotState,
    seed: jnp.ndarray,  # scalar int32 (per-step sampling stream)
    config: GenerationConfig,
    compute_dtype=jnp.bfloat16,
    attention_fn=attention_scores,
    paged_decode_fn=None,
    window_table: Optional[jnp.ndarray] = None,  # [S, R] int32 ring
):
    """One decode step for every pool slot: sample from each slot's
    carried logits, forward the sampled tokens against the pool (per-slot
    cache offsets/positions), advance the lanes.

    Returns ``(pool, state, tokens [S], emitted [S], finished [S])`` —
    ``emitted`` marks slots that produced a real token this step (eos
    included), ``finished`` the slots now terminal (eos seen, or
    ``generated`` reached the slot's ``max_new``). Free/finished slots
    still ride the dense [S] program (static shapes) but emit nothing,
    advance nothing, and their dropped cache writes touch no valid
    position — the host scheduler's job is to keep them refilled.

    ``config.gen_size`` is ignored (the cap is per-slot ``max_new``);
    ``min_new_tokens`` applies per slot against its ``generated`` count.

    ``paged_decode_fn`` (``serve.attention: pallas``) is forwarded to
    each layer's ``block_apply`` so the paged gather + score runs as the
    fused kernel; ``None`` keeps the jnp oracle path.

    ``window_table`` [S, R] (a model with window layers; the host owns
    and advances it) is each slot's ring over the window class: logical
    page ``n`` sits at entry ``n % R``. Its validity lane is computed
    here from the slot's write position, so what lies outside the window
    is masked and the attention programs see a short table and no window
    argument. A model with routed experts also returns its routing counts,
    ``moe_stats`` [layers with experts, 4], as a sixth output.
    """
    S = state.offset.shape[0]
    segments, seg_sizes = _segments_of(blocks)

    step_logits = state.logits
    if config.eos_token_id >= 0 and config.min_new_tokens > 0:
        suppress = state.generated < config.min_new_tokens
        eos_col = step_logits[:, config.eos_token_id]
        step_logits = step_logits.at[:, config.eos_token_id].set(
            jnp.where(suppress, NEG_INF, eos_col)
        )
    key = _sampling_key(jax.random.PRNGKey(seed))
    tok = sample_token(key, step_logits, config.sampling)
    emitted = state.active & ~state.finished
    tok = jnp.where(emitted, tok, jnp.int32(config.pad_token_id)).astype(
        jnp.int32
    )
    finished = state.finished
    if config.eos_token_id >= 0:
        finished = finished | (emitted & (tok == config.eos_token_id))
    generated = state.generated + emitted.astype(jnp.int32)
    finished = finished | (state.active & (generated >= state.max_new))

    rows = jnp.arange(S)
    # mark the fresh token's pool position valid BEFORE attention (the
    # token attends to itself, as in generate()'s slot_idx <= offset)
    valid = state.valid.at[rows, state.offset].set(
        emitted.astype(jnp.int32), mode="drop"
    )
    bias = jnp.where(valid > 0, 0.0, NEG_INF)[:, None, None, :].astype(
        jnp.float32
    )
    pos = state.pos[:, None]  # [S, 1] logical position of this token
    h = embed_tokens(embed, spec, tok[:, None], pos, compute_dtype)
    # gate writes through the page table: non-emitting slots (free,
    # finished, or harvested-awaiting-reuse) aim at the sentinel so their
    # scatter drops — a harvested slot's pages may already belong to
    # ANOTHER slot
    num_pages, page_size = _pool_page_geometry(pool)
    pt_step = jnp.where(
        emitted[:, None], state.pages, jnp.int32(num_pages)
    )
    moe_stats = []
    if _mixed_layers(spec):
        if window_table is None:
            raise ValueError(
                "a model with window layers decodes with its "
                "window-class ring table"
            )
        R = window_table.shape[1]
        # ring entry r, offset o holds the newest position p <= cur of
        # page r (mod R); it is seen iff it lies inside the window
        cur = state.offset[:, None]  # [S, 1] the position written now
        j = jnp.arange(R * page_size)[None, :]
        cur_page = cur // page_size
        page = cur_page - (cur_page - j // page_size) % R
        p = page * page_size + j % page_size
        seen = (p >= 0) & (p <= cur) & (p > cur - spec.window) \
            & emitted[:, None]
        wbias = jnp.where(seen, 0.0, NEG_INF)[:, None, None, :].astype(
            jnp.float32
        )
        new_pool, h = _apply_layers_with_pool(
            spec, segments, seg_sizes, pool, h,
            by_kind={
                "full": dict(mask_bias=bias, page_table=pt_step),
                "window": dict(
                    mask_bias=wbias, ring=True,
                    page_table=jnp.where(
                        emitted[:, None], window_table, PAGE_SENTINEL
                    ),
                ),
            },
            positions=pos, cache_row_offsets=state.offset,
            page_size=page_size, attention_fn=attention_fn,
            paged_decode_fn=paged_decode_fn,
            token_mask=emitted[:, None], moe_stats=moe_stats,
        )
    else:
        new_pool, h = _apply_layers_with_pool(
            spec, segments, seg_sizes, pool, h,
            mask_bias=bias, positions=pos, cache_row_offsets=state.offset,
            page_table=pt_step, page_size=page_size,
            attention_fn=attention_fn, paged_decode_fn=paged_decode_fn,
            token_mask=emitted[:, None], moe_stats=moe_stats,
        )
    with jax.named_scope("head"):
        h_normed = layer_norm(ln_f, h, spec.layer_norm_epsilon)
        next_logits = project_logits(embed, spec, h_normed)[:, 0]  # [S, V]

    adv = emitted.astype(jnp.int32)
    new_state = SlotState(
        valid=valid,
        offset=state.offset + adv,
        pos=state.pos + adv,
        generated=generated,
        max_new=state.max_new,
        active=state.active,
        finished=finished,
        logits=next_logits,
        pages=state.pages,
    )
    if moe_stats:
        return (new_pool, new_state, tok, emitted, finished,
                moe_stats_array(moe_stats))
    return new_pool, new_state, tok, emitted, finished
