"""Functional GPT-family transformer trunk.

TPU-first design, replacing the reference's HF torch modules (reference:
trlx/model/nn/ppo_models.py:41-300 wraps transformers GPT2/GPT-J):

- Parameters are plain pytrees. Per-layer tensors are **stacked along a
  leading layer axis** and the trunk runs as one `lax.scan` over layers —
  one compiled block body regardless of depth (fast compiles), natural
  slicing for the hydra frozen-branch split, and clean partition specs.
- Compute runs in `compute_dtype` (bfloat16 for the MXU); layernorm and
  softmax accumulate in float32.
- No data-dependent Python control flow: masks/positions are computed with
  array ops, padding is handled with additive mask bias, positions derive
  from the attention mask (left-padding safe).

Architecture variants (selected by ModelSpec.arch):
- "gpt2": learned positions, sequential pre-LN block, biased projections,
  tied lm head.
- "gptj": rotary (partial, `rotary_dim`), parallel attn+MLP block sharing
  one layernorm, unbiased attention projections, untied head.
- "gptneox": rotary, parallel residual with separate MLP layernorm, biased
  projections, untied head.
"""

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import ModelSpec

Params = Dict[str, Any]

NEG_INF = -1e9  # additive mask value; avoids -inf NaN propagation in softmax


@dataclass(frozen=True)
class ArchFlags:
    """Derived per-arch structural switches."""

    parallel_block: bool
    use_rotary: bool
    attn_bias: bool
    separate_mlp_ln: bool  # gpt2/neox: ln_2 feeds the MLP; gptj: shared ln_1
    rotary_interleaved: bool = False  # gptj rotates every-two; neox rotates halves
    rmsnorm: bool = False  # llama: RMSNorm (scale only, no mean/bias)
    swiglu: bool = False  # llama: silu(gate) * up MLP instead of gelu

    @classmethod
    def for_spec(cls, spec: ModelSpec) -> "ArchFlags":
        arch = spec.arch.lower()
        if arch == "gpt2":
            return cls(False, False, True, True)
        if arch == "gptj":
            return cls(True, True, False, False, rotary_interleaved=True)
        if arch == "gptneox":
            return cls(True, True, True, True)
        if arch == "llama":
            return cls(False, True, False, True, rmsnorm=True, swiglu=True)
        raise ValueError(f"unknown arch '{spec.arch}'")


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _dense_init(rng, shape, dtype, scale=0.02):
    return (scale * jax.random.normal(rng, shape)).astype(dtype)


def init_block_params(
    rng: jax.Array, spec: ModelSpec, n_layers: int, dtype=jnp.float32
) -> Params:
    """Stacked parameters for `n_layers` transformer blocks: every leaf has
    leading axis `n_layers`."""
    flags = ArchFlags.for_spec(spec)
    d, f = spec.d_model, spec.d_ff
    d_kv = spec.kv_heads * spec.head_dim  # < d under grouped-query attn
    keys = jax.random.split(rng, 8)
    # GPT-2 residual scaling: two residual additions per block.
    resid_scale = 0.02 / max(2 * spec.n_layer, 1) ** 0.5

    def stack(initer, *shape_key):
        shape, key = shape_key
        return jnp.stack([initer(k, shape) for k in jax.random.split(key, n_layers)])

    def norm_params():
        p = {"scale": jnp.ones((n_layers, d), dtype)}
        if not flags.rmsnorm:
            p["bias"] = jnp.zeros((n_layers, d), dtype)
        return p

    blocks: Params = {
        "ln_1": norm_params(),
        "attn": {
            "wq": stack(lambda k, s: _dense_init(k, s, dtype), (d, d), keys[0]),
            "wk": stack(lambda k, s: _dense_init(k, s, dtype), (d, d_kv), keys[1]),
            "wv": stack(lambda k, s: _dense_init(k, s, dtype), (d, d_kv), keys[2]),
            "wo": stack(
                lambda k, s: _dense_init(k, s, dtype, resid_scale), (d, d), keys[3]
            ),
        },
        "mlp": {
            "w_in": stack(lambda k, s: _dense_init(k, s, dtype), (d, f), keys[4]),
            "w_out": stack(
                lambda k, s: _dense_init(k, s, dtype, resid_scale), (f, d), keys[5]
            ),
        },
    }
    if flags.swiglu:
        blocks["mlp"]["w_gate"] = stack(
            lambda k, s: _dense_init(k, s, dtype), (d, f), keys[6]
        )
    else:  # biased gelu MLP (gpt2/gptj/neox)
        blocks["mlp"]["b_in"] = jnp.zeros((n_layers, f), dtype)
        blocks["mlp"]["b_out"] = jnp.zeros((n_layers, d), dtype)
    if flags.attn_bias:
        # biased attention (gpt2, neox) biases ALL four projections; gptj
        # and llama bias none — one flag states the real structure
        blocks["attn"]["bq"] = jnp.zeros((n_layers, d), dtype)
        blocks["attn"]["bk"] = jnp.zeros((n_layers, d_kv), dtype)
        blocks["attn"]["bv"] = jnp.zeros((n_layers, d_kv), dtype)
        blocks["attn"]["bo"] = jnp.zeros((n_layers, d), dtype)
    if flags.separate_mlp_ln:
        blocks["ln_2"] = norm_params()
    return blocks


def init_embed_params(rng: jax.Array, spec: ModelSpec, dtype=jnp.float32) -> Params:
    flags = ArchFlags.for_spec(spec)
    k_wte, k_wpe, k_head = jax.random.split(rng, 3)
    params: Params = {"wte": _dense_init(k_wte, (spec.vocab_size, spec.d_model), dtype)}
    if not flags.use_rotary:
        params["wpe"] = _dense_init(
            k_wpe, (spec.n_positions, spec.d_model), dtype, scale=0.01
        )
    if not spec.tie_lm_head:
        params["lm_head"] = {
            "w": _dense_init(k_head, (spec.d_model, spec.vocab_size), dtype),
            "b": jnp.zeros((spec.vocab_size,), dtype),
        }
    return params


def init_ln_f_params(spec: ModelSpec, dtype=jnp.float32) -> Params:
    p: Params = {"scale": jnp.ones((spec.d_model,), dtype)}
    if not ArchFlags.for_spec(spec).rmsnorm:  # RMSNorm (llama) has no bias
        p["bias"] = jnp.zeros((spec.d_model,), dtype)
    return p


# ---------------------------------------------------------------------------
# Core ops
# ---------------------------------------------------------------------------


def layer_norm(p: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """LayerNorm (or RMSNorm) in float32 regardless of compute dtype.

    Dispatches on the param structure: a norm WITHOUT a bias entry is an
    RMSNorm (llama) — scale * x / sqrt(mean(x^2) + eps), no centering —
    so every call site (policy/ilql/generation final norms included)
    handles both families unchanged.
    """
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if "bias" not in p:  # RMSNorm
        y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(dtype)
    mean = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(
        dtype
    )


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotate_every_two(x: jnp.ndarray) -> jnp.ndarray:
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)


def apply_rotary(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    rotary_dim: int,
    interleaved: bool = False,
    theta: float = 10000.0,
) -> jnp.ndarray:
    """Rotary position embedding on the first `rotary_dim` dims of each head.

    x: [B, T, H, hd]; positions: [B, T]. `interleaved=True` is the GPT-J
    rotate-every-two convention; False is the GPT-NeoX/llama half-rotation.
    """
    hd = x.shape[-1]
    rot_dim = rotary_dim if rotary_dim > 0 else hd
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim)
    )
    # [B, T, rot_dim/2]
    freqs = positions[..., None].astype(jnp.float32) * inv_freq
    if interleaved:
        # each frequency repeated twice, interleaved: [f0, f0, f1, f1, ...]
        emb = jnp.repeat(freqs, 2, axis=-1)[:, :, None, :]
        rotate = _rotate_every_two
    else:
        emb = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None, :]
        rotate = _rotate_half
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    x32 = x_rot.astype(jnp.float32)
    out = x32 * cos + rotate(x32) * sin
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def attention_scores(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask_bias: jnp.ndarray,
) -> jnp.ndarray:
    """Plain attention: softmax in f32, matmuls in input dtype (bf16 on MXU).

    q: [B, Tq, H, hd]; k, v: [B, Tk, Hkv, hd] with Hkv dividing H
    (grouped-query attention runs natively against the compact KV — no
    repeated copies); mask_bias: [B, 1, Tq, Tk].
    """
    B, Tq, H, hd = q.shape
    Hkv = k.shape[2]
    scale = jax.lax.rsqrt(jnp.float32(hd))
    if Hkv != H:  # GQA: group query heads over each shared KV head
        g = H // Hkv
        qg = q.reshape(B, Tq, Hkv, g, hd)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
        scores = scores * scale + mask_bias[:, :, None]
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        return out.reshape(B, Tq, H, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * scale + mask_bias
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# grouped-query attention handled natively (compact Hkv-wide k/v accepted);
# attention fns WITHOUT this attr get H-wide k/v expanded by block_apply
attention_scores.supports_gqa = True


def _project(x, w, b=None):
    if isinstance(w, (tuple, list)):
        # serve-only int8 weights (serve.weights_dtype: int8): (codes
        # int8 [.., in, out], per-output-channel scale f32 [.., 1, out]).
        # The scale factors out of the contraction, so dequant is one
        # broadcast multiply on the [.., out] result — the bf16 weight
        # copy never materializes.
        codes, scale = w
        y = (x @ codes.astype(x.dtype)) * scale.astype(x.dtype)
    else:
        y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def gelu_new(x: jnp.ndarray) -> jnp.ndarray:
    """The exact tanh-approximation GELU used by GPT-2/GPT-J/NeoX
    ("gelu_new"); written out so it matches HF bit-for-bit closer than
    jax.nn.gelu's internal formulation."""
    x3 = x * x * x
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x3)))


def block_apply(
    spec: ModelSpec,
    flags: ArchFlags,
    p: Params,
    h: jnp.ndarray,
    mask_bias: jnp.ndarray,
    positions: jnp.ndarray,
    kv_cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cache_offset: Optional[jnp.ndarray] = None,
    attention_fn=attention_scores,
    cache_row_offsets: Optional[jnp.ndarray] = None,
    page_table: Optional[jnp.ndarray] = None,
    page_size: Optional[int] = None,
    paged_decode_fn=None,
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """One transformer block on hidden states `h` [B, T, D].

    When `kv_cache` is given as (k_cache, v_cache) [B, Tbuf, H, hd], fresh
    keys/values are written into the cache buffer at scalar `cache_offset`
    (the same buffer slot for every row — sequences are kept aligned in the
    buffer; per-row *logical* positions for rotary come from `positions`),
    and attention runs q against the full buffer (decode mode: T is the
    fresh suffix, typically 1).

    `cache_row_offsets` ([B] int32) switches the write to PER-ROW buffer
    positions — the slot-pool decode mode (trlx_tpu.models.generation
    `decode_step`), where each slot advances at its own pace. Requires
    T == 1 (one fresh token per row); rows whose offset is out of bounds
    are dropped (``mode="drop"``), which is how free/finished slots
    no-op. `cache_offset` is ignored in this mode.

    `page_table` ([B, max_pages] int32) switches to the PAGED pool
    layout: `kv_cache` is then the global page pool (k_pages, v_pages)
    [num_pages, page_size, Hkv, hd] shared by all rows, and each row's
    logical buffer position p lives at physical
    ``(page_table[b, p // page_size], p % page_size)``. Fresh K/V for
    token j of row b is scattered to logical position
    ``cache_row_offsets[b] + j`` (T >= 1 is allowed here — the
    prefix-suffix prefill path writes many tokens per row); entries whose
    page id is out of bounds (the host allocator's sentinel) or whose
    logical position exceeds the table extent are dropped, which is both
    the filler-row warmup trick and the finished-slot write gate.
    Attention gathers each row's K/V context page-by-page back into
    logical order ([B, max_pages * page_size, Hkv, hd]) before scoring,
    so `mask_bias` must be [B, 1, T, max_pages * page_size]; sentinel
    pages gather clamped garbage that the (exactly-zero, see NEG_INF
    softmax underflow) masked probabilities never read.

    In paged mode `kv_cache` may also be the int8 tier's nested form —
    each of k/v a ``(codes, scales)`` pair from
    :func:`init_paged_kv_cache` — in which case fresh K/V is quantized
    per (token, head) at the scatter and dequantized at the gather.

    `paged_decode_fn` (see trlx_tpu.ops.paged_attention
    ``make_paged_decode_fn``) replaces the paged gather + attention_fn
    when T == 1 with a fused kernel call
    ``fn(q[:, 0], k_pages, v_pages, page_table, bias_row)`` operating
    on the post-scatter pool; the jnp scatter (and T > 1 prefill) are
    unchanged, keeping the jnp path as the A/B oracle.
    """
    B, T, D = h.shape
    H, hd = spec.n_head, spec.head_dim
    Hkv = spec.kv_heads
    eps = spec.layer_norm_epsilon

    # the named scopes (attn, kv_write, kv_read, mlp) land in every op's
    # metadata, so a device trace says which phase of which layer an XLA
    # op belongs to (docs/source/observability.rst)
    with jax.named_scope("attn"):
        x = layer_norm(p["ln_1"], h, eps)
        attn = p["attn"]
        q = _project(x, attn["wq"], attn.get("bq")).reshape(B, T, H, hd)
        k = _project(x, attn["wk"], attn.get("bk")).reshape(B, T, Hkv, hd)
        v = _project(x, attn["wv"], attn.get("bv")).reshape(B, T, Hkv, hd)
        if flags.use_rotary:
            q = apply_rotary(q, positions, spec.rotary_dim,
                             flags.rotary_interleaved, spec.rope_theta)
            k = apply_rotary(k, positions, spec.rotary_dim,
                             flags.rotary_interleaved, spec.rope_theta)

    def expand_kv(t):
        """H-wide KV for attention fns that can't consume the compact GQA
        form (ring/pallas); the default dense path handles Hkv natively and
        never materializes the repeat. The cache always stores the compact
        Hkv form — GQA's memory win."""
        if Hkv == H or getattr(attention_fn, "supports_gqa", False):
            return t
        return jnp.repeat(t, H // Hkv, axis=2)

    new_cache = a = None
    if kv_cache is not None and page_table is not None:
        if cache_row_offsets is None:
            raise ValueError(
                "paged cache writes need cache_row_offsets (per-row "
                "logical start positions)"
            )
        if page_size is None or page_size <= 0:
            raise ValueError(f"page_table given but page_size={page_size}")
        k_entry, v_entry = kv_cache  # [num_pages, page_size, Hkv, hd]
        quantized = isinstance(k_entry, (tuple, list))
        if quantized:
            (k_cache, k_sc), (v_cache, v_sc) = k_entry, v_entry
        else:
            k_cache, v_cache = k_entry, v_entry
        num_pages = k_cache.shape[0]
        max_pages = page_table.shape[1]
        with jax.named_scope("kv_write"):
            # logical buffer position of each fresh token, then page-id
            # gather -> physical (page row, in-page offset) scatter
            pos_buf = cache_row_offsets[:, None] + jnp.arange(T)[None, :]
            page_idx = pos_buf // page_size
            in_off = pos_buf % page_size
            pids = jnp.where(
                page_idx < max_pages,
                jnp.take_along_axis(
                    page_table, jnp.minimum(page_idx, max_pages - 1),
                    axis=1,
                ),
                num_pages,  # out past the table: drop like a sentinel page
            )
            if quantized:
                kq, ks = quantize_kv(k)  # codes [B,T,Hkv,hd], scale [B,T,Hkv]
                vq, vs = quantize_kv(v)
                k_full = k_cache.at[pids, in_off].set(kq, mode="drop")
                v_full = v_cache.at[pids, in_off].set(vq, mode="drop")
                k_sc = k_sc.at[pids, in_off].set(ks, mode="drop")
                v_sc = v_sc.at[pids, in_off].set(vs, mode="drop")
                new_cache = ((k_full, k_sc), (v_full, v_sc))
            else:
                k_full = k_cache.at[pids, in_off].set(
                    k.astype(k_cache.dtype), mode="drop"
                )
                v_full = v_cache.at[pids, in_off].set(
                    v.astype(v_cache.dtype), mode="drop"
                )
                new_cache = (k_full, v_full)
        if paged_decode_fn is not None and T == 1:
            # fused kernel: page-table walk + online softmax in one
            # pallas_call against the just-updated pool; bias collapses
            # to the per-row validity lane [B, max_pages * page_size]
            with jax.named_scope("attn"):
                a = paged_decode_fn(
                    q[:, 0],
                    new_cache[0],
                    new_cache[1],
                    page_table,
                    mask_bias.reshape(B, -1),
                )[:, None]
        else:
            # gather-by-page AFTER the scatter: within one prefill
            # program a row may legitimately read pages another row just
            # wrote (the radix cache admits same-batch prefix sharers
            # against pages whose content materializes earlier in this
            # same program)
            with jax.named_scope("kv_read"):
                ctx_pt = jnp.clip(page_table, 0, num_pages - 1)
                if quantized:
                    k_ctx = dequantize_kv(
                        k_full[ctx_pt], k_sc[ctx_pt], q.dtype
                    )
                    v_ctx = dequantize_kv(
                        v_full[ctx_pt], v_sc[ctx_pt], q.dtype
                    )
                else:
                    k_ctx = k_full[ctx_pt].astype(q.dtype)
                    v_ctx = v_full[ctx_pt].astype(q.dtype)
                k_ctx = expand_kv(
                    k_ctx.reshape(B, max_pages * page_size, Hkv, hd)
                )
                v_ctx = expand_kv(
                    v_ctx.reshape(B, max_pages * page_size, Hkv, hd)
                )
    elif kv_cache is not None:
        k_cache, v_cache = kv_cache
        with jax.named_scope("kv_write"):
            if cache_row_offsets is not None:
                if T != 1:
                    raise ValueError(
                        f"cache_row_offsets (per-row cache writes) "
                        f"requires a single fresh token per row, got T={T}"
                    )
                rows = jnp.arange(B)
                k_full = k_cache.at[rows, cache_row_offsets].set(
                    k[:, 0].astype(k_cache.dtype), mode="drop"
                )
                v_full = v_cache.at[rows, cache_row_offsets].set(
                    v[:, 0].astype(v_cache.dtype), mode="drop"
                )
            else:
                k_full = jax.lax.dynamic_update_slice_in_dim(
                    k_cache, k.astype(k_cache.dtype), cache_offset, axis=1
                )
                v_full = jax.lax.dynamic_update_slice_in_dim(
                    v_cache, v.astype(v_cache.dtype), cache_offset, axis=1
                )
            new_cache = (k_full, v_full)
        with jax.named_scope("kv_read"):
            k_ctx = expand_kv(k_full.astype(q.dtype))
            v_ctx = expand_kv(v_full.astype(q.dtype))
    else:
        k_ctx, v_ctx = expand_kv(k), expand_kv(v)

    with jax.named_scope("attn"):
        if a is None:  # not the fused paged kernel's
            a = attention_fn(q, k_ctx, v_ctx, mask_bias)
        a = _project(a.reshape(B, T, D), attn["wo"], attn.get("bo"))

    def mlp(mlp_in):
        mp = p["mlp"]
        if flags.swiglu:
            gate = jax.nn.silu(_project(mlp_in, mp["w_gate"]))
            return _project(gate * _project(mlp_in, mp["w_in"]), mp["w_out"])
        return _project(
            gelu_new(_project(mlp_in, mp["w_in"], mp["b_in"])),
            mp["w_out"],
            mp["b_out"],
        )

    with jax.named_scope("mlp"):
        if flags.parallel_block:
            mlp_in = layer_norm(p["ln_2"], h, eps) \
                if flags.separate_mlp_ln else x
            return h + a + mlp(mlp_in), new_cache

        h = h + a
        return h + mlp(layer_norm(p["ln_2"], h, eps)), new_cache


# ---------------------------------------------------------------------------
# Trunk application
# ---------------------------------------------------------------------------


def causal_mask_bias(
    attention_mask: jnp.ndarray, dtype=jnp.float32
) -> jnp.ndarray:
    """Additive [B, 1, T, T] bias combining causality and padding.

    attention_mask: [B, T] with 1 = real token.
    """
    B, T = attention_mask.shape
    causal = jnp.tril(jnp.ones((T, T), bool))
    allowed = causal[None, :, :] & (attention_mask[:, None, :] > 0)
    return jnp.where(allowed, 0.0, NEG_INF).astype(dtype)[:, None, :, :]


def mask_arg_for(
    attention_fn, attention_mask: jnp.ndarray, dtype=jnp.float32
) -> jnp.ndarray:
    """The mask argument a given attention_fn expects.

    Ring attention (trlx_tpu.ops.ring_attention) declares
    ``takes_raw_mask = True`` and receives the raw [B, T] mask — the dense
    [B, 1, T, T] bias would defeat sequence parallelism's O(T^2) -> O(T^2/sp)
    memory win. Every other fn gets the additive causal+padding bias.
    """
    if getattr(attention_fn, "takes_raw_mask", False):
        return attention_mask
    return causal_mask_bias(attention_mask, dtype)


def positions_from_mask(attention_mask: jnp.ndarray) -> jnp.ndarray:
    """Position ids that start at 0 on the first *real* token — correct under
    left padding (the reference relies on HF's equivalent handling)."""
    pos = jnp.cumsum(attention_mask, axis=-1) - 1
    return jnp.maximum(pos, 0)


def apply_blocks(
    blocks: Params,
    spec: ModelSpec,
    h: jnp.ndarray,
    mask_bias: jnp.ndarray,
    positions: jnp.ndarray,
    remat: bool = False,
    attention_fn=attention_scores,
) -> jnp.ndarray:
    """Run stacked blocks over `h` with one lax.scan."""
    flags = ArchFlags.for_spec(spec)

    def body(carry, p_layer):
        out, _ = block_apply(
            spec, flags, p_layer, carry, mask_bias, positions,
            attention_fn=attention_fn,
        )
        return out, None

    if remat:
        body = jax.checkpoint(body)

    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if n_layers == 0:
        return h
    h, _ = jax.lax.scan(body, h, blocks)
    return h


def init_kv_cache(
    spec: ModelSpec,
    n_layers: int,
    batch: int,
    buffer_len: int,
    dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(k, v) cache buffers of shape [L, B, buffer_len, Hkv, hd] — compact
    KV-head form under grouped-query attention."""
    shape = (n_layers, batch, buffer_len, spec.kv_heads, spec.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


#: numerical floor added to int8 KV/weight scales so all-zero rows (fresh
#: pool pages, padding) quantize to codes 0 / scale eps instead of 0/0
KV_QUANT_EPS = 1e-8


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization of KV rows over the head_dim axis.

    x [..., hd] -> (codes int8 [..., hd], scale f32 [...]): one scale
    per (token-row, kv-head), NOT per page — decode writes one token at
    a time into partially-filled pages, and a per-page scale would need
    a read-modify-write requantization of every resident token on each
    write. Per-(row, head) scales make the write a pure scatter, and
    keep tp parity exact: under shard_map each shard sees whole heads,
    so the scale it computes is identical to the unsharded one.

    Deterministic function of content: same bits in -> same codes out,
    which is what keeps radix prefix pages content-addressable.
    """
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=-1) / 127.0 + KV_QUANT_EPS
    codes = jnp.clip(
        jnp.round(x32 / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return codes, scale


def dequantize_kv(codes: jnp.ndarray, scale: jnp.ndarray, dtype):
    """Inverse of :func:`quantize_kv` (error <= scale/2 per element)."""
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


def init_paged_kv_cache(
    spec: ModelSpec,
    num_pages: int,
    page_size: int,
    dtype=jnp.bfloat16,
):
    """ONE layer's (k, v) page buffers [num_pages, page_size, Hkv, hd]:
    fixed-size KV pages shared by every slot, addressed through per-slot
    page tables (block_apply's paged mode). A pool is a tuple of these,
    one per layer (generation.init_page_pool).

    ``dtype=jnp.int8`` selects the quantized tier: each of k/v becomes a
    ``(codes int8 [num_pages, page_size, Hkv, hd], scales f32
    [num_pages, page_size, Hkv])`` pair (see :func:`quantize_kv`) —
    hd bytes of codes + 4 bytes of scale per (token, head) instead of
    2*hd bf16 bytes, so the same HBM holds ~2x the pages.
    """
    shape = (num_pages, page_size, spec.kv_heads, spec.head_dim)
    if jnp.dtype(dtype) == jnp.int8:
        sshape = shape[:-1]
        return (
            (jnp.zeros(shape, jnp.int8), jnp.zeros(sshape, jnp.float32)),
            (jnp.zeros(shape, jnp.int8), jnp.zeros(sshape, jnp.float32)),
        )
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def apply_blocks_with_cache(
    blocks: Params,
    cache: Tuple[jnp.ndarray, jnp.ndarray],
    spec: ModelSpec,
    h: jnp.ndarray,
    mask_bias: jnp.ndarray,
    positions: jnp.ndarray,
    cache_offset: jnp.ndarray,
    attention_fn=attention_scores,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Run stacked blocks writing/reading the KV cache (prefill or decode).

    h: [B, T, D] fresh suffix; cache: ([L, B, S, H, hd], ...) full buffers;
    mask_bias: [B, 1, T, S] against the buffer; cache_offset: scalar buffer
    index where the fresh suffix starts.

    NOTE: suitable for PREFILL (one call per sequence). The decode loop does
    NOT use this: a stacked cache flowing through scan xs/ys re-materializes
    every step (~4x the cache size in HBM traffic per token, measured on
    v5e); trlx_tpu.models.generation keeps the cache in the decode scan's
    carry (per-layer leaves / fori_loop) for in-place updates instead.
    """
    flags = ArchFlags.for_spec(spec)

    def body(carry, xs):
        p_layer, k_layer, v_layer = xs
        out, new_cache = block_apply(
            spec,
            flags,
            p_layer,
            carry,
            mask_bias,
            positions,
            kv_cache=(k_layer, v_layer),
            cache_offset=cache_offset,
            attention_fn=attention_fn,
        )
        return out, new_cache

    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if n_layers == 0:
        return h, cache
    h, (new_k, new_v) = jax.lax.scan(body, h, (blocks, cache[0], cache[1]))
    return h, (new_k, new_v)


def embed_tokens(
    embed: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    # JAX clamps out-of-bounds gathers silently; catch over-length sequences
    # at trace time instead of silently reusing the last position embedding.
    if tokens.shape[-1] > spec.n_positions:
        raise ValueError(
            f"sequence length {tokens.shape[-1]} exceeds n_positions "
            f"{spec.n_positions}"
        )
    h = embed["wte"][tokens].astype(compute_dtype)
    if "wpe" in embed:
        h = h + embed["wpe"][positions].astype(compute_dtype)
    return h


def project_logits(embed: Params, spec: ModelSpec, h_normed: jnp.ndarray) -> jnp.ndarray:
    """(Tied or untied) LM head on already-layernormed hidden; float32 logits."""
    if spec.tie_lm_head:
        logits = h_normed @ embed["wte"].T.astype(h_normed.dtype)
    else:
        head = embed["lm_head"]
        logits = h_normed @ head["w"].astype(h_normed.dtype) + head["b"].astype(
            h_normed.dtype
        )
    return logits.astype(jnp.float32)


def lm_logits(
    embed: Params, ln_f: Params, spec: ModelSpec, h: jnp.ndarray
) -> jnp.ndarray:
    """Final layernorm + LM head; returns float32 logits."""
    return project_logits(embed, spec, layer_norm(ln_f, h, spec.layer_norm_epsilon))
