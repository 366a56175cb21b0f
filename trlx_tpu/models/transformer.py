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
- "cohere2_moe": parallel block on one scale-only LayerNorm, grouped-query
  attention with an explicit head size, window (RoPE) and full (NoPE)
  layers by ``ModelSpec.layer_pattern``, a routed + shared expert FFN,
  tied head times ``logit_scale``.
- "sarvam_mla": sequential RMSNorm block, latent attention
  (:mod:`trlx_tpu.models.latent`: one latent a token in the cache, an
  up-projected and an absorbed order), ``first_dense_layers`` SwiGLU
  layers and then routed experts chosen through a bias, one shared
  expert, untied head. Its trunk is a dense segment followed by an
  expert segment: leaves of different shape cannot share one stacked
  tree.

A block is three parts, one small function per kind: the **mixer**
(:func:`_qkv`: projections, RoPE or none; the window is the caller's mask),
the **cache** writer/reader (:func:`_paged_cache`, :func:`_slot_cache`, or
none) and the **FFN** (:func:`_dense_ffn` | :func:`moe_ffn`).
"""

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import ModelSpec
from trlx_tpu.models import latent as L

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
    centred_scale_norm: bool = False  # cohere: LayerNorm without a bias
    moe: bool = False  # routed + shared experts in the FFN's place
    latent: bool = False  # latent attention (models/latent.py)

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
        if arch == "cohere2_moe":
            if not spec.n_experts:
                raise ValueError("arch 'cohere2_moe' needs n_experts > 0")
            return cls(True, True, False, False, rotary_interleaved=True,
                       centred_scale_norm=True, moe=True)
        if arch == "sarvam_mla":
            if not (spec.n_experts and spec.kv_lora_rank):
                raise ValueError(
                    "arch 'sarvam_mla' needs n_experts > 0 and kv_lora_rank"
                )
            return cls(False, True, False, True, rotary_interleaved=True,
                       rmsnorm=True, swiglu=True, moe=True, latent=True)
        raise ValueError(f"unknown arch '{spec.arch}'")


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _dense_init(rng, shape, dtype, scale=0.02):
    return (scale * jax.random.normal(rng, shape)).astype(dtype)


def init_block_params(
    rng: jax.Array, spec: ModelSpec, n_layers: int, dtype=jnp.float32,
    first_layer: int = 0,
) -> Params:
    """Stacked parameters for `n_layers` transformer blocks from depth
    ``first_layer`` on: every leaf has leading axis `n_layers`. Where the
    layers are not all alike (a model's leading dense layers before its
    expert layers) the result is a tuple of such trees, one per run of
    like layers, in order (:func:`slice_layers`, :func:`apply_blocks` and
    the serve programs' segments take either)."""
    flags = ArchFlags.for_spec(spec)
    dense_here = min(max(spec.first_dense_layers - first_layer, 0), n_layers)
    if flags.moe and 0 < dense_here < n_layers:
        k_dense, k_moe = jax.random.split(rng)
        return (
            init_block_params(k_dense, spec, dense_here, dtype, first_layer),
            init_block_params(k_moe, spec, n_layers - dense_here, dtype,
                              first_layer + dense_here),
        )
    moe_here = flags.moe and not dense_here
    d, f = spec.d_model, spec.d_ff
    d_q = spec.n_head * spec.head_dim  # == d unless the head size is explicit
    d_kv = spec.kv_heads * spec.head_dim  # < d under grouped-query attn
    keys = jax.random.split(rng, 8)
    # GPT-2 residual scaling: two residual additions per block.
    resid_scale = 0.02 / max(2 * spec.n_layer, 1) ** 0.5

    def stack(initer, *shape_key):
        shape, key = shape_key
        return jnp.stack([initer(k, shape) for k in jax.random.split(key, n_layers)])

    def norm_params():
        if flags.centred_scale_norm:
            return {"scale_centred": jnp.ones((n_layers, d), dtype)}
        p = {"scale": jnp.ones((n_layers, d), dtype)}
        if not flags.rmsnorm:
            p["bias"] = jnp.zeros((n_layers, d), dtype)
        return p

    if flags.latent:
        blocks: Params = {
            "ln_1": norm_params(),
            "attn": L.init_attn_params(
                jax.random.split(keys[0], 5), spec, n_layers, dtype,
                _dense_init, resid_scale,
            ),
        }
    else:
        blocks = {
            "ln_1": norm_params(),
            "attn": {
                "wq": stack(lambda k, s: _dense_init(k, s, dtype), (d, d_q), keys[0]),
                "wk": stack(lambda k, s: _dense_init(k, s, dtype), (d, d_kv), keys[1]),
                "wv": stack(lambda k, s: _dense_init(k, s, dtype), (d, d_kv), keys[2]),
                "wo": stack(
                    lambda k, s: _dense_init(k, s, dtype, resid_scale), (d_q, d), keys[3]
                ),
            },
        }
    if moe_here:
        blocks.update(_init_moe_params(keys[4:8], spec, n_layers, dtype,
                                       resid_scale))
        if flags.separate_mlp_ln:  # a sequential block norms its FFN's input
            blocks["ln_2"] = norm_params()
        return blocks
    blocks["mlp"] = {
        "w_in": stack(lambda k, s: _dense_init(k, s, dtype), (d, f), keys[4]),
        "w_out": stack(
            lambda k, s: _dense_init(k, s, dtype, resid_scale), (f, d), keys[5]
        ),
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


def _init_moe_params(keys, spec: ModelSpec, n_layers: int, dtype,
                     resid_scale) -> Params:
    """The expert FFN's stacked parameters: the router over ALL experts,
    the experts HELD here ([L, held, ...]) and the shared experts, which
    are kept as one SwiGLU of width ``n_shared * width`` (the sum of the
    shared experts' outputs is that SwiGLU's output; the average is its
    output over ``n_shared``)."""
    d, f, held = spec.d_model, spec.expert_width, spec.experts_held
    fs = max(spec.n_shared_experts, 1) * f

    def normal(key, shape, scale=0.02):
        return _dense_init(key, (n_layers, *shape), dtype, scale)

    ke = jax.random.split(keys[0], 3)
    ks = jax.random.split(keys[1], 3)
    out = {"moe": {
        "router": normal(keys[2], (d, spec.n_experts)),
        "w_gate": normal(ke[0], (held, d, f)),
        "w_up": normal(ke[1], (held, d, f)),
        "w_down": normal(ke[2], (held, f, d), resid_scale),
    }}
    if spec.router_bias:
        out["moe"]["router_bias"] = jnp.zeros((n_layers, spec.n_experts),
                                              jnp.float32)
    if spec.n_shared_experts:
        out["shared"] = {
            "w_gate": normal(ks[0], (d, fs)),
            "w_up": normal(ks[1], (d, fs)),
            "w_down": normal(ks[2], (fs, d), resid_scale),
        }
    return out


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
    flags = ArchFlags.for_spec(spec)
    if flags.centred_scale_norm:
        return {"scale_centred": jnp.ones((spec.d_model,), dtype)}
    p: Params = {"scale": jnp.ones((spec.d_model,), dtype)}
    if not flags.rmsnorm:  # RMSNorm (llama) has no bias
        p["bias"] = jnp.zeros((spec.d_model,), dtype)
    return p


# ---------------------------------------------------------------------------
# Core ops
# ---------------------------------------------------------------------------


def layer_norm(p: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """LayerNorm (or RMSNorm) in float32 regardless of compute dtype.

    Dispatches on the param structure: a norm WITHOUT a bias entry is an
    RMSNorm (llama) — scale * x / sqrt(mean(x^2) + eps), no centering —
    and one whose only entry is ``scale_centred`` is a LayerNorm without
    a bias (cohere), so every call site (policy/ilql/generation final norms included)
    handles both families unchanged.
    """
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if "scale_centred" in p:  # cohere: mean-centred, scale only
        y = (x32 - x32.mean(-1, keepdims=True)) * jax.lax.rsqrt(
            x32.var(-1, keepdims=True) + eps
        )
        return (y * p["scale_centred"].astype(jnp.float32)).astype(dtype)
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


def _qkv(spec: ModelSpec, flags: ArchFlags, p: Params, h, positions,
         use_rope: bool):
    """The mixer's front half: the shared norm and the q/k/v projections,
    rotated where this layer carries positions (``use_rope``; a NoPE layer
    is the same function without the rotation). Returns (x, q, k, v) with
    x the normed input the parallel block's FFN reads too."""
    B, T, _ = h.shape
    H, hd, Hkv = spec.n_head, spec.head_dim, spec.kv_heads
    x = layer_norm(p["ln_1"], h, spec.layer_norm_epsilon)
    attn = p["attn"]
    q = _project(x, attn["wq"], attn.get("bq"))
    k = _project(x, attn["wk"], attn.get("bk"))
    v = _project(x, attn["wv"], attn.get("bv"))
    if T == 1:
        # a decode step: XLA:TPU otherwise folds the head split below into
        # the dot, and the folded dot wants each weight in another order:
        # every step then writes all three matrices out again before it
        # reads them (a third of a gpt-j-6B step on v5e). The barrier
        # keeps the dot 2-D, so the weights stream as they are stored
        # (``serve/decode_weight_copy_bytes`` reads 0). Programs of
        # T > 1 keep the folded form: prefill, verify, and the trained
        # forward, where a barrier would also stand in the backward pass.
        q, k, v = jax.lax.optimization_barrier((q, k, v))
    q = q.reshape(B, T, H, hd)
    k = k.reshape(B, T, Hkv, hd)
    v = v.reshape(B, T, Hkv, hd)
    if use_rope:
        q = apply_rotary(q, positions, spec.rotary_dim,
                         flags.rotary_interleaved, spec.rope_theta)
        k = apply_rotary(k, positions, spec.rotary_dim,
                         flags.rotary_interleaved, spec.rope_theta)
    return x, q, k, v


def _paged_write(kv_cache, k, v, cache_row_offsets, page_table, page_size,
                 page_base=None, ring=False):
    """Cache writer, paged: scatter the fresh K/V of token j of row b to
    logical position ``cache_row_offsets[b] + j`` through the row's page
    table. Logical page ``n`` sits at table entry ``n`` (the full class),
    ``n - page_base[b]`` (a prefill's view of the window class) or
    ``n % max_pages`` (``ring``: the window class at decode). Entries past
    the table, or whose page id is the out-of-bounds sentinel, drop."""
    T = k.shape[1]
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
        if ring:
            page_idx = page_idx % max_pages
        in_table = page_idx < max_pages
        entry = jnp.minimum(page_idx, max_pages - 1)
        if page_base is not None:
            page_idx = page_idx - page_base[:, None]
            in_table = (page_idx >= 0) & (page_idx < max_pages)
            entry = jnp.clip(page_idx, 0, max_pages - 1)
        pids = jnp.where(
            in_table,
            jnp.take_along_axis(page_table, entry, axis=1),
            num_pages,  # out past the table: drop like a sentinel page
        )
        if quantized:
            kq, ks = quantize_kv(k)  # codes [B,T,Hkv,hd], scale [B,T,Hkv]
            vq, vs = quantize_kv(v)
            k_full = k_cache.at[pids, in_off].set(kq, mode="drop")
            v_full = v_cache.at[pids, in_off].set(vq, mode="drop")
            k_sc = k_sc.at[pids, in_off].set(ks, mode="drop")
            v_sc = v_sc.at[pids, in_off].set(vs, mode="drop")
            return ((k_full, k_sc), (v_full, v_sc))
        k_full = k_cache.at[pids, in_off].set(
            k.astype(k_cache.dtype), mode="drop"
        )
        v_full = v_cache.at[pids, in_off].set(
            v.astype(v_cache.dtype), mode="drop"
        )
        return (k_full, v_full)


def _paged_read(new_cache, page_table, page_size, dtype):
    """Cache reader, paged (the jnp path): each row's pages gathered back
    into table order, [B, max_pages * page_size, Hkv, hd]."""
    k_entry, v_entry = new_cache
    quantized = isinstance(k_entry, (tuple, list))
    B, max_pages = page_table.shape
    with jax.named_scope("kv_read"):
        if quantized:
            (k_full, k_sc), (v_full, v_sc) = k_entry, v_entry
            ctx_pt = jnp.clip(page_table, 0, k_full.shape[0] - 1)
            k_ctx = dequantize_kv(k_full[ctx_pt], k_sc[ctx_pt], dtype)
            v_ctx = dequantize_kv(v_full[ctx_pt], v_sc[ctx_pt], dtype)
        else:
            ctx_pt = jnp.clip(page_table, 0, k_entry.shape[0] - 1)
            k_ctx = k_entry[ctx_pt].astype(dtype)
            v_ctx = v_entry[ctx_pt].astype(dtype)
        tail = k_ctx.shape[-2:]
        return (k_ctx.reshape(B, max_pages * page_size, *tail),
                v_ctx.reshape(B, max_pages * page_size, *tail))


def _slot_cache(kv_cache, k, v, cache_offset):
    """Cache writer, contiguous (``generate()``'s own buffer): the same
    buffer slot ``cache_offset`` for every row. The reader is the whole
    buffer."""
    k_cache, v_cache = kv_cache
    with jax.named_scope("kv_write"):
        k_full = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), cache_offset, axis=1
        )
        v_full = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), cache_offset, axis=1
        )
    return k_full, v_full


def _dense_ffn(flags: ArchFlags, mp: Params, mlp_in):
    if flags.swiglu:
        gate = jax.nn.silu(_project(mlp_in, mp["w_gate"]))
        return _project(gate * _project(mlp_in, mp["w_in"]), mp["w_out"])
    return _project(
        gelu_new(_project(mlp_in, mp["w_in"], mp["b_in"])),
        mp["w_out"],
        mp["b_out"],
    )


def moe_ffn(spec: ModelSpec, p: Params, x, token_mask=None):
    """Routed + shared experts on x [B, T, D]; returns (out, stats).

    The router scores ALL ``n_experts`` in float32 (sigmoid), takes the
    top ``experts_per_token`` and normalises their scores over all of
    those chosen; a ``router_bias`` [E] (float32, beside the router) is
    added to the scores for the CHOICE alone and the gates weigh by the
    scores themselves, times ``routed_scaling_factor``. This process holds experts ``[expert_offset,
    expert_offset + experts_held)``: the (token, expert) pairs whose
    expert lies there are sorted by expert and computed as one grouped
    product over the experts held (``jax.lax.ragged_dot``: a native grouped
    matmul on the TPU, whose work follows the rows inside the groups).
    Every such pair is computed, whatever the skew; there is no capacity
    and nothing is dropped. What the experts held elsewhere would have
    added is left out. ``token_mask`` [B, T] takes padding and idle rows
    out of the pairs. The shared experts run on every token as one SwiGLU
    of width ``n_shared * width`` whose output is divided by ``n_shared``:
    the average of the shared experts' outputs (one shared expert is
    added as it is: the mean over one is the same number).

    stats: int32/float32 scalars (pairs_here, experts_hit, load_max,
    load_mean) of this call, for the scheduler's counters."""
    B, T, D = x.shape
    N, K = B * T, spec.experts_per_token
    held, off = spec.experts_held, spec.expert_offset
    mp = p["moe"]
    xf = x.reshape(N, D)
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(
            xf.astype(jnp.float32) @ mp["router"].astype(jnp.float32)
        )  # [N, E]
        if "router_bias" in mp:  # chooses, does not weigh
            _, top_e = jax.lax.top_k(
                scores + mp["router_bias"].astype(jnp.float32), K
            )
            top_s = jnp.take_along_axis(scores, top_e, axis=-1)
        else:
            top_s, top_e = jax.lax.top_k(scores, K)  # [N, K]
        gates = top_s / top_s.sum(-1, keepdims=True)
        if spec.routed_scaling_factor != 1.0:
            gates = gates * spec.routed_scaling_factor
    with jax.named_scope("experts"):
        local = top_e - off
        here = (local >= 0) & (local < held)
        if token_mask is not None:
            here = here & token_mask.reshape(N, 1)
        # pairs held elsewhere sort behind every group and are never computed
        key = jnp.where(here, local, held).reshape(N * K)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
        xs = xf[order // K]  # [N*K, D]: each pair's token, grouped by expert
        gate = jax.lax.ragged_dot(xs, mp["w_gate"].astype(x.dtype), sizes)
        up = jax.lax.ragged_dot(xs, mp["w_up"].astype(x.dtype), sizes)
        y = jax.lax.ragged_dot(
            jax.nn.silu(gate) * up, mp["w_down"].astype(x.dtype), sizes,
            preferred_element_type=jnp.float32,
        )  # [N*K, D]; rows past the groups are not defined
        w = jnp.where(here, gates, 0.0).reshape(N * K)[order]
        y = jnp.where(w[:, None] > 0, y * w[:, None], 0.0)
        # back to (token, choice) order; a token's pairs are summed in f32
        routed = y[jnp.argsort(order)].reshape(N, K, D).sum(1)
    out = routed.astype(x.dtype)
    if "shared" in p:
        with jax.named_scope("shared"):
            sp = p["shared"]
            act = jax.nn.silu(_project(xf, sp["w_gate"])) * _project(
                xf, sp["w_up"]
            )
            out = out + _project(act, sp["w_down"]) * jnp.asarray(
                1.0 / spec.n_shared_experts, x.dtype
            )
    stats = (sizes.sum(), (sizes > 0).sum(), sizes.max(),
             sizes.sum().astype(jnp.float32) / held)
    return out.reshape(B, T, D), stats


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
    use_rope: Optional[bool] = None,
    page_base: Optional[jnp.ndarray] = None,
    ring: bool = False,
    paged_attend_fn=None,
    token_mask: Optional[jnp.ndarray] = None,
    moe_stats: Optional[list] = None,
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """One transformer block on hidden states `h` [B, T, D].

    When `kv_cache` is given as (k_cache, v_cache) [B, Tbuf, H, hd], fresh
    keys/values are written into the cache buffer at scalar `cache_offset`
    (the same buffer slot for every row — sequences are kept aligned in the
    buffer; per-row *logical* positions for rotary come from `positions`),
    and attention runs q against the full buffer (decode mode: T is the
    fresh suffix, typically 1).

    `page_table` ([B, max_pages] int32) switches to the PAGED pool
    layout — the serve programs' (trlx_tpu.models.generation
    `prefill_into_slots` / `decode_step` / `verify_step`), where each
    slot advances at its own pace: `kv_cache` is then the global page
    pool (k_pages, v_pages) [num_pages, page_size, Hkv, hd] shared by
    all rows, and each row's logical buffer position p lives at physical
    ``(page_table[b, p // page_size], p % page_size)``. Fresh K/V for
    token j of row b is scattered to logical position
    ``cache_row_offsets[b] + j`` ([B] int32, per-row; T >= 1 — the
    prefix-suffix prefill path writes many tokens per row;
    `cache_offset` is ignored in this mode); entries whose
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

    A model whose layers differ is served by the same function: the
    caller hands each layer ITS class of page table and ITS mask (the
    window lives in the mask), and says `use_rope` (None: as the arch
    does everywhere). `page_base` / `ring` say how a window-class table
    maps logical pages (:func:`_paged_write`). `paged_attend_fn(q,
    k_pages, v_pages) -> [B, T, H, hd]` replaces gather + attention_fn
    with the caller's own reader of the post-scatter pool (the blocked
    prefill attention of models/generation.py). `token_mask` [B, T]
    marks real tokens for the expert FFN; `moe_stats`, a list, receives
    that layer's routing counts.
    """
    if flags.latent:
        if paged_attend_fn is not None or page_base is not None or ring \
                or (kv_cache is not None and page_table is None):
            raise ValueError(
                "a latent layer keeps one class of page and no contiguous "
                "cache: it takes a page table or no cache at all"
            )
        return _latent_block_apply(
            spec, flags, p, h, mask_bias, positions, kv_cache,
            cache_row_offsets, page_table, page_size, paged_decode_fn,
            token_mask, moe_stats,
        )
    B, T, D = h.shape
    H, hd = spec.n_head, spec.head_dim
    Hkv = spec.kv_heads
    eps = spec.layer_norm_epsilon

    # the named scopes (attn, kv_write, kv_read, mlp | router, experts,
    # shared) land in every op's metadata, so a device trace says which
    # phase of which layer an XLA op belongs to
    # (docs/source/observability.rst)
    with jax.named_scope("attn"):
        x, q, k, v = _qkv(
            spec, flags, p, h, positions,
            flags.use_rotary if use_rope is None else use_rope,
        )
        attn = p["attn"]

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
        new_cache = _paged_write(
            kv_cache, k, v, cache_row_offsets, page_table, page_size,
            page_base, ring,
        )
        if paged_attend_fn is not None:
            with jax.named_scope("attn"):
                a = paged_attend_fn(q, new_cache[0], new_cache[1])
        elif paged_decode_fn is not None and T == 1:
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
            k_ctx, v_ctx = _paged_read(new_cache, page_table, page_size,
                                       q.dtype)
            k_ctx, v_ctx = expand_kv(k_ctx), expand_kv(v_ctx)
    elif kv_cache is not None:
        if cache_row_offsets is not None:
            raise ValueError(
                "cache_row_offsets (per-row cache writes) go through a "
                "page_table; a contiguous cache is written at the one "
                "cache_offset"
            )
        k_full, v_full = _slot_cache(kv_cache, k, v, cache_offset)
        new_cache = (k_full, v_full)
        with jax.named_scope("kv_read"):
            k_ctx = expand_kv(k_full.astype(q.dtype))
            v_ctx = expand_kv(v_full.astype(q.dtype))
    else:
        k_ctx, v_ctx = expand_kv(k), expand_kv(v)

    with jax.named_scope("attn"):
        if a is None:  # not a fused paged reader's
            a = attention_fn(q, k_ctx, v_ctx, mask_bias)
        a = _project(a.reshape(B, T, H * hd), attn["wo"], attn.get("bo"))

    if flags.moe:
        # the parallel block: attention and experts read the same x
        m, stats = moe_ffn(spec, p, x, token_mask)
        if moe_stats is not None:
            moe_stats.append(stats)
        return h + a + m, new_cache

    with jax.named_scope("mlp"):
        if flags.parallel_block:
            mlp_in = layer_norm(p["ln_2"], h, eps) \
                if flags.separate_mlp_ln else x
            return h + a + _dense_ffn(flags, p["mlp"], mlp_in), new_cache

        h = h + a
        return h + _dense_ffn(
            flags, p["mlp"], layer_norm(p["ln_2"], h, eps)
        ), new_cache


def _latent_block_apply(spec, flags, p, h, mask_bias, positions, kv_cache,
                        cache_row_offsets, page_table, page_size,
                        latent_decode_fn, token_mask, moe_stats):
    """block_apply for a latent-attention layer (models/latent.py): a
    sequential RMSNorm block whose cache, when given, is ONE layer's
    latent pages [num_pages, page_size, latent_page_width] behind a page
    table.
    No cache: the up-projected order over the chunk itself (the train
    forward, ``mask_bias`` [B, 1, T, T]). With pages: the fresh latents
    are scattered in, then a ``T == 1`` step under ``latent_decode_fn``
    (ops/latent_attention.latent_decode_attention) runs the absorbed
    kernel over ``mask_bias``'s validity lane, and everything else reads
    the pages in blocks (``latent.attend_pages``), causal over the
    buffer positions ``cache_row_offsets + j``, in the order ``T`` picks.
    Whether the FFN is dense or routed is the layer's own parameters'."""
    B, T, _ = h.shape
    eps = spec.layer_norm_epsilon
    attn = p["attn"]
    new_cache = None
    with jax.named_scope("attn"):
        x = layer_norm(p["ln_1"], h, eps)
        qn, qr, latent = L.project(
            spec, attn, x, positions, lambda q, y: layer_norm(q, y, eps)
        )
        if kv_cache is None:
            with jax.named_scope("ctx_attn"):
                a = L.attend_chunk(spec, attn, qn, qr, latent, mask_bias)
        else:
            if cache_row_offsets is None or not page_size:
                raise ValueError(
                    "latent pages are written through cache_row_offsets "
                    "and a page_size"
                )
            new_cache = L.write_pages(
                kv_cache, latent, cache_row_offsets, page_table, page_size
            )
            if latent_decode_fn is not None and T == 1:
                with jax.named_scope("absorb"):
                    qa = L.absorb_query(spec, attn, qn, qr)
                # the page's zero tail scores nothing: q padded to match
                qa = jnp.pad(qa[:, 0], ((0, 0), (0, 0), (
                    0, spec.latent_page_width - spec.latent_width)))
                u = latent_decode_fn(
                    qa, new_cache, page_table,
                    mask_bias.reshape(B, -1), spec.kv_lora_rank,
                    L.score_scale(spec),
                )
                with jax.named_scope("absorb"):
                    a = L.unabsorb_output(spec, attn, u[:, None])
            else:
                q_pos = cache_row_offsets[:, None] + jnp.arange(T)[None, :]
                a = L.attend_pages(spec, attn, qn, qr, new_cache,
                                   page_table, q_pos, page_size)
        with jax.named_scope("o_proj"):
            a = a.reshape(B, T, -1) @ attn["wo"].astype(a.dtype)
    h = h + a
    with jax.named_scope("mlp"):
        y = layer_norm(p["ln_2"], h, eps)
        if "moe" in p:
            m, stats = moe_ffn(spec, p, y, token_mask)
            if moe_stats is not None:
                moe_stats.append(stats)
        else:
            m = _dense_ffn(flags, p["mlp"], y)
    return h + m, new_cache


# ---------------------------------------------------------------------------
# Trunk application
# ---------------------------------------------------------------------------


def causal_mask_bias(
    attention_mask: jnp.ndarray, dtype=jnp.float32, window: int = 0
) -> jnp.ndarray:
    """Additive [B, 1, T, T] bias combining causality and padding.

    attention_mask: [B, T] with 1 = real token. ``window`` > 0 keeps, for
    the query at buffer slot i, the keys at slots i - window < j <= i (a
    window layer; slots are positions for the unpadded rows such a model
    is given).
    """
    B, T = attention_mask.shape
    causal = jnp.tril(jnp.ones((T, T), bool))
    if window > 0:
        causal = causal & ~jnp.tril(jnp.ones((T, T), bool), -window)
    allowed = causal[None, :, :] & (attention_mask[:, None, :] > 0)
    return jnp.where(allowed, 0.0, NEG_INF).astype(dtype)[:, None, :, :]


def window_of(spec: ModelSpec, layer: int) -> int:
    """The window of layer ``layer``: 0 for a full layer."""
    return spec.window if spec.layer_kind(layer) == "window" else 0


def rope_of(spec: ModelSpec, flags: ArchFlags, layer: int) -> bool:
    return flags.use_rotary and spec.layer_kind(layer) in spec.rope_kinds


def _mixed_layers(spec: ModelSpec) -> bool:
    """Whether the model has window layers beside its full ones (and so
    two classes of KV page, two masks, per-layer rotation)."""
    return "window" in spec.layer_pattern


def _mechanisms(spec: ModelSpec) -> tuple:
    """What of a model the dense families lack, by name."""
    return tuple(name for name, has in (
        ("routed experts", bool(spec.n_experts)),
        ("window layers", _mixed_layers(spec)),
        ("latent attention", bool(spec.kv_lora_rank)),
    ) if has)


#: per setting: the values every model runs under, and which mechanism of
#: a model refuses any other value, with what to do instead; a mechanism a
#: setting does not name runs under every value of it
_NEW_ARCH_RUNS_UNDER = {
    "trainer": ((), {
        "routed experts": "training through a router (its place in the "
                          "hydra split, an auxiliary load loss) is not "
                          "built; serve the model instead",
    }),
    "hf_import": ((), {
        "routed experts": "no converter for this checkpoint layout; build "
                          "the model from model.model_spec",
    }),
    "rollout_cache": (("paged",), {
        "latent attention": "generate()'s contiguous cache holds per-head "
                            "K and V; a latent model is served through the "
                            "paged slot pool (trlx_tpu.serve.slots)",
    }),
    "kv_dtype": (("bf16",), {
        "window layers": "the int8 page tier is not wired to two classes "
                         "of page; use kv_dtype: bf16",
        "latent attention": "the int8 page tier keeps a scale a kv head and "
                            "a latent page has none; use kv_dtype: bf16",
    }),
    "weights_dtype": (("bf16",), {
        "routed experts": "the int8 weight tier does not cover expert "
                          "stacks; use weights_dtype: bf16",
    }),
    "speculation": (("off",), {
        "window layers": "the verifier reads one class of page table; use "
                         "speculation: off",
        "latent attention": "the verifier scores per-head K/V pages; use "
                            "speculation: off",
    }),
    "mesh": ((None,), {
        "routed experts": "there is no expert axis in serve/layouts.py; "
                          "serve one chip's share on the default mesh",
    }),
}


def require_supported(spec: ModelSpec, **settings) -> None:
    """The one place that refuses what an arch cannot run yet: raises
    NotImplementedError naming the setting, its value, the arch and the
    mechanism of it that refuses. The dense families run under every
    setting and pass. (``attention: jnp`` is not in the table: the latent
    pool has a jnp reader, ``latent.attend_pages``.)"""
    has = _mechanisms(spec)
    for name, value in settings.items():
        allowed, refusing = _NEW_ARCH_RUNS_UNDER[name]
        if value in allowed:
            continue
        for mechanism in has:
            if mechanism in refusing:
                raise NotImplementedError(
                    f"{name}={value!r} is not supported with arch "
                    f"'{spec.arch}' ({mechanism}): {refusing[mechanism]}"
                )


def slice_layers(blocks, lo: int, hi: int):
    """Layers ``[lo, hi)`` of a trunk: of one stacked tree, the slice of
    every leaf; of a tuple of stacked trees (:func:`init_block_params`),
    the runs that overlap, as one tree where one run is left (an empty
    range keeps the first run's leaves at zero layers)."""
    if not isinstance(blocks, (tuple, list)):
        return jax.tree_util.tree_map(lambda x: x[lo:hi], blocks)
    out, first = [], 0
    for seg in blocks:
        n = jax.tree_util.tree_leaves(seg)[0].shape[0]
        a, b = max(lo - first, 0), min(hi - first, n)
        if a < b:
            out.append(jax.tree_util.tree_map(lambda x: x[a:b], seg))
        first += n
    if not out:
        return jax.tree_util.tree_map(lambda x: x[:0], blocks[0])
    return out[0] if len(out) == 1 else tuple(out)


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
    first_layer: int = 0,
) -> jnp.ndarray:
    """Run stacked blocks over `h` with one lax.scan.

    A model whose layers differ (``ModelSpec.layer_pattern``) keeps one
    stacked tree and one scanned body too: the kind of layer
    ``first_layer + i`` rides the scan as two flags, which pick the
    layer's mask (the window is cut out of ``mask_bias``) and gate its
    rotation (a NoPE layer rotates by position 0, which is the identity,
    exactly). ``first_layer`` is the depth at which this stack starts
    (the hydra policy's top branch)."""
    flags = ArchFlags.for_spec(spec)
    if isinstance(blocks, (tuple, list)):  # runs of like layers, in order
        for seg in blocks:
            h = apply_blocks(seg, spec, h, mask_bias, positions, remat,
                             attention_fn, first_layer)
            first_layer += jax.tree_util.tree_leaves(seg)[0].shape[0]
        return h
    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if n_layers == 0:
        return h

    if _mixed_layers(spec):
        if mask_bias.ndim != 4:
            raise ValueError(
                "a model with window layers takes the additive "
                "[B, 1, T, T] mask (no raw-mask attention_fn)"
            )
        T = mask_bias.shape[-1]
        behind = jnp.tril(jnp.ones((T, T), bool), -spec.window)
        window_bias = jnp.where(behind, NEG_INF, mask_bias)
        layers = range(first_layer, first_layer + n_layers)
        is_window = jnp.array([window_of(spec, n) > 0 for n in layers])
        has_rope = jnp.array([rope_of(spec, flags, n) for n in layers])

        def body(carry, xs):
            p_layer, win, rope = xs
            out, _ = block_apply(
                spec, flags, p_layer, carry,
                jnp.where(win, window_bias, mask_bias),
                jnp.where(rope, positions, 0),
                attention_fn=attention_fn,
            )
            return out, None

        xs = (blocks, is_window, has_rope)
    else:
        def body(carry, p_layer):
            out, _ = block_apply(
                spec, flags, p_layer, carry, mask_bias, positions,
                attention_fn=attention_fn,
                use_rope=rope_of(spec, flags, first_layer),
            )
            return out, None

        xs = blocks

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, xs)
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
    if spec.kv_lora_rank:
        # latent pages: one buffer a layer and no V buffer (the values are
        # the first kv_lora_rank columns of the same page)
        return jnp.zeros((num_pages, page_size, spec.latent_page_width),
                         dtype)
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
    logits = logits.astype(jnp.float32)
    if spec.logit_scale != 1.0:
        logits = logits * spec.logit_scale
    return logits


def lm_logits(
    embed: Params, ln_f: Params, spec: ModelSpec, h: jnp.ndarray
) -> jnp.ndarray:
    """Final layernorm + LM head; returns float32 logits."""
    return project_logits(embed, spec, layer_norm(ln_f, h, spec.layer_norm_epsilon))
