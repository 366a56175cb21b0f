"""Latent attention (MLA): the mixer of ``arch: sarvam_mla``.

A token leaves ONE latent a layer in the cache, ``[c ; kr]``: the
compressed key/value state ``c`` (``kv_lora_rank`` numbers, RMS-normed)
and one rotated key part ``kr`` (``qk_rope_head_dim``) that all heads
share. A head's key and value are up-projections of ``c``::

    q_i = Wq_i x = [qn_i ; qr_i],  qr_i <- RoPE(qr_i)
    [kn_i ; v_i](j) = [Wuk_i ; Wuv_i] c_j
    s_ij = scale * (qn_i . kn_i(j) + qr_i . kr_j)

and the same scores and outputs can be had without ever forming ``kn`` or
``v`` (the *absorbed* order): ``q~_i = Wuk_i^T qn_i``, ``s_ij = scale *
(q~_i . c_j + qr_i . kr_j)``, ``u_i = sum_j p_ij c_j``, ``o_i = Wuv_i u_i``.

Two orders, one function each, chosen by the static chunk length ``T`` and
nothing else (:data:`ABSORB_MAX_T`): a decode step and a short suffix over
a long cached prefix run absorbed (a key costs ``T * 139k`` operations and
is read once, 1,152 bytes); a long chunk runs up-projected (a key costs
16.8M operations to up-project, once a block, and ``T * 41k`` to score).
The train forward (no cache) is the up-projected order over the chunk
itself.

A latent page is ``[page_size, latent_page_width]`` (the latent out to
whole lane tiles, the tail zero: ``ModelSpec.latent_page_width``); a
layer's pool leaf is one array ``[num_pages, page_size,
latent_page_width]``: there is no V pool, the values are the first
``kv_lora_rank`` columns of the same page.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data.configs import ModelSpec

NEG_INF = -1e9  # transformer.NEG_INF

#: the longest chunk that runs absorbed. By operations the two orders
#: cross where a key's up-projection (2 * r * H * (dn + dv) = 16.8M at the
#: published sizes) equals what absorbing adds to each of the chunk's
#: queries (2 * H * (r + dr) - 2 * H * (dn + dr + dv) = 98k): T = 171. The
#: prefill lattice's classes are powers of two, so the constant sits at the
#: class below the crossing: a question of up to 128 tokens over a cached
#: document is scored absorbed, a chunk of 256 or more up-projected. The
#: up-projected order also writes and reads 32 KiB of kn and v a key where
#: the absorbed one reads the page's 1,152 bytes (PERF.md section 6, PR 34,
#: has both orders timed alone on the chip at 128 and 256).
ABSORB_MAX_T = 128

#: keys a block of the blocked readers holds (whole pages of them)
BLOCK_KEYS = 512


def yarn_inv_freq(spec: ModelSpec) -> np.ndarray:
    """The rotated dims' frequencies [qk_rope_head_dim / 2], float32, as
    host constants. ``rope_factor`` 0: plain RoPE. Else "deepseek_yarn":
    frequency k is blended between ``theta^(-2k/d)`` (kept where a turn is
    short against the original context) and the same over ``rope_factor``
    (where it is long), linearly between the dims at which
    ``rope_beta_fast`` and ``rope_beta_slow`` turns fit the original
    context."""
    d = spec.qk_rope_head_dim
    base = spec.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if spec.rope_factor <= 1.0:
        return base.astype(np.float32)

    def dim_of(turns):
        return d * math.log(
            spec.rope_original_positions / (turns * 2 * math.pi)
        ) / (2 * math.log(spec.rope_theta))

    lo = max(math.floor(dim_of(spec.rope_beta_fast)), 0)
    hi = min(math.ceil(dim_of(spec.rope_beta_slow)), d - 1)
    ramp = np.clip(
        (np.arange(d // 2, dtype=np.float64) - lo) / max(hi - lo, 1e-3),
        0.0, 1.0,
    )
    keep = 1.0 - ramp
    return ((1.0 - keep) * base / spec.rope_factor + keep * base).astype(
        np.float32
    )


def score_scale(spec: ModelSpec) -> float:
    """``(dn + dr)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) +
    1``: YaRN's attention factor, squared because it stands for a factor on
    both q and k. (The cos/sin tables carry ``mscale / mscale_all_dim``,
    which is 1 where the two are equal, as they are published.)"""
    scale = (spec.qk_nope_head_dim + spec.qk_rope_head_dim) ** -0.5
    if spec.rope_factor > 1.0 and spec.rope_mscale_all_dim:
        m = 0.1 * spec.rope_mscale_all_dim * math.log(spec.rope_factor) + 1.0
        scale *= m * m
    return scale


def _rope(x, positions, inv_freq):
    """Rotate interleaved pairs (2k, 2k+1) of the last axis of ``x``
    [B, T, ..., d] by ``positions`` [B, T], in float32."""
    freqs = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    emb = jnp.repeat(freqs, 2, axis=-1)  # [B, T, d]: f0 f0 f1 f1 ...
    emb = emb.reshape(emb.shape[:2] + (1,) * (x.ndim - 3) + emb.shape[2:])
    x32 = x.astype(jnp.float32)
    rot = jnp.stack([-x32[..., 1::2], x32[..., ::2]], axis=-1).reshape(
        x32.shape
    )
    return (x32 * jnp.cos(emb) + rot * jnp.sin(emb)).astype(x.dtype)


def init_attn_params(keys, spec: ModelSpec, n_layers: int, dtype, init,
                     resid_scale) -> dict:
    """The five projections and the latent norm, stacked [n_layers, ...]:
    ``wq`` [d, H * (dn + dr)], ``w_dkv`` [d, r + dr], ``kv_norm`` [r],
    ``w_uk`` [r, H, dn], ``w_uv`` [r, H, dv], ``wo`` [H * dv, d]. The two
    up-projections are kept by head: a decode step applies them as one
    small dot a head (the absorption), and a matrix [r, H * dn] is laid
    out again for that at every step (8 MiB a layer each, in the compiled
    step's text); the long chunks' plain product pays the relayout
    instead, once a call."""
    d, H, r = spec.d_model, spec.n_head, spec.kv_lora_rank
    dn, dr, dv = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                  spec.v_head_dim)

    def stack(key, shape, scale=0.02):
        return init(key, (n_layers, *shape), dtype, scale)

    return {
        "wq": stack(keys[0], (d, H * (dn + dr))),
        "w_dkv": stack(keys[1], (d, r + dr)),
        "kv_norm": {"scale": jnp.ones((n_layers, r), dtype)},
        "w_uk": stack(keys[2], (r, H, dn)),
        "w_uv": stack(keys[3], (r, H, dv)),
        "wo": stack(keys[4], (H * dv, d), resid_scale),
    }


def project(spec: ModelSpec, attn: dict, x, positions, rms_norm):
    """The mixer's front half on the normed input ``x`` [B, T, D]:
    ``(qn [B, T, H, dn], qr [B, T, H, dr], latent [B, T, r + dr])``, the
    rotated parts rotated, the latent's ``c`` normed: what the cache
    holds."""
    B, T, _ = x.shape
    H, r = spec.n_head, spec.kv_lora_rank
    dn, dr = spec.qk_nope_head_dim, spec.qk_rope_head_dim
    with jax.named_scope("q_proj"):
        q = x @ attn["wq"].astype(x.dtype)
    with jax.named_scope("kv_down"):
        ckr = x @ attn["w_dkv"].astype(x.dtype)
    if T == 1:
        # a decode step: keep the dots 2-D so the weights stream as they
        # are stored (transformer._qkv has the measurement)
        q, ckr = jax.lax.optimization_barrier((q, ckr))
    inv_freq = yarn_inv_freq(spec)
    with jax.named_scope("q_proj"):
        q = q.reshape(B, T, H, dn + dr)
        qn, qr = q[..., :dn], _rope(q[..., dn:], positions, inv_freq)
    with jax.named_scope("kv_down"):
        c = rms_norm(attn["kv_norm"], ckr[..., :r])
        kr = _rope(ckr[..., r:], positions, inv_freq)
        latent = jnp.concatenate([c, kr], axis=-1)
    return qn, qr, latent


def attend_chunk(spec: ModelSpec, attn: dict, qn, qr, latent, mask_bias):
    """The up-projected order with no cache: the chunk's own latents are
    its keys (the train forward). mask_bias [B, 1, T, T]; returns
    [B, T, H, dv]."""
    r = spec.kv_lora_rank
    c, kr = latent[..., :r], latent[..., r:]
    kn = jnp.einsum("bkr,rhn->bkhn", c,
                    attn["w_uk"].astype(c.dtype))
    v = jnp.einsum("bkr,rhv->bkhv", c,
                   attn["w_uv"].astype(c.dtype))
    s = (jnp.einsum("bqhn,bkhn->bhqk", qn, kn)
         + jnp.einsum("bqhd,bkd->bhqk", qr, kr)).astype(jnp.float32)
    probs = jax.nn.softmax(s * score_scale(spec) + mask_bias, axis=-1)
    return jnp.einsum("bhqk,bkhv->bqhv", probs.astype(v.dtype), v)


def absorb_query(spec: ModelSpec, attn: dict, qn, qr):
    """``[q~ ; qr]`` [B, T, H, r + dr]: the query the absorbed order
    scores latents with, ``q~_i = Wuk_i^T qn_i``."""
    qa = jnp.einsum(
        "bthn,rhn->bthr", qn,
        attn["w_uk"].astype(qn.dtype),
    )
    return jnp.concatenate([qa, qr], axis=-1)


def unabsorb_output(spec: ModelSpec, attn: dict, u):
    """``o_i = Wuv_i u_i``: [B, T, H, r] -> [B, T, H, dv]."""
    return jnp.einsum(
        "bthr,rhv->bthv", u,
        attn["w_uv"].astype(u.dtype),
    )


def attend_pages(spec: ModelSpec, attn: dict, qn, qr, pages, table, q_pos,
                 page_size: int):
    """Attention of a chunk's queries (logical positions ``q_pos``
    [B, T]) against the latents a page table holds, in blocks of whole
    pages with a running softmax, in the order the static ``T`` picks.
    Table entry ``i`` holds logical page ``i``; key position ``kp`` is seen
    by query position ``qp`` iff ``kp <= qp``; an entry at the sentinel is
    seen by nobody. The loop stops at the last block any query reaches.
    Up-projected, ``kn`` and ``v`` exist a block at a time, never for the
    whole context (at 40k tokens whole is 1.3 GB a layer). Returns
    [B, T, H, dv]."""
    B, T, H, dn = qn.shape
    num_pages, ps, _ = pages.shape
    W = spec.latent_width  # a page's columns past it are zero padding
    if ps != page_size:
        raise ValueError(f"pool page size {ps} != page_size {page_size}")
    r, dv = spec.kv_lora_rank, spec.v_head_dim
    absorbed = T <= ABSORB_MAX_T
    ppb = max(1, BLOCK_KEYS // ps)
    n_entries = table.shape[1]
    n_blocks = -(-n_entries // ppb)
    table = jnp.pad(table, ((0, 0), (0, n_blocks * ppb - n_entries)),
                    constant_values=num_pages)
    scale = score_scale(spec)
    reach = jnp.max(q_pos // ps) + 1
    hi = jnp.clip(-(-reach // ppb), 1, n_blocks)
    qp = q_pos[:, None, :, None]  # [B, 1, T, 1]
    if absorbed:
        with jax.named_scope("absorb"):
            qa = absorb_query(spec, attn, qn, qr)  # [B, T, H, r + dr]
        width = r
    else:
        w_uk = attn["w_uk"].astype(qn.dtype)
        w_uv = attn["w_uv"].astype(qn.dtype)
        width = dv

    def body(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, i * ppb, ppb, axis=1)
        live = ids < num_pages  # [B, ppb]
        ids = jnp.where(live, ids, 0)
        lat = pages[ids][..., :W].reshape(B, ppb * ps, W).astype(qn.dtype)
        kp = (i * ppb * ps + jnp.arange(ppb * ps))[None, None, None, :]
        seen = (kp <= qp) & jnp.repeat(live, ps, axis=1)[:, None, None, :]
        if absorbed:
            s = jnp.einsum("bthw,bkw->bhtk", qa, lat)
            vals = lat[..., :r]
        else:
            c, kr = lat[..., :r], lat[..., r:]
            kn = jnp.einsum("bkr,rhn->bkhn", c, w_uk)
            vals = jnp.einsum("bkr,rhv->bkhv", c, w_uv)
            s = (jnp.einsum("bthn,bkhn->bhtk", qn, kn)
                 + jnp.einsum("bthd,bkd->bhtk", qr, kr))
        s = jnp.where(seen, s.astype(jnp.float32) * scale, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        probs = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        l = alpha * l + probs.sum(-1)
        probs = probs.astype(vals.dtype)
        if absorbed:
            new = jnp.einsum("bhtk,bkr->bhtr", probs, vals)
        else:
            new = jnp.einsum("bhtk,bkhv->bhtv", probs, vals)
        acc = acc * alpha[..., None] + new.astype(jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((B, H, T), 2.0 * NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    acc0 = jnp.zeros((B, H, T, width), jnp.float32)
    with jax.named_scope("ctx_attn"):
        _, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qn.dtype)
        out = out.transpose(0, 2, 1, 3)  # [B, T, H, width]
    if absorbed:
        with jax.named_scope("absorb"):
            out = unabsorb_output(spec, attn, out)
    return out


def write_pages(pages, latent, cache_row_offsets, page_table, page_size):
    """Scatter the fresh latents of token j of row b to logical position
    ``cache_row_offsets[b] + j`` through the row's page table; entries past
    the table, or at the sentinel, drop. One scatter into the one leaf: a
    donated pool is written in place."""
    T = latent.shape[1]
    num_pages, max_pages = pages.shape[0], page_table.shape[1]
    with jax.named_scope("kv_write"):
        latent = jnp.pad(latent, ((0, 0), (0, 0), (
            0, pages.shape[-1] - latent.shape[-1])))
        pos_buf = cache_row_offsets[:, None] + jnp.arange(T)[None, :]
        page_idx = pos_buf // page_size
        pids = jnp.where(
            page_idx < max_pages,
            jnp.take_along_axis(
                page_table, jnp.minimum(page_idx, max_pages - 1), axis=1
            ),
            num_pages,
        )
        return pages.at[pids, pos_buf % page_size].set(
            latent.astype(pages.dtype), mode="drop"
        )
