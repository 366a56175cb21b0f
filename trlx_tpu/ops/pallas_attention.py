"""Fused (flash-style) causal attention as a Pallas TPU kernel.

The hot op of every trunk forward (reference reaches cuDNN attention through
torch, SURVEY §2.9); here it is a hand-tiled TPU kernel following
/opt/skills/guides/pallas_guide.md:

- Grid (batch * heads, query blocks); each program streams KV blocks from
  VMEM through the MXU with an online-softmax accumulator (running max /
  denominator / f32 accumulator) — the [T, T] score matrix never hits HBM,
  so memory is O(T * block) instead of O(T^2) and the softmax+matmul chain
  is fused into one kernel launch.
- Causality is applied per block; KV blocks entirely above the diagonal are
  skipped via the fori_loop bound (half the FLOPs of a dense causal mask).
- Padding comes in as the raw [B, T] attention mask (1 = real), the same
  contract as trlx_tpu.ops.ring_attention (`takes_raw_mask = True`).
- Backward is two Pallas kernels wired through jax.custom_vjp — a dq pass
  (grid over query blocks, streaming KV) and a dk/dv pass (grid over KV
  blocks, streaming Q), each recomputing probabilities from the saved
  logsumexp and skipping above-diagonal tiles: same O(T * block) memory
  bound as the forward, no T x T tensor in either direction.

The public entry `flash_attention` pads T to a block multiple, reshapes
[B, T, H, hd] -> [B*H, T, hd] for the grid, and restores the layout after.
`make_pallas_attention_fn` adapts it to the transformer's attention_fn seam.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops import pallas_mode

NEG_INF = -1e9  # matches trlx_tpu.models.transformer.NEG_INF


# --------------------------------------------------------------------- #
# forward kernel
# --------------------------------------------------------------------- #


def _flash_fwd_kernel(
    q_ref,  # [1, BQ, hd]
    k_ref,  # [1, T, hd]
    v_ref,  # [1, T, hd]
    mask_ref,  # [1, 1, T] (singleton middle axis satisfies TPU tiling)
    o_ref,  # [1, BQ, hd]
    lse_ref,  # [1, 1, BQ]
    *,
    block_k: int,
    causal: bool,
    scale: float,
):
    iq = pl.program_id(1)
    BQ = q_ref.shape[1]
    T = k_ref.shape[1]
    hd = q_ref.shape[2]

    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, hd]
    q_pos = iq * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, 1), 0)

    num_k_blocks = T // block_k
    if causal:
        # skip KV blocks entirely above the diagonal
        last = (iq + 1) * BQ  # first kv index not attended by this q block
        num_live = jax.lax.min(num_k_blocks, pl.cdiv(last, block_k))
    else:
        num_live = num_k_blocks

    def body(j, carry):
        m_run, l_run, acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        kv_mask = mask_ref[0, :, pl.ds(j * block_k, block_k)]  # [1, BK]

        s = jax.lax.dot_general(
            q, k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK]
        bias = jnp.where(kv_mask > 0, 0.0, NEG_INF)
        if causal:
            kv_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            bias = bias + jnp.where(q_pos >= kv_pos, 0.0, NEG_INF)
        s = s + bias

        m_new = jnp.maximum(m_run, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_run + p.sum(-1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p, v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((BQ, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((BQ, 1), jnp.float32)
    acc0 = jnp.zeros((BQ, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_live, body, (m0, l0, acc0))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l_safe))[:, 0]


def _pad_t(x, multiple, axis, value=0):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _flash_forward(q, k, v, kv_mask, block_q, block_k, causal):
    """Padded + flattened pallas_call. q/k/v: [B, T, H, hd]; mask: [B, T].
    Returns (out [B, T, H, hd], lse [B, H, Tp]).

    Practical T ceiling: each grid program stages the FULL-length K and V
    rows ([1, Tp, hd]) in VMEM (plus q/out blocks), so usable Tp tops out
    around ~32k at hd=128 in bf16 against the ~16 MB/core VMEM budget —
    the kernel targets the single-chip 1k-32k regime. Beyond that, shard
    the sequence instead: the ring-attention sp path
    (trlx_tpu.ops.ring_attention) keeps per-device length T/sp and is the
    designed long-context mechanism."""
    B, T, H, hd = q.shape
    Tp = T + ((-T) % max(block_q, block_k))
    if Tp % block_q != 0 or Tp % block_k != 0:
        raise ValueError(
            f"block_q={block_q} / block_k={block_k} must divide the padded "
            f"length {Tp} (T={T} rounded up to max(block_q, block_k)); "
            f"a grid short of blocks would silently leave trailing query "
            f"rows unwritten"
        )
    qf = _pad_t(q, max(block_q, block_k), 1)
    kf = _pad_t(k, max(block_q, block_k), 1)
    vf = _pad_t(v, max(block_q, block_k), 1)
    maskf = _pad_t(kv_mask, max(block_q, block_k), 1)

    # [B, T, H, hd] -> [B*H, T, hd]
    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, Tp, hd)

    qf, kf, vf = flat(qf), flat(kf), flat(vf)

    grid = (B * H, Tp // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel,
        block_k=block_k,
        causal=causal,
        scale=1.0 / (hd**0.5),
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, block_q, hd), lambda bh, iq: (bh, iq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, Tp, hd), lambda bh, iq: (bh, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, Tp, hd), lambda bh, iq: (bh, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, Tp), lambda bh, iq, H=H: (bh // H, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, block_q, hd), lambda bh, iq: (bh, iq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_q), lambda bh, iq: (bh, 0, iq),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, hd), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Tp), jnp.float32),
        ],
        interpret=pallas_mode.interpret(),
    )(qf, kf, vf, maskf[:, None, :])

    out = out.reshape(B, H, Tp, hd).transpose(0, 2, 1, 3)[:, :T]
    return out, lse.reshape(B, H, Tp)  # lse kept at padded length


# --------------------------------------------------------------------- #
# backward kernels (same O(T * block) memory bound as the forward)
# --------------------------------------------------------------------- #


def _flash_bwd_dq_kernel(
    q_ref,  # [1, BQ, hd] (input dtype; scaled in-kernel)
    k_ref,  # [1, Tp, hd]
    v_ref,  # [1, Tp, hd]
    g_ref,  # [1, BQ, hd]
    lse_ref,  # [1, 1, BQ]
    dD_ref,  # [1, 1, BQ]  (rowsum(dO * O))
    mask_ref,  # [1, 1, Tp]
    dq_ref,  # [1, BQ, hd]
    *,
    block_k: int,
    causal: bool,
    scale: float,
):
    iq = pl.program_id(1)
    BQ = q_ref.shape[1]
    Tp = k_ref.shape[1]
    hd = q_ref.shape[2]

    q = q_ref[0].astype(jnp.float32) * scale
    g = g_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, None]  # [BQ, 1]
    dD = dD_ref[0, 0][:, None]
    q_pos = iq * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, 1), 0)

    n_kv = Tp // block_k
    if causal:
        num_live = jax.lax.min(n_kv, pl.cdiv((iq + 1) * BQ, block_k))
    else:
        num_live = n_kv

    def body(j, dq):
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        kv_mask = mask_ref[0, :, pl.ds(j * block_k, block_k)]  # [1, BK]

        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        bias = jnp.where(kv_mask > 0, 0.0, NEG_INF)
        if causal:
            kv_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            bias = bias + jnp.where(q_pos >= kv_pos, 0.0, NEG_INF)
        p = jnp.exp(s + bias - lse)  # [BQ, BK]
        dp = jax.lax.dot_general(
            g, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dD)
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(0, num_live, body, jnp.zeros((BQ, hd), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref,  # [1, Tp, hd] (input dtype; scaled in-kernel)
    k_ref,  # [1, BK, hd]
    v_ref,  # [1, BK, hd]
    g_ref,  # [1, Tp, hd]
    lse_ref,  # [1, 1, Tp]
    dD_ref,  # [1, 1, Tp]
    mask_ref,  # [1, 1, BK]
    dk_ref,  # [1, BK, hd]
    dv_ref,  # [1, BK, hd]
    *,
    block_q: int,
    causal: bool,
    scale: float,
):
    jk = pl.program_id(1)
    BK = k_ref.shape[1]
    Tp = q_ref.shape[1]
    hd = k_ref.shape[2]

    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    kv_mask = mask_ref[0]  # [1, BK]
    kv_pos = jk * BK + jax.lax.broadcasted_iota(jnp.int32, (1, BK), 1)

    n_q = Tp // block_q
    # causal: query blocks strictly before this KV block see none of it
    first_live = (jk * BK) // block_q if causal else 0

    def body(i, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32
        ) * scale
        g_blk = g_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)][:, None]  # [BQ, 1]
        dD = dD_ref[0, 0, pl.ds(i * block_q, block_q)][:, None]

        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK]
        bias = jnp.where(kv_mask > 0, 0.0, NEG_INF)
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0
            )
            bias = bias + jnp.where(q_pos >= kv_pos, 0.0, NEG_INF)
        p = jnp.exp(s + bias - lse)
        dv = dv + jax.lax.dot_general(
            p, g_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            g_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dD)
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        first_live, n_q, body,
        (jnp.zeros((BK, hd), jnp.float32), jnp.zeros((BK, hd), jnp.float32)),
    )
    # dk is w.r.t. the pre-scaled s = (q*scale) k^T with q already scaled,
    # so no extra factor here
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(res, g, block_q, block_k, causal):
    q, k, v, kv_mask, out, lse = res
    B, T, H, hd = q.shape
    scale = 1.0 / (hd**0.5)
    Tp = lse.shape[-1]  # padded length the forward ran at

    def pad(x):
        return _pad_t(x, Tp, 1)

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, Tp, hd)

    # keep inputs in their storage dtype (bf16 halves the VMEM footprint
    # of the full-length refs); kernels cast per block and scale q inside
    qf, kf, vf, gf = flat(pad(q)), flat(pad(k)), flat(pad(v)), flat(pad(g))
    lse_f = lse.reshape(B * H, 1, Tp)
    # D_i = rowsum(dO * O) — the softmax-jacobian diagonal term
    dD = (
        (gf.astype(jnp.float32) * flat(pad(out)).astype(jnp.float32))
        .sum(-1)
        .reshape(B * H, 1, Tp)
    )
    maskf = pad(kv_mask)[:, None, :]  # [B, 1, Tp]

    interpret = pallas_mode.interpret()
    full = lambda: pl.BlockSpec(  # noqa: E731
        (1, Tp, hd), lambda bh, blk: (bh, 0, 0), memory_space=pltpu.VMEM
    )
    blocked = lambda width: pl.BlockSpec(  # noqa: E731
        (1, width, hd), lambda bh, blk: (bh, blk, 0), memory_space=pltpu.VMEM
    )
    row_full = lambda: pl.BlockSpec(  # noqa: E731
        (1, 1, Tp), lambda bh, blk: (bh, 0, 0), memory_space=pltpu.VMEM
    )
    row_blocked = lambda width: pl.BlockSpec(  # noqa: E731
        (1, 1, width), lambda bh, blk: (bh, 0, blk), memory_space=pltpu.VMEM
    )
    mask_spec_full = pl.BlockSpec(
        (1, 1, Tp), lambda bh, blk, H=H: (bh // H, 0, 0),
        memory_space=pltpu.VMEM,
    )
    mask_spec_blocked = pl.BlockSpec(
        (1, 1, block_k), lambda bh, blk, H=H: (bh // H, 0, blk),
        memory_space=pltpu.VMEM,
    )

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_k=block_k, causal=causal, scale=scale
        ),
        grid=(B * H, Tp // block_q),
        in_specs=[
            blocked(block_q),  # q
            full(),  # k
            full(),  # v
            blocked(block_q),  # g
            row_blocked(block_q),  # lse
            row_blocked(block_q),  # dD
            mask_spec_full,  # mask
        ],
        out_specs=blocked(block_q),
        out_shape=jax.ShapeDtypeStruct((B * H, Tp, hd), jnp.float32),
        interpret=interpret,
    )(qf, kf, vf, gf, lse_f, dD, maskf)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, causal=causal,
            scale=scale,
        ),
        grid=(B * H, Tp // block_k),
        in_specs=[
            full(),  # q
            blocked(block_k),  # k
            blocked(block_k),  # v
            full(),  # g
            row_full(),  # lse
            row_full(),  # dD
            mask_spec_blocked,  # mask
        ],
        out_specs=[blocked(block_k), blocked(block_k)],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Tp, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lse_f, dD, maskf)

    def unflat(x):
        return x.reshape(B, H, Tp, hd).transpose(0, 2, 1, 3)[:, :T]

    return (
        unflat(dq).astype(q.dtype),
        unflat(dk).astype(k.dtype),
        unflat(dv).astype(v.dtype),
        None,
    )


# --------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_mask: jnp.ndarray,
    block_q: int = 128,
    block_k: int = 128,
    causal: bool = True,
) -> jnp.ndarray:
    """Fused causal attention. q/k/v: [B, T, H, hd]; kv_mask: [B, T]
    (1 = real token). Returns [B, T, H, hd] in q's dtype."""
    out, _ = _flash_forward(q, k, v, kv_mask, block_q, block_k, causal)
    return out


def _fwd(q, k, v, kv_mask, block_q, block_k, causal):
    out, lse = _flash_forward(q, k, v, kv_mask, block_q, block_k, causal)
    return out, (q, k, v, kv_mask, out, lse)


def _bwd(block_q, block_k, causal, res, g):
    return _flash_backward(res, g, block_q, block_k, causal)


flash_attention.defvjp(_fwd, _bwd)


# Below this many tokens the kernel can't win (and Mosaic rejects
# sub-128-lane mask blocks on real hardware — confirmed on v5e); the dense
# XLA path handles short batches.
_MIN_FUSED_T = 128


def make_pallas_attention_fn(
    block: int = 128, causal: bool = True, mesh=None,
    min_fused_t: int = None,
):
    """An `attention_fn` for the transformer trunk running the fused Pallas
    kernel. Takes the raw [B, T] mask (`takes_raw_mask = True`) like the
    ring-attention fn — no dense T x T bias is ever built.

    Per-call adaptivity (the actual batch length can differ from the config
    — ILQL pads to each batch's own max): sequences shorter than
    `min_fused_t` (default `_MIN_FUSED_T`; trainers pass their measured
    parity point when the kernel is auto- rather than force-enabled) fall
    back to dense XLA attention. With a `mesh`, the
    kernel runs under shard_map (batch over (dp, fsdp), heads over tp) —
    a bare Mosaic custom call has no GSPMD partitioning rule, so without
    the wrapper a multichip jit would gather the global batch per chip."""
    from trlx_tpu.models.transformer import attention_scores, causal_mask_bias

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    min_t = _MIN_FUSED_T if min_fused_t is None else min_fused_t

    def pallas_attention(q, k, v, attention_mask):
        if q.shape[1] < min_t:
            if causal:
                bias = causal_mask_bias(attention_mask)
            else:  # padding-only: every (real) key visible to every query
                bias = jnp.where(
                    attention_mask[:, None, None, :] > 0, 0.0, NEG_INF
                ).astype(jnp.float32)
            return attention_scores(q, k, v, bias)
        if mesh is None:
            return flash_attention(q, k, v, attention_mask, block, block,
                                   causal)
        n_data = mesh.shape["dp"] * mesh.shape["fsdp"]
        batch_ax = ("dp", "fsdp") if q.shape[0] % n_data == 0 else None
        head_ax = "tp" if q.shape[2] % mesh.shape["tp"] == 0 else None
        qkv_spec = P(batch_ax, None, head_ax, None)
        mask_spec = P(batch_ax, None)
        return shard_map(
            lambda q, k, v, m: flash_attention(q, k, v, m, block, block,
                                               causal),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
            out_specs=qkv_spec,
            # pallas_call's out_shape carries no varying-mesh-axes type;
            # skip the vma check for this purely per-shard kernel
            check_vma=False,
        )(q, k, v, attention_mask)

    pallas_attention.takes_raw_mask = True
    return pallas_attention
