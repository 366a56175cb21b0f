"""Ring attention: exact sequence-parallel attention over the ``sp`` mesh axis.

Long-context attention the TPU way — the capability the reference caps at a
512-token context because nothing in its stack shards the sequence dimension
(reference: configs/ppo_config.yml:9; SURVEY §5 "long-context: absent").

Design (blockwise ring, à la Liu et al. ring attention):

- Activations are sharded over ``sp`` on the sequence dim. Each device holds
  one query block [B, T/sp, H, hd] plus one key/value block, and computes
  attention against every KV block by rotating KV around the ring with
  `jax.lax.ppermute` — sp-1 hops, each riding neighbouring ICI links.
- Softmax is streamed (flash-style online renormalization: running max,
  running denominator, float32 accumulator), so the full [T, T] score matrix
  is never materialized — memory per device is O(T/sp * T/sp) instead of
  O(T^2), and the whole thing runs inside one `jit`/`shard_map` region that
  XLA overlaps with the ppermute transfers.
- Causality and padding are applied per block from global block indices that
  travel the ring alongside the KV data, so the result is bit-comparable
  (up to float reassociation) to dense `attention_scores` + causal mask.

Composes with the rest of the mesh: batch stays sharded over (dp, fsdp),
heads over tp; only the sequence dim rides sp.
"""

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e9  # matches trlx_tpu.models.transformer.NEG_INF


def _ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_mask: jnp.ndarray,
    *,
    axis_name: str,
    n_blocks: int,
    causal: bool,
    sub_block: int = 512,
) -> jnp.ndarray:
    """Per-device body under shard_map.

    q, k, v: [B, Tc, H, hd] local sequence chunks; kv_mask: [B, Tc] with
    1 = real token. Returns [B, Tc, H, hd].

    Each ring hop streams its KV chunk through `sub_block`-sized pieces
    with the same online-softmax update, so per-device score memory is
    O(Tc * sub_block) — not O(Tc^2) — and very long shards (32k+ over a
    small sp) stay inside HBM headroom.
    """
    B, Tc, H, hd = q.shape
    my_idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    # global sequence positions of this device's query block
    q_pos = my_idx * Tc + jnp.arange(Tc)

    # each device sends its KV block to the next device; after sp-1 hops
    # every device has seen every block
    perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]

    # sub-blocking of each hop's KV chunk (blockwise flash within the hop);
    # round down to a power of two first so a non-pow2 sub_block (e.g.
    # 1536) lands on 1024 against a pow2 shard instead of collapsing to 1
    sub = min(sub_block, Tc)
    sub = 1 << (sub.bit_length() - 1)
    while Tc % sub != 0:  # odd Tc degrades gracefully (sub=1 divides)
        sub //= 2
    n_sub = Tc // sub

    def accumulate(k_blk, v_blk, mask_blk, blk_idx, m_run, l_run, acc):
        """Online-softmax update of (m, l, acc) with one hop's KV chunk,
        streamed in `sub`-wide pieces."""

        def sub_step(carry, xs):
            m_run, l_run, acc = carry
            k_s, v_s, mask_s, offsets = xs  # [B?, sub, ...] pieces
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k_s).astype(
                jnp.float32
            ) * scale
            bias = jnp.where(mask_s[:, None, None, :] > 0, 0.0, NEG_INF)
            if causal:
                kv_pos = blk_idx * Tc + offsets
                bias = bias + jnp.where(
                    q_pos[:, None] >= kv_pos[None, :], 0.0, NEG_INF
                )[None, None, :, :]
            s = s + bias

            m_new = jnp.maximum(m_run, s.max(-1))
            # m_new is always finite (scores bounded below by NEG_INF), so
            # this is 0 on the -inf init and a plain rescale afterwards
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = alpha * l_run + p.sum(-1)
            acc_new = alpha[..., None] * acc + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_s.dtype), v_s
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        if n_sub == 1:
            (m, l, acc), _ = sub_step(
                (m_run, l_run, acc),
                (k_blk, v_blk, mask_blk, jnp.arange(Tc)),
            )
            return m, l, acc
        k_sub = k_blk.reshape(B, n_sub, sub, H, hd).swapaxes(0, 1)
        v_sub = v_blk.reshape(B, n_sub, sub, H, hd).swapaxes(0, 1)
        mask_sub = mask_blk.reshape(B, n_sub, sub).swapaxes(0, 1)
        offsets = jnp.arange(Tc).reshape(n_sub, sub)
        (m, l, acc), _ = jax.lax.scan(
            sub_step, (m_run, l_run, acc), (k_sub, v_sub, mask_sub, offsets)
        )
        return m, l, acc

    # initial accumulators derived from q (not jnp.zeros) so they carry q's
    # varying-mesh-axes type — scan carries must keep a consistent vma type
    # under shard_map
    base = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * 0.0  # [B, H, Tc, hd]
    # local block first, then n-1 rotations — the final block is consumed
    # without a further (wasted) ppermute hop
    m, l, acc = accumulate(
        k, v, kv_mask, my_idx, base[..., 0] - jnp.inf, base[..., 0], base
    )

    def step(carry, _):
        k_blk, v_blk, mask_blk, blk_idx, m_run, l_run, acc = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        mask_blk = jax.lax.ppermute(mask_blk, axis_name, perm)
        blk_idx = jax.lax.ppermute(blk_idx, axis_name, perm)
        m_new, l_new, acc_new = accumulate(
            k_blk, v_blk, mask_blk, blk_idx, m_run, l_run, acc
        )
        return (k_blk, v_blk, mask_blk, blk_idx, m_new, l_new, acc_new), None

    if n_blocks > 1:
        (_, _, _, _, m, l, acc), _ = jax.lax.scan(
            step, (k, v, kv_mask, my_idx, m, l, acc), None,
            length=n_blocks - 1,
        )

    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_mask: jnp.ndarray,
    mesh: Mesh,
    *,
    axis: str = "sp",
    causal: bool = True,
    sub_block: int = 512,
) -> jnp.ndarray:
    """Sequence-parallel attention over `mesh` axis ``axis``.

    q, k, v: [B, T, H, hd] with T divisible by mesh.shape[axis];
    kv_mask: [B, T] (1 = real token). Batch is treated as sharded over
    (dp, fsdp), heads over tp, sequence over `axis`. `sub_block` bounds
    per-device score memory to O(T/sp * sub_block).
    """
    n = mesh.shape[axis]
    if q.shape[1] % n != 0:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by {axis}={n}"
        )
    # shard batch/head dims only where the mesh axis divides them — a dim
    # that doesn't divide is computed replicated, which is correct, just
    # less parallel (tiny test shapes; real workloads divide). Warn loudly:
    # in a production sharded jit a non-divisible batch would all-gather
    # the GLOBAL batch per layer.
    n_data = mesh.shape["dp"] * mesh.shape["fsdp"]
    batch_ax = ("dp", "fsdp") if q.shape[0] % n_data == 0 else None
    head_ax = "tp" if q.shape[2] % mesh.shape["tp"] == 0 else None
    bad = []
    if batch_ax is None:
        bad.append(f"batch {q.shape[0]} vs dp*fsdp={n_data}")
    if head_ax is None:
        bad.append(f"heads {q.shape[2]} vs tp={mesh.shape['tp']}")
    if bad:
        import warnings

        warnings.warn(
            f"ring_attention: {'; '.join(bad)} — dimension(s) do not "
            f"divide their mesh axes; computing them REPLICATED on every "
            f"device (correct but unsharded — each device gathers the "
            f"global dimension per layer). Pad to a multiple of the mesh "
            f"extent for real workloads.",
            stacklevel=2,
        )
    qkv_spec = P(batch_ax, axis, head_ax, None)
    mask_spec = P(batch_ax, axis)
    local = functools.partial(
        _ring_attention_local, axis_name=axis, n_blocks=n, causal=causal,
        sub_block=sub_block,
    )
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
    )(q, k, v, kv_mask)


def make_sp_attention_fn(mesh: Mesh, axis: str = "sp", causal: bool = True):
    """An `attention_fn` for the transformer trunk (see
    trlx_tpu.models.transformer.block_apply) that runs ring attention over
    the mesh's ``sp`` axis.

    The returned fn takes the RAW [B, T] attention mask in place of the
    [B, 1, T, T] additive bias (`takes_raw_mask = True`), so the trunk never
    materializes a T x T mask — the point of sequence parallelism.
    """

    def sp_attention(q, k, v, attention_mask):
        return ring_attention(
            q, k, v, attention_mask, mesh, axis=axis, causal=causal
        )

    sp_attention.takes_raw_mask = True
    return sp_attention
