"""Fused paged-attention DECODE kernel (serve-side, Pallas TPU).

The jnp paged decode path (transformer.block_apply's paged mode) is a
memory-bound three-step: gather every slot's K/V pages back into logical
order ([S, max_pages * page_size, Hkv, hd] materialized in HBM), score
the single fresh query row against it, throw the gathered copy away.
At decode batch sizes that gather is a large share of the step's
attention (PERF.md section 5 has the chip's breakdown). This module
removes it, following the PagedAttention (vLLM) design on the TPU grid model:

- Grid ``(slot, page)``; the per-slot page table rides in as a
  **scalar-prefetch** operand (host int32 — data, never shape), so each
  page-step's BlockSpec index map reads ``page_table[s, p]`` and DMAs
  exactly that page of the global pool — the whole page across every kv
  head, ``(1, page_size, Hkv, hd)`` — into VMEM. The gathered [T, hd]
  context never exists in HBM.
- Every block keeps its last two dimensions FULL (Pallas' TPU block
  rule: divisible by (8, 128) or equal to the array's own extent): the
  pool block ends in ``(Hkv, hd)``, the query/output ride as
  ``[S, Hkv, G, hd]`` with block ``(1, Hkv, G, hd)``, the bias as
  ``[S, max_pages, 1, page_size]`` and the int8 scales as whole
  ``(page_size, Hkv)`` pages. A whole page at gpt-j widths is
  64 * 16 * 256 * 2 B = 512 KB per operand — far inside VMEM — and the
  pool layout stays the one the jnp path, serve/layouts.py's head-dim
  sharding and every paged test share.
- Each program holds one slot's query rows and walks the slot's pages
  with an **online-softmax** carry per kv head (running max /
  denominator / f32 accumulator in VMEM scratch, the same recurrence as
  ops/pallas_attention's flash kernel) — a static loop over the kv
  heads, each scoring its ``G = H // Hkv`` query heads (GQA runs
  natively against the compact KV) — writing the attention output once
  on the last page-step.
- Validity is the SAME additive bias row the jnp path uses
  (``0`` / ``NEG_INF`` per logical position, from the slot's ``valid``
  lane), so sentinel pages — clamped to page 0 for the DMA — contribute
  exactly-zero probability, identically to the jnp gather's clamp.
- int8 KV pages (``serve.kv_dtype: int8``) dequantize **inside** the
  kernel: the per-(row, head) scales ride the same page-indexed
  BlockSpecs and multiply the int8 block right after the DMA, so the
  bf16 copy of a page also never exists in HBM.

``make_paged_decode_fn`` adapts the kernel to the seam
``transformer.block_apply`` exposes (``paged_decode_fn``) and wraps it
in shard_map under a serve mesh — KV pools and attention heads shard on
``tp`` (serve/layouts.py) and a bare Mosaic custom call has no GSPMD
rule, so the wrapper is what keeps tp=2 greedy parity (PR 11) intact.

Off-TPU the same kernel logic runs through the Pallas interpreter
(ops/pallas_mode.py decides, never a caller) — the ``make kernels``
target and tests/test_paged_kernel.py exercise it without hardware;
tests/test_kernel_lowering.py lowers and compiles it for the TPU from
the CPU host, and chip_smoke.py checks it against the jnp path on the
chip.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops import pallas_mode

NEG_INF = -1e9  # matches trlx_tpu.models.transformer.NEG_INF


# --------------------------------------------------------------------- #
# kernel
# --------------------------------------------------------------------- #


def _decode_kernel(
    # scalar prefetch
    pt_ref,  # [S, max_pages] int32 page table (host data)
    live_ref,  # [S] int32 leading table entries that hold a visible key
    # tensor operands (per-block views; see BlockSpecs below)
    q_ref,  # [1, Hkv, G, hd] this slot's query rows, grouped by kv head
    k_ref,  # [1, page_size, Hkv, hd] the page the index map gathered
    v_ref,  # [1, page_size, Hkv, hd]
    bias_ref,  # [1, 1, 1, page_size] additive 0/NEG_INF validity bias
    *rest,  # (k_scale_ref, v_scale_ref when quantized), o_ref, scratch
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    s_id, p = pl.program_id(0), pl.program_id(1)
    Hkv, hd = q_ref.shape[1], q_ref.shape[3]

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF * 2.0)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    scale = jax.lax.rsqrt(jnp.float32(hd))

    # past the slot's live extent every key is masked: the page-step is
    # skipped whole (its block index repeats, so nothing is fetched either)
    @pl.when(p < live_ref[s_id])
    def _page():
        _score_page(q_ref, k_ref, v_ref, bias_ref, rest, m_scr, l_scr,
                    acc_scr, scale, quantized)

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        for h in range(Hkv):
            o_ref[0, h] = (
                acc_scr[h] / jnp.maximum(l_scr[h][:, :1], 1e-30)
            ).astype(o_ref.dtype)


def _score_page(q_ref, k_ref, v_ref, bias_ref, rest, m_scr, l_scr, acc_scr,
                scale, quantized):
    """One page against the slot's query rows: the online-softmax update."""
    Hkv = q_ref.shape[1]
    if quantized:
        ks_ref, vs_ref = rest[:2]
    bias = bias_ref[0, 0]  # [1, page_size], broadcasts over G
    for h in range(Hkv):  # static: one online-softmax carry per kv head
        q = q_ref[0, h]  # [G, hd], compute dtype
        k = k_ref[0, :, h, :]  # [page_size, hd]
        v = v_ref[0, :, h, :]
        if quantized:
            # fused dequant: int8 codes x per-(row, head) f32 scale, cast
            # to the compute dtype the jnp oracle dequantizes to
            k = (k.astype(jnp.float32) * ks_ref[0, :, h][:, None]).astype(
                q.dtype
            )
            v = (v.astype(jnp.float32) * vs_ref[0, :, h][:, None]).astype(
                q.dtype
            )
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G, page_size]
        s = s * scale + bias

        m_prev = m_scr[h][:, :1]  # [G, 1]
        l_prev = l_scr[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(s - m_new)
        l_new = alpha * l_prev + probs.sum(axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            probs.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])


def paged_decode_attention(
    q: jnp.ndarray,
    k_pages,
    v_pages,
    page_table: jnp.ndarray,
    bias: jnp.ndarray,
) -> jnp.ndarray:
    """One fused decode step of paged attention.

    q: [S, H, hd] — the fresh token's query row per slot (post-rotary).
    k_pages / v_pages: the global pool for ONE layer — either a plain
        [num_pages, page_size, Hkv, hd] array (bf16 tier) or an
        ``(codes int8 [num_pages, page_size, Hkv, hd],
        scales f32 [num_pages, page_size, Hkv])`` pair (int8 tier).
        The fresh token must already be scattered in (the kernel only
        reads the pool).
    page_table: [S, max_pages] int32; entries >= num_pages are the host
        allocator's sentinel (their DMA is clamped to page 0 and their
        probability masked to exactly zero by ``bias``).
    bias: [S, max_pages * page_size] f32 additive validity bias
        (0 = attend, NEG_INF = masked) over logical positions — the same
        lane the jnp path reshapes into its mask_bias.

    Returns [S, H, hd] in q's dtype. Pure function of its operands:
    jit/AOT-stable, no recompiles across steps.
    """
    quantized = isinstance(k_pages, (tuple, list))
    if quantized:
        k_codes, k_scales = k_pages
        v_codes, v_scales = v_pages
    else:
        k_codes, v_codes = k_pages, v_pages
        k_scales = v_scales = None
    S, H, hd = q.shape
    num_pages, page_size, Hkv, _ = k_codes.shape
    max_pages = page_table.shape[1]
    if H % Hkv:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    G = H // Hkv
    # query heads for kv-head h are the contiguous block [h*G, (h+1)*G)
    # — the same grouping attention_scores' GQA reshape uses
    q4 = q.reshape(S, Hkv, G, hd)
    bias4 = bias.reshape(S, max_pages, 1, page_size).astype(jnp.float32)

    # leading table entries of each slot with a key the bias lets through:
    # a table is walked that far and no farther (a short request in a pool
    # sized for long ones; a ring the context has not filled)
    seen = (bias4[:, :, 0, :] > 0.5 * NEG_INF).any(-1)  # [S, max_pages]
    live = jnp.max(
        jnp.where(seen, jnp.arange(1, max_pages + 1)[None, :], 0), axis=1
    ).astype(jnp.int32)

    def entry_of(s, p, live):
        # past the live extent the last live entry repeats: Pallas fetches
        # a block only when its index changes
        return jnp.minimum(p, jnp.maximum(live[s] - 1, 0))

    def page_of(s, p, pt, live):
        # sentinel (>= num_pages) clamps to page 0: a real DMA target
        # whose contribution the bias then zeroes — mirrors the jnp
        # path's jnp.clip gather
        pid = pt[s, entry_of(s, p, live)]
        return jnp.where(pid < num_pages, pid, 0)

    def pool_spec(*tail):
        return pl.BlockSpec(
            (1, page_size, *tail),
            lambda s, p, pt, live: (
                page_of(s, p, pt, live), *([0] * (len(tail) + 1))
            ),
        )

    q_spec = pl.BlockSpec(
        (1, Hkv, G, hd), lambda s, p, pt, live: (s, 0, 0, 0)
    )
    in_specs = [
        q_spec,
        pool_spec(Hkv, hd),
        pool_spec(Hkv, hd),
        pl.BlockSpec(
            (1, 1, 1, page_size),
            lambda s, p, pt, live: (s, entry_of(s, p, live), 0, 0),
        ),
    ]
    operands = [q4, k_codes, v_codes, bias4]
    if quantized:
        in_specs += [pool_spec(Hkv), pool_spec(Hkv)]
        operands += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, max_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running max (lane-bcast)
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running denominator
            pltpu.VMEM((Hkv, G, hd), jnp.float32),  # f32 output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, G, hd), q.dtype),
        interpret=pallas_mode.interpret(),
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), live, *operands)
    return out.reshape(S, H, hd)


# --------------------------------------------------------------------- #
# the block_apply seam
# --------------------------------------------------------------------- #


def make_paged_decode_fn(mesh=None):
    """Adapter for ``transformer.block_apply(paged_decode_fn=...)``.

    The returned fn has the seam's contract — ``fn(q1, k_pages, v_pages,
    page_table, bias_row)`` with q1 [S, H, hd] and bias_row
    [S, max_pages * page_size] — and runs the fused kernel, under
    shard_map when ``mesh`` spans more than one device: query/output
    heads and the pool's Hkv axis split over ``tp`` (the serve layout,
    serve/layouts.KV_POOL_SPECS), page tables and the bias row replicated
    host-shaped data. Heads tp doesn't divide fall back to replication,
    matching ``layouts._fit_spec_to_shape``.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def paged_decode(q1, k_pages, v_pages, page_table, bias_row):
        if mesh is None or mesh.size == 1:
            return paged_decode_attention(
                q1, k_pages, v_pages, page_table, bias_row
            )
        quantized = isinstance(k_pages, (tuple, list))
        Hkv = (k_pages[0] if quantized else k_pages).shape[2]
        tp = mesh.shape.get("tp", 1)
        head_ax = "tp" if (q1.shape[1] % tp == 0 and Hkv % tp == 0) \
            else None
        q_spec = P(None, head_ax, None)
        pool_spec = P(None, None, head_ax, None)  # [np, ps, Hkv, hd]
        kv_spec = (pool_spec, P(None, None, head_ax)) if quantized \
            else pool_spec
        return shard_map(
            paged_decode_attention,
            mesh=mesh,
            in_specs=(q_spec, kv_spec, kv_spec, P(None, None),
                      P(None, None)),
            out_specs=q_spec,
            # pallas_call's out_shape carries no varying-mesh-axes type;
            # skip the vma check for this purely per-shard kernel
            check_vma=False,
        )(q1, k_pages, v_pages, page_table, bias_row)

    return paged_decode
