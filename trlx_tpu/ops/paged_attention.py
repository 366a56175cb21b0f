"""Fused paged-attention DECODE kernel (serve-side, Pallas TPU).

The jnp paged decode path (transformer.block_apply's paged mode) is a
memory-bound three-step: gather every slot's K/V pages back into logical
order ([S, max_pages * page_size, Hkv, hd] materialized in HBM), score
the single fresh query row against it, throw the gathered copy away.
At decode batch sizes that gather is a large share of the step's
attention (PERF.md section 5 has the chip's breakdown). This module
removes it, following the PagedAttention (vLLM) design on the TPU grid
model. The per-slot page table rides in as a **scalar-prefetch** operand
(host int32 — data, never shape) and names the pages to fetch; the
gathered [T, hd] context never exists in HBM. Two walks share the
contract, the bias and the online-softmax recurrence (running max /
denominator / f32 accumulator, as in ops/pallas_attention's flash
kernel); ``folds_pages`` picks by the pool's shape alone:

**The block walk** (``_block_kernel``; pools whose ``(Hkv, hd)`` fill
whole tiles, ``Hkv % 8 == 0 and hd % 128 == 0``, in the bf16 tier).
Grid ``(slot,)``; the pool stays in HBM (``memory_space=pl.ANY``). A
slot's program walks only the blocks its live extent reaches — a
``fori_loop`` to ``ceil(live / P)``, so a short request in a table sized
for long ones costs its own pages and no grid step more. A block is
``P`` pages, whichever the table names (they are not adjacent in the
pool): ``2P`` async copies into one of two VMEM slots, started one
block ahead of the block being scored, the last block of a slot starting
the first of the next slot. ``P`` is what ``BLOCK_VMEM_BYTES`` holds of
this pool's pages (``block_plan``: 8 pages of 64 tokens at 8 kv heads of
128), so each class of page gets its own from the same rule. A fetched page's ``(page_size, Hkv)`` is folded into
one key axis — a reshape of leading dimensions, free where ``Hkv``
fills the sublane tile — and ALL ``H`` query rows are scored against it
in one ``[H, hd] x [hd, page_size * Hkv]`` dot; a key whose kv head is
not the row's is put at the carry's floor (``FLOOR``, selected, not
added onto the validity bias), so its probability is ``exp(FLOOR - m) =
0`` exactly and ``probs x V`` is one more dot over the same folded
axis. That is ``Hkv`` times the MXU work the scores need, in dots that
fill its tiles, in place of ``2 * Hkv`` dots a page that fill none and a
strided per-head load each. The validity bias reaches the folded axis
through a 0/1 matrix on the MXU (one ``[P, page_size] x [page_size,
keys]`` product a block, exact: one 1.0 a column), so no expanded copy
of it exists in HBM either. The carry is one ``[H, .]`` update a page,
in registers across the slot's whole walk.

**The page walk** (``_page_kernel``; every other pool). Grid ``(slot,
page)``: each page-step's BlockSpec index map reads ``page_table[s,
p]`` and DMAs exactly that page — the whole page across every kv head,
``(1, page_size, Hkv, hd)`` — into VMEM, a static loop over the kv
heads scores it, and steps past the live extent are skipped whole. It
is what Mosaic lowers where it cannot slice a page out of HBM: at
gpt2's 12 or 25 heads of 64 the pool's tiles are padded and a page is
no aligned slice of them; the int8 tier's ``(page_size, Hkv)`` scale
pages have ``Hkv`` of 128 lanes. Its blocks keep their last two
dimensions FULL (Pallas' TPU block rule): the pool block ends in
``(Hkv, hd)``, the query/output ride as ``[S, Hkv, G, hd]``, the bias as
``[S, max_pages, 1, page_size]``, the int8 scales as whole ``(page_size,
Hkv)`` pages, dequantized **inside** the kernel right after the DMA, so
the bf16 copy of a page also never exists in HBM.

Validity is the SAME additive bias row the jnp path uses (``0`` /
``NEG_INF`` per logical position, from the slot's ``valid`` lane), so
sentinel entries — clamped to page 0 for the DMA — and the stale pages
of a wrapped ring contribute exactly-zero probability, identically to
the jnp gather's clamp. The pool layout ``[num_pages, page_size, Hkv,
hd]`` is the one the jnp path, serve/layouts.py's head-dim sharding and
every paged test share.

``make_paged_decode_fn`` adapts the kernel to the seam
``transformer.block_apply`` exposes (``paged_decode_fn``) and wraps it
in shard_map under a serve mesh — KV pools and attention heads shard on
``tp`` (serve/layouts.py) and a bare Mosaic custom call has no GSPMD
rule, so the wrapper is what keeps tp=2 greedy parity (PR 11) intact;
each shard picks its walk from the heads it holds.

Off-TPU the same kernel logic runs through the Pallas interpreter
(ops/pallas_mode.py decides, never a caller) — the ``make kernels``
target and tests/test_paged_kernel.py exercise both walks without
hardware; tests/test_kernel_lowering.py lowers them for the TPU from the
CPU host, tests/test_zz_chip_smoke.py compiles them through Mosaic for
a described v5e, and chip_smoke.py checks them against the jnp path on
the chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops import pallas_mode

NEG_INF = -1e9  # matches trlx_tpu.models.transformer.NEG_INF

#: the online-softmax carry's floor: under every score the bias can make
#: (a masked key reads about NEG_INF), so ``exp(FLOOR - m)`` is exactly 0
#: against any running max a key has set
FLOOR = NEG_INF * 2.0

#: VMEM the block kernel gives its K and V blocks, both double-buffered
#: (4 buffers of ``P`` pages each). Timed alone on a v5e at 2, 4, 8 and 16
#: MiB (PERF.md section 6, PR 32): 4 MiB is the fastest or within 1% of it
#: at every shape tried; more only lengthens the fetch a slot's first
#: block waits for and the padding of its last
BLOCK_VMEM_BYTES = 4 * 2**20


def folds_pages(pool_shape, dtype) -> bool:
    """Whether this pool is walked in blocks (``_block_kernel``) or a page
    a grid step (``_page_kernel``). Mosaic slices a page out of a pool
    left in HBM, and folds its ``(page_size, Hkv)`` into one key axis,
    only where the page's last two dimensions fill whole tiles: at gpt2's
    12 heads of 64 it says "Slice shape along dimension 2 must be aligned
    to tiling (8), but is 12". The int8 tier's ``(page_size, Hkv)`` scale
    pages never fill a lane tile, so it walks page-steps too."""
    _, _, Hkv, hd = pool_shape
    return jnp.dtype(dtype) != jnp.int8 and Hkv % 8 == 0 and hd % 128 == 0


def block_plan(pool_shape, dtype, max_pages: int) -> tuple:
    """``(pages a block, blocks a table)`` for one class of page: what
    BLOCK_VMEM_BYTES holds of this pool's pages (K and V, double-buffered),
    at most the table, evened out over the blocks so the last one is not
    mostly padding. Pages of 128 KiB (64 tokens of 8 kv heads of 128 in
    bfloat16) come 8 at most: a ring of 66 walks 9 blocks of 8, a table of
    454 walks 57 of 8. A pool ``folds_pages`` refuses: ``(1,
    max_pages)``."""
    if not folds_pages(pool_shape, dtype):
        return 1, max_pages
    _, page_size, Hkv, hd = pool_shape
    page_bytes = page_size * Hkv * hd * jnp.dtype(dtype).itemsize
    most = max(1, min(max_pages, BLOCK_VMEM_BYTES // (4 * page_bytes)))
    blocks = -(-max_pages // most)
    return -(-max_pages // blocks), blocks


def grid_steps(pool_shape, dtype, slots: int, max_pages: int) -> int:
    """Grid steps of one call: a slot a step where its blocks are walked
    inside the step, a page a step otherwise."""
    return slots if folds_pages(pool_shape, dtype) else slots * max_pages


# --------------------------------------------------------------------- #
# the block kernel: P pages a step of the walk, one dot a page
# --------------------------------------------------------------------- #


def _block_kernel(
    # scalar prefetch
    pt_ref,  # [S, blocks * P] int32 page table, sentinel-padded
    live_ref,  # [S] int32 leading table entries that hold a visible key
    # operands
    q_ref,  # [1, H, hd] this slot's query rows
    own_ref,  # [H, keys] 1.0 where the key's kv head is the row's
    fold_ref,  # [page_size, keys] 1.0 where the key is the position's
    bias_ref,  # [1, blocks, P, page_size] additive 0/NEG_INF validity bias
    k_hbm,  # [num_pages, page_size, Hkv, hd], left in HBM
    v_hbm,
    o_ref,  # [1, H, hd]
    # scratch
    k_buf,  # [2, P, page_size, Hkv, hd] VMEM
    v_buf,
    sem,  # DMA semaphores [2 slots, K / V]
    state,  # SMEM int32 [2]: blocks walked so far; the row prefetched
):
    s_id, S = pl.program_id(0), pl.num_programs(0)
    num_pages = k_hbm.shape[0]
    _, P, page_size, Hkv, hd = k_buf.shape
    keys = page_size * Hkv
    H = q_ref.shape[1]

    @pl.when(s_id == 0)
    def _first_row():
        state[0] = 0
        state[1] = -1

    def copies(row, block, slot):
        """The 2P page copies of one block into one slot. Entries past the
        row's extent are sentinel or stale: both are clamped to a real
        page, which the bias zeroes."""
        out = []
        for j in range(P):
            pid = pt_ref[row, block * P + j]
            pid = jnp.where((pid >= 0) & (pid < num_pages), pid, 0)
            out.append(pltpu.make_async_copy(
                k_hbm.at[pid], k_buf.at[slot, j], sem.at[slot, 0]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[pid], v_buf.at[slot, j], sem.at[slot, 1]))
        return out

    def start(row, block, slot):
        for c in copies(row, block, slot):
            c.start()

    # a row walks the blocks its live extent reaches and no more; the
    # slots alternate over the whole call, so the last block of a row can
    # fetch the first of the next
    blocks = pl.cdiv(live_ref[s_id], P)
    walked = state[0]

    @pl.when((blocks > 0) & (state[1] != s_id))
    def _fetch_first():
        start(s_id, 0, walked % 2)

    q = q_ref[0]  # [H, hd], compute dtype
    own = own_ref[...] > 0.5
    fold = fold_ref[...]
    scale = jax.lax.rsqrt(jnp.float32(hd))
    next_row = jnp.minimum(s_id + 1, S - 1)

    def block_step(b, carry):
        slot = (walked + b) % 2

        @pl.when(b + 1 < blocks)
        def _fetch_next_block():
            start(s_id, b + 1, 1 - slot)

        @pl.when((b + 1 == blocks) & (s_id + 1 < S)
                 & (live_ref[next_row] > 0))
        def _fetch_next_row():
            start(next_row, 0, 1 - slot)
            state[1] = next_row

        for c in copies(s_id, b, slot):
            c.wait()

        # the block's bias rows over the folded key axis: position t's
        # value at each of its Hkv keys, exactly (one 1.0 a column)
        bias = jax.lax.dot_general(
            bias_ref[0, b], fold,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # [P, keys]
        m, l, acc = carry
        for j in range(P):  # static: one online-softmax update a page
            # (page_size, Hkv) folded into one key axis: every query row
            # against every key of the page in one MXU-sized dot, the
            # keys of other kv heads then put at the carry's floor
            k = k_buf[slot, j].reshape(keys, hd)
            v = v_buf[slot, j].reshape(keys, hd)
            s = jax.lax.dot_general(
                q, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, keys]
            s = jnp.where(own, s * scale + bias[j:j + 1], FLOOR)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            probs = jnp.exp(s - m_new)
            l = alpha * l + probs.sum(axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                probs.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m = m_new
        return m, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, blocks, block_step,
        (jnp.full((H, 1), FLOOR, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, hd), jnp.float32)),
    )
    state[0] = walked + blocks
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _walk_blocks(q, k_pages, v_pages, page_table, bias4, live, P, blocks):
    S, H, hd = q.shape
    num_pages, page_size, Hkv, _ = k_pages.shape
    max_pages = page_table.shape[1]
    keys = page_size * Hkv
    pad = blocks * P - max_pages
    # the table and the bias out to whole blocks: sentinel entries, masked
    table = jnp.pad(page_table, ((0, 0), (0, pad)), constant_values=num_pages)
    bias = jnp.pad(bias4[:, :, 0, :], ((0, 0), (0, pad), (0, 0)),
                   constant_values=NEG_INF).reshape(S, blocks, P, page_size)
    # the folded key axis: key (t, h) of a page sits at t * Hkv + h
    key = np.arange(keys)[None, :]
    own = jnp.asarray(
        np.arange(H)[:, None] // (H // Hkv) == key % Hkv, jnp.float32)
    fold = jnp.asarray(
        np.arange(page_size)[:, None] == key // Hkv, jnp.float32)
    row = pl.BlockSpec((1, H, hd), lambda s, pt, live: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            row,
            pl.BlockSpec((H, keys), lambda s, pt, live: (0, 0)),
            pl.BlockSpec((page_size, keys), lambda s, pt, live: (0, 0)),
            pl.BlockSpec((1, blocks, P, page_size),
                         lambda s, pt, live: (s, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, P, page_size, Hkv, hd), k_pages.dtype),
            pltpu.VMEM((2, P, page_size, Hkv, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        _block_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # rows in order: each fetches the next one's first block
            dimension_semantics=("arbitrary",),
            # the four page buffers, and room for the scores of a page,
            # the bias rows and the two constant masks
            vmem_limit_bytes=4 * P * page_size * Hkv * hd
            * k_pages.dtype.itemsize + 12 * 2**20,
        ),
        interpret=pallas_mode.interpret(),
        name="paged_decode_attention",
    )(table, live, q, own, fold, bias, k_pages, v_pages)


# --------------------------------------------------------------------- #
# the page-step kernel: pools Mosaic cannot slice by page, the int8 tier
# --------------------------------------------------------------------- #


def _page_kernel(
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
        m_scr[...] = jnp.full_like(m_scr, FLOOR)
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


def _walk_pages(q, k_codes, v_codes, scales, page_table, bias4, live):
    S, H, hd = q.shape
    num_pages, page_size, Hkv, _ = k_codes.shape
    max_pages = page_table.shape[1]
    G = H // Hkv
    # query heads for kv-head h are the contiguous block [h*G, (h+1)*G)
    # — the same grouping attention_scores' GQA reshape uses
    q4 = q.reshape(S, Hkv, G, hd)

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
    if scales is not None:
        in_specs += [pool_spec(Hkv), pool_spec(Hkv)]
        operands += list(scales)

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
        functools.partial(_page_kernel, quantized=scales is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv, G, hd), q.dtype),
        interpret=pallas_mode.interpret(),
        name="paged_decode_attention",
    )(page_table, live, *operands)
    return out.reshape(S, H, hd)


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

    Returns [S, H, hd] in q's dtype; a row the bias lets nothing through
    for reads zeros. Pure function of its operands: jit/AOT-stable, no
    recompiles across steps.
    """
    quantized = isinstance(k_pages, (tuple, list))
    if quantized:
        (k_codes, k_scales), (v_codes, v_scales) = k_pages, v_pages
        scales = (k_scales, v_scales)
    else:
        k_codes, v_codes, scales = k_pages, v_pages, None
    S, H, hd = q.shape
    _, page_size, Hkv, _ = k_codes.shape
    max_pages = page_table.shape[1]
    if H % Hkv:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    page_table = page_table.astype(jnp.int32)
    bias4 = bias.reshape(S, max_pages, 1, page_size).astype(jnp.float32)

    # leading table entries of each slot with a key the bias lets through:
    # a table is walked that far and no farther (a short request in a pool
    # sized for long ones; a ring the context has not filled)
    seen = (bias4[:, :, 0, :] > 0.5 * NEG_INF).any(-1)  # [S, max_pages]
    live = jnp.max(
        jnp.where(seen, jnp.arange(1, max_pages + 1)[None, :], 0), axis=1
    ).astype(jnp.int32)

    if not folds_pages(k_codes.shape, k_codes.dtype):
        return _walk_pages(q, k_codes, v_codes, scales, page_table, bias4,
                           live)
    return _walk_blocks(
        q, k_codes, v_codes, page_table, bias4, live,
        *block_plan(k_codes.shape, k_codes.dtype, max_pages),
    )


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
