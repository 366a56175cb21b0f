"""The absorbed decode kernel of latent attention (serve-side, Pallas TPU).

A latent-attention model (models/latent.py) caches one latent a token a
layer, ``[c ; kr]`` of ``latent_width`` numbers, and a decode step can
score it without ever forming a head's key or value: with ``q = [Wuk^T qn
; qr]`` (made by plain dots before the call, scope ``absorb``), ``s_j =
scale * q . latent_j`` and ``u = sum_j p_j c_j``, ``c`` being the first
``value_width`` columns of the very page the scores read. There is one
"kv head" and no V pool: every query row of a slot scores every key of a
page, so a block of pages is ONE ``[H, W] x [W, P * page_size]`` dot, one
online-softmax update, and one ``[H, P * page_size] x [P * page_size,
value_width]`` dot over the same buffer. Per cached token that is
``2 * H * (W + value_width)`` operations on ``2 * W`` bytes (139k on
1,152 at the published sizes: 121 operations a byte against a v5e's 240).

The walk is ops/paged_attention's block walk (PR 32): grid ``(slot,)``,
the pool left in HBM, a slot walks only the blocks of pages its live
extent reaches, ``P`` page copies a block into one of two VMEM buffers,
started one block ahead, the last block of a slot starting the first of
the next. ``P`` is what :data:`BLOCK_VMEM_BYTES` holds of this pool's
pages (:func:`block_plan`). Validity is the additive bias row the jnp
path's mask comes from (0 / NEG_INF a logical position), so sentinel
entries — clamped to page 0 for the copy — read exactly-zero probability.

Off-TPU the same kernel runs through the Pallas interpreter
(ops/pallas_mode.py decides).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops import pallas_mode
from trlx_tpu.ops.paged_attention import FLOOR, NEG_INF

#: VMEM for the two buffers of a block's pages (the kernel alone, timed on
#: a v5e at the cell's extents for pages of 64, 128 and 256 tokens:
#: PERF.md section 6, PR 34)
BLOCK_VMEM_BYTES = 4 * 2**20


def page_bytes(pool_shape, dtype) -> int:
    """What one page takes in HBM and in VMEM: the last dimension is laid
    out in whole lane tiles of 128, so a latent of 576 takes 640."""
    _, page_size, width = pool_shape
    return page_size * -(-width // 128) * 128 * jnp.dtype(dtype).itemsize


def block_plan(pool_shape, dtype, max_pages: int) -> tuple:
    """``(pages a block, blocks a table)``: what BLOCK_VMEM_BYTES holds of
    this pool's pages twice over, at most the table, evened out over the
    blocks so the last one is not mostly padding."""
    most = max(1, min(max_pages,
                      BLOCK_VMEM_BYTES // (2 * page_bytes(pool_shape, dtype))))
    blocks = -(-max_pages // most)
    return -(-max_pages // blocks), blocks


def _kernel(
    # scalar prefetch
    pt_ref,  # [S, blocks * P] int32 page table, sentinel-padded
    live_ref,  # [S] int32 leading table entries that hold a visible key
    # operands
    q_ref,  # [1, H, W] this slot's absorbed query rows
    bias_ref,  # [1, blocks, 1, P * page_size] additive 0/NEG_INF bias
    pool_hbm,  # [num_pages, page_size, W], left in HBM
    o_ref,  # [1, H, value_width]
    # scratch
    buf,  # [2, P, page_size, W] VMEM
    sem,  # DMA semaphores [2]
    state,  # SMEM int32 [2]: blocks walked so far; the row prefetched
    *, value_width: int, scale: float,
):
    s_id, S = pl.program_id(0), pl.num_programs(0)
    num_pages = pool_hbm.shape[0]
    _, P, page_size, W = buf.shape
    H = q_ref.shape[1]

    @pl.when(s_id == 0)
    def _first_row():
        state[0] = 0
        state[1] = -1

    def copies(row, block, slot):
        """The P page copies of one block into one buffer; entries past the
        row's extent are sentinel or stale, clamped to a real page that the
        bias zeroes."""
        out = []
        for j in range(P):
            pid = pt_ref[row, block * P + j]
            pid = jnp.where((pid >= 0) & (pid < num_pages), pid, 0)
            out.append(pltpu.make_async_copy(
                pool_hbm.at[pid], buf.at[slot, j], sem.at[slot]))
        return out

    def start(row, block, slot):
        for c in copies(row, block, slot):
            c.start()

    blocks = pl.cdiv(live_ref[s_id], P)
    walked = state[0]

    @pl.when((blocks > 0) & (state[1] != s_id))
    def _fetch_first():
        start(s_id, 0, walked % 2)

    q = q_ref[0]  # [H, W], compute dtype
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

        m, l, acc = carry
        lat = buf[slot].reshape(P * page_size, W)
        s = jax.lax.dot_general(
            q, lat,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [H, P * page_size]
        s = s * scale + bias_ref[0, b]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(s - m_new)
        l = alpha * l + probs.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            probs.astype(lat.dtype), lat[:, :value_width],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, blocks, block_step,
        (jnp.full((H, 1), FLOOR, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, value_width), jnp.float32)),
    )
    state[0] = walked + blocks
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def latent_decode_attention(
    q: jnp.ndarray,
    pages: jnp.ndarray,
    page_table: jnp.ndarray,
    bias: jnp.ndarray,
    value_width: int,
    scale: float,
) -> jnp.ndarray:
    """One decode step of absorbed latent attention.

    q: [S, H, W] — each slot's absorbed query rows ``[Wuk^T qn ; qr]``.
    pages: ONE layer's latent pool [num_pages, page_size, W]; the fresh
        token's latent must already be scattered in.
    page_table: [S, max_pages] int32; entries >= num_pages are the
        allocator's sentinel.
    bias: [S, max_pages * page_size] f32 additive validity bias (0 =
        attend, NEG_INF = masked) over logical positions.
    value_width: the leading columns of a latent that are its values.
    scale: the score scale (static).

    Returns ``u`` [S, H, value_width] in q's dtype: the probabilities
    times the latents; a row the bias lets nothing through for reads zeros.
    """
    S, H, W = q.shape
    num_pages, page_size, _ = pages.shape
    max_pages = page_table.shape[1]
    P, blocks = block_plan(pages.shape, pages.dtype, max_pages)
    page_table = page_table.astype(jnp.int32)
    bias3 = bias.reshape(S, max_pages, page_size).astype(jnp.float32)
    seen = (bias3 > 0.5 * NEG_INF).any(-1)  # [S, max_pages]
    live = jnp.max(
        jnp.where(seen, jnp.arange(1, max_pages + 1)[None, :], 0), axis=1
    ).astype(jnp.int32)
    pad = blocks * P - max_pages
    table = jnp.pad(page_table, ((0, 0), (0, pad)), constant_values=num_pages)
    bias4 = jnp.pad(bias3, ((0, 0), (0, pad), (0, 0)),
                    constant_values=NEG_INF).reshape(
                        S, blocks, 1, P * page_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda s, pt, live: (s, 0, 0)),
            pl.BlockSpec((1, blocks, 1, P * page_size),
                         lambda s, pt, live: (s, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, value_width),
                               lambda s, pt, live: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, page_size, W), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, value_width=value_width, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # rows in order: each fetches the next one's first block
            dimension_semantics=("arbitrary",),
            # the two block buffers, the bias rows of a table (a sublane
            # tile a block), the scores and probabilities of a block
            vmem_limit_bytes=2 * P * page_bytes(pages.shape, pages.dtype)
            + 2 * blocks * 8 * P * page_size * 4
            + 6 * H * P * page_size * 4 + 8 * 2**20,
        ),
        interpret=pallas_mode.interpret(),
        name="latent_decode_attention",
    )(table, live, q, bias4, pages)
