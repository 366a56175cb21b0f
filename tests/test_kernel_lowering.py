"""Both Pallas kernels lowered for the TPU platform from this CPU host,
at the head shapes of the presets in trlx_tpu/data/configs.py: 12x64
(gpt2), 16x256 (gpt-j-6b), 32 q / 8 kv x 128 (llama-3 GQA), and the
paged decode kernel at the long-context serve cell's own size (128 q / 8
kv x 128, 32 slots, tables of 454 and of 66 pages of 64).

``interpret=False`` + ``lower(lowering_platforms=("tpu",))`` runs Pallas'
TPU lowering without a chip, and with it the block rules ("the last two
dimensions of your block shape must be divisible by 8 and 128, or equal
the array's") — the rules every paged-decode block broke while the kernel
only ever ran interpreted. This checks THOSE rules only: what Mosaic
itself accepts, and whether the numbers are right on the chip, is
chip_smoke.py's job (its kernels phase).
"""

import jax
import jax.numpy as jnp
import pytest

from trlx_tpu.ops import pallas_mode
from trlx_tpu.ops.paged_attention import paged_decode_attention
from trlx_tpu.ops.pallas_attention import flash_attention

#: (query heads, kv heads, head_dim)
HEAD_SHAPES = [(12, 12, 64), (16, 16, 256), (32, 8, 128)]


@pytest.fixture()
def compiled_not_interpreted(monkeypatch):
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)


def _lower_for_tpu(fn, *args) -> str:
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert "tpu_custom_call" in text  # a Mosaic kernel, not interpreter HLO
    return text


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("H,Hkv,hd", HEAD_SHAPES)
@pytest.mark.parametrize("page_size", [16, 64])
@pytest.mark.parametrize("tier", ["bf16", "int8"])
def test_paged_decode_lowers_for_tpu(compiled_not_interpreted, H, Hkv, hd,
                                     page_size, tier):
    S, max_pages, num_pages = 16, 4, 80
    pool = (num_pages, page_size, Hkv, hd)
    if tier == "int8":
        pages = (_sds(pool, jnp.int8), _sds(pool[:3], jnp.float32))
    else:
        pages = _sds(pool, jnp.bfloat16)
    _lower_for_tpu(
        paged_decode_attention,
        _sds((S, H, hd), jnp.bfloat16), pages, pages,
        _sds((S, max_pages), jnp.int32),
        _sds((S, max_pages * page_size), jnp.float32),
    )


@pytest.mark.parametrize("max_pages,num_pages", [(454, 7168), (66, 2560)],
                         ids=["full-table-454", "window-ring-66"])
def test_paged_decode_lowers_at_the_long_context_cells_size(
        compiled_not_interpreted, max_pages, num_pages):
    """command-a-plus-05-2026.serve-longshort32's decode attention: 128
    query heads over 8 kv heads of 128, pages of 64, 32 slots, each class
    of page with its own table width and so its own pages a block."""
    S, H, Hkv, hd, page_size = 32, 128, 8, 128, 64
    pages = _sds((num_pages, page_size, Hkv, hd), jnp.bfloat16)
    _lower_for_tpu(
        paged_decode_attention,
        _sds((S, H, hd), jnp.bfloat16), pages, pages,
        _sds((S, max_pages), jnp.int32),
        _sds((S, max_pages * page_size), jnp.float32),
    )


@pytest.mark.parametrize("H,Hkv,hd", HEAD_SHAPES)
@pytest.mark.parametrize("T", [1000, 4096])
def test_flash_forward_and_backward_lower_for_tpu(compiled_not_interpreted,
                                                  H, Hkv, hd, T):
    # block_apply hands the flash kernel H-wide K/V (GQA is expanded
    # before the seam), so kv heads do not enter its shapes
    B = 2
    qkv = _sds((B, T, H, hd), jnp.bfloat16)
    mask = _sds((B, T), jnp.int32)

    def loss(q, k, v, m):
        return flash_attention(q, k, v, m, 128, 128, True).astype(
            jnp.float32
        ).sum()

    _lower_for_tpu(
        lambda q, k, v, m: flash_attention(q, k, v, m, 128, 128, True),
        qkv, qkv, qkv, mask,
    )
    _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv, mask)


def test_interpret_is_decided_by_the_backend_alone():
    """The one decision point: interpreted off-TPU (this CPU run), and no
    kernel entry point takes an override."""
    import inspect

    from trlx_tpu.ops.paged_attention import make_paged_decode_fn

    assert pallas_mode.interpret() is True
    for fn in (paged_decode_attention, make_paged_decode_fn,
               flash_attention):
        assert "interpret" not in inspect.signature(fn).parameters
