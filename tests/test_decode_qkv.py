"""A decode step's q/k/v projections (``transformer._qkv`` at ``T == 1``)
pass an optimization barrier before they are split into heads, so that
XLA:TPU streams the weights as they are stored (tests/test_zz_chip_smoke.py
holds the compiled program to that). The barrier moves no arithmetic:
here the same q, k, v as the form it replaced, bit for bit, in every
family and both dtypes, and the same served tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import ModelSpec, TRLConfig
from trlx_tpu.models import transformer as T
from trlx_tpu.serve import InferenceEngine, ServeConfig
from trlx_tpu.serve.slots import SlotScheduler
from test_cohere2_moe import SPEC as MOE_SPEC
from test_serve import tiny_config_dict


def qkv_folded(spec, flags, p, h, positions, use_rope):
    """``transformer._qkv`` as it stood before the barrier: each
    projection reshaped to heads at once, which XLA:TPU folds into the
    dot. The control of this file and of the compile-only guard."""
    B, T_, _ = h.shape
    H, hd, Hkv = spec.n_head, spec.head_dim, spec.kv_heads
    x = T.layer_norm(p["ln_1"], h, spec.layer_norm_epsilon)
    attn = p["attn"]
    q = T._project(x, attn["wq"], attn.get("bq")).reshape(B, T_, H, hd)
    k = T._project(x, attn["wk"], attn.get("bk")).reshape(B, T_, Hkv, hd)
    v = T._project(x, attn["wv"], attn.get("bv")).reshape(B, T_, Hkv, hd)
    if use_rope:
        q = T.apply_rotary(q, positions, spec.rotary_dim,
                           flags.rotary_interleaved, spec.rope_theta)
        k = T.apply_rotary(k, positions, spec.rotary_dim,
                           flags.rotary_interleaved, spec.rope_theta)
    return x, q, k, v


def small_spec(arch):
    if arch == "cohere2_moe":
        return ModelSpec(**MOE_SPEC)
    return ModelSpec(
        arch=arch, vocab_size=257, n_layer=2, n_head=4, d_model=64,
        n_positions=64, rotary_dim=0 if arch == "gpt2" else 8,
        **({"n_kv_heads": 2} if arch == "llama" else {}),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "arch", ["gpt2", "gptj", "gptneox", "llama", "cohere2_moe"]
)
def test_decode_qkv_is_the_folded_form_bit_for_bit(arch, dtype):
    spec = small_spec(arch)
    flags = T.ArchFlags.for_spec(spec)
    layer = jax.tree_util.tree_map(
        lambda x: x[0],
        T.init_block_params(jax.random.PRNGKey(7), spec, 1, dtype),
    )
    h = jax.random.normal(
        jax.random.PRNGKey(11), (5, 1, spec.d_model), jnp.float32
    ).astype(dtype)
    positions = jnp.arange(3, 8, dtype=jnp.int32)[:, None]
    use_rope = T.rope_of(spec, flags, 0)

    def run(fn):
        return jax.jit(
            lambda p, h, pos: fn(spec, flags, p, h, pos, use_rope)
        )(layer, h, positions)

    for name, ours, theirs in zip("xqkv", run(T._qkv), run(qkv_folded)):
        assert ours.dtype == theirs.dtype == dtype, name
        np.testing.assert_array_equal(
            np.asarray(ours.astype(jnp.float32)),
            np.asarray(theirs.astype(jnp.float32)), err_msg=name,
        )


def test_the_barrier_stands_in_decode_programs_alone():
    """Keyed on the static ``T == 1``: a prefill, a verify step or a
    trained forward (``T > 1``) traces to the program it always was."""
    spec = small_spec("gptj")
    flags = T.ArchFlags.for_spec(spec)
    layer = jax.tree_util.tree_map(
        lambda x: x[0],
        T.init_block_params(jax.random.PRNGKey(7), spec, 1, jnp.float32),
    )

    def barriers(t):
        h = jnp.zeros((2, t, spec.d_model), jnp.float32)
        pos = jnp.zeros((2, t), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda p, h, pos: T._qkv(spec, flags, p, h, pos, True)
        )(layer, h, pos)
        return str(jaxpr).count("optimization_barrier")

    assert barriers(1) == 1
    assert barriers(4) == 0


ROWS = [[3, 1, 4, 1, 5], [3, 1, 4, 1, 5, 9, 2, 6], [9, 2, 6], [2, 7]]


def serve_greedy():
    telemetry.start()
    engine = InferenceEngine(
        TRLConfig.from_dict(tiny_config_dict()),
        serve=ServeConfig(
            buckets=[[2, 8, 8], [4, 8, 8]], max_queue=64,
            request_timeout=30.0, slots=4,
            page_size=4,
        ),
    )
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        requests = [s.submit(r, max_new_tokens=8) for r in ROWS]
        for r in requests:
            r.wait(timeout=60.0)
        return [r.result for r in requests]
    finally:
        s.stop()


def test_served_greedy_tokens_are_what_the_folded_form_serves(monkeypatch):
    served = serve_greedy()
    assert [len(t) for t in served] == [8] * len(ROWS)
    monkeypatch.setattr(T, "_qkv", qkv_folded)
    assert serve_greedy() == served


HLO = """HloModule jit_run_decode_step

%fused_computation.1 (p: bf16[2,64,64]) -> bf16[64,64] {
  %p = bf16[2,64,64]{2,1,0} parameter(0)
  %copy.9 = bf16[2,64,64]{1,2,0} copy(%p)
  ROOT %slice.1 = bf16[64,64]{0,1} bitcast(%copy.9)
}

ENTRY %main.1 (w: bf16[2,64,64], x: bf16[4,64]) -> bf16[4,64] {
  %w = bf16[2,64,64]{2,1,0:T(8,128)(2,1)} parameter(0)
  %x = bf16[4,64]{1,0} parameter(1)
  %slice_bitcast_fusion.3 = (bf16[64,64]{0,1:T(8,128)(2,1)S(1)}, bf16[64,64]{0,1}) fusion(%w), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run_decode_step)/layer0/attn/dot_general"}
  %copy.2 = bf16[64,64]{1,0:T(8,128)(2,1)S(1)} copy(%slice_bitcast_fusion.3), metadata={op_name="jit(run_decode_step)/layer0/attn/dot_general"}
  %copy.3 = bf16[4,64]{1,0} copy(%x)
  %copy-start.1 = (bf16[2,64,64]{2,1,0:T(8,128)(2,1)S(1)}, bf16[2,64,64]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%w), cross_program_prefetch_index=0
  %transpose.4 = f32[64,64]{1,0} transpose(%copy.2), dimensions={1,0}
  ROOT %dot.1 = bf16[4,64]{1,0} dot(%x, %copy.2)
}
"""


def test_large_moves_reads_a_compiled_programs_text():
    """One matrix here is 64 x 64 bfloat16 = 8 KiB. Counted: the slice
    fusion (both outputs), the copy of it, the transpose. Not counted: a
    fusion's own body, a small copy, a prefetch into another memory
    space."""
    from trlx_tpu.utils.hlo_text import large_moves

    moves = large_moves(HLO, 64 * 64 * 2)
    assert [(m.kind, m.nbytes, m.name) for m in moves] == [
        ("slice_bitcast_fusion", 16384, "slice_bitcast_fusion.3"),
        ("copy", 8192, "copy.2"),
        ("transpose", 16384, "transpose.4"),
    ]
    assert moves[0].op_name.endswith("layer0/attn/dot_general")
    assert moves[2].op_name == ""
    assert large_moves(HLO, 1 << 20) == []
