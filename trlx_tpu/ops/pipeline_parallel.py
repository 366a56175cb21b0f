"""GPipe-style pipeline parallelism over the mesh's ``pp`` axis.

The reference has no pipeline parallelism (it scales through
Accelerate/DeepSpeed ZeRO — reference trlx/model/accelerate_base_model.py:
52-82); this op goes beyond parity: it splits the stacked-layer trunk into
``pp`` stages (the leading [L, ...] layer axis shards directly, one
contiguous slab of layers per device) and streams microbatches through the
stages with `shard_map` + `lax.ppermute`, so a model whose LAYERS don't
fit one chip trains across chips without tensor-level resharding.

Schedule: plain GPipe. With ``P`` stages and ``M`` microbatches the loop
runs ``M + P - 1`` ticks; at tick ``t`` stage ``s`` processes microbatch
``t - s`` (when in range) through its local layers, then hands the
activation to stage ``s + 1`` via a single neighbour `ppermute` (ICI
point-to-point — the cheapest collective on the mesh). Bubble fraction is
``(P - 1) / (M + P - 1)``: pick ``n_micro >= 4 * pp`` to amortize.
Backward is jax.grad through the same loop — `ppermute` transposes to the
reverse permute, recovering the GPipe backward schedule automatically;
the tick body is rematerialized (`jax.checkpoint`) so the backward does
not store per-tick layer activations.

What pp buys in THIS implementation is the PARAMETER split: each stage
holds only L/pp layers, so a trunk whose layers exceed one chip's HBM
trains across chips. Activation buffers are NOT reduced: microbatch
inputs and the output collector are full-batch, replicated per stage
(simple, correctness-first dataflow; a streamed-input variant is the
optimization path if per-stage activation memory ever binds).

Scope: the TRAIN-time forward (losses differentiate through it; verified
bit-close to the dense trunk + grads in tests/test_parallel.py). Decode
keeps its dense per-chip path — pipelining single-token steps trades a
bubble per generated token and is a different design problem. Outputs are
returned replicated across ``pp`` via a masked psum (the loss/head math
that follows runs replicated; at ``pp`` scale the [B, T, D] all-reduce is
small next to the per-stage layer compute).

Cited shapes: blocks [L, ...] as produced by
trlx_tpu.models.transformer.init_block_params; L must divide by the pp
extent, B by ``n_micro``.
"""

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from trlx_tpu.data.configs import ModelSpec
from trlx_tpu.models.transformer import apply_blocks, attention_scores

Params = Dict[str, Any]


def shard_blocks_pp(mesh: Mesh, blocks: Params) -> Params:
    """Place stacked [L, ...] blocks with the LAYER axis over ``pp``
    (each stage holds L/pp contiguous layers)."""
    return jax.device_put(
        blocks,
        jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, P("pp")), blocks
        ),
    )


def pp_apply_blocks(
    mesh: Mesh,
    blocks: Params,
    spec: ModelSpec,
    h: jnp.ndarray,
    mask_bias: jnp.ndarray,
    positions: jnp.ndarray,
    n_micro: int = 4,
    attention_fn=None,
) -> jnp.ndarray:
    """Forward `h` [B, T, D] through pp-sharded stacked blocks.

    Differentiable; equals `apply_blocks` numerically (see
    tests/test_parallel.py::test_pp_forward_matches_dense)."""
    attention_fn = attention_fn or attention_scores
    pp = mesh.shape["pp"]
    B = h.shape[0]
    if pp == 1:
        # unconditional passthrough: no microbatching constraints apply
        return apply_blocks(
            blocks, spec, h, mask_bias, positions,
            attention_fn=attention_fn,
        )
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
    L = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if L % pp:
        raise ValueError(f"n_layer {L} not divisible by pp={pp}")

    def split(x):  # [B, ...] -> [n_micro, B/n_micro, ...]
        return x.reshape((n_micro, B // n_micro) + x.shape[1:])

    micros = split(h)
    bias_m = split(mask_bias)
    pos_m = split(positions)

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P("pp"), P(), P(), P()),
        out_specs=P(),
    )
    def run(local_blocks, micros, bias_m, pos_m):
        stage = jax.lax.axis_index("pp")

        def layers(h_in, bias, pos):
            return apply_blocks(
                local_blocks, spec, h_in, bias, pos,
                attention_fn=attention_fn,
            )

        def tick(carry, t):
            h_cur, outs = carry
            m_idx = jnp.clip(t - stage, 0, n_micro - 1)
            active = (t >= stage) & (t - stage < n_micro)
            # stage 0 ingests a fresh microbatch; later stages use what
            # the previous stage handed over last tick
            h_in = jnp.where(stage == 0, micros[m_idx], h_cur)
            h_out = layers(h_in, bias_m[m_idx], pos_m[m_idx])
            h_out = jnp.where(active, h_out, h_in)
            # the LAST stage's finished microbatch is the result
            done = active & (stage == pp - 1)
            outs = outs.at[m_idx].set(
                jnp.where(done, h_out, outs[m_idx])
            )
            # neighbour hop: stage s -> s + 1 (the final stage's output
            # falls off the end; stage 0's inbound slot is ignored)
            h_next = jax.lax.ppermute(
                h_out, "pp", [(i, i + 1) for i in range(pp - 1)]
            )
            return (h_next, outs), None

        ticks = n_micro + pp - 1
        # initial carries must be marked per-stage-varying ("pvary"):
        # the tick body produces stage-dependent values, and shard_map
        # requires carry types to match across iterations
        init = jax.lax.pcast(
            (jnp.zeros_like(micros[0]), jnp.zeros_like(micros)),
            ("pp",), to="varying",
        )
        (_, outs), _ = jax.lax.scan(
            jax.checkpoint(tick), init, jnp.arange(ticks)
        )
        # replicate the last stage's outputs to every stage (masked psum)
        outs = jax.lax.psum(
            jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs)), "pp"
        )
        return outs

    outs = run(blocks, micros, bias_m, pos_m)
    return outs.reshape((B,) + h.shape[1:])
