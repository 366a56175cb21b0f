"""Hydra policy: shared frozen trunk, trainable top, frozen reference top,
value head.

Parity target: `GPTHydraHeadWithValueModel` + `ModelBranch` (reference:
trlx/model/nn/ppo_models.py:304-350, 113-300). Design difference, deliberate:
the reference's `forward_hydra` runs the *entire* trained model and then
re-runs the top layers through deep-copied frozen modules (reference:
ppo_models.py:340-347 — its own docs call this wasteful). Here the split is
structural: params are partitioned into

- ``frozen_base``: embeddings + bottom ``L - k`` blocks (never updated),
- ``trainable``:  top ``k`` blocks + ln_f + value head (+ lm head if untied),
- ``ref``:        an init-time copy of the trainable transformer part,

and one forward computes trunk **once**, then branches twice — policy logits
+ values and reference logits in a single pass. Gradients are taken w.r.t.
``trainable`` only, which also subsumes the reference's separate
bottom-layer freezing loop (reference: trlx/model/accelerate_base_model.py:38-41).
The forward is two named halves, ``trunk`` and ``forward_from_trunk``, so a
caller that runs the top several times over one batch (the PPO update's
``ppo_epochs`` passes) runs the trunk once and starts each pass from its output.

``num_layers_unfrozen`` semantics (one definition, unlike the reference's
inconsistent uses — see SURVEY §"quirks"): k = num_layers_unfrozen top
blocks are trainable; -1 means all blocks trainable (ref branch is then a
full-depth copy, matching the reference's full-model CPU copy at
trlx/orchestrator/ppo_orchestrator.py:38-39, but kept on-device and sharded).
"""

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.data.configs import ModelSpec
from trlx_tpu.models.heads import head_apply, init_head_params
from trlx_tpu.models.transformer import (
    apply_blocks,
    attention_scores,
    embed_tokens,
    init_block_params,
    init_embed_params,
    init_ln_f_params,
    layer_norm,
    mask_arg_for,
    positions_from_mask,
    project_logits,
    slice_layers,
)

Params = Dict[str, Any]


def resolve_num_unfrozen(spec: ModelSpec, num_layers_unfrozen: int) -> int:
    if num_layers_unfrozen < 0:
        return spec.n_layer
    return min(num_layers_unfrozen, spec.n_layer)


@dataclass(frozen=True)
class HydraPolicy:
    """Static description of a hydra policy; all methods are pure functions
    over the params pytree and safe to close over in `jit`."""

    spec: ModelSpec
    num_layers_unfrozen: int = -1
    compute_dtype: Any = jnp.bfloat16
    remat: bool = False
    attention_fn: Any = None  # None => plain XLA attention
    # GPipe over the mesh's pp axis for the FROZEN TRUNK (the bulk of the
    # layers — what pp exists to fit): set by the trainers when
    # train.mesh has pp > 1. The small trainable/ref tops stay dense and
    # dp/fsdp/tp-sharded as usual. jax.sharding.Mesh is hashable, so the
    # dataclass stays a valid jit-cache key.
    pp_mesh: Any = None
    pp_n_micro: int = 4

    @property
    def k(self) -> int:
        return resolve_num_unfrozen(self.spec, self.num_layers_unfrozen)

    def _attn(self):
        return self.attention_fn or attention_scores

    def _pp_active(self) -> bool:
        return (
            self.pp_mesh is not None
            and self.pp_mesh.shape.get("pp", 1) > 1
        )

    # -- init ---------------------------------------------------------------

    def init(self, rng: jax.Array, param_dtype=jnp.float32,
             frozen_dtype=None) -> Params:
        """Jitted init: one compiled program instead of hundreds of eager
        dispatches (eager-op overhead dominates otherwise).

        `frozen_dtype` (default: param_dtype) stores the frozen trunk and
        reference branch in a narrower dtype than the trainable top — the
        memory-fit lever for 6B-class models on one chip: the frozen ~L-k
        layers are never updated, so bf16 storage costs nothing in
        optimizer quality, while the trainable branch (and its adam
        moments) stays float32."""
        return _jitted_init(self, param_dtype, frozen_dtype)(rng)

    def jit_forward(self, with_ref: bool = True):
        """A cached, jitted forward(params, tokens, attention_mask)."""
        return _jitted_forward(self, with_ref)

    def _init(self, rng: jax.Array, param_dtype=jnp.float32,
              frozen_dtype=None) -> Params:
        spec, k = self.spec, self.k
        frozen_dtype = frozen_dtype or param_dtype
        k_embed, k_blocks, k_head = jax.random.split(rng, 3)
        embed = init_embed_params(k_embed, spec, param_dtype)
        blocks = init_block_params(k_blocks, spec, spec.n_layer, param_dtype)
        bottom = slice_layers(blocks, 0, spec.n_layer - k)
        top = slice_layers(blocks, spec.n_layer - k, spec.n_layer)
        ln_f = init_ln_f_params(spec, param_dtype)

        lm_head = embed.pop("lm_head", None)
        trainable: Params = {
            "blocks": top,
            "ln_f": ln_f,
            "v_head": init_head_params(k_head, spec.d_model, 1, param_dtype),
        }
        ref: Params = {
            "blocks": jax.tree_util.tree_map(jnp.copy, top),
            "ln_f": jax.tree_util.tree_map(jnp.copy, ln_f),
        }
        if lm_head is not None:
            trainable["lm_head"] = lm_head
            ref["lm_head"] = jax.tree_util.tree_map(jnp.copy, lm_head)
        params = {
            "frozen_base": {"embed": embed, "blocks": bottom},
            "trainable": trainable,
            "ref": ref,
        }
        if frozen_dtype != param_dtype:
            cast = functools.partial(
                jax.tree_util.tree_map, lambda x: x.astype(frozen_dtype)
            )
            params["frozen_base"] = cast(params["frozen_base"])
            params["ref"] = cast(params["ref"])
        return params

    # -- forward ------------------------------------------------------------

    def trunk(self, params: Params, tokens, attention_mask):
        """Embeddings + the frozen bottom blocks: ``(h, mask_bias,
        positions)``, what ``forward_from_trunk`` /
        ``forward_hidden_from_trunk`` start from. Reads
        ``params["frozen_base"]`` alone, so its output does not change
        while only ``trainable`` does."""
        positions = positions_from_mask(attention_mask)
        mask_bias = mask_arg_for(self._attn(), attention_mask)
        h = embed_tokens(
            params["frozen_base"]["embed"],
            self.spec,
            tokens,
            positions,
            self.compute_dtype,
        )
        if self._pp_active():
            from trlx_tpu.ops.pipeline_parallel import pp_apply_blocks

            # GPipe the frozen trunk (pp_apply_blocks remats its tick
            # internally, so `remat` is subsumed)
            h = pp_apply_blocks(
                self.pp_mesh, params["frozen_base"]["blocks"], self.spec,
                h, mask_bias, positions, n_micro=self.pp_n_micro,
                attention_fn=self._attn(),
            )
        else:
            h = apply_blocks(
                params["frozen_base"]["blocks"],
                self.spec,
                h,
                mask_bias,
                positions,
                remat=self.remat,
                attention_fn=self._attn(),
            )
        return h, mask_bias, positions

    def _branch_hidden(self, branch: Params, h, mask_bias, positions):
        """Run a top branch's blocks + final layernorm; returns the
        post-ln_f hidden (what both the lm head and the value head read —
        reference: ppo_models.py:62-104)."""
        h = apply_blocks(
            branch["blocks"],
            self.spec,
            h,
            mask_bias,
            positions,
            remat=self.remat,
            attention_fn=self._attn(),
            first_layer=self.spec.n_layer - self.k,
        )
        return layer_norm(branch["ln_f"], h, self.spec.layer_norm_epsilon)

    def branch_head_fn(self, branch: Params, embed: Params):
        """h_normed [B, T, D] -> float32 logits [B, T, V] for a branch —
        the head callback chunked scoring feeds T-slices through
        (trlx_tpu.ops.losses.chunked_label_logprobs)."""
        head_params = dict(embed)
        if "lm_head" in branch:
            head_params["lm_head"] = branch["lm_head"]
        return lambda h_normed: project_logits(
            head_params, self.spec, h_normed
        )


    def forward(
        self,
        params: Params,
        tokens: jnp.ndarray,
        attention_mask: jnp.ndarray,
        with_ref: bool = True,
    ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], jnp.ndarray]:
        """Returns (logits, ref_logits | None, values).

        logits/ref_logits: [B, T, V] float32; values: [B, T] float32.
        The trunk (embeddings + frozen bottom blocks) runs exactly once.
        """
        return self.forward_from_trunk(
            params, *self.trunk(params, tokens, attention_mask), with_ref
        )

    def forward_from_trunk(
        self, params: Params, h, mask_bias, positions, with_ref: bool = True
    ):
        """`forward`'s second half: both top branches and their heads over
        the trunk's output (``h, mask_bias, positions = trunk(...)``)."""
        h_top, h_ref, values = self.forward_hidden_from_trunk(
            params, h, mask_bias, positions, with_ref
        )
        embed = params["frozen_base"]["embed"]
        logits = self.branch_head_fn(params["trainable"], embed)(h_top)
        ref_logits = None
        if with_ref:
            ref_logits = jax.lax.stop_gradient(
                self.branch_head_fn(params["ref"], embed)(h_ref)
            )
        return logits, ref_logits, values

    def forward_hidden(
        self,
        params: Params,
        tokens: jnp.ndarray,
        attention_mask: jnp.ndarray,
        with_ref: bool = True,
    ):
        """Trunk + both top branches WITHOUT the lm-head projection:
        (h_policy_normed [B, T, D], h_ref_normed | None, values [B, T]).

        The scoring path pairs this with chunked_label_logprobs so the
        [B, T, V] logits tensors (the rollout program's memory peak) are
        never materialized; use `branch_head_fn` for the matching head
        callbacks."""
        return self.forward_hidden_from_trunk(
            params, *self.trunk(params, tokens, attention_mask), with_ref
        )

    def forward_hidden_from_trunk(
        self, params: Params, h, mask_bias, positions, with_ref: bool = True
    ):
        """`forward_hidden`'s second half, over the trunk's output."""
        h_top = self._branch_hidden(
            params["trainable"], h, mask_bias, positions
        )
        values = head_apply(params["trainable"]["v_head"], h_top).squeeze(-1)
        h_ref = None
        if with_ref:
            h_ref = jax.lax.stop_gradient(
                self._branch_hidden(
                    params["ref"], jax.lax.stop_gradient(h), mask_bias,
                    positions,
                )
            )
        return h_top, h_ref, values

    # -- decode support -----------------------------------------------------

    def all_blocks(self, params: Params) -> Params:
        """(bottom, trainable top) stacked segments — the live policy
        the decode engine runs in order (a branch whose layers are not all
        alike is itself a tuple of segments, and they are listed flat).
        Deliberately NOT concatenated:
        inside a jitted rollout the concat materializes a full copy of
        the trunk as an HLO temp (~10 GB at gpt-j-6B — the single-chip
        OOM bench_gptj6b_train hit); generate() consumes the segments
        directly. Under a mixed frozen_dtype the trainable top is cast
        down to the frozen storage dtype (decode computes in bf16
        anyway)."""
        bottom = params["frozen_base"]["blocks"]
        frozen_dtype = jax.tree_util.tree_leaves(bottom)[0].dtype
        top = jax.tree_util.tree_map(
            lambda b: b.astype(frozen_dtype), params["trainable"]["blocks"]
        )
        flat = lambda b: tuple(b) if isinstance(b, (tuple, list)) else (b,)
        return (*flat(bottom), *flat(top))

    def head_params_for_decode(self, params: Params) -> Tuple[Params, Params]:
        """(embed+lm_head dict, ln_f) for the live policy branch."""
        embed = dict(params["frozen_base"]["embed"])
        if "lm_head" in params["trainable"]:
            embed["lm_head"] = params["trainable"]["lm_head"]
        return embed, params["trainable"]["ln_f"]


@functools.lru_cache(maxsize=None)
def _jitted_init(policy: HydraPolicy, param_dtype, frozen_dtype=None):
    return jax.jit(lambda rng: policy._init(rng, param_dtype, frozen_dtype))


@functools.lru_cache(maxsize=None)
def _jitted_forward(policy: HydraPolicy, with_ref: bool):
    return jax.jit(
        lambda params, tokens, mask: policy.forward(params, tokens, mask, with_ref)
    )
