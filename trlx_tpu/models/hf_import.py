"""Import HuggingFace checkpoints into trlx_tpu param pytrees.

Replaces the reference's `from_pretrained` + module-surgery path (reference:
trlx/model/nn/ppo_models.py:308-328 builds an HF torch model then deep-copies
top blocks). Here we convert the torch state_dict tensor-by-tensor into our
stacked-layer pytree layout; the hydra split then happens structurally in
`HydraPolicy`-style param partitioning.

Works fully offline against a local checkpoint directory, or against any
model the local HF cache already holds. Torch is used only on the host for
deserialization — nothing torch touches the TPU.

Supported arches: gpt2 (incl. gpt2-imdb/xl), gptj (gpt-j-6B), gptneox,
llama (llama-2/-3 families incl. grouped-query attention).
"""

from typing import Any, Dict, Optional, Tuple

import numpy as np

from trlx_tpu.data.configs import ModelSpec

Params = Dict[str, Any]


def spec_from_hf_config(hf_config) -> ModelSpec:
    """Derive a ModelSpec from a transformers config object."""
    mt = hf_config.model_type
    if mt == "gpt2":
        return ModelSpec(
            arch="gpt2",
            vocab_size=hf_config.vocab_size,
            n_layer=hf_config.n_layer,
            n_head=hf_config.n_head,
            d_model=hf_config.n_embd,
            n_positions=hf_config.n_positions,
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
            tie_lm_head=True,
        )
    if mt == "gptj":
        return ModelSpec(
            arch="gptj",
            vocab_size=hf_config.vocab_size,
            n_layer=hf_config.n_layer,
            n_head=hf_config.n_head,
            d_model=hf_config.n_embd,
            n_positions=hf_config.n_positions,
            rotary_dim=hf_config.rotary_dim or 0,
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
            tie_lm_head=False,
        )
    if mt == "gpt_neox":
        return ModelSpec(
            arch="gptneox",
            vocab_size=hf_config.vocab_size,
            n_layer=hf_config.num_hidden_layers,
            n_head=hf_config.num_attention_heads,
            d_model=hf_config.hidden_size,
            d_ff=hf_config.intermediate_size,
            n_positions=hf_config.max_position_embeddings,
            rotary_dim=int(
                hf_config.rotary_pct * hf_config.hidden_size
                // hf_config.num_attention_heads
            ),
            layer_norm_epsilon=hf_config.layer_norm_eps,
            tie_lm_head=False,
        )
    if mt == "llama":
        # fail fast on structures this importer does not (yet) carry —
        # silently dropping them would produce wrong logits with no error
        if getattr(hf_config, "rope_scaling", None):
            raise ValueError(
                "llama checkpoints with rope_scaling (llama-3.1+) are not "
                "supported yet: plain rope frequencies would silently "
                "diverge from the reference model"
            )
        if getattr(hf_config, "attention_bias", False) or getattr(
            hf_config, "mlp_bias", False
        ):
            raise ValueError(
                "llama-arch checkpoints with attention_bias/mlp_bias are "
                "not supported: the converter would silently drop the bias "
                "tensors"
            )
        return ModelSpec(
            arch="llama",
            vocab_size=hf_config.vocab_size,
            n_layer=hf_config.num_hidden_layers,
            n_head=hf_config.num_attention_heads,
            d_model=hf_config.hidden_size,
            d_ff=hf_config.intermediate_size,
            n_positions=hf_config.max_position_embeddings,
            layer_norm_epsilon=hf_config.rms_norm_eps,
            tie_lm_head=getattr(hf_config, "tie_word_embeddings", False),
            n_kv_heads=hf_config.num_key_value_heads,
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        )
    if mt in ("cohere2_moe", "sarvam_mla"):
        from trlx_tpu.models.transformer import require_supported

        require_supported(
            ModelSpec(arch=mt, n_experts=1, experts_per_token=1),
            hf_import=hf_config.model_type,
        )
    raise ValueError(f"unsupported HF model_type '{mt}'")


def _np(t) -> np.ndarray:
    return t.detach().cpu().numpy().astype(np.float32)


def _stack(sd, fmt: str, n: int, transform=lambda x: x) -> np.ndarray:
    return np.stack([transform(_np(sd[fmt.format(i=i)])) for i in range(n)])


def convert_gpt2_state_dict(sd, spec: ModelSpec) -> Tuple[Params, Params, Params]:
    """GPT-2: Conv1D weights are already [in, out]; c_attn fuses qkv columns."""
    L, D = spec.n_layer, spec.d_model
    qkv_w = _stack(sd, "transformer.h.{i}.attn.c_attn.weight", L)  # [L, D, 3D]
    qkv_b = _stack(sd, "transformer.h.{i}.attn.c_attn.bias", L)  # [L, 3D]
    embed = {
        "wte": _np(sd["transformer.wte.weight"]),
        "wpe": _np(sd["transformer.wpe.weight"]),
    }
    blocks = {
        "ln_1": {
            "scale": _stack(sd, "transformer.h.{i}.ln_1.weight", L),
            "bias": _stack(sd, "transformer.h.{i}.ln_1.bias", L),
        },
        "ln_2": {
            "scale": _stack(sd, "transformer.h.{i}.ln_2.weight", L),
            "bias": _stack(sd, "transformer.h.{i}.ln_2.bias", L),
        },
        "attn": {
            "wq": qkv_w[:, :, :D],
            "wk": qkv_w[:, :, D : 2 * D],
            "wv": qkv_w[:, :, 2 * D :],
            "bq": qkv_b[:, :D],
            "bk": qkv_b[:, D : 2 * D],
            "bv": qkv_b[:, 2 * D :],
            "wo": _stack(sd, "transformer.h.{i}.attn.c_proj.weight", L),
            "bo": _stack(sd, "transformer.h.{i}.attn.c_proj.bias", L),
        },
        "mlp": {
            "w_in": _stack(sd, "transformer.h.{i}.mlp.c_fc.weight", L),
            "b_in": _stack(sd, "transformer.h.{i}.mlp.c_fc.bias", L),
            "w_out": _stack(sd, "transformer.h.{i}.mlp.c_proj.weight", L),
            "b_out": _stack(sd, "transformer.h.{i}.mlp.c_proj.bias", L),
        },
    }
    ln_f = {
        "scale": _np(sd["transformer.ln_f.weight"]),
        "bias": _np(sd["transformer.ln_f.bias"]),
    }
    return embed, blocks, ln_f


def convert_gptj_state_dict(sd, spec: ModelSpec) -> Tuple[Params, Params, Params]:
    """GPT-J: nn.Linear weights are [out, in] → transpose; no attn biases;
    shared ln_1; untied lm_head with bias."""
    L = spec.n_layer
    t = np.transpose
    embed = {
        "wte": _np(sd["transformer.wte.weight"]),
        "lm_head": {
            "w": t(_np(sd["lm_head.weight"])),
            "b": _np(sd["lm_head.bias"]),
        },
    }
    blocks = {
        "ln_1": {
            "scale": _stack(sd, "transformer.h.{i}.ln_1.weight", L),
            "bias": _stack(sd, "transformer.h.{i}.ln_1.bias", L),
        },
        "attn": {
            "wq": _stack(sd, "transformer.h.{i}.attn.q_proj.weight", L, t),
            "wk": _stack(sd, "transformer.h.{i}.attn.k_proj.weight", L, t),
            "wv": _stack(sd, "transformer.h.{i}.attn.v_proj.weight", L, t),
            "wo": _stack(sd, "transformer.h.{i}.attn.out_proj.weight", L, t),
        },
        "mlp": {
            "w_in": _stack(sd, "transformer.h.{i}.mlp.fc_in.weight", L, t),
            "b_in": _stack(sd, "transformer.h.{i}.mlp.fc_in.bias", L),
            "w_out": _stack(sd, "transformer.h.{i}.mlp.fc_out.weight", L, t),
            "b_out": _stack(sd, "transformer.h.{i}.mlp.fc_out.bias", L),
        },
    }
    ln_f = {
        "scale": _np(sd["transformer.ln_f.weight"]),
        "bias": _np(sd["transformer.ln_f.bias"]),
    }
    return embed, blocks, ln_f


def convert_gptneox_state_dict(sd, spec: ModelSpec) -> Tuple[Params, Params, Params]:
    """GPT-NeoX: fused qkv [3D, D] interleaved per head → de-interleave and
    transpose; separate input/post layernorms; untied embed_out."""
    L, D, H, hd = spec.n_layer, spec.d_model, spec.n_head, spec.head_dim

    def split_qkv_w(w):
        # [3D, D] laid out as [H, 3, hd, D]
        w = w.reshape(H, 3, hd, D)
        return tuple(np.transpose(w[:, j].reshape(D, D)) for j in range(3))

    def split_qkv_b(b):
        b = b.reshape(H, 3, hd)
        return tuple(b[:, j].reshape(D) for j in range(3))

    qs, ks, vs, bqs, bks, bvs = [], [], [], [], [], []
    for i in range(L):
        wq, wk, wv = split_qkv_w(
            _np(sd[f"gpt_neox.layers.{i}.attention.query_key_value.weight"])
        )
        bq, bk, bv = split_qkv_b(
            _np(sd[f"gpt_neox.layers.{i}.attention.query_key_value.bias"])
        )
        qs.append(wq), ks.append(wk), vs.append(wv)
        bqs.append(bq), bks.append(bk), bvs.append(bv)
    t = np.transpose
    embed = {
        "wte": _np(sd["gpt_neox.embed_in.weight"]),
        "lm_head": {
            "w": t(_np(sd["embed_out.weight"])),
            "b": np.zeros((spec.vocab_size,), np.float32),
        },
    }
    blocks = {
        "ln_1": {
            "scale": _stack(sd, "gpt_neox.layers.{i}.input_layernorm.weight", L),
            "bias": _stack(sd, "gpt_neox.layers.{i}.input_layernorm.bias", L),
        },
        "ln_2": {
            "scale": _stack(
                sd, "gpt_neox.layers.{i}.post_attention_layernorm.weight", L
            ),
            "bias": _stack(
                sd, "gpt_neox.layers.{i}.post_attention_layernorm.bias", L
            ),
        },
        "attn": {
            "wq": np.stack(qs),
            "wk": np.stack(ks),
            "wv": np.stack(vs),
            "bq": np.stack(bqs),
            "bk": np.stack(bks),
            "bv": np.stack(bvs),
            "wo": _stack(sd, "gpt_neox.layers.{i}.attention.dense.weight", L, t),
            "bo": _stack(sd, "gpt_neox.layers.{i}.attention.dense.bias", L),
        },
        "mlp": {
            "w_in": _stack(sd, "gpt_neox.layers.{i}.mlp.dense_h_to_4h.weight", L, t),
            "b_in": _stack(sd, "gpt_neox.layers.{i}.mlp.dense_h_to_4h.bias", L),
            "w_out": _stack(sd, "gpt_neox.layers.{i}.mlp.dense_4h_to_h.weight", L, t),
            "b_out": _stack(sd, "gpt_neox.layers.{i}.mlp.dense_4h_to_h.bias", L),
        },
    }
    ln_f = {
        "scale": _np(sd["gpt_neox.final_layer_norm.weight"]),
        "bias": _np(sd["gpt_neox.final_layer_norm.bias"]),
    }
    return embed, blocks, ln_f


def convert_llama_state_dict(sd, spec: ModelSpec) -> Tuple[Params, Params, Params]:
    """LLaMA: RMSNorm (weight only), unbiased q/k/v/o projections (k/v in
    compact GQA width), SwiGLU mlp (gate/up/down), untied lm_head. HF's
    llama uses the half-rotation rotary convention — exactly our
    interleaved=False path — so weights transpose straight across."""
    L = spec.n_layer
    t = np.transpose

    embed = {"wte": _np(sd["model.embed_tokens.weight"])}
    if not spec.tie_lm_head:
        embed["lm_head"] = {
            "w": t(_np(sd["lm_head.weight"])),
            "b": np.zeros((spec.vocab_size,), np.float32),
        }
    blocks = {
        "ln_1": {
            "scale": _stack(sd, "model.layers.{i}.input_layernorm.weight", L),
        },
        "ln_2": {
            "scale": _stack(
                sd, "model.layers.{i}.post_attention_layernorm.weight", L
            ),
        },
        "attn": {
            "wq": _stack(sd, "model.layers.{i}.self_attn.q_proj.weight", L, t),
            "wk": _stack(sd, "model.layers.{i}.self_attn.k_proj.weight", L, t),
            "wv": _stack(sd, "model.layers.{i}.self_attn.v_proj.weight", L, t),
            "wo": _stack(sd, "model.layers.{i}.self_attn.o_proj.weight", L, t),
        },
        "mlp": {
            "w_gate": _stack(sd, "model.layers.{i}.mlp.gate_proj.weight", L, t),
            "w_in": _stack(sd, "model.layers.{i}.mlp.up_proj.weight", L, t),
            "w_out": _stack(sd, "model.layers.{i}.mlp.down_proj.weight", L, t),
        },
    }
    ln_f = {"scale": _np(sd["model.norm.weight"])}
    return embed, blocks, ln_f


_CONVERTERS = {
    "gpt2": convert_gpt2_state_dict,
    "gptj": convert_gptj_state_dict,
    "gptneox": convert_gptneox_state_dict,
    "llama": convert_llama_state_dict,
}


def convert_state_dict(sd, spec: ModelSpec) -> Tuple[Params, Params, Params]:
    """(embed, stacked blocks, ln_f) from a torch state_dict."""
    return _CONVERTERS[spec.arch.lower()](sd, spec)


def load_trunk_from_hf(model_path: str, local_files_only: Optional[bool] = None):
    """Load an HF causal-LM checkpoint (local dir or cached hub name) and
    return (spec, embed, blocks, ln_f) as numpy pytrees.

    Local files are tried first so offline environments fail fast instead of
    stalling on hub retries (shared policy: trlx_tpu.utils.hf_offline)."""
    from transformers import AutoConfig, AutoModelForCausalLM

    from trlx_tpu.utils.hf_offline import local_first_attempts

    attempts = (
        [{"local_files_only": local_files_only}]
        if local_files_only is not None
        else list(local_first_attempts())
    )
    last_err = None
    for kw in attempts:
        try:
            hf_config = AutoConfig.from_pretrained(model_path, **kw)
            spec = spec_from_hf_config(hf_config)
            model = AutoModelForCausalLM.from_pretrained(model_path, **kw)
            sd = model.state_dict()
            embed, blocks, ln_f = convert_state_dict(sd, spec)
            return spec, embed, blocks, ln_f
        except Exception as e:  # noqa: BLE001 - propagate last attempt below
            last_err = e
    raise last_err


def ilql_params_from_trunk(
    net, embed: Params, blocks: Params, ln_f: Params, rng
) -> Params:
    """Assemble the ILQL param split from an imported trunk: bottom frozen,
    top trainable, fresh V/Q heads, target = copy of Q heads (parity:
    reference CausalLMWithValueHeads loads the HF trunk then attaches heads,
    trlx/model/nn/ilql_models.py:32-84)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.heads import init_head_params

    spec, k = net.spec, net.k
    keys = jax.random.split(rng, 3)
    as_jnp = lambda tree: jax.tree_util.tree_map(jnp.asarray, tree)
    bottom = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x[: spec.n_layer - k]), blocks
    )
    top = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x[spec.n_layer - k :]), blocks
    )
    embed = dict(as_jnp(embed))
    lm_head = embed.pop("lm_head", None)

    q1 = init_head_params(keys[0], spec.d_model, spec.vocab_size)
    trainable: Params = {
        "blocks": top,
        "ln_f": as_jnp(ln_f),
        "v_head": init_head_params(keys[1], spec.d_model, 1),
        "q1_head": q1,
    }
    target: Params = {"q1_head": jax.tree_util.tree_map(jnp.copy, q1)}
    if net.two_qs:
        q2 = init_head_params(keys[2], spec.d_model, spec.vocab_size)
        trainable["q2_head"] = q2
        target["q2_head"] = jax.tree_util.tree_map(jnp.copy, q2)
    if lm_head is not None:
        trainable["lm_head"] = lm_head
    from trlx_tpu.models.ilql import split_embed_for_unfreeze

    frozen_embed, train_embed = split_embed_for_unfreeze(embed, k, spec)
    if train_embed is not None:
        trainable["embed"] = train_embed
    return {
        "frozen_base": {"embed": frozen_embed, "blocks": bottom},
        "trainable": trainable,
        "target": target,
    }


def hydra_params_from_trunk(
    policy, embed: Params, blocks: Params, ln_f: Params, rng,
    frozen_dtype=None,
) -> Params:
    """Assemble the hydra param split from an imported trunk: bottom frozen,
    top trainable, ref = copy of top; fresh value head. `frozen_dtype`
    narrows the storage of the frozen bottom + embeddings (the trainable
    top stays as imported — float32)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models.heads import init_head_params

    spec, k = policy.spec, policy.k
    as_jnp = lambda tree: jax.tree_util.tree_map(jnp.asarray, tree)
    bottom = jax.tree_util.tree_map(lambda x: jnp.asarray(x[: spec.n_layer - k]), blocks)
    top = jax.tree_util.tree_map(lambda x: jnp.asarray(x[spec.n_layer - k :]), blocks)
    ln_f = as_jnp(ln_f)
    embed = dict(as_jnp(embed))
    lm_head = embed.pop("lm_head", None)  # trainable: stays as imported
    if frozen_dtype is not None:
        cast = lambda tree: jax.tree_util.tree_map(
            lambda x: x.astype(frozen_dtype), tree
        )
        bottom = cast(bottom)
        embed = cast(embed)

    trainable: Params = {
        "blocks": top,
        "ln_f": ln_f,
        "v_head": init_head_params(rng, spec.d_model, 1),
    }
    ref: Params = {
        "blocks": jax.tree_util.tree_map(jnp.copy, top),
        "ln_f": jax.tree_util.tree_map(jnp.copy, ln_f),
    }
    if lm_head is not None:
        trainable["lm_head"] = lm_head
        ref["lm_head"] = jax.tree_util.tree_map(jnp.copy, lm_head)
    if frozen_dtype is not None:
        # the ref branch is frozen too — same storage dtype as the trunk
        # (matches HydraPolicy._init and the ModelConfig.param_dtype docs)
        ref = jax.tree_util.tree_map(
            lambda x: x.astype(frozen_dtype), ref
        )
    return {
        "frozen_base": {"embed": embed, "blocks": bottom},
        "trainable": trainable,
        "ref": ref,
    }
