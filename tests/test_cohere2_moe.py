"""``arch: cohere2_moe`` against its plain reference (benchmarks/lib/
reference_cohere2_moe.py), on seeded random weights at a small size:

(a) the train forward (``HydraPolicy.forward`` -> ``apply_blocks``), logits;
(b) prefill then decode through the two-class paged cache, logits at every
    decoded position, over a context of more than three windows so that
    window-class pages are released behind it, ``attention: jnp | pallas``;
(c) the shares add up: the routed parts of all 8 shares and the shared
    experts counted once are the uncut reference's expert layer;
(d) a prompt prefilled in chunks gives the logits of one-shot prefill;
(e) every token routed to the same experts: nothing is dropped;
(g) the dense families' specs and parameter trees are what they were;
and the one refusal of what the arch cannot run under yet.
(The allocator's cases (f) are in test_paged.py and test_slots.py.)"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.drivers import common  # noqa: E402
from benchmarks.lib import reference_cohere2_moe as R  # noqa: E402
from benchmarks.lib import weights as W  # noqa: E402
from benchmarks.lib import weights_cohere2_moe as WC  # noqa: E402
from trlx_tpu.data.configs import ModelSpec  # noqa: E402
from trlx_tpu.models import transformer as T  # noqa: E402
from trlx_tpu.models.policy import HydraPolicy  # noqa: E402
from trlx_tpu.serve import InferenceEngine, ServeConfig  # noqa: E402
from trlx_tpu.serve.slots import SlotScheduler  # noqa: E402

SEED = 3
SPEC = {
    "arch": "cohere2_moe", "vocab_size": 512, "n_layer": 4, "n_head": 8, "n_kv_heads": 2, "head_size": 16,
    "d_model": 64, "d_ff": 32, "n_positions": 512, "rope_theta": 50000.0, "layer_norm_epsilon": 1e-5,
    "tie_lm_head": True, "logit_scale": 1.0, "layer_pattern": ["window", "window", "window", "full"],
    "window": 16, "rope_kinds": ["window"], "n_experts": 16, "experts_per_token": 2, "n_shared_experts": 2,
    "expert_width": 32, "experts_held": 4, "expert_offset": 4,
}
SERVE = {"page_size": 8, "slots": 3, "pages": 64, "window_pages": 24,
         "buckets": [[1, 16, 16], [2, 16, 16], [1, 128, 16]], "flight_recorder_steps": 64}
# the tests' pool is float32 (the served one is bfloat16, which alone moves a logit of standard deviation 1.3 by
# 0.02): what is left is the order of float32 sums, and any fault of position, page or window is orders above it
POOL_TOL = 2e-4


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def prompt_of(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 500, size=n)]


def build(spec=SPEC, **serve):
    cfg = common.trl_config(spec, {"num_layers_unfrozen": 1, "compute_dtype": "float32", "param_dtype": "float32"},
                            {}, {"gen_kwargs": {"do_sample": False}}, SEED)
    engine = InferenceEngine(cfg, serve=ServeConfig.from_dict({**SERVE, **serve}),
                             params=WC.hydra_weights(spec, SEED, 1, jnp.float32))
    sched = SlotScheduler(engine)
    rt = sched.runtime  # before any program is compiled: the same pool, its pages kept in float32
    rt.pool = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), rt.pool)
    sched.warmup()
    return sched


def run_to_end(sched, prompt, max_new):
    """One request driven by hand (admit, then step by step): its tokens and
    the logits each was chosen from."""
    req = sched.submit(prompt, max_new_tokens=max_new)
    sched._admit()
    slot, logits = next(iter(sched._live)), []
    while sched._live:
        logits.append(np.asarray(sched.runtime.state.logits[slot]))
        sched._step()
    return req, np.stack(logits)


def reference_logits(spec, prompt, out):
    seq = list(prompt) + list(out)
    return R.forward_logits(spec, SEED, seq, positions=np.arange(len(prompt) - 1, len(seq) - 1))


# ------------------------------------------------------------ (a) the train forward
@pytest.mark.parametrize("offset, unfrozen", [(0, 1), (4, 1), (12, 2), (4, -1)])
def test_train_forward_equals_the_reference(offset, unfrozen):
    spec = {**SPEC, "expert_offset": offset}
    policy = HydraPolicy(spec=ModelSpec.from_dict(spec), num_layers_unfrozen=unfrozen, compute_dtype=jnp.float32)
    params = WC.hydra_weights(spec, SEED, policy.k, jnp.float32)
    init = jax.eval_shape(lambda: policy.init(jax.random.PRNGKey(0)))
    params["trainable"]["v_head"] = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), init["trainable"]["v_head"])
    common.same_layout(params, {k: v for k, v in init.items() if k != "ref"})  # the benchmark fills the program's tree
    tokens = np.asarray([prompt_of(70)])
    logits, _, _ = policy.forward(params, jnp.asarray(tokens), jnp.ones_like(tokens), with_ref=False)
    assert np.abs(np.asarray(logits[0]) - R.forward_logits(spec, SEED, tokens[0])).max() < 2e-5


# ------------------------------------------------------------ (b) prefill, then decode through two classes of page
# 8 kv heads of 128: the narrowest pool the paged decode kernel walks in blocks (ops/paged_attention.folds_pages)
WIDE_KV = {**SPEC, "n_head": 16, "n_kv_heads": 8, "head_size": 128}


@pytest.mark.parametrize("attention, spec", [("jnp", SPEC), ("pallas", SPEC), ("pallas", WIDE_KV)],
                         ids=["jnp", "pallas", "pallas-blocks"])
def test_decode_behind_the_window_equals_the_full_forward(attention, spec, monkeypatch):
    from trlx_tpu.ops import paged_attention

    # 4 pages a block at the wide pool's 32 KiB a page: the full table of 18 in 5 blocks, the ring in one
    monkeypatch.setattr(paged_attention, "BLOCK_VMEM_BYTES", 4 * 4 * 8 * 8 * 128 * 4)
    sched = build(spec, attention=attention)
    if spec is WIDE_KV:
        k_pages = [kv for seg in sched.runtime.pool for kv in seg][3][0]  # the full layer's
        assert paged_attention.block_plan(k_pages.shape, k_pages.dtype, sched.runtime.max_pages) == (4, 5)
    prompt = prompt_of(100)  # 6 windows; prefilled in 6 chunks of 16 and a rest of 4
    req, got = run_to_end(sched, prompt, 12)
    ref = reference_logits(spec, prompt, req.result)
    assert np.abs(got - ref).max() < POOL_TOL
    assert req.result == [int(t) for t in ref.argmax(-1)]
    cache = sched.cache
    assert cache.window_pages_freed >= 100 // 8 - 3  # released as the prefill and the decode passed them
    assert cache.window_reserved == 0 and not any(cache.window_allocator._ref) and not any(cache.allocator._ref)


def test_a_prefix_hit_reads_the_window_pages_the_trie_kept():
    sched = build()
    prompt = prompt_of(100)
    run_to_end(sched, prompt, 4)
    again = prompt[:96] + [7, 8, 9]
    req, got = run_to_end(sched, again, 6)
    assert req.trace.prefix_blocks_hit == 12 and req.trace.suffix_len == 3
    assert np.abs(got - reference_logits(SPEC, again, req.result)).max() < POOL_TOL


def test_three_slots_at_once_equal_the_reference():
    sched = build()
    prompts = [prompt_of(n, seed=n) for n in (100, 13, 60)]
    reqs = [sched.submit(p, max_new_tokens=g) for p, g in zip(prompts, (10, 16, 7))]
    while sched.queue_depth() or sched._live:
        sched._admit()
        sched._step()
    for p, r in zip(prompts, reqs):
        assert r.result == [int(t) for t in reference_logits(SPEC, p, r.result).argmax(-1)]
    assert sched.cache.window_reserved == 0 and not any(sched.cache.window_allocator._ref)


# ------------------------------------------------------------ (c) the shares add up
def layer_inputs(n_tokens=24):
    key = W.base_key(SEED)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, n_tokens, SPEC["d_model"]), jnp.float32)
    return key, x


def program_expert_layer(spec, key, x, token_mask=None):
    p = WC.program_layer(WC.layer_flat(spec, key, 1))
    return T.moe_ffn(ModelSpec.from_dict(spec), p, x, token_mask)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    key, x = layer_inputs()
    uncut = {**SPEC, "experts_held": 16, "expert_offset": 0}
    p_all = WC.layer_flat(uncut, key, 1)
    routed, shared, _ = R.experts(uncut, p_all, x[0], R.MATMULS["float32"], held=range(16))
    total = np.zeros_like(np.asarray(routed))
    for share in range(8):
        spec = {**SPEC, "experts_held": 2, "expert_offset": 2 * share}
        out, stats = program_expert_layer(spec, key, x)
        # every share computes the shared experts alike: counted once
        total += np.asarray(out[0]) - (np.asarray(shared) if share else 0.0)
    assert np.abs(total - np.asarray(routed + shared)).max() < 1e-5


def test_a_share_computes_its_own_pairs_and_counts_them():
    key, x = layer_inputs()
    out, (pairs, hit, load_max, load_mean) = program_expert_layer(SPEC, key, x)
    top_e, _ = R.route(SPEC, x[0], WC.layer_flat(SPEC, key, 1)["moe/router"], R.MATMULS["float32"])
    here = (np.asarray(top_e) >= 4) & (np.asarray(top_e) < 8)
    assert int(pairs) == here.sum() and int(hit) == len(set(np.asarray(top_e)[here]))
    assert float(load_mean) == pytest.approx(here.sum() / 4)
    masked, (pairs_masked, *_) = program_expert_layer(SPEC, key, x, jnp.arange(24)[None, :] < 10)
    assert int(pairs_masked) == here[:10].sum()  # padding makes no pair
    assert np.abs(np.asarray(masked[0, :10]) - np.asarray(out[0, :10])).max() < 1e-6


# ------------------------------------------------------------ (e) all tokens to the same experts
@pytest.mark.parametrize("n_tokens", [8, 64])
def test_every_token_routed_alike_drops_nothing(n_tokens):
    key = W.base_key(SEED)
    row = jax.random.normal(jax.random.PRNGKey(2), (SPEC["d_model"],), jnp.float32)
    x = jnp.tile(row, (1, n_tokens, 1))  # one routing for every token: the worst skew there is
    flat = WC.layer_flat(SPEC, key, 1)
    top_e, _ = R.route(SPEC, x[0], flat["moe/router"], R.MATMULS["float32"])
    spec = {**SPEC, "expert_offset": int(top_e[0, 0]) // 4 * 4}  # the share that holds the first choice
    flat = WC.layer_flat(spec, key, 1)
    out, (pairs, hit, load_max, _) = T.moe_ffn(ModelSpec.from_dict(spec), WC.program_layer(flat), x)
    routed, shared, _ = R.experts(spec, flat, x[0], R.MATMULS["float32"], held=R.held_experts(spec))
    assert int(load_max) == n_tokens and int(pairs) >= n_tokens
    assert np.abs(np.asarray(out[0]) - np.asarray(routed + shared)).max() < 1e-5


# ------------------------------------------------------------ (d) chunks
def test_chunked_prefill_gives_the_logits_of_one_shot_prefill():
    prompt = prompt_of(100)
    chunked = build()
    assert chunked.engine.chunk_len(128) == 16 and chunked.engine.prompt_classes() == ((16, (1, 2)),)
    one_shot = build(buckets=[[1, 128, 16]])
    assert one_shot.engine.chunk_len(128) == 0
    (req_c, got_c), (req_o, got_o) = run_to_end(chunked, prompt, 8), run_to_end(one_shot, prompt, 8)
    assert req_c.result == req_o.result and np.abs(got_c - got_o).max() < 1e-4
    from trlx_tpu import telemetry

    # 6 whole chunks, then the rest through the smallest program that holds it
    assert telemetry.current().registry.counters["serve/prefill_chunks"] >= 6


def test_a_dense_family_is_chunked_by_the_same_rule():
    from test_paged import build_engine

    def served(buckets):
        sched = SlotScheduler(build_engine(buckets=buckets, page_size=4, slots=2))
        sched.warmup()
        req = sched.submit([t % 200 for t in prompt_of(17, seed=5)], max_new_tokens=8)
        while not req.done.is_set():
            sched._admit()
            sched._step()
        return sched.engine, [t % 256 for t in req.result], req.result

    chunked, _, tokens = served([[1, 4, 8], [1, 20, 8]])
    assert chunked.chunk_len(20) == 4 and chunked.chunk_len(4) == 0
    one_shot, _, expected = served([[1, 20, 8]])
    assert one_shot.chunk_len(20) == 0 and tokens == expected


# ------------------------------------------------------------ (g) the dense families are what they were
DENSE_LEAVES = {
    "gpt2": "attn/bk attn/bo attn/bq attn/bv attn/wk attn/wo attn/wq attn/wv ln_1/bias ln_1/scale ln_2/bias "
            "ln_2/scale mlp/b_in mlp/b_out mlp/w_in mlp/w_out",
    "gptj": "attn/wk attn/wo attn/wq attn/wv ln_1/bias ln_1/scale mlp/b_in mlp/b_out mlp/w_in mlp/w_out",
    "gptneox": "attn/bk attn/bo attn/bq attn/bv attn/wk attn/wo attn/wq attn/wv ln_1/bias ln_1/scale ln_2/bias "
               "ln_2/scale mlp/b_in mlp/b_out mlp/w_in mlp/w_out",
    "llama": "attn/wk attn/wo attn/wq attn/wv ln_1/scale ln_2/scale mlp/w_gate mlp/w_in mlp/w_out",
}


@pytest.mark.parametrize("arch", sorted(DENSE_LEAVES))
def test_dense_specs_and_parameter_trees_are_unchanged(arch):
    spec = ModelSpec(arch=arch, n_layer=2, n_head=4, d_model=32, vocab_size=64, n_kv_heads=2 if arch == "llama" else 0)
    assert (spec.head_dim, spec.layer_pattern, spec.page_classes, spec.n_experts) == (8, (), ("full",), 0)
    assert spec == ModelSpec.from_dict({"arch": arch, "n_layer": 2, "n_head": 4, "d_model": 32, "vocab_size": 64,
                                        "n_kv_heads": 2 if arch == "llama" else 0})
    blocks = T.init_block_params(jax.random.PRNGKey(0), spec, 2)
    flat = W.flatten(blocks)
    assert " ".join(sorted(flat)) == DENSE_LEAVES[arch]
    assert flat["attn/wq"].shape == (2, 32, 32) and flat["attn/wo"].shape == (2, 32, 32)
    assert flat["attn/wk"].shape == (2, 32, 16 if arch == "llama" else 32)
    T.require_supported(spec, trainer="JaxPPOTrainer", kv_dtype="int8", mesh={"tp": 2}, speculation="lookup")


# ------------------------------------------------------------ what the arch cannot run under yet
@pytest.mark.parametrize("setting, value", [
    ("kv_dtype", "int8"), ("weights_dtype", "int8"), ("speculation", "lookup"), ("mesh", {"tp": 2}),
    ("trainer", "JaxPPOTrainer"), ("trainer", "JaxILQLTrainer"), ("hf_import", "cohere2_moe"),
])
def test_one_refusal_names_the_setting_and_the_arch(setting, value):
    with pytest.raises(NotImplementedError, match=f"{setting}=.*cohere2_moe") as refused:
        if setting == "trainer":
            from trlx_tpu.trainers import BaseRLTrainer

            cfg = common.trl_config(SPEC, {"num_layers_unfrozen": 1}, {}, {}, SEED)
            type(value, (), {"_load_or_spec": BaseRLTrainer._load_or_spec})()._load_or_spec(cfg)
        elif setting == "hf_import":
            from types import SimpleNamespace

            from trlx_tpu.models.hf_import import spec_from_hf_config

            spec_from_hf_config(SimpleNamespace(model_type=value))
        else:
            cfg = common.trl_config(SPEC, {"num_layers_unfrozen": 1}, {}, {"gen_kwargs": {"do_sample": False}}, SEED)
            InferenceEngine(cfg, serve=ServeConfig.from_dict({**SERVE, setting: value}), init=False)
    assert repr(value) in str(refused.value)
