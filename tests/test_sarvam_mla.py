"""``arch: sarvam_mla`` against its plain reference (benchmarks/lib/
reference_sarvam_mla.py: float32, the up-projected order only), on seeded
random weights at a small size:

(a) the train forward (``HydraPolicy.forward`` -> ``apply_blocks``), logits,
    for every place the hydra split can cut the dense-then-experts trunk;
(b) prefill in chunks, then decode through latent pages, logits at every
    decoded position, over a context that crosses several pages, in both
    orders of attention, ``attention: jnp | pallas``; a prefix shared by two
    live slots;
(c) the absorbed order equals the up-projected one;
(d) the Pallas kernel (interpreted) against the ``jnp`` absorbed form at
    ragged extents;
(e) the router: its bias chooses and does not weigh, the factor 2.5, every
    token to one expert still equals the reference;
(f) the shares add up: the four chips' expert parts, with the shared expert
    and everything computed alike counted once, are the uncut layer;
(g) the other archs' specs and parameter trees are what they were;
(h) ``require_supported`` refuses each setting by name, with the mechanism;
(i) the latent pool's leaves are written in place."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.drivers import common  # noqa: E402
from benchmarks.lib import reference_sarvam_mla as R  # noqa: E402
from benchmarks.lib import weights as W  # noqa: E402
from benchmarks.lib import weights_sarvam_mla as WS  # noqa: E402
from trlx_tpu.data.configs import ModelSpec  # noqa: E402
from trlx_tpu.models import latent as L  # noqa: E402
from trlx_tpu.models import transformer as T  # noqa: E402
from trlx_tpu.models.policy import HydraPolicy  # noqa: E402
from trlx_tpu.serve import InferenceEngine, ServeConfig  # noqa: E402
from trlx_tpu.serve.slots import SlotScheduler  # noqa: E402

SEED = 3
SPEC = {
    "arch": "sarvam_mla", "vocab_size": 512, "n_layer": 3, "n_head": 4, "d_model": 64, "d_ff": 128,
    "n_positions": 512, "layer_norm_epsilon": 1e-6, "tie_lm_head": False, "n_experts": 16, "experts_per_token": 2,
    "n_shared_experts": 1, "expert_width": 32, "experts_held": 4, "expert_offset": 4, "router_bias": True,
    "routed_scaling_factor": 2.5, "first_dense_layers": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0, "rope_factor": 40.0, "rope_beta_fast": 32.0,
    "rope_beta_slow": 1.0, "rope_mscale_all_dim": 1.0, "rope_original_positions": 64,
}
MSPEC = ModelSpec.from_dict(SPEC)
SERVE = {"page_size": 8, "slots": 3, "pages": 64, "buckets": [[1, 16, 16], [2, 16, 16], [1, 128, 16]],
         "flight_recorder_steps": 64}
# the tests' pool is float32: what is left is the order of float32 sums (and the absorbed order's other
# association of the same products); any fault of position, page, scale or order is orders above it
POOL_TOL = 2e-4


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def prompt_of(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 500, size=n)]


def build(spec=SPEC, **serve):
    cfg = common.trl_config(spec, {"num_layers_unfrozen": 2, "compute_dtype": "float32", "param_dtype": "float32"},
                            {}, {"gen_kwargs": {"do_sample": False}}, SEED)
    engine = InferenceEngine(cfg, serve=ServeConfig.from_dict({**SERVE, **serve}),
                             params=WS.hydra_weights(spec, SEED, 2, jnp.float32))
    sched = SlotScheduler(engine)
    rt = sched.runtime  # before any program is compiled: the same pool, its pages kept in float32
    rt.pool = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), rt.pool)
    sched.warmup()
    return sched


def run_to_end(sched, prompt, max_new):
    """One request driven by hand (admit, then step by step): its tokens and the logits each was chosen from."""
    req = sched.submit(prompt, max_new_tokens=max_new)
    sched._admit()
    slot, logits = next(iter(sched._live)), []
    while sched._live:
        logits.append(np.asarray(sched.runtime.state.logits[slot]))
        sched._step()
    return req, np.stack(logits)


def reference_logits(prompt, out, spec=SPEC):
    seq = list(prompt) + list(out)
    return R.forward_logits(spec, SEED, seq, positions=np.arange(len(prompt) - 1, len(seq) - 1))


# ------------------------------------------------------------ (a) the train forward
@pytest.mark.parametrize("unfrozen", [2, 1, 3, -1])
def test_train_forward_equals_the_reference(unfrozen):
    policy = HydraPolicy(spec=MSPEC, num_layers_unfrozen=unfrozen, compute_dtype=jnp.float32)
    params = WS.hydra_weights(SPEC, SEED, policy.k, jnp.float32)
    init = jax.eval_shape(lambda: policy.init(jax.random.PRNGKey(0)))
    params["trainable"]["v_head"] = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), init["trainable"]["v_head"])
    common.same_layout(params, {k: v for k, v in init.items() if k != "ref"})  # the benchmark fills the program's tree
    tokens = np.asarray([prompt_of(70)])
    logits, _, _ = policy.forward(params, jnp.asarray(tokens), jnp.ones_like(tokens), with_ref=False)
    assert np.abs(np.asarray(logits[0]) - R.forward_logits(SPEC, SEED, tokens[0])).max() < 5e-5


def test_the_trunk_is_a_dense_segment_then_an_expert_segment():
    blocks = T.init_block_params(jax.random.PRNGKey(0), MSPEC, 3)
    dense, routed = blocks
    assert set(dense) == {"ln_1", "ln_2", "attn", "mlp"} and set(routed) == {"ln_1", "ln_2", "attn", "moe", "shared"}
    assert dense["attn"]["wq"].shape == (1, 64, 4 * 24) and routed["moe"]["w_gate"].shape == (2, 4, 64, 32)
    assert routed["moe"]["router_bias"].shape == (2, 16) and routed["moe"]["router_bias"].dtype == jnp.float32
    assert set(dense["attn"]) == {"wq", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo"}
    assert dense["attn"]["w_uk"].shape == (1, 32, 4, 16)  # by head: what the absorbed order's small dots read
    # slices of it: one tree where one run is left, the pair where the cut leaves both
    assert set(T.slice_layers(blocks, 0, 1)) == set(dense) and set(T.slice_layers(blocks, 1, 3)) == set(routed)
    assert isinstance(T.slice_layers(blocks, 0, 2), tuple)
    assert jax.tree_util.tree_leaves(T.slice_layers(blocks, 0, 0))[0].shape[0] == 0


# ------------------------------------------------------------ (b) chunks, then decode through latent pages
@pytest.mark.parametrize("attention, absorb_max", [("jnp", 128), ("jnp", 4), ("pallas", 128), ("pallas", 4)],
                         ids=["jnp-absorbed", "jnp-up-projected", "pallas-absorbed", "pallas-up-projected"])
def test_chunked_prefill_and_paged_decode_equal_the_full_forward(attention, absorb_max, monkeypatch):
    from trlx_tpu.ops import latent_attention

    monkeypatch.setattr(L, "ABSORB_MAX_T", absorb_max)  # the prefill programs of 16 in either order
    # 3 pages a block at the test's 4 KiB a page: the table of 18 in 6 blocks
    monkeypatch.setattr(latent_attention, "BLOCK_VMEM_BYTES", 2 * 3 * 8 * 128 * 4)
    sched = build(attention=attention)
    pages = sched.runtime.pool[0][0]
    assert pages.shape == (64, 8, 128)  # one leaf a layer: the latent of 40 out to a lane tile, no V pool
    assert latent_attention.block_plan(pages.shape, pages.dtype, sched.runtime.max_pages) == (3, 6)
    prompt = prompt_of(100)  # 12.5 pages; prefilled in 6 chunks of 16 and a rest of 4
    req, got = run_to_end(sched, prompt, 12)
    ref = reference_logits(prompt, req.result)
    assert np.abs(got - ref).max() < POOL_TOL
    assert req.result == [int(t) for t in ref.argmax(-1)]
    from trlx_tpu import telemetry

    reg = telemetry.current().registry
    assert reg.counters["serve/prefill_chunks"] >= 6
    assert reg.gauges["serve/latent_bytes_per_token"] == 128 * 4
    assert reg.gauges["serve/latent_order{bucket=b1p16}"] == int(absorb_max >= 16)
    assert not any(sched.cache.allocator._ref)


def test_two_live_slots_read_one_documents_pages():
    sched = build()
    document = prompt_of(96)  # 12 whole pages
    run_to_end(sched, document, 1)  # committed to the prefix cache
    asks = [document + prompt_of(n, seed=n) for n in (5, 11)]
    reqs = [sched.submit(p, max_new_tokens=g) for p, g in zip(asks, (9, 6))]
    shared = []
    while sched.queue_depth() or sched._live:
        sched._admit()
        sched._step()
        shared.append(sched._pages_read())
    for p, r in zip(asks, reqs):
        assert r.trace.prefix_blocks_hit == 12
        ref = reference_logits(p, r.result)
        assert r.result == [int(t) for t in ref.argmax(-1)]
    # while both were live every document page was read twice from one copy: 24 of the 26 or so pages read
    assert max(s for _, s in shared) == 24 and all(s <= n for n, s in shared)
    assert not any(sched.cache.allocator._ref[p] > 1 for p in range(64))  # the trie's own reference is what is left


def test_three_slots_at_once_equal_the_reference():
    sched = build()
    prompts = [prompt_of(n, seed=n) for n in (100, 13, 60)]
    reqs = [sched.submit(p, max_new_tokens=g) for p, g in zip(prompts, (10, 16, 7))]
    while sched.queue_depth() or sched._live:
        sched._admit()
        sched._step()
    for p, r in zip(prompts, reqs):
        assert r.result == [int(t) for t in reference_logits(p, r.result).argmax(-1)]
    # the flight record's routing counts come from the two expert layers alone
    stats = sched.runtime.moe_stats_host
    assert stats == [] and sched._fr_pairs > 0


# ------------------------------------------------------------ (c) one layer, two orders
def layer_on_pages(absorb_max, monkeypatch, t_chunk=24, context=40):
    """A chunk of ``t_chunk`` queries over ``context`` cached latents through ``attend_pages``."""
    monkeypatch.setattr(L, "ABSORB_MAX_T", absorb_max)
    key = jax.random.PRNGKey(5)
    attn = jax.tree_util.tree_map(
        lambda x: x[0], T.init_block_params(key, MSPEC, 1, first_layer=0)["attn"])
    attn = jax.tree_util.tree_map(lambda x: x * 8.0, attn)  # scores that matter
    ks = jax.random.split(key, 4)
    total = context + t_chunk
    latent = jax.random.normal(ks[0], (2, total, MSPEC.latent_width))
    qn = jax.random.normal(ks[1], (2, t_chunk, 4, 16))
    qr = jax.random.normal(ks[2], (2, t_chunk, 4, 8))
    ps, n_pages = 8, 2 * (total // 8)
    table = jnp.arange(n_pages, dtype=jnp.int32).reshape(2, -1)
    pages = L.write_pages(jnp.zeros((n_pages, ps, MSPEC.latent_page_width)), latent,
                          jnp.zeros((2,), jnp.int32), table, ps)
    q_pos = context + jnp.arange(t_chunk)[None, :].repeat(2, 0)
    return L.attend_pages(MSPEC, attn, qn, qr, pages, table, q_pos, ps), (attn, qn, qr, latent)


def test_the_absorbed_order_equals_the_up_projected_one(monkeypatch):
    absorbed, (attn, qn, qr, latent) = layer_on_pages(128, monkeypatch)
    up_projected, _ = layer_on_pages(4, monkeypatch)
    assert np.abs(np.asarray(absorbed - up_projected)).max() < 2e-5
    # and both are the no-cache form over the whole sequence, at the chunk's positions
    causal = jnp.tril(jnp.ones((64, 64), bool))[None, None]
    whole = L.attend_chunk(
        MSPEC, attn, jnp.pad(qn, ((0, 0), (40, 0), (0, 0), (0, 0))), jnp.pad(qr, ((0, 0), (40, 0), (0, 0), (0, 0))),
        latent, jnp.where(causal, 0.0, T.NEG_INF))
    assert np.abs(np.asarray(whole[:, 40:] - up_projected)).max() < 2e-5


def test_the_order_is_chosen_by_the_chunk_length_alone():
    assert L.ABSORB_MAX_T == 128
    text = {}
    for t_chunk in (1, 128, 256):
        qn = jax.ShapeDtypeStruct((1, t_chunk, 4, 16), jnp.float32)
        qr = jax.ShapeDtypeStruct((1, t_chunk, 4, 8), jnp.float32)
        attn = jax.eval_shape(lambda: jax.tree_util.tree_map(
            lambda x: x[0], T.init_block_params(jax.random.PRNGKey(0), MSPEC, 1)["attn"]))
        text[t_chunk] = str(jax.make_jaxpr(
            lambda a, qn, qr, pages, table, pos: L.attend_pages(MSPEC, a, qn, qr, pages, table, pos, 8))(
            attn, qn, qr, jax.ShapeDtypeStruct((8, 8, 128), jnp.float32),
            jax.ShapeDtypeStruct((1, 4), jnp.int32), jax.ShapeDtypeStruct((1, t_chunk), jnp.int32)))
    # the running sum is over latents (32 wide) absorbed, over a head's values (16 wide) up-projected
    absorbed = lambda t: f"f32[1,4,{t},32]" in text[t] and f"f32[1,4,{t},16]" not in text[t]
    assert absorbed(1) and absorbed(128) and not absorbed(256) and "f32[1,4,256,16]" in text[256]


# ------------------------------------------------------------ (d) the kernel, interpreted
@pytest.mark.parametrize("page_size, table, budget_pages", [(8, 11, 3), (16, 5, 2), (8, 4, 8)])
def test_the_kernel_equals_the_jnp_absorbed_form_at_ragged_extents(page_size, table, budget_pages, monkeypatch):
    from trlx_tpu.ops import latent_attention as K

    S, H, W, r = 5, 4, 128, 32
    monkeypatch.setattr(K, "BLOCK_VMEM_BYTES", 2 * budget_pages * page_size * W * 4)
    rng = np.random.RandomState(page_size + table)
    num_pages = S * table + 3
    pages = jnp.asarray(rng.randn(num_pages, page_size, W), jnp.float32).at[..., 40:].set(0.0)
    q = jnp.asarray(rng.randn(S, H, W), jnp.float32)
    ids = rng.permutation(num_pages)[:S * table].reshape(S, table).astype(np.int32)
    lengths = np.array([table * page_size, 1, 0, page_size + 3, 3 * page_size][:S])  # one row sees nothing
    for s, n in enumerate(lengths):
        ids[s, -(-n // page_size):] = num_pages  # the sentinel past the row's extent
    bias = jnp.where(jnp.arange(table * page_size)[None, :] < lengths[:, None], 0.0, K.NEG_INF)
    got = K.latent_decode_attention(q, pages, jnp.asarray(ids), bias, r, 0.37)
    lat = pages[jnp.clip(ids, 0, num_pages - 1)].reshape(S, table * page_size, W)
    s = jnp.einsum("shw,skw->shk", q, lat) * 0.37 + bias[:, None, :]
    want = jnp.einsum("shk,skr->shr", jax.nn.softmax(s, -1), lat[..., :r])
    want = jnp.where((lengths > 0)[:, None, None], want, 0.0)
    assert np.abs(np.asarray(got - want)).max() < 2e-5


# ------------------------------------------------------------ (e) the router
def layer_inputs(n_tokens=48):
    key = W.base_key(SEED)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, n_tokens, SPEC["d_model"]), jnp.float32)
    return key, x


def program_expert_layer(spec, key, x, token_mask=None, flat=None):
    flat = WS.layer_flat(spec, key, 1, False) if flat is None else flat
    return T.moe_ffn(ModelSpec.from_dict(spec), WS.program_layer(flat), x, token_mask)


def test_the_bias_chooses_and_does_not_weigh():
    key, x = layer_inputs(256)
    uncut = {**SPEC, "experts_held": 16, "expert_offset": 0}
    flat = WS.layer_flat(uncut, key, 1, False)
    mm = R.MATMULS["float32"]
    scores = np.asarray(jax.nn.sigmoid(mm(x[0], flat["moe/router"])))
    top_e, gates = R.route(uncut, x[0], flat["moe/router"], flat["moe/router_bias"], mm)
    by_score, _ = R.route(uncut, x[0], flat["moe/router"], flat["moe/router_bias"], mm, fault="bias_not_in_choice")
    differs = (np.sort(np.asarray(top_e), -1) != np.sort(np.asarray(by_score), -1)).any(-1).mean()
    assert 0.01 < differs < 0.15  # the seeded bias changes the chosen set in a few percent of the tokens
    chosen = np.take_along_axis(scores, np.asarray(top_e), -1)
    assert np.allclose(np.asarray(gates), 2.5 * chosen / chosen.sum(-1, keepdims=True), atol=1e-6)  # from s, not s + b
    out, _ = program_expert_layer(uncut, key, x, flat=flat)
    routed, shared, *_ = R.experts(uncut, flat, x[0], mm, held=range(16))
    assert np.abs(np.asarray(out[0]) - np.asarray(routed + shared)).max() < 2e-5
    # a program that chose by the scores alone, or weighed by score + bias, is told apart
    wrong, *_ = R.experts(uncut, flat, x[0], mm, held=range(16), fault="bias_not_in_choice")
    assert np.abs(np.asarray(out[0]) - np.asarray(wrong + shared)).max() > 1e-2
    no_bias = {**flat, "moe/router_bias": jnp.zeros_like(flat["moe/router_bias"])}
    assert np.abs(np.asarray(program_expert_layer(uncut, key, x, flat=no_bias)[0] - out)).max() > 1e-2


@pytest.mark.parametrize("n_tokens", [8, 64])
def test_every_token_routed_alike_drops_nothing(n_tokens):
    key = W.base_key(SEED)
    row = jax.random.normal(jax.random.PRNGKey(2), (SPEC["d_model"],), jnp.float32)
    x = jnp.tile(row, (1, n_tokens, 1))  # one routing for every token: the worst skew there is
    flat = WS.layer_flat(SPEC, key, 1, False)
    top_e, _ = R.route(SPEC, x[0], flat["moe/router"], flat["moe/router_bias"], R.MATMULS["float32"])
    spec = {**SPEC, "expert_offset": int(top_e[0, 0]) // 4 * 4}  # the share that holds the first choice
    flat = WS.layer_flat(spec, key, 1, False)
    out, (pairs, hit, load_max, _) = program_expert_layer(spec, key, x, flat=flat)
    routed, shared, *_ = R.experts(spec, flat, x[0], R.MATMULS["float32"], held=R.held_experts(spec))
    assert int(load_max) == n_tokens and int(pairs) >= n_tokens
    assert np.abs(np.asarray(out[0]) - np.asarray(routed + shared)).max() < 2e-5


# ------------------------------------------------------------ (f) the shares add up
def test_the_four_shares_add_up_to_the_uncut_layer():
    key, x = layer_inputs()
    uncut = {**SPEC, "experts_held": 16, "expert_offset": 0}
    flat = WS.layer_flat(uncut, key, 1, False)
    routed, shared, *_ = R.experts(uncut, flat, x[0], R.MATMULS["float32"], held=range(16))
    total = np.zeros_like(np.asarray(routed))
    for share in range(4):
        out, _ = program_expert_layer({**SPEC, "experts_held": 4, "expert_offset": 4 * share}, key, x)
        # every chip computes the shared expert (and attention, router, norms) alike: counted once
        total += np.asarray(out[0]) - (np.asarray(shared) if share else 0.0)
    assert np.abs(total - np.asarray(routed + shared)).max() < 2e-5


def test_a_share_holds_the_uncut_models_experts_and_vocabulary_rows():
    key = W.base_key(SEED)
    uncut = WS.layer_flat({**SPEC, "experts_held": 16, "expert_offset": 0}, key, 2, False)
    mine = WS.layer_flat(SPEC, key, 2, False)
    assert sorted(n for n in mine if n.startswith("moe/w_up/")) == [f"moe/w_up/{e}" for e in (4, 5, 6, 7)]
    assert all(np.array_equal(mine[n], uncut[n]) for n in mine)
    assert mine["moe/router"].shape == (64, 16) and mine["moe/router_bias"].shape == (16,)  # all 16 outputs kept


# ------------------------------------------------------------ (g) the other archs are what they were
OTHER_LEAVES = {
    "gpt2": "attn/bk attn/bo attn/bq attn/bv attn/wk attn/wo attn/wq attn/wv ln_1/bias ln_1/scale ln_2/bias "
            "ln_2/scale mlp/b_in mlp/b_out mlp/w_in mlp/w_out",
    "gptj": "attn/wk attn/wo attn/wq attn/wv ln_1/bias ln_1/scale mlp/b_in mlp/b_out mlp/w_in mlp/w_out",
    "gptneox": "attn/bk attn/bo attn/bq attn/bv attn/wk attn/wo attn/wq attn/wv ln_1/bias ln_1/scale ln_2/bias "
               "ln_2/scale mlp/b_in mlp/b_out mlp/w_in mlp/w_out",
    "llama": "attn/wk attn/wo attn/wq attn/wv ln_1/scale ln_2/scale mlp/w_gate mlp/w_in mlp/w_out",
    "cohere2_moe": "attn/wk attn/wo attn/wq attn/wv ln_1/scale_centred moe/router moe/w_down moe/w_gate moe/w_up "
                   "shared/w_down shared/w_gate shared/w_up",
}


@pytest.mark.parametrize("arch", sorted(OTHER_LEAVES))
def test_the_other_archs_specs_and_trees_are_unchanged(arch):
    extra = {"n_experts": 8, "experts_per_token": 2, "n_shared_experts": 2} if arch == "cohere2_moe" else {}
    spec = ModelSpec(arch=arch, n_layer=2, n_head=4, d_model=32, vocab_size=64, **extra)
    assert (spec.kv_lora_rank, spec.latent_width, spec.first_dense_layers, spec.router_bias,
            spec.routed_scaling_factor, spec.rope_factor) == (0, 0, 0, False, 1.0, 0.0)
    assert spec == ModelSpec.from_dict({"arch": arch, "n_layer": 2, "n_head": 4, "d_model": 32, "vocab_size": 64,
                                        **extra})
    blocks = T.init_block_params(jax.random.PRNGKey(0), spec, 2)
    assert not isinstance(blocks, tuple)  # one stacked tree, as before
    assert " ".join(sorted(W.flatten(blocks))) == OTHER_LEAVES[arch]
    pool = T.init_paged_kv_cache(spec, 4, 8)
    assert [x.shape for x in pool] == [(4, 8, 4, 8)] * 2  # K and V of every head
    assert not T.ArchFlags.for_spec(spec).latent


def test_the_pool_and_its_sizes_follow_from_the_spec():
    from trlx_tpu.telemetry.flops import kv_bytes_per_token

    published = ModelSpec(arch="sarvam_mla", n_layer=5, n_head=64, d_model=4096, n_experts=128, experts_per_token=8,
                          kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    assert (published.latent_width, published.latent_page_width) == (576, 640)
    assert kv_bytes_per_token(published) == 5 * 640 * 2  # what a page takes; 576 x 2 of it are read
    assert T.init_paged_kv_cache(published, 2, 64).shape == (2, 64, 640)
    assert L.score_scale(ModelSpec.from_dict({**SPEC, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64})) \
        == pytest.approx(0.135234, rel=1e-5)
    freqs = L.yarn_inv_freq(ModelSpec.from_dict({**SPEC, "qk_rope_head_dim": 64, "rope_original_positions": 4096}))
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # fast dims keep their frequency, slow dims turn 40 times slower, the ramp lies between (dims 10 to 23)
    assert np.allclose(freqs[:10], base[:10]) and np.allclose(freqs[23:], base[23:] / 40.0)
    assert np.all(freqs[11:23] < base[11:23]) and np.all(freqs[11:23] > base[11:23] / 40.0)
    assert np.allclose(freqs, R.inv_freq({**SPEC, "qk_rope_head_dim": 64, "rope_original_positions": 4096}))


# ------------------------------------------------------------ (h) what the arch cannot run under yet
@pytest.mark.parametrize("setting, value, mechanism", [
    ("kv_dtype", "int8", "latent attention"), ("weights_dtype", "int8", "routed experts"),
    ("speculation", "lookup", "latent attention"), ("mesh", {"tp": 2}, "routed experts"),
    ("trainer", "JaxPPOTrainer", "routed experts"), ("trainer", "JaxILQLTrainer", "routed experts"),
    ("hf_import", "sarvam_mla", "routed experts"), ("rollout_cache", "contiguous", "latent attention"),
])
def test_one_refusal_names_the_setting_the_arch_and_the_mechanism(setting, value, mechanism):
    with pytest.raises(NotImplementedError, match=f"{setting}=.*sarvam_mla.*{mechanism}") as refused:
        if setting == "trainer":
            from trlx_tpu.trainers import BaseRLTrainer

            cfg = common.trl_config(SPEC, {"num_layers_unfrozen": 1}, {}, {}, SEED)
            type(value, (), {"_load_or_spec": BaseRLTrainer._load_or_spec})()._load_or_spec(cfg)
        elif setting == "hf_import":
            from trlx_tpu.models.hf_import import spec_from_hf_config

            spec_from_hf_config(type("C", (), {"model_type": value})())
        elif setting == "rollout_cache":
            from trlx_tpu.models.generation import GenerationConfig, generate

            blocks = T.init_block_params(jax.random.PRNGKey(0), MSPEC, 3)
            generate(MSPEC, blocks, {}, {}, jnp.zeros((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32),
                     jax.random.PRNGKey(0), GenerationConfig(gen_size=2))
        else:
            cfg = common.trl_config(SPEC, {"num_layers_unfrozen": 2}, {}, {"gen_kwargs": {"do_sample": False}}, SEED)
            InferenceEngine(cfg, serve=ServeConfig.from_dict({**SERVE, setting: value}), init=False)
    assert str(refused.value).count("(") >= 1 and "routed experts, window layers" not in str(refused.value)


def test_a_window_model_is_refused_by_its_own_mechanism():
    spec = ModelSpec(arch="cohere2_moe", n_layer=4, n_head=4, d_model=32, n_experts=8, experts_per_token=2,
                     layer_pattern=("window", "full"), window=8)
    with pytest.raises(NotImplementedError, match=r"kv_dtype='int8'.*\(window layers\): .*two classes"):
        T.require_supported(spec, kv_dtype="int8")
    with pytest.raises(NotImplementedError, match=r"mesh=.*\(routed experts\)"):
        T.require_supported(spec, mesh={"tp": 2})
    T.require_supported(spec, rollout_cache="contiguous")  # generate() refuses window layers itself, by its own message
    T.require_supported(MSPEC, kv_dtype="bf16", weights_dtype="bf16", speculation="off", mesh=None)


# ------------------------------------------------------------ (i) the latent leaves are written in place
@pytest.mark.parametrize("program", ["decode_step", "prefill_suffix"])
def test_latent_pool_leaves_are_written_in_place(program):
    """tests/test_paged.py::test_pool_leaves_are_written_in_place, for a pool whose leaf is one array of latent
    pages: every leaf is consumed by exactly ONE scatter, whose output, read only by the blocked reader's
    gathers (inside its loop), is the leaf the program returns; nothing as large as a leaf is sliced or moved."""
    from test_paged import _flat_eqns, _is_var
    from trlx_tpu.models.generation import (
        GenerationConfig, _segments_of, decode_step, init_page_pool, init_slot_state, prefill_into_slots)

    blocks = T.init_block_params(jax.random.PRNGKey(0), MSPEC, 3)
    embed = T.init_embed_params(jax.random.PRNGKey(1), MSPEC)
    ln_f = T.init_ln_f_params(MSPEC)
    _, seg_sizes = _segments_of(blocks)
    S, ps, max_pages, B, P = 3, 4, 4, 2, 8
    rows = max(x.size for x in jax.tree_util.tree_leaves((blocks, embed))) // (ps * 128) + 1
    pool = jax.eval_shape(lambda: init_page_pool(MSPEC, seg_sizes, rows, ps, cache_dtype=jnp.float32))
    assert all(not isinstance(leaf, tuple) for seg in pool for leaf in seg)  # one array a layer
    state = jax.eval_shape(lambda: init_slot_state(S, max_pages * ps, MSPEC.vocab_size, max_pages=max_pages))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    model = (MSPEC, blocks, embed, ln_f)
    if program == "decode_step":
        fn = lambda pool, st: decode_step(*model, pool, st, jnp.int32(0), GenerationConfig(gen_size=1),
                                          compute_dtype=jnp.float32)[0]
        args = ()
    else:
        fn = lambda pool, st, t, m, sid, mn, pt, start: prefill_into_slots(
            *model, pool, st, t, m, sid, mn, pt, ps, compute_dtype=jnp.float32, start=start, prefix_context=True)[0]
        args = (i32(B, P), i32(B, P), i32(B), i32(B), i32(B, max_pages), i32(B))
    closed = jax.make_jaxpr(fn)(pool, state, *args)
    leaves_in = closed.jaxpr.invars[:3]
    eqns = list(_flat_eqns(closed.jaxpr, {}))
    leaf_size = min(v.aval.size for v in leaves_in)
    for leaf, returned in zip(leaves_in, closed.jaxpr.outvars):
        readers = [e for e in eqns if any(v is leaf for v in e[1])]
        assert [e[0] for e in readers] == ["scatter"]
        _, ins, (written,) = readers[0]
        assert ins[0] is leaf and returned is written
        after = {e[0] for e in eqns if any(v is written for v in e[1])}
        assert after and after <= {"gather", "while"}, after
    big = lambda v: _is_var(v) and v.aval.size >= leaf_size
    moved = [name for name, ins, outs in eqns
             if name in ("slice", "dynamic_slice", "dynamic_update_slice", "concatenate")
             and any(big(v) for v in ins + outs)]
    assert not moved, moved
