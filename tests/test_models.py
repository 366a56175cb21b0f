"""Model-layer tests: trunk variants, hydra equivalence, masking semantics.

The hydra-equivalence test is the analogue of the reference's only unit
tests (reference: unittests/test_ppo.py:26-48): at init the ref branch is an
exact copy of the trainable branch, so policy logits and ref logits must be
bit-identical.

Forwards are jitted and cached per (arch, k) to keep the suite fast.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.configs import ModelSpec
from trlx_tpu.models.policy import HydraPolicy

TINY = dict(vocab_size=97, n_layer=4, n_head=4, d_model=64, n_positions=64)
B, T = 2, 12


@functools.lru_cache(maxsize=None)
def setup(arch="gpt2", k=2):
    spec_kw = dict(TINY)
    if arch in ("gptj", "gptneox"):
        spec_kw.update(rotary_dim=8, tie_lm_head=False)
    spec = ModelSpec(arch=arch, **spec_kw)
    policy = HydraPolicy(spec=spec, num_layers_unfrozen=k, compute_dtype=jnp.float32)
    params = policy.init(jax.random.PRNGKey(0))
    return policy, params, policy.jit_forward()


def toks(key, shape=(B, T), lo=1):
    return jax.random.randint(jax.random.PRNGKey(key), shape, lo, 97)


def full_mask(b=B, t=T):
    return jnp.ones((b, t), jnp.int32)


@pytest.mark.parametrize("arch", ["gpt2", "gptj", "gptneox"])
def test_forward_shapes(arch):
    _, params, fwd = setup(arch)
    logits, ref_logits, values = fwd(params, toks(1), full_mask())
    assert logits.shape == (B, T, 97)
    assert ref_logits.shape == (B, T, 97)
    assert values.shape == (B, T)
    assert logits.dtype == jnp.float32


@pytest.mark.parametrize("arch", ["gpt2", "gptj"])
@pytest.mark.parametrize("k", [0, 2, -1])
def test_hydra_equivalence_at_init(arch, k):
    """Ref branch is an init-time copy → ref logits must equal policy logits
    exactly (parity with reference unittests/test_ppo.py:35-48)."""
    _, params, fwd = setup(arch, k)
    logits, ref_logits, _ = fwd(params, toks(2), full_mask())
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))


def test_hydra_diverges_after_top_perturbation():
    """Perturbing a trainable top block changes policy logits but not ref."""
    _, params, fwd = setup()
    tokens = toks(3)
    _, ref_before, _ = fwd(params, tokens, full_mask())
    params = jax.tree_util.tree_map(lambda x: x, params)  # shallow-copy tree
    params["trainable"]["blocks"]["attn"]["wq"] = (
        params["trainable"]["blocks"]["attn"]["wq"] + 0.05
    )
    logits, ref_after, _ = fwd(params, tokens, full_mask())
    np.testing.assert_array_equal(np.asarray(ref_before), np.asarray(ref_after))
    assert not np.allclose(np.asarray(logits), np.asarray(ref_after))


@pytest.mark.parametrize("arch", ["gpt2", "gptj"])
def test_left_padding_invariance(arch):
    """Logits at real positions are identical whether or not the prompt is
    left-padded (mask bias + mask-derived positions must both be right)."""
    _, params, fwd = setup(arch)
    pad, t = 4, T - 4
    tokens = toks(4, (1, t))
    logits, _, values = fwd(params, tokens, full_mask(1, t))

    padded = jnp.concatenate([jnp.zeros((1, pad), tokens.dtype), tokens], axis=1)
    mask = jnp.concatenate([jnp.zeros((1, pad), jnp.int32), full_mask(1, t)], axis=1)
    logits_p, _, values_p = fwd(params, padded, mask)

    np.testing.assert_allclose(
        np.asarray(logits_p[:, pad:]), np.asarray(logits), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(values_p[:, pad:]), np.asarray(values), rtol=1e-4, atol=1e-4
    )


def test_causality():
    """Changing a future token must not change logits at earlier positions."""
    _, params, fwd = setup()
    tokens = toks(6)
    logits, _, _ = fwd(params, tokens, full_mask())
    tampered = tokens.at[:, -1].set((tokens[:, -1] + 1) % 97)
    logits_t, _, _ = fwd(params, tampered, full_mask())
    np.testing.assert_array_equal(
        np.asarray(logits[:, :-1]), np.asarray(logits_t[:, :-1])
    )
    assert not np.array_equal(np.asarray(logits[:, -1]), np.asarray(logits_t[:, -1]))


def test_grads_flow_only_through_trainable():
    policy, params, _ = setup()
    tokens = toks(7)
    mask = full_mask()

    @jax.jit
    def grad_fn(trainable):
        def loss_fn(tr):
            p = {**params, "trainable": tr}
            logits, _, values = policy.forward(p, tokens, mask, with_ref=False)
            return jnp.mean(logits**2) + jnp.mean(values**2)

        return jax.grad(loss_fn)(trainable)

    grads = grad_fn(params["trainable"])
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    nonzero = [float(jnp.abs(g).max()) > 0 for g in flat]
    assert all(nonzero), "some trainable params receive no gradient"


def test_param_dtype_bfloat16_frozen_split():
    """model.param_dtype=bfloat16 must narrow ONLY the frozen trunk and
    reference branch; the trainable branch (and so its adam moments) stays
    float32, and both rollout and train step still run."""
    import jax

    from tests.test_ppo_e2e import PROMPTS, make_config, reward_fn
    from trlx_tpu.utils.loading import get_model, get_orchestrator, get_pipeline
    from trlx_tpu.utils.tokenizer import ByteTokenizer

    config = make_config(total_steps=2, epochs=2, num_rollouts=16,
                         chunk_size=16, batch_size=16, ppo_epochs=1)
    config.model.param_dtype = "bfloat16"
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()

    frozen_leaves = jax.tree_util.tree_leaves(trainer.params["frozen_base"])
    ref_leaves = jax.tree_util.tree_leaves(trainer.params["ref"])
    train_leaves = jax.tree_util.tree_leaves(trainer.params["trainable"])
    assert all(x.dtype == jnp.bfloat16 for x in frozen_leaves)
    assert all(x.dtype == jnp.bfloat16 for x in ref_leaves)
    assert all(x.dtype == jnp.float32 for x in train_leaves)

    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    orch.make_experience(config.method.num_rollouts)
    trainer.learn(log_fn=lambda s: None)
    assert trainer.iter_count == 2
    # trainable stayed fp32 through the update
    assert all(
        x.dtype == jnp.float32
        for x in jax.tree_util.tree_leaves(trainer.params["trainable"])
    )


def test_memory_fit_check_gptj_geometry(monkeypatch):
    """gpt-j-6B at fp32 frozen storage (~18 GB) must fail fast with an
    actionable error on a 16 GB device; bf16 frozen storage (~10 GB)
    must pass. (docs/source/performance.rst "Memory fit")"""
    import jax

    from tests.test_ppo_e2e import make_config
    from trlx_tpu.data.configs import ModelSpec
    from trlx_tpu.utils.loading import get_model

    config = make_config(total_steps=2)
    trainer = get_model(config.model.model_type)(config)
    trainer.config.model.num_layers_unfrozen = 2

    class FakeDev:
        def memory_stats(self):
            return {"bytes_limit": 16 * 2**30}

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev()])
    gptj = ModelSpec.preset("gpt-j-6b")
    with pytest.raises(ValueError, match="param_dtype"):
        trainer._check_memory_fit(gptj, jnp.float32)
    # bf16 frozen storage is NOT enough on one chip: the untied fp32
    # trainable lm_head + adam (~2.5 GB) plus top blocks keep the total
    # ~19 GB (docs/source/performance.rst "Memory fit")
    with pytest.raises(ValueError, match="fsdp"):
        trainer._check_memory_fit(gptj, jnp.bfloat16)
    # the shipped ppo_gptj.yml mesh (fsdp=2 x tp=4) divides the params 8x
    from trlx_tpu.parallel import build_mesh

    trainer.mesh = build_mesh({"fsdp": 2, "tp": 4})
    trainer._check_memory_fit(gptj, jnp.bfloat16)  # fits: no raise
    trainer.mesh = None
    # and the env override really overrides
    monkeypatch.setenv("TRLX_TPU_SKIP_MEMCHECK", "1")
    trainer._check_memory_fit(gptj, jnp.float32)

def test_ilql_memory_fit_check_fires(monkeypatch):
    """The ILQL trainer must run the pre-flight HBM check too: a gpt-j-6B
    ILQL config (fp32 everything + [d, V] Q/target heads) fails fast on a
    16 GB device instead of OOMing mid-init."""
    import jax

    from tests.test_ilql import rw_config
    from trlx_tpu.utils.loading import get_model

    class FakeDev:
        def memory_stats(self):
            return {"bytes_limit": 16 * 2**30}

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev()])
    config = rw_config(n_nodes=21)
    config.model.model_spec = {
        "arch": "gptj", "vocab_size": 50400, "n_layer": 28, "n_head": 16,
        "d_model": 4096, "n_positions": 2048, "rotary_dim": 64,
        "tie_lm_head": False,
    }
    with pytest.raises(ValueError, match="HBM"):
        get_model(config.model.model_type)(config)


def test_debug_nans_no_cross_trainer_leak():
    """A trainer with debug_nans=true must not leak jax_debug_nans into a
    later trainer constructed with debug_nans=false — but an EXTERNALLY
    enabled flag must survive framework trainers that didn't ask for it."""
    import jax

    from tests.test_ppo_e2e import make_config
    from trlx_tpu.utils.loading import get_model

    assert not jax.config.jax_debug_nans
    try:
        cfg = make_config(total_steps=2)
        cfg.train.debug_nans = True
        get_model(cfg.model.model_type)(cfg)
        assert jax.config.jax_debug_nans

        cfg2 = make_config(total_steps=2)
        get_model(cfg2.model.model_type)(cfg2)
        assert not jax.config.jax_debug_nans, (
            "framework-set debug_nans leaked into the next trainer"
        )

        # externally-set flag is preserved through a default trainer
        jax.config.update("jax_debug_nans", True)
        get_model(cfg2.model.model_type)(cfg2)
        assert jax.config.jax_debug_nans, (
            "externally-set debug_nans was clobbered"
        )

        # external enable + config enable: the framework must NOT claim
        # ownership of a flag the user already set, so a later default
        # trainer leaves it on
        get_model(cfg.model.model_type)(cfg)  # debug_nans=True config
        get_model(cfg2.model.model_type)(cfg2)
        assert jax.config.jax_debug_nans, (
            "external flag disabled after a config-enabled trainer"
        )
    finally:
        jax.config.update("jax_debug_nans", False)


def test_memory_fit_counts_optimizer_choice(monkeypatch):
    """The precheck's optimizer-state term follows train.optimizer: the
    bf16-frozen single-chip 6B hydra that FAILS under fp32 AdamW (~19 GB)
    PASSES under adafactor (~15 GB) — the lever a 6B train run on one
    chip needs."""
    import jax

    from tests.test_ppo_e2e import make_config
    from trlx_tpu.data.configs import ModelSpec
    from trlx_tpu.utils.loading import get_model

    config = make_config(total_steps=2)
    trainer = get_model(config.model.model_type)(config)
    trainer.config.model.num_layers_unfrozen = 2

    class FakeDev:
        def memory_stats(self):
            return {"bytes_limit": 16 * 2**30}

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev()])
    gptj = ModelSpec.preset("gpt-j-6b")
    with pytest.raises(ValueError, match="adafactor"):
        trainer._check_memory_fit(gptj, jnp.bfloat16)
    trainer.config.train.optimizer = "adafactor"
    trainer._check_memory_fit(gptj, jnp.bfloat16)  # fits: no raise
    # bf16 adam moments shave 2 bytes/param — still too big at 6B
    trainer.config.train.optimizer = "adamw"
    trainer.config.train.adam_moment_dtype = "bfloat16"
    with pytest.raises(ValueError, match="HBM"):
        trainer._check_memory_fit(gptj, jnp.bfloat16)


def test_build_optimizer_variants_step():
    """adafactor and bf16-mu adamw both produce valid updates on a tiny
    param tree, and the adamw mu state is actually stored in bfloat16."""
    import optax

    from tests.test_ppo_e2e import make_config
    from trlx_tpu.trainers.ppo_trainer import build_optimizer

    config = make_config(total_steps=2)
    params = {"w": jnp.ones((4, 8), jnp.float32),
              "b": jnp.zeros((8,), jnp.float32)}
    grads = jax.tree_util.tree_map(lambda x: x + 0.1, params)

    config.train.optimizer = "adamw"
    config.train.adam_moment_dtype = "bfloat16"
    opt = build_optimizer(config.train)
    state = opt.init(params)
    mus = [x.dtype for x in jax.tree_util.tree_leaves(state)
           if hasattr(x, "dtype") and x.dtype == jnp.bfloat16]
    assert mus, "no bfloat16 moment state found"
    updates, _ = opt.update(grads, state, params)
    stepped = optax.apply_updates(params, updates)
    assert all(jnp.isfinite(x).all()
               for x in jax.tree_util.tree_leaves(stepped))

    config.train.optimizer = "adafactor"
    opt = build_optimizer(config.train)
    state = opt.init(params)
    updates, _ = opt.update(grads, state, params)
    stepped = optax.apply_updates(params, updates)
    assert all(jnp.isfinite(x).all()
               for x in jax.tree_util.tree_leaves(stepped))

    config.train.optimizer = "sgd"
    with pytest.raises(ValueError, match="adamw, adafactor"):
        build_optimizer(config.train)
