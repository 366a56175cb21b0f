"""End-to-end PPO: the full four-piece loop (pipeline → orchestrator →
store → trainer) learns a synthetic reward on a tiny from-config model.

This is the promotion of the reference's de-facto integration-test style
(deterministic synthetic task, from-config tiny model, programmatic reward —
reference: examples/ilql_randomwalks.py) to the PPO path, which the
reference never tests end-to-end.
"""

import functools

import jax
import numpy as np

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.utils.loading import get_model, get_orchestrator, get_pipeline
from trlx_tpu.utils.tokenizer import ByteTokenizer


def make_config(total_steps=60, batch_size=16, num_layers_unfrozen=1,
                learning_rate=3e-3, epochs=100, ppo_epochs=2,
                num_rollouts=32, chunk_size=16):
    return TRLConfig.from_dict(
        {
            "model": {
                "model_path": "from-config",
                "tokenizer_path": "byte",
                "model_type": "JaxPPOTrainer",
                "num_layers_unfrozen": num_layers_unfrozen,
                "model_spec": {
                    "vocab_size": 257,
                    "n_layer": 2,
                    "n_head": 4,
                    "d_model": 64,
                    "n_positions": 32,
                },
                "compute_dtype": "float32",
            },
            "train": {
                "n_ctx": 32,
                "epochs": epochs,
                "total_steps": total_steps,
                "batch_size": batch_size,
                "grad_clip": 1.0,
                "lr_ramp_steps": 0,
                "lr_decay_steps": total_steps,
                "weight_decay": 1e-6,
                "learning_rate_init": learning_rate,
                "learning_rate_target": learning_rate,
                "log_interval": 1000,
                "checkpoint_interval": 10**9,
                "eval_interval": 10**9,
                "pipeline": "PPOPipeline",
                "orchestrator": "PPOOrchestrator",
                "input_size": 4,
                "gen_size": 8,
                "seed": 0,
            },
            "method": {
                "name": "ppoconfig",
                "num_rollouts": num_rollouts,
                "chunk_size": chunk_size,
                "ppo_epochs": ppo_epochs,
                "init_kl_coef": 0.02,
                "target": 6.0,
                "horizon": 10000,
                "gamma": 1.0,
                "lam": 0.95,
                "cliprange": 0.2,
                "cliprange_value": 0.2,
                "vf_coef": 1.0,
                "gen_kwargs": {
                    "max_length": 8,
                    "min_length": 8,
                    "top_k": 0,
                    "top_p": 1.0,
                    "do_sample": True,
                },
            },
        }
    )


PROMPTS = ["the ", "a qu", "some", "word", "text", "abcd", "lore", "ipsu"] * 4


def reward_fn(texts):
    """Dense synthetic reward: fraction of lowercase letters in the text.
    Combined with a printable-ASCII logit mask (lossless ByteTokenizer
    decode), every rollout gets a distinct, crisp score — a tiny random-init
    model demonstrably learns this in a few rounds, unlike sparse
    token-count rewards."""
    return [float(np.mean([c.islower() for c in t] or [0.0])) for t in texts]


PRINTABLE_MASK = np.zeros(257, bool)
PRINTABLE_MASK[32:127] = True


@functools.lru_cache(maxsize=None)
def build():
    config = make_config()
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    return config, trainer, pipeline, orch




def test_make_experience_fills_store_with_correct_shapes():
    config, trainer, pipeline, orch = build()
    trainer.store.clear_history()
    info = orch.make_experience(config.method.num_rollouts)
    assert len(trainer.store) == 32
    batch = next(iter(trainer.store.create_loader(8)))
    assert batch.query_tensors.shape == (8, 4)
    assert batch.response_tensors.shape == (8, 8)
    assert batch.logprobs.shape == (8, 8)
    assert batch.values.shape == (8, 8)
    assert batch.rewards.shape == (8, 8)
    assert np.isfinite(batch.logprobs).all()
    assert np.isfinite(batch.rewards).all()
    assert info["rollouts"] == 32


def test_train_step_improves_loss_on_fixed_batch():
    config, trainer, pipeline, orch = build()
    trainer.store.clear_history()
    orch.make_experience(config.method.num_rollouts)
    import jax

    batch = next(iter(trainer.store.create_loader(16)))
    batch = jax.tree_util.tree_map(np.asarray, batch)
    losses = []
    for _ in range(4):
        trainer.params, trainer.opt_state, stats = trainer._train_step(
            trainer.params, trainer.opt_state, batch
        )
        losses.append(float(stats["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"


def test_ppo_learns_synthetic_reward():
    """The full loop (learn() driving make_experience per epoch) must raise
    the dense synthetic reward measurably. Deterministic: fixed PRNG seed,
    seeded loaders, deterministic reward."""
    from trlx_tpu.utils.loading import get_model as _gm

    config = make_config(
        total_steps=10**9,
        batch_size=32,
        num_layers_unfrozen=-1,
        learning_rate=6e-2,
        epochs=12,
        ppo_epochs=3,
        num_rollouts=64,
        chunk_size=32,
    )
    config.train.gen_size = 4
    config.method.gen_kwargs.update(max_length=4, min_length=4)
    trainer = _gm(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    trainer.set_logit_mask(PRINTABLE_MASK)
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )

    orch.make_experience(config.method.num_rollouts)
    logs = []
    trainer.learn(log_fn=logs.append)
    scores = [s["mean_score"] for s in logs if "mean_score" in s]
    assert len(scores) >= 8, f"expected per-epoch rollout logs, got {len(scores)}"
    early = float(np.mean(scores[:3]))
    late = float(np.mean(scores[-3:]))
    # each mean_score averages 64 rollouts; noise sigma ~0.02, expected
    # drift ~0.06+ (mean generated byte rises ~8 points / 128)
    assert late > early + 0.03, (
        f"PPO did not learn: early rollout score={early:.4f} "
        f"late={late:.4f} (all: {[round(s, 4) for s in scores]})"
    )


def test_evaluate_rotates_prompts_across_eval_points():
    """Each evaluate() call must score a different slice of the prompt set
    (a fixed first-batch eval overstates metric stability)."""
    config, trainer, pipeline, orch = build()
    seen = []
    orig_reward, trainer.reward_fn = trainer.reward_fn, (
        lambda texts: (seen.append(tuple(texts)), [0.0] * len(texts))[1]
    )
    try:
        trainer.evaluate(n=4)
        trainer.evaluate(n=4)
        trainer.evaluate(n=4)
    finally:
        trainer.reward_fn = orig_reward
    assert len(seen) == 3
    assert len(set(seen)) > 1, "every eval point scored the same prompts"


def test_eos_terminated_rollouts_end_to_end():
    """Variable-length generation (eos enabled, min_length < max_length):
    rollouts carry real per-row response masks and the full
    rollout -> finalize -> GAE -> update path stays finite."""
    config = make_config(total_steps=2, epochs=2, ppo_epochs=1,
                         num_rollouts=16, chunk_size=16, batch_size=16)
    config.method.gen_kwargs.update(min_length=0, max_length=8)
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    # eos that the random policy will actually hit: byte 65 ('A')
    trainer.gen_config = trainer.gen_config._replace(
        eos_token_id=65, min_new_tokens=0)
    trainer._build_jitted_fns()
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    info = orch.make_experience(config.method.num_rollouts)
    assert np.isfinite(info["mean_score"])
    batch = next(iter(trainer.store.create_loader(16)))
    masks = np.asarray(batch.response_masks)
    lengths = masks.sum(axis=1)
    assert lengths.min() < masks.shape[1], "no row ever terminated early"
    # rewards only on real tokens
    rewards = np.asarray(batch.rewards)
    assert np.allclose(rewards[masks == 0], 0.0, atol=1e-6)
    trainer.learn(log_fn=lambda s: None)
    assert trainer.iter_count >= 1


def test_make_experience_crosses_host_boundary_twice_per_chunk(monkeypatch):
    """Architecture guard: one device_get (sequences + seq_kl) and one
    host->device scores transfer per rollout chunk — per-token
    logprobs/values/rewards must never round-trip through the host
    (each sync stalls the host until the device queue drains)."""
    import jax

    import trlx_tpu.orchestrator.ppo_orchestrator as orch_mod

    config, trainer, pipeline, orch = build()
    orch._bank = None  # force a fresh bank upload outside the counter
    orch._idx_loader = None
    bank = orch._prompt_bank()  # uploaded once, not per chunk

    fetches = []
    real_device_get = jax.device_get

    def counting_device_get(x):
        fetches.append(jax.tree_util.tree_leaves(x))
        return real_device_get(x)

    monkeypatch.setattr(orch_mod.jax, "device_get", counting_device_get)

    finals = []
    real_finalize = trainer.finalize_rewards
    monkeypatch.setattr(
        trainer, "finalize_rewards",
        lambda *a: (finals.append(1), real_finalize(*a))[1],
    )

    n_chunks = 2
    trainer.store.clear_history()
    orch.make_experience(n_chunks * orch.chunk_size)

    assert len(fetches) == n_chunks, "expected ONE device_get per chunk"
    assert len(finals) == n_chunks, "expected ONE scores dispatch per chunk"
    for leaves in fetches:
        fetched = sum(np.asarray(leaf).nbytes for leaf in leaves)
        # sequences [B, P+G] int32 + seq_kl [B] f32 and nothing bigger
        B = orch.chunk_size
        expected_max = B * (config.train.input_size
                            + config.train.gen_size) * 4 + B * 4
        assert fetched <= expected_max, (
            f"per-chunk fetch grew to {fetched} bytes - per-token arrays "
            f"are leaking into the host round trip"
        )

def test_make_experience_rounds_up_and_warns():
    """A num_rollouts that is not a chunk_size multiple is rounded UP (whole
    fused chunks only) with a warning, and the info dict reports the count
    actually produced — never fewer than asked, never silently more."""
    import pytest

    config, trainer, pipeline, orch = build()
    trainer.store.clear_history()
    with pytest.warns(UserWarning, match="not a multiple"):
        info = orch.make_experience(8)  # chunk_size is 16
    assert info["rollouts"] == 16
    assert len(trainer.store) == 16

    trainer.store.clear_history()
    with pytest.warns(UserWarning, match="not a multiple"):
        info = orch.make_experience(24)
    assert info["rollouts"] == 32
    assert len(trainer.store) == 32

    with pytest.raises(ValueError, match="positive"):
        orch.make_experience(0)


def test_termination_either_bound():
    """Training stops when EITHER total_steps or epochs is reached — a
    deliberate, documented divergence from the reference, which keeps
    training until BOTH are exceeded (reference
    accelerate_ppo_model.py:174-177) and thereby overruns total_steps
    whenever epochs is the larger bound."""
    def run(total_steps, epochs):
        config = make_config(total_steps=total_steps, epochs=epochs,
                             ppo_epochs=2, batch_size=16,
                             num_rollouts=32, chunk_size=16)
        trainer = get_model(config.model.model_type)(config)
        trainer.tokenizer = ByteTokenizer()
        pipeline = get_pipeline(config.train.pipeline)(
            PROMPTS, trainer.tokenizer, config
        )
        orch = get_orchestrator(config.train.orchestrator)(
            trainer, pipeline, reward_fn=reward_fn, chunk_size=16
        )
        orch.make_experience(config.method.num_rollouts)
        trainer.learn(log_fn=lambda s: None)
        return trainer

    # total_steps binds first: 32 rollouts / 16 batch * 2 ppo_epochs
    # = 4 steps/epoch; stops during the first pass (the post-loop epoch
    # increment leaves the counter at 1), not after 100 epochs
    trainer = run(total_steps=4, epochs=100)
    assert trainer.iter_count == 4
    assert trainer.epoch == 1

    # epochs binds first: one pass over the store, total_steps untouched
    trainer = run(total_steps=10**9, epochs=1)
    assert trainer.iter_count == 4
    assert trainer.epoch == 1


def _fresh_rig(continuous, lr=0.0, epochs=4, total_steps=10**6,
               ppo_epochs=2, masked=False, gen_size=None, **kw):
    config = make_config(total_steps=total_steps, epochs=epochs,
                         learning_rate=lr, ppo_epochs=ppo_epochs, **kw)
    config.train.continuous_rollouts = continuous
    if gen_size is not None:  # before construction: shapes bake into jit
        config.train.gen_size = gen_size
        config.method.gen_kwargs.update(max_length=gen_size,
                                        min_length=gen_size)
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    if masked:
        trainer.set_logit_mask(PRINTABLE_MASK)
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    scores = []

    def recording_reward(texts):
        out = reward_fn(texts)
        scores.append(float(np.mean(out)))
        return out

    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=recording_reward,
        chunk_size=config.method.chunk_size,
    )
    return config, trainer, orch, scores


def test_continuous_rollouts_equivalence_at_lr_zero():
    """train.continuous_rollouts changes WHEN rollouts are dispatched
    (before the epoch's updates, with pre-update params) but nothing
    else: at learning_rate=0 the params never move, so the synced and
    continuous loops must produce bit-identical experience streams —
    same prompt order, same sampling keys, same scores, same final
    store."""
    runs = {}
    for continuous in (False, True):
        config, trainer, orch, scores = _fresh_rig(continuous)
        orch.make_experience(config.method.num_rollouts)
        trainer.learn(log_fn=lambda s: None)
        stacked = trainer.store._stacked()
        runs[continuous] = (
            scores,
            jax.device_get(jax.tree_util.tree_leaves(stacked)),
            trainer.iter_count,
            trainer.epoch,
        )
    assert runs[False][0] == runs[True][0], "score streams diverged"
    assert runs[False][2] == runs[True][2]
    assert runs[False][3] == runs[True][3]
    for a, b in zip(runs[False][1], runs[True][1]):
        np.testing.assert_array_equal(a, b)


def test_continuous_rollouts_trains_with_stale_experience():
    """With a real learning rate, continuous mode still learns the
    synthetic lowercase task (staleness of one update phase does not
    break optimization) and runs the same number of refreshes as the
    synced loop would."""
    # the geometry test_ppo_learns_synthetic_reward demonstrates learning
    # with (printable mask, full unfreeze, short gens), lr tempered for the
    # off-policy refresh
    config, trainer, orch, scores = _fresh_rig(
        True, lr=3e-2, epochs=12, total_steps=10**6, ppo_epochs=3,
        masked=True, batch_size=32, num_layers_unfrozen=-1,
        num_rollouts=64, chunk_size=32, gen_size=4,
    )
    orch.make_experience(config.method.num_rollouts)
    trainer.learn(log_fn=lambda s: None)
    # 12 epochs x (64 rollouts / 32 batch) x 3 ppo passes
    assert trainer.iter_count == 12 * 2 * 3
    assert trainer.epoch == 12
    # 11 refreshes + the initial make_experience, 2 chunks each
    assert len(scores) == 12 * 2
    assert np.mean(scores[-4:]) > np.mean(scores[:4]) + 0.03
