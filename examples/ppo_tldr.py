"""PPO TL;DR summarization with a mesh-resident learned reward model.

The BASELINE.md workload beyond the reference's surface: instead of a host
`reward_fn` callback (the reference's only reward path), the reward model
is a trunk + scalar head CO-RESIDENT with the policy on the mesh
(trlx_tpu/models/reward.py) — rollout scoring runs jitted on device and
its scores ride the orchestrator's single per-chunk fetch, so a learned
RM costs zero extra host round trips.

Online path (HF hub available): gpt2 policy + an RM initialized from the
same pretrained trunk with a fresh scalar head (stand-in for a trained
summarization RM checkpoint). Offline fallback: the SAME wiring on
from-config tiny models with synthetic documents.

Run: python examples/ppo_tldr.py [--config configs/ppo_tldr.yml]
"""

import argparse
import sys
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.models.reward import DeviceRewardModel, RewardModel
from trlx_tpu.utils.loading import get_model, get_orchestrator, get_pipeline


def synthetic_documents(n=256, seed=0):
    """Deterministic document-like prompts ending in the TL;DR cue."""
    rng = np.random.default_rng(seed)
    words = ["data", "model", "train", "loss", "token", "batch", "step",
             "eval", "mesh", "chip"]
    docs = []
    for _ in range(n):
        body = " ".join(rng.choice(words, size=30))
        docs.append(body + "\nTL;DR:")
    return docs


def build_reward_model(config, trainer):
    """RM co-resident on the trainer's mesh, initialized from the trainer's
    OWN already-loaded trunk — the checkpoint is read exactly once (at 6B
    scale a second host copy would double peak RAM). With a from-config
    trainer this reuses its random-init trunk; either way the RM gets a
    fresh scalar head (stand-in for a trained RM checkpoint)."""
    spec = trainer.policy.spec
    model = RewardModel(
        spec=spec,
        compute_dtype=trainer.policy.compute_dtype,
    )
    p = trainer.params
    embed = dict(p["frozen_base"]["embed"])
    blocks = trainer.policy.all_blocks(p)  # (bottom, top) segment pair
    ln_f = p["trainable"]["ln_f"]
    # DeviceRewardModel deep-copies, decoupling the RM from the trainer's
    # donated buffers
    params = model.from_trunk(embed, blocks, ln_f, jax.random.PRNGKey(1))
    return DeviceRewardModel(
        model, params, trainer.tokenizer, mesh=trainer.mesh,
        max_length=config.train.input_size + config.train.gen_size,
    )


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default=str(
        Path(__file__).resolve().parent.parent / "configs" / "ppo_tldr.yml"
    ))
    args = parser.parse_args()
    config = TRLConfig.load_yaml(args.config)

    offline = False
    try:
        # pretrained path: the trainer loads the checkpoint (once); the RM
        # below reuses that trunk
        trainer = get_model(config.model.model_type)(config)
    except RuntimeError as e:
        offline = True
        print(f"pretrained load unavailable ({e}); "
              f"running the offline synthetic fallback", file=sys.stderr)
        # offline fallback: tiny from-config policy, byte tokenizer,
        # short synthetic documents
        config.model.model_spec = {
            "vocab_size": 257, "n_layer": 4, "n_head": 8, "d_model": 256,
            "n_positions": 128,
        }
        config.model.tokenizer_path = "byte"
        config.model.compute_dtype = "float32"
        config.train.input_size = 48
        config.train.gen_size = 16
        config.train.epochs = 4
        config.train.batch_size = 16
        config.method.num_rollouts = 32
        config.method.chunk_size = 16
        config.method.gen_kwargs = {"max_length": 16, "min_length": 16,
                                    "do_sample": True}
        config.train.log_interval = 4
        config.train.eval_interval = 10**9
        config.train.checkpoint_interval = 10**9
        trainer = get_model(config.model.model_type)(config)

    if offline:
        from trlx_tpu.utils.tokenizer import ByteTokenizer

        trainer.tokenizer = ByteTokenizer()

    reward_model = build_reward_model(config, trainer)
    prompts = synthetic_documents()
    pipeline = get_pipeline(config.train.pipeline)(
        prompts, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_model,
        chunk_size=config.method.chunk_size,
    )
    info = orch.make_experience(config.method.num_rollouts)
    print({"first_rollout": info})
    trainer.learn()
    print({"final_eval": trainer.evaluate()})


if __name__ == "__main__":
    from trlx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
