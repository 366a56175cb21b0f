"""PPO sentiment tuning — the reference's primary example
(parity: reference examples/ppo_sentiments.py:1-39).

Online path (HF hub or local cache available): lvwerra/gpt2-imdb policy,
distilbert-imdb sentiment reward on the host, IMDB prompts.

Offline fallback (no network, no cache): the SAME wiring — registry-built
trainer, prompt pipeline, orchestrator, learn loop — on a from-config tiny
model with a byte tokenizer and a synthetic lowercase-ratio reward. The
fallback demonstrates the loop end-to-end without pretending to be
sentiment; swap in the online pieces on a connected machine.

Run: python examples/ppo_sentiments.py [--config configs/ppo_config.yml]
"""

import argparse
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.utils.loading import get_model, get_orchestrator, get_pipeline


def online_pieces(config):
    """(reward_fn, prompts) from HF assets; raises when unreachable."""
    from datasets import load_dataset
    from transformers import pipeline as hf_pipeline

    sentiment_pipe = hf_pipeline(
        "sentiment-analysis", "lvwerra/distilbert-imdb", device=-1
    )

    def reward_fn(samples):
        # positive-class logit, as the reference's sentiment_score
        # (reference: examples/ppo_sentiments.py:20-28)
        out = sentiment_pipe(samples, return_all_scores=True, batch_size=32)
        return [scores[1]["score"] for scores in out]

    ds = load_dataset("imdb", split="test")
    prompts = [t for t in ds["text"] if len(t) < 500]
    return reward_fn, prompts


def offline_pieces(config):
    """Synthetic fallback: tiny from-config model, byte tokenizer,
    lowercase-ratio reward."""
    config.model.model_spec = {
        "vocab_size": 257,
        "n_layer": 4,
        "n_head": 8,
        "d_model": 256,
        "n_positions": 128,
    }
    config.model.tokenizer_path = "byte"
    config.model.compute_dtype = "float32"
    config.train.epochs = 6
    config.train.total_steps = 200
    # always leave the observability record behind, even if this demo is
    # killed before its first checkpoint creates the run dir
    config.train.telemetry_dir = config.train.checkpoint_dir
    # save often enough that a killed demo run has something to resume
    # from (the YAML's resume_from: auto picks it up on the next launch)
    config.train.checkpoint_interval = 50
    # per-iteration observability (time/* breakdown, throughput/*,
    # fault/*) every 4 steps — the demo run is short
    config.train.log_interval = 4
    config.train.batch_size = 64
    config.method.num_rollouts = 64
    config.method.chunk_size = 64
    config.train.learning_rate_init = 2e-3
    config.train.learning_rate_target = 1e-3

    def reward_fn(samples):
        return [
            float(np.mean([c.islower() for c in s] or [0.0])) for s in samples
        ]

    rng = np.random.default_rng(0)
    prompts = [
        "".join(chr(c) for c in rng.integers(32, 127, size=12))
        for _ in range(256)
    ]
    return reward_fn, prompts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=str(
        Path(__file__).resolve().parent.parent / "configs" / "ppo_config.yml"
    ))
    args = ap.parse_args()
    config = TRLConfig.load_yaml(args.config)

    try:
        reward_fn, prompts = online_pieces(config)
        print("using HF sentiment reward + IMDB prompts")
    except Exception as e:
        print(f"HF assets unavailable ({type(e).__name__}); "
              "running the offline synthetic fallback")
        reward_fn, prompts = offline_pieces(config)

    trainer = get_model(config.model.model_type)(config)
    # the shipped config says resume_from: "auto" — kill this script at
    # any point and relaunch it; it continues from the newest committed
    # checkpoint under train.checkpoint_dir (keep_checkpoints bounds the
    # disk it uses). First launch: nothing to resume, fresh start.
    if getattr(trainer, "_resumed", False):
        print(f"resumed from checkpoint at iter {trainer.iter_count} "
              f"(train.resume_from: {config.train.resume_from!r})")
    pipeline = get_pipeline(config.train.pipeline)(
        prompts, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    info = orch.make_experience(config.method.num_rollouts)
    print({"rollout": info})
    trainer.learn()
    # the learn loop logged time/* / throughput/* / fault/* per interval
    # and left telemetry.json + trace.jsonl (open in https://ui.perfetto.dev)
    # in the run dir — see docs/source/observability.rst
    run_dir = config.train.telemetry_dir or config.train.checkpoint_dir
    print(f"observability record (telemetry.json + Perfetto trace.jsonl) "
          f"under {run_dir!r}")


if __name__ == "__main__":
    from trlx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
