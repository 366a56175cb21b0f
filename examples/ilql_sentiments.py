"""Offline ILQL sentiment tuning
(parity: reference examples/ilql_sentiments.py).

Online path: gpt2 trunk, labeled IMDB reviews as offline data, distilbert
sentiment as reward_fn for scoring train returns and eval generations.

Offline fallback: the SAME wiring on a from-config tiny model with a byte
tokenizer and a synthetic labeled corpus (sentences containing "good" are
positive, "bad" negative); reward is a lexicon count. Demonstrates the
offline RL path end-to-end without the hub.

Run: python examples/ilql_sentiments.py [--config configs/ilql_config.yml]
"""

import argparse
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.utils.loading import get_model, get_orchestrator


def online_pieces(config):
    from datasets import load_dataset
    from transformers import pipeline as hf_pipeline

    sentiment_pipe = hf_pipeline(
        "sentiment-analysis", "lvwerra/distilbert-imdb", device=-1
    )

    def reward_fn(samples):
        if samples and not isinstance(samples[0], str):
            # token rows from eval generations -> text
            samples = ["".join(map(chr, (t for t in s if t < 256)))
                       for s in samples]
        out = sentiment_pipe(samples, return_all_scores=True, batch_size=32)
        return [scores[1]["score"] for scores in out]

    ds = load_dataset("imdb", split="train")
    train_samples = [t for t in ds["text"] if len(t) < 500][:4096]
    # bos-only eval prompts, as the reference uses
    # (examples/ilql_sentiments.py:37-41)
    eval_prompts = ["<|endoftext|>"] * 64
    return reward_fn, train_samples, eval_prompts


def offline_pieces(config):
    config.model.model_spec = {
        "vocab_size": 257,
        "n_layer": 4,
        "n_head": 8,
        "d_model": 256,
        "n_positions": 64,
    }
    config.model.tokenizer_path = "byte"
    config.model.compute_dtype = "float32"
    config.train.epochs = 8
    config.train.batch_size = 64
    config.train.gen_size = 24
    config.train.eval_interval = 50
    config.train.checkpoint_interval = 10**9

    rng = np.random.default_rng(0)
    fillers = ["the movie was", "i think it is", "overall it felt",
               "honestly it was", "the plot seemed"]
    pos, neg = "good", "bad"
    train_samples = [
        f"{rng.choice(fillers)} {pos if rng.random() < 0.5 else neg}"
        for _ in range(2048)
    ]

    def reward_fn(samples):
        if samples and not isinstance(samples[0], str):
            samples = ["".join(map(chr, (int(t) for t in s if int(t) < 256)))
                       for s in samples]
        return [float(s.count(pos)) - float(s.count(neg)) for s in samples]

    eval_prompts = ["the movie was"] * 32
    return reward_fn, train_samples, eval_prompts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=str(
        Path(__file__).resolve().parent.parent / "configs" / "ilql_config.yml"
    ))
    args = ap.parse_args()
    config = TRLConfig.load_yaml(args.config)

    try:
        reward_fn, train_samples, eval_prompts = online_pieces(config)
        print("using HF sentiment reward + IMDB offline data")
    except Exception as e:
        print(f"HF assets unavailable ({type(e).__name__}); "
              "running the offline synthetic fallback")
        reward_fn, train_samples, eval_prompts = offline_pieces(config)

    trainer = get_model(config.model.model_type)(config)
    get_orchestrator(config.train.orchestrator)(
        trainer, train_samples, eval_prompts, reward_fn=reward_fn
    )
    print({"before": trainer.evaluate(n=32)})
    trainer.learn()
    print({"after": trainer.evaluate(n=32)})


if __name__ == "__main__":
    from trlx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
