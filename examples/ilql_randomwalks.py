"""Offline ILQL on the random-walks graph task — the reference's designed
smoke test (parity: reference examples/ilql_randomwalks.py:76-110).

Fully offline: synthetic graph data, from-config tiny GPT-2, programmatic
reward and percent-of-optimal-path metric. Runs on CPU or one TPU chip in
about a minute.

Run: python examples/ilql_randomwalks.py
"""

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from examples.randomwalks_data import generate_random_walks
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.utils.loading import get_model, get_orchestrator


def main():
    config = TRLConfig.load_yaml(str(
        Path(__file__).resolve().parent.parent / "configs" / "ilql_config.yml"
    ))
    # the reference overrides the shipped ILQL config the same way
    # (examples/ilql_randomwalks.py:79-81, 98-100)
    config.train.gen_size = 10
    config.train.epochs = 10
    config.train.batch_size = 64
    config.train.eval_interval = 50
    config.train.log_interval = 25
    config.train.checkpoint_interval = 10**9
    config.model.tokenizer_path = "byte"
    config.model.compute_dtype = "float32"

    walks, logit_mask, stats_fn, reward_fn = generate_random_walks(seed=1000)
    config.model.model_spec = {
        "vocab_size": int(logit_mask.shape[0]),
        "n_layer": 4,
        "n_head": 4,
        "d_model": 144,
        "n_positions": 16,
    }
    eval_prompts = np.arange(1, logit_mask.shape[0]).reshape(-1, 1)

    trainer = get_model(config.model.model_type)(config, logit_mask=logit_mask)
    get_orchestrator(config.train.orchestrator)(
        trainer, walks, eval_prompts, reward_fn=reward_fn, stats_fn=stats_fn
    )

    print({"walk_baseline": stats_fn(walks)})
    print({"before": trainer.evaluate()})
    trainer.learn()
    print({"after": trainer.evaluate()})


if __name__ == "__main__":
    from trlx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
