"""What both drivers share: the program's config object, the benchmark's
weights in the program's (hydra) layout, counters and compile events."""

import jax
import jax.numpy as jnp

from benchmarks.lib import weights as W

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def deep_update(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = deep_update(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def trl_config(spec: dict, model: dict, train: dict, method: dict, seed: int):
    """The program's TRLConfig for a from-config model (no checkpoint, no hub)."""
    from trlx_tpu.data.configs import TRLConfig

    base_train = {
        "n_ctx": spec["n_positions"], "epochs": 1, "total_steps": 4, "batch_size": 8, "grad_clip": 1.0,
        "lr_ramp_steps": 0, "lr_decay_steps": 4, "weight_decay": 1e-6, "learning_rate_init": 1e-4,
        "learning_rate_target": 1e-4, "log_interval": 10**9, "checkpoint_interval": 10**9,
        "eval_interval": 10**9, "pipeline": "PPOPipeline", "orchestrator": "PPOOrchestrator",
        "input_size": 4, "gen_size": 8, "seed": int(seed) & 0x7FFFFFFF,
        # a directory that never exists: the trainer then writes no telemetry files
        "checkpoint_dir": "benchmarks/.run/none",
    }
    return TRLConfig.from_dict({
        "model": {"model_path": "from-config", "tokenizer_path": "byte", "model_type": "JaxPPOTrainer",
                  "model_spec": dict(spec), **model},
        "train": {**base_train, **train},
        "method": {"name": "ppoconfig", **method},
    })


def hydra_weights(spec: dict, seed: int, k: int, frozen_dtype, with_ref_and_value: bool,
                  trainable_store_dtype=jnp.float32):
    """The benchmark's weights (lib/weights.py) in the program's layout:
    frozen_base (embedding + bottom L-k blocks), trainable (top k blocks,
    final norm, heads; float32) and, for the trainer, the reference branch.
    One jitted call from the seed, on the device, in the types kept."""
    key = W.base_key(seed)
    L = spec["n_layer"]

    @jax.jit
    def make(key):  # an argument: closed over, the seed would be a constant of the program and every seed a compile
        top = W.top_params(spec, key, with_ref_and_value, trainable_store_dtype, jnp.float32)
        embed = W.top_params(spec, key, False, frozen_dtype, frozen_dtype, only=("embed",))["embed"]

        def branch():
            b = {"blocks": W.stacked_layers(spec, key, L - k, L, trainable_store_dtype, jnp.float32),
                 "ln_f": top["ln_f"]}
            if "lm_head" in top:
                b["lm_head"] = top["lm_head"]
            return b

        trainable = branch()
        tree = {"frozen_base": {"embed": embed,
                                "blocks": W.stacked_layers(spec, key, 0, L - k, frozen_dtype, frozen_dtype)},
                "trainable": trainable}
        if with_ref_and_value:
            trainable["v_head"] = top["v_head"]
            tree["ref"] = jax.tree_util.tree_map(lambda x: x.astype(frozen_dtype), branch())
        return tree

    return make(key)


def same_layout(ours, theirs) -> None:
    """The program's param tree has to be the one the benchmark fills."""
    a, b = jax.tree_util.tree_structure(ours), jax.tree_util.tree_structure(theirs)
    if a != b:
        raise RuntimeError(f"the program's param tree changed: benchmark {a} vs program {b}")
    for x, y in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise RuntimeError(f"param leaf differs: benchmark {x.shape}/{x.dtype} vs program {y.shape}/{y.dtype}")


class CompileCounter:
    """Counts every backend compile request JAX makes (cache hit or not)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


def fault_counters() -> dict:
    from trlx_tpu import telemetry

    tel = telemetry.current()
    if tel is None:
        return {}
    return {k: v for k, v in tel.registry.counters.items()
            if k.startswith("fault/") or k == "compile/recompiles"}


def annotation(name: str):
    return jax.profiler.TraceAnnotation(name)
