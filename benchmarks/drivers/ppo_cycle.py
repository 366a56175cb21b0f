"""ppo_cycle: whole PPO cycles back to back. One cycle is
``PPOOrchestrator.make_experience(batch)`` (the fused rollout program, the
host reward, the store) and ``JaxPPOTrainer.learn()`` (one dispatch of
``ppo_epochs`` AdamW steps), built through the program's registries.

Set-up drives the same trainer through its first cycle and keeps what that
cycle produced (sampled tokens, rollout log-probabilities and values, the
last step's loss, Adam's first moment, the parameters' change); ``check``
replays that cycle from the tokens alone in the plain reference."""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import common
from benchmarks.lib import reference as R
from benchmarks.lib import traffic as T


class Cell:
    def __init__(self, env):
        self.cell, self.spec, self.mix, self.seed = env["cell"], env["spec"], env["mix"], env["seed"]
        self.B = self.mix["batch"]
        self.P, self.G = self.mix["prompt_tokens"], self.mix["gen_tokens"]
        self.k = self.cell["model"]["num_layers_unfrozen"]
        self.frozen_dtype = common.DTYPES[self.cell["model"].get("param_dtype", "float32")]
        self.say = env.get("say", lambda text: None)
        self.stats_log = []
        self.compiles = common.CompileCounter()

    # ------------------------------------------------------------ set-up
    def setup(self):
        from trlx_tpu.utils.loading import get_model, get_orchestrator, get_pipeline
        from trlx_tpu.utils.tokenizer import ByteTokenizer

        mix, opt = self.mix, self.mix["optimizer"]
        method = dict(mix["method"])
        method["gen_kwargs"] = {"max_length": self.G, "min_length": self.G, **mix["sampling"]}
        config = common.trl_config(
            self.spec, self.cell["model"],
            {"batch_size": self.B, "total_steps": method["ppo_epochs"], "log_interval": method["ppo_epochs"],
             "input_size": self.P, "gen_size": self.G, "grad_clip": opt["grad_clip"],
             "weight_decay": opt["weight_decay"], "learning_rate_init": opt["learning_rate"],
             "learning_rate_target": opt["learning_rate"], **self.cell.get("train", {})},
            method, self.seed)
        trainer = get_model(config.model.model_type)(config)
        trainer.tokenizer = ByteTokenizer()
        self.say("trainer built")
        # the benchmark's weights take the place of the program's own init
        shapes = jax.eval_shape(lambda: trainer.params)
        trainer.params = None
        params = common.hydra_weights(self.spec, self.seed, self.k, self.frozen_dtype, True)
        common.same_layout(params, shapes)
        trainer.params = params
        jax.block_until_ready(params)
        self.say("weights made from the seed")
        pipeline = get_pipeline(config.train.pipeline)(T.ppo_prompts(mix, self.seed), trainer.tokenizer, config)

        def reward_fn(texts):
            with common.annotation("bench/reward_fn"):
                return [T.reward(t) for t in texts]

        self.orch = get_orchestrator(config.train.orchestrator)(
            trainer, pipeline, reward_fn=reward_fn, chunk_size=method["chunk_size"])
        self.trainer = trainer

        # the first cycle, through the window's own calls, kept for ``check``
        theta0 = jax.device_get(trainer.params["trainable"])  # on the host: the chip holds what the program holds
        kept = {}
        rollout = trainer.rollout

        def rollout_kept(*a):
            pending = rollout(*a)
            kept["decode_logp"] = pending[0].gen_logprobs
            return pending

        trainer.rollout = rollout_kept
        self.cycle()
        trainer.rollout = rollout
        self.say("first cycle done")
        self.first = self._readings(theta0, kept)
        del theta0, kept
        self.say("its readings kept")
        self.cycle()  # warm: the second cycle has to find every program compiled
        self.stats_log.clear()

    def cycle(self):
        tr = self.trainer
        tr.store.clear_history()
        tr.iter_count = 0
        tr.epoch = 0
        a = time.perf_counter()
        with common.annotation("bench/make_experience"):
            self.orch.make_experience(self.B)
        b = time.perf_counter()
        with common.annotation("bench/learn"):
            tr.learn(log_fn=self.stats_log.append)
            np.asarray(jax.tree_util.tree_leaves(tr.params["trainable"])[0].ravel()[:1])  # the fetch that ends it
        return b - a, time.perf_counter() - b

    def _readings(self, theta0, kept):
        """What the first cycle produced, small enough to keep on the host."""
        tr = self.trainer
        batch = tr.store._stacked()
        adam = next(s for s in jax.tree_util.tree_leaves(tr.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                    if hasattr(s, "mu"))
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))(tr.params["trainable"], theta0)
        stats = [s for s in self.stats_log if "loss" in s]
        return {
            "tokens": np.concatenate([np.asarray(batch.query_tensors), np.asarray(batch.response_tensors)], 1),
            "logprobs": np.asarray(batch.logprobs), "values": np.asarray(batch.values),
            "rewards": np.asarray(batch.rewards), "decode_logp": np.asarray(kept["decode_logp"]),
            "loss": float(stats[-1]["loss"]) if stats else float("nan"),
            "mu": R.leaf_norms(adam.mu), "delta": R.leaf_norms(delta),
        }

    # ------------------------------------------------------------ window
    def _jit_cache_sizes(self):
        tr = self.trainer
        return {n: getattr(tr, n)._cache_size() for n in ("_rollout_fn", "_train_multi_indexed", "_train_multi")
                if hasattr(getattr(tr, n, None), "_cache_size")}

    def window(self, seconds, tracer=None):
        before = (self.compiles.count, common.fault_counters(), self._jit_cache_sizes())
        trace_cycles = self.cell.get("trace", {}).get("cycles", 3) if tracer else 0
        spans, t0 = [], time.perf_counter()
        if tracer:
            tracer.start()
        while True:
            spans.append(self.cycle())
            if tracer and len(spans) == trace_cycles:
                tracer.stop()
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        after = (self.compiles.count, common.fault_counters(), self._jit_cache_sizes())
        self.measured = {
            "cycles": len(spans), "elapsed_s": elapsed, "samples": len(spans) * self.B,
            "rollout_s": [s[0] for s in spans], "update_s": [s[1] for s in spans],
            "compiles_in_window": after[0] - before[0],
            "fault_counters_moved": sum(abs(after[1].get(k, 0) - before[1].get(k, 0)) for k in after[1]),
            "jit_cache_growth": sum(after[2][k] - before[2][k] for k in after[2]),
        }
        return self.measured

    def end_to_end(self):
        m = self.measured
        return {"ppo_samples_per_s": m["samples"] / m["elapsed_s"]}, m["cycles"], 0

    def release(self):
        self.trainer = self.orch = None
        gc.collect()

    # ------------------------------------------------------------ correct
    def hyper(self):
        m, o = self.mix["method"], self.mix["optimizer"]
        return {"prompt": self.P, "gen": self.G, "k_unfrozen": self.k, "ppo_epochs": m["ppo_epochs"],
                "kl_coef": m["init_kl_coef"], "gamma": m["gamma"], "lam": m["lam"], "cliprange": m["cliprange"],
                "cliprange_value": m["cliprange_value"], "vf_coef": m["vf_coef"], "lr": o["learning_rate"],
                "weight_decay": o["weight_decay"], "grad_clip": o["grad_clip"], "b1": o["b1"], "b2": o["b2"],
                "eps": o["eps"]}

    @staticmethod
    def gaps(got, ref, keep):
        """The numbers compared: ``got`` (the program, or a control put in its place) against the reference."""
        vscale = float(np.std(ref["old_values"])) + 1e-30
        mu, delta = R.leaf_gaps(got["mu"], ref["mu"]), R.leaf_gaps(got["delta"], ref["delta"], keep)
        out = {
            "roll_logp": float(np.max(np.abs(got["logprobs"] - ref["old_logp"]))),
            "roll_value": float(np.max(np.abs(got["values"] - ref["old_values"])) / vscale),
            # over the largest loss of the reference's steps: the last one alone comes near nought on some seeds
            "loss_last": abs(got["loss"] - ref["losses"][-1]) / max(max(abs(x) for x in ref["losses"]), 1e-30),
            # the worst leaf, and the median leaf: Adam makes the worst one swing from seed to seed (PERF.md section 2)
            "adam_mu_leaf": max(mu.values()), "adam_mu_median": float(np.median(list(mu.values()))),
            "delta_leaf": max(delta.values()), "delta_median": float(np.median(list(delta.values()))),
        }
        if "decode_logp" in got:
            out["decode_logp"] = float(np.max(np.abs(got["decode_logp"] - ref["old_logp"])))
        if "rewards" in got:
            out["roll_reward"] = float(np.max(np.abs(got["rewards"] - ref["rewards"])))
        return out

    def _reference(self, tokens, **kw):
        scores = [T.reward(T.decode_bytes(row)) for row in tokens]
        return R.ppo_reference(self.spec, self.seed, tokens, scores, self.hyper(), frozen_dtype=self.frozen_dtype, **kw)

    @staticmethod
    def _as_got(r):
        return {"logprobs": r["old_logp"], "values": r["old_values"], "decode_logp": r["old_logp"],
                "rewards": r["rewards"], "loss": r["losses"][-1], "mu": r["mu"], "delta": r["delta"]}

    def check(self, probe=False):
        first, limits = self.first, self.cell["correct"]
        ref = self._reference(first["tokens"])
        keep = R.moving_leaves(ref["g1"])
        readings = self.gaps(first, ref, keep)
        m = self.measured
        for name in ("compiles_in_window", "fault_counters_moved", "jit_cache_growth"):
            readings[name] = m[name]
        compared = {n: {"value": v, "limit": limits[n], "ok": bool(v <= limits[n])}
                    for n, v in readings.items() if n in limits}
        extra = {"uncompared": {n: v for n, v in readings.items() if n not in limits},
                 "left_out_of_delta": sorted(set(ref["g1"]) - keep), "ref_losses": ref["losses"],
                 "program_loss": first["loss"],
                 "worst_leaves": {"adam_mu": R.worst_leaf_gap(first["mu"], ref["mu"])[1],
                                  "delta": R.worst_leaf_gap(first["delta"], ref["delta"], keep)[1]}}
        if probe:
            extra["probe"] = self.probe(first, ref, keep)
        return all(c["ok"] for c in compared.values()), compared, extra

    def probe(self, first, ref, keep):
        """Readings of the control (the reference in fp8, put in the program's
        place) and of the planted faults, by the same measure as the program's."""
        control = self._as_got(self._reference(first["tokens"], mm="fp8"))

        def leaves(got):  # every leaf's gap, for the look at a worst leaf that swings from seed to seed
            return {"mu": R.leaf_gaps(got["mu"], ref["mu"]), "delta": R.leaf_gaps(got["delta"], ref["delta"], keep)}

        out = {"program": self.gaps(first, ref, keep), "control_fp8": self.gaps(control, ref, keep),
               "leaves": {"program": leaves(first), "control_fp8": leaves(control)},
               "ref_norms": {"mu": ref["mu"], "g1": ref["g1"]}}
        half = np.arange(first["tokens"].shape[0] // 2)
        flt = self._as_got(self._reference(first["tokens"], rows=half))
        for name, of_ref in (("logprobs", "old_logp"), ("values", "old_values"), ("decode_logp", "old_logp"),
                             ("rewards", "rewards")):
            flt[name] = ref[of_ref]  # the rollout is not what this fault breaks
        out["fault_half_batch"] = self.gaps(flt, ref, keep)
        out["leaves"]["fault_half_batch"] = leaves(flt)
        altered = first["tokens"].copy()
        altered[:, self.P + self.G // 2] = (altered[:, self.P + self.G // 2] + 1) % self.spec["vocab_size"]
        out["fault_token_altered"] = self.gaps(self._as_got(self._reference(altered)), ref, keep)
        unchanged = dict(self._as_got(ref), mu={k: 0.0 for k in ref["mu"]}, delta={k: 0.0 for k in ref["delta"]},
                         loss=ref["losses"][0])
        out["fault_state_unchanged"] = self.gaps(unchanged, ref, keep)
        return out
