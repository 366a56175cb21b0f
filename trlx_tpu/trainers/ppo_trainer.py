"""PPO trainer: jitted train step, rollout scoring, and the learn loop.

Parity target: reference `AcceleratePPOModel` + `AccelerateRLModel`
(reference: trlx/model/accelerate_ppo_model.py:47-209,
trlx/model/accelerate_base_model.py:26-185). TPU-first differences:

- One jitted `train_step` does GAE (lax.scan) + advantage whitening + the
  forward + clipped losses + optax update; the reference runs a Python GAE
  loop and separate backward/step calls (accelerate_ppo_model.py:68-82,196-203).
- One jitted rollout program (`rollout`) selects prompts from the
  device-resident bank, generates, and scores — policy logprobs, frozen-ref
  logprobs, values, and per-token KL-penalty rewards in a single forward
  that shares the trunk. The reference runs generate + the trained model
  AND a second hydra/CPU-copy pass (ppo_orchestrator.py:64-98).
- Gradient clipping and weight decay from the config are actually applied
  (the reference configures but never applies them — SURVEY quirks).
- Distribution comes from the mesh (trlx_tpu.parallel), not an Accelerator.

Registered under both "JaxPPOTrainer" and the reference's name
"AcceleratePPOModel" so reference YAMLs resolve.
"""


import sys
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.ppo_types import PPORLBatch
from trlx_tpu.models.generation import (
    GenerationConfig,
    decide_unroll,
    generate,
)
from trlx_tpu.models.hf_import import hydra_params_from_trunk
from trlx_tpu.models.policy import HydraPolicy, resolve_num_unfrozen
from trlx_tpu.ops.losses import (
    chunked_label_logprobs,
    gae_advantages,
    kl_penalty_rewards,
    logprobs_from_logits,
    ppo_losses,
    whiten,
)
from trlx_tpu.pipeline.ppo_pipeline import PPORolloutStorage
from trlx_tpu.trainers import BaseRLTrainer, register_trainer
from trlx_tpu.trainers.kl_controllers import make_kl_controller
from trlx_tpu.utils import Clock, cosine_schedule
from trlx_tpu.utils.jaxpr_scans import scan_sites
from trlx_tpu.utils.tokenizer import load_tokenizer
from trlx_tpu.utils.trackers import generations_table, make_tracker

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


def build_optimizer(train_config, sched=None) -> optax.GradientTransformation:
    """Grad-clip + configured optimizer + LR schedule (default: cosine
    anneal from lr_init to lr_target over total_steps; the ILQL trainer
    passes its ramp-up/decay schedule instead). Reference parity:
    accelerate_base_model.py:63-70, with clip and weight decay actually
    wired.

    train.optimizer selects the state/memory tradeoff — "adamw" (default,
    reference parity; train.adam_moment_dtype: bfloat16 halves the first
    moment) or "adafactor" (factored second moment, no first moment:
    optimizer state drops from 8 bytes/param to ~0, the lever that fits
    6B-class PPO on one 16 GB chip). _check_memory_fit counts the same
    choice."""
    if sched is None:
        sched = cosine_schedule(
            train_config.learning_rate_init,
            train_config.total_steps,
            lr_min=train_config.learning_rate_target,
        )
    name = getattr(train_config, "optimizer", "adamw").lower()
    if name == "adafactor":
        opt = optax.adafactor(
            learning_rate=sched,
            weight_decay_rate=train_config.weight_decay or None,
        )
    elif name == "adamw":
        opt = optax.adamw(
            sched,
            weight_decay=train_config.weight_decay,
            mu_dtype=DTYPES[
                getattr(train_config, "adam_moment_dtype", "float32")
            ],
        )
    else:
        raise ValueError(
            f"train.optimizer '{name}' is not one of: adamw, adafactor"
        )
    return optax.chain(
        optax.clip_by_global_norm(train_config.grad_clip), opt
    )


#: the named scope of the frozen trunk's forward inside an update program
#: (with "update/top", "update/loss", "update/opt": op metadata only, what
#: a device trace's ops are grouped by)
TRUNK_SCOPE = "update/trunk"


def trunk_passes(jaxpr) -> int:
    """How many times one call of a traced update function runs the
    frozen trunk: the layer loops under ``TRUNK_SCOPE`` (the outermost
    there: a pipelined trunk nests the layers' loop in its ticks'), each
    times the trip counts of the loops it stands in. 1 where the trunk is
    evaluated beside the scan over ``ppo_epochs``, ``ppo_epochs`` where
    it is evaluated inside; 0 where no layer is frozen."""
    return sum(
        site.runs for site in scan_sites(jaxpr)
        if TRUNK_SCOPE in site.scope
        and not any(TRUNK_SCOPE in s.scope for s in site.enclosing)
    )


def ppo_update_fns(policy: HydraPolicy, method, opt, guard_on: bool = False,
                   max_step_kl: float = 0.0):
    """The PPO update as pure functions ``(train_step, train_multi,
    train_multi_indexed)`` of ``(params, opt_state, batch)``, for the
    trainer to jit.

    One dispatch is two parts. ``shared`` computes what every pass over a
    batch reads and none changes: GAE + whitened advantages, the token
    and attention arrays, and the frozen trunk's output ``h`` (the bottom
    ``L - k`` blocks read ``params["frozen_base"]`` alone). ``one_pass``
    is one optimization pass that starts from it: the trainable top's
    forward and backward, the clipped losses, the optimizer step.
    ``train_multi`` runs ``shared`` once, OUTSIDE its scan over
    ``ppo_epochs``, and scans ``one_pass``; ``train_step`` is the same
    two parts run once. The structure is the program's, not the
    compiler's: XLA:CPU moves a loop-invariant inner loop out of a scan
    by itself, XLA:TPU does not (measured on v5e at gpt2-xl: with the
    trunk inside the scanned body, three of four 46-layer forwards
    recomputed the same ``h``). ``trunk_passes`` reads the structure back
    off the traced program; ``tests/test_ppo_update_structure.py`` holds
    it."""
    m = method

    def shared(params, batch: PPORLBatch):
        query = batch.query_tensors
        response = batch.response_tensors
        resp_mask = batch.response_masks
        advantages, returns = gae_advantages(
            batch.values, batch.rewards, m.gamma, m.lam, mask=resp_mask
        )
        advantages = jax.lax.stop_gradient(
            whiten(advantages, mask=resp_mask)
        )
        tokens = jnp.concatenate([query, response], axis=1)
        # attention matches what generation attended (the rollout's own
        # prompt mask, response pads included — the reference's unmasked
        # forward does the same, ppo_orchestrator.py:71); only the
        # LOSSES exclude pads.
        mask = jnp.concatenate(
            [batch.query_masks, jnp.ones(response.shape, jnp.int32)],
            axis=1,
        )
        with jax.named_scope(TRUNK_SCOPE):
            trunk_out = policy.trunk(params, tokens, mask)
        return advantages, returns, trunk_out

    def one_pass(params, opt_state, batch: PPORLBatch, shared_out):
        advantages, returns, trunk_out = shared_out
        response = batch.response_tensors
        P, G = batch.query_tensors.shape[1], response.shape[1]
        old_values = batch.values
        resp_mask = batch.response_masks

        def loss_fn(trainable):
            p = {**params, "trainable": trainable}
            with jax.named_scope("update/top"):
                logits, _, values = policy.forward_from_trunk(
                    p, *trunk_out, with_ref=False
                )
            with jax.named_scope("update/loss"):
                window = slice(P - 1, P + G - 1)
                logprobs = logprobs_from_logits(logits[:, window], response)
                vpred = values[:, window]
                return ppo_losses(
                    logprobs, vpred, batch.logprobs, old_values,
                    advantages, returns,
                    m.cliprange, m.cliprange_value, m.vf_coef,
                    mask=resp_mask,
                )

        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params["trainable"]
        )
        with jax.named_scope("update/opt"):
            updates, new_opt_state = opt.update(
                grads, opt_state, params["trainable"]
            )
            trainable = optax.apply_updates(params["trainable"], updates)
            stats["grad_norm"] = optax.global_norm(grads)
            if guard_on:
                ok = jnp.isfinite(loss) & jnp.isfinite(stats["grad_norm"])
                if max_step_kl > 0:
                    ok &= stats["approx_kl"] <= max_step_kl
                # commit-or-keep on device: a NaN update (grads poison the
                # optimizer moments too) must not touch either tree
                trainable = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o),
                    trainable, params["trainable"],
                )
                new_opt_state = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o),
                    new_opt_state, opt_state,
                )
                stats["bad_step"] = 1.0 - ok.astype(jnp.float32)
        params = {**params, "trainable": trainable}
        return params, new_opt_state, stats

    def train_step(params, opt_state, batch: PPORLBatch):
        return one_pass(params, opt_state, batch, shared(params, batch))

    def train_multi(params, opt_state, batch: PPORLBatch):
        """`ppo_epochs` optimization passes over one minibatch in a
        single dispatch (the reference's inner loop,
        accelerate_ppo_model.py:196-203, as a lax.scan). Returns the
        LAST pass's stats, matching what the per-step loop logged."""
        shared_out = shared(params, batch)  # once a batch, not once a pass

        def one(carry, _):
            params, opt_state, stats = one_pass(*carry, batch, shared_out)
            return (params, opt_state), stats

        (params, opt_state), stats_seq = jax.lax.scan(
            one, (params, opt_state), None, length=m.ppo_epochs
        )
        last_stats = jax.tree_util.tree_map(lambda x: x[-1], stats_seq)
        if guard_on:
            # ANY bad inner pass marks the whole dispatch (each pass
            # already self-skipped on device; the host guard counts
            # the dispatch once)
            last_stats["bad_step"] = stats_seq["bad_step"].max()
        return params, opt_state, last_stats

    def train_multi_indexed(params, opt_state, store_batch: PPORLBatch,
                            idx):
        """train_multi on store rows `idx`, gathered INSIDE the one
        dispatch. The device-resident store otherwise pays one eager
        gather dispatch per batch field (7 of them) before the train
        program (same device-resident-indexing design as the ILQL
        trainer's train_step_indexed)."""
        batch = jax.tree_util.tree_map(lambda x: x[idx], store_batch)
        return train_multi(params, opt_state, batch)

    return train_step, train_multi, train_multi_indexed


@register_trainer("JaxPPOTrainer")
@register_trainer("AcceleratePPOModel")
class JaxPPOTrainer(BaseRLTrainer):
    """PPO with KL penalty against a frozen reference policy.

    The orchestrator injects itself + reward_fn via `set_orchestrator`
    (parity with the reference's circular binding,
    ppo_orchestrator.py:41-43)."""

    def __init__(self, config: TRLConfig, train_mode: bool = True, mesh=None):
        super().__init__(config, train_mode, mesh=mesh)
        self.rollout_clock = Clock()
        self.iter_count = 0
        self.epoch = 0

        self.tokenizer = load_tokenizer(config.model.tokenizer_path)
        compute_dtype = DTYPES[config.model.compute_dtype]

        # --- model ---------------------------------------------------------
        rng = jax.random.PRNGKey(config.train.seed)
        self._rng, init_rng, head_rng = jax.random.split(rng, 3)
        spec, trunk = self._load_or_spec(config)
        if self.mesh is not None and self.mesh.shape.get("sp", 1) > 1:
            T = config.train.input_size + config.train.gen_size
            sp = self.mesh.shape["sp"]
            if T % sp != 0:
                raise ValueError(
                    f"mesh sp={sp} requires input_size + gen_size "
                    f"({config.train.input_size} + {config.train.gen_size} "
                    f"= {T}) to be divisible by it (ring attention splits "
                    f"the train-time sequence across sp devices)"
                )
        k = resolve_num_unfrozen(spec, config.model.num_layers_unfrozen)
        self.policy = HydraPolicy(
            spec=spec,
            num_layers_unfrozen=config.model.num_layers_unfrozen,
            compute_dtype=compute_dtype,
            remat=config.train.remat,
            attention_fn=self._train_attention_fn(),
            # every forward this policy runs: train batches + rollout
            # scoring chunks + eval chunks (eval reuses chunk_size)
            **self._pp_kwargs(
                spec.n_layer - k, config.train.batch_size,
                config.method.chunk_size,
            ),
        )
        # param_dtype applies to the FROZEN trunk + reference branch only;
        # the trainable branch and its optimizer state stay float32 (the
        # 6B-on-one-chip memory lever — frozen storage dtype costs nothing
        # in optimizer quality; see docs/source/performance.rst)
        frozen_dtype = DTYPES[config.model.param_dtype]
        self._check_memory_fit(spec, frozen_dtype)
        if trunk is not None:
            self.params = hydra_params_from_trunk(
                self.policy, *trunk, head_rng, frozen_dtype=frozen_dtype
            )
        else:
            self.params = self.policy.init(
                init_rng, frozen_dtype=frozen_dtype
            )

        # --- optimizer -----------------------------------------------------
        self.opt = build_optimizer(config.train)
        self.params, self.opt_state = self._shard_model_state(
            self.params, self.opt
        )
        # decode-preferred at-rest layout for the frozen attention stacks:
        # removes the rollout program's full-stack layout-copy temps
        # (~2.5 GB at gpt-j-6B). Size-gated inside: below ~2 GiB of
        # stacks it returns the SAME object and the trainer keeps plain
        # jit's fast C++ dispatch (see relayout_for_decode — the AOT path
        # custom layouts require hashes every argument in Python per
        # dispatch, a trade only 6B-class models win).
        from trlx_tpu.parallel import relayout_for_decode

        relayouted = relayout_for_decode(self.params)
        self._layout_faithful = relayouted is not self.params
        self.params = relayouted

        # --- rollout machinery --------------------------------------------
        self.store = PPORolloutStorage()
        m = config.method
        self.kl_ctl = make_kl_controller(m.init_kl_coef, m.target, m.horizon)
        eos = getattr(self.tokenizer, "eos_token_id", -1)
        self.gen_config = GenerationConfig.from_gen_kwargs(
            config.train.gen_size,
            m.gen_kwargs or {},
            eos_token_id=eos if eos is not None else -1,
            pad_token_id=getattr(self.tokenizer, "pad_token_id", 0) or 0,
            prompt_len=config.train.input_size,
        )

        self.orch = None
        self.reward_fn: Optional[Callable] = None
        self.logit_mask = None  # optional [V] bool; see set_logit_mask
        # analytic throughput accounting (trlx_tpu.telemetry.flops): one
        # optimization step touches input+gen tokens; MFU divides the
        # resulting flops rate by the chip's bf16 peak when known
        from trlx_tpu.telemetry import ppo_train_flops_per_token

        self._tokens_per_sample = (
            config.train.input_size + config.train.gen_size
        )
        self._flops_per_token = ppo_train_flops_per_token(
            spec, config.model.num_layers_unfrozen
        )
        self._build_jitted_fns()
        # resume at CONSTRUCTION, not first learn(): the documented flow
        # runs make_experience() before learn(), and rollouts generated by
        # un-restored params would poison the first epoch's importance
        # ratios/advantages with a policy mismatch
        self.maybe_resume()

    # ------------------------------------------------------------------ #

    def set_orchestrator(self, orch, reward_fn: Callable) -> None:
        self.orch = orch
        self.reward_fn = reward_fn

    def set_logit_mask(self, mask) -> None:
        """Restrict sampling to tokens where mask is True (e.g. graph edges,
        printable subsets). Rebuilds the jitted generation closure."""
        self.logit_mask = None if mask is None else jnp.asarray(mask)
        self._build_jitted_fns()

    # -- jitted cores --------------------------------------------------- #

    def _build_jitted_fns(self):
        policy = self.policy
        m = self.config.method
        opt = self.opt
        gen_config = self.gen_config
        compute = DTYPES[self.config.model.compute_dtype]
        # divergence containment baked into the step program: with
        # train.max_bad_steps > 0 a bad update (non-finite loss/grad-norm,
        # or approx_kl above train.max_step_kl) is NOT committed — the
        # select happens on device, so the donated params/opt-state buffers
        # keep their pre-step values and the host only reads the verdict
        # flag (trlx_tpu.utils.faults.StepGuard does the counting/rollback)
        guard_on = getattr(self.config.train, "max_bad_steps", 0) > 0
        max_step_kl = float(getattr(self.config.train, "max_step_kl", 0.0))

        logit_mask = self.logit_mask
        # decided EAGERLY on the concrete params (shardings visible) and
        # closed over: inside the jitted rollout the weights are tracers
        # and generate()'s own per-device HBM backoff cannot engage
        unroll = decide_unroll(
            policy.spec, self.params, m.chunk_size,
            self.config.train.input_size + self.config.train.gen_size,
        )

        def generate_fn(params, query, query_mask, rng):
            blocks = policy.all_blocks(params)
            embed, ln_f = policy.head_params_for_decode(params)
            return generate(
                policy.spec, blocks, embed, ln_f, query, query_mask, rng,
                gen_config, compute_dtype=compute, logit_mask=logit_mask,
                unroll_layers=unroll,
            )

        def score_fn(params, sequences, attention_mask, response_mask,
                     kl_coef, input_size):
            """One shared-trunk forward → (logprobs, ref_logprobs, values)
            over the response window + KL-penalty rewards WITHOUT the task
            score (the host adds it to the last real token after reward_fn
            runs — keeps this dispatchable before the reward exists, so one
            host round trip covers generation + scoring).

            Logprobs are computed CHUNKED from the branch hidden states
            (trlx_tpu.ops.losses.chunked_label_logprobs): the [B, T, V]
            logits tensors of the policy AND reference branch — 2.7 GB at
            gpt2-124M [128, 52], the fused rollout program's memory peak —
            are never materialized. Replaces the reference's two forward
            passes + host KL math (ppo_orchestrator.py:70-98)."""
            h_top, h_ref, values = policy.forward_hidden(
                params, sequences, attention_mask, with_ref=True
            )
            P = input_size  # static
            response = sequences[:, P:]
            window = slice(P - 1, sequences.shape[1] - 1)
            embed = params["frozen_base"]["embed"]
            logprobs = chunked_label_logprobs(
                policy.branch_head_fn(params["trainable"], embed),
                h_top[:, window], response,
            )
            ref_logprobs = chunked_label_logprobs(
                policy.branch_head_fn(params["ref"], embed),
                h_ref[:, window], response,
            )
            vals = values[:, window]
            rewards, seq_kl = kl_penalty_rewards(
                logprobs, ref_logprobs,
                jnp.zeros(sequences.shape[0], jnp.float32),
                kl_coef, mask=response_mask,
            )
            return logprobs, vals, rewards, seq_kl

        def rollout_fn(params, bank_tokens, bank_mask, idx, rng, kl_coef):
            """One fused device program per rollout chunk: prompt selection
            (device-resident bank, host sends only [chunk] indices) ->
            generation -> shared-trunk scoring -> KL-penalty rewards.

            Every host<->device sync stalls the host until the device
            queue drains, so the rollout keeps everything on device and
            the orchestrator fetches only (sequences, seq_kl) — the two
            things the host reward callback actually needs."""
            query = bank_tokens[idx]
            query_mask = bank_mask[idx]
            out = generate_fn(params, query, query_mask, rng)
            logprobs, vals, kl_rewards, seq_kl = score_fn(
                params, out.sequences, out.attention_mask, out.gen_mask,
                kl_coef, query.shape[1],
            )
            return out, query, query_mask, logprobs, vals, kl_rewards, seq_kl

        def finalize_rewards(kl_rewards, gen_mask, scores):
            """Add the host task score to each row's last real response token
            (parity: reference ppo_orchestrator.py:92). Runs on device so the
            rollout's per-token tensors never round-trip through the host;
            `scores` arrives as a tiny per-row host array riding the
            dispatch."""
            last = jnp.maximum(gen_mask.sum(axis=-1) - 1, 0)
            return kl_rewards.at[
                jnp.arange(kl_rewards.shape[0]), last
            ].add(scores)

        train_step, train_multi, train_multi_indexed = ppo_update_fns(
            policy, m, opt, guard_on=guard_on, max_step_kl=max_step_kl
        )

        # plain jit, or the AOT path with pinned output formats when the
        # relayout engaged, or plain jit with pinned output shardings
        # under a mesh (BaseRLTrainer._step_jit)
        jit_, pin = self._step_jit()
        train_out = pin and (pin(self.params), pin(self.opt_state), None)
        self._generate_fn = jit_(generate_fn)
        self._rollout_fn = jit_(rollout_fn)
        self._train_step = jit_(
            train_step, donate_argnums=(0, 1), out_shardings=train_out
        )
        self._train_multi = jit_(
            train_multi, donate_argnums=(0, 1), out_shardings=train_out
        )
        self._train_multi_indexed = jit_(
            train_multi_indexed, donate_argnums=(0, 1),
            out_shardings=train_out,
        )
        self._finalize_rewards = jax.jit(finalize_rewards)
        self._say_trunk_passes(train_multi)

    def _say_trunk_passes(self, train_multi) -> None:
        """When the update programs are built: how many times one dispatch
        runs the frozen trunk, read off the jaxpr of the pure
        ``train_multi`` (`trunk_passes`; traced over shapes, nothing
        compiles, and the jitted attributes are not touched), as the gauge
        ``ppo/update_trunk_passes`` and one log line."""
        from trlx_tpu import telemetry

        train = self.config.train
        B, P, G = train.batch_size, max(train.input_size, 1), train.gen_size

        def of(n, dtype):
            return jax.ShapeDtypeStruct((B, n), dtype)

        batch = PPORLBatch(
            query_tensors=of(P, jnp.int32),
            response_tensors=of(G, jnp.int32),
            logprobs=of(G, jnp.float32),
            values=of(G, jnp.float32),
            rewards=of(G, jnp.float32),
            response_masks=of(G, jnp.int32),
            query_masks=of(P, jnp.int32),
        )
        state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (self.params, self.opt_state),
        )
        passes = trunk_passes(jax.make_jaxpr(train_multi)(*state, batch))
        telemetry.set_gauge("ppo/update_trunk_passes", passes)
        print(
            f"[trlx_tpu] ppo update: the frozen trunk "
            f"({self.policy.spec.n_layer - self.policy.k} of "
            f"{self.policy.spec.n_layer} layers) runs in {passes} of "
            f"{self.config.method.ppo_epochs} epochs",
            file=sys.stderr, flush=True,
        )

    # -- BaseRLTrainer surface ------------------------------------------ #

    def next_rng(self):
        self._rng, key = jax.random.split(self._rng)
        return key

    def generate(self, query_tokens, query_mask):
        (query, mask), n = self._pad_rows(
            (np.asarray(query_tokens), np.asarray(query_mask))
        )
        query, mask = self._put((query, mask))
        out = self._generate_fn(self.params, query, mask, self.next_rng())
        if n != query.shape[0]:
            out = jax.tree_util.tree_map(lambda x: x[:n], out)
        return out

    def act(self, batch):
        """Generate responses for a prompt batch; returns (query, response,
        texts) (parity: reference accelerate_base_model.py:103-130)."""
        query, mask = batch
        out = self.generate(query, mask)
        sequences, gen_tokens = jax.device_get(
            (out.sequences, out.gen_tokens)
        )
        texts = self.tokenizer.batch_decode(sequences, skip_special_tokens=True)
        return np.asarray(query), gen_tokens, texts

    def sample(self, prompts, length: int, n_samples: int):
        enc = self.tokenizer(
            prompts,
            max_length=self.config.train.input_size,
            padding="max_length",
            truncation=True,
        )
        out = self.generate(
            np.asarray(enc["input_ids"]), np.asarray(enc["attention_mask"])
        )
        return self.tokenizer.batch_decode(np.asarray(out.sequences))

    def rollout(self, bank_tokens, bank_mask, idx):
        """Dispatch one fused rollout chunk (select prompts by `idx` from the
        device-resident bank, generate, score). Returns DEVICE arrays
        (out, query, query_mask, logprobs, values, kl_rewards, seq_kl) — no
        host sync; the orchestrator batches the one fetch it needs."""
        idx = jnp.asarray(idx, dtype=jnp.int32)
        return self._rollout_fn(
            self.params, bank_tokens, bank_mask, idx, self.next_rng(),
            jnp.float32(self.kl_ctl.value),
        )

    def finalize_rewards(self, kl_rewards, gen_mask, scores):
        """Device-side rewards = kl_rewards + task score at the last real
        token; `scores` is a small host array riding the dispatch."""
        return self._finalize_rewards(
            kl_rewards, gen_mask, np.asarray(scores, np.float32)
        )

    def get_components(self) -> Dict:
        return {
            "params": self.params,
            "opt_state": self.opt_state,
            "state": {
                "iter_count": self.iter_count,
                "epoch": self.epoch,
                "kl_coef": self.kl_ctl.value,
                "rng": np.asarray(jax.random.key_data(self._rng)).tolist(),
            },
            # checkpoints are self-describing: the serve CLI rebuilds the
            # policy from this (trlx_tpu.serve); restore ignores it
            "config": self.config.to_nested_dict(),
        }

    def set_components(self, components: Dict) -> None:
        self.params = components["params"]
        if getattr(self, "_layout_faithful", False):
            # checkpoint restore rebuilds default layouts, but the jitted
            # closures pinned the custom at-rest formats — without
            # re-applying, the next rollout AOT-compiles for default
            # layouts and re-materializes the layout-copy temps (the 6B
            # single-chip OOM the relayout exists to prevent)
            from trlx_tpu.parallel import relayout_for_decode

            self.params = relayout_for_decode(self.params)
        self.opt_state = components["opt_state"]
        state = components["state"]
        self.iter_count = int(state["iter_count"])
        self.epoch = int(state["epoch"])
        self.kl_ctl.value = float(state["kl_coef"])
        self._rng = jax.random.wrap_key_data(
            jnp.asarray(state["rng"], dtype=jnp.uint32)
        )

    # -- learn loop ------------------------------------------------------ #

    def evaluate(self, eval_prompts=None, n: int = 16):
        """Generate from eval prompts and score with reward_fn (parity:
        reference post_backward eval, accelerate_ppo_model.py:130-161)."""
        if self.reward_fn is None:
            return {}
        if eval_prompts is None:
            if self.orch is None:
                return {}
            # rotate which prompts are scored: a fixed first batch of an
            # unshuffled loader would overstate metric stability across
            # eval points
            self._eval_round = getattr(self, "_eval_round", -1) + 1
            loader = self.orch.pipeline.create_loader(
                n, shuffle=True, seed=self._eval_round
            )
            try:
                eval_prompts = next(iter(loader))
            except StopIteration:
                return {}
        from trlx_tpu.supervisor import chaos, seam_timeout
        from trlx_tpu.utils.faults import retry_call
        from trlx_tpu.utils.profiling import annotate

        query, mask = eval_prompts
        # annotate = telemetry span + supervisor heartbeat: a hung eval
        # or reward call shows up as a stalled phase, not a silent wedge
        with annotate("eval"):
            chaos.maybe_inject("eval")
            out = self.generate(query, mask)
            sequences, gen_tokens = jax.device_get(
                (out.sequences, out.gen_tokens)
            )
            texts = self.tokenizer.batch_decode(
                sequences, skip_special_tokens=True
            )
            with annotate("reward_fn"):
                scores = np.asarray(retry_call(
                    self.reward_fn, texts,
                    retries=getattr(self.config.train, "host_retries", 2),
                    backoff=getattr(
                        self.config.train, "host_retry_backoff", 0.5
                    ),
                    timeout=seam_timeout(self.config.train),
                    seam="reward_fn",
                    label="reward_fn (eval)",
                ), np.float32)
        query_texts = self.tokenizer.batch_decode(
            np.asarray(query), skip_special_tokens=True
        )
        response_texts = self.tokenizer.batch_decode(
            gen_tokens, skip_special_tokens=True
        )
        return {
            "mean_score": float(scores.mean()),
            "samples": texts[:4],
            # decoded query/response/score rows (reference:
            # accelerate_ppo_model.py:147-161)
            "generations_table": generations_table(
                query_texts, response_texts, scores
            ),
        }

    def learn(self, log_fn: Callable = None, save_fn=None, eval_fn=None):
        """PPO optimization loop (parity: reference
        accelerate_ppo_model.py:163-209): iterate minibatches over the
        rollout store, `ppo_epochs` passes per batch, KL-coef update +
        periodic eval between batches, fresh experience each outer epoch.

        Termination DELIBERATELY diverges from the reference: training
        stops when EITHER `total_steps` or `epochs` is reached. The
        reference keeps going until BOTH are exceeded
        (accelerate_ppo_model.py:174-177), which overruns `total_steps`
        whenever `epochs` is the larger bound — with a cosine LR schedule
        annealed over `total_steps`, those overrun steps train at the
        floor LR. Tested in
        tests/test_ppo_e2e.py::test_termination_either_bound.

        Set $TRLX_TPU_PROFILE_DIR to capture a jax.profiler device trace of
        the loop (trlx_tpu.utils.profiling). With train.telemetry (default
        on) every log emission carries the time/* phase breakdown,
        throughput/* (tokens/sec, samples/sec, MFU), fault/* counters and
        device/* HBM gauges, and a telemetry.json summary + Chrome-trace
        trace.jsonl land in the run dir at exit (trlx_tpu.telemetry, docs
        "Observability"). SIGTERM during the loop
        checkpoints at the next step boundary and returns cleanly
        (train.save_on_preemption, trlx_tpu.utils.preemption). With
        train.max_bad_steps > 0, non-finite / KL-breaching updates are
        skipped on device and contained by rollback-to-checkpoint
        (trlx_tpu.utils.faults.StepGuard); a run that re-diverges after
        rollback raises DivergenceError instead of training on garbage.
        The run supervisor (trlx_tpu.supervisor) rides the same loop:
        train.stall_timeout arms a heartbeat watchdog over the loop's
        phases, train.max_walltime save-and-exits before the reservation
        ends, and a hung host seam past its retry budget is converted to
        a clean checkpoint-and-exit (StallError)."""
        from trlx_tpu.supervisor import StallError
        from trlx_tpu.utils.preemption import PreemptionGuard
        from trlx_tpu.utils.profiling import annotate, maybe_trace

        cfg = self.config.train
        m = self.config.method
        log_fn = self._main_process_log(log_fn or make_tracker(self.config))
        clock = Clock()
        self.maybe_resume()  # no-op when already restored at construction
        step_guard = self._make_step_guard(log_fn)
        sup = self._make_supervisor()

        # auto poll_interval is capped so preemption-detection latency
        # stays bounded relative to eviction grace windows (a spot node
        # gives ~30s); train.preempt_poll_interval overrides for regimes
        # where 8 steps outlast the grace period.
        try:
            with maybe_trace(), PreemptionGuard(
                cfg.save_on_preemption,
                poll_interval=(cfg.preempt_poll_interval
                               or min(cfg.log_interval, 8)),
            ) as guard, sup:
                self._learn_loop(log_fn, cfg, m, clock, annotate, guard,
                                 step_guard, sup)
        except StallError:
            # hung seam past its retry budget: checkpoint-and-exit (the
            # run is resumable; the re-raise tells the operator why it
            # stopped)
            self._contain_stall(log_fn)
            raise
        finally:
            # every exit path (completion, preemption, DivergenceError,
            # StallError) leaves the run's telemetry.json + trace.jsonl
            self._finish_telemetry("ppo", clock)

    @staticmethod
    def _epoch_batch_count(n_rows: int, batch_size: int) -> int:
        """Optimization-batch steps one epoch runs over `n_rows` store
        rows — the SINGLE definition of the epoch length. Both
        `_batch_runner` paths iterate with drop-last semantics
        (batch_iterator drop_last=True), and `_will_refresh` predicts the
        epoch-end iter_count from this same helper, so the
        continuous-rollout refresh prediction can never drift from the
        loaders' actual batch count."""
        return n_rows // batch_size

    def _batch_runner(self, cfg):
        """(iterator, run, rows): one optimization-batch step per item;
        both paths yield exactly `_epoch_batch_count(len(store),
        batch_size)` items (last partial batch dropped).

        Device-resident store + no mesh: the iterator yields INDEX arrays
        and `run` gathers the rows inside the single train dispatch
        (_train_multi_indexed) instead of one eager gather per field.
        Otherwise (host-side rollouts, or a mesh needing shard_batch):
        the classic batch loader."""
        from trlx_tpu.pipeline import batch_iterator

        data = self.store._stacked()
        if (
            self.mesh is None
            and data is not None
            and self._device_resident(data)
        ):
            iterator = batch_iterator(
                len(data), cfg.batch_size, True, self.epoch,
                lambda idx: idx, drop_last=True,
            )

            def run(idx):
                return self._train_multi_indexed(
                    self.params, self.opt_state, data,
                    jnp.asarray(idx, jnp.int32),
                )

            return iterator, run, len
        # store.create_loader delegates to batch_iterator with the same
        # drop_last=True default — the contract _epoch_batch_count states
        iterator = self.store.create_loader(
            cfg.batch_size, shuffle=True, seed=self.epoch
        )

        def run(batch):
            return self._train_multi(
                self.params, self.opt_state, self._put(batch)
            )

        return iterator, run, lambda b: len(b.query_tensors)

    def _will_refresh(self, cfg, m) -> bool:
        """Whether the post-epoch experience refresh will run, PREDICTED
        before the epoch's updates: the epoch advances iter_count by
        exactly `_epoch_batch_count * ppo_epochs`, so the continuation
        condition is computable up-front — which is what lets continuous
        mode dispatch the next epoch's rollouts before this epoch's
        updates."""
        if self.orch is None:
            return False
        n_batches = self._epoch_batch_count(len(self.store), cfg.batch_size)
        end_count = self.iter_count + n_batches * m.ppo_epochs
        return end_count < cfg.total_steps and self.epoch + 1 < cfg.epochs

    def _learn_loop(self, log_fn, cfg, m, clock, annotate, guard=None,
                    step_guard=None, sup=None):
        from trlx_tpu.supervisor import chaos

        while self.iter_count < cfg.total_steps and self.epoch < cfg.epochs:
            loader, run, rows = self._batch_runner(cfg)
            pending_exp = None
            if cfg.continuous_rollouts and self._will_refresh(cfg, m):
                # dispatch the NEXT epoch's rollout programs now, against
                # the CURRENT (pre-update) params: the device runs them
                # ahead of the update programs queued below, and the
                # post-epoch harvest no longer waits for a
                # rollout-after-update chain — one host sync saved per
                # cycle. Cost: that experience is one update phase stale
                # (train.continuous_rollouts docs).
                pending_exp = self.orch.start_experience(
                    m.num_rollouts, self.iter_count
                )
            for item in loader:
                with annotate("ppo_update"):
                    chaos.maybe_inject("ppo_update")
                    # all ppo_epochs passes in ONE dispatch: one scanned
                    # program, not N dispatches with a host hop between
                    self.params, self.opt_state, stats = run(item)
                    self.iter_count += m.ppo_epochs
                clock.tick(rows(item) * m.ppo_epochs)
                # divergence verdict (no-op sync-free when disabled); a
                # rollback here restores params/opt/iter_count from the
                # last checkpoint and the loop simply keeps going
                self._observe_step(step_guard, stats)

                intervals = self.intervals(self.iter_count)
                if intervals["do_log"]:
                    # the dispatch above returned at once: the wait for
                    # the update program itself lands in this fetch
                    with annotate("ppo_stats_fetch"):
                        fetched = jax.device_get(stats)
                    host_stats = {k: float(v) for k, v in fetched.items()}
                    sps = clock.samples_per_second()
                    host_stats.update(
                        iter=self.iter_count,
                        epoch=self.epoch,
                        kl_coef=self.kl_ctl.value,
                        samples_per_sec=sps,
                    )
                    # observability payload: time/* phase breakdown,
                    # throughput/* (tokens/sec + MFU), fault/* counters,
                    # device/* HBM gauges (trlx_tpu.telemetry; {} when
                    # train.telemetry is off)
                    host_stats.update(self._telemetry_stats(sps))
                    log_fn(host_stats)
                if intervals["do_eval"]:
                    ev = self.evaluate()
                    if ev:
                        log_fn({"iter": self.iter_count, **ev})
                if intervals["do_save"]:
                    self.save()
                # periodic telemetry flush (train.telemetry_flush_every;
                # no-op by default) so a SIGKILL still leaves artifacts
                self._maybe_flush_telemetry()
                if self._preempt(log_fn, guard,
                                 just_saved=intervals["do_save"],
                                 sup=sup):
                    return
                if self.iter_count >= cfg.total_steps:
                    break

            # post-epoch: refresh experience (reference
            # accelerate_ppo_model.py:122-128)
            self.epoch += 1
            if pending_exp is not None:
                # continuous mode: harvest the rollouts dispatched before
                # this epoch's updates (a preemption mid-epoch above
                # abandons them — the dispatched device work is moot)
                self.store.clear_history()
                info = self.orch.finish_experience(pending_exp)
                log_fn({"iter": self.iter_count, "epoch": self.epoch, **info,
                        **self._telemetry_stats(clock.samples_per_second())})
                if self._preempt(log_fn, guard, sup=sup):
                    return
            elif self.orch is not None and self.iter_count < cfg.total_steps \
                    and self.epoch < cfg.epochs:
                self.store.clear_history()
                with annotate("rollout_refresh"):
                    info = self.orch.make_experience(
                        m.num_rollouts, self.iter_count
                    )
                # the refresh emission carries the observability payload
                # too: short runs (or long log_intervals) still surface
                # time/* / throughput/* / fault/* every epoch
                log_fn({"iter": self.iter_count, "epoch": self.epoch, **info,
                        **self._telemetry_stats(clock.samples_per_second())})
                if self._preempt(log_fn, guard, sup=sup):
                    return

    def post_rollout_kl_update(self, mean_kl: float, n_samples: int) -> None:
        self.kl_ctl.update(mean_kl, n_samples)


