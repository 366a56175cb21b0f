"""ILQL trainer: jitted offline train step, Polyak target sync,
advantage-shifted sampling eval.

Parity target: reference `ILQLModel` (trlx/model/accelerate_ilql_model.py:23-181).
TPU-first differences:

- One jitted train step (loss + adamw update with grad clip / weight decay
  applied — the reference configures but never applies them).
- Target-Q Polyak sync is a jitted pytree lerp on the configured interval
  (reference ilql_models.py:185-214, minus the ZeRO gather machinery that
  SPMD makes unnecessary).
- Sampling uses the shared decode engine with the ILQL advantage-shifted
  warper (log pi + beta * (target_Q - V), top-k, temperature — reference
  ilql_models.py:249-252) via the extras_fn hook; supports the [V, V]
  per-previous-token logit mask of the randomwalks task.

Registered under "JaxILQLTrainer" and the reference name "ILQLModel".
"""

import os
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.ilql_types import ILQLBatch
from trlx_tpu.models.generation import (
    GenerationConfig,
    decide_unroll,
    generate,
)
from trlx_tpu.models.hf_import import ilql_params_from_trunk
from trlx_tpu.models.ilql import ILQLModel as ILQLNet, sync_targets
from trlx_tpu.models.policy import resolve_num_unfrozen
from trlx_tpu.ops.losses import ilql_losses_chunked
from trlx_tpu.ops.sampling import SamplingParams, warp_top_k
from trlx_tpu.trainers import BaseRLTrainer, register_trainer
from trlx_tpu.utils import Clock, rampup_decay_schedule
from trlx_tpu.utils.aotjit import aot_jit
from trlx_tpu.utils.tokenizer import load_tokenizer
from trlx_tpu.utils.trackers import make_tracker, samples_table

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


@register_trainer("JaxILQLTrainer")
@register_trainer("ILQLModel")
class JaxILQLTrainer(BaseRLTrainer):
    def __init__(self, config: TRLConfig, train_mode: bool = True,
                 logit_mask=None, mesh=None):
        super().__init__(config, train_mode, mesh=mesh)
        self.iter_count = 0
        self.tokenizer = load_tokenizer(config.model.tokenizer_path)
        self.max_length = config.train.gen_size

        m = config.method
        rng = jax.random.PRNGKey(config.train.seed)
        self._rng, init_rng = jax.random.split(rng)
        spec, trunk = self._load_or_spec(config)
        # pre-flight HBM fit (same fail-fast as PPO): no ref branch, but
        # the Q/V heads are trainable [d, V] tensors with adam moments and
        # the target-Q copies are frozen [d, V] tensors — at 6B scale each
        # is ~0.8 GB and must be counted
        n_q = 2 if m.two_qs else 1
        head_params = n_q * spec.d_model * spec.vocab_size + spec.d_model
        self._check_memory_fit(
            spec, jnp.float32, ref_branch=False,
            extra_trainable=head_params,
            extra_frozen=n_q * spec.d_model * spec.vocab_size,
            embed_trainable=(
                resolve_num_unfrozen(spec, config.model.num_layers_unfrozen)
                == spec.n_layer
            ),
        )
        self.net = ILQLNet(
            spec=spec,
            num_layers_unfrozen=config.model.num_layers_unfrozen,
            two_qs=m.two_qs,
            compute_dtype=DTYPES[config.model.compute_dtype],
            remat=config.train.remat,
            attention_fn=self._train_attention_fn(),
            **self._pp_kwargs(
                spec.n_layer
                - resolve_num_unfrozen(
                    spec, config.model.num_layers_unfrozen
                ),
                config.train.batch_size,
            ),
        )
        if trunk is not None:
            self.params = ilql_params_from_trunk(self.net, *trunk, init_rng)
        else:
            self.params = self.net.init(init_rng)

        sched = rampup_decay_schedule(
            config.train.lr_ramp_steps,
            config.train.lr_decay_steps,
            config.train.learning_rate_init,
            config.train.learning_rate_target,
        )
        from trlx_tpu.trainers.ppo_trainer import build_optimizer

        self.opt = build_optimizer(config.train, sched=sched)
        self.params, self.opt_state = self._shard_model_state(
            self.params, self.opt
        )
        # decode-preferred at-rest layout for the frozen attention stacks
        # — size-gated no-op below 6B-class stacks (see the PPO trainer's
        # note and trlx_tpu.parallel.relayout_for_decode)
        from trlx_tpu.parallel import relayout_for_decode

        relayouted = relayout_for_decode(self.params)
        self._layout_faithful = relayouted is not self.params
        self.params = relayouted

        # [V] or [V, V] boolean; True = DISALLOWED (the reference passes the
        # adjacency complement, examples/ilql_randomwalks.py:72)
        self.logit_mask = None if logit_mask is None else jnp.asarray(logit_mask)

        # installed by OfflineOrchestrator
        self.train_store = None
        self.eval_pipeline = None
        self.reward_fn: Optional[Callable] = None
        self.stats_fn: Optional[Callable] = None

        # analytic flops for throughput/mfu emission; tokens-per-sample is
        # set in _learn_loop from the collated dataset's real width
        from trlx_tpu.telemetry import ilql_train_flops_per_token

        self._flops_per_token = ilql_train_flops_per_token(
            spec,
            resolve_num_unfrozen(spec, config.model.num_layers_unfrozen),
            m.two_qs,
        )

        self._build_jitted_fns()
        # resume at construction (see JaxPPOTrainer: restored state must be
        # live before any evaluation/sampling the caller does pre-learn)
        self.maybe_resume()

    # ------------------------------------------------------------------ #

    def tokenize(self, texts):
        """bos + text + eos (parity: reference
        accelerate_ilql_model.py:67-74)."""
        bos = getattr(self.tokenizer, "bos_token", None) or ""
        eos = getattr(self.tokenizer, "eos_token", None) or ""
        enc = self.tokenizer(
            [bos + x + eos for x in texts],
            max_length=self.max_length,
            truncation=True,
            padding=False,
        )
        return enc

    def _build_jitted_fns(self):
        net = self.net
        m = self.config.method
        opt = self.opt
        # same on-device commit gate as the PPO step (see the PPO
        # trainer's note): with train.max_bad_steps > 0 a non-finite
        # loss/grad-norm leaves params and optimizer state untouched and
        # only the bad_step verdict reaches the host StepGuard
        guard_on = getattr(self.config.train, "max_bad_steps", 0) > 0

        def train_step(params, opt_state, batch: ILQLBatch):
            def loss_fn(trainable):
                p = {**params, "trainable": trainable}
                # chunked heads: the five [B, T, V] head tensors (~3 GB
                # fp32 at gpt2 vocab [64, 48]) were the step's HBM-traffic
                # bound; per-T-chunk projections reduce to gather/lse
                # immediately and remat in the backward
                h_normed = net.forward_hidden(
                    p, batch.input_ids, batch.attention_mask
                )
                lm_fn, q_fns, tq_fns, v_fn = net.head_fns(p)
                return ilql_losses_chunked(
                    lm_fn, q_fns, tq_fns, v_fn(h_normed), h_normed,
                    batch.input_ids, batch.attention_mask, batch.rewards,
                    m.gamma, m.tau, m.cql_scale, m.awac_scale,
                )

            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params["trainable"]
            )
            updates, new_opt_state = opt.update(
                grads, opt_state, params["trainable"]
            )
            trainable = optax.apply_updates(params["trainable"], updates)
            stats["grad_norm"] = optax.global_norm(grads)
            if guard_on:
                ok = jnp.isfinite(loss) & jnp.isfinite(stats["grad_norm"])
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

        beta = m.beta
        top_k = m.top_k
        temperature = m.temperature
        logit_mask = self.logit_mask

        # eager unroll decision closed over the jitted closures (same
        # rationale as the PPO trainer: tracers hide shardings); sized on
        # the training batch — eval calls reuse it, close enough
        unroll = decide_unroll(
            net.spec, self.params, self.config.train.batch_size,
            self.config.train.n_ctx,
        )

        def generate_fn(params, query, query_mask, rng, gen_config):
            blocks = net.all_blocks(params)
            embed, ln_f = net.head_params_for_decode(params)

            def extras(h_normed, logits, prev_tok):
                """pi~ = softmax(topk(log pi + beta * (minQ_target - V))
                / temp) (reference ilql_models.py:246-252), plus the
                per-prev-token edge mask of randomwalks. The mask is
                applied BEFORE log_softmax, as the reference does
                (ilql_models.py:246-247): pi renormalizes over allowed
                tokens, and top-k never selects a disallowed token."""
                if logit_mask is not None:
                    if logit_mask.ndim == 2:
                        disallowed = logit_mask[prev_tok]
                    else:
                        disallowed = logit_mask[None, :]
                    logits = jnp.where(disallowed, -1e9, logits)
                tq, v = net.heads_on_hidden(params, h_normed)
                adv = tq - v
                pi = jax.nn.log_softmax(logits, axis=-1)
                shifted = warp_top_k(pi + beta * adv, top_k)
                return shifted / temperature

            return generate(
                net.spec, blocks, embed, ln_f, query, query_mask, rng,
                gen_config, compute_dtype=net.compute_dtype, extras_fn=extras,
                unroll_layers=unroll,
            )

        def train_step_indexed(params, opt_state, dataset: ILQLBatch, idx):
            """Train on dataset rows `idx` — the dataset stays device-
            resident across the whole run and the host sends only a [B]
            index array per step instead of uploading every batch."""
            batch = jax.tree_util.tree_map(lambda x: x[idx], dataset)
            return train_step(params, opt_state, batch)

        # plain jit, or the AOT path with pinned output formats when the
        # relayout engaged, or plain jit with pinned output shardings
        # under a mesh (BaseRLTrainer._step_jit)
        jit_, pin = self._step_jit()
        params_out = pin and pin(self.params)
        train_out = pin and (params_out, pin(self.opt_state), None)
        self._train_step = jit_(
            train_step, donate_argnums=(0, 1), out_shardings=train_out
        )
        self._train_step_indexed = jit_(
            train_step_indexed, donate_argnums=(0, 1),
            out_shardings=train_out,
        )
        self._sync = jit_(
            lambda p: sync_targets(p, m.alpha), out_shardings=params_out
        )
        self._generate_fn = generate_fn
        self._generate_jitted = {}

    # -- sampling --------------------------------------------------------- #

    def next_rng(self):
        self._rng, key = jax.random.split(self._rng)
        return key

    def generate(self, query_tokens, query_mask, gen_size: Optional[int] = None):
        eos = getattr(self.tokenizer, "eos_token_id", 0) or 0
        G = gen_size or self.config.train.gen_size
        key = ("gen", G)
        if key not in self._generate_jitted:
            gen_config = GenerationConfig(
                gen_size=G,
                # warping happens inside extras_fn (reference semantics);
                # the sampler then just draws categorically
                sampling=SamplingParams(do_sample=True),
                eos_token_id=eos,
                pad_token_id=eos,
            )
            jit_ = aot_jit if self._layout_faithful else jax.jit
            self._generate_jitted[key] = jit_(
                lambda p, q, m, r: self._generate_fn(p, q, m, r, gen_config)
            )
        (query, mask), n = self._pad_rows(
            (np.asarray(query_tokens), np.asarray(query_mask))
        )
        query, mask = self._put((query, mask))
        out = self._generate_jitted[key](
            self.params, query, mask, self.next_rng()
        )
        if n != query.shape[0]:
            out = jax.tree_util.tree_map(lambda x: x[:n], out)
        return out

    def act(self, batch):
        query, mask = batch
        out = self.generate(query, mask)
        # one batched device->host fetch, not one per field
        sequences, gen_tokens = jax.device_get(
            (out.sequences, out.gen_tokens)
        )
        texts = self.tokenizer.batch_decode(sequences, skip_special_tokens=True)
        return np.asarray(query), gen_tokens, texts

    def sample(self, prompts, length: int = None, n_samples: int = None):
        query, mask = self._encode_prompts(prompts)
        out = self.generate(query, mask, gen_size=length)
        return np.asarray(out.sequences)

    def _encode_prompts(self, prompts):
        """Prompts may be strings or pre-tokenized id rows (the randomwalks
        example passes token tensors, examples/ilql_randomwalks.py:83)."""
        if len(prompts) and isinstance(prompts[0], str):
            enc = self.tokenizer(
                prompts, max_length=self.config.train.input_size or 8,
                padding="max_length", truncation=True,
            )
            return np.asarray(enc["input_ids"]), np.asarray(enc["attention_mask"])
        rows = [np.atleast_1d(np.asarray(p, np.int32)) for p in prompts]
        maxlen = max(len(r) for r in rows)
        ids = np.zeros((len(rows), maxlen), np.int32)
        mask = np.zeros((len(rows), maxlen), np.int32)
        for i, r in enumerate(rows):
            ids[i, maxlen - len(r):] = r  # left pad
            mask[i, maxlen - len(r):] = 1
        return ids, mask

    # -- checkpoint surface ------------------------------------------------ #

    def get_components(self) -> Dict:
        return {
            "params": self.params,
            "opt_state": self.opt_state,
            "state": {
                "iter_count": self.iter_count,
                "rng": np.asarray(jax.random.key_data(self._rng)).tolist(),
            },
            # checkpoints are self-describing (see the PPO trainer's note)
            "config": self.config.to_nested_dict(),
        }

    def set_components(self, components: Dict) -> None:
        self.params = components["params"]
        if getattr(self, "_layout_faithful", False):
            # re-pin the custom at-rest layouts after a restore (see the
            # PPO trainer's identical note)
            from trlx_tpu.parallel import relayout_for_decode

            self.params = relayout_for_decode(self.params)
        self.opt_state = components["opt_state"]
        self.iter_count = int(components["state"]["iter_count"])
        self._rng = jax.random.wrap_key_data(
            jnp.asarray(components["state"]["rng"], dtype=jnp.uint32)
        )

    # -- learn loop -------------------------------------------------------- #

    #: in-loop eval cap — the reference samples/tabulates at most 128 eval
    #: rows per eval point (reference: accelerate_ilql_model.py:128-157);
    #: scanning an unbounded eval set every eval_interval is the cost bug.
    EVAL_CAP = 128

    def evaluate(self, n: int = None):
        """Generate from eval prompts with the advantage-shifted sampler and
        score/stat them (parity: reference accelerate_ilql_model.py:109-157).

        n: row cap; None applies EVAL_CAP, 0 means the full eval set
        (explicit opt-in for final/offline evaluation)."""
        if self.eval_pipeline is None or len(self.eval_pipeline) == 0:
            return {}
        from trlx_tpu.supervisor import seam_timeout
        from trlx_tpu.utils.profiling import annotate

        prompts = self.eval_pipeline.texts
        if n is None:
            n = self.EVAL_CAP
        if n:
            prompts = prompts[:n]
        # annotate = telemetry span + supervisor heartbeat (a hung eval
        # or reward call is a stalled phase, not a silent wedge)
        with annotate("eval"):
            samples = self.sample(prompts)
            sample_lists = [list(map(int, row)) for row in samples]
            logs = {}
            decoded = None
            if len(prompts) and isinstance(prompts[0], str):
                decoded = self.tokenizer.batch_decode(samples)
            if self.reward_fn is not None:
                from trlx_tpu.utils.faults import retry_call

                with annotate("reward_fn"):
                    rewards = np.asarray(
                        retry_call(
                            self.reward_fn,
                            decoded if decoded is not None else sample_lists,
                            retries=getattr(
                                self.config.train, "host_retries", 2
                            ),
                            backoff=getattr(
                                self.config.train, "host_retry_backoff", 0.5
                            ),
                            timeout=seam_timeout(self.config.train),
                            seam="reward_fn",
                            label="reward_fn (eval)",
                        ),
                        np.float32,
                    )
                logs["reward"] = float(rewards.mean())
                if decoded is not None:
                    # first-128 samples table (reference:
                    # accelerate_ilql_model.py:128-157)
                    logs["samples_table"] = samples_table(decoded, rewards)
            if self.stats_fn is not None:
                logs.update(self.stats_fn(sample_lists))
        return logs

    def learn(self, log_fn: Callable = None, save_fn=None, eval_fn=None):
        """Set $TRLX_TPU_PROFILE_DIR to capture a jax.profiler device trace
        of the loop (trlx_tpu.utils.profiling). With train.telemetry
        (default on) every log emission carries the time/* / throughput/*
        / fault/* / device/* breakdown and a telemetry.json + trace.jsonl
        land in the run dir at exit (trlx_tpu.telemetry, docs
        "Observability"). SIGTERM during the loop
        checkpoints at the next step boundary and returns cleanly
        (train.save_on_preemption, trlx_tpu.utils.preemption). With
        train.max_bad_steps > 0, non-finite updates are skipped on device
        and contained by rollback-to-checkpoint
        (trlx_tpu.utils.faults.StepGuard, same containment as PPO). The
        run supervisor (trlx_tpu.supervisor) rides the same loop:
        train.stall_timeout arms the heartbeat watchdog,
        train.max_walltime save-and-exits before the reservation ends,
        and a hung host seam past its retry budget converts to a clean
        checkpoint-and-exit (StallError)."""
        from trlx_tpu.utils.preemption import PreemptionGuard
        from trlx_tpu.utils.profiling import maybe_trace

        self.maybe_resume()  # no-op when already restored at construction
        # capped like the PPO loop: bounded detection latency vs eviction
        # grace windows; train.preempt_poll_interval overrides
        cfg = self.config.train
        sup = self._make_supervisor()
        with maybe_trace(), PreemptionGuard(
            cfg.save_on_preemption,
            poll_interval=(cfg.preempt_poll_interval
                           or min(cfg.log_interval, 8)),
        ) as guard, sup:
            self._learn_loop(log_fn, save_fn, eval_fn, guard, sup)

    def _learn_loop(self, log_fn=None, save_fn=None, eval_fn=None,
                    guard=None, sup=None):
        from trlx_tpu.supervisor import StallError

        cfg = self.config.train
        m = self.config.method
        log_fn = self._main_process_log(log_fn or make_tracker(self.config))
        step_guard = self._make_step_guard(log_fn)
        clock = Clock()
        try:
            self._learn_epochs(log_fn, guard, step_guard, clock, cfg, m,
                               sup)
        except StallError:
            # hung seam past its retry budget: checkpoint-and-exit (the
            # run is resumable via train.resume_from: auto)
            self._contain_stall(log_fn)
            raise
        finally:
            # every exit path (completion, preemption, DivergenceError,
            # StallError) leaves the run's telemetry.json + trace.jsonl
            self._finish_telemetry("ilql", clock)

    def _learn_epochs(self, log_fn, guard, step_guard, clock, cfg, m,
                      sup=None):
        from trlx_tpu.supervisor import chaos
        from trlx_tpu.utils.profiling import annotate

        eos = getattr(self.tokenizer, "eos_token_id", 0) or 0

        # the loader's pad id must be a valid model token (masked out in the
        # loss, but kept in-range so gathers never see out-of-vocab ids) —
        # byte pad 256 vs a tiny graph vocab would otherwise overflow
        pad_id = min(eos, self.net.spec.vocab_size - 1)
        sp = self.mesh.shape.get("sp", 1) if self.mesh is not None else 1

        # collate + upload the WHOLE offline dataset once (rows pad to the
        # store-global max length, so per-batch shapes are identical);
        # every train step then sends only a [batch] index array. Tradeoff:
        # one long outlier row inflates every step's compute to its length
        # — with uniform offline data (the norm) that's free, and it buys
        # ONE traced shape + zero per-batch uploads. Rows are
        # padded (repeat-last) to the mesh's dp*fsdp multiple for
        # shard_batch; indices only ever address the n real rows. Datasets
        # too large to sit in HBM next to params+opt keep the per-batch
        # upload path.
        from trlx_tpu.pipeline import batch_iterator

        n = len(self.train_store)
        full = next(iter(self.train_store.create_loader(
            n, shuffle=False, eos_token_id=pad_id, pad_to_multiple=sp,
        )))
        # the collated store-global width IS the per-sample token count
        # every step processes (throughput/tokens_per_sec, MFU)
        self._tokens_per_sample = int(full.input_ids.shape[1])
        from trlx_tpu.utils import tree_bytes

        device_resident = tree_bytes(full) <= int(os.environ.get(
            "TRLX_TPU_DATASET_HBM_BYTES", 512 * 2**20
        ))
        if device_resident:
            padded, _ = self._pad_rows(full)
            dataset = self._put(padded)

        for epoch in range(cfg.epochs):
            idx_loader = batch_iterator(
                n, cfg.batch_size, True, epoch, lambda idx: idx,
                # a partial final batch can't shard over (dp, fsdp)
                drop_last=self.mesh is not None,
            )
            for idx in idx_loader:
                if self.iter_count % cfg.eval_interval == 0:
                    ev = self.evaluate()
                    if ev:
                        log_fn({"iter": self.iter_count, **ev})

                with annotate("ilql_update"):
                    chaos.maybe_inject("ilql_update")
                    if device_resident:
                        self.params, self.opt_state, stats = (
                            self._train_step_indexed(
                                self.params, self.opt_state, dataset,
                                jnp.asarray(idx, jnp.int32),
                            )
                        )
                    else:
                        batch = jax.tree_util.tree_map(
                            lambda x: x[idx], full
                        )
                        self.params, self.opt_state, stats = self._train_step(
                            self.params, self.opt_state, self._put(batch)
                        )
                self.iter_count += 1
                clock.tick(len(idx))
                # divergence verdict (free when disabled); a rollback
                # restores params/opt/iter_count from the last checkpoint
                self._observe_step(step_guard, stats)

                if self.iter_count % m.steps_for_target_q_sync == 0:
                    self.params = self._sync(self.params)

                if self.iter_count % cfg.log_interval == 0:
                    # the wait for the update program lands here
                    with annotate("ilql_stats_fetch"):
                        fetched = jax.device_get(stats)
                    host = {k: float(v) for k, v in fetched.items()}
                    sps = clock.samples_per_second()
                    host.update(
                        iter=self.iter_count,
                        epoch=epoch,
                        samples_per_sec=sps,
                    )
                    # time/* / throughput/* / fault/* / device/* payload
                    # ({} when train.telemetry is off)
                    host.update(self._telemetry_stats(sps))
                    log_fn(host)
                saved_now = (
                    self.iter_count % cfg.checkpoint_interval == 0
                    and self.iter_count > 0
                )
                if saved_now:
                    self.save()
                # periodic telemetry flush (train.telemetry_flush_every;
                # no-op by default) so a SIGKILL still leaves artifacts
                self._maybe_flush_telemetry()
                if self._preempt(log_fn, guard, just_saved=saved_now,
                                 sup=sup):
                    return
                if self.iter_count >= cfg.total_steps:
                    return
