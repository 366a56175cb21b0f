"""RL trainer base + registry.

Parity target: reference trlx/model/__init__.py:14-140 (`_MODELS`,
`register_model`, `BaseRLModel`). The reference calls trainers "models"; we
register under both vocabularies. The abstract surface (`act` / `sample` /
`learn` / `save` / `load` / `intervals` / `push_to_store`) is preserved, but
state is functional: parameters and optimizer state are pytrees held by the
trainer, stepped by jitted pure functions.
"""

import sys
from abc import abstractmethod
from typing import Callable, Dict

from trlx_tpu.utils.registry import BuiltinLoader, make_register

_TRAINERS: Dict[str, type] = {}

# Whether THIS framework enabled jax_debug_nans (vs the user setting
# JAX_DEBUG_NANS externally). Lets a later trainer constructed with
# debug_nans=false undo a flag a previous trainer in the same process set,
# without ever clobbering an externally-enabled debug flag.
_framework_set_debug_nans = False
_load_builtins = BuiltinLoader(
    ("trlx_tpu.trainers.ppo_trainer", "trlx_tpu.trainers.ilql_trainer")
)

#: Decorator registering a trainer class under a string name.
register_trainer = make_register(_TRAINERS)


# Reference-compatible alias (reference: trlx/model/__init__.py:17).
register_model = register_trainer


class BaseRLTrainer:
    """Abstract RL trainer (parity: reference trlx/model/__init__.py:40-140).

    Subclasses own: tokenizer, model params (pytrees), optimizer state, the
    rollout/train store, and jitted step functions.
    """

    def __init__(self, config, train_mode: bool = True, mesh=None):
        from trlx_tpu.parallel import initialize_runtime, mesh_from_config

        self.config = config
        self.train_mode = train_mode
        self.store = None
        # opt-in only: an unset config flag must not clobber a debug flag
        # the user enabled externally (JAX_DEBUG_NANS / jax.config) — but a
        # flag the FRAMEWORK set for an earlier trainer must not leak into
        # later trainers constructed with debug_nans=false
        global _framework_set_debug_nans
        if getattr(config.train, "debug_nans", False):
            import jax

            # only claim ownership when WE flipped it: if the user enabled
            # the flag externally before this trainer, a later default
            # trainer must not turn it off
            if not jax.config.jax_debug_nans:
                jax.config.update("jax_debug_nans", True)
                _framework_set_debug_nans = True
        elif _framework_set_debug_nans:
            import jax

            jax.config.update("jax_debug_nans", False)
            _framework_set_debug_nans = False
        # multi-host bootstrap first (no-op single-process), so the mesh
        # sees the pod's global device list
        initialize_runtime()
        # mesh: explicit > config (TrainConfig.mesh) > None (single device)
        self.mesh = mesh if mesh is not None else mesh_from_config(config.train)
        # telemetry session (train.telemetry, default on): started at
        # construction — BEFORE maybe_resume/make_experience — so restore
        # counters and pre-learn rollout spans land in the run's registry.
        # A fresh trainer = a fresh session (process-local, last one wins).
        from trlx_tpu import telemetry

        self._telemetry = telemetry.start_from_config(config)
        # per-token flops / tokens-per-sample for throughput + MFU
        # emission; subclasses overwrite with their analytic values
        self._flops_per_token = 0
        self._tokens_per_sample = 0

    # -- SPMD helpers (shared by all trainers) --------------------------- #

    def _pp_kwargs(self, n_bottom_layers: int, *batch_sizes) -> Dict:
        """Policy-dataclass kwargs that turn on GPipe for the frozen trunk
        when train.mesh has pp > 1 (trlx_tpu.ops.pipeline_parallel),
        validated up-front: the frozen layer count must split evenly into
        stages and every batch the forward sees must split into
        microbatches — a config error here beats a shape error three jit
        frames deep."""
        if self.mesh is None or self.mesh.shape.get("pp", 1) <= 1:
            return {}
        pp = self.mesh.shape["pp"]
        if self.mesh.shape.get("sp", 1) > 1:
            raise ValueError(
                "train.mesh pp > 1 cannot combine with sp > 1: ring "
                "attention runs its own shard_map over sp, which cannot "
                "nest inside the GPipe stage shard_map"
            )
        n_micro = self.config.train.pp_num_microbatches
        if n_bottom_layers == 0:
            # 0 % pp == 0, so without this check a fully-unfrozen model
            # sails through the divisibility test below and silently
            # pipelines an EMPTY trunk — the whole pp device slice idles
            raise ValueError(
                f"pipeline parallelism: num_layers_unfrozen leaves zero "
                f"frozen trunk layers, but train.mesh pp={pp} pipelines "
                f"only the frozen trunk — the entire pp device slice "
                f"would sit idle. Freeze at least pp layers (lower "
                f"num_layers_unfrozen) or set pp: 1."
            )
        if n_bottom_layers % pp:
            raise ValueError(
                f"pipeline parallelism: the frozen trunk has "
                f"{n_bottom_layers} layers, not divisible into pp={pp} "
                f"stages; adjust num_layers_unfrozen or the pp extent"
            )
        for b in batch_sizes:
            if b % n_micro:
                raise ValueError(
                    f"pipeline parallelism: batch of {b} rows is not "
                    f"divisible into train.pp_num_microbatches={n_micro} "
                    f"microbatches"
                )
        return {"pp_mesh": self.mesh, "pp_n_micro": n_micro}

    def _shard_model_state(self, params, opt):
        """(sharded params, sharded opt state) under the framework specs
        when a mesh is active; pass-through otherwise."""
        from trlx_tpu.parallel import shard_params, sharded_opt_init

        if self.mesh is not None:
            params = shard_params(self.mesh, params)
        return params, sharded_opt_init(opt, self.mesh, params["trainable"])

    def _step_jit(self):
        """``(jit_, pin)`` for the trainers' step programs. Default: plain
        ``jax.jit`` (C++ fastpath dispatch) behind ``telemetry.programs.jit``,
        which registers the executable at its first call, and no pin. When
        the decode relayout engaged (6B-class frozen stacks, ``_layout_faithful``),
        the params carry custom at-rest layouts that only the AOT compile
        path preserves (trlx_tpu.utils.aotjit), and ``pin(tree)`` gives
        the per-leaf formats the train steps must pass as
        ``out_shardings`` — otherwise the donated update emits
        default-layout frozen leaves and the NEXT cycle's rollout
        recompiles for default layouts, resurrecting the layout-copy
        temps (observed: a 6B second-cycle OOM after a clean first
        cycle). Under a mesh ``pin`` holds the SHARDINGS instead: left to
        the partitioner, the leaves the rules replicate (layernorms,
        row-parallel biases, v_head.w2) come back fsdp-sharded from the
        first update, and the second cycle's rollout and update programs
        both compile again for the drifted signature (chip_smoke.py's
        jit-cache check caught it)."""
        import jax

        from trlx_tpu.telemetry import programs
        from trlx_tpu.utils.aotjit import aot_jit, formats_of

        if self._layout_faithful:
            return aot_jit, formats_of
        if self.mesh is not None:
            return programs.jit, lambda tree: jax.tree_util.tree_map(
                lambda x: x.sharding, tree
            )
        return programs.jit, None

    def _put(self, tree):
        """Host batch -> device: sharded over (dp, fsdp) when a mesh is
        active, plain transfer otherwise.

        Always ONE `jax.device_put` for the whole tree: per-leaf transfers
        each pay a host<->device round trip (0.9 ms on a directly
        attached v5e — chip_smoke.py; a PPO batch has seven leaves).
        Trees whose every leaf is already a device array (batches sliced
        from the device-resident rollout store) pass through untouched."""
        import jax

        from trlx_tpu.parallel import shard_batch

        if self.mesh is None:
            if self._device_resident(tree):
                return tree
            return jax.device_put(tree)
        return shard_batch(self.mesh, tree)

    @staticmethod
    def _device_resident(tree) -> bool:
        """Every leaf is already a device array (e.g. batches sliced from
        the device-resident rollout store)."""
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
        return bool(leaves) and all(
            isinstance(x, jax.Array) for x in leaves
        )

    def _pad_rows(self, tree):
        """(padded tree, real row count): repeat the final row until the
        batch dim is a multiple of dp*fsdp. Covers ad-hoc batch sizes (eval
        prompts, user sample() calls) that the mesh couldn't shard; callers
        slice results back to the real count."""
        import jax
        import numpy as np

        leaves = jax.tree_util.tree_leaves(tree)
        n = leaves[0].shape[0]
        if self.mesh is None:
            return tree, n
        n_data = self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
        pad = (-n) % n_data
        if pad == 0:
            return tree, n
        return (
            jax.tree_util.tree_map(
                lambda x: np.concatenate(
                    [x, np.repeat(np.asarray(x)[-1:], pad, axis=0)], axis=0
                ),
                tree,
            ),
            n,
        )

    # auto-enable threshold, set from v5e measurements of attention
    # fwd+bwd (both directions Pallas kernels): ~parity with dense at 1k,
    # ~1.8x at 4k (11 vs 20 ms), ~11x at 8k (62 vs 696 ms) where the
    # T x T score tensors blow past cache/HBM headroom — and the kernels'
    # O(T * block) memory frees HBM for batch at any length, so the kernel
    # engages from the parity point up (force via model.fused_attention)
    FUSED_ATTENTION_MIN_T = 1024

    def _train_attention_fn(self):
        """Attention implementation for train-time forwards, in precedence
        order: ring attention when the mesh has an sp axis > 1 (sequence
        parallelism, trlx_tpu.ops.ring_attention); the fused Pallas kernel
        on TPU for long contexts or when model.fused_attention forces it
        (trlx_tpu.ops.pallas_attention); else None = dense XLA attention.
        Generation keeps the dense KV-cache decode path either way — decode
        steps attend 1 query token, nothing to fuse."""
        import jax

        if self.mesh is not None and self.mesh.shape.get("sp", 1) > 1:
            from trlx_tpu.ops.ring_attention import make_sp_attention_fn

            return make_sp_attention_fn(self.mesh)
        fused = self.config.model.fused_attention
        if fused is None:
            T = self.config.train.input_size + self.config.train.gen_size
            fused = (
                jax.default_backend() == "tpu"
                and T >= self.FUSED_ATTENTION_MIN_T
            )
        if fused:
            from trlx_tpu.ops.pallas_attention import make_pallas_attention_fn

            # gate per-call on the ACTUAL traced length, not just the config
            # length: ILQL collates the whole store once padded to the
            # store-global max, and eval/sample calls trace their own
            # lengths — auto-enabled runs can still see sequences below the
            # kernel's measured parity point; those take the dense fallback
            # inside the fn.
            # An explicit model.fused_attention=True keeps the kernel's own
            # lower floor (the user asked for the kernel).
            forced = self.config.model.fused_attention is not None
            return make_pallas_attention_fn(
                mesh=self.mesh,
                min_fused_t=None if forced else self.FUSED_ATTENTION_MIN_T,
            )
        return None

    def _check_memory_fit(self, spec, frozen_dtype, ref_branch=True,
                          extra_trainable=0, extra_frozen=0,
                          embed_trainable=False) -> None:
        """Fail BEFORE allocation with an actionable message when the model
        state clearly cannot fit the per-device HBM budget (a 24 GB fp32
        gpt-j-6B OOMing mid-init is far harder to diagnose). Estimates
        params (frozen in frozen_dtype, trainable+ref tops, fp32 adam
        moments for the trainable top), divided by the mesh's parameter
        sharding extent (fsdp * tp).

        The estimate is a deliberate LOWER bound: dividing by fsdp*tp
        assumes every tensor shards over both axes, but the sharding rules
        replicate small tensors (layernorms, biases, v_head) — a config
        that passes can still OOM near the boundary; one that fails
        definitely would have. Skipped when the runtime exposes no
        bytes_limit or TRLX_TPU_SKIP_MEMCHECK=1.

        `ref_branch=False` drops the frozen reference-branch term (ILQL has
        no ref copy); `extra_trainable` / `extra_frozen` add
        parameter-count terms for method-specific heads (ILQL's Q/V heads
        and frozen target-Q copies)."""
        import os

        if os.environ.get("TRLX_TPU_SKIP_MEMCHECK"):
            return
        import jax
        import numpy as np

        limit = (jax.local_devices()[0].memory_stats() or {}).get(
            "bytes_limit"
        )
        if not limit:  # the CPU backend reports no stats
            return
        d, f, L, V = spec.d_model, spec.d_ff, spec.n_layer, spec.vocab_size
        per_layer = 4 * d * d + 2 * d * f  # qkv/o + mlp (biases negligible)
        k = self.config.model.num_layers_unfrozen
        k = L if k < 0 else min(k, L)
        embed = V * d + spec.n_positions * d
        # an untied lm_head lives in BOTH the trainable branch (fp32 +
        # adam) and the ref copy (frozen_dtype) — at 6B scale it is ~2.5 GB
        # of the trainable budget and must not be omitted
        lm_head = 0 if spec.tie_lm_head else V * d
        frozen_sz = np.dtype(frozen_dtype).itemsize
        # optimizer-state bytes/param follow train.optimizer — the lever
        # build_optimizer documents: fp32 AdamW 8 (mu + nu), bf16-mu AdamW
        # 6, adafactor ~0 (factored nu is O(rows + cols) per matrix)
        opt_name = getattr(self.config.train, "optimizer", "adamw").lower()
        if opt_name == "adafactor":
            opt_bytes = 0
        else:
            mu_dtype = getattr(
                self.config.train, "adam_moment_dtype", "float32"
            )
            opt_bytes = (2 if mu_dtype == "bfloat16" else 4) + 4
        # ILQL full unfreeze trains the embeddings (round-5 parity,
        # trlx_tpu.models.ilql.split_embed_for_unfreeze): their fp32 +
        # optimizer bytes move into the trainable term — at 6B scale the
        # ~206M embed params carry ~1.6 GB of Adam moments that must not
        # be omitted
        embed_train = embed if embed_trainable else 0
        embed_frozen = 0 if embed_trainable else embed
        est = (
            ((L - k) * per_layer + embed_frozen) * frozen_sz  # frozen trunk
            + (k * per_layer + lm_head) * frozen_sz * (1 if ref_branch else 0)
            + (k * per_layer + lm_head + embed_train + extra_trainable)
            * (4 + opt_bytes)
            + extra_frozen * frozen_sz
        )
        shards = 1
        if self.mesh is not None:
            shards = self.mesh.shape.get("fsdp", 1) * self.mesh.shape.get(
                "tp", 1
            )
        est //= shards
        if est > int(limit * 1.05):
            # param_dtype only helps methods with a frozen-dtype storage
            # path (the PPO hydra); suggesting it for ILQL would send the
            # user down a dead end
            dtype_opt = (
                "set model.param_dtype: bfloat16 (frozen trunk + ref "
                "branch storage; trainable/optimizer stay fp32), "
                if ref_branch else ""
            )
            opt_hint = (
                "set train.optimizer: adafactor (drops the "
                f"{opt_bytes} optimizer bytes/param), "
                if opt_bytes else ""
            )
            raise ValueError(
                f"model state needs ~{est / 2**30:.1f} GB/device but the "
                f"device reports {limit / 2**30:.1f} GB HBM. Options: "
                f"{dtype_opt}{opt_hint}lower num_layers_unfrozen, shard "
                f"over a mesh with fsdp/tp, or set TRLX_TPU_SKIP_MEMCHECK=1 "
                f"to try anyway."
            )

    def push_to_store(self, data) -> None:
        """Append experience to the rollout store
        (parity: reference model/__init__.py:46)."""
        self.store.push(data)

    @abstractmethod
    def act(self, prompts):
        """Generate responses for a batch of prompts; returns (query_tokens,
        response_tokens, response_texts)."""
        raise NotImplementedError

    @abstractmethod
    def sample(self, prompts, length: int, n_samples: int):
        """Sample continuations from the current policy."""
        raise NotImplementedError

    @abstractmethod
    def learn(self, log_fn: Callable = None, save_fn: Callable = None,
              eval_fn: Callable = None):
        """Run the optimization loop over the store."""
        raise NotImplementedError

    @abstractmethod
    def get_components(self) -> Dict:
        """Named checkpointable components
        (parity: reference model/__init__.py:90-99)."""
        raise NotImplementedError

    def _load_or_spec(self, config):
        """(spec, trunk | None): pretrained import when no explicit
        model_spec is configured; a from-config random init otherwise.

        A failing pretrained load RAISES instead of silently training a
        from-scratch model — a typo'd model_path must not masquerade as a
        successful run. Opt into random init explicitly via
        `model.model_spec`."""
        if config.model.model_spec is not None:
            from trlx_tpu.models.transformer import require_supported

            spec = config.model.resolve_spec()
            require_supported(spec, trainer=type(self).__name__)
            return spec, None
        from trlx_tpu.models.hf_import import load_trunk_from_hf

        try:
            spec, embed, blocks, ln_f = load_trunk_from_hf(
                config.model.model_path
            )
        except Exception as e:
            raise RuntimeError(
                f"could not load pretrained weights for "
                f"'{config.model.model_path}': {e!r}. For a from-config "
                f"randomly-initialized model, set model.model_spec in the "
                f"config instead."
            ) from e
        return spec, (embed, blocks, ln_f)

    def _main_process_log(self, log_fn: Callable) -> Callable:
        """Emit metrics from process 0 only (parity: the reference's
        main-process-only tracker init + accelerator.print,
        accelerate_base_model.py:58-61)."""
        from trlx_tpu.parallel import is_main_process

        if log_fn is None or is_main_process():
            return log_fn
        return lambda stats: None

    def save(self, directory: str = None) -> None:
        """Checkpoint components (reference's torch.save per component →
        Orbax here; see trlx_tpu.utils.checkpoint). Saves are
        crash-atomic (staged + renamed — a preemption mid-save cannot
        corrupt the previous checkpoint) and single-writer (process-0
        gate lives inside save_components). With no explicit
        `directory`, saves land as ``checkpoint_dir/step_<iter>`` with a
        LATEST marker and ``train.keep_checkpoints`` retention — the
        layout ``resume_from: auto`` and divergence rollback restore
        from.

        Supervised: the save runs as the watchdog's ``checkpoint_save``
        phase and, with ``train.checkpoint_timeout`` set, through a
        bounded worker — a save wedged on a dead filesystem raises
        SeamTimeout instead of silently hanging the run
        (trlx_tpu.supervisor)."""
        from trlx_tpu import supervisor
        from trlx_tpu.supervisor import bounded_call, chaos
        from trlx_tpu.utils.checkpoint import (
            save_components,
            save_step_checkpoint,
        )

        def write():
            if directory is not None:
                save_components(self.get_components(), directory)
                return
            save_step_checkpoint(
                self.get_components(),
                self.config.train.checkpoint_dir,
                step=getattr(self, "iter_count", 0),
                keep=getattr(self.config.train, "keep_checkpoints", 0),
            )

        with supervisor.phase("checkpoint_save"):
            chaos.maybe_inject("checkpoint_save")
            timeout = float(
                getattr(self.config.train, "checkpoint_timeout", 0.0) or 0.0
            )
            if timeout > 0:
                bounded_call(write, timeout=timeout, label="checkpoint_save")
            else:
                write()

    def load(self, directory: str = None) -> None:
        from trlx_tpu.utils.checkpoint import restore_components

        restored = restore_components(
            self.get_components(), directory or self.config.train.checkpoint_dir
        )
        self.set_components(restored)

    def _rollback_to_latest(self):
        """Restore the newest valid checkpoint under checkpoint_dir (the
        StepGuard's rollback hook). Returns the restored path, or None
        when no committed checkpoint exists."""
        from trlx_tpu.utils.checkpoint import find_latest_checkpoint

        directory = find_latest_checkpoint(self.config.train.checkpoint_dir)
        if directory is None:
            return None
        self.load(directory)
        return directory

    def _make_step_guard(self, log_fn):
        """The learn loops' divergence guard (trlx_tpu.utils.faults),
        built from train.max_bad_steps; disabled (and cost-free) at the
        default 0."""
        from trlx_tpu.utils.faults import StepGuard

        return StepGuard(
            max_bad_steps=getattr(self.config.train, "max_bad_steps", 0),
            rollback_fn=self._rollback_to_latest,
            log=log_fn,
        )

    def _observe_step(self, step_guard, stats) -> None:
        """Feed one jitted-step verdict to the StepGuard. Only syncs the
        tiny bad_step flag to host when guarding is enabled — the
        disabled path costs nothing per step."""
        if step_guard is None or not step_guard.enabled:
            return
        import jax

        host = jax.device_get(
            {
                k: stats[k]
                for k in ("bad_step", "loss", "grad_norm", "approx_kl")
                if k in stats
            }
        )
        detail = {k: float(v) for k, v in host.items() if k != "bad_step"}
        step_guard.observe(
            bad=float(host.get("bad_step", 0.0)) > 0,
            step=self.iter_count,
            detail=detail,
        )

    def _telemetry_stats(self, samples_per_sec: float) -> Dict:
        """The per-iteration observability payload the learn loops merge
        into their stats emission: ``time/*`` last phase durations,
        ``fault/*`` counters, ``device/*`` HBM gauges, ``compile/*``
        first-call latencies, plus ``throughput/*`` computed here from
        the loop's sample clock and the trainer's analytic flops. Empty
        when telemetry is disabled (the reference-parity stream)."""
        from trlx_tpu import telemetry

        tel = telemetry.current()
        if tel is None:
            return {}
        out = tel.tracker_stats()
        out["throughput/samples_per_sec"] = samples_per_sec
        if self._tokens_per_sample:
            tokens_per_sec = samples_per_sec * self._tokens_per_sample
            out["throughput/tokens_per_sec"] = tokens_per_sec
            mfu = telemetry.mfu_estimate(
                tokens_per_sec, self._flops_per_token
            )
            if mfu is not None:
                out["throughput/mfu"] = mfu
        return out

    def _maybe_flush_telemetry(self) -> None:
        """Periodic telemetry flush (``train.telemetry_flush_every``):
        rewrite ``run_dir/telemetry.json`` + ``trace.jsonl`` on an
        iteration cadence so a SIGKILL'd run (which never reaches the
        learn()-exit ``_finish_telemetry``) still leaves artifacts. Write
        failures are reported, never raised — observability must not
        kill training."""
        from trlx_tpu import telemetry

        every = int(getattr(self.config.train, "telemetry_flush_every", 0))
        if every <= 0:
            return
        tel = telemetry.current()
        if tel is None:
            return
        last = getattr(self, "_telemetry_flushed_at", 0)
        if self.iter_count - last < every:
            return
        self._telemetry_flushed_at = self.iter_count
        try:
            tel.write()
        except Exception as e:
            print(
                f"[trlx_tpu] periodic telemetry flush failed ({e!r}); "
                f"continuing",
                file=sys.stderr, flush=True,
            )

    def _finish_telemetry(self, kind: str, clock=None) -> None:
        """learn()-exit hook: stamp the run's headline throughput and
        persist/print the telemetry summary (trlx_tpu.telemetry — writes
        ``run_dir/telemetry.json`` + ``trace.jsonl``). Runs on every exit
        path including exceptions, so a diverged/preempted run still
        leaves its observability record behind."""
        from trlx_tpu import telemetry

        tel = telemetry.current()
        if tel is None:
            return
        if clock is not None and clock.total_samples:
            sps = clock.samples_per_second()
            tel.set_headline(
                f"{kind}_learn_samples_per_sec", sps, "samples/s"
            )
            if self._tokens_per_sample:
                tel.registry.set_gauge(
                    "throughput/tokens_per_sec",
                    sps * self._tokens_per_sample,
                )
        tel.finish()

    def _preempt(self, log_fn, guard, just_saved: bool = False,
                 sup=None) -> bool:
        """Checkpoint + True when ANY process wants the loop to stop:
        SIGTERM preemption (trlx_tpu.utils.preemption), the supervisor's
        walltime deadline (train.max_walltime), or a stall escalation
        that found the loop still alive (trlx_tpu.supervisor). All three
        ride the same rank-agreement collective (PreemptionGuard.poll),
        so multi-host ranks exit together; resume via
        train.resume_from picks up exactly here. `just_saved`: an
        interval checkpoint fired at this same step boundary — skip the
        redundant second Orbax write (the eviction grace period is
        short)."""
        local = sup is not None and sup.stop_requested()
        if guard is None:
            stop = local
        else:
            stop = guard.poll(extra=local)
        if not stop:
            return False
        if not just_saved:
            self.save()
        reason = sup.stop_reason() if local else "preempted"
        log_fn({"iter": self.iter_count, reason: 1.0})
        return True

    def _make_supervisor(self):
        """The learn loops' run supervisor (trlx_tpu.supervisor), built
        from the train.stall_* / max_walltime knobs — inert (but still a
        valid context manager) when they are all 0. Also installs the
        chaos schedule from $TRLX_TPU_CHAOS / train.chaos, counters
        fresh, so every learn() call injects at the same schedule points.
        The rescue hook is a bounded best-effort save for the
        checkpoint-exit escalation path — it runs on the watchdog thread
        while the main thread is wedged, so it is itself bounded."""
        from trlx_tpu.supervisor import RunSupervisor, bounded_call, chaos

        chaos.configure_from(self.config.train)

        def rescue():
            bounded_call(
                self.save,
                timeout=float(
                    getattr(self.config.train, "checkpoint_timeout", 0.0)
                    or 120.0
                ),
                label="stall rescue checkpoint",
            )

        return RunSupervisor.from_config(
            self.config.train, rescue_fn=rescue
        )

    def _contain_stall(self, log_fn) -> None:
        """StallError containment at learn() level: a hung seam past its
        retry budget (SeamTimeout) becomes a clean checkpoint-and-exit —
        commit a resumable checkpoint (best-effort: the stall may be the
        checkpoint path itself), emit the verdict, and let the caller
        re-raise so the operator/scheduler sees a failed-but-resumable
        run (train.resume_from: auto picks up exactly here)."""
        try:
            self.save()
        except Exception as e:
            print(
                f"[trlx_tpu] stall-exit checkpoint failed ({e!r}); the "
                f"last interval checkpoint remains the resume point",
                flush=True,
            )
        log_fn({"iter": self.iter_count, "stalled": 1.0})

    def maybe_resume(self) -> bool:
        """Restore from config.train.resume_from once, at trainer
        construction — BEFORE any make_experience/evaluate the caller runs,
        so resumed rollouts come from the restored policy, not the fresh
        init. The kill-and-continue path the reference's dead checkpointing
        never had (reference: trlx/model/__init__.py:101-129). Returns True
        when a restore actually happened.

        ``resume_from: auto`` resolves to the newest valid checkpoint
        under checkpoint_dir — and to a FRESH start when none exists, so
        the same config line covers both the first launch and every
        restart after preemption (half-written saves are skipped by
        find_latest_checkpoint; see docs "Fault tolerance")."""
        directory = getattr(self.config.train, "resume_from", "")
        if not directory or getattr(self, "_resumed", False):
            return False
        if directory == "auto":
            from trlx_tpu.utils.checkpoint import find_latest_checkpoint

            directory = find_latest_checkpoint(
                self.config.train.checkpoint_dir
            )
            if directory is None:
                return False
        self.load(directory)
        self._resumed = True
        return True

    def set_components(self, components: Dict) -> None:
        raise NotImplementedError

    def intervals(self, steps: int) -> Dict[str, bool]:
        """Which periodic actions fire at `steps`
        (parity: reference model/__init__.py:131-140)."""
        return {
            "do_log": steps % self.config.train.log_interval == 0,
            "do_eval": steps % self.config.train.eval_interval == 0,
            "do_save": steps > 0
            and steps % self.config.train.checkpoint_interval == 0,
        }
