"""Top-level YAML → typed dataclass configuration.

Keeps the reference's three-section layout and field names
(model / train / method — reference: trlx/data/configs.py:10-158) so its
shipped YAMLs parse unchanged, and adds TPU-native fields with defaults:
mesh axis sizes, dtypes, and from-config model architecture specs (used when
no pretrained checkpoint is reachable).
"""

from dataclasses import dataclass
from typing import Any, Dict, Optional

import yaml

from trlx_tpu.data.method_configs import (
    MethodConfig,
    filter_known_fields as _filter_known,
    get_method,
)


@dataclass(frozen=True)
class ModelSpec:
    """Architecture hyperparameters for building a model from config.

    Frozen (hashable) so jitted functions can be cached per spec. Used both
    for from-scratch tiny models (the reference builds one in
    examples/ilql_randomwalks.py:98-100 via GPT2Config) and as the shape
    contract when importing pretrained HF weights.
    """

    arch: str = "gpt2"  # gpt2 | gptj | gptneox | llama | cohere2_moe | sarvam_mla
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 0  # 0 => 4 * d_model
    n_positions: int = 1024
    rotary_dim: int = 0  # gptj/gptneox: rotary dims per head (0 => head_dim)
    layer_norm_epsilon: float = 1e-5
    tie_lm_head: bool = True  # gpt2 ties lm_head to wte; gptj/neox do not
    n_kv_heads: int = 0  # grouped-query attention (llama); 0 => n_head
    rope_theta: float = 10000.0
    head_size: int = 0  # width of one head; 0 => d_model // n_head
    # A model whose layers differ repeats ``layer_pattern`` over its depth:
    # layer n is of kind ``layer_pattern[n % len]``, "full" (causal over the
    # whole context) or "window" (the last ``window`` keys). Empty: every
    # layer is full, as the dense families are. ``rope_kinds`` names the
    # kinds whose q/k are rotated; a kind left out carries no positions.
    layer_pattern: tuple = ()
    window: int = 0
    rope_kinds: tuple = ("full", "window")
    # Routed experts (0 = a dense FFN): the router scores all ``n_experts``
    # and takes ``experts_per_token``; this process holds the
    # ``experts_held`` experts from ``expert_offset`` on (0 held => all)
    # and computes their part of the sum. ``n_shared_experts`` run on every
    # token and are averaged.
    n_experts: int = 0
    experts_per_token: int = 0
    n_shared_experts: int = 0
    expert_width: int = 0  # 0 => d_ff
    experts_held: int = 0
    expert_offset: int = 0
    logit_scale: float = 1.0
    # The router's extras (DeepSeek-V3's): a per-expert bias added to the
    # scores for the CHOICE of the top experts alone (the gates weigh by
    # the scores), and a factor on the normalised gates. The first
    # ``first_dense_layers`` layers keep a dense FFN of width ``d_ff``.
    router_bias: bool = False
    routed_scaling_factor: float = 1.0
    first_dense_layers: int = 0
    # Latent attention (MLA; 0 = per-head K and V): the cache holds, a
    # token a layer, one latent of ``kv_lora_rank`` and one rotated key
    # part of ``qk_rope_head_dim`` shared by all heads; a head scores with
    # ``qk_nope_head_dim + qk_rope_head_dim`` and reads values of
    # ``v_head_dim``.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN ("deepseek_yarn"; factor 0 = plain RoPE): the rotated
    # frequencies are blended between theta^(-2k/d) and the same over
    # ``rope_factor``, by where each falls between ``rope_beta_fast`` and
    # ``rope_beta_slow`` turns over ``rope_original_positions``; the
    # score scale carries mscale(factor, rope_mscale_all_dim) squared.
    rope_factor: float = 0.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rope_original_positions: int = 0

    def __post_init__(self):
        if self.d_ff == 0:
            object.__setattr__(self, "d_ff", 4 * self.d_model)
        for name in ("layer_pattern", "rope_kinds"):  # lists from JSON/YAML
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.n_experts:
            if self.expert_width == 0:
                object.__setattr__(self, "expert_width", self.d_ff)
            if self.experts_held == 0:
                object.__setattr__(self, "experts_held", self.n_experts)
            if not 0 < self.experts_per_token <= self.n_experts:
                raise ValueError("experts_per_token must be in 1..n_experts")
            if self.expert_offset + self.experts_held > self.n_experts:
                raise ValueError(
                    "expert_offset + experts_held exceeds n_experts"
                )
        if self.d_model % self.n_head != 0:
            raise ValueError("d_model must be divisible by n_head")
        if self.kv_lora_rank:
            if min(self.qk_nope_head_dim, self.qk_rope_head_dim,
                   self.v_head_dim) <= 0 or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent attention needs qk_nope_head_dim, v_head_dim "
                    "and an even qk_rope_head_dim"
                )
            if self.layer_pattern or self.n_kv_heads:
                raise ValueError(
                    "latent attention keeps one class of page and no "
                    "kv heads: leave layer_pattern and n_kv_heads unset"
                )
        if not 0 <= self.first_dense_layers <= self.n_layer:
            raise ValueError("first_dense_layers must be in 0..n_layer")
        if self.n_kv_heads and self.n_head % self.n_kv_heads != 0:
            raise ValueError("n_head must be divisible by n_kv_heads")
        if any(k not in ("full", "window") for k in self.layer_pattern):
            raise ValueError(
                f"layer_pattern {self.layer_pattern} names a kind other "
                f"than full | window"
            )
        if "window" in self.layer_pattern:
            if self.window <= 0:
                raise ValueError("a window layer needs window > 0")
            if "full" not in self.layer_pattern:
                raise ValueError(
                    "layer_pattern needs a full layer beside its window "
                    "layers (the full class carries the slot's lanes)"
                )

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_head

    def layer_kind(self, layer: int) -> str:
        """"full" | "window": the kind of layer ``layer`` (0-based)."""
        if not self.layer_pattern:
            return "full"
        return self.layer_pattern[layer % len(self.layer_pattern)]

    @property
    def page_classes(self) -> tuple:
        """The classes of KV page a paged pool of this model keeps, one
        per kind of layer present: ("full",) for a dense model."""
        kinds = set(self.layer_pattern) or {"full"}
        return tuple(k for k in ("full", "window") if k in kinds)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_head

    @property
    def latent_width(self) -> int:
        """Numbers a token a layer a latent page holds: the latent and the
        shared rotated key part (0: per-head K and V)."""
        return self.kv_lora_rank + self.qk_rope_head_dim \
            if self.kv_lora_rank else 0

    @property
    def latent_page_width(self) -> int:
        """Numbers a token of a latent PAGE: ``latent_width`` out to whole
        lane tiles of 128 (576 -> 640, the tail zero). The TPU lays the
        last dimension of a buffer out so whether asked or not, and the
        decode kernel can slice a page out of the pool only if it is asked
        (Mosaic: "Slice shape along dimension 2 must be aligned to tiling
        (128), but is 576"; the operand's memref is the padded 640)."""
        return -(-self.latent_width // 128) * 128


    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "ModelSpec":
        return cls(**_filter_known(cls, config))

    # Named presets for the model families the reference exercises
    # (reference: README.md:14, configs/ppo_config.yml:2, configs/ppo_gptj.yml:2).
    @classmethod
    def preset(cls, name: str) -> "ModelSpec":
        presets = {
            "gpt2": cls(arch="gpt2", n_layer=12, n_head=12, d_model=768),
            "gpt2-medium": cls(arch="gpt2", n_layer=24, n_head=16, d_model=1024),
            "gpt2-large": cls(arch="gpt2", n_layer=36, n_head=20, d_model=1280),
            "gpt2-xl": cls(arch="gpt2", n_layer=48, n_head=25, d_model=1600),
            "gpt-j-6b": cls(
                arch="gptj",
                vocab_size=50400,
                n_layer=28,
                n_head=16,
                d_model=4096,
                n_positions=2048,
                rotary_dim=64,
                tie_lm_head=False,
            ),
            "llama-2-7b": cls(
                arch="llama",
                vocab_size=32000,
                n_layer=32,
                n_head=32,
                d_model=4096,
                d_ff=11008,
                n_positions=4096,
                layer_norm_epsilon=1e-5,
                tie_lm_head=False,
            ),
            "llama-3-8b": cls(
                arch="llama",
                vocab_size=128256,
                n_layer=32,
                n_head=32,
                n_kv_heads=8,
                d_model=4096,
                d_ff=14336,
                n_positions=8192,
                rope_theta=500000.0,
                layer_norm_epsilon=1e-5,
                tie_lm_head=False,
            ),
        }
        key = name.lower()
        if key not in presets:
            raise KeyError(f"Unknown model preset '{name}'; known: {sorted(presets)}")
        return presets[key]


@dataclass
class ModelConfig:
    """Model section (field parity: reference trlx/data/configs.py:27-31).

    `device` is accepted for YAML compatibility and ignored — placement on
    TPU is controlled by the mesh (see TrainConfig.mesh).

    TPU extras:
    :param model_arch: architecture family when building/importing
    :param model_spec: dict of ModelSpec overrides for from-config models
    :param param_dtype: storage dtype for FROZEN parameters (PPO hydra:
        the frozen trunk + reference branch). The trainable branch and
        optimizer state always stay float32. "bfloat16" is the memory
        lever that fits gpt-j-6B PPO on one 16 GB chip
        (docs/source/performance.rst)
    :param compute_dtype: dtype matmuls/activations run in (bf16 for MXU)
    :param fused_attention: True forces the Pallas flash-attention kernel
        for train-time forwards, False forces the dense XLA path, None
        (default) auto-selects it on TPU for long contexts
    """

    model_path: str
    tokenizer_path: str
    model_type: str
    device: str = ""
    num_layers_unfrozen: int = -1
    model_arch: str = "gpt2"
    model_spec: Optional[dict] = None
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    fused_attention: Optional[bool] = None

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        return cls(**_filter_known(cls, config))

    def resolve_spec(self) -> "ModelSpec":
        """Single source of truth for the architecture spec: `model_spec`
        overrides, with `model_arch` supplying the arch unless the spec dict
        sets it explicitly."""
        overrides = dict(self.model_spec or {})
        overrides.setdefault("arch", self.model_arch)
        return ModelSpec.from_dict(overrides)


@dataclass
class TrainConfig:
    """Train section (field parity: reference trlx/data/configs.py:94-119).

    `accelerate` / `accelerate_config_path` are accepted for YAML
    compatibility and ignored; distribution is expressed by `mesh`.

    TPU extras:
    :param mesh: axis sizes, e.g. {"dp": -1, "fsdp": 1, "tp": 1, "sp": 1};
        -1 means "all remaining devices"
    :param seed: global PRNG seed (JAX is explicit about randomness)
    :param remat: rematerialize transformer blocks in the backward pass
    :param debug_nans: enable jax_debug_nans — jitted programs fail fast at
        the op that produced a NaN instead of training on garbage (SURVEY
        §5 sanitizer gap; costs recompiles + sync, debug only). For long
        unattended runs prefer ``max_bad_steps`` (skip/rollback/abort —
        trlx_tpu.utils.faults) over fail-fast.
    :param resume_from: checkpoint dir, run dir, or "auto" (newest valid
        checkpoint under ``checkpoint_dir``; fresh start when none)
    :param keep_checkpoints: retention — newest N step checkpoints kept
    :param max_bad_steps: consecutive skipped (non-finite / KL-breaching)
        steps before rollback-to-checkpoint; second strike aborts
    :param max_step_kl: PPO per-step policy-KL bound counted as bad
    :param host_retries / host_retry_backoff: bounded retry for host
        seams (reward_fn, trackers)
    :param telemetry / telemetry_dir: unified metrics/span telemetry
        (trlx_tpu.telemetry) — per-iteration time/* / throughput/* /
        fault/* keys and a telemetry.json + trace.jsonl at learn() exit
    :param stall_timeout / stall_first_timeout / stall_grace /
        stall_action / host_call_timeout / checkpoint_timeout /
        max_walltime / chaos: run-supervisor knobs (trlx_tpu.supervisor)
        — heartbeat watchdog with stack-dump + escalation, bounded host
        seams that time out HUNG calls, walltime save-and-exit, and
        deterministic chaos drills
    """

    n_ctx: int
    epochs: int
    total_steps: int
    batch_size: int
    grad_clip: float

    lr_ramp_steps: int
    lr_decay_steps: int
    weight_decay: float
    learning_rate_init: float
    learning_rate_target: float

    log_interval: int
    checkpoint_interval: int
    eval_interval: int

    pipeline: str
    orchestrator: str

    input_size: int = 0
    gen_size: int = 1024

    accelerate: bool = True
    accelerate_config_path: str = ""

    project_name: str = ""
    # metric sink: "print" (default), "wandb", "jsonl:<path>", "none"
    # (reference: Accelerator(log_with="wandb"), accelerate_base_model.py:52)
    tracker: str = "print"

    mesh: Optional[Dict[str, int]] = None
    # microbatches per GPipe pass when mesh.pp > 1 (bubble fraction is
    # (pp-1)/(n_micro+pp-1): raise toward 4*pp to amortize)
    pp_num_microbatches: int = 4
    seed: int = 0
    remat: bool = False
    checkpoint_dir: str = "ckpts"
    # restore components at trainer construction (kill-and-continue
    # resume). A directory restores that checkpoint (or the newest valid
    # "step_<N>" inside it); "auto" resumes from the newest valid
    # checkpoint under checkpoint_dir and starts FRESH when there is none
    # — the fire-and-forget setting for preemptible jobs (docs
    # "Fault tolerance"). "" disables.
    resume_from: str = ""
    # retention: keep only the newest N committed "step_<N>" checkpoints
    # under checkpoint_dir, garbage-collecting older ones (and dead
    # staging dirs from saves killed mid-write) after each successful
    # save. 0 keeps everything.
    keep_checkpoints: int = 0
    # divergence containment (trlx_tpu.utils.faults.StepGuard): a train
    # step with non-finite loss/grad-norm (or KL above max_step_kl) is
    # SKIPPED on device — params/opt-state not committed — and counted;
    # this many CONSECUTIVE bad steps roll the run back to its last
    # checkpoint, and a second strike aborts with a diagnostic instead of
    # training on garbage. 0 disables (no per-step verdict sync —
    # reference-parity fast path).
    max_bad_steps: int = 0
    # PPO only: per-step bound on the policy-update KL (the train step's
    # approx_kl stat, new policy vs rollout policy). A step above it
    # counts as bad under max_bad_steps. 0 = finiteness checks only.
    max_step_kl: float = 0.0
    # bounded retry-with-backoff for host-side seams (user reward_fn
    # calls, tracker emissions): extra attempts after the first failure,
    # and the base backoff seconds (doubled per retry). A seam that still
    # fails after the budget raises (reward) or degrades to stdout
    # (tracker — trlx_tpu.utils.trackers.ResilientTracker).
    host_retries: int = 2
    host_retry_backoff: float = 0.5
    # PPO only: dispatch the next epoch's rollout programs BEFORE the
    # current epoch's updates drain (one host-sync saved per cycle;
    # whether that still pays on a directly attached chip is ROADMAP
    # S4's measurement). Semantics:
    # each epoch trains on experience generated by the PREVIOUS epoch's
    # policy (staleness of exactly one update phase) instead of the
    # reference's strictly on-policy refresh. Default off = reference
    # semantics.
    continuous_rollouts: bool = False
    # "adamw" (reference parity: torch AdamW, accelerate_base_model.py:63)
    # or "adafactor" — the TPU-memory lever: factored second moment and no
    # first moment drop optimizer state from 8 bytes/param to ~0, which is
    # what fits 6B-class PPO on a single 16 GB chip
    optimizer: str = "adamw"
    # adamw first-moment (mu) storage dtype; "bfloat16" halves mu. The
    # second moment stays float32 (optax exposes no nu dtype; its sqrt is
    # precision-sensitive anyway)
    adam_moment_dtype: str = "float32"
    # trap SIGTERM during learn(): checkpoint at the next step boundary and
    # return cleanly (preemptible VMs / node drains), resumable via
    # resume_from (trlx_tpu.utils.preemption)
    save_on_preemption: bool = True
    # multi-process runs agree on preemption via a small collective; it
    # runs every this-many step boundaries. 0 = auto (min(log_interval, 8)
    # — throttled for high-dispatch-latency runtimes while staying inside
    # eviction grace windows). Lower it (e.g. 1) when single steps are
    # slow enough that 8 of them outlast your scheduler's SIGTERM grace.
    preempt_poll_interval: int = 0
    # ---- run supervisor (trlx_tpu.supervisor, docs "Fault tolerance"):
    # "stuck != dead" containment for unattended runs ----
    # heartbeat watchdog: a learn-loop phase (rollout, reward_fn,
    # ppo_update/ilql_update, eval, checkpoint_save) open longer than this
    # many seconds is a STALL — all-thread stacks dump to stderr,
    # telemetry flushes, fault/stalls increments, and stall_grace seconds
    # later the run escalates per stall_action. 0 disables the watchdog.
    stall_timeout: float = 0.0
    # budget for the FIRST occurrence of each phase, which carries trace +
    # XLA-compile cost (the same first-call separation telemetry keeps).
    # 0 = 5 * stall_timeout.
    stall_first_timeout: float = 0.0
    # seconds between the stall dump and escalation. "checkpoint_exit"
    # attempts a bounded rescue checkpoint from the watchdog thread and
    # hard-exits 75 (EX_TEMPFAIL: schedulers restart; resume_from: auto
    # continues); "abort" hard-exits 70 with no rescue. A stalled-but-
    # alive loop (a hung seam whose timeout fires) instead exits cleanly
    # through StallError containment before escalation is needed.
    stall_grace: float = 60.0
    stall_action: str = "checkpoint_exit"
    # bounded-worker timeout for host seams (reward_fn calls, tracker
    # emissions): a HUNG call — not just a failing one — raises
    # SeamTimeout after this many seconds and consumes one host_retries
    # attempt. 0 falls back to stall_timeout; both 0 = unbounded
    # (reference-parity behavior).
    host_call_timeout: float = 0.0
    # bounded-worker timeout for checkpoint saves (a dead NFS/GCS mount
    # must not silently wedge the run). 0 = unbounded.
    checkpoint_timeout: float = 0.0
    # walltime deadline: once the learn loop has run this many seconds it
    # checkpoints and exits cleanly at the next step boundary (set below
    # the reservation/queue limit; multi-host ranks agree through the
    # preemption collective and exit together). 0 disables.
    max_walltime: float = 0.0
    # deterministic chaos-injection schedule for drills/CI, e.g.
    # "reward_fn:hang=30@3;ppo_update:sigterm@2"
    # (trlx_tpu.supervisor.chaos; $TRLX_TPU_CHAOS overrides). "" disables.
    chaos: str = ""
    # unified telemetry (trlx_tpu.telemetry, docs "Observability"): the
    # learn loops emit per-iteration time/* phase durations, throughput/*
    # (tokens/sec, samples/sec, MFU), fault/* counters, and device/* HBM
    # gauges through the configured tracker, and write a telemetry.json
    # summary + Chrome-trace/Perfetto trace.jsonl at learn() exit. False
    # disables the whole subsystem — zero records, zero overhead (the
    # reference-parity metrics stream).
    telemetry: bool = True
    # where telemetry.json / trace.jsonl land. "" = checkpoint_dir, and
    # then only written when that directory exists (a checkpoint has been
    # committed); an explicit path is always created and written.
    telemetry_dir: str = ""
    # flush the telemetry summary/trace to run_dir every N training
    # iterations (reusing the learn()-exit writer), so a SIGKILL'd run —
    # which never reaches the exit hook — still leaves observability
    # artifacts no older than N iterations. 0 (default) = exit-only.
    telemetry_flush_every: int = 0
    debug_nans: bool = False

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        return cls(**_filter_known(cls, config))


@dataclass
class TRLConfig:
    """Top-level config (reference: trlx/data/configs.py:126-158)."""

    model: ModelConfig
    train: TrainConfig
    method: MethodConfig

    @classmethod
    def load_yaml(cls, yml_fp: str) -> "TRLConfig":
        with open(yml_fp, mode="r") as f:
            config = yaml.safe_load(f)
        return cls.from_dict(config)

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "TRLConfig":
        if config.get("serve"):
            # the serve: section is ``python -m trlx_tpu.serve``'s to
            # read; a retired value in it is refused at load all the same
            from trlx_tpu.serve.engine import refuse_retired

            refuse_retired(config["serve"])
        return cls(
            ModelConfig.from_dict(config["model"]),
            TrainConfig.from_dict(config["train"]),
            get_method(config["method"]["name"]).from_dict(config["method"]),
        )

    def to_nested_dict(self) -> Dict[str, Any]:
        """Round-trippable three-section dict: ``from_dict(to_nested_dict())``
        rebuilds an equivalent config (method.name is a dataclass field,
        so the method registry key survives). JSON-serializable — the
        trainers embed it as the checkpoint's ``config`` component
        (meta.json), which is how ``python -m trlx_tpu.serve`` rebuilds
        the exact architecture/tokenizer/sampling without a config file."""
        return {
            "model": dict(self.model.__dict__),
            "train": dict(self.train.__dict__),
            "method": dict(self.method.__dict__),
        }

    def to_dict(self) -> Dict[str, Any]:
        """Flat merged view of all three sections (the shape trackers log).

        Collision-safe: a field name appearing in more than one section is
        emitted once per section as ``<section>.<name>`` instead of letting
        the later section silently overwrite the earlier one (a method
        field shadowing a train field would otherwise corrupt logged
        hyperparameters)."""
        sections = {
            "model": self.model.__dict__,
            "train": self.train.__dict__,
            "method": self.method.__dict__,
        }
        counts: Dict[str, int] = {}
        for fields in sections.values():
            for k in fields:
                counts[k] = counts.get(k, 0) + 1
        data: Dict[str, Any] = {}
        for section, fields in sections.items():
            for k, v in fields.items():
                data[k if counts[k] == 1 else f"{section}.{k}"] = v
        return data
