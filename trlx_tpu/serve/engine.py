"""Checkpoint-to-endpoint inference engine with bucketed AOT decode.

The training side produces checkpoints (trlx_tpu.utils.checkpoint) and an
engine-grade jitted KV-cache decode (trlx_tpu.models.generation) — but
until this module the only consumer of either was the learn loop itself.
:class:`InferenceEngine` closes the train->serve gap:

- **restore**: loads the policy from a checkpoint dir or a run dir
  (``find_latest_checkpoint`` resolves the newest committed ``step_<N>``),
  reading the architecture/config from the checkpoint's own ``meta.json``
  ``config`` component when none is passed (trainers embed it at save).
  Only the ``params`` component is restored — the optimizer state never
  leaves disk.
- **strip**: serving needs the live policy branch only. The restored tree
  is reduced to (trunk blocks + trainable top blocks, embed + lm_head,
  ln_f) via the policy's own decode helpers; the reference branch and the
  value head are dropped, so steady-state HBM holds one policy, not the
  training triple.
- **bucket lattice**: decode shapes are static under XLA, so every
  compiled shape comes from a small lattice of
  ``(batch, prompt_len, gen_len)`` buckets. The slot scheduler
  (trlx_tpu.serve.slots) compiles one prefill program per
  ``(batch, prompt_len)`` class of it and one decode step for the whole
  pool; each program gets its OWN ``aot_jit`` wrapper (its own
  executable cache), so warming program N+1 is a first compile, not a
  steady-state miss, and ``compile/recompiles`` staying 0 is the serving
  invariant it already is for training.
- **the oracle**: :meth:`InferenceEngine.decode` runs ``generate()``
  over one bucket, batch to completion. It serves no traffic: the tests
  compare the scheduler's tokens against it.

What a request is lives in :mod:`trlx_tpu.serve.admission`; the
scheduler in :mod:`trlx_tpu.serve.slots`; the HTTP surface in
:class:`trlx_tpu.serve.server`.
"""

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.method_configs import filter_known_fields

Bucket = Tuple[int, int, int]  # (batch, prompt_len, gen_len)

#: default lattice for tiny/dev models; production lattices come from the
#: YAML ``serve:`` section or --buckets (docs/source/serving.rst has the
#: sizing guide)
_DEFAULT_BUCKETS = ((4, 32, 32), (8, 64, 64))


@dataclass
class ServeConfig:
    """The ``serve:`` YAML section / CLI knobs (all host-side).

    :param buckets: the (batch, prompt_len, gen_len) lattice to
        precompile. Requests round UP to the smallest (prompt_len,
        gen_len) shape class that fits; the admission batch extent is
        chosen at admission from the same-class queue population.
    :param max_queue: admission control — ``submit`` rejects once this
        many requests are queued (the client sees HTTP 429).
    :param request_timeout: bound on one request's queue+decode walltime;
        a breach raises SeamTimeout (HTTP 503) instead of holding the
        connection forever.
    :param stall_timeout: serve-side watchdog budget for one admission
        or decode step (trlx_tpu.supervisor); a hung one dumps all-thread
        stacks and counts ``fault/stalls`` instead of leaving a silently
        dead port. 0 disables.
    :param host / port: bind address for the HTTP endpoint.
    :param seed: base PRNG seed for sampling (each decode step adds
        its counter; greedy decode ignores it).
    :param slots: slot-pool size of the continuous-batching scheduler
        (trlx_tpu.serve.slots); 0 (default) sizes it to the largest
        compiled batch extent. Pool HBM is
        ``2 * n_layer * pages * page_size * kv_heads * head_dim``
        cache-dtype elements: the pool is fixed-size KV pages addressed
        through per-slot page tables, with radix-tree prefix caching
        (requests sharing a committed prompt prefix skip re-prefilling
        it).
    :param page_size: tokens per KV page (clamped to the slot buffer
        length). Smaller pages waste less on the last partial page and match shorter shared prefixes; larger
        pages mean fewer table entries and bigger contiguous reads. Also
        the prefix-cache granularity: only whole committed pages are
        shared.
    :param pages: page-pool size; 0 (default) sizes it to
        ``slots * ceil(buffer_len / page_size)`` — every slot can hold
        the longest request the lattice admits. Size it DOWN (or slots
        UP) to bank on real traffic being shorter than worst case: admission
        reserves only each request's own ``ceil((prompt + max_new) /
        page_size)`` pages, so mixed-length traffic packs more live
        slots into the same HBM (docs/source/serving.rst has the
        pages-per-GB formula).
    :param window_pages: size of the WINDOW class of the page pool, for
        a model with window layers (``ModelSpec.layer_pattern``; ``pages``
        then sizes the full class): 0 (default) gives every slot a whole
        ring, ``slots * (window / page_size + 2)``. A slot maps only the
        window-class pages its window still reaches, so real traffic
        needs fewer (docs/source/serving.rst, "Two classes of page").
    :param request_tracing: per-request lifecycle tracing
        (trlx_tpu.serve.trace): every request carries a
        :class:`RequestTrace` with monotonic timestamps at each edge
        (received/enqueued/admitted/prefill/first-token/harvested),
        feeding the ``serve/ttft`` / ``serve/itl`` / ``serve/goodput``
        SLO family, Perfetto per-request tracks, and the opt-in
        ``"trace": true`` response payload. Host-side only.
    :param slo_ttft_ms: the TTFT service-level objective in ms —
        ``serve/goodput`` is the fraction of completed requests whose
        time-to-first-token beat it. 0 counts every request as good.
    :param slo_target: the goodput OBJECTIVE (fraction of requests
        that must meet ``slo_ttft_ms``) the windowed SLO engine scores
        burn rates against: ``slo/burn_rate_fast`` = (1 - goodput_5m)
        / (1 - slo_target), so 1.0 burns the error budget exactly at
        the sustainable rate (docs "Observability", runbook).
    :param flight_recorder_steps: ring size of the slot scheduler's
        per-step flight recorder (step index, lane counts, occupancy,
        pages_free, admissions/evictions, step walltime); dumped on
        watchdog stalls, chaos firings, and poisoned-step resets, and
        served live at ``GET /debug/state``. 0 disables.
    :param max_replays: per-request replay budget for crash-only
        recovery (trlx_tpu.serve.slots): a poisoned step or admission
        re-queues its in-flight requests — committed tokens kept
        host-side, decode resumed suffix-only through the prefix cache —
        up to this many times; past the budget the request fails with a
        typed 503 instead of retrying forever against a deterministic
        fault. 0 disables replay (every poisoned step fails its
        requests, the pre-recovery behavior).
    :param drain_timeout: graceful-drain budget (SIGTERM or
        ``POST /admin/drain``): admission flips to 429+``Retry-After``,
        in-flight and already-queued requests get this many seconds to
        finish, leftovers are shed with a typed 503, telemetry and the
        flight recorder flush, and the process exits 0.
    :param watch_checkpoints: poll interval (seconds) for live
        checkpoint hot-swap — the server watches the serving run dir's
        ``LATEST`` marker and reloads new committed ``step_<N>``
        checkpoints in place (same-sharding weight install, smoke probe,
        rollback on failure, zero recompiles). 0 (default) disables
        polling; ``POST /admin/reload`` works either way.
    :param degrade_step_ms: adaptive-admission step-time threshold — a
        decode step slower than this marks the scheduler degraded, which
        halves the effective queue bound (on top of the always-on
        degradation signals: slot/page starvation). 0 disables the
        step-time signal.
    :param mesh: the serve mesh, ``{axis: size}`` over ``tp`` / ``fsdp``
        (e.g. ``{tp: 4}`` for a v5e-4 slice; CLI ``--mesh tp=2,fsdp=2``).
        Weights shard Megatron-style and KV pages shard on the head
        dimension under ``tp`` (trlx_tpu.serve.layouts); the scheduler,
        radix cache, allocator, and page tables stay host-side and
        mesh-oblivious. None (default) serves from a single-device mesh —
        the identical code path, today's behavior.
    :param mesh_weights: weight placement on the second matrix axis:
        ``"fsdp"`` (default) shards it for capacity — a 6B policy fits a
        small slice; ``"replicated"`` keeps each weight whole per chip —
        no all-gathers on the decode matvec path when HBM affords it
        (docs/source/serving.rst has the sizing formula).
    :param attention: decode attention implementation: ``"jnp"``
        (default) gathers each slot's pages back into logical order in HBM before scoring — the A/B
        oracle and CPU fallback; ``"pallas"`` runs the fused
        paged-attention decode kernel (trlx_tpu.ops.paged_attention):
        page-table walk, gather, and online softmax in one pallas_call,
        no materialized [T, hd] context. Greedy outputs are pinned
        bit-identical between the two at bf16 KV. Off-TPU the kernel
        runs interpreted (tier-1 coverage), so ``jnp`` is the right
        production choice on CPU hosts.
    :param kv_dtype: KV page-pool element tier: ``"bf16"`` (default) or
        ``"int8"`` — symmetric per-(token, kv-head) scales quantized at
        write time and dequantized inside the gather (fused into the
        kernel under ``attention: pallas``). Pages shrink from
        ``2 * head_dim`` to ``head_dim + 4`` bytes per head, so the
        same pool HBM holds ~2x the pages; greedy outputs stay
        parity-tested against one-shot generate() within a logit
        tolerance rather than bit-identical.
    :param weights_dtype: serve-only weight tier applied at the
        strip-at-load seam: ``"bf16"`` (default) installs the
        checkpoint's dtype; ``"int8"`` quantizes the block matmul
        weights (wq/wk/wv/wo/w_in/w_out/w_gate) to int8 codes with
        per-output-channel f32 scales, dequantizing on the fly in the
        matvec (the scale factors out of the contraction). Halves
        resident block weights — the gpt-j-6B headroom knob. Embeddings,
        lm_head, layernorms, and biases stay full precision.
    :param speculation: speculative-decoding tier: ``"off"`` (default)
        decodes one token per step; ``"lookup"`` proposes up to
        ``spec_k`` continuation tokens per slot from a draft-free n-gram
        index over the request's own prompt + committed history (backed
        by the radix cache's committed blocks) and verifies them in one
        batched ``verify_step`` pass; ``"draft"`` proposes with a small
        draft model (``spec_draft_checkpoint``) instead. Greedy
        verification keeps output BIT-IDENTICAL to ``off`` — the knob
        trades nothing but the verify pass's FLOPs. Requires greedy
        decode (``do_sample: false``).
    :param spec_k: proposed tokens verified per slot per speculative
        step (static — one more compiled executable, zero steady-state
        recompiles). 3-8 fits most traces; past the typical acceptance
        run length, extra k only pads the verify pass.
    :param spec_ngram_max: longest history suffix n-gram the lookup
        tier matches on (longer grams propose first — fewer, better
        matches).
    :param spec_draft_checkpoint: draft-model checkpoint directory for
        ``speculation: draft``, restored through the same shard-aware
        partial-restore path as the serving engine.
    :param spec_index_max_keys: per-slot LRU bound on the lookup tier's
        n-gram match keys, so a long-lived slot's host index cannot grow
        unboundedly.
    """

    buckets: List[List[int]] = field(
        default_factory=lambda: [list(b) for b in _DEFAULT_BUCKETS]
    )
    max_queue: int = 256
    request_timeout: float = 120.0
    stall_timeout: float = 0.0
    host: str = "127.0.0.1"
    port: int = 8080
    seed: int = 0
    slots: int = 0
    page_size: int = 64
    pages: int = 0
    window_pages: int = 0
    request_tracing: bool = True
    slo_ttft_ms: float = 500.0
    slo_target: float = 0.99
    flight_recorder_steps: int = 256
    max_replays: int = 2
    drain_timeout: float = 30.0
    watch_checkpoints: float = 0.0
    degrade_step_ms: float = 0.0
    mesh: Optional[Dict[str, int]] = None
    mesh_weights: str = "fsdp"
    attention: str = "jnp"
    kv_dtype: str = "bf16"
    weights_dtype: str = "bf16"
    #: per-tenant quota table, {tenant: {max_inflight, max_queue_share,
    #: rps, burst, priority}} — None/{} disables quota enforcement
    #: entirely (docs "Fault tolerance", overload containment). The
    #: "default" entry also governs tenants the config does not name.
    tenants: Optional[Dict[str, Dict[str, Any]]] = None
    #: brownout degradation: under sustained pressure clamp best-effort
    #: tenants' max_new_tokens to this many (0 = brownout off)
    brownout_max_new: int = 0
    #: pressure must hold this long (s) before brownout engages, and be
    #: absent for brownout_recover_s before it releases — hysteresis so
    #: the mode cannot flap with the step-time signal
    brownout_after_s: float = 2.0
    brownout_recover_s: float = 5.0
    #: every this-many admission rounds a queued request gains one
    #: effective priority level, so a saturating high-priority stream
    #: cannot starve low-priority tenants forever (0 = aging off)
    priority_aging_rounds: int = 64
    #: speculative decoding (docs "Speculative decoding"): proposal
    #: tier + how many tokens one verify pass scores per slot
    speculation: str = "off"
    spec_k: int = 4
    spec_ngram_max: int = 3
    spec_draft_checkpoint: Optional[str] = None
    spec_index_max_keys: int = 512

    @classmethod
    def from_dict(cls, config: Optional[Dict[str, Any]]) -> "ServeConfig":
        """Unknown keys are dropped, as every config section drops them
        (a file may be older or newer than the code) — except a retired
        setting that asks for a path that is gone
        (:func:`refuse_retired`)."""
        config = config or {}
        refuse_retired(config)
        return cls(**filter_known_fields(cls, config))


#: settings that once chose between serve paths: the value that named
#: the surviving path, and what that path is. Config files and
#: checkpoints written before PR 31 carry these keys; the surviving
#: value loads (and is dropped like any legacy key).
RETIRED_SETTINGS = {
    "scheduler": ("slots", "every request is served by the continuous-"
                  "batching slot scheduler (trlx_tpu.serve.slots)"),
    "kv_layout": ("paged", "the KV pool is always fixed-size pages "
                  "behind per-slot page tables (serve.page_size, "
                  "serve.pages)"),
}


def refuse_retired(section: Dict[str, Any]) -> None:
    """Refuse, by name, a ``serve:`` section whose retired setting asks
    for a removed path: dropped in silence, the file's traffic would be
    served another way than the file says."""
    for key, (kept, now) in RETIRED_SETTINGS.items():
        if key in section and section[key] != kept:
            raise ValueError(
                f"serve.{key}: {section[key]!r} was removed in PR 31; "
                f"{now}. Drop the key (or leave it at {kept!r})."
            )


#: block matmul leaves serve.weights_dtype: int8 quantizes — the stacked
#: [L, in, out] matrices; biases/layernorms/embeddings stay full precision
_QUANT_WEIGHT_LEAVES = ("wq", "wk", "wv", "wo", "w_in", "w_out", "w_gate")


def quantize_serve_weights(blocks):
    """Serve-only int8 weight views: each stacked block matrix
    [L, in, out] becomes a ``(codes int8, scale f32 [L, 1, out])`` pair
    — symmetric per-output-channel quantization, consumed on the fly by
    ``transformer._project`` (the scale factors out of the contraction,
    so no bf16 weight copy ever materializes). Applied at the
    strip-at-load seam, AFTER restore and BEFORE mesh placement, by both
    :meth:`InferenceEngine._install_params` and
    :meth:`InferenceEngine.strip_for_serve` so hot-swap candidates match
    the serving tree leaf-for-leaf."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.parallel.sharding import _path_names

    def leaf(kp, x):
        names = _path_names(kp)
        name = names[-1] if names else ""
        if name not in _QUANT_WEIGHT_LEAVES or getattr(x, "ndim", 0) != 3:
            return x
        x32 = x.astype(jnp.float32)
        scale = (
            jnp.max(jnp.abs(x32), axis=1, keepdims=True) / 127.0 + 1e-8
        )
        codes = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(
            jnp.int8
        )
        return codes, scale

    # is_leaf guard: already-quantized trees pass through untouched
    return jax.tree_util.tree_map_with_path(
        leaf, blocks,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and all(hasattr(m, "ndim") for m in x),
    )


def _normalize_buckets(buckets) -> Tuple[Bucket, ...]:
    out = []
    for b in buckets:
        t = tuple(int(x) for x in b)
        if len(t) != 3 or any(x <= 0 for x in t):
            raise ValueError(
                f"serve bucket {b!r} is not a positive "
                f"(batch, prompt_len, gen_len) triple"
            )
        out.append(t)
    if not out:
        raise ValueError("serve.buckets must name at least one bucket")
    # sort by shape class then batch: pick_bucket scans smallest-first
    return tuple(sorted(set(out), key=lambda t: (t[1], t[2], t[0])))


def _split_layers(seg, release: bool):
    """A stacked [n, ...] block segment as n per-layer [1, ...] trees (a
    segment of one layer is returned as it is). ``release``: delete each
    stacked leaf once its slices are made."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(seg)
    n = leaves[0].shape[0] if leaves else 0
    if n <= 1:
        return (seg,) * n
    layers = [[] for _ in range(n)]
    for x in leaves:
        parts = [x[i:i + 1] for i in range(n)]
        if release:
            jax.block_until_ready(parts)
            x.delete()
        for layer, part in zip(layers, parts):
            layer.append(part)
    return tuple(treedef.unflatten(layer) for layer in layers)


class InferenceEngine:
    """A restored policy + its precompiled decode bucket lattice.

    Thread-safety: :meth:`decode` serializes dispatches under a lock —
    one device program runs at a time (the lock makes direct
    multi-threaded use safe rather than fast).
    """

    def __init__(self, config: TRLConfig, serve: Optional[ServeConfig] = None,
                 params: Optional[Dict] = None, init: bool = True):
        """Build from an in-memory param tree (``params``) — the
        checkpoint path is :meth:`from_checkpoint`. ``params`` defaults
        to a fresh policy init (useful only for tests/dev); ``init=False``
        defers weight installation entirely (the checkpoint path installs
        the restored tree instead of paying a throwaway random init).
        ``params`` may also be a zero-argument maker of the tree: the
        engine then owns what it makes, as it owns a restored or a fresh
        tree, and may release it piece by piece while it strips it (a
        model with routed experts: :meth:`strip_for_serve`)."""
        import jax.numpy as jnp

        from trlx_tpu import telemetry
        from trlx_tpu.data.method_configs import PPOConfig
        from trlx_tpu.models.generation import GenerationConfig
        from trlx_tpu.models.policy import HydraPolicy
        from trlx_tpu.ops.sampling import SamplingParams
        from trlx_tpu.utils.tokenizer import load_tokenizer

        if not isinstance(config.method, PPOConfig):
            raise NotImplementedError(
                f"the inference engine serves hydra (PPO) policies; this "
                f"config's method is '{config.method.name}'. ILQL "
                f"checkpoints carry Q/V heads and a different param "
                f"layout — serve support for them is a separate policy "
                f"adapter."
            )
        # a serve process owns a telemetry session even without a trainer
        # (/metrics reads the active session's summary); a session an
        # embedding trainer already started is reused, not clobbered
        if telemetry.current() is None:
            telemetry.start()
        self.config = config
        self.serve = serve or ServeConfig()
        if self.serve.slots < 0:
            raise ValueError(
                f"serve.slots={self.serve.slots} must be >= 0 (0 = auto)"
            )
        if self.serve.page_size < 1:
            raise ValueError(
                f"serve.page_size={self.serve.page_size} must be >= 1"
            )
        if self.serve.pages < 0 or self.serve.window_pages < 0:
            raise ValueError(
                f"serve.pages={self.serve.pages} and serve.window_pages="
                f"{self.serve.window_pages} must be >= 0 (0 = auto)"
            )
        if self.serve.slo_ttft_ms < 0:
            raise ValueError(
                f"serve.slo_ttft_ms={self.serve.slo_ttft_ms} must be >= 0 "
                f"(0 = every completed request counts toward goodput)"
            )
        if not 0.0 <= self.serve.slo_target < 1.0:
            raise ValueError(
                f"serve.slo_target={self.serve.slo_target} must be in "
                f"[0, 1) — 1.0 leaves no error budget to burn"
            )
        if self.serve.flight_recorder_steps < 0:
            raise ValueError(
                f"serve.flight_recorder_steps="
                f"{self.serve.flight_recorder_steps} must be >= 0 "
                f"(0 = disabled)"
            )
        if self.serve.max_replays < 0:
            raise ValueError(
                f"serve.max_replays={self.serve.max_replays} must be >= 0 "
                f"(0 = a poisoned step fails its requests, no replay)"
            )
        if self.serve.drain_timeout <= 0:
            raise ValueError(
                f"serve.drain_timeout={self.serve.drain_timeout} must be "
                f"> 0 (a drain with no budget is just SIGKILL)"
            )
        if self.serve.watch_checkpoints < 0:
            raise ValueError(
                f"serve.watch_checkpoints={self.serve.watch_checkpoints} "
                f"must be >= 0 (0 = no polling; POST /admin/reload only)"
            )
        if self.serve.degrade_step_ms < 0:
            raise ValueError(
                f"serve.degrade_step_ms={self.serve.degrade_step_ms} "
                f"must be >= 0 (0 = step-time degradation signal off)"
            )
        if self.serve.brownout_max_new < 0:
            raise ValueError(
                f"serve.brownout_max_new={self.serve.brownout_max_new} "
                f"must be >= 0 (0 = brownout degradation off)"
            )
        if self.serve.brownout_after_s <= 0:
            raise ValueError(
                f"serve.brownout_after_s={self.serve.brownout_after_s} "
                f"must be > 0 (pressure debounce before brownout)"
            )
        if self.serve.brownout_recover_s <= 0:
            raise ValueError(
                f"serve.brownout_recover_s="
                f"{self.serve.brownout_recover_s} must be > 0 "
                f"(hysteresis: calm time required before recovery)"
            )
        if self.serve.priority_aging_rounds < 0:
            raise ValueError(
                f"serve.priority_aging_rounds="
                f"{self.serve.priority_aging_rounds} must be >= 0 "
                f"(0 = priority aging off)"
            )
        if self.serve.tenants is not None:
            # parse eagerly so a bad tenants block fails at boot with a
            # config-shaped error, not at first admission
            from trlx_tpu.serve.admission import TenantTable

            TenantTable(self.serve.tenants, self.serve.max_queue)
        if self.serve.mesh_weights not in ("fsdp", "replicated"):
            raise ValueError(
                f"serve.mesh_weights '{self.serve.mesh_weights}' is not "
                f"one of: fsdp, replicated"
            )
        if self.serve.attention not in ("jnp", "pallas"):
            raise ValueError(
                f"serve.attention '{self.serve.attention}' is not one "
                f"of: jnp, pallas"
            )
        if self.serve.kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"serve.kv_dtype '{self.serve.kv_dtype}' is not one of: "
                f"bf16, int8"
            )
        if self.serve.weights_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"serve.weights_dtype '{self.serve.weights_dtype}' is "
                f"not one of: bf16, int8"
            )
        if self.serve.speculation not in ("off", "lookup", "draft"):
            raise ValueError(
                f"serve.speculation '{self.serve.speculation}' is not "
                f"one of: off, lookup, draft"
            )
        if self.serve.spec_k < 1:
            raise ValueError(
                f"serve.spec_k={self.serve.spec_k} must be >= 1 "
                f"(disable speculation with serve.speculation: off)"
            )
        if self.serve.spec_ngram_max < 1:
            raise ValueError(
                f"serve.spec_ngram_max={self.serve.spec_ngram_max} "
                f"must be >= 1"
            )
        if self.serve.spec_index_max_keys < 1:
            raise ValueError(
                f"serve.spec_index_max_keys="
                f"{self.serve.spec_index_max_keys} must be >= 1 "
                f"(the per-slot n-gram index needs at least one key)"
            )
        if (self.serve.speculation == "draft"
                and not self.serve.spec_draft_checkpoint):
            raise ValueError(
                "serve.speculation 'draft' needs "
                "serve.spec_draft_checkpoint (the draft model to "
                "propose with) — or use speculation: lookup"
            )
        from trlx_tpu.serve.layouts import build_serve_mesh

        #: the serve mesh every executable compiles against — a
        #: single-device mesh when serve.mesh is unset (same code path,
        #: today's placement), a {tp, fsdp} slice otherwise
        self.mesh = build_serve_mesh(self.serve.mesh)
        self.buckets = _normalize_buckets(self.serve.buckets)
        self.tokenizer = load_tokenizer(config.model.tokenizer_path)

        spec, trunk = self._resolve_spec_and_trunk(config)
        from trlx_tpu.models.transformer import require_supported

        require_supported(
            spec, kv_dtype=self.serve.kv_dtype,
            weights_dtype=self.serve.weights_dtype,
            speculation=self.serve.speculation, mesh=self.serve.mesh,
        )
        for b, p, g in self.buckets:
            if p + g > spec.n_positions:
                raise ValueError(
                    f"serve bucket (batch={b}, prompt={p}, gen={g}) needs "
                    f"{p + g} positions but the model has n_positions="
                    f"{spec.n_positions}"
                )
        self.spec = spec
        self._compute_dtype = {"float32": jnp.float32,
                               "bfloat16": jnp.bfloat16,
                               "float16": jnp.float16}[
                                   config.model.compute_dtype]
        self.policy = HydraPolicy(
            spec=spec,
            num_layers_unfrozen=config.model.num_layers_unfrozen,
            compute_dtype=self._compute_dtype,
        )
        self._trunk = trunk
        self.blocks = self.embed = self.ln_f = None
        #: monotonically-increasing weight generation: 1 at construction,
        #: bumped by commit_version() on each successful hot-swap; stamped
        #: into every request at admission (``serve/model_version`` gauge)
        self.model_version = 1
        self.checkpoint_path: Optional[str] = None
        if callable(params):
            self._install_params(params(), owned=True)
        elif params is not None:
            self._install_params(params)
        elif init:
            self._install_params(self._init_params(), owned=True)

        eos = getattr(self.tokenizer, "eos_token_id", -1)
        pad = getattr(self.tokenizer, "pad_token_id", 0) or 0
        gk = dict(config.method.gen_kwargs or {})
        # serving semantics: stop at eos (min_new_tokens=0) — unlike the
        # trainers' fixed-length translation of min_length==max_length
        self._gen_base = GenerationConfig(
            gen_size=1,  # per-bucket _replace below
            sampling=SamplingParams(
                temperature=float(gk.get("temperature", 1.0)),
                top_k=int(gk.get("top_k", 0) or 0),
                top_p=float(gk.get("top_p", 1.0)),
                do_sample=bool(gk.get("do_sample", True)),
            ),
            eos_token_id=eos if eos is not None else -1,
            pad_token_id=pad,
            min_new_tokens=0,
        )
        if self.serve.speculation != "off" \
                and self._gen_base.sampling.do_sample:
            raise ValueError(
                "serve.speculation requires greedy decode "
                "(gen_kwargs do_sample: false): acceptance compares "
                "proposals against the argmax stream — under sampling "
                "the verified output would not match plain decode"
            )
        self.pad_token_id = pad
        import threading

        self._decode_fns = {}  # bucket -> aot_jit'd generate closure
        # eager, not lazy: a first-use `if lock is None` check is itself
        # a race — two first-callers each build a Lock and hold
        # different ones (graftlint: lazy-lock)
        self._lock = threading.Lock()

    # -- construction --------------------------------------------------- #

    @staticmethod
    def _resolve_spec_and_trunk(config: TRLConfig):
        """(spec, pretrained trunk | None) — mirrors the trainers'
        `_load_or_spec`: an explicit model_spec wins (offline-safe);
        otherwise the HF import supplies both spec and init weights
        (which the checkpoint restore then overwrites)."""
        if config.model.model_spec is not None:
            return config.model.resolve_spec(), None
        from trlx_tpu.models.hf_import import load_trunk_from_hf

        try:
            spec, embed, blocks, ln_f = load_trunk_from_hf(
                config.model.model_path
            )
        except Exception as e:
            raise RuntimeError(
                f"could not resolve the model architecture for serving: "
                f"pretrained load of '{config.model.model_path}' failed "
                f"({e!r}) and the config has no model.model_spec. Serve "
                f"from a config whose model section matches the "
                f"checkpoint's (the checkpoint's own meta.json 'config' "
                f"component has it for checkpoints saved by this "
                f"framework)."
            ) from e
        return spec, (embed, blocks, ln_f)

    @classmethod
    def from_checkpoint(cls, checkpoint: str, config=None,
                        serve: Optional[ServeConfig] = None,
                        ) -> "InferenceEngine":
        """Load a policy from ``checkpoint`` (a committed checkpoint dir,
        or a run dir whose newest valid ``step_<N>`` is used).

        ``config`` may be a TRLConfig, a YAML path, or None — None reads
        the ``config`` component the trainers embed in the checkpoint's
        meta.json, so ``python -m trlx_tpu.serve --checkpoint <dir>``
        needs nothing else. Only the ``params`` component is restored;
        opt_state/ref/value-head training baggage is stripped (module
        docstring).

        Boot is integrity-gated: the candidate checkpoint's bytes are
        verified against its manifest first, and when ``checkpoint`` is
        a RUN dir a corrupt newest step is quarantined and boot falls
        back to the previous good one (``CheckpointCorrupt`` only
        surfaces when the caller pointed at a corrupt checkpoint
        directly — there is nothing behind it to boot from)."""
        import json
        import os

        from trlx_tpu.utils.checkpoint import (
            META_NAME,
            CheckpointCorrupt,
            find_latest_checkpoint,
            is_valid_checkpoint,
            verify_or_quarantine,
        )

        while True:
            resolved = checkpoint if is_valid_checkpoint(checkpoint) \
                else find_latest_checkpoint(checkpoint)
            if resolved is None:
                raise FileNotFoundError(
                    f"no committed checkpoint at '{checkpoint}' (expected "
                    f"a checkpoint dir with '{META_NAME}', or a run dir "
                    f"of 'step_<N>' checkpoints)"
                )
            try:
                verify_or_quarantine(resolved, component="params")
                break
            except CheckpointCorrupt:
                if is_valid_checkpoint(checkpoint):
                    raise  # pointed at the corrupt checkpoint itself
                print(
                    f"[trlx_tpu.serve] boot falling back past corrupt "
                    f"checkpoint '{resolved}' under '{checkpoint}'",
                    flush=True,
                )
        if config is None:
            with open(os.path.join(resolved, META_NAME)) as f:
                meta = json.load(f)
            if "config" not in meta:
                raise ValueError(
                    f"checkpoint '{resolved}' carries no embedded config "
                    f"(saved by a pre-serving version?); pass the training "
                    f"config explicitly (--config <yml> on the CLI)."
                )
            config = TRLConfig.from_dict(meta["config"])
        elif isinstance(config, str):
            config = TRLConfig.load_yaml(config)

        engine = cls(config, serve=serve, init=False)
        # streaming partial restore: decode subset only, per-leaf onto
        # the live serve shardings (load_params docstring)
        params, _ = engine.load_params(resolved)
        engine._install_params(params, owned=True)
        engine.checkpoint_path = resolved
        return engine

    def _init_params(self) -> Dict:
        """A full-structure hydra param tree — the checkpoint-restore
        template (and the dev-mode weights). Transient by design: the
        engine never retains it; only the decode views survive."""
        import jax

        if self._trunk is not None:
            from trlx_tpu.models.hf_import import hydra_params_from_trunk

            return hydra_params_from_trunk(
                self.policy, *self._trunk, jax.random.PRNGKey(0)
            )
        return self.policy.init(jax.random.PRNGKey(0))

    def _install_params(self, params: Dict, owned: bool = False) -> None:
        """Keep only what decode reads: (trunk, trainable-top) block
        segments, embed (+lm_head), ln_f. The full tree is NOT retained —
        once the caller's reference drops, the reference branch and the
        value head are garbage (opt_state was never restored at all), so
        steady-state memory holds one serving policy, not the training
        triple. The views land on the serve mesh under the decode
        partition rules (trlx_tpu.serve.layouts) — on the default
        single-device mesh that is plain device placement. ``owned``: no
        caller keeps ``params`` (see :meth:`strip_for_serve`)."""
        from trlx_tpu import telemetry
        from trlx_tpu.serve import layouts
        from trlx_tpu.utils import tree_bytes

        total = tree_bytes(params)  # before the strip may release leaves
        blocks, embed, ln_f = self.strip_for_serve(params, owned)
        self.blocks, self.embed, self.ln_f = layouts.shard_decode_views(
            self.mesh, (blocks, embed, ln_f),
            weights=self.serve.mesh_weights,
        )
        kept = tree_bytes((self.blocks, self.embed, self.ln_f))
        telemetry.set_gauge("serve/model_gb", kept / 2**30)
        telemetry.set_gauge(
            "serve/stripped_gb", max(total - kept, 0) / 2**30
        )
        telemetry.set_gauge("serve/mesh_devices", self.mesh.size)
        telemetry.set_gauge(
            "serve/params_gb_per_device",
            layouts.tree_bytes_per_device(
                (self.blocks, self.embed, self.ln_f)
            ) / 2**30,
        )
        self._decode_fns = {}  # shapes unchanged but weights swapped

    def mesh_info(self) -> Dict[str, Any]:
        """The serve-mesh block /healthz and /debug/state report: axis
        names/sizes, device count, weight placement, per-device params
        GB (the thing capacity planning actually sizes against)."""
        from trlx_tpu.serve import layouts

        info = layouts.mesh_info(self.mesh, self.serve.mesh_weights)
        if self.blocks is not None:
            per_dev = layouts.tree_bytes_per_device(
                (self.blocks, self.embed, self.ln_f)
            )
            info["params_gb_per_device"] = round(per_dev / 2**30, 6)
        return info

    # -- live hot-swap (crash-only serving; docs "Fault tolerance") ------- #

    def strip_for_serve(self, params: Dict, owned: bool = False):
        """Reduce a full hydra tree to the decode views — the hot-swap
        analogue of :meth:`_install_params`'s strip, WITHOUT installing:
        the candidate weights must pass :meth:`validate_swap` and a smoke
        probe before they replace the serving set.

        A model with routed experts is kept as per-layer leaves: a
        layer's expert stack goes to the grouped product (a custom call)
        as a whole buffer; sliced out of a stacked [L, ...] tree it would
        be copied every step. The split is made once, here, and copies
        every segment of more than one layer. ``owned`` says no caller
        keeps ``params``: each stacked leaf is then deleted as soon as
        its per-layer copies exist, so the transient is one leaf and not
        a second trunk."""
        blocks = self.policy.all_blocks(params)
        embed, ln_f = self.policy.head_params_for_decode(params)
        if self.serve.weights_dtype == "int8":
            blocks = quantize_serve_weights(blocks)
        if self.spec.n_experts:
            blocks = tuple(
                layer for seg in blocks for layer in _split_layers(seg, owned)
            )
        return blocks, embed, ln_f

    def validate_swap(self, views) -> None:
        """Reject architecture drift BEFORE touching the serving weights:
        a hot-swap candidate must match the installed views leaf-for-leaf
        in structure, shape, and dtype — anything else would invalidate
        the compiled executables (the ``compile/recompiles == 0``
        invariant) and needs a restart, not a reload."""
        import jax

        old = (self.blocks, self.embed, self.ln_f)
        old_struct = jax.tree_util.tree_structure(old)
        new_struct = jax.tree_util.tree_structure(views)
        if old_struct != new_struct:
            raise ValueError(
                "hot-swap rejected: candidate param tree structure does "
                "not match the serving policy (architecture drift — e.g. "
                "a different model or num_layers_unfrozen). Restart the "
                "endpoint against the new checkpoint instead."
            )
        for o, n in zip(jax.tree_util.tree_leaves(old),
                        jax.tree_util.tree_leaves(views)):
            if o.shape != n.shape or o.dtype != n.dtype:
                raise ValueError(
                    f"hot-swap rejected: candidate leaf {n.shape}/"
                    f"{n.dtype} does not match serving leaf {o.shape}/"
                    f"{o.dtype} — shape/dtype drift would force a "
                    f"recompile; restart the endpoint instead."
                )

    def install_views(self, views) -> None:
        """Install pre-stripped (blocks, embed, ln_f) decode views
        WITHOUT resetting the compiled executables — the hot-swap path.
        Each new leaf is placed with the OLD leaf's sharding
        (``jax.device_put`` onto the same layout, after which the old
        buffers are unreferenced and freed), so the swap never changes
        what the AOT executables were compiled against; the compiled fns
        take the views as arguments, not captures, so new values flow
        through with zero recompiles. Callers must have run
        :meth:`validate_swap` first."""
        import jax

        def put(new, old):
            try:
                return jax.device_put(new, old.sharding)
            except (AttributeError, ValueError):
                return new  # host array / shardless leaf: use as-is

        blocks, embed, ln_f = views
        self.blocks = jax.tree_util.tree_map(put, blocks, self.blocks)
        self.embed = jax.tree_util.tree_map(put, embed, self.embed)
        self.ln_f = jax.tree_util.tree_map(put, ln_f, self.ln_f)

    def commit_version(self, checkpoint: Optional[str] = None) -> int:
        """Bump the model version AFTER a successful swap+probe (the
        scheduler calls this at its step boundary); a rolled-back swap
        never commits, so the gauge always names the weights actually
        serving."""
        from trlx_tpu import telemetry

        self.model_version += 1
        if checkpoint:
            self.checkpoint_path = checkpoint
        telemetry.set_gauge("serve/model_version", self.model_version)
        return self.model_version

    def _serve_restore_template(self) -> Dict:
        """ShapeDtypeStruct tree of the decode SUBSET of the ``params``
        component: frozen trunk + trainable blocks/ln_f (+ lm_head when
        untied). The reference branch and value head are absent, so a
        partial restore never reads — let alone stages — them. Built
        abstractly (``jax.eval_shape``): no throwaway init is ever
        materialized."""
        import jax

        def abstract_init(rng):
            if self._trunk is not None:
                from trlx_tpu.models.hf_import import (
                    hydra_params_from_trunk,
                )

                return hydra_params_from_trunk(
                    self.policy, *self._trunk, rng
                )
            return self.policy.init(rng)

        full = jax.eval_shape(abstract_init, jax.random.PRNGKey(0))
        trainable = {
            k: v for k, v in full["trainable"].items() if k != "v_head"
        }
        return {"frozen_base": full["frozen_base"],
                "trainable": trainable}

    def load_params(self, checkpoint: str):
        """Restore the decode subset of a checkpoint for install or
        hot-swap: (partial params tree, resolved checkpoint dir).
        ``checkpoint`` may be a committed checkpoint dir or a run dir
        (the newest valid ``step_<N>`` is used).

        Leaves stream from disk one at a time, each landing directly on
        its live serve sharding (restore_component_sharded) — peak host
        staging during a reload is ~one leaf, not one model, and the
        training-only subtrees (reference branch, value head, opt state)
        never leave disk. The returned tree is exactly what
        :meth:`strip_for_serve` / :meth:`_install_params` read.

        The resolved checkpoint's ``params`` bytes are manifest-verified
        before a single leaf lands on device; corruption quarantines the
        step dir and raises ``CheckpointCorrupt`` — for the hot-swap
        path that is deliberately FAIL-FAST (no silent fallback: the old
        weights are still serving, and ``/admin/reload`` answering 409
        is what makes a fleet rollout abort on the old version instead
        of "succeeding" onto the step it already runs)."""
        from trlx_tpu.serve import layouts
        from trlx_tpu.utils.checkpoint import (
            find_latest_checkpoint,
            is_valid_checkpoint,
            restore_component_sharded,
        )

        resolved = checkpoint if is_valid_checkpoint(checkpoint) \
            else find_latest_checkpoint(checkpoint)
        if resolved is None:
            raise FileNotFoundError(
                f"no committed checkpoint at '{checkpoint}' to reload "
                f"from (expected a checkpoint dir or a run dir of "
                f"'step_<N>' checkpoints)"
            )
        template = self._serve_restore_template()
        shardings = layouts.decode_param_shardings(
            self.mesh, template, weights=self.serve.mesh_weights
        )
        params = restore_component_sharded(
            "params", template, shardings, resolved
        )
        return params, resolved

    # -- bucket lattice -------------------------------------------------- #

    def shape_classes(self) -> Tuple[Tuple[int, int], ...]:
        """Distinct (prompt_len, gen_len) classes, smallest first."""
        seen = []
        for _, p, g in self.buckets:
            if (p, g) not in seen:
                seen.append((p, g))
        return tuple(seen)

    def pick_shape(self, prompt_len: int,
                   max_new_tokens: int) -> Tuple[int, int]:
        """Smallest (prompt_len, gen_len) shape class fitting the
        request — the bucket-rounding rule. Raises ValueError (HTTP 400)
        when nothing fits."""
        for p, g in self.shape_classes():
            if prompt_len <= p and max_new_tokens <= g:
                return (p, g)
        raise ValueError(
            f"request (prompt_len={prompt_len}, max_new_tokens="
            f"{max_new_tokens}) fits no serve bucket; lattice shape "
            f"classes (prompt, gen): {list(self.shape_classes())}"
        )

    def max_new_tokens_cap(self) -> int:
        return max(g for _, _, g in self.buckets)

    def default_max_new_tokens(self) -> int:
        return min(g for _, _, g in self.buckets)

    # -- slot-scheduler lattice (trlx_tpu.serve.slots) -------------------- #

    def prompt_classes(self) -> Tuple[Tuple[int, Tuple[int, ...]], ...]:
        """Distinct ONE-SHOT prompt lengths with their admission batch
        extents, smallest prompt first — the slot scheduler's prefill
        lattice, one program each (prefill shape is (batch, prompt_len);
        the gen extent lives in per-slot ``max_new`` lanes, not in the
        compiled shape). A class served in chunks (:meth:`chunk_len`) has
        no program of its own and is not listed."""
        classes = {}
        for b, p, _ in self.buckets:
            classes.setdefault(p, set()).add(b)
        chunked = self._chunked_from()
        return tuple(
            (p, tuple(sorted(classes[p]))) for p in sorted(classes)
            if chunked is None or p < chunked
        )

    def _chunked_from(self) -> Optional[int]:
        """THE CHUNK RULE (docs/source/serving.rst): a prompt class more
        than four times as long as the next shorter class of the
        lattice, and every class after it, is prefilled in chunks of
        that shorter class's length through its prefix-context program.
        None: every class is one-shot."""
        lengths = sorted({p for _, p, _ in self.buckets})
        for shorter, longer in zip(lengths, lengths[1:]):
            if longer > 4 * shorter:
                return longer
        return None

    def chunk_len(self, prompt_len: int) -> int:
        """The chunk a prompt class is prefilled in: 0 for a one-shot
        class, else the length of the longest one-shot class."""
        chunked = self._chunked_from()
        if chunked is None or prompt_len < chunked:
            return 0
        return self.prompt_classes()[-1][0]

    def prefill_batch_sizes(self, prompt_len: int) -> Tuple[int, ...]:
        """Ascending admission batch extents for one prompt class (a
        class served in chunks is admitted one request at a time)."""
        if self.chunk_len(prompt_len):
            return (1,)
        for p, extents in self.prompt_classes():
            if p == prompt_len:
                return extents
        raise ValueError(
            f"prompt_len {prompt_len} is not a compiled prompt class "
            f"(have {[p for p, _ in self.prompt_classes()]})"
        )

    def slot_count(self) -> int:
        """Slot-pool size: ``serve.slots``, or the largest compiled batch
        extent when 0 (one admission batch of the widest bucket fills
        the pool)."""
        return self.serve.slots or max(b for b, _, _ in self.buckets)

    def slot_buffer_len(self) -> int:
        """Per-slot KV buffer length: the largest prompt+gen extent any
        bucket needs (bucket validation already pinned it under
        n_positions)."""
        return max(p + g for _, p, g in self.buckets)

    # -- page-pool lattice ------------------------------------------------ #

    def page_size_tokens(self) -> int:
        """Effective KV page size: ``serve.page_size`` clamped to the
        slot buffer length (a page longer than the longest request
        holds nothing a shorter one would not)."""
        return min(self.serve.page_size, self.slot_buffer_len())

    def pages_per_slot(self) -> int:
        """Page-table width: pages covering one slot's full extent."""
        ps = self.page_size_tokens()
        return -(-self.slot_buffer_len() // ps)

    def page_count(self) -> int:
        """Page-pool size: ``serve.pages``, or slots x pages-per-slot when
        0 (every slot can hold the longest request the lattice admits)."""
        return self.serve.pages or self.slot_count() * self.pages_per_slot()

    # -- the window class (a model with window layers) -------------------- #

    def window_ring_pages(self) -> int:
        """Width of a slot's window-class ring at decode: the pages a
        window reaches, the one being written, and one to spare —
        ``window / page_size + 2``. 0 for a model without window layers."""
        if "window" not in self.spec.page_classes:
            return 0
        return -(-self.spec.window // self.page_size_tokens()) + 2

    def window_page_count(self) -> int:
        """Size of the window class: ``serve.window_pages``, or a whole
        ring for every slot when 0."""
        return self.serve.window_pages or (
            self.slot_count() * self.window_ring_pages()
        )

    def request_page_need(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case pages one request reserves at admission (prefix
        hits only reduce it)."""
        ps = self.page_size_tokens()
        return -(-(prompt_len + max_new_tokens) // ps)

    # -- decode ---------------------------------------------------------- #

    def _decode_fn(self, bucket: Bucket):
        """The bucket's compiled generate closure — one ``aot_jit``
        instance PER bucket so each owns its executable cache: warming a
        new bucket is a first compile, never a steady-state miss, and any
        later ``compile/recompiles`` increment is a real drift signal."""
        fn = self._decode_fns.get(bucket)
        if fn is None:
            from trlx_tpu.models.generation import decide_unroll, generate
            from trlx_tpu.utils.aotjit import aot_jit

            B, P, G = bucket
            cfg = self._gen_base._replace(gen_size=G)
            spec = self.spec
            compute = self._compute_dtype
            unroll = decide_unroll(spec, self.blocks, B, P + G)

            def run(blocks, embed, ln_f, tokens, mask, rng):
                return generate(
                    spec, blocks, embed, ln_f, tokens, mask, rng, cfg,
                    compute_dtype=compute, unroll_layers=unroll,
                )

            # the program's name in a device trace, as its span's
            run.__name__ = f"run_generate_b{B}p{P}g{G}"
            fn = self._decode_fns[bucket] = aot_jit(run)
        return fn

    def span_name(self, bucket: Bucket) -> str:
        B, P, G = bucket
        return f"serve/decode_b{B}p{P}g{G}"

    def decode(self, bucket: Bucket, tokens: np.ndarray, mask: np.ndarray,
               seed: int = 0):
        """THE ONE-SHOT ORACLE: ``generate()`` over one bucket-shaped
        batch, decoded to completion. It serves no traffic — the slot
        scheduler does, through its own prefill/step programs — and is
        kept because the tests compare the scheduler's tokens against
        it. tokens/mask are left-padded ``[B, P]`` int32; returns the
        GenerationOutput as host numpy (blocking)."""
        import jax

        from trlx_tpu import telemetry

        B, P, G = bucket
        if tokens.shape != (B, P):
            raise ValueError(
                f"decode batch shape {tokens.shape} does not match "
                f"bucket (batch={B}, prompt={P})"
            )
        fn = self._decode_fn(bucket)
        rng = jax.random.PRNGKey(seed)
        with self._lock, telemetry.span(self.span_name(bucket)):
            out = fn(
                self.blocks, self.embed, self.ln_f,
                np.ascontiguousarray(tokens, np.int32),
                np.ascontiguousarray(mask, np.int32), rng,
            )
            out = jax.device_get(out)
        return out

    # -- request shaping -------------------------------------------------- #

    def encode_prompt(self, prompt: str) -> List[int]:
        ids = self.tokenizer.encode(prompt)
        # HF fast tokenizers return lists; keep plain ints either way
        return [int(t) for t in ids]

    def pad_batch(self, rows: Sequence[Sequence[int]], bucket: Bucket
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Left-pad token rows into the bucket's [B, P] shape; rows short
        of B are filled by repeating the first row (the filler decodes
        garbage that is simply never read back)."""
        B, P, _ = bucket
        if len(rows) > B or not rows:
            raise ValueError(f"{len(rows)} rows for a batch-{B} bucket")
        tokens = np.full((B, P), self.pad_token_id, np.int32)
        mask = np.zeros((B, P), np.int32)
        for i in range(B):
            row = rows[i] if i < len(rows) else rows[0]
            row = list(row)[-P:]
            tokens[i, P - len(row):] = row
            mask[i, P - len(row):] = 1
        return tokens, mask

    def depad_row(self, out, row: int, max_new_tokens: int) -> List[int]:
        """One request's completion from a batched GenerationOutput:
        the row's generated tokens, truncated to its own max_new_tokens,
        cut where gen_mask ends (eos included, pads after excluded)."""
        gen = np.asarray(out.gen_tokens[row])[:max_new_tokens]
        gmask = np.asarray(out.gen_mask[row])[:max_new_tokens]
        return [int(t) for t, m in zip(gen, gmask) if m]
