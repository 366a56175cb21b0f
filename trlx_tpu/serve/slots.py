"""Continuous-batching slot scheduler: iteration-level serving decode.

Batching *request-to-completion* (``InferenceEngine.decode``, the
oracle) decodes all of a bucket's ``gen_size`` steps before the next
batch starts: short requests wait behind long ones, and filler rows
decode at full cost. This module schedules at the *step* level instead
(Orca, Yu et al., OSDI '22), over a persistent
device-resident KV **slot pool** (the static-shape analogue of vLLM's
paged KV blocks, Kwon et al., SOSP '23):

- :class:`SlotPoolRuntime` owns the pool + per-slot lanes and the
  AOT-compiled device primitives (trlx_tpu.models.generation):
  ``prefill_into_slots`` — two executables per (batch, prompt_len)
  admission bucket (plain + the ``prefill_suffix`` prefix-context
  variant) — and ``decode_step`` —
  ONE executable for all slots. The pool is per-layer leaves, each
  donated on accelerators and written by one scatter, so a step
  updates it in place (``serve/decode_alias_bytes``); warmup runs every
  prefill bucket against the live pool with out-of-bounds sentinel slot
  ids (scatters ``mode="drop"`` — compiles the shape, touches nothing),
  then one decode step. Steady state is first-compiles only:
  ``compile/recompiles == 0`` stays the serving invariant.
- The pool is block-granular: fixed-size KV pages shared by all slots,
  addressed
  through per-slot page tables, with a host free-list allocator and a
  radix-tree prefix cache (trlx_tpu.serve.paged) — admission reserves
  ``ceil((prompt + max_new) / page_size)`` pages instead of the
  worst-case buffer, prompts sharing committed prefixes skip
  re-prefilling them, and page exhaustion QUEUES requests (never
  fails).
- :class:`SlotScheduler` runs the host loop: at every step boundary it
  **harvests** finished rows (EOS, or the request's own
  ``max_new_tokens`` — not the bucket's gen extent), frees their slots
  (and pages) immediately, and **admits** queued requests into free
  slots via bucketed prefill. Short requests no longer wait for long
  ones; filler rows become free slots; steady-state **slot occupancy**
  (``serve/slot_occupancy``) is the utilization signal.

Containment: the worker thread enters the serve supervisor; admission
runs as the ``serve_admit`` phase (chaos seam ``serve_admit`` — a wedged admission is a stall the watchdog can
attribute, not silence) and each decode step as ``serve_decode`` with a
heartbeat per step. Crash-only recovery (docs "Fault tolerance",
"serving lifecycle"): the unit of failure is the STEP, not the request.
A poisoned step (or admission) dumps the flight recorder, resets the
lanes + prefix cache, and RE-QUEUES every in-flight request with its
committed tokens journaled host-side — re-admission prefills
``prompt + committed`` (the committed prefix maps copy-free through the
radix cache) and resumes decode from the last committed
token, bit-identical under greedy decode. The per-request replay budget
is ``serve.max_replays`` (exceed -> ReplayExhausted, HTTP 503). The
``serve_replay`` chaos seam fires at recovery entry; a fault THERE is a
double fault and falls back to failing the batch (the PR-5 behavior).
:meth:`SlotScheduler.drain` runs the graceful half (finish in-flight
within ``serve.drain_timeout``, admission -> Draining/429), and
:meth:`SlotScheduler.request_swap` hot-swaps checkpoints at a step
boundary with a smoke probe + rollback — both worker-applied, zero
recompiles (seam ``serve_reload``).

Metrics (trlx_tpu.telemetry): ``serve/admissions`` / ``serve/evictions``
/ ``serve/preempted_steps`` counters, ``serve/slot_occupancy`` gauge,
the paged-pool family (``serve/prefix_tokens_saved`` /
``serve/evicted_pages`` counters, ``serve/pages_free`` /
``serve/prefix_hit_rate`` / ``serve/pages_per_request_p95`` gauges,
``serve/pages_per_request`` histogram), plus the shared
``serve/requests|responses|rejected|request_errors|generated_tokens``
family and the path-labeled ``serve/request_latency{path=slots}``
histogram.

Overload containment (docs "Fault tolerance"): requests carry a tenant;
``serve.tenants`` quotas are enforced at :meth:`SlotScheduler.submit`
(typed :class:`QuotaExceeded` 429s with per-tenant ``Retry-After``,
``serve/shed_quota{tenant=...}``), priority admission ages queued
requests (``serve.priority_aging_rounds``) so low-priority tenants
cannot starve, and sustained pressure (the :meth:`_degraded` signal
held for ``serve.brownout_after_s``) enters a hysteretic BROWNOUT that
clamps best-effort tenants' ``max_new_tokens`` to
``serve.brownout_max_new`` — partial answers before typed sheds. The
:meth:`pressure` block is published on ``/readyz`` + ``/debug/state``
so the fleet router can shed upstream before forwarding.
"""

import sys
import threading
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from trlx_tpu import supervisor, telemetry
from trlx_tpu.serve.admission import (
    DEFAULT_TENANT,
    Draining,
    DrainTimeout,
    QueueFull,
    ReplayExhausted,
    Request,
    TenantTable,
    _validate_deadline,
    shed_expired,
)
from trlx_tpu.serve.trace import FlightRecorder, RequestTrace
from trlx_tpu.supervisor import chaos, monotonic

#: filler rows in a prefill bucket aim at slot id == num_slots — one past
#: the pool end, dropped by every mode="drop" scatter on device


class SlotPoolRuntime:
    """Device half of the slot scheduler: pool buffers, per-slot lanes,
    and the compiled prefill/step executables."""

    def __init__(self, engine, num_slots: Optional[int] = None):
        import functools

        import jax
        import jax.numpy as jnp

        from trlx_tpu.models.generation import (
            _segments_of,
            init_page_pool,
            init_slot_state,
        )
        from trlx_tpu.serve import layouts

        self.engine = engine
        self.num_slots = engine.slot_count() if num_slots is None \
            else int(num_slots)
        self._segments, self._seg_sizes = _segments_of(engine.blocks)
        self._vocab = engine.spec.vocab_size
        # CPU has no buffer donation; donating there only prints warnings
        self._donate = jax.default_backend() != "cpu"
        #: the serve mesh (engine-owned); every executable compiles with
        #: explicit in/out shardings on it, so a tp/fsdp slice and the
        #: default single-device mesh run the SAME code path
        self.mesh = engine.mesh
        self._host_sharding = layouts.replicated(self.mesh)
        #: a model with window layers keeps a second class of page: every
        #: program then takes the window-class tables beside the full ones
        #: and returns the expert layer's routing counts
        self.two_class = "window" in engine.spec.page_classes
        #: latent attention: a layer's pool leaf is one array of latent
        #: pages, read by models/latent.py's blocked readers and, under
        #: ``attention: pallas``, the absorbed decode kernel
        self.latent = bool(engine.spec.kv_lora_rank)
        #: routed experts: every program also returns its routing counts
        self.routed = bool(engine.spec.n_experts)
        #: such models have the prefix-context prefill variant alone (no
        #: local K/V buffer to prefill in)
        self.context_only = self.two_class or self.latent
        self.ring_pages = self.num_window_pages = 0
        self.moe_stats = []  # device arrays [L, 4] since the last fetch
        self.moe_stats_host = []  # the same, fetched with the last step
        self.page_size = engine.page_size_tokens()
        self.max_pages = engine.pages_per_slot()
        self.num_pages = engine.page_count()
        if self.two_class:
            self.ring_pages = engine.window_ring_pages()
            self.num_window_pages = engine.window_page_count()
        # logical per-slot extent rounds UP to whole pages
        self.buffer_len = self.max_pages * self.page_size
        # serve.kv_dtype picks the pool tier: int8 swaps each (k, v)
        # array for (codes, scales) pairs (transformer.quantize_kv);
        # everything downstream — shardings, prefill/decode, reset —
        # flows from this partial, so the tier is set exactly once
        cache_dtype = (
            jnp.int8 if engine.serve.kv_dtype == "int8"
            else jnp.bfloat16
        )
        self._init_pool = functools.partial(
            init_page_pool, engine.spec, self._seg_sizes,
            {"full": self.num_pages, "window": self.num_window_pages}
            if self.two_class else self.num_pages,
            self.page_size, cache_dtype=cache_dtype,
        )
        self._init_state = functools.partial(
            init_slot_state, self.num_slots, self.buffer_len, self._vocab,
            max_pages=self.max_pages,
        )
        # KV pages shard on the head dim under tp; the per-slot lanes
        # (and page tables — host data, never shape) replicate. Built
        # DIRECTLY sharded via jitted init + out_shardings: no device
        # ever materializes the whole pool, and the first buffers already
        # carry the shardings the executables are compiled against (a
        # later reshard would be a steady-state signature change — a
        # recompile).
        self._pool_shardings = layouts.kv_pool_shardings(
            self.mesh, jax.eval_shape(self._init_pool)
        )
        self._state_shardings = layouts.replicated_like(
            self.mesh, jax.eval_shape(self._init_state)
        )
        self.pool = jax.jit(
            self._init_pool, out_shardings=self._pool_shardings
        )()
        self.state = jax.jit(
            self._init_state, out_shardings=self._state_shardings
        )()
        self._prefill_fns = {}  # (Bp, P, suffix) -> aot_jit'd closure
        self._step_fn = None
        #: speculation: k proposed tokens verified per step (0 = off);
        #: K is STATIC, so verify_step is one more executable compiled
        #: at warmup — never a steady-state signature change
        self.spec_k = (
            int(engine.serve.spec_k)
            if engine.serve.speculation != "off" else 0
        )
        self._verify_step_fn = None
        self.fetch_s = 0.0  # the last step's wait for its result (_fetch)
        self.warmed = False

    def _view_shardings(self):
        """The live decode views' actual shardings (engine._install_params
        placed them on the serve mesh) — pinned as executable
        in_shardings; hot-swap re-puts onto the same shardings, so the
        signatures never drift."""
        import jax

        sh = lambda t: jax.tree_util.tree_map(lambda x: x.sharding, t)
        e = self.engine
        return sh(e.blocks), sh(e.embed), sh(e.ln_f)

    # -- compiled closures ----------------------------------------------- #

    def _prefill_fn(self, bucket, suffix: bool = False):
        key = (*bucket, suffix)
        fn = self._prefill_fns.get(key)
        if fn is None:
            from trlx_tpu.models.generation import prefill_into_slots
            from trlx_tpu.utils.aotjit import aot_jit

            spec = self.engine.spec
            compute = self.engine._compute_dtype
            ps = self.page_size

            # a model with window layers also hands over its
            # window-class tables (and is always ``suffix``)
            def run(blocks, embed, ln_f, pool, state, tokens, mask,
                    slot_ids, max_new, page_tables, start,
                    window_tables=None, window_base=None):
                return prefill_into_slots(
                    spec, blocks, embed, ln_f, pool, state, tokens,
                    mask, slot_ids, max_new, page_tables, ps,
                    compute_dtype=compute, start=start,
                    prefix_context=suffix,
                    window_tables=window_tables,
                    window_base=window_base,
                )

            # the program's name in a device trace (jit_run_prefill_b4p128),
            # as its span's: a reader finds it without guessing
            Bp, P = bucket
            run.__name__ = f"run_prefill{'_sfx' if suffix else ''}_b{Bp}p{P}"
            # host args (tokens/mask/slot_ids/max_new/tables/start)
            # replicate; pool + state keep their build shardings in AND
            # out — the step loop's signatures are pinned, so
            # compile/recompiles == 0 survives the mesh
            n_host = 8 if self.two_class else 6
            fn = self._prefill_fns[key] = aot_jit(
                run, donate_argnums=(3, 4) if self._donate else (),
                in_shardings=(
                    *self._view_shardings(),
                    self._pool_shardings, self._state_shardings,
                    *([self._host_sharding] * n_host),
                ),
                out_shardings=(
                    self._pool_shardings, self._state_shardings,
                    *([self._host_sharding] * self.routed),
                ),
            )
        return fn

    def window_table_pages(self, prompt_len: int) -> int:
        """Width of the window-class table of the prefill program of
        ``prompt_len``: the pages the window reaches before the suffix,
        the pages the suffix writes, and one for a start inside a page."""
        return self.ring_pages - 1 + -(-prompt_len // self.page_size)

    def _decode_fn(self):
        if self._step_fn is None:
            from trlx_tpu.models.generation import decode_step
            from trlx_tpu.utils.aotjit import aot_jit

            spec = self.engine.spec
            cfg = self.engine._gen_base
            compute = self.engine._compute_dtype

            # serve.attention: pallas swaps the paged gather+score for
            # the fused decode kernel; shard_map'd when the mesh spans
            # devices so tp head-sharding (and greedy parity) holds.
            # Prefill stays jnp either way — the kernel is decode-only.
            paged_decode_fn = None
            if self.engine.serve.attention == "pallas" and self.latent:
                from trlx_tpu.ops.latent_attention import (
                    latent_decode_attention as paged_decode_fn,
                )
            elif self.engine.serve.attention == "pallas":
                from trlx_tpu.ops.paged_attention import (
                    make_paged_decode_fn,
                )
                from trlx_tpu.serve import layouts

                paged_decode_fn = make_paged_decode_fn(
                    None if layouts.is_single_device(self.mesh)
                    else self.mesh
                )

            # ``window_table``: a model with window layers alone
            def run(blocks, embed, ln_f, pool, state, seed,
                    window_table=None):
                return decode_step(
                    spec, blocks, embed, ln_f, pool, state, seed, cfg,
                    compute_dtype=compute,
                    paged_decode_fn=paged_decode_fn,
                    window_table=window_table,
                )

            run.__name__ = "run_decode_step"
            self._step_fn = aot_jit(
                run, donate_argnums=(3, 4) if self._donate else (),
                in_shardings=(
                    *self._view_shardings(),
                    self._pool_shardings, self._state_shardings,
                    *([self._host_sharding] * (1 + self.two_class)),
                ),
                out_shardings=(
                    self._pool_shardings, self._state_shardings,
                    *([self._host_sharding] * (3 + self.routed)),
                ),
            )
        return self._step_fn

    def _verify_fn(self):
        """The speculation verifier: decode_step's shape with K+1
        candidates per slot — always the jnp attention path (the pallas
        decode kernel is T==1; the verify pass amortizes the gather over
        K+1 query positions anyway)."""
        if self._verify_step_fn is None:
            from trlx_tpu.models.generation import verify_step
            from trlx_tpu.utils.aotjit import aot_jit

            spec = self.engine.spec
            cfg = self.engine._gen_base
            compute = self.engine._compute_dtype

            def run(blocks, embed, ln_f, pool, state, seed,
                    proposals, n_proposed):
                return verify_step(
                    spec, blocks, embed, ln_f, pool, state, seed,
                    proposals, n_proposed, cfg, compute_dtype=compute,
                )

            run.__name__ = "run_verify_step"
            self._verify_step_fn = aot_jit(
                run, donate_argnums=(3, 4) if self._donate else (),
                in_shardings=(
                    *self._view_shardings(),
                    self._pool_shardings, self._state_shardings,
                    self._host_sharding, self._host_sharding,
                    self._host_sharding,
                ),
                out_shardings=(
                    self._pool_shardings, self._state_shardings,
                    self._host_sharding, self._host_sharding,
                    self._host_sharding,
                ),
            )
        return self._verify_step_fn

    # -- spans ------------------------------------------------------------ #

    def prefill_span(self, bucket, suffix: bool = False) -> str:
        Bp, P = bucket
        return f"serve/prefill{'_sfx' if suffix else ''}_b{Bp}p{P}"

    STEP_SPAN = "serve/slot_step"
    VERIFY_SPAN = "serve/spec_verify"
    FETCH_SPAN = "serve/step_fetch"  # child of either of the two above

    # -- device calls ------------------------------------------------------ #

    def prefill(self, bucket, tokens: np.ndarray, mask: np.ndarray,
                slot_ids, max_new, page_tables, start,
                suffix: bool = False, window_tables=None,
                window_base=None) -> None:
        """Admit one prompt bucket into the pool (filler rows carry the
        out-of-bounds sentinel and are dropped on device).
        ``page_tables`` [Bp, max_pages] maps each row's logical pages
        (sentinel-padded), ``start`` is its committed prefix length, and
        ``suffix=True`` selects the prefix-context (``prefill_suffix``)
        executable; tokens/mask are right-padded."""
        e = self.engine
        suffix = suffix or self.context_only  # the one variant there is
        fn = self._prefill_fn(bucket, suffix)
        args = [
            e.blocks, e.embed, e.ln_f, self.pool, self.state,
            np.ascontiguousarray(tokens, np.int32),
            np.ascontiguousarray(mask, np.int32),
            np.asarray(slot_ids, np.int32),
            np.asarray(max_new, np.int32),
            np.ascontiguousarray(page_tables, np.int32),
            np.asarray(start, np.int32),
        ]
        if self.two_class:
            Bp, P = bucket
            if window_tables is None:  # nothing mapped: every write drops
                window_tables = np.full(
                    (Bp, self.window_table_pages(P)),
                    self.num_window_pages, np.int32,
                )
                window_base = np.zeros((Bp,), np.int32)
            args += [
                np.ascontiguousarray(window_tables, np.int32),
                np.asarray(window_base, np.int32),
            ]
        with telemetry.span(self.prefill_span(bucket, suffix)):
            if self.routed:
                self.pool, self.state, stats = fn(*args)
                self.moe_stats.append(stats)
            else:
                self.pool, self.state = fn(*args)

    def step(self, seed: int, window_table=None):
        """One decode step for every slot; returns host-side
        (tokens [S], emitted [S], finished [S]) numpy arrays. A model
        with window layers takes the slots' window-class ring tables
        (``window_table`` [S, ring_pages], the scheduler's); a model with
        routed experts leaves the routing counts of this step and of the
        prefills since the last one in ``moe_stats_host`` — they ride the
        token fetch."""
        e = self.engine
        fn = self._decode_fn()
        with telemetry.span(self.STEP_SPAN):
            extra = ()
            if self.two_class:
                if window_table is None:
                    window_table = np.full(
                        (self.num_slots, self.ring_pages),
                        self.num_window_pages, np.int32,
                    )
                extra = (np.ascontiguousarray(window_table, np.int32),)
            self.pool, self.state, *out = fn(
                e.blocks, e.embed, e.ln_f, self.pool, self.state,
                np.int32(seed), *extra,
            )
            if not self.routed:
                return self._fetch(tuple(out))
            pending, self.moe_stats = self.moe_stats, []
            tok, emitted, finished, stats, self.moe_stats_host = \
                self._fetch((*out, pending))
            self.moe_stats_host.append(stats)  # the step's comes last
            return tok, emitted, finished

    def verify(self, seed: int, proposals: np.ndarray,
               n_proposed: np.ndarray):
        """One speculative verification step for every slot: scores the
        K proposals + the free token in one batched pass. Returns
        host-side (cand [S, K+1], counts [S], finished [S]) — each
        slot emits ``cand[s, :counts[s]]``."""
        e = self.engine
        fn = self._verify_fn()
        with telemetry.span(self.VERIFY_SPAN):
            self.pool, self.state, cand, counts, finished = fn(
                e.blocks, e.embed, e.ln_f, self.pool, self.state,
                np.int32(seed),
                np.ascontiguousarray(proposals, np.int32),
                np.asarray(n_proposed, np.int32),
            )
            return self._fetch((cand, counts, finished))

    def _fetch(self, outputs):
        """The step's blocking device->host fetch: what the host waited
        for the step's result (``fetch_s``, the flight record's
        ``fetch_ms``). The programs dispatched before the step (this
        iteration's prefills) run ahead of it and are waited out here."""
        import jax

        start = monotonic()
        with telemetry.span(self.FETCH_SPAN):
            host = jax.device_get(outputs)
        self.fetch_s = monotonic() - start
        return host

    def reset_lanes(self) -> None:
        """Fresh all-free per-slot lanes, REUSING the pool buffers — the
        poisoned-step containment path. Zeroed lanes (valid/active/pages)
        already gate every read of the big KV buffers, so their stale
        contents are harmless and keeping them avoids transiently holding
        2x the pool in HBM mid-reset. The one case the old arrays cannot
        be trusted is donation: a program that failed mid-execution may
        have CONSUMED the donated buffers — detected per-leaf via
        ``is_deleted()``, and only then is the pool reallocated (on its
        original mesh shardings — a reset never drifts a signature)."""
        import jax

        def consumed(leaf):
            try:
                return leaf.is_deleted()
            except Exception:
                return True  # uninspectable -> rebuild, the safe side

        if any(consumed(x) for x in jax.tree_util.tree_leaves(self.pool)):
            self.pool = jax.jit(
                self._init_pool, out_shardings=self._pool_shardings
            )()
        self.state = jax.jit(
            self._init_state, out_shardings=self._state_shardings
        )()

    # -- warmup ------------------------------------------------------------ #

    def _report_decode_memory(self) -> None:
        """What the compiler made of the decode step, as three gauges and
        one log line. ``serve/decode_alias_bytes``: bytes of arguments
        aliased to outputs (the whole pool + the lanes when every layer's
        scatter lands in place; 0 on the CPU, which has no donation).
        ``serve/decode_temp_bytes``: the program's temporaries (prefetched
        weight slices and a few layers' gathered pages, never a pool's
        worth); a second pool in either number is a copy a later change
        brought back. ``serve/decode_weight_copy_bytes``: bytes of the ops
        that only move data and write at least a layer's q, k or v matrix
        (utils/hlo_text.large_moves): 0 while every weight reaches its
        dot in the layout it is stored in; a weight written out again in
        another layout, every step, shows here before a trace is read."""
        e = self.engine
        extra = (np.zeros((self.num_slots, self.ring_pages), np.int32),) \
            if self.two_class else ()
        import jax

        from trlx_tpu.serve import layouts
        from trlx_tpu.utils.hlo_text import large_moves

        compiled = self._decode_fn().compiled_for(
            e.blocks, e.embed, e.ln_f, self.pool, self.state, np.int32(0),
            *extra,
        )
        said = []
        stats = compiled.memory_analysis()
        if stats is not None:  # None: a backend without the analysis
            alias, temp = stats.alias_size_in_bytes, stats.temp_size_in_bytes
            telemetry.set_gauge("serve/decode_alias_bytes", alias)
            telemetry.set_gauge("serve/decode_temp_bytes", temp)
            pool = layouts.tree_bytes_per_device(self.pool)
            said += [
                f"{alias / 2**30:.3f} GiB of arguments aliased to outputs "
                f"(pool {pool / 2**30:.3f} GiB per device)",
                f"{temp / 2**30:.3f} GiB of temporaries",
            ]
        seg, layers = next(  # a hydra's frozen segment may hold no layer
            (s, n) for s, n in zip(self._segments, self._seg_sizes) if n
        )
        one_matrix = min(  # the int8 tier's (codes, scale): the codes
            layouts.tree_bytes_per_device(
                jax.tree_util.tree_leaves(seg["attn"][name])[0]
            ) // layers
            for name in (("wq", "w_dkv", "w_uk", "w_uv", "wo")
                         if self.latent else ("wq", "wk", "wv"))
        )
        moved = sum(
            m.nbytes for m in large_moves(compiled.as_text(), one_matrix)
        )
        telemetry.set_gauge("serve/decode_weight_copy_bytes", moved)
        said.append(f"{moved / 2**30:.3f} GiB of weight-sized copies")
        if self.latent:
            said += self._report_latent_pool()
        elif e.serve.attention == "pallas":
            said += self._report_paged_walk()
        print(f"[trlx_tpu.serve] decode step: {', '.join(said)}",
              file=sys.stderr, flush=True)

    def _report_paged_walk(self) -> list:
        """What the paged decode kernel chose for each class of page, from
        the pool a device holds and the table's width: the pages it
        fetches and scores a block
        (``serve/paged_attn_pages_per_block{class=}``; 1 = a pool it
        walks a page a grid step) and the grid steps of one layer's call
        (``serve/paged_attn_grid_steps{class=}``)."""
        import jax

        from trlx_tpu.ops.paged_attention import block_plan, grid_steps

        spec = self.engine.spec
        layers = [kv for seg in self.pool for kv in seg]
        tables = {"full": self.max_pages, "window": self.ring_pages}
        said = []
        for kind in spec.page_classes:
            k_pages = next(kv[0] for i, kv in enumerate(layers)
                           if spec.layer_kind(i) == kind)
            codes = jax.tree_util.tree_leaves(k_pages)[0]
            shape = codes.sharding.shard_shape(codes.shape)
            pages, blocks = block_plan(shape, codes.dtype, tables[kind])
            steps = grid_steps(shape, codes.dtype, self.num_slots,
                               tables[kind])
            labels = {"class": kind}
            telemetry.set_gauge("serve/paged_attn_pages_per_block", pages,
                                labels)
            telemetry.set_gauge("serve/paged_attn_grid_steps", steps, labels)
            said.append(
                f"{kind} pages walked {pages} a block, {blocks} blocks a "
                f"table of {tables[kind]}, {steps} grid steps a call"
            )
        return said

    def _report_latent_pool(self) -> list:
        """What a latent pool costs and how it is read, at warm-up:
        ``serve/latent_bytes_per_token`` (a layer: the latent out to whole
        lane tiles, what a page really takes), the absorbed decode
        kernel's walk under ``attention: pallas``
        (``serve/latent_attn_pages_per_block``,
        ``serve/latent_attn_blocks_per_table``) and the order each prefill
        class compiled (``serve/latent_order{bucket=}``: 1 absorbed, 0
        up-projected; chosen by the class's length alone)."""
        from trlx_tpu.models.latent import ABSORB_MAX_T
        from trlx_tpu.ops.latent_attention import block_plan

        spec = self.engine.spec
        pages = self.pool[0][0]
        per_token = pages.shape[-1] * pages.dtype.itemsize
        telemetry.set_gauge("serve/latent_bytes_per_token", per_token)
        said = [f"latent pages of {per_token} bytes a token a layer "
                f"({spec.latent_width} numbers read)"]
        if self.engine.serve.attention == "pallas":
            shape = pages.sharding.shard_shape(pages.shape)
            P, blocks = block_plan(shape, pages.dtype, self.max_pages)
            telemetry.set_gauge("serve/latent_attn_pages_per_block", P)
            telemetry.set_gauge("serve/latent_attn_blocks_per_table", blocks)
            said.append(f"walked {P} pages a block, {blocks} blocks a "
                        f"table of {self.max_pages}")
        orders = {}
        for Bp, P, _ in self._prefill_fns:
            absorbed = P <= ABSORB_MAX_T
            telemetry.set_gauge("serve/latent_order", int(absorbed),
                                {"bucket": f"b{Bp}p{P}"})
            orders.setdefault("absorbed" if absorbed else "up-projected",
                              []).append(f"b{Bp}p{P}")
        said += [f"{k}: {' '.join(v)}" for k, v in sorted(orders.items())]
        return said

    def warmup(self) -> Dict[str, float]:
        """Compile every admission bucket + the decode step up front.
        All rows aim at the sentinel slot, so the live pool is untouched;
        each compile is a first call in its own executable cache (the
        ``compile/recompiles == 0`` invariant). Returns {span:
        first-call seconds}."""
        pad = self.engine.pad_token_id
        latencies = {}
        # a model with window layers has the prefix-context variant alone
        variants = (True,) if self.context_only else (False, True)
        for P, extents in self.engine.prompt_classes():
            for Bp in extents:
                for suffix in variants:
                    tokens = np.full((Bp, P), pad, np.int32)
                    mask = np.zeros((Bp, P), np.int32)
                    tokens[:, 0] = 0  # right-padded: one real token FIRST
                    mask[:, 0] = 1
                    self.prefill(
                        (Bp, P), tokens, mask,
                        np.full((Bp,), self.num_slots, np.int32),
                        np.ones((Bp,), np.int32),
                        page_tables=np.full(
                            (Bp, self.max_pages), self.num_pages, np.int32
                        ),
                        start=np.zeros((Bp,), np.int32),
                        suffix=suffix,
                    )
        self.step(0)
        self._report_decode_memory()
        if self.spec_k > 0:
            # compile the verifier against the all-free pool: every row
            # is non-emitting, so the sentinel-gated table drops every
            # write and the pass is pure shape
            self.verify(
                0,
                np.zeros((self.num_slots, self.spec_k), np.int32),
                np.zeros((self.num_slots,), np.int32),
            )
        tel = telemetry.current()
        if tel is not None:
            spans = [
                self.prefill_span((Bp, P), suffix)
                for P, extents in self.engine.prompt_classes()
                for Bp in extents
                for suffix in variants
            ] + [self.STEP_SPAN]
            if self.spec_k > 0:
                spans.append(self.VERIFY_SPAN)
            for span in spans:
                hist = tel.registry.hists.get(f"time/{span}")
                if hist is not None and hist.first is not None:
                    latencies[span] = hist.first
        self.warmed = True
        telemetry.set_gauge(
            "serve/slot_programs_warmed",
            len(self._prefill_fns) + 1 + (1 if self.spec_k > 0 else 0),
        )
        return latencies


class _LiveSlot:
    """Host bookkeeping for one occupied slot. ``pages`` is the slot's
    full page-table content (matched prefix pages
    first — every entry holds one allocator reference released at
    harvest); ``committed`` the pages this admission inserted into the
    radix tree (the rollback handle for a failed prefill)."""

    __slots__ = ("request", "tokens", "pages", "committed", "wmap",
                 "wquota", "pos0", "page_arr")

    def __init__(self, request: Request, pages=None, committed=None):
        self.request = request
        self.tokens: List[int] = []
        self.pages: List[int] = pages or []
        self.committed: List[int] = committed or []
        # the window class (a model with window layers): the window-class
        # pages the slot maps now, {logical page: page id}, each holding
        # one reference; the most it may map at once (reserved at
        # admission); and the position of its first decoded token less
        # the tokens journaled before admission
        self.wmap: Dict[int, int] = {}
        self.wquota = 0
        self.pos0 = 0
        # ``pages`` as an array (a latent pool's flight record counts the
        # pages a step reads, every step)
        self.page_arr = None


class SlotScheduler:
    """The continuous-batching decode driver: one worker thread running
    the admit -> step -> harvest loop over the slot pool
    (``submit``/``start``/``stop``/``queue_depth``; a submitted
    :class:`Request` completes through its ``done`` event).
    """

    def __init__(self, engine, max_queue: Optional[int] = None,
                 run_supervisor=None, slots: Optional[int] = None,
                 draft=None):
        self.engine = engine
        cfg = engine.serve
        self.max_queue = cfg.max_queue if max_queue is None else max_queue
        self.run_supervisor = run_supervisor
        self.runtime = SlotPoolRuntime(engine, num_slots=slots)
        #: host paged-KV broker (allocator + radix prefix cache)
        self.cache = self._new_cache()
        #: the slots' window-class rings (a model with window layers):
        #: host data handed to every decode step, logical page n of slot
        #: s at entry n % ring_pages
        self._wtable = np.full(
            (self.runtime.num_slots, max(self.runtime.ring_pages, 1)),
            self.runtime.num_window_pages, np.int32,
        )
        # the expert layer's counts and the window pages released since
        # the last flight record
        self._fr_pairs = self._fr_pairs_step = self._fr_experts_hit = 0
        self._fr_window_freed = 0
        self._moe_load = 0.0  # the last step's load_max_over_mean
        self._prompt_tokens_total = 0  # prefix hit-rate denominators
        self._prefix_tokens_saved = 0
        self._queue = deque()  # guarded-by: _cond
        self._cond = threading.Condition()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._free = list(range(self.runtime.num_slots))
        self._live: Dict[int, _LiveSlot] = {}
        self._step_counter = 0
        self._starved = False  # queue waited while no slot/page was free
        #: (event, slot, request) ring — "admit"/"free"; the e2e tests
        #: read it to prove a freed slot was reused mid-decode
        self.events = deque(maxlen=4096)
        self._tracing = bool(getattr(cfg, "request_tracing", True))
        self._slo_s = float(getattr(cfg, "slo_ttft_ms", 0.0)) / 1000.0
        #: per-step engine black box (serve.flight_recorder_steps; 0
        #: disables); dumped on stall/chaos/poison, served at /debug/state
        fr_steps = int(getattr(cfg, "flight_recorder_steps", 0))
        self.flight: Optional[FlightRecorder] = (
            FlightRecorder(fr_steps) if fr_steps > 0 else None
        )
        # admissions/evictions since the last flight-recorder record —
        # reset by _run after each step's record lands in the ring
        self._fr_admitted = 0
        self._fr_evicted = 0
        # the current iteration's host phases, for its flight record
        self._admit_s = 0.0
        self._harvest_s = 0.0
        # -- speculation (docs "Speculative decoding") ------------------ #
        #: propose -> verify -> accept per step when serve.speculation
        #: is on; per-slot host state lives in _speculators (lookup
        #: tier), dropped at harvest/replay so host memory is bounded
        self._spec_mode = cfg.speculation
        self.spec_k = self.runtime.spec_k
        self._speculators: Dict[int, object] = {}
        self._draft = draft  # tests inject; built lazily otherwise
        if (self._spec_mode == "draft" and draft is None
                and cfg.spec_draft_checkpoint):
            from trlx_tpu.serve.speculate import DraftProposer

            self._draft = DraftProposer.from_checkpoint(
                cfg.spec_draft_checkpoint, engine, self.spec_k
            )
        self._spec_proposed_total = 0
        self._spec_accepted_total = 0
        self._fr_spec_proposed = 0
        self._fr_spec_accepted = 0
        # -- crash-only lifecycle state (docs "Fault tolerance") -------- #
        self._draining = False  # guarded-by: _cond
        self._drain_deadline = 0.0
        self._drained = threading.Event()
        #: worker-applied hot-swap: {"params", "label", "done", "result"}
        self._pending_swap: Optional[Dict] = None  # guarded-by: _cond
        self._last_step_ms = 0.0
        self._replayed_requests = 0  # lifetime; /debug/state + bench
        # -- overload containment (docs "Fault tolerance") -------------- #
        #: per-tenant quota table; no serve.tenants config = every check
        #: is a no-op (guarded-by: _cond, like the queue it meters)
        self.tenants = TenantTable(
            getattr(cfg, "tenants", None), self.max_queue
        )
        self._aging_rounds = int(getattr(cfg, "priority_aging_rounds", 0))
        #: brownout state machine (worker-written, HTTP-read; a stale
        #: read only mis-times one clamp): pressure held for
        #: brownout_after_s -> clamp best-effort tenants; calm for
        #: brownout_recover_s -> recover. Stamps are monotonic() or 0.
        self._brownout = False
        self._pressure_since = 0.0
        self._calm_since = 0.0
        self._brownout_max_new = int(getattr(cfg, "brownout_max_new", 0))
        self._brownout_after_s = float(
            getattr(cfg, "brownout_after_s", 2.0)
        )
        self._brownout_recover_s = float(
            getattr(cfg, "brownout_recover_s", 5.0)
        )

    def _new_cache(self):
        """A fresh allocator + radix tree over the pool's classes."""
        from trlx_tpu.serve.paged import RadixCache

        rt = self.runtime
        return RadixCache(
            rt.num_pages, rt.page_size,
            window_pages=rt.num_window_pages,
            window=self.engine.spec.window if rt.two_class else 0,
        )

    # -- lifecycle ------------------------------------------------------- #

    def warmup(self) -> Dict[str, float]:
        return self.runtime.warmup()

    @property
    def warmed(self) -> bool:
        return self.runtime.warmed

    def start(self) -> "SlotScheduler":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="trlx-serve-slots", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        with self._cond:
            pending = list(self._queue)
            self._queue.clear()
        live = list(self._live.values())
        self._live.clear()
        self._speculators.clear()
        self._free = list(range(self.runtime.num_slots))
        for req in pending + [s.request for s in live]:
            req.error = RuntimeError("serve slot scheduler stopped")
            req.done.set()

    # -- submission ------------------------------------------------------- #

    def queue_depth(self) -> int:
        # under the cond: /healthz, admission 429s and the drain path
        # ask from off-worker threads while submit/admit mutate it
        with self._cond:
            return len(self._queue)

    def free_slots(self) -> int:
        return len(self._free)

    def submit(self, tokens: List[int],
               max_new_tokens: Optional[int] = None,
               seed: Optional[int] = None,
               trace: Optional[RequestTrace] = None,
               deadline_ms: Optional[float] = None,
               priority: Optional[int] = None,
               tenant: Optional[str] = None) -> Request:
        """Enqueue one request (ValueError when no bucket fits, QueueFull
        past ``max_queue``, Draining during a graceful drain). ``seed``
        is accepted (the HTTP body carries one) but the sampling stream
        is per-STEP (a request's draws depend on which steps it rides),
        so only greedy decode is exactly reproducible.

        Overload control: ``deadline_ms`` bounds queueing — a request
        still queued past it is shed (DeadlineExceeded, 503) at the next
        admission scan instead of decoded uselessly; higher ``priority``
        admits first (ties FIFO; ``None`` takes the tenant's configured
        default, and queued requests AGE upward every
        ``serve.priority_aging_rounds`` admission scans so nothing
        starves forever). Per-tenant ``serve.tenants`` quotas reject
        over-quota tenants with a typed :class:`QuotaExceeded` (429 +
        the tenant's own ``Retry-After``) while the rest of the fleet
        keeps being admitted. When the engine is degraded (slot/page
        starvation, or a step over ``serve.degrade_step_ms``) the
        effective queue bound halves — and pressure SUSTAINED for
        ``serve.brownout_after_s`` enters brownout, clamping best-effort
        tenants' ``max_new_tokens`` to ``serve.brownout_max_new``
        (response flag ``"degraded": true``) before shedding them."""
        if not tokens:
            raise ValueError("empty prompt: at least one token is required")
        if max_new_tokens is None:
            max_new_tokens = self.engine.default_max_new_tokens()
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens <= 0:
            raise ValueError(f"max_new_tokens={max_new_tokens} must be >= 1")
        deadline_s = _validate_deadline(deadline_ms)
        tenant = DEFAULT_TENANT if not tenant else str(tenant)
        if priority is None:
            priority = self.tenants.priority_for(tenant)
        # brownout clamp BEFORE bucket rounding so the clamped request
        # also reserves the smaller shape class (and fewer KV pages)
        browned_out = (
            self._brownout and self._brownout_max_new > 0
            and self.tenants.best_effort(tenant)
            and max_new_tokens > self._brownout_max_new
        )
        if browned_out:
            max_new_tokens = self._brownout_max_new
            telemetry.inc("serve/brownout_clamped")
            telemetry.inc("serve/brownout_clamped",
                          labels={"tenant": tenant})
        shape = self.engine.pick_shape(len(tokens), max_new_tokens)
        need = self.engine.request_page_need(len(tokens), max_new_tokens)
        if need > self.runtime.num_pages:
            raise ValueError(
                f"request needs {need} KV pages worst-case but the "
                f"pool holds {self.runtime.num_pages}; raise "
                f"serve.pages (or serve.page_size) — queueing could "
                f"never admit it"
            )
        if self.runtime.two_class and (
            self._window_quota(need, 0) > self.runtime.num_window_pages
        ):
            raise ValueError(
                f"request needs up to {self._window_quota(need, 0)} "
                f"window-class KV pages at once but the pool holds "
                f"{self.runtime.num_window_pages}; raise "
                f"serve.window_pages — queueing could never admit it"
            )
        if trace is None and self._tracing:
            trace = RequestTrace()
        req = Request(list(tokens), max_new_tokens, shape, seed=seed,
                      trace=trace, deadline_s=deadline_s,
                      priority=priority, tenant=tenant)
        req.degraded = browned_out
        if self.tenants.enabled:
            chaos.maybe_inject("serve_quota")
        with self._cond:
            if self._draining:
                telemetry.inc("serve/rejected")
                raise Draining(
                    "server is draining: admission is closed while "
                    "in-flight requests finish (serve.drain_timeout); "
                    "retry against another replica"
                )
            denied = self.tenants.try_admit(
                tenant,
                queued=sum(
                    1 for r in self._queue if r.tenant == tenant
                ),
                inflight=sum(
                    1 for s in list(self._live.values())
                    if s.request.tenant == tenant
                ),
                now=monotonic(),
            )
            if denied is not None:
                telemetry.inc("serve/rejected")
                telemetry.inc("serve/shed_quota")
                telemetry.inc("serve/shed_quota",
                              labels={"tenant": tenant})
                raise denied
            cap = self.max_queue
            if self._degraded():
                cap = max(1, self.max_queue // 2)
            telemetry.set_gauge("serve/admission_limit", cap)
            if len(self._queue) >= cap:
                telemetry.inc("serve/rejected")
                detail = " (halved: engine degraded)" \
                    if cap < self.max_queue else ""
                raise QueueFull(
                    f"serve queue is full ({cap} pending{detail}); "
                    f"retry with backoff (serve.max_queue bounds queueing "
                    f"delay — raise it to trade latency for acceptance)"
                )
            self._queue.append(req)
            telemetry.inc("serve/requests")
            telemetry.set_gauge("serve/queue_depth", len(self._queue))
            self._cond.notify_all()
        return req

    def _degraded(self) -> bool:
        """Adaptive-admission signal: requests starved for slots/pages,
        the page pool pinned empty, or the last step over the
        ``serve.degrade_step_ms`` budget."""
        if self._starved:
            return True
        if self.cache.free_pages() == 0:
            return True
        limit_ms = float(getattr(self.engine.serve, "degrade_step_ms", 0.0))
        return bool(limit_ms > 0 and self._last_step_ms > limit_ms)

    def _update_brownout(self, now: float) -> None:
        """Hysteretic brownout state machine, advanced once per worker
        iteration: the :meth:`_degraded` pressure signal must hold
        continuously for ``serve.brownout_after_s`` before brownout
        engages, and be absent continuously for
        ``serve.brownout_recover_s`` before it releases — a flapping
        signal moves neither edge. Gauge ``serve/brownout`` tracks the
        mode; ``serve/brownout_entries`` counts engagements."""
        if self._brownout_max_new <= 0:
            return
        if self._degraded():
            self._calm_since = 0.0
            if self._pressure_since == 0.0:
                self._pressure_since = now
            elif (not self._brownout
                  and now - self._pressure_since >= self._brownout_after_s):
                self._brownout = True
                telemetry.inc("serve/brownout_entries")
                telemetry.set_gauge("serve/brownout", 1)
        else:
            self._pressure_since = 0.0
            if not self._brownout:
                self._calm_since = 0.0
            elif self._calm_since == 0.0:
                self._calm_since = now
            elif now - self._calm_since >= self._brownout_recover_s:
                self._brownout = False
                telemetry.set_gauge("serve/brownout", 0)

    def pressure(self) -> Dict:
        """The published backpressure block (``/readyz`` +
        ``/debug/state``): one JSON object the fleet router's prober
        reads to shed best-effort traffic LOCALLY (cheap 429 +
        Retry-After) instead of forwarding a doomed hop. Lock-free
        reads — a slightly stale view only mis-times one shed."""
        out = {
            "degraded": self._degraded(),
            "brownout": self._brownout,
            "starved": self._starved,
            "queue_depth": len(self._queue),
            "free_slots": len(self._free),
            "retry_after_s": self.retry_after_s(),
            "pages_free": self.cache.free_pages(),
        }
        if self.spec_k > 0:
            out["spec_acceptance_rate"] = round(
                self._spec_acceptance_rate(), 4
            )
        return out

    def step_p50_s(self) -> float:
        """Recent decode-step p50 (the ``time/serve/slot_step``
        histogram's steady-state window) — the pacing term in
        ``Retry-After``. Falls back to 50ms before any steps land."""
        tel = telemetry.current()
        if tel is not None:
            hist = tel.registry.hists.get(f"time/{self.runtime.STEP_SPAN}")
            if hist is not None and hist.count:
                return max(hist.quantile(0.5), 1e-4)
        return 0.05

    def retry_after_s(self) -> int:
        """The 429 ``Retry-After`` hint: queue depth x recent step p50 —
        roughly how long the current backlog takes to start draining.
        Never below 1s (clients must not hot-loop on a full queue)."""
        estimate = len(self._queue) * self.step_p50_s()
        return max(1, int(-(-estimate // 1)))

    # -- worker ----------------------------------------------------------- #

    def _occupancy(self) -> float:
        return len(self._live) / max(self.runtime.num_slots, 1)

    def _admit(self) -> None:
        """Move queued requests into free slots, one prompt-class bucket
        at a time (highest-priority head's class first, ties FIFO by
        ``seq``). Queued requests past their ``deadline_ms`` are shed
        here (DeadlineExceeded, ``serve/shed_expired``) before any slot
        is spent on them. Sets ``_starved`` when requests are left
        waiting with no free slot (or no obtainable page) — the
        next step then counts as ``serve/preempted_steps``.

        Priority aging: every scan bumps each queued request's ``age``;
        the effective priority is ``priority + age //
        serve.priority_aging_rounds`` (0 rounds = aging off), so a
        saturating high-priority stream raises — never pins — the wait
        of low-priority tenants (the starvation regression test bounds
        it)."""
        aging = self._aging_rounds

        def by_prio(r):
            boost = r.age // aging if aging > 0 else 0
            return (-(r.priority + boost), r.seq)

        first_scan = True
        while True:
            with self._cond:
                if first_scan:
                    first_scan = False
                    for r in self._queue:
                        r.age += 1
                if self._queue:
                    survivors = shed_expired(list(self._queue), monotonic())
                    if len(survivors) != len(self._queue):
                        self._queue = deque(survivors)
                        telemetry.set_gauge(
                            "serve/queue_depth", len(self._queue)
                        )
                self._starved = bool(self._queue) and not self._free
                if not self._queue or not self._free:
                    return
                P = min(self._queue, key=by_prio).shape[0]
                extents = self.engine.prefill_batch_sizes(P)
                same = sorted(
                    (r for r in self._queue if r.shape[0] == P), key=by_prio
                )
                take = min(len(same), len(self._free), extents[-1])
                batch = same[:take]
                for r in batch:
                    self._queue.remove(r)
                telemetry.set_gauge("serve/queue_depth", len(self._queue))
            admitted_all = True
            with supervisor.phase("serve_admit"):
                try:
                    chaos.maybe_inject("serve_admit")
                    admitted_all = self._prefill_batch(batch, P, extents)
                except Exception as e:
                    # a poisoned admission RE-QUEUES its requests for
                    # replay (bounded by serve.max_replays) instead of
                    # failing them (page-starved ones were already
                    # re-queued and removed from `batch`); the
                    # pool lanes were only touched if the device call
                    # ran, and dropped-sentinel scatters cannot corrupt
                    # live slots
                    if self.flight is not None:
                        self.flight.dump(f"admission failure: {e!r}")
                    self._requeue_for_replay(batch, e)
                supervisor.beat()
            if not admitted_all:
                # page pool exhausted mid-batch: requests stay QUEUED
                # (never crashed/failed) until harvests return pages —
                # keep stepping the live slots instead of spinning here
                self._starved = True
                return

    def _spawn_speculator(self, slot: int, history: List[int]) -> None:
        """Lookup-tier per-slot state: the n-gram index over the
        request's own prompt + journaled committed tokens. Bounded
        (``serve.spec_index_max_keys`` LRU) and dropped at harvest/
        replay — the slow soaks assert the map drains to empty."""
        if self.spec_k <= 0 or self._spec_mode != "lookup":
            return
        from trlx_tpu.serve.speculate import SlotSpeculator

        cfg = self.engine.serve
        self._speculators[slot] = SlotSpeculator(
            history, self.spec_k,
            ngram_max=int(getattr(cfg, "spec_ngram_max", 3)),
            max_keys=int(getattr(cfg, "spec_index_max_keys", 512)),
        )

    def _prefill_batch(self, batch: List[Request], P: int, extents) -> bool:
        """Prefill one admission batch; returns False when the page
        allocator ran dry and part of the batch went back to the queue."""
        if self.runtime.context_only or self.engine.chunk_len(P):
            return self._prefill_batch_classes(batch, P, extents)
        return self._prefill_batch_paged(batch, P, extents)

    def _prefill_batch_paged(self, batch: List[Request], P: int,
                             extents) -> bool:
        """Admission: radix-match each prompt, reserve pages for
        the unmatched suffix + decode budget, map hit pages copy-free
        into the page table, and prefill ONLY the suffix. Requests the
        allocator cannot cover (even after LRU eviction) go back to the
        queue head in order — exhaustion queues, never crashes."""
        ps = self.runtime.page_size
        chaos.maybe_inject("serve_prefix_match")
        plans = []  # (request, toks, matched, pages, committed)
        deferred: List[Request] = []
        for i, r in enumerate(batch):
            # replay: the journaled committed tokens extend the prompt —
            # the already-decoded prefix radix-matches (its pages are
            # still cached unless the poisoned reset wiped them) and only
            # the unmatched suffix prefills
            toks = (r.tokens + r.committed)[-P:]
            matched = self.cache.match(toks)
            need = self.engine.request_page_need(
                len(toks), r.remaining_new_tokens()
            ) - len(matched)
            fresh = self.cache.alloc(need)
            if fresh is None:
                self.cache.release_all(matched)
                deferred = batch[i:]
                break
            pages = matched + fresh
            committed = self.cache.commit(toks, pages)
            plans.append((r, toks, matched, pages, committed))
        if deferred:
            with self._cond:
                for r in reversed(deferred):
                    if r.trace is not None:  # page starvation -> re-queued
                        r.trace.queue_reentries += 1
                    self._queue.appendleft(r)
                telemetry.set_gauge("serve/queue_depth", len(self._queue))
            # the _admit exception handler must not fail re-queued rows
            batch[:] = [p[0] for p in plans]
        if not plans:
            telemetry.set_gauge(
                "serve/pages_free", self.cache.free_pages()
            )
            return False

        Bp = next(b for b in extents if b >= len(plans))
        slots = [self._free.pop() for _ in plans]
        pad = self.engine.pad_token_id
        tokens = np.full((Bp, P), pad, np.int32)
        mask = np.zeros((Bp, P), np.int32)
        page_tables = np.full(
            (Bp, self.runtime.max_pages), self.runtime.num_pages, np.int32
        )
        starts = np.zeros((Bp,), np.int32)
        max_new = np.ones((Bp,), np.int32)
        slot_ids = np.full((Bp,), self.runtime.num_slots, np.int32)
        admit_at = monotonic()
        version = self.engine.model_version
        for j, ((r, toks, matched, pages, _), s) in enumerate(
            zip(plans, slots)
        ):
            start = len(matched) * ps
            suf = toks[start:]
            tokens[j, :len(suf)] = suf  # right-padded suffix
            mask[j, :len(suf)] = 1
            page_tables[j, :len(pages)] = pages
            starts[j] = start
            max_new[j] = r.remaining_new_tokens()
            slot_ids[j] = s
            r.model_version = version
            if r.trace is not None:
                r.trace.admitted = admit_at
                r.trace.bucket = (Bp, P)
                r.trace.prefill_start = admit_at
                r.trace.pages_reserved = len(pages)
                r.trace.prefix_blocks_hit = len(matched)
                r.trace.suffix_len = len(suf)
                r.trace.model_version = version
        try:
            self.runtime.prefill(
                (Bp, P), tokens, mask, slot_ids, max_new,
                page_tables=page_tables, start=starts,
                suffix=bool(starts.any()),
            )
        except Exception:
            self._free.extend(slots)  # nothing was admitted
            for _, _, _, _, committed in reversed(plans):
                self.cache.rollback(committed)  # content never landed
            for _, _, _, pages, _ in plans:
                self.cache.release_all(pages)
            raise
        prefill_end = monotonic()
        saved = 0
        for (r, toks, matched, pages, committed), s in zip(plans, slots):
            if r.trace is not None:
                r.trace.prefill_end = prefill_end
            live = _LiveSlot(r, pages=pages, committed=committed)
            live.tokens = list(r.committed)
            self._live[s] = live
            self.events.append(("admit", s, r))
            self._spawn_speculator(s, r.tokens + r.committed)
            saved += len(matched) * ps
            self._prompt_tokens_total += len(toks)
            telemetry.observe("serve/pages_per_request", len(pages))
        self._fr_admitted += len(plans)
        self._prefix_tokens_saved += saved
        if saved:
            telemetry.inc("serve/prefix_tokens_saved", saved)
        telemetry.inc("serve/admissions", len(plans))
        for p in plans:
            telemetry.inc("serve/admissions",
                          labels={"tenant": p[0].tenant})
        telemetry.set_gauge("serve/slot_occupancy", self._occupancy())
        self._emit_pool_gauges()
        return not deferred

    # -- two classes of page, prefill in chunks --------------------------- #

    def _window_quota(self, total_blocks: int, first_block: int) -> int:
        """The most window-class pages one request maps at once: what a
        window reaches, plus the pages the longest prefill program writes
        in one call, plus one (a start inside a page) — or everything from
        ``first_block`` on, if the request is shorter than that."""
        rt = self.runtime
        longest = self.engine.prompt_classes()[-1][0]
        return min(total_blocks - first_block,
                   rt.ring_pages - 1 + -(-longest // rt.page_size))

    def _prefill_batch_classes(self, batch: List[Request], P: int,
                               extents) -> bool:
        """Paged admission of a model with two classes of page or latent
        pages, and of any prompt class served in chunks. As :meth:`_prefill_batch_paged`
        (match, reserve, commit, prefill the unmatched suffix; exhaustion
        of EITHER class queues), and besides: the match is cut back to
        where the window-class pages are still kept; a slot maps only the
        window-class pages its window reaches and reserves the most it
        will map at once; a suffix longer than the longest one-shot
        program is prefilled in chunks of that program's length through
        the prefix-context program, every chunk but the last aimed at the
        sentinel slot (it leaves its pages and no lanes)."""
        rt, cache = self.runtime, self.cache
        ps, two = rt.page_size, rt.two_class
        chaos.maybe_inject("serve_prefix_match")
        plans = []  # (request, toks, matched blocks, live slot)
        deferred: List[Request] = []
        for i, r in enumerate(batch):
            toks = (r.tokens + r.committed)[-P:]
            matched, wmap = (
                cache.match_classes(toks) if two
                else (cache.match(toks), {})
            )
            total = self.engine.request_page_need(
                len(toks), r.remaining_new_tokens()
            )
            fresh = cache.alloc(total - len(matched))
            quota = 0
            if fresh is not None and two:
                quota = self._window_quota(
                    total, max(len(matched) - cache.window_blocks, 0)
                )
                if cache.alloc_window(
                    0, reserve=max(quota - len(wmap), 0)
                ) is None:
                    cache.release_all(fresh)
                    fresh = None
            if fresh is None:
                cache.release_all(matched)
                if two:
                    cache.release_window(list(wmap.values()))
                deferred = batch[i:]
                break
            pages = matched + fresh
            live = _LiveSlot(r, pages=pages,
                             committed=cache.commit(toks, pages))
            live.tokens = list(r.committed)
            live.wmap, live.wquota = wmap, max(quota, len(wmap))
            live.pos0 = len(toks) - len(live.tokens)
            if rt.latent:
                live.page_arr = np.asarray(pages, np.int64)
            plans.append((r, toks, len(matched), live))
        if deferred:
            with self._cond:
                for r in reversed(deferred):
                    if r.trace is not None:  # page starvation -> re-queued
                        r.trace.queue_reentries += 1
                    self._queue.appendleft(r)
                telemetry.set_gauge("serve/queue_depth", len(self._queue))
            # the _admit exception handler must not fail re-queued rows
            batch[:] = [p[0] for p in plans]
        if not plans:
            self._emit_pool_gauges()
            return False

        slots = [self._free.pop() for _ in plans]
        admit_at = monotonic()
        version = self.engine.model_version
        try:
            chunk = self.engine.chunk_len(P)
            if chunk:
                # a class served in chunks is admitted one request at a
                # time: every chunk but the last through the chunk program
                (r, toks, m, live), = plans
                start = m * ps
                while len(toks) - start > chunk:
                    self._prefill_call(
                        (1, chunk), [(live, toks, start, start + chunk)],
                        [rt.num_slots], [1], final=False,
                    )
                    start += chunk
                last = next(p for p, _ in self.engine.prompt_classes()
                            if p >= len(toks) - start)
                self._stamp_admission(plans, (1, last), admit_at, version)
                self._prefill_call(
                    (1, last), [(live, toks, start, len(toks))], slots,
                    [r.remaining_new_tokens()],
                )
            else:
                Bp = next(b for b in extents if b >= len(plans))
                self._stamp_admission(plans, (Bp, P), admit_at, version)
                self._prefill_call(
                    (Bp, P),
                    [(live, toks, m * ps, len(toks))
                     for _, toks, m, live in plans],
                    slots, [p[0].remaining_new_tokens() for p in plans],
                )
        except Exception:
            self._free.extend(slots)  # nothing was admitted
            for _, _, _, live in reversed(plans):
                cache.rollback(live.committed)  # content never landed
            for _, _, _, live in plans:
                self._release_slot_pages(live)
            raise
        prefill_end = monotonic()
        saved = 0
        for (r, toks, m, live), s in zip(plans, slots):
            if r.trace is not None:
                r.trace.prefill_end = prefill_end
            self._live[s] = live
            self._wtable[s] = rt.num_window_pages
            for block, wpage in live.wmap.items():
                self._wtable[s, block % rt.ring_pages] = wpage
            self.events.append(("admit", s, r))
            saved += m * ps
            self._prompt_tokens_total += len(toks)
            telemetry.observe("serve/pages_per_request", len(live.pages))
        self._fr_admitted += len(plans)
        self._prefix_tokens_saved += saved
        if saved:
            telemetry.inc("serve/prefix_tokens_saved", saved)
        telemetry.inc("serve/admissions", len(plans))
        for p in plans:
            telemetry.inc("serve/admissions",
                          labels={"tenant": p[0].tenant})
        telemetry.set_gauge("serve/slot_occupancy", self._occupancy())
        self._emit_pool_gauges()
        return not deferred

    def _stamp_admission(self, plans, bucket, admit_at, version) -> None:
        ps = self.runtime.page_size
        for r, toks, m, live in plans:
            r.model_version = version
            if r.trace is not None:
                r.trace.admitted = admit_at
                r.trace.bucket = bucket
                r.trace.prefill_start = admit_at
                r.trace.pages_reserved = len(live.pages)
                r.trace.prefix_blocks_hit = m
                r.trace.suffix_len = len(toks) - m * ps
                r.trace.model_version = version

    def _prefill_call(self, bucket, rows, slot_ids, max_new,
                      final: bool = True) -> None:
        """One prefill program over ``rows`` [(live slot, tokens, start,
        end)]: tokens[start:end] of each row, right-padded into the
        bucket, written at logical positions from ``start`` on. Before the
        call each row is given the window-class pages the call writes;
        after it those pages go to the trie's blocks that own none, and
        the pages now wholly behind the row's window are released. A call
        that is not ``final`` is a chunk of a longer prompt (span
        ``serve/prefill_chunk``, counter ``serve/prefill_chunks``)."""
        rt, cache = self.runtime, self.cache
        Bp, P = bucket
        ps, two = rt.page_size, rt.two_class
        wb = cache.window_blocks
        tokens = np.full((Bp, P), self.engine.pad_token_id, np.int32)
        mask = np.zeros((Bp, P), np.int32)
        page_tables = np.full((Bp, rt.max_pages), rt.num_pages, np.int32)
        starts = np.zeros((Bp,), np.int32)
        new = np.ones((Bp,), np.int32)
        ids = np.full((Bp,), rt.num_slots, np.int32)
        wtables = wbase = None
        if two:
            wtables = np.full((Bp, rt.window_table_pages(P)),
                              rt.num_window_pages, np.int32)
            wbase = np.zeros((Bp,), np.int32)
        for j, (live, toks, start, end) in enumerate(rows):
            tokens[j, :end - start] = toks[start:end]
            mask[j, :end - start] = 1
            page_tables[j, :len(live.pages)] = live.pages
            starts[j], new[j], ids[j] = start, max_new[j], slot_ids[j]
            if two:
                for block in range(start // ps, -(-end // ps)):
                    if block not in live.wmap:
                        live.wmap[block], = cache.alloc_window(
                            1, reserved=True
                        )
                wbase[j] = max(start // ps - wb, 0)
                for block, wpage in live.wmap.items():
                    if 0 <= block - wbase[j] < wtables.shape[1]:
                        wtables[j, block - wbase[j]] = wpage
        span = supervisor.NULL_CM if final \
            else telemetry.span("serve/prefill_chunk")
        with span:
            rt.prefill(
                bucket, tokens, mask, ids, new, page_tables=page_tables,
                start=starts, suffix=bool(starts.any()) or not final,
                window_tables=wtables, window_base=wbase,
            )
        if not final:
            telemetry.inc("serve/prefill_chunks")
        if two:
            for live, toks, start, end in rows:
                for block in range(start // ps, end // ps):  # whole blocks
                    cache.attach_window(live.pages[block], live.wmap[block])
                self._release_behind(live, end)

    def _release_behind(self, live: _LiveSlot, next_pos: int,
                        slot: Optional[int] = None) -> None:
        """Release the window-class pages wholly behind the window of the
        query at ``next_pos`` and of every later one: logical pages before
        ``(next_pos - window + 1) // page_size``. The places return to
        the slot's reservation; a page the trie owns stays cached. A
        decoding ``slot``'s ring entries go back to the sentinel."""
        rt = self.runtime
        first = (next_pos - self.engine.spec.window + 1) // rt.page_size
        behind = [b for b in live.wmap if b < first]
        if behind:
            if slot is not None:
                for b in behind:
                    self._wtable[slot, b % rt.ring_pages] = \
                        rt.num_window_pages
            self.cache.release_window(
                [live.wmap.pop(b) for b in behind], behind=True,
                back_to_reserve=True,
            )
            telemetry.inc("serve/window_pages_freed", len(behind))
            self._fr_window_freed += len(behind)

    def _advance_windows(self) -> None:
        """Before a decode step: every live slot gets the window-class
        page its next token is written to (out of its reservation, when
        the token opens a page) and drops the pages that token's window
        has passed; the rings handed to the step follow."""
        rt = self.runtime
        for s, live in self._live.items():
            pos = live.pos0 + len(live.tokens)  # written by this step
            block = pos // rt.page_size
            if block not in live.wmap:
                live.wmap[block], = self.cache.alloc_window(
                    1, reserved=True
                )
                self._wtable[s, block % rt.ring_pages] = live.wmap[block]
            self._release_behind(live, pos, s)

    def _release_slot_pages(self, live: _LiveSlot) -> None:
        """Harvest (or a failed admission): drop every page reference the
        slot holds, of both classes, and what is left of its window-class
        reservation."""
        self.cache.release_all(live.pages)
        if self.runtime.two_class:
            self.cache.window_reserved -= live.wquota - len(live.wmap)
            self.cache.release_window(list(live.wmap.values()))
            live.wmap = {}

    def _note_moe_stats(self) -> None:
        """The expert layer's routing counts of the last step and of the
        prefills since the step before it (they rode the token fetch):
        [L, 4] each of (pairs_here, experts_hit, load_max, load_mean).
        ``pairs_here`` counts every pair computed, prefill included;
        ``experts_hit`` and the load are the decode step's own (a prefill
        chunk hits every expert held: it says nothing about the step)."""
        stats = self.runtime.moe_stats_host
        if not stats:
            return
        step = stats[-1]  # the decode step's own
        pairs = int(sum(a[:, 0].sum() for a in stats))
        hit = int(step[:, 1].sum())
        telemetry.inc("serve/moe/pairs_here", pairs)
        telemetry.inc("serve/moe/experts_hit", hit)
        # the step's worst layer: the fullest expert over the mean expert
        self._moe_load = float(
            np.max(step[:, 2] / np.maximum(step[:, 3], 1e-9))
        )
        telemetry.set_gauge("serve/moe/load_max_over_mean", self._moe_load)
        self._fr_pairs += pairs
        self._fr_pairs_step += int(step[:, 0].sum())
        self._fr_experts_hit += hit
        self.runtime.moe_stats_host = []

    def _hit_rate(self) -> float:
        return self._prefix_tokens_saved / max(self._prompt_tokens_total, 1)

    def _pages_in_use(self) -> Dict[str, int]:
        """Pages of each class that are not on a free list (mapped by a
        slot or kept by the trie)."""
        rt = self.runtime
        out = {"full": rt.num_pages - self.cache.free_pages()}
        if rt.two_class:
            out["window"] = (
                rt.num_window_pages - self.cache.window_free_pages()
            )
        return out

    def _emit_pool_gauges(self) -> None:
        telemetry.set_gauge("serve/pages_free", self.cache.free_pages())
        if self.runtime.two_class:
            for cls, n in self._pages_in_use().items():
                telemetry.set_gauge("serve/pages_in_use", n,
                                    labels={"class": cls})
        telemetry.set_gauge("serve/prefix_hit_rate", self._hit_rate())
        tel = telemetry.current()
        if tel is not None:
            hist = tel.registry.hists.get("serve/pages_per_request")
            if hist is not None:
                telemetry.set_gauge(
                    "serve/pages_per_request_p95", hist.quantile(0.95)
                )

    def pool_stats(self) -> Dict:
        """Host view of the KV pool — the /healthz ``kv`` block. Under a
        tp mesh every device holds a head-slice of EVERY page (tables are
        replicated host data), so the per-device footprint is the pool
        bytes over tp while page counts stay global."""
        from trlx_tpu.serve import layouts

        from trlx_tpu.telemetry.flops import kv_bytes_per_token

        kv_dtype = self.engine.serve.kv_dtype
        return {
            "kv_dtype": kv_dtype,
            "kv_bytes_per_token": kv_bytes_per_token(
                self.engine.spec, kv_dtype
            ),
            "slots": self.runtime.num_slots,
            "pool_gb_per_device": round(
                layouts.tree_bytes_per_device(self.runtime.pool) / 2**30,
                6,
            ),
            "page_size": self.runtime.page_size,
            "pages_total": self.runtime.num_pages,
            "pages_free": self.cache.free_pages(),
            "pages_cached": self.cache.cached_pages(),
            "evicted_pages": self.cache.evicted_pages,
            "prefix_hit_rate": round(self._hit_rate(), 4),
            "prefix_tokens_saved": self._prefix_tokens_saved,
        }

    def _clamp_proposal(self, live: _LiveSlot, n: int) -> int:
        """Cap a slot's proposal at the request's remaining budget: the
        free token spends one, so at most ``remaining - 1`` proposals
        could ever be accepted (the device clamps identically — this
        just skips shipping doomed proposals)."""
        remaining = live.request.max_new_tokens - len(live.tokens)
        return max(0, min(n, self.spec_k, remaining - 1))

    def _spec_acceptance_rate(self) -> float:
        return self._spec_accepted_total / max(self._spec_proposed_total, 1)

    def _gather_proposals(self):
        """Host half of the propose->verify->accept loop: one [S, K]
        proposal batch from the active tier — per-slot n-gram lookup
        (backed by the radix cache's committed blocks) or the draft
        model. Returns ``(proposals, n_proposed)`` or None when every
        row is dry; None falls the step back to plain ``decode_step``,
        so the worst case is exactly today's behavior. Any
        proposal-side fault (including the ``serve_speculate`` chaos
        seam) also returns None: nothing was dispatched yet, so nothing
        is half-committed — the step completes unspeculated and
        ``serve/spec_fallbacks`` counts the event."""
        try:
            chaos.maybe_inject("serve_speculate")
            S, K = self.runtime.num_slots, self.spec_k
            props = np.zeros((S, K), np.int32)
            nprops = np.zeros((S,), np.int32)
            if self._spec_mode == "draft" and self._draft is not None:
                histories: List[Optional[List[int]]] = [None] * S
                for s, live in self._live.items():
                    histories[s] = live.request.tokens + live.tokens
                drafted = self._draft.propose(histories)
                for s, live in self._live.items():
                    p = drafted[s][:K]
                    n = self._clamp_proposal(live, len(p))
                    props[s, :n] = p[:n]
                    nprops[s] = n
            else:
                for s, live in self._live.items():
                    sp = self._speculators.get(s)
                    if sp is None:
                        continue
                    p = sp.propose(self.cache)[:K]
                    n = self._clamp_proposal(live, len(p))
                    props[s, :n] = p[:n]
                    nprops[s] = n
            if not nprops.any():
                return None
            return props, nprops
        except Exception:
            telemetry.inc("serve/spec_fallbacks")
            return None

    def _step(self) -> None:
        plan = None
        with supervisor.phase("serve_decode"):
            chaos.maybe_inject("serve_decode")
            seed = self.engine.serve.seed + self._step_counter
            self._step_counter += 1
            if self.spec_k > 0 and self._live:
                plan = self._gather_proposals()
            if plan is not None:
                # speculative step: K proposals + the free token score
                # in ONE verify pass; each slot emits its longest
                # greedy-matching prefix (>= 1 token — never worse than
                # a plain step)
                props, nprops = plan
                cand, counts, finished = self.runtime.verify(
                    seed, props, nprops
                )
                counts = np.asarray(counts, np.int32)
                proposed = int(nprops.sum())
                accepted = int(np.maximum(counts - 1, 0).sum())
                span = self.runtime.VERIFY_SPAN
            else:
                if self.runtime.two_class:
                    self._advance_windows()
                    tok, emitted, finished = self.runtime.step(
                        seed, self._wtable
                    )
                else:
                    tok, emitted, finished = self.runtime.step(seed)
                if self.runtime.routed:
                    self._note_moe_stats()
                # plain decode is the counts <= 1 degenerate case of the
                # same harvest shape
                cand = np.asarray(tok)[:, None]
                counts = np.asarray(emitted).astype(np.int32)
                proposed = accepted = 0
                span = self.runtime.STEP_SPAN
            supervisor.beat()
        if self._starved:
            telemetry.inc("serve/preempted_steps")
        if plan is not None:
            if proposed:
                telemetry.inc("serve/spec_proposed", proposed)
            if accepted:
                # each accepted proposal is one decode_step the target
                # model never ran — under greedy verify the two counters
                # are equal by construction
                telemetry.inc("serve/spec_accepted", accepted)
                telemetry.inc("serve/spec_steps_saved", accepted)
            self._spec_proposed_total += proposed
            self._spec_accepted_total += accepted
            self._fr_spec_proposed += proposed
            self._fr_spec_accepted += accepted
            telemetry.set_gauge(
                "serve/spec_acceptance_rate", self._spec_acceptance_rate()
            )
        with telemetry.span("serve/harvest"):
            self._harvest(cand, counts, finished, span)

    def _harvest(self, cand, counts, finished, span: str) -> None:
        """Host half of a step: hand each live slot its tokens, complete
        and free the finished ones. ``_harvest_s`` is the flight record's
        ``harvest_ms``."""
        done_at = monotonic()
        emitted_total = 0
        for slot in list(self._live):
            live = self._live[slot]
            c = int(counts[slot])
            if c:
                toks = [int(t) for t in cand[slot, :c]]
                live.tokens.extend(toks)
                emitted_total += c
                sp = self._speculators.get(slot)
                if sp is not None:
                    sp.append(toks)
                if live.request.trace is not None:
                    for _ in range(c):
                        live.request.trace.note_token(done_at)
            if finished[slot]:
                req = live.request
                req.result = live.tokens
                req.latency_s = done_at - req.enqueued_at
                if req.trace is not None:
                    req.trace.harvested = done_at
                    req.trace.complete("slots", self._slo_s)
                req.done.set()
                del self._live[slot]
                self._speculators.pop(slot, None)
                self._free.append(slot)
                # committed (trie-owned) pages stay cached at refcount 0
                # — hit-able until LRU eviction; the rest return to the
                # free list
                self._release_slot_pages(live)
                telemetry.set_gauge(
                    "serve/pages_free", self.cache.free_pages()
                )
                self.events.append(("free", slot, req))
                self._fr_evicted += 1
                telemetry.inc("serve/evictions")
                telemetry.inc("serve/responses")
        if emitted_total:
            telemetry.inc("serve/generated_tokens", emitted_total)
            tel = telemetry.current()
            if tel is not None:
                hist = tel.registry.hists.get(f"time/{span}")
                if hist is not None and hist.last > 0:
                    telemetry.set_gauge(
                        "serve/tokens_per_sec", emitted_total / hist.last
                    )
        telemetry.set_gauge("serve/slot_occupancy", self._occupancy())
        self._harvest_s = monotonic() - done_at

    def _reset_cache(self) -> None:
        """Fresh allocator + radix tree. The lanes are gone whenever this
        runs, so every page mapping (and every cached prefix whose
        content can no longer be trusted — poisoned step, or KV computed
        under pre-swap weights) resets with them."""
        self.cache = self._new_cache()
        self._wtable[:] = self.runtime.num_window_pages
        telemetry.set_gauge("serve/pages_free", self.cache.free_pages())

    def _fail_live(self, error: BaseException) -> None:
        """Last-resort containment (double fault, or replay disabled):
        fail every in-flight request, free all slots, reset the device
        lanes, keep the loop serving."""
        live = list(self._live.values())
        self._live.clear()
        self._speculators.clear()
        self._free = list(range(self.runtime.num_slots))
        telemetry.inc("serve/request_errors", len(live))
        # contain FIRST, signal last: a waiter released by done.set()
        # must observe the post-reset pool/cache, not a torn intermediate
        self.runtime.reset_lanes()
        self._reset_cache()
        for s in live:
            s.request.error = error
            s.request.done.set()
        telemetry.set_gauge("serve/slot_occupancy", 0.0)

    def _requeue_for_replay(self, requests: List[Request],
                            error: BaseException) -> None:
        """Journal-and-requeue: each request goes back to the queue head
        (original admission order) carrying its committed tokens, unless
        its ``serve.max_replays`` budget is spent or its grown effective
        prompt no longer fits the bucket lattice — those complete with
        ReplayExhausted (HTTP 503) and a reason."""
        max_replays = int(getattr(self.engine.serve, "max_replays", 2))
        survivors = []
        for req in requests:
            req.replays += 1
            if req.trace is not None:
                req.trace.replays = req.replays
                req.trace.queue_reentries += 1
            if req.replays > max_replays:
                telemetry.inc("serve/request_errors")
                req.error = ReplayExhausted(
                    f"request hit {max_replays} engine faults "
                    f"(serve.max_replays) and will not be replayed "
                    f"again; last fault: {error!r}"
                )
                req.done.set()
                continue
            try:
                # the committed prefix is part of the replay prompt, so
                # the admission bucket can grow a class — or grow PAST
                # the lattice, which ends the request with a reason
                # instead of a crash
                req.shape = self.engine.pick_shape(
                    len(req.tokens) + len(req.committed),
                    req.remaining_new_tokens(),
                )
            except ValueError as e:
                telemetry.inc("serve/request_errors")
                req.error = ReplayExhausted(
                    f"cannot replay: prompt + {len(req.committed)} "
                    f"committed tokens no longer fit the bucket "
                    f"lattice ({e})"
                )
                req.done.set()
                continue
            survivors.append(req)
        if survivors:
            self._replayed_requests += len(survivors)
            telemetry.inc("serve/replays", len(survivors))
            with self._cond:
                for req in sorted(
                    survivors, key=lambda r: r.seq, reverse=True
                ):
                    self._queue.appendleft(req)
                telemetry.set_gauge("serve/queue_depth", len(self._queue))
                self._cond.notify_all()

    def _recover_step(self, error: BaseException) -> None:
        """Poisoned-step recovery: dump the flight recorder (the engine
        state that led INTO the poisoned step is exactly what the ring
        holds), reset lanes + cache, then re-queue — not fail — every
        in-flight request with its committed tokens journaled. The
        ``serve_replay`` chaos seam fires before any mutation; a fault
        there (or during the reset itself) is a double fault and falls
        back to :meth:`_fail_live`."""
        if self.flight is not None:
            self.flight.dump(f"poisoned step: {error!r}")
        try:
            chaos.maybe_inject("serve_replay")
        except Exception as twice:
            self._fail_live(twice)
            return
        live = list(self._live.values())
        self._live.clear()
        # speculation state is derived from per-slot histories that are
        # about to be re-journaled — replay re-admission rebuilds it
        # fresh, so a poisoned step can never leak a stale index
        self._speculators.clear()
        self._free = list(range(self.runtime.num_slots))
        try:
            self.runtime.reset_lanes()
            self._reset_cache()
        except Exception as twice:
            telemetry.inc("serve/request_errors", len(live))
            for s in live:
                s.request.error = twice
                s.request.done.set()
            telemetry.set_gauge("serve/slot_occupancy", 0.0)
            return
        for s in live:
            # journal BEFORE requeue: live.tokens is committed-so-far
            # (prior journal + tokens harvested since re-admission)
            s.request.committed = list(s.tokens)
        self._requeue_for_replay([s.request for s in live], error)
        telemetry.set_gauge("serve/slot_occupancy", 0.0)

    # -- graceful drain ---------------------------------------------------- #

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful drain: flip admission to Draining (HTTP 429), keep
        admitting ALREADY-QUEUED requests and stepping until everything
        in flight finishes, then return. Requests still unfinished at
        the deadline (default ``serve.drain_timeout``) complete with
        DrainTimeout (HTTP 503) — shed with a reason, never dropped.
        Dumps the flight recorder on entry so a killed replica's
        post-mortem has engine state. Returns True when the drain was
        clean (nothing shed). Idempotent; the worker is stopped on the
        way out."""
        if timeout is None:
            timeout = float(getattr(self.engine.serve, "drain_timeout",
                                    30.0))
        with self._cond:
            already = self._draining
            self._draining = True
            self._drain_deadline = monotonic() + float(timeout)
            self._cond.notify_all()
        if not already:
            telemetry.inc("serve/drains")
            if self.flight is not None:
                self.flight.dump("drain")
        if self._thread is None:
            # never started: nothing in flight can ever finish
            self._drain_expire()
        else:
            self._drained.wait(timeout=float(timeout) + 10.0)
        with self._cond:
            clean = not self._queue and not self._live
        self.stop()
        return clean

    def _drain_expire(self) -> None:
        """Drain deadline passed: complete everything still in flight
        with DrainTimeout (worker thread, or inline when the worker was
        never started)."""
        with self._cond:
            pending = list(self._queue)
            self._queue.clear()
            telemetry.set_gauge("serve/queue_depth", 0)
        live = list(self._live.values())
        self._live.clear()
        self._speculators.clear()
        self._free = list(range(self.runtime.num_slots))
        victims = pending + [s.request for s in live]
        if victims:
            telemetry.inc("serve/request_errors", len(victims))
        if live:
            self.runtime.reset_lanes()
            self._reset_cache()
        for req in victims:
            req.error = DrainTimeout(
                "server drain deadline (serve.drain_timeout) passed "
                "with the request still in flight; retry against "
                "another replica"
            )
            req.done.set()
        telemetry.set_gauge("serve/slot_occupancy", 0.0)
        self._drained.set()

    # -- live checkpoint hot-swap ------------------------------------------ #

    def request_swap(self, params, label: str = "") -> Dict:
        """Hot-swap the serving weights to ``params`` (a full TRAINING
        param tree; the engine strips it to decode views). The swap is
        worker-applied at a step boundary: admission pauses (submit
        still accepts — the endpoint never refuses connections), live
        slots finish on their admitted version, then the worker resets
        KV state, installs the candidate into same-sharding buffers,
        smoke-probes one bucket for non-finite logits, and either
        commits (``serve/model_version`` bumps) or rolls back to the old
        views. Zero recompiles either way — the compiled executables
        take the weights as ARGUMENTS. Blocks until applied; returns
        ``{"reloaded", "model_version", ...}``."""
        box = {
            "params": params, "label": label,
            "done": threading.Event(), "result": None,
        }
        with self._cond:
            if self._pending_swap is not None:
                return {
                    "reloaded": False,
                    "model_version": self.engine.model_version,
                    "reason": "another reload is already in progress",
                }
            self._pending_swap = box
            self._cond.notify_all()
        if self._thread is None:
            self._apply_pending_swap()  # idle engine: swap inline
        else:
            box["done"].wait(
                timeout=float(self.engine.serve.request_timeout) + 30.0
            )
        if box["result"] is None:
            return {
                "reloaded": False,
                "model_version": self.engine.model_version,
                "reason": "reload timed out waiting for a step boundary",
            }
        return box["result"]

    def _apply_pending_swap(self) -> None:
        """Worker-side half of :meth:`request_swap`; runs only with
        ``_live`` empty (the step boundary). Probe failure — shape/dtype
        drift, non-finite logits, a ``serve_reload`` chaos fault —
        restores the old view references and the engine keeps serving
        version N."""
        # snapshot the box under the cond: request_swap publishes it
        # from the HTTP thread while the worker polls for it
        with self._cond:
            box = self._pending_swap
        if box is None:
            return
        e = self.engine
        old_version = e.model_version
        old_views = (e.blocks, e.embed, e.ln_f)
        try:
            chaos.maybe_inject("serve_reload")
            views = e.strip_for_serve(box["params"])
            e.validate_swap(views)
            # KV pages + cached prefixes were computed under the OLD
            # weights — wrong under the new ones. Lanes are already
            # empty (step-boundary swap); reset the cache with them.
            self.runtime.reset_lanes()
            self._reset_cache()
            e.install_views(views)
            self._probe_swap()
        except Exception as err:
            e.install_views(old_views)  # rollback: old refs still alive
            self.runtime.reset_lanes()
            self._reset_cache()
            telemetry.inc("serve/reload_failures")
            box["result"] = {
                "reloaded": False, "model_version": old_version,
                "reason": f"{type(err).__name__}: {err}",
            }
        else:
            version = e.commit_version(box["label"] or None)
            telemetry.inc("serve/reloads")
            box["result"] = {
                "reloaded": True, "model_version": version,
                "previous_version": old_version,
            }
        # the box is consumed; clear under the cond so a request_swap
        # racing this publish sees either the old pending box or None,
        # never a torn in-between
        with self._cond:
            self._pending_swap = None
        box["done"].set()

    def _probe_swap(self) -> None:
        """One-bucket smoke probe through the ALREADY-COMPILED smallest
        prefill executable (zero recompiles): prefill a dummy token into
        real slot 0 and require finite logits under the candidate
        weights. The lanes are reset afterwards — the probe leaves no
        live lane (or page mapping) behind."""
        rt = self.runtime
        P, extents = next(iter(self.engine.prompt_classes()))
        Bp = extents[0]
        pad = self.engine.pad_token_id
        tokens = np.full((Bp, P), pad, np.int32)
        mask = np.zeros((Bp, P), np.int32)
        tokens[:, 0] = 0
        mask[:, 0] = 1
        slot_ids = np.full((Bp,), rt.num_slots, np.int32)
        slot_ids[0] = 0  # ONE real row — the probe reads its logits
        page_tables = np.full((Bp, rt.max_pages), rt.num_pages, np.int32)
        need = self.engine.request_page_need(1, 1)
        # the cache was reset just above: pages 0..need-1 are free and
        # unmapped, and the post-probe reset unmaps them again
        page_tables[0, :need] = np.arange(need, dtype=np.int32)
        rt.prefill(
            (Bp, P), tokens, mask, slot_ids, np.ones((Bp,), np.int32),
            page_tables=page_tables, start=np.zeros((Bp,), np.int32),
        )
        logits = np.asarray(rt.state.logits[0])
        rt.reset_lanes()
        if not np.all(np.isfinite(logits)):
            raise ValueError(
                "smoke probe produced non-finite logits under the "
                "candidate checkpoint; rolling back"
            )

    def _record_step(self, start: float, end: float) -> None:
        """One compact flight-recorder record per engine step; the
        admitted/evicted deltas accumulated since the last record reset
        here so each record owns exactly its step's churn."""
        if self.flight is None:
            self._fr_admitted = self._fr_evicted = 0
            self._fr_spec_proposed = self._fr_spec_accepted = 0
            return
        rec = {
            "step": self._step_counter,
            "t": round(end, 4),
            "active": len(self._live),
            "finished": self._fr_evicted,
            "admitted": self._fr_admitted,
            "occupancy": round(self._occupancy(), 4),
            "step_ms": round((end - start) * 1000.0, 3),
            # the iteration's host phases: admission before the step
            # (prefill dispatches included), the wait for the step's
            # result, and the harvest after it
            "admit_ms": round(self._admit_s * 1000.0, 3),
            "fetch_ms": round(self.runtime.fetch_s * 1000.0, 3),
            "harvest_ms": round(self._harvest_s * 1000.0, 3),
            "pages_free": self.cache.free_pages(),
        }
        if self.runtime.two_class:
            in_use = self._pages_in_use()
            rec.update(pages_full=in_use["full"],
                       pages_window=in_use["window"],
                       window_freed=self._fr_window_freed)
            self._fr_window_freed = 0
        if self.runtime.routed:
            rec.update(pairs_here=self._fr_pairs,
                       pairs_step=self._fr_pairs_step,
                       experts_hit=self._fr_experts_hit,
                       moe_load=round(self._moe_load, 3))
            self._fr_pairs = self._fr_pairs_step = self._fr_experts_hit = 0
        if self.runtime.latent:
            rec["pages_read"], rec["shared_pages_read"] = self._pages_read()
        if self.spec_k > 0:
            # a speculation regression (acceptance collapsing to 0) must
            # be visible in a stall dump, not only in the counters
            rec["spec_proposed"] = self._fr_spec_proposed
            rec["spec_accepted"] = self._fr_spec_accepted
        self.flight.record(**rec)
        self._fr_admitted = self._fr_evicted = 0
        self._fr_spec_proposed = self._fr_spec_accepted = 0

    def _pages_read(self):
        """(pages the live slots' contexts reach, those of them that
        another live slot reaches too): what the last decode step read of
        a latent pool, and how much of it the prefix cache let two slots
        read from one copy."""
        ps = self.runtime.page_size
        reach = [
            live.page_arr[:(live.pos0 + len(live.tokens) - 1) // ps + 1]
            for live in self._live.values() if live.page_arr is not None
        ]
        if not reach:
            return 0, 0
        pages = np.concatenate(reach)
        readers = np.bincount(pages, minlength=self.runtime.num_pages)
        return int(pages.size), int((readers[pages] > 1).sum())

    def dump_flight_recorder(self) -> None:
        """Supervisor stall hook (``RunSupervisor.add_dump_fn``): print
        the ring to stderr next to the watchdog's all-thread stack dump
        so a stall is attributable to a concrete engine state."""
        if self.flight is not None:
            self.flight.dump("watchdog stall")

    def debug_state(self) -> Dict:
        """Live engine state for ``GET /debug/state``: queue/slot map,
        the flight-recorder ring, and the KV pool/radix stats. Read from
        the HTTP thread without a lock — every container is copied (or
        read atomically) under the GIL, so a torn view is impossible and
        a slightly stale one is fine for a debug endpoint."""
        slots = {}
        for s, live in list(self._live.items()):
            req = live.request
            slots[str(s)] = {
                "trace_id": req.trace.trace_id
                if req.trace is not None else None,
                "prompt_len": len(req.tokens),
                "max_new_tokens": req.max_new_tokens,
                "tokens_emitted": len(live.tokens),
                "pages": len(live.pages),
                "tenant": req.tenant,
            }
        return {
            "scheduler": "slots",
            "step": self._step_counter,
            "queue_depth": len(self._queue),
            "free_slots": len(self._free),
            "starved": self._starved,
            "degraded": self._degraded(),
            "pressure": self.pressure(),
            "tenants": (
                self.tenants.snapshot(monotonic())
                if self.tenants.enabled else {}
            ),
            "draining": self._draining,
            "model_version": self.engine.model_version,
            "replayed_requests": self._replayed_requests,
            "last_step_ms": round(self._last_step_ms, 3),
            "slots": slots,
            "flight_recorder": (
                self.flight.snapshot() if self.flight is not None else []
            ),
            "flight_dumps": self.flight.dumps if self.flight else 0,
            "kv": self.pool_stats(),
            "mesh": self.engine.mesh_info(),
            "speculation": {
                "mode": self._spec_mode,
                "k": self.spec_k,
                "proposed": self._spec_proposed_total,
                "accepted": self._spec_accepted_total,
                "acceptance_rate": round(self._spec_acceptance_rate(), 4),
            },
        }

    def _run(self) -> None:
        sup_cm = self.run_supervisor
        if sup_cm is None:
            sup_cm = supervisor.NULL_CM
        with sup_cm:
            while not self._stop.is_set():
                # one coherent snapshot of the cross-thread poll state
                # per iteration (the HTTP thread publishes swaps and
                # drains under the cond); _live/_free are worker-owned
                with self._cond:
                    swap_pending = self._pending_swap is not None
                    draining = self._draining
                    queue_empty = not self._queue
                self._update_brownout(monotonic())
                admit_start = monotonic()
                if swap_pending:
                    # admission pauses so _live can empty; queued +
                    # in-flight requests finish on the ADMITTED version
                    if not self._live:
                        self._apply_pending_swap()
                        continue
                else:
                    # an empty queue leaves _admit nothing to place: it
                    # gets no span of its own
                    with (supervisor.NULL_CM if queue_empty
                          else telemetry.span("serve/admit")):
                        self._admit()
                self._admit_s = monotonic() - admit_start
                if draining:
                    if not self._live and queue_empty:
                        self._drained.set()
                    elif monotonic() >= self._drain_deadline:
                        self._drain_expire()
                if not self._live:
                    with self._cond:
                        if not self._queue and not self._stop.is_set() \
                                and self._pending_swap is None:
                            self._cond.wait(timeout=0.1)
                    continue
                step_start = monotonic()
                try:
                    self._step()
                except Exception as e:
                    self._recover_step(e)
                else:
                    end = monotonic()
                    self._last_step_ms = (end - step_start) * 1000.0
                    self._record_step(step_start, end)
