# Style targets (parity: reference Makefile:1-14, black/isort/flake8 there).
# ruff covers formatting-adjacent lint + import order; graftlint
# (trlx_tpu/analysis, `make lint`) enforces the project's own invariant
# rules — JAX hazards, lock discipline, telemetry/chaos contracts, and
# the core style subset — with zero dependencies, so it runs everywhere.

.PHONY: style check lint test faults telemetry chaos serve serve-mesh serve-soak serve-chaos router kernels defense fleet-chaos obs overload overload-drill spec

# graftlint: the repo's AST invariant checker (docs "Static analysis").
# Exit 1 on any finding; `python -m trlx_tpu.analysis --list-rules` for
# the catalog. No baseline file — HEAD is always clean. --budget asserts
# the walltime contract (whole repo incl. the concurrency tier's thread
# model in < 10 s) so lint stays cheap enough to gate every commit;
# `--changed-only <ref>` is the pre-commit fast path.
lint:
	python -m trlx_tpu.analysis --budget 10

check: lint kernels defense obs overload spec
	@command -v ruff >/dev/null 2>&1 \
		&& ruff check trlx_tpu tests examples __graft_entry__.py \
		|| true

# Pallas kernel tier (trlx_tpu/ops): the fused-attention train kernels
# and the paged-attention decode kernel, run in interpret mode on CPU —
# the parity oracle the kernel-parity-tested lint rule points at.
# Covers kernel-vs-jnp greedy/logit parity (bf16 bit-identical tokens,
# int8 within tolerance), the int8 KV round-trip bound, and the
# serve-engine sweeps with serve.attention: pallas. On a real TPU the
# same tests exercise the compiled kernels.
kernels:
	env JAX_PLATFORMS=cpu python -m pytest \
		tests/test_pallas_attention.py tests/test_paged_kernel.py \
		-q -m 'not slow'

style:
	@command -v ruff >/dev/null 2>&1 \
		&& ruff check --fix trlx_tpu tests examples __graft_entry__.py \
		|| python -m trlx_tpu.analysis

# the tier-1 contract (ROADMAP.md): CPU-pinned so a dev-box run never
# grabs an accelerator, and 'not slow' so it matches what CI gates on
test:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -x -q -m 'not slow'

# fault-injection tier: atomic-checkpoint crash scenarios, divergence
# containment (NaN skip / rollback / second-strike abort), flaky host
# seams, preemption corner cases. Part of the non-slow tier-1 set; this
# target runs just them for a fast robustness signal.
faults:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_faults.py \
		tests/test_checkpoint.py -q

# observability tier: metrics-registry semantics, span tracing +
# Chrome-trace JSONL validity, fault-counter wiring, tracker fixes, and
# the CPU smoke learn() emission (time/*, throughput/*, fault/* keys +
# telemetry.json / trace.jsonl). Part of the non-slow tier-1 set.
telemetry:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py \
		tests/test_trackers.py -q

# run-supervisor tier: the deterministic chaos-injection matrix
# (hang/exc/slow/sigterm at named seams) driving watchdog stall
# detection + stack dumps, bounded host-seam timeouts, walltime-deadline
# exits, escalation, and the checkpoint-and-exit containment. Part of
# the non-slow tier-1 set; this target runs just them.
chaos:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_supervisor.py -q

# inference-serving tier (trlx_tpu/serve, docs "Serving"): bucketed AOT
# decode engine (checkpoint restore + strip, zero steady-state
# recompiles), the continuous-batching slot scheduler (test_slots.py:
# parity vs one-shot generate(), queue-overflow admission control,
# step-level harvest + slot reuse mid-decode, occupancy
# metrics, and the chaos drill on the serve_admit seam — hang = watchdog
# stall, exc = contained batch failure), the paged KV pool + radix
# prefix cache (test_paged.py: allocator/radix semantics, greedy-parity
# sweep across page sizes, prefix-hit prefill skipping, exhaustion
# queue-not-crash, serve_prefix_match chaos drill, pool health on
# /healthz), HTTP endpoint parity e2e, the
# serve_decode/serve_request containment paths, and the
# request-lifecycle observability layer (test_request_trace.py:
# RequestTrace/TTFT/ITL semantics, Perfetto span export validity,
# Prometheus /metrics exposition, /debug/state schema, flight-recorder
# dumps on poisoned steps and watchdog stalls), and the crash-only
# serving lifecycle (test_lifecycle.py: restart-recovery greedy-parity
# sweep across page sizes x kill points, deadline shed + priority
# admission, graceful drain under load with 429 + Retry-After at the
# door, live checkpoint hot-swap under load + probe rollback + LATEST
# watcher). Part of the non-slow tier-1 set; this target runs just
# them. The slow-marked soak (hundreds of mixed-length requests, zero
# recompiles, zero slot leaks) is opt-in via `make serve-soak`; the
# chaos lifecycle soak (injected poison/reload + a real-SIGTERM
# subprocess drill) via `make serve-chaos`.
serve:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_serve.py \
		tests/test_slots.py tests/test_paged.py \
		tests/test_request_trace.py tests/test_lifecycle.py \
		-q -m 'not slow'

# sharded-serving rig (tests/test_serve_mesh.py): tp=2 and tp=2 x
# fsdp=2 engines on CPU-simulated devices — greedy bit-parity vs the
# single-device engine across page sizes, replay + hot-swap under the
# mesh, zero recompiles, zero page leaks. Slow-marked (per-mesh bucket
# compiles would blow the tier-1 walltime budget) so this target is the
# way to run them; the multichip dryrun's serve leg is the fast canary.
# The forced device count is set EXPLICITLY here so the target works
# outside the pytest conftest (which forces the same 8 devices for
# in-process tier-1 runs).
serve-mesh:
	env JAX_PLATFORMS=cpu \
		XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		python -m pytest tests/test_serve_mesh.py -q -m mesh

# fleet-router tier (trlx_tpu/router, docs "Fleet routing"): the
# stdlib-only front-end that spreads /generate over N engine replicas —
# prefix-affinity placement (block math bit-identical to serve/paged.py,
# greedy-parity asserted per routed response), health-driven membership
# with zero-loss failover onto a second replica, router-side rolling
# checkpoint upgrades (fence -> quiesce -> /admin/reload -> smoke ->
# re-admit, fleet never below N-1 admitting, cross-version parity), the
# 503-not-a-hang empty-fleet path, X-Hop-Count forwarding/508 cap, the
# router/* metric family on the router's own /metrics, and chaos drills
# on the router_route / router_probe / router_rollout seams. Part of
# the non-slow tier-1 set; this target runs just them.
router:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_router.py \
		-q -m 'not slow'

# defense-in-depth tier (docs "Fault tolerance", fleet containment):
# the fast containment units — circuit-breaker state machine, retry
# budget accounting + typed-503 exhaustion, hedge racing and its chaos
# seam, response validation / failover over stub replicas, prober
# debounce, and the checkpoint manifest (bit-flip / truncation / torn
# meta detection, quarantine, run-dir fallback, component-scoped
# verify). Stub-backed and CPU-cheap, so it gates `make check`; the
# live-replica drills are the slow `make fleet-chaos` tier.
defense:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_defense.py \
		-q -m 'not slow'

# fleet observability tier (docs "Observability"): labeled-metric
# storage + Prometheus exposition (label sets, cumulative _bucket
# histogram family, sanitize-collision disambiguation), the SLO
# window/burn-rate engine, stitched fleet traces (FleetTrace ring,
# sampled access log with tail capture + rotation), and the
# `python -m trlx_tpu.obs` CLI — including a subprocess smoke run of
# summarize/trace/tail against the fixture access.jsonl. Stub-backed
# and CPU-cheap, so it gates `make check`.
obs:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_obs.py \
		-q -m 'not slow'

# fleet chaos harness: router + live replicas through the containment
# drills end to end — replica killed mid-trace (zero lost requests,
# failovers within the retry budget, oracle bit-parity), corrupt
# checkpoint published mid-rollout (rollout aborts, fleet stays on the
# old version, bad step quarantined), boot fallback past a corrupt
# newest step, hedged requests against real engines, and a
# corrupt-response backend contained by its breaker. Slow-marked (real
# engine builds + warmups); opt-in via this target.
fleet-chaos:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_chaos.py \
		-q -m slow

# multi-tenant overload-containment tier (docs "Fault tolerance",
# overload containment): the fast units — per-tenant token-bucket /
# queue-share / inflight quota math, typed 429 QuotaExceeded with
# tenant-derived Retry-After (never a global QueueFull for an
# over-quota tenant), priority-aging starvation bound, brownout
# hysteresis + best-effort max_new_tokens clamp, the /readyz pressure
# block, the serve_quota chaos seam, and router-side pressure shedding
# + per-tenant retry-budget slices over stub backends. Stub-backed and
# CPU-cheap, so it gates `make check`; the live three-tenant isolation
# drill (4x aggressor, premium goodput floor, zero recompiles, greedy
# prefix-parity for browned-out completions) is the slow
# `make overload-drill` tier.
overload:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_overload.py \
		-q -m 'not slow'

overload-drill:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_overload.py \
		-q -m slow

# speculative-decoding tier (trlx_tpu/serve/speculate.py + the
# verify_step executable, docs "Serving" > "Speculative decoding"):
# n-gram index / radix peek proposal semantics, the greedy-parity sweep
# (speculation on == off bit-identical across page sizes x KV dtypes x
# staggered admission, zero recompiles), the >= 1.5 effective-tokens-
# per-step floor on repetitive traces, serve_speculate chaos drills
# (exc = clean fallback to plain decode, hang = watchdog-attributable
# serve_decode stall), poisoned-step speculation-state reset, the
# draft-model tier, and the config/CLI gating. CPU-cheap, so it gates
# `make check`; the slow speculation soak rides `make serve-soak`.
spec:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_speculation.py \
		-q -m 'not slow'

serve-soak:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_slots.py \
		tests/test_paged.py tests/test_speculation.py -q -m slow

# crash-only lifecycle soak: waves of mixed traffic with injected
# poisoned steps/admissions and a live hot-swap (zero lost requests,
# zero page leaks, zero recompiles, clean drain), plus the subprocess
# SIGTERM drill (in-flight work finishes, process exits 0)
serve-chaos:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_lifecycle.py \
		-q -m slow
