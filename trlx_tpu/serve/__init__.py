"""Inference serving: checkpoint-to-endpoint engine for trained policies.

The first subsystem on the inference side of the ROADMAP's north star
("serves heavy traffic"): everything before this package hardened the
*training* workload; this one consumes its artifacts. A checkpoint
written by the trainers (trlx_tpu.utils.checkpoint) becomes a long-lived
local HTTP endpoint in one command::

    python -m trlx_tpu.serve --checkpoint ckpts/ppo_sentiments

Three layers (docs/source/serving.rst):

- :class:`InferenceEngine` (serve.engine) — restores the policy (params
  only; ref branch / value head / optimizer state stripped) and holds
  the static (batch, prompt_len, gen_len) **bucket lattice** every
  compiled shape comes from, so steady-state requests never recompile
  (``compile/recompiles == 0`` is the serving invariant);
- :class:`SlotScheduler` (serve.slots) — continuous batching:
  step-level scheduling over a persistent device-resident KV **slot
  pool**; at every decode step finished rows (EOS / per-request
  ``max_new_tokens``) are harvested, their slots freed immediately, and
  queued requests admitted via bucketed prefill — short requests never
  wait for long ones. The pool is block-granular (fixed-size KV pages +
  per-slot page tables, host free-list allocator) with radix-tree
  **prefix caching** (serve.paged): admission reserves pages for each
  request's own length instead of the worst case, and prompts sharing a
  committed prefix skip re-prefilling it. What a request is and the
  typed ways one is refused (``max_queue`` admission control, tenant
  quotas) live in serve.admission;
- :class:`InferenceServer` (serve.server) — stdlib ThreadingHTTPServer
  JSON API (``POST /generate``, ``GET /healthz``, ``GET /metrics``)
  wired into the telemetry registry, the supervisor watchdog
  (``serve_admit`` / ``serve_decode`` phases + heartbeats), bounded
  request handling, and the ``serve_admit`` / ``serve_decode`` /
  ``serve_request`` chaos seams.
"""

from trlx_tpu.serve.admission import QueueFull, Request  # noqa: F401
from trlx_tpu.serve.engine import InferenceEngine, ServeConfig  # noqa: F401
from trlx_tpu.serve.server import InferenceServer  # noqa: F401
from trlx_tpu.serve.slots import SlotScheduler  # noqa: F401

__all__ = [
    "InferenceEngine",
    "InferenceServer",
    "QueueFull",
    "Request",
    "ServeConfig",
    "SlotScheduler",
]
