"""Admission: what a request is, and the typed ways one is refused.

Shared by the slot scheduler (trlx_tpu.serve.slots) and the HTTP layer
(trlx_tpu.serve.server): :class:`Request` (one queued generation and its
completion slot, with the crash-only replay journal), the rejection
types the server maps to HTTP codes (:class:`QueueFull` and its
subclasses -> 429; :class:`ReplayExhausted`, :class:`DeadlineExceeded`,
:class:`DrainTimeout` -> 503), :func:`shed_expired` (queued past its
``deadline_ms``), and the multi-tenant quota table.

Multi-tenant admission (docs "Fault tolerance", overload containment):
requests carry a tenant identity; a ``serve.tenants`` config attaches
per-tenant quotas (token-bucket rate, inflight cap, queue share)
enforced by :class:`TenantTable` at ``SlotScheduler.submit`` — an
over-quota tenant gets a typed :class:`QuotaExceeded` (429 + per-tenant
``Retry-After``) while other tenants keep being admitted. The
``serve_quota`` chaos seam fires on that check so the shed path is
drillable.
"""

import itertools
import threading
from typing import Dict, List, Optional

from trlx_tpu import telemetry
from trlx_tpu.serve.trace import RequestTrace
from trlx_tpu.supervisor import monotonic


class QueueFull(RuntimeError):
    """Admission control rejection: the serve queue is at ``max_queue``.
    Clients should back off and retry (HTTP 429)."""


class Draining(QueueFull):
    """Admission rejection because the server is draining (SIGTERM or
    ``POST /admin/drain``): retry against another replica (HTTP 429 +
    ``Retry-After``). IS-A :class:`QueueFull` so callers handle both
    the same way."""


class QuotaExceeded(QueueFull):
    """Per-tenant admission rejection: THIS tenant's quota
    (``serve.tenants`` rate bucket, ``max_inflight``, or
    ``max_queue_share``) is exhausted while the server itself may still
    have room — other tenants keep being admitted. IS-A
    :class:`QueueFull` (HTTP 429) so callers need no new handling, but
    carries the tenant name and a per-tenant ``Retry-After`` derived
    from the tenant's own bucket refill instead of the global queue
    estimate."""

    def __init__(self, message: str, tenant: str = "",
                 retry_after_s: int = 1):
        super().__init__(message)
        self.tenant = tenant
        self.retry_after_s = int(retry_after_s)


class ReplayExhausted(RuntimeError):
    """A request's crash-only replay budget (``serve.max_replays``) ran
    out, or its grown prompt (original + committed tokens) no longer
    fits any compiled bucket — the request cannot be re-executed and
    fails with a typed reason (HTTP 503)."""


class DeadlineExceeded(RuntimeError):
    """The request's own ``deadline_ms`` passed while it was still
    queued — shed by overload control instead of decoded uselessly
    (HTTP 503, ``serve/shed_expired``)."""


class DrainTimeout(RuntimeError):
    """The graceful-drain budget (``serve.drain_timeout``) expired with
    this request still unfinished; it is shed with a reason instead of
    killed with the process (HTTP 503)."""


#: global admission order: ties in priority admit FIFO by this stamp,
#: and replayed requests keep their original position
_SEQ = itertools.count()

#: tenant charged for requests that carry no ``X-Tenant-Id`` header /
#: ``"tenant"`` body field — quota config for it lives under the
#: ``serve.tenants`` ``"default"`` entry, which also governs tenants
#: the config does not name
DEFAULT_TENANT = "default"

_TENANT_KEYS = ("max_inflight", "max_queue_share", "rps", "burst",
                "priority")


class TenantPolicy:
    """One parsed ``serve.tenants`` entry.

    ``rps``/``burst`` form a token bucket (``rps <= 0`` disables rate
    limiting; ``burst <= 0`` defaults to ``max(1, rps)``);
    ``max_inflight`` caps admitted-but-unfinished requests (``<= 0``
    unlimited); ``max_queue_share`` caps the fraction of
    ``serve.max_queue`` the tenant's QUEUED requests may occupy
    (``<= 0`` unlimited); ``priority`` is the default admission
    priority for the tenant's requests — ``<= 0`` marks the tenant
    best-effort, i.e. brownout-clampable and router-sheddable under
    fleet pressure."""

    __slots__ = ("name", "max_inflight", "max_queue_share", "rps",
                 "burst", "priority")

    def __init__(self, name: str, spec):
        spec = dict(spec or {})
        unknown = sorted(set(spec) - set(_TENANT_KEYS))
        if unknown:
            raise ValueError(
                f"serve.tenants[{name!r}]: unknown keys {unknown} "
                f"(known: {list(_TENANT_KEYS)})"
            )
        self.name = name
        self.max_inflight = int(spec.get("max_inflight", 0))
        self.max_queue_share = float(spec.get("max_queue_share", 0.0))
        if self.max_queue_share > 1.0:
            raise ValueError(
                f"serve.tenants[{name!r}].max_queue_share="
                f"{self.max_queue_share:g} must be <= 1.0 (a fraction "
                f"of serve.max_queue)"
            )
        self.rps = float(spec.get("rps", 0.0))
        burst = float(spec.get("burst", 0.0))
        self.burst = burst if burst > 0 else max(1.0, self.rps)
        self.priority = int(spec.get("priority", 0))

    @property
    def best_effort(self) -> bool:
        return self.priority <= 0


class TenantTable:
    """Per-tenant admission accounting.

    NOT internally locked: the scheduler invokes it under its own
    lock (the same discipline as router/resilience.RetryBudget). The
    ``"default"`` entry, when present, governs both the default tenant
    and any tenant the config does not name (they share its bucket);
    with no ``serve.tenants`` config at all every check is a no-op, so
    quota-free deployments pay nothing."""

    def __init__(self, config, max_queue: int):
        config = config or {}
        self.policies = {
            str(name): TenantPolicy(str(name), spec)
            for name, spec in config.items()
        }
        self.enabled = bool(self.policies)
        self.max_queue = int(max_queue)
        now = monotonic()
        self._buckets = {n: (p.burst, now)
                         for n, p in self.policies.items()}

    def policy(self, tenant: str) -> Optional[TenantPolicy]:
        p = self.policies.get(tenant)
        return self.policies.get(DEFAULT_TENANT) if p is None else p

    def priority_for(self, tenant: str) -> int:
        p = self.policy(tenant)
        return 0 if p is None else p.priority

    def best_effort(self, tenant: str) -> bool:
        p = self.policy(tenant)
        return True if p is None else p.best_effort

    def _refill(self, p: TenantPolicy, now: float) -> float:
        tokens, stamp = self._buckets[p.name]
        if p.rps > 0 and now > stamp:
            tokens = min(p.burst, tokens + (now - stamp) * p.rps)
        self._buckets[p.name] = (tokens, now)
        return tokens

    def _retry_after(self, p: TenantPolicy, now: float) -> int:
        """Seconds until the tenant's bucket holds a whole token again
        — the per-tenant Retry-After hint; >= 1 (HTTP header integer)."""
        if p.rps <= 0:
            return 1
        tokens, _ = self._buckets[p.name]
        deficit = (1.0 - tokens) / p.rps
        return max(1, int(-(-deficit // 1)))

    def try_admit(self, tenant: str, queued: int, inflight: int,
                  now: float) -> Optional[QuotaExceeded]:
        """One admission attempt for ``tenant`` currently holding
        ``queued`` queued and ``inflight`` running requests (counted by
        the caller under its lock). Returns None and spends one bucket
        token on success, or a ready-to-raise :class:`QuotaExceeded`
        (no token spent) naming the exhausted quota."""
        if not self.enabled:
            return None
        p = self.policy(tenant)
        if p is None:
            return None
        self._refill(p, now)
        if p.max_inflight > 0 and queued + inflight >= p.max_inflight:
            return QuotaExceeded(
                f"tenant {tenant!r} is at its max_inflight="
                f"{p.max_inflight} admitted-but-unfinished requests "
                f"(serve.tenants); retry after in-flight work drains",
                tenant=tenant, retry_after_s=self._retry_after(p, now),
            )
        if p.max_queue_share > 0 and queued >= max(
            1, int(p.max_queue_share * self.max_queue)
        ):
            return QuotaExceeded(
                f"tenant {tenant!r} holds its full "
                f"max_queue_share={p.max_queue_share:g} slice of the "
                f"{self.max_queue}-deep serve queue (serve.tenants); "
                f"other tenants keep their share — retry with backoff",
                tenant=tenant, retry_after_s=self._retry_after(p, now),
            )
        if p.rps > 0:
            tokens, _ = self._buckets[p.name]
            if tokens < 1.0:
                return QuotaExceeded(
                    f"tenant {tenant!r} is over its {p.rps:g} rps rate "
                    f"quota (burst {p.burst:g}, serve.tenants); retry "
                    f"after the bucket refills",
                    tenant=tenant,
                    retry_after_s=self._retry_after(p, now),
                )
            self._buckets[p.name] = (tokens - 1.0, now)
        return None

    def snapshot(self, now: float) -> Dict:
        """Debug view for ``/debug/state``: per-tenant bucket levels
        and policy knobs (never mutates bucket stamps)."""
        out = {}
        for name, p in self.policies.items():
            tokens, stamp = self._buckets[name]
            if p.rps > 0 and now > stamp:
                tokens = min(p.burst, tokens + (now - stamp) * p.rps)
            out[name] = {
                "tokens": round(tokens, 3), "rps": p.rps,
                "burst": p.burst, "max_inflight": p.max_inflight,
                "max_queue_share": p.max_queue_share,
                "priority": p.priority,
            }
        return out


def _validate_deadline(deadline_ms) -> Optional[float]:
    """HTTP ``deadline_ms`` -> seconds (None passes through); <= 0 is a
    request that could never be served, a caller bug (HTTP 400)."""
    if deadline_ms is None:
        return None
    deadline_ms = float(deadline_ms)
    if deadline_ms <= 0:
        raise ValueError(
            f"deadline_ms={deadline_ms:g} must be > 0 (the deadline is "
            f"relative to request receipt)"
        )
    return deadline_ms / 1000.0


def shed_expired(requests, now: float) -> List["Request"]:
    """Split off requests whose deadline passed while queued, failing
    each with :class:`DeadlineExceeded` (+ ``serve/shed_expired``);
    returns the survivors in order."""
    kept = []
    for req in requests:
        if req.deadline_at is not None and now > req.deadline_at:
            telemetry.inc("serve/shed_expired")
            telemetry.inc("serve/request_errors")
            req.error = DeadlineExceeded(
                f"request shed: its deadline_ms passed after "
                f"{(now - req.enqueued_at) * 1000.0:.0f}ms in queue "
                f"(overload — see serve/queue_depth and "
                f"serve/shed_expired)"
            )
            req.done.set()
        else:
            kept.append(req)
    return kept


class Request:
    """One queued generation request and its completion slot.

    Crash-only recovery journal: ``committed`` holds the tokens already
    harvested host-side — on a poisoned step the request is re-queued
    with them instead of failed, and re-admission prefills
    ``tokens + committed`` to resume decode from the last committed
    token (greedy decode is Markov on the token prefix, so the
    continuation is bit-identical). ``replays`` counts those re-queues
    against ``serve.max_replays``."""

    __slots__ = ("tokens", "max_new_tokens", "seed", "shape",
                 "enqueued_at", "done", "result", "error", "latency_s",
                 "trace", "seq", "priority", "deadline_at", "replays",
                 "committed", "model_version", "tenant", "age",
                 "degraded")

    def __init__(self, tokens: List[int], max_new_tokens: int,
                 shape, seed: Optional[int] = None,
                 trace: Optional[RequestTrace] = None,
                 deadline_s: Optional[float] = None,
                 priority: int = 0, tenant: str = DEFAULT_TENANT):
        self.tokens = tokens
        self.max_new_tokens = max_new_tokens
        self.seed = seed
        self.shape = shape  # (prompt_len, gen_len) class
        self.enqueued_at = monotonic()
        self.done = threading.Event()
        self.result: Optional[List[int]] = None
        self.error: Optional[BaseException] = None
        self.latency_s: float = 0.0
        self.trace = trace
        self.seq = next(_SEQ)
        self.priority = int(priority)
        self.deadline_at = (
            None if deadline_s is None else self.enqueued_at + deadline_s
        )
        self.replays = 0
        self.committed: List[int] = []
        self.model_version = 0  # stamped at admission
        self.tenant = tenant
        #: admission rounds spent queued — feeds priority aging
        #: (serve.priority_aging_rounds) so low-priority tenants cannot
        #: be starved forever by a saturating high-priority stream
        self.age = 0
        #: True when brownout clamped this request's max_new_tokens
        #: (surfaced as "degraded": true in the HTTP response)
        self.degraded = False
        if trace is not None:
            trace.enqueued = self.enqueued_at
            trace.tenant = tenant

    def remaining_new_tokens(self) -> int:
        """Decode budget still owed after the committed prefix — always
        >= 1 for a live/queued request (a request whose last token was
        committed finished at that same harvest)."""
        return self.max_new_tokens - len(self.committed)

    def wait(self, timeout: Optional[float] = None) -> "Request":
        """Block until decoded; re-raises the worker-side error if the
        batch failed, raises TimeoutError if `timeout` expires first."""
        if not self.done.wait(timeout):
            raise TimeoutError(
                f"request not decoded within {timeout:.3g}s (queue "
                f"backlog or a stalled decode — check serve/queue_depth "
                f"and fault/stalls)"
            )
        if self.error is not None:
            raise self.error
        return self
