"""Deterministic chaos injection at named run seams.

Every containment path in this codebase — seam timeouts, watchdog stall
detection, StepGuard rollback, preemption checkpointing — exists for a
failure that CI cannot wait to happen naturally. This module injects
those failures ON SCHEDULE, from a plain string, so the whole
containment matrix is exercisable on CPU in tier-1 tests and in
operator drills (``make chaos``, docs "Fault tolerance").

A schedule is a ``;``-separated list of rules::

    <seam>:<action>[=<param>][@<occurrences>]

- ``seam``: a named injection point. The wired seams are ``reward_fn``
  and ``tracker`` (fired before each *attempt* inside ``retry_call``, so
  an injected hang lands inside the bounded worker and an injected
  exception consumes a retry), plus the phase seams ``rollout``,
  ``ppo_update``, ``ilql_update``, ``eval``, and ``checkpoint_save``
  (fired once at phase entry). The serving subsystem (trlx_tpu.serve)
  adds ``serve_decode`` (fired inside the supervised ``serve_decode``
  phase, before the slot scheduler's per-step decode dispatch; a
  ``hang`` there drives the watchdog stall path), ``serve_admit`` (fired inside
  the slot scheduler's ``serve_admit`` phase after an admission batch is
  selected, before its prefill dispatch — a ``hang`` makes a wedged
  admission an attributable stall, an ``exc`` fails just that batch),
  ``serve_prefix_match`` (fired inside the same ``serve_admit`` phase at
  the top of the slot scheduler's PAGED admission, before the radix
  prefix walk / page allocation — a ``hang`` proves a wedged
  prefix-match is a watchdog-attributable ``serve_admit`` stall, not
  silence), ``serve_request`` (fired at request-handler entry — an
  ``exc`` surfaces as the HTTP 500 error path), ``serve_quota`` (fired
  at submit-time tenant-quota evaluation, only when ``serve.tenants``
  is configured, before the scheduler lock — an ``exc`` proves an
  admission-control fault surfaces as that request's typed error, never
  a wedged queue or a lost request), ``serve_replay`` (fired
  at poisoned-step RECOVERY entry, before any state mutation — an
  ``exc`` there is the double-fault drill: replay is abandoned and the
  in-flight batch fails like pre-replay containment), and
  ``serve_reload`` (fired at checkpoint hot-swap application, before
  the candidate weights install — an ``exc`` drives the
  rollback-to-old-version path, ``serve/reload_failures``), and
  ``serve_speculate`` (fired inside the supervised ``serve_decode``
  phase at proposal-gathering entry, before anything is dispatched to
  the device — an ``exc`` falls that step back to plain decode with
  nothing half-committed, ``serve/spec_fallbacks``; a ``hang`` is a
  watchdog-attributable ``serve_decode`` stall). The fleet
  router (trlx_tpu.router) adds ``router_route`` (fired at request
  routing, before a replica is picked — an ``exc`` surfaces as the
  router's 500 error path without touching any backend), ``router_probe``
  (fired at the top of each health-prober sweep — an ``exc`` proves a
  failed sweep leaves fleet membership untouched rather than ejecting
  everything), ``router_rollout`` (fired at each per-replica rolling-
  upgrade step, before the replica is fenced — an ``exc`` aborts the
  rollout with every replica re-admitted on its old version), and
  ``router_hedge`` (fired just before a hedged backup request launches
  — an ``exc`` suppresses ONLY the hedge, ``router/hedges_suppressed``;
  the primary attempt still serves the request). Checkpointing adds
  ``checkpoint_verify`` (fired at manifest-verification entry inside
  ``trlx_tpu.utils.checkpoint.verify_checkpoint`` — an ``exc`` is
  converted to ``CheckpointCorrupt`` and drives the quarantine/
  fall-back-to-previous-step path exactly like real bit-rot).
- ``action``: ``hang`` (block ``param`` seconds, default 3600 — a
  bounded seam times out, the watchdog sees everything else), ``exc``
  (raise :class:`ChaosError`), ``slow`` (sleep ``param`` seconds, default
  1, then proceed), ``sigterm`` (deliver SIGTERM to this process —
  drives the PreemptionGuard path — then proceed).
- ``occurrences``: which 1-based calls of that seam fire — ``3``,
  ``1,2``, ``2-4``, mixes thereof, or ``*`` (every call, the default).

Examples::

    reward_fn:hang=30@3          # third reward_fn attempt hangs 30s
    reward_fn:exc@1,2            # first two attempts raise (retry drill)
    ppo_update:sigterm@2         # SIGTERM mid-epoch (preemption drill)
    rollout:slow=0.5@*;eval:exc@1

The schedule comes from ``$TRLX_TPU_CHAOS`` or ``train.chaos`` (env
wins), is parsed once, and counts calls per seam — fully deterministic:
the same schedule against the same run injects at the same points.
Injection sites are free when no schedule is active (one module-global
``is None`` check).

Injected hangs wait on an interruptible event rather than a raw sleep:
:func:`reset` (test teardown) releases every in-flight hang by raising
:class:`ChaosHang` in its (already abandoned) worker thread, so test
processes don't accumulate sleeping threads.
"""

import os
import re
import threading
import time
from typing import List, Optional, Tuple

ENV_VAR = "TRLX_TPU_CHAOS"

#: the closed seam namespace. Every injection point in the library —
#: ``maybe_inject(<seam>)``, ``retry_call(seam=...)``, and the
#: supervised phase names chaos fires on — must appear here, and every
#: entry must be exercised by at least one test; graftlint
#: (chaos-seam-registered / chaos-seam-tested) enforces both ways, so a
#: typo'd seam in a schedule or a drill that can never fire is a lint
#: failure, not a silent no-op. Keep the docstring's seam tour in sync.
KNOWN_SEAMS = (
    # retry_call seams (fired per attempt, inside the bounded worker)
    "reward_fn",
    "tracker",
    # training phase seams (fired once at phase entry)
    "rollout",
    "ppo_update",
    "ilql_update",
    "eval",
    "checkpoint_save",
    # serving seams (see the module docstring for where each lands)
    "serve_admit",
    "serve_prefix_match",
    "serve_decode",
    "serve_request",
    "serve_quota",
    "serve_replay",
    "serve_reload",
    "serve_speculate",
    # fleet-router seams (trlx_tpu.router; see the docstring's seam tour)
    "router_route",
    "router_probe",
    "router_rollout",
    "router_hedge",
    # checkpoint-integrity seam (trlx_tpu.utils.checkpoint)
    "checkpoint_verify",
)

_ACTIONS = ("hang", "exc", "slow", "sigterm")

_RULE_RE = re.compile(
    r"^(?P<seam>[A-Za-z0-9_./-]+):(?P<action>[a-z_]+)"
    r"(?:=(?P<param>[0-9.]+))?(?:@(?P<occ>[0-9,\-*]+))?$"
)


class ChaosError(RuntimeError):
    """The injected failure (action ``exc``)."""


class ChaosHang(RuntimeError):
    """An injected hang released early by :func:`reset` — only ever seen
    by abandoned bounded-call workers."""


class _Rule:
    __slots__ = ("seam", "action", "param", "spans")

    def __init__(self, seam: str, action: str, param: Optional[float],
                 spans: Optional[List[Tuple[int, int]]]):
        self.seam = seam
        self.action = action
        self.param = param
        self.spans = spans  # None = every occurrence

    def matches(self, n: int) -> bool:
        if self.spans is None:
            return True
        return any(lo <= n <= hi for lo, hi in self.spans)


def _parse_occurrences(occ: str) -> Optional[List[Tuple[int, int]]]:
    if occ == "*":
        return None
    spans = []
    for part in occ.split(","):
        if "-" in part:
            lo, hi = part.split("-", 1)
            spans.append((int(lo), int(hi)))
        else:
            spans.append((int(part), int(part)))
    return spans


def parse_schedule(spec: str) -> List[_Rule]:
    """Parse a schedule string; raises ``ValueError`` with the offending
    rule on any syntax error (a typo'd drill must fail loudly, not
    silently inject nothing)."""
    rules = []
    for raw in spec.split(";"):
        raw = raw.strip()
        if not raw:
            continue
        m = _RULE_RE.match(raw)
        if m is None:
            raise ValueError(
                f"chaos rule '{raw}' does not parse; expected "
                f"'<seam>:<action>[=<param>][@<occurrences>]' "
                f"(e.g. 'reward_fn:hang=30@3')"
            )
        action = m.group("action")
        if action not in _ACTIONS:
            raise ValueError(
                f"chaos rule '{raw}': unknown action '{action}' "
                f"(known: {', '.join(_ACTIONS)})"
            )
        param = m.group("param")
        rules.append(_Rule(
            m.group("seam"), action,
            float(param) if param is not None else None,
            _parse_occurrences(m.group("occ") or "*"),
        ))
    return rules


class ChaosSchedule:
    """Parsed rules + deterministic per-seam call counters."""

    def __init__(self, rules: List[_Rule]):
        self.rules = rules
        self.counts = {}
        self.injected = 0

    def fire(self, seam: str) -> None:
        n = self.counts.get(seam, 0) + 1
        self.counts[seam] = n
        for rule in self.rules:
            if rule.seam == seam and rule.matches(n):
                self.injected += 1
                _execute(rule, seam, n)
                return  # first matching rule wins


# ------------------------------------------------------------------ #
# module state: one active schedule, one hang-release event
# ------------------------------------------------------------------ #

_schedule: Optional[ChaosSchedule] = None
_env_checked = False
_release = threading.Event()


def configure(spec: str) -> Optional[ChaosSchedule]:
    """Install (and return) the schedule parsed from ``spec`` — counters
    start fresh. Empty spec clears the schedule."""
    global _schedule, _env_checked
    _env_checked = True
    _schedule = ChaosSchedule(parse_schedule(spec)) if spec else None
    return _schedule


def configure_from(train) -> Optional[ChaosSchedule]:
    """The trainers' entry point: ``$TRLX_TPU_CHAOS`` overrides
    ``train.chaos``; when neither is set the current schedule (e.g. one a
    test installed via :func:`configure`) is left untouched."""
    spec = os.environ.get(ENV_VAR) or getattr(train, "chaos", "") or ""
    if spec:
        return configure(spec)
    return _schedule


def reset() -> None:
    """Clear the schedule and release every in-flight injected hang
    (they raise :class:`ChaosHang` in their abandoned workers)."""
    global _schedule, _env_checked, _release
    _schedule = None
    _env_checked = False
    old, _release = _release, threading.Event()
    old.set()


def active() -> Optional[ChaosSchedule]:
    """The current schedule, lazily initialized from ``$TRLX_TPU_CHAOS``
    the first time anything asks."""
    global _env_checked
    if _schedule is None and not _env_checked:
        configure(os.environ.get(ENV_VAR, ""))
    return _schedule


def maybe_inject(seam: str) -> None:
    """Fire the schedule at ``seam`` — the one call injection sites make.
    Free (a None check) when no schedule is active."""
    sched = active()
    if sched is not None:
        sched.fire(seam)


def _execute(rule: _Rule, seam: str, n: int) -> None:
    from trlx_tpu import telemetry

    telemetry.inc("chaos/injections")
    print(
        f"[trlx_tpu] chaos: injecting '{rule.action}' at seam "
        f"'{seam}' (call {n})",
        flush=True,
    )
    if rule.action == "exc":
        raise ChaosError(
            f"chaos: injected failure at seam '{seam}' (call {n})"
        )
    if rule.action == "slow":
        time.sleep(rule.param if rule.param is not None else 1.0)
        return
    if rule.action == "hang":
        released = _release.wait(
            rule.param if rule.param is not None else 3600.0
        )
        if released:
            raise ChaosHang(
                f"chaos: injected hang at seam '{seam}' (call {n}) "
                f"released by reset()"
            )
        return
    if rule.action == "sigterm":
        import signal

        os.kill(os.getpid(), signal.SIGTERM)
        return
