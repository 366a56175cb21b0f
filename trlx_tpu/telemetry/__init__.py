"""Unified telemetry: metrics registry + span tracer + device monitors.

One process-local session owns a :class:`MetricsRegistry` and a
:class:`SpanTracer`. The trainers start it at construction (knob:
``train.telemetry``, default on) and every subsystem reports through the
module-level functions below — ``span()``, ``inc()``, ``set_gauge()``,
``observe()`` — which are strict no-ops while no session is active, so a
library import or a ``telemetry: false`` run records NOTHING and pays
one ``is None`` check per call site (zero-overhead-by-default; tested in
tests/test_telemetry.py).

What flows where:

- per iteration, the learn loops merge ``session.tracker_stats()`` into
  the stats dict they already emit — ``time/*`` phase durations,
  ``throughput/*``, ``fault/*`` counters, ``device/*`` HBM gauges,
  ``compile/*`` first-call latencies — so wandb / jsonl / print sinks
  all carry the breakdown unchanged (flat float dict, the existing
  tracker protocol);
- at ``learn()`` exit, ``session.finish()`` prints a one-line digest
  (stderr: stdout belongs to the entry point, and benchmarks/run.py's
  is one JSON line) and writes ``<run_dir>/telemetry.json`` (the
  run-level summary, headline ``metric``/``value``/``unit`` at the top)
  plus
  ``<run_dir>/trace.jsonl`` (Chrome-trace/Perfetto span timeline).

``run_dir`` resolves to ``train.telemetry_dir`` or, when unset, to
``train.checkpoint_dir`` — written only if that directory already exists
(a checkpoint has been committed) so ad-hoc constructions don't scatter
files; an explicit ``telemetry_dir`` is always created and written.

See docs/source/observability.rst for the full metric-name catalog.
"""

import contextlib
import json
import os
import sys
from typing import Any, Dict, Optional

from trlx_tpu.telemetry.device import sample_device_stats
from trlx_tpu.telemetry.flops import (  # noqa: F401  (re-exports)
    PEAK_FLOPS,
    decode_flops_per_token,
    ilql_train_flops_per_token,
    mfu_estimate,
    peak_flops,
    ppo_train_flops_per_token,
)
from trlx_tpu.telemetry.registry import MetricsRegistry, TimingHist  # noqa: F401
from trlx_tpu.telemetry.tracer import SpanTracer
from trlx_tpu.utils import profiling

#: counters pre-registered at session start so ``fault/*`` keys appear in
#: every emission from the first iteration — a dashboard shows 0, not a
#: missing series, before the first fault
_PREDECLARED_COUNTERS = (
    "fault/skipped_steps",
    "fault/rollbacks",
    "fault/divergence_aborts",
    "fault/host_retries",
    "fault/host_giveups",
    "fault/tracker_emissions_lost",
    "fault/tracker_degraded",
    "fault/preempt_sigterm",
    # run-supervisor containment (trlx_tpu.supervisor): watchdog stall
    # detections/escalations, hung-seam timeouts, walltime save-and-exits
    "fault/stalls",
    "fault/stall_escalations",
    "fault/seam_timeouts",
    "fault/walltime_exits",
    "fault/checkpoint_debris_cleared",
    "checkpoint/saves",
    "checkpoint/restores",
    # end-to-end checkpoint byte integrity (utils.checkpoint manifest
    # verification; docs "Fault tolerance", quarantine runbook):
    # verified/skipped split restores by manifest coverage, failures
    # and quarantines are the bit-rot alarm that must read 0
    "checkpoint/verified",
    "checkpoint/verify_skipped",
    "checkpoint/verify_failures",
    "checkpoint/quarantined",
    # steady-state executable-cache misses after warmup
    # (trlx_tpu.utils.aotjit): a sharding/layout drift that recompiles
    # every step shows up as a counter climbing with iter, not silence
    "compile/recompiles",
    # every backend compile JAX itself reports (aot_jit or plain jax.jit,
    # persistent-cache hit or not), also counted per {span=...} it fell
    # in: after warm-up a healthy run holds this still
    "compile/backend_compiles",
    # chaos drills fired (supervisor.chaos): "the instrumentation itself
    # acted" — must read 0, not absent, on a healthy run
    "chaos/injections",
)


class TelemetrySession:
    def __init__(self, run_dir: str = "", force_dir: bool = False):
        self.registry = MetricsRegistry()
        self.tracer = SpanTracer(registry=self.registry)
        self.run_dir = run_dir
        self.force_dir = force_dir
        self.headline: Optional[Dict[str, Any]] = None
        # lazily-attached windowed SLO engine (serve.trace.SloEngine —
        # created by serve.trace.slo_engine() on first SLO-scored
        # request). Living on the session keeps the telemetry:false
        # contract: no session, no engine, no windows.
        self.slo: Optional[Any] = None
        self.registry.predeclare(_PREDECLARED_COUNTERS)

    # -- per-iteration ---------------------------------------------------- #

    def tracker_stats(self) -> Dict[str, float]:
        """Flat float dict for the metrics stream: counters, gauges, last
        span durations, with device HBM gauges freshly sampled."""
        sample_device_stats(self.registry)
        return self.registry.tracker_stats()

    # -- run-level -------------------------------------------------------- #

    def set_headline(self, metric: str, value: float, unit: str) -> None:
        self.headline = {
            "metric": metric, "value": round(float(value), 3), "unit": unit,
        }

    def summary(self) -> Dict[str, Any]:
        """Run-level record: headline metric/value/unit at the top, then
        the full registry."""
        sample_device_stats(self.registry)
        out: Dict[str, Any] = dict(self.headline or {})
        out.update(self.registry.summary())
        out["trace_events"] = len(self.tracer.events)
        return out

    def write(self) -> Optional[Dict[str, str]]:
        """``telemetry.json`` + ``trace.jsonl`` under run_dir, process-0
        only. Returns the paths, or None when no writable run dir is
        configured (see the module docstring's gating rule)."""
        if not self.run_dir:
            return None
        if not self.force_dir and not os.path.isdir(self.run_dir):
            return None
        from trlx_tpu.parallel import is_main_process

        if not is_main_process():
            return None
        os.makedirs(self.run_dir, exist_ok=True)
        summary_path = os.path.join(self.run_dir, "telemetry.json")
        tmp = f"{summary_path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.summary(), f, indent=1)
        os.replace(tmp, summary_path)
        trace_path = self.tracer.write_jsonl(
            os.path.join(self.run_dir, "trace.jsonl")
        )
        return {"summary": summary_path, "trace": trace_path}

    def finish(self) -> None:
        """Persist + print the digest. Called at every learn() exit (safe
        to call repeatedly — later calls overwrite with the newer state).
        The digest goes to stderr: an entry point's stdout may be a
        protocol (benchmarks/run.py's is ONE JSON line)."""
        paths = self.write()
        if paths is None:
            return
        counters = {
            k: v for k, v in self.registry.counters.items() if v
        }
        head = self.headline or {}
        print(
            f"[trlx_tpu] telemetry: "
            f"{head.get('metric', 'run')}={head.get('value', 'n/a')} "
            f"{head.get('unit', '')}; nonzero counters {counters or '{}'}; "
            f"summary -> {paths['summary']}, trace -> {paths['trace']}",
            file=sys.stderr, flush=True,
        )


# --------------------------------------------------------------------- #
# module-level API: the one active session + no-op-when-disabled hooks
# --------------------------------------------------------------------- #

_session: Optional[TelemetrySession] = None
_NULL_CM = contextlib.nullcontext()  # reusable & reentrant
_listening = False  # the jax.monitoring listener is registered, once


def start(run_dir: str = "", force_dir: bool = False) -> TelemetrySession:
    """Activate a fresh session (a new run = fresh metrics); returns it.
    The process's first session registers the compile listener."""
    global _session, _listening
    if not _listening:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _listening = True
    _session = TelemetrySession(run_dir=run_dir, force_dir=force_dir)
    return _session


def start_from_config(config) -> Optional[TelemetrySession]:
    """The trainers' entry point: honor ``train.telemetry`` (default on)
    and resolve the run dir (``train.telemetry_dir``, else checkpoint_dir
    with the exists-gate)."""
    train = getattr(config, "train", None)
    if not getattr(train, "telemetry", True):
        return None
    explicit = getattr(train, "telemetry_dir", "") or ""
    run_dir = explicit or getattr(train, "checkpoint_dir", "") or ""
    return start(run_dir=run_dir, force_dir=bool(explicit))


def stop() -> None:
    global _session
    _session = None


def current() -> Optional[TelemetrySession]:
    return _session


def span(name: str):
    """Context manager around one named phase: THE place a program span
    is made. With a session it is timed and recorded (SpanTracer); while
    profiler annotations are on (``profiling.set_annotations``) it also
    opens one ``jax.profiler.TraceAnnotation`` of the same name, session
    or not; with neither it is a shared no-op."""
    if _session is None:
        return profiling.trace_annotation(name) or _NULL_CM
    return _session.tracer.span(name)


#: the event the benchmark harness counts too: one per backend compile
#: request, on the thread that asked for it
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_jax_duration(event: str, duration: float, **_kwargs) -> None:
    """Lay each compile JAX reports to the span it fell in."""
    session = _session
    if session is None or event != _COMPILE_EVENT:
        return
    registry = session.registry
    registry.inc("compile/backend_compiles")
    registry.inc(
        "compile/backend_compiles",
        labels={"span": session.tracer.current_span() or "none"},
    )
    registry.observe("compile/backend_compile_s", duration)


def inc(name: str, n: float = 1.0, labels=None) -> None:
    if _session is not None:
        _session.registry.inc(name, n, labels=labels)


def predeclare(names) -> None:
    """Register counters at 0 in the active session (no-op without one).

    Subsystem-scoped twin of ``_PREDECLARED_COUNTERS``: a subsystem that
    only runs in SOME processes (the serving endpoint's ``serve/*``
    family) declares its series when IT starts, so dashboards/scrapes see
    zeros instead of missing keys — without polluting every training
    run's emission with counters that can never fire there."""
    if _session is not None:
        _session.registry.predeclare(names)


def set_gauge(name: str, value: float, labels=None) -> None:
    if _session is not None:
        _session.registry.set_gauge(name, value, labels=labels)


def observe(name: str, seconds: float, labels=None) -> None:
    if _session is not None:
        _session.registry.observe(name, seconds, labels=labels)


def summary() -> Dict[str, Any]:
    """The active session's run-level summary ({} when disabled)."""
    return _session.summary() if _session is not None else {}


def prometheus_text() -> str:
    """The active session's registry in Prometheus text exposition
    format ("" when disabled) — the serve endpoint's content-negotiated
    ``GET /metrics`` body (trlx_tpu.telemetry.prometheus)."""
    if _session is None:
        return ""
    from trlx_tpu.telemetry.prometheus import render

    return render(_session.registry)
