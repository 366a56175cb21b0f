"""Profiling hooks: jax.profiler traces + named step annotations.

The reference's only tracing is the hand-rolled `Clock` (reference:
trlx/utils/__init__.py:50-88, SURVEY §5 "tracing: minimal"); here the same
wall-clock metrics are kept (trlx_tpu.utils.Clock) and real device traces
are added on top:

- set ``TRLX_TPU_PROFILE_DIR=/path`` (or pass `trace_dir`) and the learn
  loops wrap themselves in `jax.profiler.trace`, producing a TensorBoard-
  loadable trace of the jitted generate/score/train programs;
- while annotations are on (``set_annotations``; ``maybe_trace`` switches
  them on for as long as its trace runs) every program span
  (``trlx_tpu.telemetry.span``: rollout, reward_fn, ppo_update,
  serve/slot_step, ...) also opens a ``jax.profiler.TraceAnnotation`` of
  the same name, so the program's phases lie on the trace's host plane,
  on the device trace's clock.

``annotate(name)`` is a program span plus a run-supervisor phase
heartbeat: the learn loops' phases, which the watchdog times.

Zero overhead when disabled: with annotations off AND no telemetry
session AND no supervisor, ``annotate`` collapses to a shared no-op
context manager.
"""

import contextlib
import os
from typing import Optional

_ENV_VAR = "TRLX_TPU_PROFILE_DIR"

_tracing_active = False  # written by set_annotations() alone


def trace_dir_from_env() -> Optional[str]:
    return os.environ.get(_ENV_VAR) or None


def set_annotations(on: bool) -> None:
    """Switch the program spans' ``jax.profiler.TraceAnnotation``s on or
    off. Whoever starts a profiler trace switches them on for as long as
    it runs (``maybe_trace`` does); off, a span opens none."""
    global _tracing_active
    _tracing_active = bool(on)


def trace_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation(name)`` while annotations are on,
    else None. ``trlx_tpu.telemetry.span`` is the one caller: a program
    span is made there and nowhere else."""
    if not _tracing_active:
        return None
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str] = None):
    """jax.profiler trace into ``trace_dir`` when a directory is
    configured (argument or $TRLX_TPU_PROFILE_DIR), with the program
    spans' annotations on; no-op otherwise. The Python tracer is off: it
    roughly doubles the host's time per step (PERF.md, PR 24), and the
    program's own spans name the host's phases without it."""
    trace_dir = trace_dir or trace_dir_from_env()
    if not trace_dir:
        yield
        return
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=options):
        set_annotations(True)
        try:
            yield
        finally:
            set_annotations(False)


class _Stacked:
    """Enter/exit a fixed pair of context managers (program span +
    supervisor heartbeat) without contextlib.ExitStack's allocation cost —
    this sits on the per-step hot path."""

    __slots__ = ("cms",)

    def __init__(self, *cms):
        self.cms = cms

    def __enter__(self):
        for cm in self.cms:
            cm.__enter__()
        return self

    def __exit__(self, *exc):
        suppressed = False
        for cm in reversed(self.cms):
            suppressed = bool(cm.__exit__(*exc)) or suppressed
        return suppressed


def annotate(name: str):
    """Named host phase: a program span (``telemetry.span`` — the span
    record, and the profiler annotation while annotations are on) plus a
    run-supervisor phase heartbeat (no-op without an active supervisor —
    trlx_tpu.supervisor: the watchdog times the innermost open phase
    against train.stall_timeout)."""
    from trlx_tpu import supervisor, telemetry

    span = telemetry.span(name)
    heartbeat = supervisor.phase(name)
    if heartbeat is supervisor.NULL_CM:
        return span
    return _Stacked(span, heartbeat)
