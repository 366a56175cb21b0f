"""Lightweight span tracer: named host-side phases as Chrome-trace events.

``jax.profiler.trace`` ($TRLX_TPU_PROFILE_DIR, trlx_tpu.utils.profiling)
captures a full device trace — heavyweight, TensorBoard-loadable, and
usually off. This tracer is the always-cheap complement: every
``span(name)`` records one complete event (``ph: "X"`` with microsecond
``ts``/``dur``) into a bounded in-memory buffer, exported as one JSON
object per line (JSONL) that Perfetto (https://ui.perfetto.dev) opens
directly; for chrome://tracing wrap the lines in ``[...]``. Span names
follow the phase vocabulary the learn loops and the serve scheduler use
(docs/source/observability.rst "Spans"); the first occurrence of each
name is flagged (``args.first_call``) because on jitted phases it
contains the trace + XLA-compile cost.

One clock, one zero: every timestamp is ``time.monotonic`` (which is
``trlx_tpu.supervisor.monotonic``, the serve path's only clock) minus
``t0``, whether the span was timed live (``span``) or handed over with
its stamps (``add_span``, the request tracks). Each thread is its own
Perfetto track (``tid``), and each live span
records the span that was open around it on its thread
(``args.parent``), so a layer's self time is its duration minus its
children's.

While profiler annotations are on (``profiling.set_annotations``), a
live span also opens a ``jax.profiler.TraceAnnotation`` of the same
name: the program's spans then lie on the host plane of the profiler's
``.xplane.pb``, on the device trace's clock, beside the device's
programs.

Durations are HOST wall-clock between span entry and exit. JAX dispatch
is asynchronous, so a span around a dispatch measures trace/compile/
enqueue time — device execution lands in whichever later span first
blocks on the result (typically a fetch). Read a span as "what the host
waited for", not as device time.

Every span also feeds the metrics registry: a ``time/<name>`` histogram
observation, and a ``compile/<name>_first_s`` gauge on the first call.
"""

import json
import os
import threading
import time
from typing import Optional

from trlx_tpu.telemetry.registry import MetricsRegistry
from trlx_tpu.utils import profiling


class _Span:
    """One live span: a plain enter/exit pair (no generator frame — this
    sits on the serve scheduler's per-step path)."""

    __slots__ = ("tracer", "name", "start", "annotation")

    def __init__(self, tracer: "SpanTracer", name: str):
        self.tracer = tracer
        self.name = name

    def __enter__(self):
        self.annotation = profiling.trace_annotation(self.name)
        if self.annotation is not None:
            self.annotation.__enter__()
        self.tracer._stack().append(self.name)
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc):
        end = time.monotonic()
        stack = self.tracer._stack()
        stack.pop()
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        self.tracer._close(self.name, self.start, end,
                           stack[-1] if stack else None)
        return False


class SpanTracer:
    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        max_events: int = 100_000,
    ):
        self.registry = registry
        self.max_events = max_events
        self.t0 = time.monotonic()
        self.events = []
        self.dropped = 0
        self._seen = set()
        self._named_tracks = set()
        self._local = threading.local()  # .stack: this thread's open spans
        self._tids = {}  # thread ident -> track id, in order of first span
        self._tid_lock = threading.Lock()

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def current_span(self) -> Optional[str]:
        """The innermost span open on the calling thread, or None."""
        stack = self._stack()
        return stack[-1] if stack else None

    def _thread_tid(self) -> int:
        """This thread's track: 0 for the first thread that records a
        span, 1 for the next... (request tracks count from
        ``serve.trace.REQUEST_TID_BASE``, so the two never meet)."""
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._tid_lock:  # a thread's first span only
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def _close(self, name: str, start: float, end: float,
               parent: Optional[str]) -> None:
        dur = end - start
        first = name not in self._seen
        self._seen.add(name)
        if len(self.events) < self.max_events:
            event = {
                "name": name,
                "ph": "X",
                "ts": round((start - self.t0) * 1e6, 3),
                "dur": round(dur * 1e6, 3),
                "pid": os.getpid(),
                "tid": self._thread_tid(),
            }
            if first or parent is not None:
                args = event["args"] = {}
                if first:
                    args["first_call"] = True
                if parent is not None:
                    args["parent"] = parent
            self.events.append(event)
        else:
            self.dropped += 1
        if self.registry is not None:
            self.registry.observe(f"time/{name}", dur)
            if first:
                self.registry.set_gauge(f"compile/{name}_first_s", dur)

    def add_span(self, name: str, start: float, end: float,
                 tid: int = 0, args=None) -> None:
        """Append one complete event from its two ``time.monotonic``
        stamps rather than a live ``span()`` context — the serve request
        traces export their lifecycle phases through here, one Perfetto
        track (tid) per request. Bounded by the same ``max_events``
        budget."""
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        event = {
            "name": name,
            "ph": "X",
            "ts": round((start - self.t0) * 1e6, 3),
            "dur": round(max(end - start, 0.0) * 1e6, 3),
            "pid": os.getpid(),
            "tid": int(tid),
        }
        if args:
            event["args"] = dict(args)
        self.events.append(event)

    def name_track(self, tid: int, label: str) -> None:
        """Label one tid with a Chrome-trace thread_name metadata event
        (once per tid) so Perfetto shows e.g. ``req 3f2a...`` instead of
        a bare integer."""
        if tid in self._named_tracks or len(self.events) >= self.max_events:
            return
        self._named_tracks.add(tid)
        self.events.append({
            "name": "thread_name",
            "ph": "M",
            "pid": os.getpid(),
            "tid": int(tid),
            "args": {"name": label},
        })

    def write_jsonl(self, path: str,
                    max_bytes: int = 64 * 1024 * 1024) -> str:
        """One Chrome-trace event per line. Perfetto loads the file as-is;
        a dropped-events marker is appended when the buffer overflowed so
        a truncated trace never reads as a complete one.

        The file is size-bounded: when the serialized events exceed
        ``max_bytes`` the OLDEST lines are dropped until the rest fit
        (the recent tail is what a post-mortem reads), counted into the
        same dropped-events marker — a long run's ``trace.jsonl`` never
        grows past the budget."""
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        lines = [json.dumps(event) + "\n" for event in self.events]
        dropped = self.dropped
        total = sum(len(line) for line in lines)
        at = 0
        while at < len(lines) - 1 and total > max_bytes:
            total -= len(lines[at])
            at += 1
            dropped += 1
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            f.writelines(lines[at:])
            if dropped:
                f.write(json.dumps({
                    "name": f"[{dropped} events dropped]",
                    "ph": "X",
                    "ts": round((time.monotonic() - self.t0) * 1e6, 3),
                    "dur": 0,
                    "pid": os.getpid(),
                    "tid": 0,
                }) + "\n")
        os.replace(tmp, path)
        return path
