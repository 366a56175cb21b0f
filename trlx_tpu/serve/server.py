"""Stdlib HTTP endpoint over the inference engine + slot scheduler.

``ThreadingHTTPServer`` + JSON — no new dependencies, matching the rest
of the codebase's stdlib-only host layer. Four routes:

- ``POST /generate`` — body ``{"prompt": str | "tokens": [int],
  "max_new_tokens": int?, "seed": int?, "trace": bool?}``; returns the
  completion with its de-padded tokens, the bucket shape class that
  served it, the measured queue+decode latency, and (tracing on) its
  ``trace_id`` — minted at THIS edge, or honored from an inbound
  ``X-Request-Id`` header, and echoed back as ``X-Request-Id`` so
  client/server logs join on it. ``"trace": true`` additionally returns
  the request's full lifecycle breakdown
  (trlx_tpu.serve.trace.RequestTrace.to_dict). Errors are typed: 400
  (bad request / no bucket fits), 429 (queue full — admission control),
  503 (request timed out past ``serve.request_timeout``), 500
  (decode/chaos failure).
- ``GET /healthz`` — liveness + lattice + queue depth. A process whose
  decode thread is wedged still answers (HTTP is a different thread) —
  which is exactly why the scheduler runs under the supervisor watchdog:
  the hang surfaces as a stack-dumping stall (``fault/stalls``) rather
  than a green health check over a dead port.
- ``GET /metrics`` — content-negotiated: the default is the full
  telemetry registry summary as JSON (counters, gauges, timing
  histograms with p50/p95 and first-call-apart compile latencies — the
  shape ``telemetry.json`` persists); an ``Accept`` header naming
  ``text/plain``, ``openmetrics`` or ``prometheus`` gets the Prometheus
  text exposition instead (trlx_tpu.telemetry.prometheus), so a
  Prometheus server scrapes the endpoint directly.
- ``GET /debug/state`` — the live engine state: queue depth, per-slot
  occupancy map (trace ids, emitted-token counts, page counts), the
  flight-recorder ring, and the KV pool/radix stats. The slot
  scheduler's black box, readable BEFORE a stall forces a dump.
- ``GET /readyz`` — READINESS, split from /healthz liveness: 200 only
  while the server is warmed AND admitting (503 once draining), so an
  orchestrator rotates the replica out of the pool while /healthz stays
  green and in-flight work finishes.
- ``POST /admin/drain`` — graceful shutdown (also wired to SIGTERM):
  admission flips to 429 + ``Retry-After``, in-flight requests finish
  within ``serve.drain_timeout`` (stragglers complete with 503 +
  reason), telemetry and the flight recorder flush, the process exits
  0. Returns 202 immediately; poll /readyz.
- ``POST /admin/reload`` — live checkpoint hot-swap (docs "Fault
  tolerance"): body ``{"checkpoint": path?}`` (default: re-resolve the
  serving run directory's ``LATEST``); the new params restore into
  same-sharding buffers, smoke-probe one bucket, and swap at a step
  boundary — rollback + 409 on probe failure, zero recompiles either
  way. ``serve.watch_checkpoints`` > 0 polls ``LATEST`` and reloads
  automatically.

Proxy hygiene: every proxy in front of this server (the fleet router,
trlx_tpu.router) increments ``X-Hop-Count`` as it forwards; a request
arriving with more than :data:`MAX_HOPS` hops is rejected with a typed
508 (:class:`HopLimitExceeded`, ``serve/hop_limit_rejects``) instead of
looping forever through a router misconfigured to point at itself. The
hop count is echoed back as a response header and in the ``"trace":
true`` payload, so a trace shows how many proxies a request crossed.

Request handling runs through :func:`trlx_tpu.supervisor.bounded_call`
(``serve.request_timeout``): a request wedged behind a hung decode
raises SeamTimeout in the handler (503 + ``fault/seam_timeouts``)
instead of holding the socket forever. The ``serve_request`` chaos seam
fires at handler entry so the error path is drillable
(``serve_request:exc`` -> HTTP 500 with the injected error). 429s carry
``Retry-After`` (queue depth x recent step p50); replay-budget
exhaustion, queued-past-deadline sheds, and drain-deadline sheds map to
503 with their reason strings.
"""

import json
import os
import signal
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from trlx_tpu import telemetry
from trlx_tpu.serve.admission import (
    DeadlineExceeded,
    DrainTimeout,
    QueueFull,
    QuotaExceeded,
    ReplayExhausted,
)
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.serve.trace import SLO_COUNTERS, RequestTrace
from trlx_tpu.utils.checkpoint import CheckpointCorrupt
from trlx_tpu.supervisor import (
    RunSupervisor,
    SeamTimeout,
    bounded_call,
    chaos,
    monotonic,
)

#: counters pre-registered when a server starts so the ``serve/*`` series
#: exist in /metrics from the first scrape, not the first event
_SERVE_COUNTERS = (
    "serve/requests",
    "serve/responses",
    "serve/rejected",
    "serve/request_errors",
    "serve/generated_tokens",
    # slot-scheduler family (trlx_tpu.serve.slots): admissions into pool
    # slots, harvested/freed slots, steps decoded while requests starved
    # for a free slot
    "serve/admissions",
    "serve/evictions",
    "serve/preempted_steps",
    # paged-KV family (trlx_tpu.serve.paged): prompt tokens whose prefill
    # was skipped via radix prefix hits, cached pages LRU-evicted under
    # allocation pressure
    "serve/prefix_tokens_saved",
    "serve/evicted_pages",
    # two classes of page and routed experts (docs "Two classes of
    # page"): window-class pages released behind a window, chunks of a
    # prompt prefilled in chunks, (token, expert) pairs computed here and
    # distinct experts a decode step hit
    "serve/window_pages_freed",
    "serve/prefill_chunks",
    "serve/moe/pairs_here",
    "serve/moe/experts_hit",
    # crash-only lifecycle family (docs "Fault tolerance"): in-flight
    # requests re-queued after a poisoned step, queued requests shed past
    # their deadline, graceful drains entered, checkpoint hot-swaps
    # committed / rolled back
    "serve/replays",
    "serve/shed_expired",
    "serve/drains",
    "serve/reloads",
    "serve/reload_failures",
    # proxy hygiene (fleet routing, docs "Serving"): requests rejected
    # past the X-Hop-Count cap — a climbing counter means a routing loop
    "serve/hop_limit_rejects",
    # overload containment (docs "Fault tolerance"): per-tenant quota
    # sheds (also labeled {tenant=...}), brownout max_new_tokens clamps,
    # and brownout mode engagements — the tenant-labeled twins appear on
    # first increment (labels cannot be predeclared)
    "serve/shed_quota",
    "serve/brownout_clamped",
    "serve/brownout_entries",
    # speculative decoding (docs "Speculative decoding"): proposed
    # tokens shipped to verify_step, proposals accepted (== decode
    # steps the target model never ran under greedy verify), and
    # proposal-side faults that fell a step back to plain decode
    "serve/spec_proposed",
    "serve/spec_accepted",
    "serve/spec_steps_saved",
    "serve/spec_fallbacks",
)

#: proxy-hop ceiling: any sane fleet topology is 1-2 hops deep (client
#: -> router -> replica); past this the request is looping, not routing
MAX_HOPS = 8


class HopLimitExceeded(RuntimeError):
    """Inbound ``X-Hop-Count`` above :data:`MAX_HOPS` — a proxy loop
    (e.g. a router whose backend list includes itself), mapped to 508
    Loop Detected at the HTTP edge."""


class _Handler(BaseHTTPRequestHandler):
    # set per-server via type(); silences the default per-request stderr log
    server_ref: "InferenceServer" = None

    def log_message(self, format, *args):  # noqa: A002 (stdlib signature)
        return

    # -- helpers --------------------------------------------------------- #

    def _json(self, code: int, payload: dict, headers=None) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for name, value in (headers or {}).items():
            self.send_header(name, value)
        self.end_headers()
        self.wfile.write(body)

    def _text(self, code: int, body: str, content_type: str) -> None:
        raw = body.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(raw)))
        self.end_headers()
        self.wfile.write(raw)

    def _error(self, code: int, message: str) -> None:
        self._json(code, {"error": message})

    # -- routes ---------------------------------------------------------- #

    def do_GET(self) -> None:  # noqa: N802 (stdlib casing)
        srv = self.server_ref
        if self.path == "/healthz":
            self._json(200, {
                "status": "ok",
                "warmed": srv.warmed,
                "scheduler": "slots",  # the one there is; clients key on it
                "buckets": [list(b) for b in srv.engine.buckets],
                "queue_depth": srv.scheduler.queue_depth(),
                "slots": srv.scheduler.runtime.num_slots,
                "free_slots": srv.scheduler.free_slots(),
                "kv": srv.scheduler.pool_stats(),
                "mesh": srv.engine.mesh_info(),
            })
        elif self.path == "/metrics":
            accept = self.headers.get("Accept", "") or ""
            wants_text = any(
                key in accept.lower()
                for key in ("text/plain", "openmetrics", "prometheus")
            )
            if wants_text:
                from trlx_tpu.telemetry import prometheus

                self._text(
                    200, telemetry.prometheus_text(), prometheus.CONTENT_TYPE
                )
            else:
                self._json(200, telemetry.summary())
        elif self.path == "/readyz":
            # readiness is admission: a draining (or not-yet-warmed)
            # replica answers 503 here while /healthz stays 200, so the
            # orchestrator rotates it without killing in-flight work
            ready = srv.warmed and not srv.draining
            self._json(200 if ready else 503, {
                "ready": ready,
                "warmed": srv.warmed,
                "draining": srv.draining,
                "model_version": srv.engine.model_version,
                # backpressure block (overload containment): the router's
                # prober reads this to shed best-effort tenants BEFORE
                # forwarding into a page-starved/browned-out replica
                "pressure": srv.scheduler.pressure(),
            })
        elif self.path == "/debug/state":
            self._json(200, srv.scheduler.debug_state())
        elif self.path == "/debug/slo":
            # live windowed goodput/burn-rate per label set (serve.trace
            # SloEngine); an empty body when telemetry is off or nothing
            # has been scored yet — never a 404, dashboards poll this
            tel = telemetry.current()
            slo = tel.slo if tel is not None else None
            self._json(200, slo.snapshot() if slo is not None
                       else {"series": []})
        else:
            self._error(404, f"no route '{self.path}' (have /generate, "
                             f"/admin/drain, /admin/reload [POST], "
                             f"/healthz, /readyz, /metrics, /debug/state, "
                             f"/debug/slo)")

    def do_POST(self) -> None:  # noqa: N802 (stdlib casing)
        srv = self.server_ref
        # the trace clock starts at the HTTP edge, before body parsing;
        # an inbound X-Request-Id becomes the trace id (client log join)
        received_at = monotonic()
        request_id = self.headers.get("X-Request-Id") or None
        try:
            hops = int(self.headers.get("X-Hop-Count") or 0)
            if hops < 0:
                raise ValueError
        except ValueError:
            self._error(400, "X-Hop-Count must be a non-negative integer")
            return
        if hops > MAX_HOPS:
            # typed 508: a proxy loop, not a client or service error
            telemetry.inc("serve/hop_limit_rejects")
            e = HopLimitExceeded(
                f"X-Hop-Count {hops} exceeds the {MAX_HOPS}-hop proxy "
                f"cap — routing loop? (a router listing itself as a "
                f"backend forwards forever)"
            )
            self._error(508, str(e))
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(body, dict):
                raise ValueError("body must be a JSON object")
        except (ValueError, json.JSONDecodeError) as e:
            self._error(400, f"bad JSON body: {e}")
            return
        if self.path == "/admin/drain":
            srv.begin_drain()
            self._json(202, {
                "draining": True,
                "drain_timeout": srv.engine.serve.drain_timeout,
            })
            return
        if self.path == "/admin/reload":
            try:
                result = srv.reload(body.get("checkpoint"))
            except (FileNotFoundError, ValueError) as e:
                self._error(400, str(e))
                return
            except CheckpointCorrupt as e:
                # integrity gate tripped BEFORE any leaf touched the
                # device: the corrupt step is quarantined upstream and
                # the old weights keep serving — a conflict (409), not a
                # crash, and the typed reason is what makes a fleet
                # rollout abort instead of retrying into the same bytes
                telemetry.inc("serve/reload_failures")
                self._json(409, {
                    "reloaded": False,
                    "reason": f"checkpoint corrupt: {e}",
                })
                return
            except Exception as e:
                telemetry.inc("serve/reload_failures")
                self._error(500, f"{type(e).__name__}: {e}")
                return
            # probe failure / concurrent reload: weights unchanged, the
            # old version keeps serving — a conflict, not a crash
            self._json(200 if result.get("reloaded") else 409, result)
            return
        if self.path != "/generate":
            self._error(404, f"no POST route '{self.path}' (have "
                             f"/generate, /admin/drain, /admin/reload)")
            return
        tenant = self.headers.get("X-Tenant-Id") or None
        try:
            payload = bounded_call(
                lambda: srv.handle_generate(
                    body, trace_id=request_id, received_at=received_at,
                    hops=hops, tenant=tenant,
                ),
                timeout=srv.engine.serve.request_timeout,
                label="serve_request",
            )
        except QuotaExceeded as e:
            # per-TENANT admission control: Retry-After comes from the
            # tenant's own bucket refill, not the global queue estimate
            # (other tenants are still being admitted)
            self._json(429, {"error": str(e), "tenant": e.tenant},
                       headers={"Retry-After": str(e.retry_after_s)})
            return
        except QueueFull as e:
            # admission control (queue full OR draining): tell the
            # client WHEN to come back — queue depth x recent step p50
            self._json(429, {"error": str(e)}, headers={
                "Retry-After": str(srv.scheduler.retry_after_s()),
            })
            return
        except (ValueError, TypeError) as e:
            self._error(400, str(e))
            return
        except (ReplayExhausted, DeadlineExceeded, DrainTimeout) as e:
            # the request itself is fine — the SERVICE could not finish
            # it (replay budget spent, queued past deadline, drain
            # deadline): 503 with the reason, safe to retry elsewhere
            self._error(503, str(e))
            return
        except (SeamTimeout, TimeoutError) as e:
            self._error(503, str(e))
            return
        except Exception as e:
            telemetry.inc("serve/request_errors")
            self._error(500, f"{type(e).__name__}: {e}")
            return
        headers = {}
        if payload.get("trace_id"):
            headers["X-Request-Id"] = payload["trace_id"]
        if hops:
            headers["X-Hop-Count"] = str(hops)
        self._json(200, payload, headers=headers)


class InferenceServer:
    """Engine + slot scheduler + supervisor + HTTP listener, one object.

    The decode driver is the continuous-batching :class:`SlotScheduler`
    (trlx_tpu.serve.slots — step-level harvest/admission over the
    persistent KV slot pool).

    ``start()`` warms the decode programs (unless ``warmup=False``),
    starts the scheduler's worker (which enters the serve supervisor when
    ``serve.stall_timeout`` > 0), and binds the HTTP thread; ``stop()``
    tears all three down. Usable in-process (tests pass port=0 and read
    ``server.port``) or via ``python -m trlx_tpu.serve``.
    """

    def __init__(self, engine, host: Optional[str] = None,
                 port: Optional[int] = None):
        self.engine = engine
        cfg = engine.serve
        self.host = cfg.host if host is None else host
        self.port = cfg.port if port is None else port
        sup = None
        if cfg.stall_timeout > 0:
            # serving has no checkpoint to rescue; a stalled-decode
            # escalation aborts the process (exit 70) so the scheduler
            # restarts a fresh, working replica
            sup = RunSupervisor(
                stall_timeout=cfg.stall_timeout, stall_action="abort"
            )
        self.supervisor = sup
        self.scheduler = SlotScheduler(engine, run_supervisor=sup)
        if sup is not None:
            # a watchdog stall dumps the engine-step ring next to the
            # all-thread stack dump (trlx_tpu.serve.trace.FlightRecorder)
            sup.add_dump_fn(self.scheduler.dump_flight_recorder)
        self._httpd: Optional[ThreadingHTTPServer] = None  # guarded-by: _stop_lock
        self._http_thread: Optional[threading.Thread] = None  # guarded-by: _stop_lock
        self._stop_lock = threading.Lock()
        # -- crash-only lifecycle (docs "Fault tolerance") -------------- #
        self._lifecycle_lock = threading.Lock()
        self._drain_thread: Optional[threading.Thread] = None  # guarded-by: _lifecycle_lock
        # SIGTERM sets this; serve_forever's poll loop runs the actual
        # begin_drain(). The handler itself may not take _lifecycle_lock
        # (non-reentrant: a SIGTERM landing while the interrupted frame
        # holds it — e.g. Ctrl-C racing /admin/drain — self-deadlocks)
        # nor construct the drain thread.
        self._drain_requested = threading.Event()
        self._drain_done = threading.Event()
        self._drain_clean = False
        self._watch_stop = threading.Event()
        self._watch_thread: Optional[threading.Thread] = None  # guarded-by: _stop_lock
        self._watch_last_tried: Optional[str] = None

    @property
    def draining(self) -> bool:
        """Admission state for /readyz: True once a drain has begun
        (SIGTERM or POST /admin/drain), from the moment of entry —
        including the window between SIGTERM landing and the poll loop
        starting the drain thread."""
        if self._drain_requested.is_set():
            return True
        with self._lifecycle_lock:
            started = self._drain_thread is not None
        return started or self.scheduler._draining

    @property
    def warmed(self) -> bool:
        """Whether the scheduler's prefill/step executables are compiled."""
        return self.scheduler.warmed

    # -- request semantics ---------------------------------------------- #

    def handle_generate(self, body: dict, trace_id: Optional[str] = None,
                        received_at: Optional[float] = None,
                        hops: int = 0,
                        tenant: Optional[str] = None) -> dict:
        """One request end-to-end: tokenize, submit, wait, shape the
        response. Runs inside bounded_call — raising is the error path
        (the handler maps exception types to HTTP codes). ``trace_id``,
        ``received_at``, ``hops`` (the inbound ``X-Hop-Count``, 0 =
        no proxy in front), and ``tenant`` (the ``X-Tenant-Id`` header;
        the JSON ``"tenant"`` field is the headerless fallback) come
        from the HTTP edge; direct callers may omit all of them (the
        scheduler mints a trace at submit and charges the default
        tenant)."""
        chaos.maybe_inject("serve_request")
        if tenant is None and body.get("tenant") is not None:
            tenant = str(body["tenant"])
        if "tokens" in body:
            tokens = [int(t) for t in body["tokens"]]
        elif "prompt" in body:
            tokens = self.engine.encode_prompt(str(body["prompt"]))
        else:
            raise ValueError("body needs 'prompt' (string) or 'tokens' "
                             "(token-id list)")
        max_new = body.get("max_new_tokens")
        seed = body.get("seed")
        deadline_ms = body.get("deadline_ms")
        trace = None
        if self.engine.serve.request_tracing:
            trace = RequestTrace(trace_id=trace_id, received=received_at)
        priority = body.get("priority")
        req = self.scheduler.submit(
            tokens, max_new_tokens=max_new,
            seed=None if seed is None else int(seed),
            trace=trace,
            deadline_ms=None if deadline_ms is None else float(deadline_ms),
            priority=None if priority is None else int(priority),
            tenant=tenant,
        )
        req.wait()  # bounded by the caller's bounded_call
        payload = {
            "tokens": req.result,
            "text": self.engine.tokenizer.decode(
                req.result, skip_special_tokens=True
            ),
            "bucket": list(req.shape),
            "latency_ms": round(req.latency_s * 1000.0, 3),
            "queue_depth": self.scheduler.queue_depth(),
            "model_version": req.model_version,
        }
        if req.degraded:
            # brownout clamped this request's max_new_tokens — a partial
            # answer, declared so the client can tell it from a full one
            payload["degraded"] = True
        if req.trace is not None:
            req.trace.responded = monotonic()
            payload["trace_id"] = req.trace.trace_id
            if body.get("trace"):
                payload["trace"] = req.trace.to_dict()
                if hops:
                    payload["trace"]["hops"] = hops
        return payload

    # -- graceful drain --------------------------------------------------- #

    def begin_drain(self) -> None:
        """Start a graceful drain without blocking the caller (SIGTERM
        handlers and the /admin/drain route must return immediately):
        admission flips to 429 now; a background thread finishes the
        in-flight work, flushes telemetry, and tears the server down.
        Idempotent."""
        with self._lifecycle_lock:
            if self._drain_thread is not None:
                return
            self._drain_thread = threading.Thread(
                target=self._do_drain, name="trlx-serve-drain", daemon=True
            )
            self._drain_thread.start()

    def _do_drain(self) -> None:
        try:
            # scheduler-level drain: rejects new work, finishes (or
            # deadline-sheds) everything in flight, dumps the flight
            # recorder, stops the worker
            self._drain_clean = self.scheduler.drain()
        finally:
            self._watch_stop.set()
            try:
                tel = telemetry.current()
                if tel is not None:
                    tel.write()  # the post-mortem must not lose metrics
            except Exception as e:
                print(f"[trlx_tpu.serve] telemetry flush failed during "
                      f"drain: {e!r}", file=sys.stderr, flush=True)
            self.stop()
            print(f"[trlx_tpu.serve] drained "
                  f"({'clean' if self._drain_clean else 'deadline hit'})",
                  file=sys.stderr, flush=True)
            self._drain_done.set()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Blocking drain for in-process callers (tests): begin + wait.
        Returns True when everything in flight finished cleanly."""
        self.begin_drain()
        budget = timeout if timeout is not None \
            else self.engine.serve.drain_timeout + 30.0
        self._drain_done.wait(timeout=budget)
        return self._drain_clean

    # -- live checkpoint hot-swap ----------------------------------------- #

    def reload(self, checkpoint: Optional[str] = None) -> dict:
        """Hot-swap the serving weights from ``checkpoint`` (a concrete
        checkpoint directory, or a run directory whose ``LATEST`` is
        re-resolved; default: the run directory this engine was built
        from). Delegates the swap protocol — step-boundary install,
        smoke probe, rollback — to the scheduler; raises
        FileNotFoundError/ValueError for unusable paths (HTTP 400)."""
        if checkpoint is None:
            if self.engine.checkpoint_path is None:
                raise ValueError(
                    "no default checkpoint to reload: the engine was not "
                    "built from one — name one in the request body "
                    '({"checkpoint": "..."})'
                )
            checkpoint = os.path.dirname(self.engine.checkpoint_path)
        params, resolved = self.engine.load_params(checkpoint)
        result = self.scheduler.request_swap(params, label=resolved)
        result["checkpoint"] = resolved
        if result.get("reloaded"):
            print(f"[trlx_tpu.serve] hot-swapped to {resolved} "
                  f"(model_version {result['model_version']})",
                  file=sys.stderr, flush=True)
        else:
            print(f"[trlx_tpu.serve] reload REJECTED ({resolved}): "
                  f"{result.get('reason')}", file=sys.stderr, flush=True)
        return result

    def _watch_loop(self) -> None:
        """``serve.watch_checkpoints`` poller: re-resolve the run
        directory's ``LATEST`` every interval and hot-swap when it moves.
        A checkpoint that fails its probe is remembered and not retried
        until ``LATEST`` moves again (no hot-loop on a bad save)."""
        from trlx_tpu.utils.checkpoint import find_latest_checkpoint

        interval = float(self.engine.serve.watch_checkpoints)
        run_dir = os.path.dirname(self.engine.checkpoint_path)
        while not self._watch_stop.wait(interval):
            if self.draining:
                return
            try:
                latest = find_latest_checkpoint(run_dir)
            except OSError as e:
                print(f"[trlx_tpu.serve] checkpoint watch: {e!r}",
                      file=sys.stderr, flush=True)
                continue
            if latest is None or latest == self.engine.checkpoint_path \
                    or latest == self._watch_last_tried:
                continue
            self._watch_last_tried = latest
            try:
                self.reload(latest)
            except Exception as e:
                telemetry.inc("serve/reload_failures")
                print(f"[trlx_tpu.serve] watched reload of {latest} "
                      f"failed: {e!r}", file=sys.stderr, flush=True)

    # -- lifecycle ------------------------------------------------------- #

    def start(self, warmup: bool = True) -> "InferenceServer":
        telemetry.predeclare(_SERVE_COUNTERS)
        if self.engine.serve.request_tracing:
            telemetry.predeclare(SLO_COUNTERS)
            telemetry.set_gauge("serve/goodput", 0.0)
            # pin the windowed-SLO objective for this serve process so
            # burn rates are scored against the configured target from
            # the first request (no-op when telemetry is off)
            from trlx_tpu.serve.trace import slo_engine

            slo_engine(target=self.engine.serve.slo_target)
        telemetry.set_gauge("serve/slot_occupancy", 0.0)
        # quantization tier, visible per scrape: bytes one committed
        # token holds resident, and the KV element width in bits
        # (16 = bf16, 8 = int8) — the numeric twin of /healthz's
        # ``kv.kv_dtype`` string
        from trlx_tpu.telemetry.flops import kv_bytes_per_token

        kv_dtype = self.engine.serve.kv_dtype
        telemetry.set_gauge(
            "serve/kv_bytes_per_token",
            kv_bytes_per_token(self.engine.spec, kv_dtype),
        )
        telemetry.set_gauge(
            "serve/kv_dtype", 8 if kv_dtype == "int8" else 16
        )
        # page pool health, scraped from 0
        telemetry.set_gauge(
            "serve/pages_free", self.scheduler.cache.free_pages()
        )
        telemetry.set_gauge("serve/prefix_hit_rate", 0.0)
        telemetry.set_gauge("serve/pages_per_request_p95", 0.0)
        if self.engine.serve.speculation != "off":
            telemetry.set_gauge("serve/spec_acceptance_rate", 0.0)
        telemetry.set_gauge(
            "serve/model_version", self.engine.model_version
        )
        # serve-mesh capacity gauges, scraped from startup (also set at
        # every weight install; re-asserted here so /metrics carries them
        # even before the first install on deferred-init paths)
        from trlx_tpu.serve import layouts

        telemetry.set_gauge("serve/mesh_devices", self.engine.mesh.size)
        if self.engine.blocks is not None:
            telemetry.set_gauge(
                "serve/params_gb_per_device",
                layouts.tree_bytes_per_device(
                    (self.engine.blocks, self.engine.embed,
                     self.engine.ln_f)
                ) / 2**30,
            )
        if warmup and not self.warmed:
            for name, secs in self.scheduler.warmup().items():
                print(f"[trlx_tpu.serve] warmed {name}: {secs:.3f}s "
                      f"first call (compile)", file=sys.stderr, flush=True)
        self.scheduler.start()
        if self.engine.serve.watch_checkpoints > 0 \
                and self._watch_thread is None:
            if self.engine.checkpoint_path is None:
                print("[trlx_tpu.serve] serve.watch_checkpoints set but "
                      "the engine was not built from a checkpoint; "
                      "nothing to watch", file=sys.stderr, flush=True)
            else:
                self._watch_stop.clear()
                watch = threading.Thread(
                    target=self._watch_loop, name="trlx-serve-watch",
                    daemon=True,
                )
                # publish under the same lock stop() swaps under — a
                # drain-thread stop() racing start() must see either
                # None or a joinable thread, never a torn handle
                with self._stop_lock:
                    self._watch_thread = watch
                watch.start()
        handler = type("Handler", (_Handler,), {"server_ref": self})
        httpd = ThreadingHTTPServer((self.host, self.port), handler)
        self.port = httpd.server_address[1]  # resolve port=0
        http_thread = threading.Thread(
            target=httpd.serve_forever, name="trlx-serve-http",
            daemon=True,
        )
        with self._stop_lock:
            self._httpd = httpd
            self._http_thread = http_thread
        http_thread.start()
        print(f"[trlx_tpu.serve] listening on http://{self.host}:"
              f"{self.port} (buckets {[list(b) for b in self.engine.buckets]})",
              file=sys.stderr, flush=True)
        return self

    def stop(self) -> None:
        # idempotent and thread-safe: the drain thread's _do_drain and the
        # owner's own stop() may race here
        self._watch_stop.set()
        with self._stop_lock:
            watch, self._watch_thread = self._watch_thread, None
            httpd, self._httpd = self._httpd, None
            http_thread, self._http_thread = self._http_thread, None
        if watch is not None:
            watch.join(timeout=5.0)
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if http_thread is not None:
            http_thread.join(timeout=5.0)
        self.scheduler.stop()

    def _on_sigterm(self, signum, frame) -> None:
        # runs between bytecodes on whatever frame the signal interrupts:
        # Event.set() only. begin_drain() takes the non-reentrant
        # _lifecycle_lock and builds a Thread — if SIGTERM lands while
        # the interrupted frame is inside begin_drain() (Ctrl-C racing
        # /admin/drain), doing that here self-deadlocks. The poll loop
        # in serve_forever picks the request up within a second.
        self._drain_requested.set()

    def serve_forever(self) -> None:
        """Block the calling thread until the server drains (the CLI's
        tail). SIGTERM and Ctrl-C both begin a graceful drain — finish
        in-flight work within ``serve.drain_timeout``, flush telemetry +
        flight recorder — and this returns normally, so the process
        exits 0 and the orchestrator sees a clean rotation."""
        try:
            signal.signal(signal.SIGTERM, self._on_sigterm)
        except ValueError as e:
            # not the main thread: Ctrl-C/begin_drain() still work
            print(f"[trlx_tpu.serve] SIGTERM handler not installed: {e}",
                  file=sys.stderr, flush=True)
        try:
            while not self._drain_done.wait(timeout=1.0):
                if self._drain_requested.is_set():
                    print("[trlx_tpu.serve] SIGTERM: beginning graceful "
                          "drain", file=sys.stderr, flush=True)
                    # start the drain FIRST, then clear, so `draining`
                    # (request-set OR thread-started) never flickers off
                    self.begin_drain()
                    self._drain_requested.clear()
        except KeyboardInterrupt:
            print("[trlx_tpu.serve] interrupted; beginning graceful drain",
                  file=sys.stderr, flush=True)
            self.begin_drain()
            self._drain_done.wait(
                timeout=self.engine.serve.drain_timeout + 30.0
            )
        finally:
            self.stop()
