"""Crash-only serving lifecycle tests (trlx_tpu/serve, docs "Fault
tolerance" / "Serving"): the restart-recovery greedy-parity sweep
(page-size x kill-point matrix — every in-flight request survives a
poisoned step / engine rebuild bit-identical, zero recompiles, zero
page leaks), deadline-aware overload control (queued-past-deadline
shed + priority admission), graceful drain under load (SIGTERM /
``POST /admin/drain`` -> 429 + Retry-After at the door, in-flight work
finishes, flight-recorder dump, ``/readyz`` flips while ``/healthz``
stays alive), live checkpoint hot-swap under load (step-boundary
install, smoke-probe rollback on poisoned weights, ``LATEST`` watcher),
and the slow-marked chaos soak + SIGTERM subprocess drill behind
``make serve-chaos``.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.serve import InferenceEngine, InferenceServer, ServeConfig
from trlx_tpu.serve.admission import DeadlineExceeded
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.supervisor import chaos
from test_serve import tiny_config_dict
from test_slots import direct_generate


def build_engine(page_size=4, buckets=None, **overrides):
    telemetry.start()
    serve = ServeConfig(**{
        "buckets": buckets or [[2, 8, 8]], "max_queue": 64,
        "request_timeout": 30.0, "slots": 4,
        "page_size": page_size, **overrides,
    })
    return InferenceEngine(TRLConfig.from_dict(tiny_config_dict()),
                           serve=serve)


def _http(port, path, method="GET", payload=None):
    """(status, headers, body) — HTTPError is a RESPONSE here, not an
    exception: the error taxonomy is what these tests assert."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode() if payload is not None else None,
        headers={"Content-Type": "application/json"},
        method=method,
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, dict(resp.headers), json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


# --------------------------------------------------------------------- #
# tentpole: restart recovery — the unit of failure is the step
# --------------------------------------------------------------------- #

ROWS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 4, 6], [1, 3, 5, 7],
        [9, 8, 7]]
MAX_NEW = 4

# greedy decode is Markov on the token prefix, so the expected output
# is the SAME for every page size / kill point — computed once against
# the first engine's weights (all config-built engines share them)
_EXPECTED = []


def expected_rows(engine):
    if not _EXPECTED:
        for i in range(0, len(ROWS), 2):
            pair = ROWS[i:i + 2]
            oracle = direct_generate(engine, pair, (2, 8, 8),
                                     gen_size=MAX_NEW)
            for j in range(len(pair)):
                _EXPECTED.append(engine.depad_row(oracle, j, MAX_NEW))
    return _EXPECTED


@pytest.mark.parametrize("page_size", [3, 8, 16])  # 16 = bucket T_max
def test_restart_recovery_greedy_parity_sweep(page_size):
    """The acceptance drill, swept across page sizes: kill the engine
    mid-prefill (serve_admit fault), mid-decode (poisoned step with
    committed tokens), and with a queued backlog behind the live batch.
    Every request must complete BIT-IDENTICAL to an uninterrupted run,
    with zero recompiles and zero leaked slots/pages."""
    engine = build_engine(page_size=page_size)
    registry = telemetry.current().registry
    want = expected_rows(engine)
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        for kill, schedule in [
            ("mid_prefill", "serve_admit:exc@1"),
            ("mid_decode", "serve_decode:exc@2"),
            ("queued_backlog", "serve_decode:exc@1"),
        ]:
            chaos.configure(schedule)
            reqs = [s.submit(list(r), max_new_tokens=MAX_NEW)
                    for r in ROWS]
            for r in reqs:
                r.wait(timeout=60.0)
            chaos.reset()
            for i, req in enumerate(reqs):
                assert req.result == want[i], (
                    f"{kill}/page_size={page_size}: request {i} diverged "
                    f"from the uninterrupted oracle"
                )
            assert any(r.replays >= 1 for r in reqs), kill
            stats = s.pool_stats()
            assert s.free_slots() == 4, kill
            assert (stats["pages_free"] + stats["pages_cached"]
                    == stats["pages_total"]), f"{kill}: leaked pages"
        assert registry.counters.get("serve/request_errors", 0.0) == 0.0
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert registry.counters["serve/replays"] >= 3.0
    finally:
        chaos.reset()
        s.stop()
        telemetry.start()


# --------------------------------------------------------------------- #
# deadline-aware overload control
# --------------------------------------------------------------------- #


def test_deadline_shed_and_priority_admission():
    """A request queued past its ``deadline_ms`` is shed at the next
    admission scan (DeadlineExceeded, serve/shed_expired) — never
    decoded uselessly — while a higher-priority request jumps the FIFO
    order and is admitted in the first wave."""
    engine = build_engine(page_size=4)
    registry = telemetry.current().registry
    s = SlotScheduler(engine, slots=2)
    s.warmup()
    # queue up BEFORE starting the worker: the first admission scan is
    # deterministic — priority order decides the wave, and the doomed
    # request's deadline has already passed
    blockers = [s.submit([i + 1], max_new_tokens=4) for i in range(2)]
    doomed = s.submit([7, 7], max_new_tokens=2, deadline_ms=5.0)
    vip = s.submit([5, 5], max_new_tokens=2, priority=5)
    time.sleep(0.05)  # doomed expires while still queued
    s.start()
    try:
        vip.wait(timeout=30.0)
        for b in blockers:
            b.wait(timeout=30.0)
        with pytest.raises(DeadlineExceeded, match="deadline_ms"):
            doomed.wait(timeout=10.0)
        assert registry.counters["serve/shed_expired"] >= 1.0
        # priority 5 beat the earlier-submitted FIFO requests to a slot
        admits = [ev for ev in s.events if ev[0] == "admit"]
        assert vip in [ev[2] for ev in admits[:2]], (
            "priority request was not admitted in the first wave"
        )
        assert all(ev[2] is not doomed for ev in admits)
    finally:
        s.stop()
        telemetry.start()


# --------------------------------------------------------------------- #
# hot-swap: probe rollback on poisoned weights
# --------------------------------------------------------------------- #


def test_hot_swap_probe_rollback_keeps_serving():
    """A candidate checkpoint full of NaNs passes shape validation but
    fails the one-bucket smoke probe: the swap rolls back, the version
    never bumps, and the OLD weights keep serving bit-identically."""
    engine = build_engine(page_size=4)
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        good = s.submit([1, 2, 3], max_new_tokens=2)
        good.wait(timeout=30.0)
        params = engine._init_params()
        poisoned = jax.tree_util.tree_map(
            lambda x: np.full(x.shape, np.nan, x.dtype)
            if np.issubdtype(np.asarray(x).dtype, np.floating) else x,
            params,
        )
        res = s.request_swap(poisoned, label="poisoned")
        assert res["reloaded"] is False
        assert "non-finite" in res["reason"]
        assert engine.model_version == 1
        assert registry.counters["serve/reload_failures"] >= 1.0
        again = s.submit([1, 2, 3], max_new_tokens=2)
        again.wait(timeout=30.0)
        assert again.result == good.result, (
            "rollback did not restore the serving weights"
        )
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
    finally:
        s.stop()
        telemetry.start()


def test_hot_swap_chaos_reload_fault_rolls_back_then_recovers():
    """Chaos drill for the ``serve_reload`` seam (KNOWN_SEAMS contract,
    graftlint chaos-seam-tested): an injected fault at swap application
    rolls back to the old weights and keeps serving; the NEXT swap on
    the same scheduler — the ``@1`` occurrence consumed — commits."""
    engine = build_engine(page_size=4)
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    chaos.configure("serve_reload:exc@1")
    try:
        good = s.submit([1, 2, 3], max_new_tokens=2)
        good.wait(timeout=30.0)
        res = s.request_swap(engine._init_params(), label="drill")
        assert res["reloaded"] is False
        assert "ChaosError" in res["reason"]
        assert engine.model_version == 1
        assert registry.counters["serve/reload_failures"] >= 1.0
        # rollback kept the OLD weights serving bit-identically
        again = s.submit([1, 2, 3], max_new_tokens=2)
        again.wait(timeout=30.0)
        assert again.result == good.result, (
            "chaos rollback did not restore the serving weights"
        )
        res2 = s.request_swap(engine._init_params(), label="recovered")
        assert res2["reloaded"] is True
        assert engine.model_version == 2
    finally:
        chaos.reset()
        s.stop()
        telemetry.start()


# --------------------------------------------------------------------- #
# HTTP lifecycle e2e: drain under load, Retry-After, hot-swap under load
# --------------------------------------------------------------------- #

SERVE_HTTP = ServeConfig(
    buckets=[[2, 8, 8], [4, 8, 8]], max_queue=8, request_timeout=60.0,
    slots=4, page_size=4,
    drain_timeout=15.0,
)


@pytest.fixture(scope="module")
def http_engine():
    telemetry.start()
    return InferenceEngine(TRLConfig.from_dict(tiny_config_dict()),
                           serve=SERVE_HTTP)


def _burst(port, rows, max_new=8):
    """Fire len(rows) concurrent /generate calls; returns the slots the
    responses land in + the threads to join."""
    out = [None] * len(rows)

    def call(i):
        out[i] = _http(port, "/generate", "POST",
                       {"tokens": rows[i], "max_new_tokens": max_new})

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(rows))]
    for t in threads:
        t.start()
    return out, threads


def test_drain_under_load_e2e(http_engine):
    """SIGTERM-equivalent drill over HTTP: mid-burst ``POST
    /admin/drain`` returns 202 and flips ``/readyz`` to 503 while
    ``/healthz`` stays 200 (rotate, don't kill); NEW submissions bounce
    with 429 + Retry-After; every in-flight request finishes 200; the
    drain is clean and dumps the flight recorder."""
    registry = telemetry.start().registry
    srv = InferenceServer(http_engine, port=0).start(warmup=True)
    release = threading.Event()
    try:
        status, _, body = _http(srv.port, "/readyz")
        assert status == 200 and body["ready"] is True

        # hold the decode steps while the drill looks at the draining
        # server: a toy engine otherwise finishes the burst, drains and
        # closes its socket under the drill's own requests
        step = srv.scheduler.runtime.step

        def held_step(seed):
            release.wait(timeout=60.0)
            return step(seed)

        srv.scheduler.runtime.step = held_step
        rows = [[1, 2, 3], [4, 5], [6, 7], [8, 9, 1], [2, 2], [3, 1, 4]]
        out, threads = _burst(srv.port, rows)
        # wait until the engine actually holds live work
        deadline = time.monotonic() + 30.0
        while not srv.scheduler._live and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.scheduler._live, "burst never reached the slots"
        # ...and has accepted the whole burst: a request that arrives
        # once the drain has begun is refused (429), by design
        while (registry.counters.get("serve/requests", 0.0) < len(rows)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert registry.counters["serve/requests"] == len(rows)

        status, _, body = _http(srv.port, "/admin/drain", "POST", {})
        assert status == 202 and body["draining"] is True
        assert body["drain_timeout"] == SERVE_HTTP.drain_timeout

        status, _, body = _http(srv.port, "/readyz")
        assert status == 503 and body["draining"] is True
        status, _, _ = _http(srv.port, "/healthz")
        assert status == 200, "liveness must survive a drain"

        status, headers, body = _http(
            srv.port, "/generate", "POST",
            {"tokens": [9, 9], "max_new_tokens": 1},
        )
        assert status == 429
        assert "draining" in body["error"]
        assert int(headers["Retry-After"]) >= 1

        release.set()
        for t in threads:
            t.join(timeout=60.0)
        for i, (status, _, body) in enumerate(out):
            assert status == 200, f"in-flight request {i} lost: {body}"
            assert body["tokens"], i

        assert srv._drain_done.wait(timeout=30.0)
        assert srv._drain_clean is True
        assert registry.counters["serve/drains"] == 1.0
        assert registry.counters["serve/flight_dumps"] >= 1.0
        assert registry.counters.get("serve/request_errors", 0.0) == 0.0
    finally:
        release.set()
        srv.stop()
        telemetry.start()


def test_retry_after_paces_the_backlog(http_engine):
    """Satellite drill: 429s carry ``Retry-After`` = queue depth x
    recent step p50 (>= 1s) — measured against a queue deliberately
    wedged by a chaos-hung decode, then fully recovered via replay
    once the seam is released."""
    telemetry.start()
    srv = InferenceServer(http_engine, port=0).start(warmup=True)
    try:
        chaos.configure("serve_decode:hang=60@1")
        out, threads = _burst(srv.port, [[1, 2]], max_new=2)
        deadline = time.monotonic() + 30.0
        while not srv.scheduler._live and time.monotonic() < deadline:
            time.sleep(0.01)
        # fill the queue behind the wedged step...
        more, more_threads = _burst(
            srv.port, [[3 + i, 4] for i in range(SERVE_HTTP.max_queue)],
            max_new=2,
        )
        deadline = time.monotonic() + 30.0
        while (srv.scheduler.queue_depth() < SERVE_HTTP.max_queue
               and time.monotonic() < deadline):
            time.sleep(0.01)
        # ...and the next arrival is paced, not just bounced
        status, headers, body = _http(
            srv.port, "/generate", "POST",
            {"tokens": [7, 7], "max_new_tokens": 1},
        )
        assert status == 429
        assert int(headers["Retry-After"]) >= 1
        assert "full" in body["error"]
        # release the hang: the poisoned step replays EVERYTHING
        chaos.reset()
        for t in threads + more_threads:
            t.join(timeout=90.0)
        for status, _, body in out + more:
            assert status == 200, body
    finally:
        chaos.reset()
        srv.stop()
        telemetry.start()


def test_sigterm_handler_only_sets_the_event(http_engine):
    """Regression (graftlint signal-unsafe-call): the SIGTERM handler
    used to call begin_drain() directly — taking the non-reentrant
    _lifecycle_lock and constructing the drain thread INSIDE the
    handler. A SIGTERM landing while the interrupted frame was already
    inside begin_drain() (Ctrl-C racing /admin/drain) self-deadlocked
    with no second thread involved. Now the handler only sets
    _drain_requested: this drill reproduces the interleaving by
    delivering the handler while _lifecycle_lock is held and requires
    it to return immediately, flip admission at once, and leave the
    actual drain to serve_forever's poll loop."""
    telemetry.start()
    srv = InferenceServer(http_engine, port=0).start(warmup=True)
    try:
        delivered = threading.Event()

        def deliver():
            srv._on_sigterm(signal.SIGTERM, None)
            delivered.set()

        with srv._lifecycle_lock:  # the frame the signal interrupted
            threading.Thread(target=deliver, daemon=True).start()
            assert delivered.wait(timeout=5.0), \
                "_on_sigterm blocked on _lifecycle_lock"
            assert srv._drain_requested.is_set()
        # no drain thread from the handler — starting it is the poll
        # loop's job — but admission flips from the signal alone
        with srv._lifecycle_lock:
            assert srv._drain_thread is None
        assert srv.draining is True
        status, _, body = _http(srv.port, "/readyz")
        assert status == 503 and body["draining"] is True
        status, _, _ = _http(srv.port, "/healthz")
        assert status == 200, "liveness must survive the window"
        # the poll loop's half, inlined: begin_drain consumes the flag
        srv.begin_drain()
        srv._drain_requested.clear()
        assert srv._drain_done.wait(timeout=60.0)
        assert srv._drain_clean is True
    finally:
        try:
            srv.stop()
        except RuntimeError:
            pass
        telemetry.start()


def test_hot_swap_under_load_e2e(tmp_path):
    """Live reload mid-burst: the endpoint NEVER refuses connections,
    in-flight requests finish on their admitted version, the swap lands
    at a step boundary with zero recompiles, and post-swap output is
    bit-identical to direct generation under the NEW weights."""
    from trlx_tpu.utils.loading import get_model

    run = str(tmp_path / "run")
    cfg_a = TRLConfig.from_dict(tiny_config_dict())
    get_model(cfg_a.model.model_type)(cfg_a).save(
        os.path.join(run, "step_1")
    )
    d2 = tiny_config_dict()
    d2["train"]["seed"] = 1
    cfg_b = TRLConfig.from_dict(d2)
    get_model(cfg_b.model.model_type)(cfg_b).save(
        os.path.join(run, "step_2")
    )

    registry = telemetry.start().registry
    engine = InferenceEngine.from_checkpoint(
        os.path.join(run, "step_1"), serve=SERVE_HTTP
    )
    srv = InferenceServer(engine, port=0).start(warmup=True)
    try:
        assert engine.model_version == 1
        rows = [[1, 2, 3], [4, 5], [6, 7, 8], [2, 4], [5, 5, 5], [8, 1]]
        out, threads = _burst(srv.port, rows)
        # reload resolves the run dir's newest step (step_2) by default
        status, _, body = _http(srv.port, "/admin/reload", "POST", {})
        assert status == 200, body
        assert body["reloaded"] is True
        assert body["model_version"] == 2
        assert body["checkpoint"].endswith("step_2")
        for t in threads:
            t.join(timeout=90.0)
        versions = set()
        for status, _, body in out:
            assert status == 200, body  # never refused mid-swap
            versions.add(body["model_version"])
        assert versions <= {1, 2}

        # post-swap parity against the CURRENT (new) serving views
        status, _, body = _http(
            srv.port, "/generate", "POST",
            {"tokens": [1, 2, 3], "max_new_tokens": 4},
        )
        assert status == 200 and body["model_version"] == 2
        oracle = direct_generate(engine, [[1, 2, 3]], (2, 8, 8),
                                 gen_size=4)
        assert body["tokens"] == engine.depad_row(oracle, 0, 4)

        status, _, metrics = _http(srv.port, "/metrics")
        assert metrics["gauges"]["serve/model_version"] == 2
        assert metrics["counters"]["serve/reloads"] == 1
        assert metrics["counters"]["compile/recompiles"] == 0
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        status, _, body = _http(srv.port, "/readyz")
        assert status == 200  # a swap never unreadies the replica
    finally:
        srv.stop()
        telemetry.start()


def test_watch_checkpoints_auto_swaps(tmp_path):
    """``serve.watch_checkpoints`` polls the run dir and hot-swaps when
    a newer committed ``step_<N>`` lands — no /admin/reload needed."""
    from trlx_tpu.utils.loading import get_model

    run = str(tmp_path / "run")
    cfg = TRLConfig.from_dict(tiny_config_dict())
    trainer = get_model(cfg.model.model_type)(cfg)
    trainer.save(os.path.join(run, "step_1"))

    telemetry.start()
    serve = ServeConfig(
        buckets=[[2, 8, 8]], max_queue=8, request_timeout=30.0,
        slots=2, page_size=4,
        watch_checkpoints=0.2,
    )
    engine = InferenceEngine.from_checkpoint(run, serve=serve)
    srv = InferenceServer(engine, port=0).start(warmup=True)
    try:
        assert engine.model_version == 1
        trainer.save(os.path.join(run, "step_2"))
        deadline = time.monotonic() + 20.0
        while engine.model_version < 2 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert engine.model_version == 2, "watcher never swapped"
        assert engine.checkpoint_path.endswith("step_2")
        status, _, body = _http(
            srv.port, "/generate", "POST",
            {"tokens": [1, 2], "max_new_tokens": 2},
        )
        assert status == 200 and body["model_version"] == 2
    finally:
        srv.stop()
        telemetry.start()


# --------------------------------------------------------------------- #
# slow tier (make serve-chaos): SIGTERM subprocess drill + chaos soak
# --------------------------------------------------------------------- #


@pytest.mark.slow
def test_sigterm_drains_and_exits_zero(tmp_path):
    """The real-signal drill: a subprocess endpoint gets SIGTERM with a
    request in flight, finishes it, logs the drain, and exits 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    worker = os.path.join(os.path.dirname(__file__),
                          "lifecycle_worker.py")
    proc = subprocess.Popen(
        [sys.executable, worker], env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        port = None
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if line.startswith("PORT="):
                port = int(line.strip().split("=", 1)[1])
                break
            if not line and proc.poll() is not None:
                break
        assert port, f"worker never came up: {proc.stderr.read()}"

        out, threads = _burst(port, [[1, 2, 3], [4, 5]], max_new=8)
        time.sleep(0.2)  # let the burst reach the slots
        proc.send_signal(signal.SIGTERM)
        _, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err
        assert "drained" in err
        for t in threads:
            t.join(timeout=10.0)
        for status, _, body in out:
            assert status == 200, (body, err)
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.mark.slow
def test_serve_chaos_soak():
    """The crash-only soak: waves of mixed-length traffic with injected
    poisoned steps, a poisoned admission, and a live hot-swap — ZERO
    lost requests, zero page leaks, zero recompiles, and a clean drain
    at the end."""
    engine = build_engine(
        page_size=4, buckets=[[2, 8, 8], [4, 8, 8], [4, 16, 8]],
        max_queue=128,
    )
    registry = telemetry.current().registry
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    done = []
    try:
        for wave in range(6):
            if wave == 1:
                chaos.configure("serve_decode:exc@2")
            elif wave == 3:
                chaos.configure("serve_admit:exc@1")
            reqs = []
            for i in range(12):
                n = 1 + (wave * 12 + i) % 10      # prompt lengths 1..10
                mn = 1 + (wave + i) % 6           # gen lengths 1..6
                row = [(j + i) % 250 + 1 for j in range(n)]
                reqs.append(s.submit(row, max_new_tokens=mn))
            for r in reqs:
                r.wait(timeout=120.0)
            chaos.reset()
            done.extend(reqs)
            if wave == 2:
                res = s.request_swap(engine._init_params(), label="soak")
                assert res["reloaded"] is True, res
        assert all(r.result is not None for r in done), "lost a request"
        assert len(done) == 72
        assert s.drain() is True  # idle: clean by construction
        stats = s.pool_stats()
        assert (stats["pages_free"] + stats["pages_cached"]
                == stats["pages_total"]), "soak leaked pages"
        assert registry.counters["serve/replays"] >= 1.0
        assert registry.counters["serve/reloads"] == 1.0
        assert registry.counters.get("serve/request_errors", 0.0) == 0.0
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert engine.model_version == 2
    finally:
        chaos.reset()
        s.stop()
        telemetry.start()
