"""Request-lifecycle observability tests (trlx_tpu/serve/trace +
telemetry/prometheus): RequestTrace TTFT/ITL semantics, SLO histogram
derivation + goodput, Perfetto span export validity (every line parses,
children nest inside the parent on the request's own track), Prometheus
text exposition (schema + predeclared-zero series + content negotiation
on /metrics), /debug/state, flight-recorder ring/dump behavior on
poisoned steps and watchdog stalls.
"""

import json
import re
import time
import urllib.request

import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.serve import InferenceEngine, InferenceServer, ServeConfig
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.serve.trace import FlightRecorder, RequestTrace
from trlx_tpu.supervisor import RunSupervisor, chaos
from trlx_tpu.telemetry import prometheus
from trlx_tpu.telemetry.registry import MetricsRegistry, TimingHist
from test_serve import tiny_config_dict

SERVE_TRACED = ServeConfig(
    buckets=[[2, 8, 8], [4, 8, 8]],
    max_queue=64,
    request_timeout=30.0,
    slots=4,
    page_size=4,
    slo_ttft_ms=0.0,  # every completed request counts good
    flight_recorder_steps=32,
)


@pytest.fixture(scope="module")
def engine():
    telemetry.start()
    cfg = TRLConfig.from_dict(tiny_config_dict())
    return InferenceEngine(cfg, serve=SERVE_TRACED)


@pytest.fixture()
def fresh_registry():
    session = telemetry.start()
    yield session.registry
    telemetry.start()


@pytest.fixture()
def scheduler(engine, fresh_registry):
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    yield s
    s.stop()


# --------------------------------------------------------------------- #
# TimingHist summary edge cases
# --------------------------------------------------------------------- #


def test_timing_hist_empty_summary():
    h = TimingHist()
    stats = h.stats()
    assert stats["count"] == 0
    assert stats["total_s"] == 0.0
    assert stats["p50_s"] == 0.0 and stats["p95_s"] == 0.0
    assert "first_s" not in stats
    assert h.quantile(0.5) == 0.0 and h.quantile(0.95) == 0.0


def test_timing_hist_single_observation_quantiles():
    h = TimingHist()
    h.observe(0.25)
    # the lone sample is the 'first' (kept apart from the steady-state
    # window) but still answers every quantile
    assert h.quantile(0.5) == 0.25
    assert h.quantile(0.95) == 0.25
    stats = h.stats()
    assert stats["count"] == 1 and stats["first_s"] == 0.25
    assert stats["p50_s"] == 0.25 and stats["p95_s"] == 0.25


def test_timing_hist_p95_with_ties():
    h = TimingHist()
    h.observe(0.1)  # first call, kept apart
    for _ in range(19):
        h.observe(0.2)
    h.observe(0.9)
    # window = 19 ties at 0.2 + one 0.9; p95 over 20 samples indexes the
    # sorted tail, p50 lands mid-tie
    assert h.quantile(0.50) == 0.2
    assert h.quantile(0.95) == 0.9
    h2 = TimingHist()
    for _ in range(10):
        h2.observe(0.5)  # ALL ties
    assert h2.quantile(0.95) == 0.5


# --------------------------------------------------------------------- #
# Prometheus exposition
# --------------------------------------------------------------------- #

# one exposition sample: name{optional comma-joined labels} float
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z0-9_]+=\"[^\"]*\"(,[a-zA-Z0-9_]+=\"[^\"]*\")*\})? "
    r"-?\d+(\.\d+)?([eE][+-]?\d+)?$"
)


def test_prometheus_sanitize():
    assert prometheus.sanitize("serve/ttft") == "trlx_tpu_serve_ttft"
    assert prometheus.sanitize("time/ppo-update") == "trlx_tpu_time_ppo_update"
    assert prometheus.sanitize("9lives").startswith("trlx_tpu__9")


def test_prometheus_render_schema():
    reg = MetricsRegistry()
    reg.inc("serve/requests", 3)
    reg.set_gauge("serve/goodput", 0.5)
    reg.observe("serve/ttft", 0.1)
    reg.observe("serve/ttft", 0.2)
    text = prometheus.render(reg)
    assert text.endswith("\n")
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            parts = line.split()
            assert len(parts) == 4
            assert parts[3] in ("counter", "gauge", "summary",
                                "histogram")
        else:
            assert _SAMPLE.match(line), f"malformed sample line: {line!r}"
    assert "# TYPE trlx_tpu_serve_requests_total counter" in text
    assert "trlx_tpu_serve_requests_total 3.0" in text
    assert "trlx_tpu_serve_goodput 0.5" in text
    assert 'trlx_tpu_serve_ttft_seconds{quantile="0.5"}' in text
    assert 'trlx_tpu_serve_ttft_seconds{quantile="0.95"}' in text
    assert "trlx_tpu_serve_ttft_seconds_count 2.0" in text
    assert (
        "trlx_tpu_serve_ttft_seconds_sum 0.30000000000000004" in text
        or "trlx_tpu_serve_ttft_seconds_sum 0.3" in text
    )


def test_prometheus_predeclared_zero_in_both_expositions(fresh_registry):
    telemetry.predeclare(["serve/slo_good"])
    # JSON: the counter exists at 0 (a dashboard sees a zero series)
    assert telemetry.summary()["counters"]["serve/slo_good"] == 0.0
    # Prometheus: same
    assert "trlx_tpu_serve_slo_good_total 0.0" in telemetry.prometheus_text()


def test_prometheus_empty_histogram_renders_zeros():
    reg = MetricsRegistry()
    reg.hists["serve/itl"] = TimingHist()
    text = prometheus.render(reg)
    assert 'trlx_tpu_serve_itl_seconds{quantile="0.95"} 0.0' in text
    assert "trlx_tpu_serve_itl_seconds_sum 0.0" in text
    assert "trlx_tpu_serve_itl_seconds_count 0.0" in text


def test_prometheus_text_empty_without_session():
    telemetry.stop()
    try:
        assert telemetry.prometheus_text() == ""
    finally:
        telemetry.start()


# --------------------------------------------------------------------- #
# RequestTrace semantics
# --------------------------------------------------------------------- #


def test_trace_itl_aggregation_and_ttft(fresh_registry):
    tr = RequestTrace(trace_id="abc", received=100.0)
    tr.enqueued = 100.0
    tr.admitted = 100.5
    tr.prefill_start = 100.5
    tr.prefill_end = 100.6
    tr.note_token(101.0)  # first token: TTFT, no ITL gap yet
    tr.note_token(101.2)
    tr.note_token(101.3)
    tr.note_token(101.7)
    assert tr.ttft() == pytest.approx(1.0)
    assert tr.itl_count == 3
    assert tr.itl_min == pytest.approx(0.1)
    assert tr.itl_max == pytest.approx(0.4)
    assert tr.itl_mean() == pytest.approx(0.7 / 3)
    # gaps reached the global histogram, raw timestamps were not stored
    assert fresh_registry.hists["serve/itl"].count == 3
    tr.harvested = 101.7
    tr.complete("slots", slo_ttft_s=2.0)
    assert fresh_registry.hists["serve/ttft"].last == pytest.approx(1.0)
    assert fresh_registry.hists["serve/queue_time"].last == pytest.approx(0.5)
    assert fresh_registry.hists["serve/prefill_time"].last == \
        pytest.approx(0.1, abs=1e-9)
    assert fresh_registry.hists["serve/decode_time"].last == \
        pytest.approx(1.1)
    assert fresh_registry.hists["serve/request_latency{path=slots}"] \
        .last == pytest.approx(1.7)
    assert fresh_registry.gauges["serve/goodput"] == 1.0

    d = tr.to_dict()
    assert d["trace_id"] == "abc"
    assert d["ttft_ms"] == pytest.approx(1000.0)
    assert d["tokens"] == 4
    assert d["itl_mean_ms"] == pytest.approx(700.0 / 3, abs=0.01)


def test_trace_goodput_slo_gating(fresh_registry):
    slow = RequestTrace(received=0.0)
    slow.enqueued = 0.0
    slow.note_token(10.0)  # TTFT 10s
    slow.harvested = 10.0
    slow.complete("slots", slo_ttft_s=0.5)
    assert fresh_registry.gauges["serve/goodput"] == 0.0
    fast = RequestTrace(received=20.0)
    fast.enqueued = 20.0
    fast.note_token(20.1)  # TTFT 0.1s
    fast.harvested = 20.1
    fast.complete("slots", slo_ttft_s=0.5)
    assert fresh_registry.gauges["serve/goodput"] == 0.5
    assert fresh_registry.counters["serve/slo_total"] == 2.0
    assert fresh_registry.counters["serve/slo_good"] == 1.0


def test_trace_perfetto_export_parses_and_nests(fresh_registry, tmp_path):
    tel = telemetry.current()
    t0 = tel.tracer.t0
    tr = RequestTrace(trace_id="feed", received=t0 + 1.0)
    tr.enqueued = t0 + 1.0
    tr.admitted = t0 + 1.5
    tr.prefill_start = t0 + 1.5
    tr.prefill_end = t0 + 1.6
    tr.note_token(t0 + 1.7)
    tr.note_token(t0 + 1.8)
    tr.harvested = t0 + 1.8
    tr.complete("slots", slo_ttft_s=0.0)

    path = tel.tracer.write_jsonl(str(tmp_path / "trace.jsonl"))
    events = []
    with open(path) as f:
        for line in f:
            events.append(json.loads(line))  # every line must parse
    mine = [e for e in events if e.get("tid") == tr.tid]
    names = {e["name"] for e in mine}
    assert {"serve/request", "serve/req_queue", "serve/req_prefill",
            "serve/req_decode"} <= names
    meta = [e for e in mine if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "req feed"
    spans = {e["name"]: e for e in mine if e["ph"] == "X"}
    parent = spans["serve/request"]
    assert parent["args"]["trace_id"] == "feed"
    p_start, p_end = parent["ts"], parent["ts"] + parent["dur"]
    for child in ("serve/req_queue", "serve/req_prefill",
                  "serve/req_decode"):
        c = spans[child]
        # ts/dur are rounded to 3 decimals (µs) on export
        assert c["ts"] >= p_start - 0.01
        assert c["ts"] + c["dur"] <= p_end + 0.01


# --------------------------------------------------------------------- #
# FlightRecorder
# --------------------------------------------------------------------- #


def test_flight_recorder_ring_is_bounded():
    fr = FlightRecorder(steps=4)
    for i in range(10):
        fr.record(step=i, active=1)
    snap = fr.snapshot()
    assert len(snap) == 4
    assert [r["step"] for r in snap] == [6, 7, 8, 9]


def test_flight_recorder_dump_format(fresh_registry, capsys):
    fr = FlightRecorder(steps=8)
    fr.record(step=1, active=2, pages_free=3)
    fr.record(step=2, active=1, pages_free=5)
    fr.dump("unit drill")
    assert fr.dumps == 1
    assert fresh_registry.counters["serve/flight_dumps"] == 1.0
    err = capsys.readouterr().err
    assert "FLIGHT RECORDER (unit drill): last 2 engine steps" in err
    records = [
        json.loads(line.split("] ", 1)[1])
        for line in err.strip().splitlines()
        if line.startswith("[trlx_tpu.serve] {")
    ]
    assert records == [{"step": 1, "active": 2, "pages_free": 3},
                       {"step": 2, "active": 1, "pages_free": 5}]


def test_supervisor_dump_fn_hook_is_fault_tolerant(capsys):
    sup = RunSupervisor(stall_timeout=0.0)
    fired = []
    sup.add_dump_fn(lambda: 1 / 0)  # a broken dump fn must not cascade
    sup.add_dump_fn(lambda: fired.append(True))
    sup._run_dump_fns()
    assert fired == [True]
    assert "stall state dump" in capsys.readouterr().err


# --------------------------------------------------------------------- #
# scheduler end-to-end: traces populate, SLO family lands
# --------------------------------------------------------------------- #


def test_slots_requests_carry_complete_traces(scheduler, fresh_registry):
    reqs = [scheduler.submit([1, 2, 3], max_new_tokens=4)
            for _ in range(3)]
    for r in reqs:
        r.wait(timeout=30.0)
    for r in reqs:
        tr = r.trace
        assert tr is not None
        # lifecycle edges are monotonic non-decreasing
        assert tr.received <= tr.enqueued <= tr.admitted
        assert tr.admitted <= tr.prefill_start <= tr.prefill_end
        assert tr.prefill_end <= tr.first_token <= tr.last_token
        assert tr.last_token <= tr.harvested
        assert tr.bucket is not None and tr.bucket[1] == 8
        assert tr.pages_reserved >= 1  # paged layout reserved pages
        assert tr.ttft() > 0.0
        # N emitted tokens (EOS may cut max_new short) -> N-1 gaps
        assert tr.itl_count == len(r.result) - 1
    gaps = sum(len(r.result) - 1 for r in reqs)
    # the SLO family landed in the registry
    assert fresh_registry.hists["serve/ttft"].count == 3
    assert fresh_registry.hists["serve/itl"].count == gaps
    assert fresh_registry.hists["serve/queue_time"].count == 3
    assert fresh_registry.hists["serve/prefill_time"].count == 3
    assert fresh_registry.hists["serve/decode_time"].count == 3
    assert fresh_registry.hists[
        "serve/request_latency{path=slots}"].count == 3
    # slo_ttft_ms=0 -> everything counts good
    assert fresh_registry.gauges["serve/goodput"] == 1.0
    # the deprecated UNLABELED end-to-end histogram is retired: the
    # per-path series above is the only request_latency emission
    assert "serve/request_latency" not in fresh_registry.hists
    # tracing stayed host-side: zero steady-state recompiles
    assert fresh_registry.counters.get("compile/recompiles", 0.0) == 0.0


def test_tracing_off_yields_no_traces(engine, fresh_registry):
    engine.serve.request_tracing = False
    try:
        s = SlotScheduler(engine)
        s.warmup()
        s.start()
        try:
            r = s.submit([1, 2], max_new_tokens=2)
            r.wait(timeout=30.0)
        finally:
            s.stop()
        assert r.trace is None
        assert "serve/ttft" not in fresh_registry.hists
    finally:
        engine.serve.request_tracing = True


def test_flight_recorder_records_engine_steps(scheduler):
    r = scheduler.submit([1, 2, 3], max_new_tokens=4)
    r.wait(timeout=30.0)
    deadline = time.monotonic() + 5.0
    while not scheduler.flight.snapshot() and time.monotonic() < deadline:
        time.sleep(0.01)
    snap = scheduler.flight.snapshot()
    assert snap, "no flight-recorder records after a decoded request"
    for rec in snap:
        assert {"step", "t", "active", "finished", "admitted",
                "occupancy", "step_ms", "pages_free"} <= set(rec)
    assert sum(rec["finished"] for rec in snap) >= 1
    assert sum(rec["admitted"] for rec in snap) >= 1


def test_poisoned_step_dumps_flight_recorder(engine, fresh_registry,
                                             capsys):
    """The flight recorder still dumps on a poisoned step even though
    the request now SURVIVES it (crash-only replay) — the post-mortem
    record and the recovery are independent; the trace records the
    replay + queue re-entry."""
    chaos.configure("serve_decode:exc@1")
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        r = s.submit([1, 2, 3], max_new_tokens=2)
        assert r.wait(timeout=30.0).result is not None  # replayed, done
        assert s.flight.dumps >= 1
        assert fresh_registry.counters["serve/flight_dumps"] >= 1.0
        assert "FLIGHT RECORDER (poisoned step" in capsys.readouterr().err
        assert r.trace.replays == 1
        assert r.trace.queue_reentries >= 1
        assert r.trace.to_dict()["replays"] == 1
        # containment: the loop keeps serving after the dump
        ok = s.submit([4, 5], max_new_tokens=2)
        assert ok.wait(timeout=30.0).result is not None
    finally:
        chaos.reset()
        s.stop()


def test_watchdog_stall_dumps_flight_recorder(engine, fresh_registry,
                                              capsys):
    """The acceptance drill: a chaos-hung decode trips the watchdog,
    whose stall escalation dumps the flight-recorder ring (wired via
    RunSupervisor.add_dump_fn) next to the stack dump."""
    sup = RunSupervisor(
        stall_timeout=0.3, stall_first_timeout=0.3,
        stall_grace=10_000.0, exit_fn=lambda code: None,
    )
    s = SlotScheduler(engine, run_supervisor=sup)
    sup.add_dump_fn(s.dump_flight_recorder)  # the server's wiring
    s.warmup()
    s.start()
    try:
        first = s.submit([1, 2], max_new_tokens=1)
        first.wait(timeout=30.0)  # the ring now holds real step records
        # configure() restarts the seam counters, so @1 is the NEXT step
        chaos.configure("serve_decode:hang=60@1")
        hung = s.submit([3, 4], max_new_tokens=2)
        # stalls increments at the TOP of the watchdog's _on_stall; the
        # dump fns run after the stack dump — poll the dump itself
        deadline = time.monotonic() + 15.0
        while s.flight.dumps == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sup.stalls >= 1, "watchdog never flagged the hung step"
        assert s.flight.dumps >= 1
        err = capsys.readouterr().err
        assert "FLIGHT RECORDER (watchdog stall)" in err
        chaos.reset()  # release the hang
        # the released ChaosHang surfaces as a poisoned step, which
        # now RE-QUEUES the request for replay instead of failing it
        assert hung.wait(timeout=15.0).result is not None
        assert hung.replays == 1
    finally:
        chaos.reset()
        s.stop()


# --------------------------------------------------------------------- #
# HTTP surface: trace payloads, /debug/state, Prometheus /metrics
# --------------------------------------------------------------------- #


def _http(port, path, method="GET", payload=None, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode() if payload is not None else None,
        headers={"Content-Type": "application/json", **(headers or {})},
        method=method,
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, dict(resp.headers), resp.read()


@pytest.fixture(scope="module")
def server(engine):
    telemetry.start()
    srv = InferenceServer(engine, port=0).start(warmup=True)
    yield srv
    srv.stop()
    telemetry.start()


def test_generate_returns_trace_id_and_optin_trace(server):
    status, headers, raw = _http(
        server.port, "/generate", "POST",
        {"tokens": [1, 2, 3], "max_new_tokens": 2},
    )
    body = json.loads(raw)
    assert status == 200
    assert re.fullmatch(r"[0-9a-f]{16}", body["trace_id"])
    assert headers["X-Request-Id"] == body["trace_id"]
    assert "trace" not in body  # opt-in only

    status, headers, raw = _http(
        server.port, "/generate", "POST",
        {"tokens": [1, 2, 3], "max_new_tokens": 2, "trace": True},
        headers={"X-Request-Id": "client-supplied-id"},
    )
    body = json.loads(raw)
    assert body["trace_id"] == "client-supplied-id"  # honored inbound
    assert headers["X-Request-Id"] == "client-supplied-id"  # echoed
    tr = body["trace"]
    assert tr["trace_id"] == "client-supplied-id"
    assert tr["tokens"] == len(body["tokens"])
    assert tr["ttft_ms"] > 0.0
    assert tr["total_ms"] >= tr["ttft_ms"]
    for key in ("queue_ms", "prefill_ms", "decode_ms", "itl_mean_ms",
                "queue_reentries", "pages_reserved"):
        assert key in tr


def test_metrics_content_negotiation(server):
    _http(server.port, "/generate", "POST",
          {"tokens": [1, 2], "max_new_tokens": 2})
    # default: the JSON registry summary
    status, headers, raw = _http(server.port, "/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("application/json")
    body = json.loads(raw)
    assert "serve/ttft" in body["timings"]
    assert body["counters"]["serve/slo_total"] >= 1.0
    # Accept: text/plain -> Prometheus exposition
    status, headers, raw = _http(
        server.port, "/metrics", headers={"Accept": "text/plain"}
    )
    text = raw.decode()
    assert status == 200
    assert headers["Content-Type"] == prometheus.CONTENT_TYPE
    assert 'trlx_tpu_serve_ttft_seconds{quantile="0.95"}' in text
    assert "trlx_tpu_serve_goodput" in text
    for line in text.strip().splitlines():
        if not line.startswith("# TYPE "):
            assert _SAMPLE.match(line), f"malformed sample line: {line!r}"


def test_debug_state_endpoint(server):
    _http(server.port, "/generate", "POST",
          {"tokens": [1, 2, 3], "max_new_tokens": 2})
    status, _, raw = _http(server.port, "/debug/state")
    body = json.loads(raw)
    assert status == 200
    assert body["scheduler"] == "slots"
    assert body["step"] >= 1
    assert body["queue_depth"] == 0
    assert body["free_slots"] == 4  # everything harvested
    assert body["slots"] == {}
    assert body["kv"]["pages_total"] >= 1
    assert isinstance(body["flight_recorder"], list)
    assert body["flight_recorder"], "flight ring empty after a decode"
    rec = body["flight_recorder"][-1]
    assert {"step", "active", "occupancy", "pages_free"} <= set(rec)
    # 404 catalog names the route
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as e:
        _http(server.port, "/debug/nope")
    assert e.value.code == 404
    assert "/debug/state" in e.value.read().decode()
