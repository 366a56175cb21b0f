"""Multi-tenant overload containment (docs "Fault tolerance", overload
runbook): per-tenant quota admission — token bucket, ``max_inflight``,
``max_queue_share`` — answering over-quota tenants with the typed 429
:class:`QuotaExceeded` (its own ``Retry-After``, never the global
``QueueFull``) while neighbours keep being admitted; priority aging so
a saturating high-priority stream cannot starve best-effort tenants;
the hysteretic brownout state machine clamping best-effort
``max_new_tokens`` under sustained pressure; the ``/readyz`` pressure
block the fleet router's prober ingests to shed best-effort traffic at
its own edge (429 + the replicas' pacing, nothing forwarded); per-tenant
retry-budget slices debited before the fleet bucket; and the
``serve_quota`` chaos seam (KNOWN_SEAMS contract). Fast tier-1 via
``make overload``; the slow three-tenant isolation drill (4x aggressor,
premium goodput floor, zero recompiles, greedy prefix-parity for
browned-out completions) is ``make overload-drill``.
"""

import contextlib
import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from test_defense import _StubReplica, _router_over
from test_serve import tiny_config_dict
from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.router import FleetRouter, RouterConfig
from trlx_tpu.serve import InferenceEngine, InferenceServer, ServeConfig
from trlx_tpu.serve.admission import (
    DEFAULT_TENANT,
    QueueFull,
    QuotaExceeded,
    TenantPolicy,
    TenantTable,
)
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.supervisor import chaos, monotonic

SERVE_OVERLOAD = ServeConfig(
    buckets=[[2, 8, 8], [4, 8, 8]],  # (B, P, G): one prompt class P=8
    max_queue=32,
    request_timeout=30.0,
    slots=2,
    page_size=4,  # divides the bucket's prompt and prompt + gen
)


@pytest.fixture(scope="module")
def engine():
    """One tiny greedy slot-scheduler engine shared by the tests (warm
    executables amortized; each test builds its own scheduler)."""
    telemetry.start()
    cfg = TRLConfig.from_dict(tiny_config_dict())
    return InferenceEngine(cfg, serve=SERVE_OVERLOAD)


@pytest.fixture()
def fresh_registry():
    session = telemetry.start()
    yield session.registry
    telemetry.start()


@contextlib.contextmanager
def serve_overrides(engine, **overrides):
    """Temporarily rewrite ``engine.serve`` knobs: schedulers read the
    config at CONSTRUCTION, so build the scheduler/server inside the
    ``with`` block; the shared module engine is restored on exit."""
    saved = {k: getattr(engine.serve, k) for k in overrides}
    for k, v in overrides.items():
        setattr(engine.serve, k, v)
    try:
        yield engine
    finally:
        for k, v in saved.items():
            setattr(engine.serve, k, v)


# --------------------------------------------------------------------- #
# quota primitives: pure state machines, time passed by argument
# --------------------------------------------------------------------- #


def test_tenant_policy_knobs_and_validation():
    p = TenantPolicy("t", {"rps": 2, "priority": 1})
    assert p.rps == 2.0
    assert p.burst == 2.0, "burst defaults to max(1, rps)"
    assert not p.best_effort, "priority > 0 is not best-effort"
    assert TenantPolicy("t", {}).best_effort
    assert TenantPolicy("t", {"rps": 0.5}).burst == 1.0
    assert TenantPolicy("t", {"rps": 2, "burst": 8}).burst == 8.0
    with pytest.raises(ValueError, match="unknown keys"):
        TenantPolicy("t", {"bogus": 1})
    with pytest.raises(ValueError, match="max_queue_share"):
        TenantPolicy("t", {"max_queue_share": 1.5})


def test_quota_exceeded_is_a_typed_queue_full():
    e = QuotaExceeded("over quota", tenant="t", retry_after_s=3)
    # IS-A QueueFull: scheduler-agnostic callers need no new handling,
    # but the HTTP layer can surface the tenant and its own pacing
    assert isinstance(e, QueueFull)
    assert e.tenant == "t" and e.retry_after_s == 3


def test_tenant_table_bucket_spend_refill_and_retry_after():
    table = TenantTable({"t": {"rps": 1.0, "burst": 2}}, max_queue=64)
    now = monotonic()
    assert table.try_admit("t", queued=0, inflight=0, now=now) is None
    assert table.try_admit("t", queued=0, inflight=0, now=now) is None
    denied = table.try_admit("t", queued=0, inflight=0, now=now)
    assert isinstance(denied, QuotaExceeded)
    assert denied.tenant == "t" and denied.retry_after_s == 1
    assert "rps" in str(denied)
    # continuous refill: one whole token back after a second
    assert table.try_admit("t", queued=0, inflight=0,
                           now=now + 1.05) is None
    denied = table.try_admit("t", queued=0, inflight=0, now=now + 1.05)
    assert isinstance(denied, QuotaExceeded)


def test_tenant_table_inflight_and_queue_share_caps():
    table = TenantTable({"t": {"max_inflight": 2}}, max_queue=10)
    now = monotonic()
    # max_inflight counts queued + admitted-but-unfinished together
    assert table.try_admit("t", queued=0, inflight=1, now=now) is None
    denied = table.try_admit("t", queued=1, inflight=1, now=now)
    assert isinstance(denied, QuotaExceeded)
    assert "max_inflight" in str(denied)

    share = TenantTable({"t": {"max_queue_share": 0.3}}, max_queue=10)
    assert share.try_admit("t", queued=2, inflight=0, now=now) is None
    denied = share.try_admit("t", queued=3, inflight=0, now=now)
    assert isinstance(denied, QuotaExceeded)
    assert "max_queue_share" in str(denied)


def test_unknown_tenants_share_the_default_bucket():
    table = TenantTable({"default": {"rps": 0.01, "burst": 1}},
                        max_queue=64)
    now = monotonic()
    assert table.try_admit("alice", 0, 0, now) is None
    # alice spent the shared token; bob is governed by the same entry
    denied = table.try_admit("bob", 0, 0, now)
    assert isinstance(denied, QuotaExceeded)
    assert denied.tenant == "bob"
    assert table.priority_for("anyone") == 0
    assert table.best_effort("anyone")


def test_tenant_table_without_config_is_a_noop():
    table = TenantTable(None, max_queue=4)
    assert not table.enabled
    now = monotonic()
    for _ in range(100):
        assert table.try_admit("anyone", 1000, 1000, now) is None


def test_bad_tenants_block_fails_at_boot():
    cfg = TRLConfig.from_dict(tiny_config_dict())
    with pytest.raises(ValueError, match="unknown keys"):
        InferenceEngine(
            cfg,
            serve=ServeConfig(buckets=[[2, 8, 8]],
                              tenants={"x": {"bogus": 1}}),
            init=False,
        )
    with pytest.raises(ValueError, match="max_queue_share"):
        InferenceEngine(
            cfg,
            serve=ServeConfig(buckets=[[2, 8, 8]],
                              tenants={"x": {"max_queue_share": 1.5}}),
            init=False,
        )


def test_router_config_validates_tenants_and_threshold():
    with pytest.raises(ValueError, match="shed_pressure_threshold"):
        RouterConfig(backends=["h:1"], shed_pressure_threshold=1.5)
    with pytest.raises(ValueError, match="unknown key"):
        RouterConfig(backends=["h:1"], tenants={"x": {"bogus": 1}})
    with pytest.raises(ValueError, match="must be a mapping"):
        RouterConfig(backends=["h:1"], tenants={"x": "not a dict"})
    cfg = RouterConfig(
        backends=["h:1"],
        tenants={"p": {"rps": 2, "burst": 4, "priority": 1}},
        shed_pressure_threshold=0.5,
    )
    assert cfg.tenants["p"]["rps"] == 2


# --------------------------------------------------------------------- #
# engine admission: typed sheds, aging, brownout (no worker needed)
# --------------------------------------------------------------------- #


def test_slots_quota_shed_is_typed_not_global(engine, fresh_registry):
    with serve_overrides(engine, tenants={"free": {"rps": 0.01,
                                                   "burst": 2}}):
        sched = SlotScheduler(engine)
        sched.submit([1, 2], max_new_tokens=4, tenant="free")
        sched.submit([1, 2], max_new_tokens=4, tenant="free")
        with pytest.raises(QuotaExceeded) as exc:
            sched.submit([1, 2], max_new_tokens=4, tenant="free")
        e = exc.value
        assert isinstance(e, QueueFull)
        assert e.tenant == "free" and e.retry_after_s >= 1
        assert "rps" in str(e)
        # the shed is THIS tenant's: the shared queue still admits
        ok = sched.submit([1, 2], max_new_tokens=4)
        assert ok.tenant == DEFAULT_TENANT
        assert fresh_registry.counters["serve/shed_quota"] == 1.0
        assert fresh_registry.counters[
            "serve/shed_quota{tenant=free}"] == 1.0
        assert fresh_registry.counters["serve/rejected"] == 1.0


def test_over_share_tenant_never_sees_global_queue_full(
    engine, fresh_registry
):
    # share slice: int(0.25 * 8) = 2 queued; the 8-deep global queue
    # still has room, so the refusal must be the typed per-tenant one
    with serve_overrides(engine, max_queue=8,
                         tenants={"bulk": {"max_queue_share": 0.25}}):
        sched = SlotScheduler(engine)
        sched._free = []  # no admission: submissions stay queued
        sched.submit([1, 2], max_new_tokens=4, tenant="bulk")
        sched.submit([1, 2], max_new_tokens=4, tenant="bulk")
        with pytest.raises(QuotaExceeded, match="max_queue_share"):
            sched.submit([1, 2], max_new_tokens=4, tenant="bulk")
        # a neighbour tenant keeps its own share of the same queue
        ok = sched.submit([1, 2], max_new_tokens=4, tenant="other")
        assert ok in sched._queue


def test_priority_aging_prevents_starvation(engine, fresh_registry):
    """Satellite regression: a queued best-effort request gains one
    effective priority level every ``priority_aging_rounds`` admission
    scans, so fresh high-priority arrivals raise — never pin — its
    wait. With aging off the same shape starves it."""
    with serve_overrides(engine, priority_aging_rounds=2):
        sched = SlotScheduler(engine)
        sched.warmup()
        sched._free = []  # park every slot: scans only age the queue
        low = sched.submit([5, 6], max_new_tokens=4, priority=0)
        for _ in range(4):
            sched._admit()
        assert low.age == 4  # effective priority now 0 + 4 // 2 = 2
        highs = [sched.submit([5, 6], max_new_tokens=4, priority=1)
                 for _ in range(2)]
        sched._free = [0]  # one slot frees: exactly one admission
        sched._admit()
        assert low not in sched._queue, "the aged request admits first"
        assert all(h in sched._queue for h in highs)

    with serve_overrides(engine, priority_aging_rounds=0):
        sched = SlotScheduler(engine)
        sched.warmup()
        sched._free = []
        low = sched.submit([5, 6], max_new_tokens=4, priority=0)
        for _ in range(4):
            sched._admit()
        high = sched.submit([5, 6], max_new_tokens=4, priority=1)
        sched._free = [0]
        sched._admit()
        assert high not in sched._queue, "aging off: priority wins"
        assert low in sched._queue


def test_brownout_hysteresis_state_machine(engine, fresh_registry):
    with serve_overrides(engine, brownout_max_new=2, brownout_after_s=1.0,
                         brownout_recover_s=2.0):
        sched = SlotScheduler(engine)
        t0 = 100.0
        sched._starved = True  # the _degraded() pressure signal
        sched._update_brownout(t0)
        assert not sched._brownout, "first pressured tick only stamps"
        sched._update_brownout(t0 + 0.9)
        assert not sched._brownout, "pressure not yet held after_s"
        sched._update_brownout(t0 + 1.0)
        assert sched._brownout
        assert fresh_registry.counters["serve/brownout_entries"] == 1.0
        assert fresh_registry.gauges["serve/brownout"] == 1.0
        # a flapping signal moves neither edge: brief calm then pressure
        # again resets the recovery clock
        sched._starved = False
        sched._update_brownout(t0 + 1.5)
        assert sched._brownout
        sched._starved = True
        sched._update_brownout(t0 + 1.6)
        sched._starved = False
        sched._update_brownout(t0 + 2.0)
        sched._update_brownout(t0 + 3.9)
        assert sched._brownout, "calm for 1.9s < recover_s=2.0"
        sched._update_brownout(t0 + 4.0)
        assert not sched._brownout
        assert fresh_registry.gauges["serve/brownout"] == 0.0
        # re-entry is a fresh engagement
        sched._starved = True
        sched._update_brownout(t0 + 5.0)
        sched._update_brownout(t0 + 6.0)
        assert sched._brownout
        assert fresh_registry.counters["serve/brownout_entries"] == 2.0

    with serve_overrides(engine, brownout_max_new=0):
        sched = SlotScheduler(engine)  # brownout disabled entirely
        sched._starved = True
        sched._update_brownout(1.0)
        sched._update_brownout(100.0)
        assert not sched._brownout


def test_brownout_clamps_best_effort_only(engine, fresh_registry):
    with serve_overrides(
        engine,
        tenants={"premium": {"priority": 1}, "default": {}},
        brownout_max_new=2,
    ):
        sched = SlotScheduler(engine)
        sched._brownout = True
        r = sched.submit([1, 2], max_new_tokens=8, tenant="guest")
        assert r.degraded and r.max_new_tokens == 2
        assert fresh_registry.counters["serve/brownout_clamped"] == 1.0
        assert fresh_registry.counters[
            "serve/brownout_clamped{tenant=guest}"] == 1.0
        # non-best-effort tenants ride through untouched
        p = sched.submit([1, 2], max_new_tokens=8, tenant="premium")
        assert not p.degraded and p.max_new_tokens == 8
        # an already-short best-effort request has nothing to clamp
        s = sched.submit([1, 2], max_new_tokens=2, tenant="guest")
        assert not s.degraded and s.max_new_tokens == 2


def test_pressure_block_and_debug_state(engine, fresh_registry):
    with serve_overrides(engine, tenants={"default": {"rps": 5,
                                                      "burst": 5}}):
        sched = SlotScheduler(engine)
        p = sched.pressure()
        assert {"degraded", "brownout", "starved", "queue_depth",
                "free_slots", "retry_after_s"} <= set(p)
        assert p["queue_depth"] == 0 and p["free_slots"] == 2
        assert p["brownout"] is False and p["degraded"] is False
        assert p["retry_after_s"] >= 1
        state = sched.debug_state()
        assert state["pressure"]["free_slots"] == 2
        assert state["tenants"]["default"]["burst"] == 5.0
        assert state["tenants"]["default"]["rps"] == 5.0


def test_serve_quota_chaos_seam_refuses_cleanly(engine, fresh_registry):
    """The ``serve_quota`` chaos drill: an exc injected INSIDE the quota
    admission check refuses the request outright — nothing is
    half-enqueued, and the very next submit admits normally. Quota-free
    deployments never reach the seam."""
    with serve_overrides(engine, tenants={"default": {}}):
        sched = SlotScheduler(engine)
        chaos.configure("serve_quota:exc@1")
        try:
            with pytest.raises(chaos.ChaosError):
                sched.submit([1, 2], max_new_tokens=4)
            assert len(sched._queue) == 0, "no half-enqueued request"
            ok = sched.submit([1, 2], max_new_tokens=4)
            assert ok in sched._queue
        finally:
            chaos.reset()
    sched = SlotScheduler(engine)  # no serve.tenants: seam not armed
    chaos.configure("serve_quota:exc@1")
    try:
        ok = sched.submit([1, 2], max_new_tokens=4)
        assert ok in sched._queue
    finally:
        chaos.reset()


# --------------------------------------------------------------------- #
# HTTP surface: X-Tenant-Id, typed 429 + Retry-After, /readyz pressure
# --------------------------------------------------------------------- #


def _http(port, method, path, body=None, headers=None, timeout=30):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read() or b"{}")


def test_http_quota_429_degraded_flag_and_readyz_pressure(engine):
    telemetry.start()
    with serve_overrides(engine, tenants={"miser": {"rps": 0.01,
                                                    "burst": 1}},
                         brownout_max_new=2):
        srv = InferenceServer(engine, port=0).start(warmup=True)
        try:
            status, _ = _http(srv.port, "POST", "/generate",
                              {"tokens": [1, 2], "max_new_tokens": 2},
                              headers={"X-Tenant-Id": "miser"})
            assert status == 200
            # bucket spent: the same tenant's next request is the typed
            # 429 with ITS pacing, via header or body field alike
            with pytest.raises(urllib.error.HTTPError) as exc:
                _http(srv.port, "POST", "/generate",
                      {"tokens": [1, 2], "max_new_tokens": 2},
                      headers={"X-Tenant-Id": "miser"})
            e = exc.value
            assert e.code == 429
            assert int(e.headers["Retry-After"]) >= 1
            assert json.loads(e.read())["tenant"] == "miser"
            with pytest.raises(urllib.error.HTTPError) as exc2:
                _http(srv.port, "POST", "/generate",
                      {"tokens": [1, 2], "max_new_tokens": 2,
                       "tenant": "miser"})
            assert exc2.value.code == 429
            # an ungoverned tenant is untouched by miser's quota
            status, _ = _http(srv.port, "POST", "/generate",
                              {"tokens": [1, 2], "max_new_tokens": 2})
            assert status == 200
            # browned-out best-effort answers carry "degraded": true
            srv.scheduler._brownout = True
            status, body = _http(srv.port, "POST", "/generate",
                                 {"tokens": [1, 2], "max_new_tokens": 6,
                                  "tenant": "guest"})
            assert status == 200
            assert body.get("degraded") is True
            srv.scheduler._brownout = False
            # /readyz publishes the pressure block the prober ingests
            status, ready = _http(srv.port, "GET", "/readyz")
            assert status == 200
            assert {"degraded", "brownout", "queue_depth", "free_slots",
                    "retry_after_s"} <= set(ready["pressure"])
        finally:
            srv.stop()
    telemetry.start()


# --------------------------------------------------------------------- #
# router edge: pressure shedding + per-tenant retry-budget slices
# --------------------------------------------------------------------- #


def _edge_router(n_backends=1, **overrides):
    """An UNSTARTED router (no prober, no listener): membership and
    pressure are driven directly through _apply_probe, the
    test_defense.py idiom."""
    telemetry.start()
    cfg = dict(
        backends=[f"127.0.0.1:{9200 + i}" for i in range(n_backends)],
        port=0, page_size=4, probe_interval=0.5,
    )
    cfg.update(overrides)
    return FleetRouter(RouterConfig(**cfg))


def test_router_sheds_best_effort_under_fleet_pressure():
    router = _edge_router(tenants={"premium": {"priority": 1},
                                   "default": {"priority": 0}})
    registry = telemetry.current().registry
    (b,) = router.backends
    b.admitted = True
    b.ever_admitted = True
    router._apply_probe(b, True, 1, {
        "queue_depth": 9,
        "pressure": {"degraded": True, "brownout": True,
                     "retry_after_s": 7},
    })
    assert b.pressure["brownout"] is True
    status, payload, headers = router.forward(
        {"tokens": [1], "max_new_tokens": 1})
    assert status == 429
    assert payload["shed_pressure"] is True
    assert payload["tenant"] == "default"
    assert headers["Retry-After"] == "7", "the replica's own pacing"
    assert registry.counters["router/shed_pressure"] == 1.0
    assert registry.counters[
        "router/shed_pressure{tenant=default}"] == 1.0
    # an admission decision, not a request error
    assert registry.counters.get("router/request_errors", 0.0) == 0.0
    # premium rides through the shed gate (it would hit the network
    # next, so assert on the gate itself)
    assert router._shed_for_pressure("premium") is None
    # pressure clears with the next sweep: nobody is shed
    router._apply_probe(b, True, 1, {"pressure": {"degraded": False}})
    assert router._shed_for_pressure("default") is None
    telemetry.start()


def test_router_shed_threshold_is_a_fleet_fraction():
    router = _edge_router(n_backends=2,
                          tenants={"default": {"priority": 0}},
                          shed_pressure_threshold=1.0)
    b1, b2 = router.backends
    for b in (b1, b2):
        b.admitted = True
    b1.pressure = {"degraded": True, "retry_after_s": 3}
    assert router._shed_for_pressure("default") is None, "1/2 < 1.0"
    router.config.shed_pressure_threshold = 0.5
    assert router._shed_for_pressure("default") == 3
    b2.pressure = {"brownout": True, "retry_after_s": 11}
    router.config.shed_pressure_threshold = 1.0
    assert router._shed_for_pressure("default") == 11, \
        "the worst pressured replica's pacing wins"
    router.config.shed_pressure_threshold = 0.0  # disabled
    assert router._shed_for_pressure("default") is None
    telemetry.start()


def test_router_tenant_budget_slice_exhausts_before_fleet():
    """One aggressor's failover storm drains ITS slice — the typed 503
    names the tenant and paces at its refill — while the fleet bucket
    stays available to everyone else."""
    stubs = [_StubReplica(mode="e503"), _StubReplica(mode="e503")]
    router = _router_over(
        stubs, breaker_threshold=0, failover_retries=5,
        retry_budget=16.0, retry_budget_refill=2.0,
        tenants={"aggressor": {"rps": 0.5, "burst": 1}},
    )
    registry = telemetry.current().registry
    try:
        status, payload, headers = router.forward(
            {"tokens": [1, 2], "max_new_tokens": 1,
             "tenant": "aggressor"})
        assert status == 503
        assert payload["retry_budget_exhausted"] is True
        assert payload["tenant"] == "aggressor"
        assert "tenant 'aggressor'" in payload["error"]
        assert headers["Retry-After"] == "2", "1 token / 0.5 rps refill"
        assert registry.counters[
            "router/tenant_budget_exhausted"] == 1.0
        assert registry.counters[
            "router/tenant_budget_exhausted{tenant=aggressor}"] == 1.0
        assert registry.counters[
            "router/retry_budget_spent{tenant=aggressor}"] == 1.0
        assert registry.counters.get(
            "router/retry_budget_exhausted", 0.0) == 0.0
        # an unsliced tenant spends the FLEET bucket freely
        status2, payload2, _ = router.forward(
            {"tokens": [3], "max_new_tokens": 1, "tenant": "premium"})
        assert status2 == 503  # both stubs shed — but through failovers
        assert registry.counters[
            "router/retry_budget_spent{tenant=premium}"] >= 2.0
        assert not payload2.get("retry_budget_exhausted")
    finally:
        router.stop()
        for s in stubs:
            s.stop()
        telemetry.start()


class _ThrottlingStub:
    """A backend that admits probes but answers /generate with its own
    quota 429 + Retry-After — the engine-side QuotaExceeded surface as
    the router sees it over the wire."""

    def __init__(self, retry_after=9):
        outer_retry = retry_after

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # noqa: A002
                return

            def _json(self, code, payload, extra=None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (extra or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                if self.path == "/readyz":
                    self._json(200, {"ready": True, "model_version": 1})
                else:
                    self._json(404, {"error": "no route"})

            def do_POST(self):  # noqa: N802
                length = int(self.headers.get("Content-Length", 0))
                self.rfile.read(length)
                self._json(
                    429,
                    {"error": "tenant 'miser' over its rps quota",
                     "tenant": "miser"},
                    extra={"Retry-After": str(outer_retry)},
                )

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        ).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_router_propagates_upstream_retry_after_on_terminal_429():
    """Satellite: a terminal upstream 429 keeps its pacing semantics —
    the replica's Retry-After and typed payload reach the client
    unchanged instead of a bare router error."""
    stub = _ThrottlingStub(retry_after=9)
    router = _router_over([stub], failover_retries=0)
    try:
        status, payload, headers = router.forward(
            {"tokens": [1, 2], "max_new_tokens": 1, "tenant": "miser"})
        assert status == 429
        assert headers["Retry-After"] == "9"
        assert payload["tenant"] == "miser"
        assert "quota" in payload["error"]
    finally:
        router.stop()
        stub.stop()
        telemetry.start()


def test_router_empty_fleet_503_carries_retry_after():
    router = _edge_router()  # its one backend never admitted
    status, payload, headers = router.forward(
        {"tokens": [1], "max_new_tokens": 1})
    assert status == 503
    assert "Retry-After" in headers
    assert int(headers["Retry-After"]) >= 1, "paced, never a dead end"
    telemetry.start()


# --------------------------------------------------------------------- #
# the slow three-tenant isolation drill (`make overload-drill`)
# --------------------------------------------------------------------- #


@pytest.mark.slow
def test_three_tenant_isolation_drill(engine):
    """Premium + standard steady state, then a 4x-over-quota aggressor
    burst while the engine is browned out: every shed is the typed
    per-tenant 429, nothing accepted is lost, premium goodput holds its
    floor, zero steady-state recompiles — and browned-out completions
    are greedy PREFIXES of the unclamped decode (degraded means
    shorter, never different)."""
    session = telemetry.start()
    registry = session.registry
    prompts = [[3 + (i * 5) % 11, 1 + (i * 7) % 13] for i in range(64)]
    accepted = []  # (tenant, requested_max_new, request)
    sheds = []
    with serve_overrides(
        engine,
        max_queue=64,
        slo_ttft_ms=0,  # every completed request counts good
        priority_aging_rounds=4,
        brownout_max_new=2,
        brownout_after_s=0.05,
        brownout_recover_s=10.0,
        tenants={
            "premium": {"priority": 1, "max_queue_share": 0.9},
            "default": {"priority": 0, "max_queue_share": 0.5},
            "aggressor": {"rps": 0.5, "burst": 4, "priority": 0,
                          "max_queue_share": 0.5},
        },
    ):
        sched = SlotScheduler(engine)
        sched.warmup()
        sched.start()
        try:
            # wave 1: a premium backlog deep enough to starve slots
            for i in range(24):
                accepted.append(("premium", 8, sched.submit(
                    prompts[i], max_new_tokens=8, tenant="premium")))
            for i in range(8):
                accepted.append(("standard", 8, sched.submit(
                    prompts[24 + i], max_new_tokens=8,
                    tenant="standard")))
            deadline = time.time() + 30
            while (not sched.pressure()["brownout"]
                   and time.time() < deadline):
                time.sleep(0.005)
            assert sched.pressure()["brownout"], \
                "a sustained backlog must engage brownout"
            # wave 2 under brownout: late best-effort arrivals are
            # clamped, and the aggressor bursts 4x its token bucket
            for i in range(4):
                accepted.append(("standard", 8, sched.submit(
                    prompts[32 + i], max_new_tokens=8,
                    tenant="standard")))
            for i in range(16):
                try:
                    accepted.append(("aggressor", 8, sched.submit(
                        prompts[36 + i], max_new_tokens=8,
                        tenant="aggressor")))
                except QueueFull as e:
                    sheds.append(e)
            for _, _, r in accepted:
                r.wait(timeout=120.0)
        finally:
            sched.stop()

        assert sheds, "a 4x burst must overflow the aggressor's bucket"
        assert all(isinstance(e, QuotaExceeded) for e in sheds), \
            "every shed is the typed per-tenant 429, never QueueFull"
        assert all(e.tenant == "aggressor" and e.retry_after_s >= 1
                   for e in sheds)
        assert all(r.result is not None and r.error is None
                   for _, _, r in accepted), "zero accepted-then-lost"
        premium = [r for t, _, r in accepted if t == "premium"]
        assert len(premium) == 24
        assert not any(r.degraded for r in premium), \
            "premium is never brownout-clamped"
        assert registry.gauges["slo/goodput_5m{tenant=premium}"] >= 0.9
        late_std = [r for t, _, r in accepted if t == "standard"][8:]
        assert late_std and all(
            r.degraded and r.max_new_tokens == 2 for r in late_std
        ), "best-effort arrivals under brownout are clamped + flagged"
        assert registry.counters["serve/brownout_entries"] >= 1.0
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0

    # greedy prefix-parity: replay a sample (including every degraded
    # one) through a fresh untenanted scheduler at full budget
    telemetry.start()
    ref = SlotScheduler(engine)
    ref.warmup()
    ref.start()
    try:
        degraded = [(t, m, r) for t, m, r in accepted if r.degraded]
        for _, requested, r in accepted[:6] + degraded[:4]:
            full = ref.submit(
                list(r.tokens), max_new_tokens=requested
            ).wait(timeout=60.0).result
            assert r.result == full[:len(r.result)], \
                "degraded output must be a prefix, never different"
            if not r.degraded:
                assert r.result == full
    finally:
        ref.stop()
    telemetry.start()
