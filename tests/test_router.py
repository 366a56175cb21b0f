"""Fleet-router tests (trlx_tpu/router, docs "Serving" / "Fleet
routing"): prefix-affinity routing picks the cache-warm replica with
greedy output bit-identical to a direct single-engine run, a killed
backend fails over with zero lost requests (ejection + re-admission),
a rolling checkpoint upgrade keeps >= N-1 replicas admitting with
cross-version parity and ``router/fleet_model_version`` convergence,
chaos drills for all three router seams (KNOWN_SEAMS contract), and
the X-Hop-Count proxy-loop cap end to end.
"""

import json
import os
import threading
import urllib.error
import urllib.request

import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.router import AffinityIndex, FleetRouter, RouterConfig
from trlx_tpu.serve import InferenceEngine, InferenceServer, ServeConfig
from trlx_tpu.serve.server import MAX_HOPS
from trlx_tpu.supervisor import chaos
from test_serve import tiny_config_dict
from test_slots import direct_generate

MAX_NEW = 4

#: one shared 4-token system prefix (= exactly one committed page at
#: page_size=4) + distinct tails, all inside the [2, 8, 8] bucket
PREFIX = [1, 2, 3, 4]
TAILS = [[5], [6, 7], [8], [9, 1], [2, 2], [7, 5], [3], [4, 4, 4]]
ROWS = [PREFIX + t for t in TAILS]

SERVE = dict(
    buckets=[[4, 8, 8]], max_queue=64, request_timeout=60.0,
    slots=4, page_size=4,
)
BUCKET = (4, 8, 8)


def _http(port, path, method="GET", payload=None, headers=None):
    """(status, headers, body) — HTTPError is a RESPONSE here, not an
    exception: the error taxonomy is what these tests assert."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode() if payload is not None else None,
        headers={"Content-Type": "application/json", **(headers or {})},
        method=method,
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, dict(resp.headers), json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


#: shared warmed replicas, built lazily and reused across tests — the
#: engine build + bucket warmup dominates fleet startup, and nothing in
#: these tests depends on a cold engine (greedy parity is pinned
#: regardless of radix-cache state, and every test gets a FRESH router
#: + a fresh telemetry registry). Tests that kill a pool server either
#: revive it in place (the failover drill) or leave it for the next
#: ``_start_fleet`` to revive.
_POOL = []


def _revive(server):
    """A replacement replica for a killed pool server: a new scheduler
    on the SAME engine (the weights survive; only the slot runtime
    re-warms)."""
    return InferenceServer(server.engine, port=0).start(warmup=True)


def _pool_servers(n):
    while len(_POOL) < n:
        engine = InferenceEngine(
            TRLConfig.from_dict(tiny_config_dict()),
            serve=ServeConfig(**SERVE),
        )
        _POOL.append(InferenceServer(engine, port=0).start(warmup=True))
    for i in range(n):
        if _POOL[i]._httpd is None:  # killed by a previous test
            _POOL[i] = _revive(_POOL[i])
    return _POOL[:n]


@pytest.fixture(scope="module", autouse=True)
def _pool_teardown():
    yield
    for s in _POOL:
        try:
            s.stop()
        except RuntimeError:
            pass
    _POOL.clear()


def _start_fleet(n=2, checkpoint=None, **router_overrides):
    """n warmed in-process replicas + a router fronting them. The
    caller stops everything via the returned closer. Checkpoint-backed
    fleets are built fresh (reload mutates their weights); the default
    fleet borrows the shared pool."""
    telemetry.start()
    if checkpoint is not None:
        servers = [
            InferenceServer(
                InferenceEngine.from_checkpoint(
                    checkpoint, serve=ServeConfig(**SERVE)
                ),
                port=0,
            ).start(warmup=True)
            for _ in range(n)
        ]
    else:
        servers = _pool_servers(n)
    router = FleetRouter(RouterConfig(**{
        "backends": [f"127.0.0.1:{s.port}" for s in servers],
        "port": 0, "page_size": SERVE["page_size"],
        "probe_interval": 0.1, "failover_backoff": 0.01,
        **router_overrides,
    })).start()

    def close():
        router.stop()
        if checkpoint is not None:
            for s in servers:
                try:
                    s.stop()
                except RuntimeError:
                    pass  # already stopped by the test (kill drill)
        telemetry.start()

    return servers, router, close


def _burst(port, rows, max_new=MAX_NEW):
    out = [None] * len(rows)

    def call(i):
        out[i] = _http(port, "/generate", "POST",
                       {"tokens": rows[i], "max_new_tokens": max_new})

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(rows))]
    for t in threads:
        t.start()
    return out, threads


# --------------------------------------------------------------------- #
# AffinityIndex unit: the paged.py block math, matching, feedback decay
# --------------------------------------------------------------------- #

def test_affinity_index_block_math_mirrors_paged():
    idx = AffinityIndex(page_size=4)
    # (L - 1) // page_size committed blocks: the final partial block
    # (and a block the last token merely COMPLETES) is never cacheable
    assert idx.blocks([1] * 3) == []
    assert idx.blocks([1] * 4) == []
    assert idx.blocks(list(range(5))) == [(0, 1, 2, 3)]
    assert len(idx.blocks(list(range(17)))) == 4


def test_affinity_index_longest_match_and_decay():
    idx = AffinityIndex(page_size=4)
    long_row = list(range(17))   # 4 committed blocks
    idx.insert(long_row, "A")
    b, depth = idx.match(long_row, lambda x: True)
    assert (b, depth) == ("A", 4)
    # a shorter shared-prefix row still matches at its own depth
    b, depth = idx.match(list(range(9)), lambda x: True)
    assert (b, depth) == ("A", 2)
    # the allow predicate models admission: an ejected owner never wins
    assert idx.match(long_row, lambda x: x != "A") == (None, 0)
    # feedback decay: the replica reported only 1 block hit out of the
    # 4 predicted — the deeper 3 entries were evicted server-side
    assert idx.decay(long_row, "A", reported_blocks=1,
                     predicted_blocks=4) == 3
    b, depth = idx.match(long_row, lambda x: True)
    assert (b, depth) == ("A", 1)


def test_affinity_index_lru_cap():
    idx = AffinityIndex(page_size=2, max_entries=8)
    for i in range(20):
        idx.insert([i, i, i, i, i], f"b{i}")
    assert len(idx) <= 8


def test_prober_and_route_handlers_share_the_affinity_lock():
    """Regression (graftlint race-detected): AffinityIndex is NOT
    thread-safe on its own — the prober's ejection path
    (drop_backend iterates the entry dict), the route handlers'
    match/insert/decay, and /fleet's len() must all go through
    FleetRouter._lock, which the ``# guarded-by: _lock`` annotation now
    makes a proof obligation. This drill reproduces the
    prober-vs-handler interleaving in-process: an unguarded
    drop_backend against concurrent inserts dies with 'dictionary
    changed size during iteration' or tears an entry."""
    telemetry.start()
    router = FleetRouter(RouterConfig(
        backends=["127.0.0.1:1", "127.0.0.1:2"],
        port=0, page_size=2,
        # the drill alternates ready/not-ready each sweep, so the
        # default debounce (2 consecutive failures) would never eject
        # and drop_backend would go unstressed
        probe_failures_threshold=1,
    ))
    b1, b2 = router.backends
    for b in router.backends:
        b.admitted = True
        b.ever_admitted = True
    rows = [[i] * 9 for i in range(8)]  # 4 committed blocks each
    errors = []

    def prober():
        # ready/not-ready flapping ejects + re-admits b2: every
        # ejection runs affinity.drop_backend against the handlers'
        # concurrent inserts
        try:
            for i in range(200):
                router._apply_probe(b2, i % 2 == 1, 1,
                                    {"queue_depth": 0})
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    def handler(seed):
        try:
            for i in range(300):
                key = rows[(i + seed) % len(rows)]
                backend, depth, how = router._pick(key, exclude=())
                if backend is None:
                    continue
                router._note_routed(
                    backend, key, depth, how, 200,
                    {"trace": {"prefix_blocks_hit": 1}},
                )
                router.fleet_state()
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    threads = [threading.Thread(target=prober, daemon=True)] + [
        threading.Thread(target=handler, args=(s,), daemon=True)
        for s in range(3)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not any(t.is_alive() for t in threads), "drill wedged"
    assert not errors, errors
    # structurally intact after the churn: every surviving entry still
    # points at a fleet member
    with router._lock:
        owners = {id(v[0]) for v in router.affinity._entries.values()}
    assert owners <= {id(b1), id(b2)}


def test_router_config_validation():
    with pytest.raises(ValueError, match="at least one replica"):
        RouterConfig(backends=[])
    with pytest.raises(ValueError, match="page_size"):
        RouterConfig(backends=["x:1"], page_size=0)
    cfg = RouterConfig.from_dict({
        "backends": ["127.0.0.1:8081"], "page_size": 16,
        "not_a_knob": True,  # unknown keys are filtered, not fatal
    })
    assert cfg.page_size == 16


# --------------------------------------------------------------------- #
# tentpole e2e: affinity routing with bit-parity against direct decode
# --------------------------------------------------------------------- #

def test_affinity_picks_cache_warm_replica_with_parity():
    """The acceptance drill: a shared-prefix trace through 2 replicas
    shows affinity hit rate >= 0.5, greedy output bit-identical to
    direct single-engine generation, and zero recompiles."""
    servers, router, close = _start_fleet(n=2)
    registry = telemetry.current().registry
    try:
        engine = servers[0].engine
        want = []
        for at in range(0, len(ROWS), BUCKET[0]):
            chunk = ROWS[at:at + BUCKET[0]]
            oracle = direct_generate(engine, chunk, BUCKET,
                                     gen_size=MAX_NEW)
            want.extend(engine.depad_row(oracle, j, MAX_NEW)
                        for j in range(len(chunk)))
        # sequential, so every request after the first finds the prefix
        # already indexed (and the owning replica's radix cache warm)
        for i, row in enumerate(ROWS):
            status, headers, body = _http(
                router.port, "/generate", "POST",
                {"tokens": row, "max_new_tokens": MAX_NEW,
                 "trace": True},
            )
            assert status == 200, body
            assert body["tokens"] == want[i], (
                f"request {i} diverged from the direct-engine oracle"
            )
            assert headers.get("X-Request-Id"), "trace id must round-trip"
        hits = registry.counters["router/affinity_hits"]
        total = hits + registry.counters["router/affinity_misses"]
        assert total == len(ROWS)
        assert hits / total >= 0.5, (
            f"affinity hit rate {hits / total:.2f} below the 0.5 gate"
        )
        assert registry.gauges["router/affinity_hit_rate"] >= 0.5
        # the warm replica actually HIT its radix cache (the fleet-wide
        # payoff the router exists for), and the fleet stayed compiled
        status, _, metrics = _http(router.port, "/metrics")
        assert metrics["counters"]["serve/prefix_tokens_saved"] >= 1.0
        assert metrics["counters"].get("compile/recompiles", 0.0) == 0.0
        assert metrics["gauges"]["router/fleet_goodput"] > 0.0
    finally:
        close()


def test_router_metrics_and_health_surfaces():
    servers, router, close = _start_fleet(n=2)
    try:
        status, _, body = _http(router.port, "/healthz")
        assert status == 200 and body["admitting"] == 2
        assert len(body["backends"]) == 2
        status, _, body = _http(router.port, "/readyz")
        assert status == 200 and body["ready"] is True
        # content negotiation mirrors the engines' /metrics
        status, _, metrics = _http(router.port, "/metrics")
        assert metrics["counters"]["router/requests"] == 0.0
        assert metrics["gauges"]["router/fleet_size"] == 2.0
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/metrics",
            headers={"Accept": "text/plain"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            text = resp.read().decode()
            assert resp.headers["Content-Type"].startswith("text/plain")
        assert "trlx_router_requests" in text.replace("/", "_") or \
            "router" in text
    finally:
        close()


# --------------------------------------------------------------------- #
# failover: a killed backend loses zero requests; eject + re-admit
# --------------------------------------------------------------------- #

def test_failover_zero_loss_on_killed_backend():
    # probe_interval=30: membership only moves when the test sweeps, so
    # the kill is guaranteed to be discovered by a FAILED REQUEST first
    servers, router, close = _start_fleet(n=2, failover_retries=1,
                                          probe_interval=30.0)
    registry = telemetry.current().registry
    try:
        # sequential warm-up burst: the shared prefix ends up owned by
        # one replica — which is exactly the one we kill, so the next
        # burst's affinity picks are all aimed at a dead backend
        for row in ROWS[:4]:
            status, _, body = _http(
                router.port, "/generate", "POST",
                {"tokens": row, "max_new_tokens": MAX_NEW},
            )
            assert status == 200, body
        owner_url = max(router.fleet_state()["backends"],
                        key=lambda b: b["requests"])["url"]
        victim = next(s for s in servers
                      if owner_url.endswith(f":{s.port}"))
        victim_port = victim.port
        victim.stop()  # the kill: connection refused from here on
        # the router has NOT probed yet — requests that land on the
        # dead replica must fail over, not fail
        out, threads = _burst(router.port, ROWS)
        for t in threads:
            t.join(timeout=90.0)
        for i, (status, _, body) in enumerate(out):
            assert status == 200, f"request {i} lost in failover: {body}"
        router.probe_fleet()
        assert router.admitting_count() == 2, (
            "one failed sweep must not eject (debounced at "
            "probe_failures_threshold=2)"
        )
        router.probe_fleet()  # second consecutive failure: now ejected
        assert router.admitting_count() == 1
        assert registry.counters["router/ejections"] >= 1.0
        status, _, body = _http(router.port, "/readyz")
        assert status == 200, "one dead replica must not unready the fleet"
        # recovery: a replacement replica on the same endpoint is
        # re-admitted by the next sweep and serves again
        revived = InferenceServer(
            victim.engine, port=victim_port
        ).start(warmup=True)  # /readyz gates admission on warmed
        _POOL[_POOL.index(victim)] = revived
        router.probe_fleet()
        assert router.admitting_count() == 2
        assert registry.counters["router/readmissions"] >= 1.0
        assert registry.counters["router/failovers"] >= 1.0
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
    finally:
        close()


def test_all_backends_down_is_503_not_a_hang():
    # no server ever listens on the backend address: the startup probe
    # finds nothing admittable and /generate must answer immediately
    # (the ejection-after-kill variant is the failover test above)
    telemetry.start()
    router = FleetRouter(RouterConfig(
        backends=["127.0.0.1:9"], port=0, page_size=4,
        probe_interval=30.0, probe_timeout=2.0, request_timeout=10.0,
        failover_retries=1, failover_backoff=0.01,
    )).start()
    try:
        status, _, body = _http(
            router.port, "/generate", "POST",
            {"tokens": [1, 2], "max_new_tokens": 1},
        )
        assert status == 503
        assert "no admitting replica" in body["error"]
        status, _, _ = _http(router.port, "/readyz")
        assert status == 503, "an empty fleet must not report ready"
    finally:
        router.stop()
        telemetry.start()


# --------------------------------------------------------------------- #
# rolling upgrades: N-1 admitting, cross-version parity, convergence
# --------------------------------------------------------------------- #

def test_rolling_upgrade_under_load(tmp_path):
    """POST /admin/rollout walks the fleet one replica at a time while
    traffic flows: zero lost requests, never below N-1 admitting, every
    response bit-identical to the direct oracle FOR ITS VERSION, and
    router/fleet_model_version converges to the new version."""
    from trlx_tpu.utils.loading import get_model

    import jax
    import numpy as np

    run = str(tmp_path / "run")
    cfg = TRLConfig.from_dict(tiny_config_dict())
    trainer = get_model(cfg.model.model_type)(cfg)
    trainer.save(os.path.join(run, "step_1"))
    # step_2 = step_1 with every float weight negated: finite (passes
    # the reload smoke probe) but decodes visibly differently, so the
    # cross-version parity assertions below cannot pass vacuously
    trainer.params = jax.tree_util.tree_map(
        lambda x: -x if np.issubdtype(np.asarray(x).dtype, np.floating)
        else x,
        trainer.params,
    )
    trainer.save(os.path.join(run, "step_2"))
    servers, router, close = _start_fleet(
        n=2, checkpoint=os.path.join(run, "step_1"), rollout_timeout=60.0
    )
    registry = telemetry.current().registry
    try:
        probe_row = ROWS[0]
        engine = servers[0].engine
        oracle_v1 = engine.depad_row(
            direct_generate(engine, [probe_row], BUCKET,
                            gen_size=MAX_NEW), 0, MAX_NEW)
        results = []
        min_admitting = [len(servers)]
        done = threading.Event()

        def traffic():
            while not done.is_set():
                results.append(_http(
                    router.port, "/generate", "POST",
                    {"tokens": probe_row, "max_new_tokens": MAX_NEW},
                ))
                min_admitting[0] = min(min_admitting[0],
                                       router.admitting_count())

        t = threading.Thread(target=traffic)
        t.start()
        try:
            # no explicit checkpoint: each replica's reload resolves its
            # run dir's newest committed step (step_2)
            status, _, body = _http(router.port, "/admin/rollout",
                                    "POST", {})
        finally:
            done.set()
            t.join(timeout=90.0)
        assert status == 200, body
        assert body["ok"] is True
        assert [s["model_version"] for s in body["steps"]] == [2, 2]
        assert min_admitting[0] >= len(servers) - 1, (
            "rollout dropped below N-1 admitting replicas"
        )
        # post-swap: engine A now holds the v2 weights; its direct
        # decode is the v2 oracle
        oracle_v2 = engine.depad_row(
            direct_generate(engine, [probe_row], BUCKET,
                            gen_size=MAX_NEW), 0, MAX_NEW)
        assert oracle_v2 != oracle_v1, "step_2 must actually differ"
        assert results, "traffic thread never completed a request"
        for status, _, body in results:
            assert status == 200, f"request lost mid-rollout: {body}"
            want = oracle_v1 if body["model_version"] == 1 else oracle_v2
            assert body["tokens"] == want, (
                f"version {body['model_version']} response diverged "
                f"from its oracle"
            )
        status, _, metrics = _http(router.port, "/metrics")
        assert metrics["gauges"]["router/fleet_model_version"] == 2.0
        assert metrics["counters"]["router/rollout_steps"] == 2.0
        assert metrics["counters"].get("router/rollout_aborts", 0.0) == 0.0
        assert metrics["counters"].get("compile/recompiles", 0.0) == 0.0
        assert registry.gauges["router/rollout_in_progress"] == 0.0
    finally:
        close()


# --------------------------------------------------------------------- #
# chaos drills: the three router seams (KNOWN_SEAMS contract)
# --------------------------------------------------------------------- #

def test_chaos_router_route_surfaces_500_then_recovers():
    """``router_route:exc`` fires BEFORE a replica is picked: the
    request fails at the router (500, router/request_errors) without
    consuming failover budget or touching a backend; the next request
    (occurrence consumed) routes normally."""
    servers, router, close = _start_fleet(n=2)
    registry = telemetry.current().registry
    chaos.configure("router_route:exc@1")
    try:
        status, _, body = _http(
            router.port, "/generate", "POST",
            {"tokens": [1, 2], "max_new_tokens": 1},
        )
        assert status == 500 and "ChaosError" in body["error"]
        assert registry.counters["router/request_errors"] >= 1.0
        assert registry.counters.get("router/failovers", 0.0) == 0.0
        status, _, body = _http(
            router.port, "/generate", "POST",
            {"tokens": [1, 2], "max_new_tokens": 1},
        )
        assert status == 200, body
    finally:
        chaos.reset()
        close()


def test_chaos_router_probe_leaves_membership_untouched():
    """``router_probe:exc`` fails a whole prober sweep; fleet
    membership must be exactly what it was — nothing ejected by the
    drill — and the next sweep runs normally."""
    servers, router, close = _start_fleet(n=2)
    try:
        assert router.admitting_count() == 2
        chaos.configure("router_probe:exc@1")
        with pytest.raises(chaos.ChaosError):
            router.probe_fleet()
        assert router.admitting_count() == 2, (
            "a failed probe sweep must not eject replicas"
        )
        router.probe_fleet()  # occurrence consumed: sweeps recover
        assert router.admitting_count() == 2
    finally:
        chaos.reset()
        close()


def test_chaos_router_rollout_aborts_and_readmits():
    """``router_rollout:exc`` at the first per-replica step: the
    rollout aborts, every replica stays admitted on its OLD version,
    and traffic keeps flowing."""
    servers, router, close = _start_fleet(n=2)
    registry = telemetry.current().registry
    chaos.configure("router_rollout:exc@1")
    try:
        status, _, body = _http(router.port, "/admin/rollout", "POST", {})
        assert status == 409
        assert body["ok"] is False and "ChaosError" in str(body)
        assert registry.counters["router/rollout_aborts"] == 1.0
        assert router.admitting_count() == 2, (
            "an aborted rollout must re-admit every replica"
        )
        with router._lock:
            assert all(b.model_version == 1 for b in router.backends)
        status, _, body = _http(
            router.port, "/generate", "POST",
            {"tokens": [1, 2], "max_new_tokens": 1},
        )
        assert status == 200, body
    finally:
        chaos.reset()
        close()


# --------------------------------------------------------------------- #
# X-Hop-Count: the proxy-loop cap, engine-side and through the router
# --------------------------------------------------------------------- #

def test_hop_count_cap_and_trace_echo():
    servers, router, close = _start_fleet(n=1)
    try:
        port = servers[0].port
        # engine direct: over the cap is a typed 508, not a 4xx/5xx blur
        status, _, body = _http(
            port, "/generate", "POST",
            {"tokens": [1, 2], "max_new_tokens": 1},
            headers={"X-Hop-Count": str(MAX_HOPS + 1)},
        )
        assert status == 508 and "hop" in body["error"].lower()
        status, _, body = _http(
            port, "/generate", "POST", {"tokens": [1, 2]},
            headers={"X-Hop-Count": "banana"},
        )
        assert status == 400
        # through the router: the hop the router adds is echoed in the
        # response header and the trace payload (one hop: client->router)
        status, headers, body = _http(
            router.port, "/generate", "POST",
            {"tokens": [1, 2], "max_new_tokens": 1, "trace": True},
        )
        assert status == 200
        assert body["trace"]["hops"] == 1
        # an inbound count at the cap overflows at the BACKEND and the
        # router passes the typed 508 through rather than retrying it
        status, _, body = _http(
            router.port, "/generate", "POST",
            {"tokens": [1, 2], "max_new_tokens": 1},
            headers={"X-Hop-Count": str(MAX_HOPS)},
        )
        assert status == 508
        registry = telemetry.current().registry
        assert registry.counters["serve/hop_limit_rejects"] >= 2.0
    finally:
        close()
