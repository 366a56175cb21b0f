"""Sharded-serving invariants (trlx_tpu/serve/layouts, docs "Serving"):
a tp=2 (and tp=2 x fsdp=2) slot engine on CPU-simulated devices must be
indistinguishable from the single-device engine — greedy outputs
bit-identical across page sizes with shared prefixes and staggered
admission, replay-after-poisoned-step and hot-swap-under-load parity
preserved under the mesh, zero recompiles, zero page leaks — plus the
streaming (per-leaf, sharded, partial) checkpoint reload and the mesh
observability surface. Run standalone via ``make serve-mesh``.

Slow-marked (the ~1 min of per-mesh bucket compiles would push tier-1
past its walltime budget); the multichip dryrun's serve leg keeps a
fast mesh-parity canary in the default gate.
"""

import os

import jax
import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.serve import InferenceEngine, InferenceServer, ServeConfig
from trlx_tpu.serve.slots import SlotScheduler
from trlx_tpu.supervisor import chaos
from test_lifecycle import _http
from test_serve import tiny_config_dict
from test_slots import direct_generate

pytestmark = [pytest.mark.mesh, pytest.mark.slow]

BUCKETS = [[2, 8, 8], [4, 8, 8]]
MAX_NEW = 4

#: shared 4-token prefix (page-aligned at page_size 4) + per-request
#: tails — exercises radix prefix hits under the sharded pool
PREFIX = [11, 22, 33, 44]
ROWS = [
    PREFIX + [1, 2, 3],
    PREFIX + [4, 5],
    PREFIX + [6, 7, 8, 9],
    [2, 4, 6],  # no shared prefix: the cold path stays covered
    PREFIX + [1, 3],
    PREFIX + [9, 8, 7],
]


def mesh_engine(mesh=None, page_size=4, weights="fsdp", **overrides):
    serve = ServeConfig(**{
        "buckets": BUCKETS, "max_queue": 64, "request_timeout": 30.0,
        "slots": 4, "page_size": page_size, "mesh": mesh,
        "mesh_weights": weights,
        **overrides,
    })
    return InferenceEngine(TRLConfig.from_dict(tiny_config_dict()),
                           serve=serve)


# greedy decode is Markov on the token prefix: the oracle (one-shot
# generate on a SINGLE-DEVICE engine) is the same for every page size
# and mesh — computed once; all config-built engines share weights
_EXPECTED = []


def expected_rows():
    if not _EXPECTED:
        oracle_engine = mesh_engine(mesh=None)
        for i in range(0, len(ROWS), 2):
            pair = ROWS[i:i + 2]
            out = direct_generate(oracle_engine, pair, (2, 8, 8),
                                  gen_size=MAX_NEW)
            for j in range(len(pair)):
                _EXPECTED.append(oracle_engine.depad_row(out, j, MAX_NEW))
    return _EXPECTED


def run_staggered(sched):
    """Two admission waves: the second submits while the first is still
    decoding (6 requests > 4 slots forces queueing either way), so
    prefix hits land against live, partially-decoded slots."""
    first = [sched.submit(list(r), max_new_tokens=MAX_NEW)
             for r in ROWS[:4]]
    first[0].wait(timeout=60.0)  # wave 1 admitted and producing
    rest = [sched.submit(list(r), max_new_tokens=MAX_NEW)
            for r in ROWS[4:]]
    for r in first + rest:
        r.wait(timeout=60.0)
    return [r.result for r in first + rest]


def assert_no_leaks(sched):
    stats = sched.pool_stats()
    assert sched.free_slots() == sched.runtime.num_slots
    assert stats["pages_free"] + stats["pages_cached"] \
        == stats["pages_total"], "leaked pages"


# --------------------------------------------------------------------- #
# tentpole: greedy bit-parity vs single-chip, zero recompiles
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("page_size", [3, 8, 16])  # 16 = bucket T_max
def test_tp2_greedy_parity_page_sweep(serve_mesh_devices, page_size):
    registry = telemetry.start().registry
    want = expected_rows()
    engine = mesh_engine(mesh={"tp": 2}, page_size=page_size)
    assert engine.mesh.size == 2
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        got = run_staggered(s)
        assert got == want, (
            f"page_size={page_size}: tp=2 outputs diverged from the "
            f"single-device oracle"
        )
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert_no_leaks(s)
        # the pool really is head-sharded: 2 shards per KV page leaf
        k0 = jax.tree_util.tree_leaves(s.runtime.pool)[0]
        assert len(k0.sharding.device_set) == 2
    finally:
        s.stop()
        telemetry.start()


@pytest.mark.parametrize("mesh,weights", [
    ({"tp": 2, "fsdp": 2}, "fsdp"),
    ({"tp": 2}, "replicated"),
])
def test_mesh_variants_greedy_parity(serve_mesh_devices, mesh, weights):
    """tp x fsdp (weights fsdp-sharded) and tp-with-replicated-weights
    both decode bit-identically to single-chip, zero recompiles."""
    registry = telemetry.start().registry
    want = expected_rows()
    engine = mesh_engine(mesh=mesh, weights=weights)
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        got = run_staggered(s)
        assert got == want, f"mesh={mesh}, weights={weights}"
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert_no_leaks(s)
    finally:
        s.stop()
        telemetry.start()


def test_tp2_pallas_kernel_greedy_parity(serve_mesh_devices):
    """The fused paged-attention decode kernel under a tp=2 mesh
    (``serve.attention: pallas``, kernel shard_map'd over the
    head-sharded pool) emits greedy tokens identical to the
    single-device jnp oracle — the tp parity invariant holds through
    the kernel tier, zero recompiles, zero leaks."""
    registry = telemetry.start().registry
    want = expected_rows()
    engine = mesh_engine(mesh={"tp": 2}, attention="pallas")
    assert engine.mesh.size == 2
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        got = run_staggered(s)
        assert got == want, (
            "tp=2 pallas kernel outputs diverged from the single-device "
            "oracle"
        )
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert_no_leaks(s)
    finally:
        s.stop()
        telemetry.start()


def test_tp2_speculation_greedy_parity(serve_mesh_devices):
    """Speculative decoding (``serve.speculation: lookup``) under a tp=2
    mesh emits greedy tokens bit-identical to the single-device oracle —
    the batched ``verify_step`` executable shards exactly like
    ``decode_step`` (pool head-sharded, candidates replicated), so the
    parity invariant holds through the speculation tier with zero
    recompiles and zero leaks."""
    registry = telemetry.start().registry
    want = expected_rows()
    engine = mesh_engine(mesh={"tp": 2}, speculation="lookup", spec_k=4)
    assert engine.mesh.size == 2
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        got = run_staggered(s)
        assert got == want, (
            "tp=2 speculative outputs diverged from the single-device "
            "oracle"
        )
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert registry.counters.get("serve/spec_fallbacks", 0.0) == 0.0
        assert not s._speculators  # released at harvest
        assert_no_leaks(s)
    finally:
        s.stop()
        telemetry.start()


# --------------------------------------------------------------------- #
# crash-only invariants under the mesh
# --------------------------------------------------------------------- #


def test_replay_after_poisoned_step_parity_mesh(serve_mesh_devices):
    """A poisoned decode step on the tp=2 engine replays every in-flight
    request bit-identically to the uninterrupted single-device oracle —
    journal, radix re-map, and suffix prefill all stay host-side and
    mesh-oblivious."""
    # short prompts: replay re-prefills prompt + committed tokens, which
    # must still fit the (8, 8) lattice after a mid-decode poison
    rows = [[11, 22, 1], [11, 22, 4, 5], [6, 7], [11, 22, 9], [2, 4, 6],
            [11, 22, 3, 1]]
    registry = telemetry.start().registry
    oracle_engine = mesh_engine(mesh=None)
    want = []
    for i in range(0, len(rows), 2):
        out = direct_generate(oracle_engine, rows[i:i + 2], (2, 8, 8),
                              gen_size=MAX_NEW)
        want += [oracle_engine.depad_row(out, j, MAX_NEW)
                 for j in range(2)]
    engine = mesh_engine(mesh={"tp": 2})
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        chaos.configure("serve_decode:exc@2")
        reqs = [s.submit(list(r), max_new_tokens=MAX_NEW) for r in rows]
        for r in reqs:
            r.wait(timeout=60.0)
        chaos.reset()
        assert [r.result for r in reqs] == want
        assert any(r.replays >= 1 for r in reqs)
        assert registry.counters.get("serve/request_errors", 0.0) == 0.0
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert_no_leaks(s)
    finally:
        chaos.reset()
        s.stop()
        telemetry.start()


def test_hot_swap_under_load_mesh(serve_mesh_devices, tmp_path):
    """Live hot-swap on the tp=2 engine mid-burst: new weights stream
    per-shard onto the live shardings, in-flight requests finish, and
    post-swap outputs are bit-identical to a single-device engine built
    from the new checkpoint. Zero recompiles throughout."""
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
    serve = ServeConfig(buckets=BUCKETS, max_queue=64,
                        request_timeout=30.0, slots=4,
                        page_size=4, mesh={"tp": 2})
    engine = InferenceEngine.from_checkpoint(
        os.path.join(run, "step_1"), serve=serve
    )
    s = SlotScheduler(engine)
    s.warmup()
    s.start()
    try:
        inflight = [s.submit(list(r), max_new_tokens=MAX_NEW)
                    for r in ROWS]
        params, resolved = engine.load_params(run)  # newest = step_2
        res = s.request_swap(params, label=resolved)
        assert res["reloaded"] is True, res
        for r in inflight:
            r.wait(timeout=60.0)
        assert engine.model_version == 2

        after = [s.submit(list(r), max_new_tokens=MAX_NEW)
                 for r in ROWS[:2]]
        for r in after:
            r.wait(timeout=60.0)
        # cross-version parity bar: a SINGLE-DEVICE engine from step_2
        oracle = InferenceEngine.from_checkpoint(
            os.path.join(run, "step_2"),
            serve=ServeConfig(buckets=BUCKETS, slots=4, page_size=4),
        )
        out = direct_generate(oracle, ROWS[:2], (2, 8, 8),
                              gen_size=MAX_NEW)
        assert [r.result for r in after] == [
            oracle.depad_row(out, j, MAX_NEW) for j in range(2)
        ]
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
        assert_no_leaks(s)
    finally:
        s.stop()
        telemetry.start()


# --------------------------------------------------------------------- #
# streaming reload (per-leaf, partial, sharded) — the size probe
# --------------------------------------------------------------------- #


def test_streaming_reload_is_partial_and_sharded(serve_mesh_devices,
                                                 tmp_path):
    """load_params restores the decode SUBSET only, each leaf already
    device-committed on its live serve sharding: the training-only
    subtrees (ref branch, value head) never load, so reload's transient
    footprint is bounded by the serving set — the size probe — and
    install_views' per-shard device_put is a no-op re-place."""
    from trlx_tpu.utils import tree_bytes
    from trlx_tpu.utils.loading import get_model

    run = str(tmp_path / "run")
    cfg = TRLConfig.from_dict(tiny_config_dict())
    get_model(cfg.model.model_type)(cfg).save(os.path.join(run, "step_1"))

    telemetry.start()
    serve = ServeConfig(buckets=BUCKETS, slots=4,
                        page_size=4, mesh={"tp": 2})
    engine = InferenceEngine.from_checkpoint(
        os.path.join(run, "step_1"), serve=serve
    )
    params, resolved = engine.load_params(run)
    assert resolved.endswith("step_1")

    # partial: the training-only subtrees are ABSENT, not just unused
    assert "ref" not in params
    assert "v_head" not in params["trainable"]
    full_bytes = tree_bytes(jax.eval_shape(engine._init_params))
    got_bytes = tree_bytes(params)
    assert got_bytes < full_bytes, (
        "streamed reload restored as many bytes as a full restore — "
        "the partial template is not being honored"
    )

    # sharded: leaves land committed on the LIVE view shardings (the
    # hot-swap device_put then moves nothing)
    wq = params["frozen_base"]["blocks"]["attn"]["wq"]
    assert isinstance(wq, jax.Array)
    assert wq.sharding == engine.blocks[0]["attn"]["wq"].sharding
    assert len(wq.sharding.device_set) == 2  # really tp-split

    # value parity vs the serving views installed from the same
    # checkpoint (from_checkpoint used the identical streaming path)
    np.testing.assert_array_equal(
        np.asarray(wq), np.asarray(engine.blocks[0]["attn"]["wq"])
    )
    np.testing.assert_array_equal(
        np.asarray(params["trainable"]["ln_f"]["scale"]),
        np.asarray(engine.ln_f["scale"]),
    )


# --------------------------------------------------------------------- #
# observability: /healthz + /debug/state mesh block, capacity gauges
# --------------------------------------------------------------------- #


def test_mesh_observability_surface(serve_mesh_devices):
    registry = telemetry.start().registry
    engine = mesh_engine(mesh={"tp": 2}, buckets=[[2, 8, 8]])
    srv = InferenceServer(engine, port=0).start(warmup=True)
    try:
        status, _, body = _http(srv.port, "/healthz")
        assert status == 200
        assert body["mesh"]["devices"] == 2
        assert body["mesh"]["axes"] == {"tp": 2}
        assert body["mesh"]["weights"] == "fsdp"
        assert body["mesh"]["params_gb_per_device"] > 0
        assert body["kv"]["pool_gb_per_device"] > 0

        status, _, state = _http(srv.port, "/debug/state")
        assert status == 200
        assert state["mesh"]["devices"] == 2

        status, _, metrics = _http(srv.port, "/metrics")
        assert metrics["gauges"]["serve/mesh_devices"] == 2
        assert metrics["gauges"]["serve/params_gb_per_device"] > 0
        assert registry.counters.get("compile/recompiles", 0.0) == 0.0
    finally:
        srv.stop()
        telemetry.start()
