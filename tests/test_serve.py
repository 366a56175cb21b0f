"""Inference-serving tests (trlx_tpu/serve): bucket lattice + the
one-shot ``decode`` oracle, submit/wait validation, the ``serve:``
config surface (retired settings refused by name, files written before
they were retired still loading), HTTP endpoint routes, and the
checkpoint->endpoint parity e2e the subsystem exists for. The slot
scheduler's own tier is test_slots.py.
"""

import json
import pathlib
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.serve import (
    InferenceEngine,
    InferenceServer,
    ServeConfig,
    SlotScheduler,
)
from trlx_tpu.supervisor import chaos

REPO = pathlib.Path(__file__).resolve().parent.parent


def tiny_config_dict(do_sample=False):
    return {
        "model": {
            "model_path": "from-config",
            "tokenizer_path": "byte",
            "model_type": "JaxPPOTrainer",
            "num_layers_unfrozen": 1,
            "model_spec": {
                "vocab_size": 257,
                "n_layer": 2,
                "n_head": 4,
                "d_model": 64,
                "n_positions": 32,
            },
            "compute_dtype": "float32",
        },
        "train": {
            "n_ctx": 32,
            "epochs": 1,
            "total_steps": 4,
            "batch_size": 8,
            "grad_clip": 1.0,
            "lr_ramp_steps": 0,
            "lr_decay_steps": 4,
            "weight_decay": 1e-6,
            "learning_rate_init": 1e-3,
            "learning_rate_target": 1e-3,
            "log_interval": 1000,
            "checkpoint_interval": 10**9,
            "eval_interval": 10**9,
            "pipeline": "PPOPipeline",
            "orchestrator": "PPOOrchestrator",
            "input_size": 4,
            "gen_size": 8,
            "seed": 0,
            "telemetry": False,
        },
        "method": {
            "name": "ppoconfig",
            "num_rollouts": 8,
            "chunk_size": 8,
            "ppo_epochs": 1,
            "gen_kwargs": {
                "max_length": 8,
                "min_length": 8,
                "top_k": 0,
                "top_p": 1.0,
                "do_sample": do_sample,
            },
        },
    }


SERVE = ServeConfig(
    buckets=[[2, 8, 8], [4, 8, 8], [4, 16, 8]],
    max_queue=64,
    request_timeout=30.0,
    page_size=4,
)


@pytest.fixture(scope="module")
def engine():
    """One tiny greedy-decode engine shared by the unit tests (warm
    executables amortized across them)."""
    telemetry.start()
    cfg = TRLConfig.from_dict(tiny_config_dict())
    return InferenceEngine(cfg, serve=SERVE)


@pytest.fixture()
def fresh_registry():
    session = telemetry.start()
    yield session.registry
    telemetry.start()


@pytest.fixture()
def scheduler(engine):
    """Not started: nothing drains, so submit's own checks are what runs."""
    s = SlotScheduler(engine)
    yield s
    s.stop()


# --------------------------------------------------------------------- #
# engine: lattice + shaping
# --------------------------------------------------------------------- #


def test_pick_shape_rounds_up_to_smallest_fit(engine):
    assert engine.pick_shape(3, 5) == (8, 8)
    assert engine.pick_shape(8, 8) == (8, 8)
    assert engine.pick_shape(9, 8) == (16, 8)
    with pytest.raises(ValueError, match="fits no serve bucket"):
        engine.pick_shape(17, 8)
    with pytest.raises(ValueError, match="fits no serve bucket"):
        engine.pick_shape(4, 9)


def test_pad_batch_left_pads_and_fills(engine):
    bucket = (4, 8, 8)
    tokens, mask = engine.pad_batch([[1, 2, 3], [4]], bucket)
    assert tokens.shape == mask.shape == (4, 8)
    assert list(tokens[0, -3:]) == [1, 2, 3] and mask[0, :5].sum() == 0
    assert tokens[1, -1] == 4 and mask[1].sum() == 1
    # filler rows repeat row 0 (never read back)
    np.testing.assert_array_equal(tokens[2], tokens[0])
    np.testing.assert_array_equal(tokens[3], tokens[0])


def test_bucket_validation():
    cfg = TRLConfig.from_dict(tiny_config_dict())
    with pytest.raises(ValueError, match="n_positions"):
        InferenceEngine(
            cfg, serve=ServeConfig(buckets=[[2, 32, 32]]), init=False
        )
    with pytest.raises(ValueError, match="triple"):
        InferenceEngine(
            cfg, serve=ServeConfig(buckets=[[2, 8]]), init=False
        )


def test_engine_rejects_non_ppo_method():
    cfg_dict = tiny_config_dict()
    cfg_dict["method"] = {"name": "ilqlconfig"}
    cfg = TRLConfig.from_dict(cfg_dict)
    with pytest.raises(NotImplementedError, match="hydra"):
        InferenceEngine(cfg, serve=SERVE, init=False)


def test_decode_compiles_each_bucket_once(engine, fresh_registry):
    """The one-shot oracle: a bucket's first ``decode`` is a first
    compile in ITS OWN cache, never a steady-state miss, and a second
    call of the same bucket compiles nothing."""
    engine._decode_fns = {}
    for b in engine.buckets:
        tokens, mask = engine.pad_batch([[1, 2]], b)
        engine.decode(b, tokens, mask, seed=0)
    assert fresh_registry.counters.get("compile/recompiles", 0.0) == 0.0
    assert set(engine._decode_fns) == set(engine.buckets)
    b = engine.buckets[0]
    tokens, mask = engine.pad_batch([[1, 2]], b)
    engine.decode(b, tokens, mask, seed=3)
    assert fresh_registry.counters.get("compile/recompiles", 0.0) == 0.0
    assert len(engine._decode_fn(b)._cache) == 1
    # per-bucket first-call (compile) latency recorded apart by the tracer
    assert f"compile/{engine.span_name(b)}_first_s" in fresh_registry.gauges
    assert engine.max_new_tokens_cap() == 8
    assert engine.default_max_new_tokens() == 8


# --------------------------------------------------------------------- #
# submit / wait
# --------------------------------------------------------------------- #


def test_submit_validation(engine, scheduler):
    with pytest.raises(ValueError, match="empty prompt"):
        scheduler.submit([], max_new_tokens=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        scheduler.submit([1], max_new_tokens=0)
    with pytest.raises(ValueError, match="fits no serve bucket"):
        scheduler.submit([1], max_new_tokens=99)
    # bucket rounding: each request carries the shape class it rounds to
    assert scheduler.submit([1, 2], max_new_tokens=8).shape == (8, 8)
    assert scheduler.submit(
        list(range(1, 13)), max_new_tokens=8
    ).shape == (16, 8)


def test_wait_timeout_raises(engine, scheduler):
    req = scheduler.submit([1, 2], max_new_tokens=2)
    with pytest.raises(TimeoutError, match="not decoded within"):
        req.wait(timeout=0.05)


# --------------------------------------------------------------------- #
# HTTP endpoint
# --------------------------------------------------------------------- #


def _post(port, payload, path="/generate"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, json.loads(resp.read())


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=60
    ) as resp:
        return resp.status, json.loads(resp.read())


@pytest.fixture(scope="module")
def server(engine):
    telemetry.start()
    srv = InferenceServer(engine, port=0).start(warmup=True)
    yield srv
    srv.stop()


def test_healthz(server):
    status, body = _get(server.port, "/healthz")
    assert status == 200
    assert body["status"] == "ok" and body["warmed"]
    assert [2, 8, 8] in body["buckets"]


def test_generate_roundtrip(server):
    status, body = _post(
        server.port, {"prompt": "hello", "max_new_tokens": 4}
    )
    assert status == 200
    assert isinstance(body["tokens"], list) and len(body["tokens"]) <= 4
    assert isinstance(body["text"], str)
    assert body["bucket"] == [8, 8]
    assert body["latency_ms"] >= 0


def test_generate_by_tokens_matches_prompt(server):
    engine = server.engine
    toks = engine.encode_prompt("abc")
    s1, b1 = _post(server.port, {"prompt": "abc", "max_new_tokens": 6})
    s2, b2 = _post(server.port, {"tokens": toks, "max_new_tokens": 6})
    assert s1 == s2 == 200
    assert b1["tokens"] == b2["tokens"]  # greedy: composition-independent


def test_http_error_taxonomy(server):
    # 400: bad JSON
    with pytest.raises(urllib.error.HTTPError) as e:
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/generate",
            data=b"{not json", method="POST",
        )
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 400
    # 400: no prompt/tokens
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {"wrong": 1})
    assert e.value.code == 400
    # 400: request exceeds every bucket
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {"prompt": "x", "max_new_tokens": 10_000})
    assert e.value.code == 400
    # 404: unknown routes
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(server.port, "/nope")
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server.port, {}, path="/nope")
    assert e.value.code == 404


def test_chaos_request_exc_maps_to_500(server):
    chaos.configure("serve_request:exc@1")
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server.port, {"prompt": "x", "max_new_tokens": 2})
        assert e.value.code == 500
        assert "chaos" in json.loads(e.value.read())["error"]
    finally:
        chaos.reset()


def test_queue_full_maps_to_429(server):
    scheduler = server.scheduler
    old = scheduler.max_queue
    scheduler.max_queue = 0
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server.port, {"prompt": "x", "max_new_tokens": 2})
        assert e.value.code == 429
    finally:
        scheduler.max_queue = old


def test_metrics_dump_has_serve_family(server):
    _post(server.port, {"prompt": "warm", "max_new_tokens": 2})
    status, body = _get(server.port, "/metrics")
    assert status == 200
    counters, gauges = body["counters"], body["gauges"]
    assert counters["serve/requests"] >= 1
    assert counters["serve/admissions"] >= 1
    assert "serve/rejected" in counters  # predeclared even before firing
    assert "serve/queue_depth" in gauges
    assert "serve/slot_occupancy" in gauges
    assert "serve/tokens_per_sec" in gauges
    assert "time/serve/slot_step" in body["timings"]
    assert any(k.startswith("time/serve/prefill_") for k in body["timings"])
    assert "serve/request_latency{path=slots}" in body["timings"]
    hist = body["timings"]["serve/request_latency{path=slots}"]
    assert "p50_s" in hist and "p95_s" in hist


def test_metrics_carry_nothing_of_the_static_path(server):
    """A warmed server's /metrics, JSON and Prometheus text: no series
    labelled with the retired path, none of the three names only the
    batch-to-completion scheduler set."""
    _post(server.port, {"prompt": "warm", "max_new_tokens": 2})
    _, body = _get(server.port, "/metrics")
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/metrics",
        headers={"Accept": "text/plain"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        text = resp.read().decode()
    assert 'path="slots"' in text
    names = [k for section in ("counters", "gauges", "timings")
             for k in body[section]]
    for gone in ("static", "serve/batches", "serve/batch_fill_ratio",
                 "serve/buckets_warmed"):
        assert not [k for k in names if gone in k], gone
        assert gone.replace("/", "_") not in text, gone


# --------------------------------------------------------------------- #
# checkpoint -> endpoint e2e (the acceptance scenario)
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("seed", [0])
def test_checkpoint_to_endpoint_parity_e2e(tmp_path, seed):
    """Train-side checkpoint in, HTTP endpoint out: >= 8 concurrent
    mixed-length requests decode token-identically to a direct
    ``generate()`` call at the same bucket, with zero steady-state
    recompiles and the serve/* metric family in /metrics."""
    from trlx_tpu.models.generation import generate
    from trlx_tpu.utils.loading import get_model

    cfg = TRLConfig.from_dict(tiny_config_dict())
    trainer = get_model(cfg.model.model_type)(cfg)
    ckpt = str(tmp_path / "ckpt")
    trainer.save(ckpt)

    registry = telemetry.start().registry
    serve_cfg = ServeConfig(
        buckets=[[8, 8, 8]], max_queue=64, request_timeout=60.0,
        page_size=4,
    )
    # config=None: the architecture comes from the checkpoint's own
    # embedded meta.json config — the self-describing-checkpoint path
    engine = InferenceEngine.from_checkpoint(ckpt, serve=serve_cfg)
    server = InferenceServer(engine, port=0).start(warmup=True)
    try:
        prompts = ["a", "bc", "def", "ghij", "klmno", "pqrstu",
                   "vwxyz12", "34567890"]
        rows = [engine.encode_prompt(p) for p in prompts]
        assert sorted({len(r) for r in rows}) == list(range(1, 9))

        results = [None] * len(prompts)
        errors = []

        def call(i):
            try:
                _, body = _post(
                    server.port,
                    {"prompt": prompts[i], "max_new_tokens": 8},
                )
                results[i] = body
            except Exception as e:  # surfaces in the main thread below
                errors.append((i, e))

        threads = [
            threading.Thread(target=call, args=(i,))
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, f"request failures: {errors}"

        # direct generate() at the same bucket: identical stacked batch
        bucket = (8, 8, 8)
        tokens, mask = engine.pad_batch(rows, bucket)
        gen_cfg = engine._gen_base._replace(gen_size=8)
        direct = jax.jit(
            lambda b, e, lf, t, m, r: generate(
                engine.spec, b, e, lf, t, m, r, gen_cfg,
                compute_dtype=jnp.float32,
            )
        )(engine.blocks, engine.embed, engine.ln_f, tokens, mask,
          jax.random.PRNGKey(seed))
        for i in range(len(prompts)):
            expect = engine.depad_row(direct, i, 8)
            assert results[i]["tokens"] == expect, (
                f"request {i} ({prompts[i]!r}) diverged from direct "
                f"generate(): {results[i]['tokens']} vs {expect}"
            )

        # serving invariant: exactly one compile per warmed program and
        # ZERO steady-state recompiles across all live traffic
        _, metrics = _get(server.port, "/metrics")
        assert metrics["counters"]["compile/recompiles"] == 0
        assert registry.counters["compile/recompiles"] == 0.0
        assert "compile/serve/prefill_b8p8_first_s" in metrics["gauges"]
        assert "compile/serve/slot_step_first_s" in metrics["gauges"]
        assert "serve/request_latency{path=slots}" in metrics["timings"]
        assert metrics["counters"]["serve/requests"] >= 8
        assert metrics["counters"]["serve/generated_tokens"] > 0
        assert metrics["gauges"].get("serve/model_gb", 0) > 0
    finally:
        server.stop()
        telemetry.start()


def test_from_checkpoint_without_embedded_config_raises(tmp_path):
    from trlx_tpu.utils.checkpoint import save_components

    save_components({"state": {"iter_count": 0}}, str(tmp_path / "c"))
    with pytest.raises(ValueError, match="no embedded config"):
        InferenceEngine.from_checkpoint(str(tmp_path / "c"))


def test_from_checkpoint_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="no committed checkpoint"):
        InferenceEngine.from_checkpoint(str(tmp_path / "nope"))


# --------------------------------------------------------------------- #
# CLI surface
# --------------------------------------------------------------------- #


def test_cli_bucket_parsing():
    from trlx_tpu.serve.__main__ import build_parser, parse_buckets

    assert parse_buckets("8x32x16,4x8x8") == [[8, 32, 16], [4, 8, 8]]
    with pytest.raises(ValueError, match="BATCHxPROMPTxGEN"):
        parse_buckets("8x32")
    args = build_parser().parse_args(
        ["--checkpoint", "c", "--buckets", "2x8x8", "--port", "0",
         "--max-queue", "7", "--slots", "3", "--page-size", "4"]
    )
    from trlx_tpu.serve.__main__ import serve_config_from_args

    cfg = serve_config_from_args(args)
    assert cfg.buckets == [[2, 8, 8]]
    assert cfg.port == 0 and cfg.max_queue == 7
    assert cfg.slots == 3 and cfg.page_size == 4
    # flags unset: the ServeConfig defaults survive
    bare = serve_config_from_args(
        build_parser().parse_args(["--checkpoint", "c"])
    )
    assert bare.slots == 0 and bare.page_size == 64


@pytest.mark.parametrize("flag, value", [
    ("--scheduler", "slots"), ("--kv-layout", "paged"),
])
def test_cli_refuses_the_retired_flags(flag, value, capsys):
    """The flags went with the paths they chose between: argparse
    refuses them (exit 2) even at the value that names what is left."""
    from trlx_tpu.serve.__main__ import build_parser

    with pytest.raises(SystemExit) as refused:
        build_parser().parse_args(["--checkpoint", "c", flag, value])
    assert refused.value.code == 2
    assert flag in capsys.readouterr().err


def test_serve_config_roundtrip():
    cfg = ServeConfig.from_dict(
        {"buckets": [[2, 8, 8]], "max_queue": 7, "unknown_key": 1}
    )
    assert cfg.buckets == [[2, 8, 8]] and cfg.max_queue == 7


# --------------------------------------------------------------------- #
# settings retired in PR 31: refused by name, or loaded and dropped
# --------------------------------------------------------------------- #

RETIRED = [("scheduler", "static", "slot scheduler"),
           ("kv_layout", "contiguous", "pages")]
#: what every config file and checkpoint written before PR 31 carries
LEGACY_SERVE = {"scheduler": "slots", "kv_layout": "paged",
                "max_wait_ms": 20.0}


@pytest.mark.parametrize("key, value, survivor", RETIRED)
def test_from_dict_refuses_a_retired_path_by_name(key, value, survivor):
    with pytest.raises(ValueError, match=f"serve.{key}.*{value}") as e:
        ServeConfig.from_dict({"buckets": [[2, 8, 8]], key: value})
    assert "PR 31" in str(e.value) and survivor in str(e.value)


@pytest.mark.parametrize("key", sorted(k for k, _, _ in RETIRED))
def test_from_dict_drops_the_surviving_value(key):
    cfg = ServeConfig.from_dict({"slots": 3, key: LEGACY_SERVE[key]})
    assert cfg.slots == 3 and not hasattr(cfg, key)


def test_trl_config_refuses_a_retired_serve_block_at_load(tmp_path):
    import yaml

    body = {**tiny_config_dict(), "serve": {"kv_layout": "contiguous"}}
    path = tmp_path / "old.yml"
    path.write_text(yaml.safe_dump(body))
    with pytest.raises(ValueError, match="serve.kv_layout.*PR 31"):
        TRLConfig.load_yaml(str(path))
    body["serve"] = dict(LEGACY_SERVE)  # the surviving values: loads
    path.write_text(yaml.safe_dump(body))
    assert TRLConfig.load_yaml(str(path)).train.gen_size == 8


def test_checkpoint_written_before_pr31_still_loads(tmp_path):
    """Checkpoints are self-describing, and the ones users hold embed a
    config written when the three keys existed."""
    from trlx_tpu.utils.checkpoint import META_NAME
    from trlx_tpu.utils.loading import get_model

    cfg = TRLConfig.from_dict(tiny_config_dict())
    ckpt = tmp_path / "ckpt"
    get_model(cfg.model.model_type)(cfg).save(str(ckpt))
    meta = json.loads((ckpt / META_NAME).read_text())
    meta["config"]["serve"] = dict(LEGACY_SERVE)
    (ckpt / META_NAME).write_text(json.dumps(meta))
    engine = InferenceEngine.from_checkpoint(
        str(ckpt), serve=ServeConfig.from_dict(
            {**LEGACY_SERVE, "buckets": [[2, 8, 8]], "page_size": 4}
        ), )
    assert engine.checkpoint_path == str(ckpt)
    assert engine.page_count() == 2 * 4  # slots x pages-per-slot
    telemetry.start()


SERVE_CELLS = sorted(
    p.name for p in (REPO / "benchmarks" / "workloads").glob("*.json")
    if "serve" in json.loads(p.read_text())
)


@pytest.mark.parametrize("block", ["serve", "rehearse.serve"])
@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_accepted_serve_cells_load_as_they_are(cell, block):
    """The benchmark's workload files may not change, and name the two
    retired keys at their surviving values: each ``serve`` block still
    loads, and every other key lands on a field (none is dropped in
    silence, which would run the cell some other way than it says)."""
    import dataclasses

    data = json.loads((REPO / "benchmarks" / "workloads" / cell).read_text())
    section = data["serve"] if block == "serve" else data["rehearse"]["serve"]
    if block == "serve":  # where a file still names them (the cells of before PR 31 do)
        assert section.get("scheduler", "slots") == "slots"
        assert section.get("kv_layout", "paged") == "paged"
    cfg = ServeConfig.from_dict(section)
    fields = {f.name for f in dataclasses.fields(ServeConfig)}
    for key, value in section.items():
        if key in ("scheduler", "kv_layout"):
            continue
        assert key in fields, f"{cell} {block}.{key} is dropped"
        assert getattr(cfg, key) == value


def test_config_embeds_and_roundtrips():
    """The trainers' checkpoint config component parses back into an
    equivalent TRLConfig (the serve CLI's no-config path)."""
    cfg = TRLConfig.from_dict(tiny_config_dict())
    rebuilt = TRLConfig.from_dict(cfg.to_nested_dict())
    assert rebuilt.model.__dict__ == cfg.model.__dict__
    assert rebuilt.train.__dict__ == cfg.train.__dict__
    assert rebuilt.method.__dict__ == cfg.method.__dict__
    assert json.loads(json.dumps(cfg.to_nested_dict()))  # JSON-safe
