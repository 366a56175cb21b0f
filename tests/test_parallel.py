"""SPMD parallelism tests on the 8-virtual-CPU-device mesh.

Validates the layer the reference delegates to Accelerate/NCCL/DeepSpeed
(reference: trlx/model/accelerate_base_model.py:52-82): mesh construction,
parameter sharding (dp/fsdp/tp), and that the sharded PPO train step is
numerically identical to the single-device one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tests.test_ppo_e2e import PROMPTS, make_config, reward_fn
from trlx_tpu.parallel import (
    build_mesh,
    param_sharding_specs,
    shard_batch,
)
from trlx_tpu.parallel.mesh import resolve_axis_sizes
from trlx_tpu.utils.loading import get_model, get_orchestrator, get_pipeline
from trlx_tpu.utils.tokenizer import ByteTokenizer

# --------------------------------------------------------------------- #
# mesh construction
# --------------------------------------------------------------------- #


def test_resolve_axis_sizes_wildcard():
    sizes = resolve_axis_sizes({"dp": -1, "tp": 2}, 8)
    assert sizes == {"dp": 4, "pp": 1, "fsdp": 1, "sp": 1, "tp": 2}


def test_resolve_axis_sizes_errors():
    with pytest.raises(ValueError):
        resolve_axis_sizes({"dp": 3}, 8)  # doesn't cover all devices
    with pytest.raises(ValueError):
        resolve_axis_sizes({"dp": -1, "tp": -1}, 8)  # two wildcards
    with pytest.raises(ValueError):
        resolve_axis_sizes({"bogus": 2}, 8)  # unknown axis


def test_build_mesh_shapes(devices):
    mesh = build_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    assert mesh.shape == {"dp": 2, "pp": 1, "fsdp": 2, "sp": 1, "tp": 2}
    assert mesh.devices.size == 8


# --------------------------------------------------------------------- #
# parameter sharding
# --------------------------------------------------------------------- #


def _tiny_trainer(mesh_cfg=None, **kw):
    config = make_config(**kw)
    config.train.mesh = mesh_cfg
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    return config, trainer


def test_params_are_sharded_on_mesh(devices):
    _, trainer = _tiny_trainer({"dp": 2, "fsdp": 2, "tp": 2})
    wq = trainer.params["trainable"]["blocks"]["attn"]["wq"]
    spec = wq.sharding.spec
    assert spec == P(None, "fsdp", "tp")
    # each device holds 1/(fsdp*tp) of the matrix
    L, D, _ = wq.shape
    shard = wq.addressable_shards[0].data
    assert shard.shape == (L, D // 2, D // 2)

    # adam moments inherit the param shardings (ZeRO-equivalent)
    mu = trainer.opt_state[1][0].mu["blocks"]["attn"]["wq"]
    assert mu.sharding.spec == spec

    # layernorms replicated
    ln = trainer.params["trainable"]["ln_f"]["scale"]
    assert ln.sharding.spec in (P(), P(None))


def test_param_specs_cover_every_leaf(devices):
    _, trainer = _tiny_trainer()
    specs = param_sharding_specs(trainer.params)
    leaves, _ = jax.tree_util.tree_flatten(specs)
    assert all(isinstance(s, P) for s in leaves)
    # embeddings and projections must actually be partitioned
    assert specs["frozen_base"]["embed"]["wte"] == P("tp", "fsdp")
    assert specs["trainable"]["v_head"]["w1"] == P("fsdp", "tp")


def test_shard_batch_partitions_leading_dim(devices):
    mesh = build_mesh({"dp": 4, "fsdp": 2})
    x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
    sx = shard_batch(mesh, x)
    assert sx.sharding.spec == P(("dp", "fsdp"))
    assert sx.addressable_shards[0].data.shape == (1, 3)
    np.testing.assert_array_equal(np.asarray(sx), x)


# --------------------------------------------------------------------- #
# numerical parity: sharded vs single-device
# --------------------------------------------------------------------- #


def _rollout_batch(trainer, config):
    trainer.store.clear_history()
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    orch.make_experience(config.method.num_rollouts)
    batch = next(iter(trainer.store.create_loader(16, shuffle=False)))
    return jax.tree_util.tree_map(np.asarray, batch)


def test_sharded_train_step_matches_single_device(devices):
    """One PPO train step over the (2, 2, 2) mesh must produce the same loss
    and the same updated params as the unsharded step — sharding is an
    execution detail, not a numerics change."""
    config_s, single = _tiny_trainer(None)
    batch = _rollout_batch(single, config_s)

    config_m, meshed = _tiny_trainer({"dp": 2, "fsdp": 2, "tp": 2})

    # identical init by construction (same seed); verify on one leaf
    np.testing.assert_array_equal(
        np.asarray(single.params["trainable"]["blocks"]["attn"]["wq"]),
        np.asarray(meshed.params["trainable"]["blocks"]["attn"]["wq"]),
    )

    p1, o1, stats1 = single._train_step(
        single.params, single.opt_state, jax.tree_util.tree_map(jnp.asarray, batch)
    )
    p2, o2, stats2 = meshed._train_step(
        meshed.params, meshed.opt_state, shard_batch(meshed.mesh, batch)
    )

    np.testing.assert_allclose(
        float(stats1["loss"]), float(stats2["loss"]), rtol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(p1["trainable"]["v_head"]["w2"]),
        np.asarray(p2["trainable"]["v_head"]["w2"]),
        rtol=2e-3, atol=2e-5,
    )
    # result stays sharded: the updated params keep their specs
    assert (
        p2["trainable"]["blocks"]["attn"]["wq"].sharding.spec
        == P(None, "fsdp", "tp")
    )


def test_sharded_generation_runs_and_matches_shapes(devices):
    config, meshed = _tiny_trainer({"dp": 2, "fsdp": 2, "tp": 2})
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, meshed.tokenizer, config
    )
    query, mask = next(iter(pipeline.create_loader(8)))
    out = meshed.generate(query, mask)
    assert out.sequences.shape == (8, 4 + 8)
    assert np.isfinite(np.asarray(out.gen_logprobs)).all()


def test_generation_pads_odd_batch_on_mesh(devices):
    """Ad-hoc batch sizes (eval prompts, user sample calls) that don't
    divide dp*fsdp are padded to shard, then sliced back."""
    config, meshed = _tiny_trainer({"dp": 2, "fsdp": 2, "tp": 2})
    query = np.full((6, 4), 97, np.int32)
    mask = np.ones((6, 4), np.int32)
    out = meshed.generate(query, mask)
    assert out.sequences.shape[0] == 6
    assert np.isfinite(np.asarray(out.gen_logprobs)).all()


def test_sharded_ppo_e2e_smoke(devices):
    """Full rollout -> train loop on the mesh: one epoch, finite stats."""
    config, meshed = _tiny_trainer(
        {"dp": 2, "fsdp": 2, "tp": 2},
        total_steps=4, epochs=1, num_rollouts=16, chunk_size=16,
        batch_size=16, ppo_epochs=1,
    )
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, meshed.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        meshed, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    orch.make_experience(config.method.num_rollouts)
    logs = []
    meshed.learn(log_fn=logs.append)
    assert meshed.iter_count > 0


@pytest.mark.parametrize("arch", ["gptj", "gptneox", "llama"])
def test_tp_sharded_forward_matches_dense_other_arches(devices, arch):
    """Tensor-parallel forward parity for the gpt-j /
    gpt-neox / llama families (rotary, parallel blocks, untied heads,
    GQA + swiglu for llama — the structures larger workloads shard
    over tp)."""
    import jax.numpy as jnp

    from trlx_tpu.data.configs import ModelSpec
    from trlx_tpu.models.policy import HydraPolicy
    from trlx_tpu.parallel import shard_params

    spec = ModelSpec(
        arch=arch, vocab_size=64, n_layer=2, n_head=4, d_model=32,
        n_positions=32, rotary_dim=8 if arch == "gptj" else 0,
        tie_lm_head=False,
        n_kv_heads=2 if arch == "llama" else 0,
    )
    policy = HydraPolicy(
        spec=spec, num_layers_unfrozen=1, compute_dtype=jnp.float32
    )
    params = policy.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    mask = jnp.ones((4, 16), jnp.int32)
    logits, ref, values = policy.forward(params, tokens, mask)

    mesh = build_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    sharded = shard_params(mesh, params)
    # tp must actually partition the attention projections
    wq = sharded["trainable"]["blocks"]["attn"]["wq"]
    assert wq.sharding.spec == P(None, "fsdp", "tp")
    with mesh:
        logits_s, ref_s, values_s = jax.jit(
            lambda p, t, m: policy.forward(p, t, m)
        )(sharded, tokens, mask)

    np.testing.assert_allclose(
        np.asarray(logits_s), np.asarray(logits), atol=2e-4
    )
    np.testing.assert_allclose(np.asarray(ref_s), np.asarray(ref), atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(values_s), np.asarray(values), atol=2e-4
    )


def test_ppo_gptj_config_builds_and_steps_on_mesh(devices):
    """The shipped ppo_gptj.yml wiring (gptj arch, tp+fsdp mesh) builds a
    trainer and completes a rollout + train step at toy scale."""
    from trlx_tpu.data.configs import TRLConfig

    config = TRLConfig.load_yaml("configs/ppo_gptj.yml")
    # toy geometry, real arch + real mesh axes from the shipped config
    config.model.model_spec = {
        "arch": "gptj", "vocab_size": 257, "n_layer": 2, "n_head": 4,
        "d_model": 64, "n_positions": 64, "rotary_dim": 16,
        "tie_lm_head": False,
    }
    config.model.tokenizer_path = "byte"
    config.model.compute_dtype = "float32"
    config.train.mesh = {"dp": -1, "fsdp": 2, "tp": 2}
    config.train.total_steps = 2
    config.train.epochs = 1
    config.train.batch_size = 8
    config.train.input_size = 4
    config.train.gen_size = 8
    config.train.log_interval = 1
    config.train.eval_interval = 10**9
    config.train.checkpoint_interval = 10**9
    config.method.num_rollouts = 8
    config.method.chunk_size = 8
    config.method.ppo_epochs = 1

    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    info = orch.make_experience(config.method.num_rollouts)
    assert np.isfinite(info["mean_score"])
    logs = []
    trainer.learn(log_fn=logs.append)
    train_logs = [l for l in logs if "loss" in l]
    assert train_logs and np.isfinite(train_logs[-1]["loss"])


def test_sharded_ilql_e2e_smoke(devices):
    """ILQL offline flow (store -> jitted loss/update/Polyak sync) on the
    full (dp, fsdp, sp, tp) mesh — the dryrun's second leg as a test."""
    import __graft_entry__

    mesh = build_mesh({"dp": -1, "fsdp": 2, "sp": 2, "tp": 2})
    steps = __graft_entry__._dryrun_ilql(mesh)
    assert steps > 0


def test_ppo_e2e_llama_arch_on_mesh(devices):
    """PPO rollout + train with the llama family (RMSNorm/SwiGLU/GQA) on
    the tp+fsdp mesh — the modern-family counterpart of the gptj smoke."""
    from trlx_tpu.data.configs import TRLConfig

    config = TRLConfig.from_dict({
        "model": {
            "model_path": "from-config", "tokenizer_path": "byte",
            "model_type": "JaxPPOTrainer", "num_layers_unfrozen": 1,
            "model_spec": {
                "arch": "llama", "vocab_size": 257, "n_layer": 2,
                "n_head": 4, "n_kv_heads": 2, "d_model": 64,
                "n_positions": 64, "tie_lm_head": False,
            },
            "compute_dtype": "float32",
        },
        "train": {
            "n_ctx": 64, "epochs": 1, "total_steps": 2, "batch_size": 8,
            "grad_clip": 1.0, "lr_ramp_steps": 0, "lr_decay_steps": 2,
            "weight_decay": 1e-6, "learning_rate_init": 1e-3,
            "learning_rate_target": 1e-3, "log_interval": 1,
            "checkpoint_interval": 10**9, "eval_interval": 10**9,
            "pipeline": "PPOPipeline", "orchestrator": "PPOOrchestrator",
            "input_size": 4, "gen_size": 8, "seed": 0,
            "mesh": {"dp": -1, "fsdp": 2, "tp": 2},
        },
        "method": {
            "name": "ppoconfig", "num_rollouts": 8, "chunk_size": 8,
            "ppo_epochs": 1,
            "gen_kwargs": {"max_length": 8, "min_length": 8,
                           "do_sample": True},
        },
    })
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    info = orch.make_experience(config.method.num_rollouts)
    assert np.isfinite(info["mean_score"])
    logs = []
    trainer.learn(log_fn=logs.append)
    train_logs = [l for l in logs if "loss" in l]
    assert train_logs and np.isfinite(train_logs[-1]["loss"])


def test_broadcast_host_floats_single_process_identity():
    from trlx_tpu.parallel import broadcast_host_floats

    vals = [0.25, -1.5, 3.0]
    out = broadcast_host_floats(vals)
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, np.asarray(vals, np.float32))


def test_broadcast_host_floats_uses_process0_when_multihost(monkeypatch):
    """Multi-process: every host must get process-0's array via
    multihost_utils.broadcast_one_to_all (divergent host reward floats
    would otherwise fork the SPMD replicas)."""
    import jax
    from jax.experimental import multihost_utils

    from trlx_tpu.parallel import broadcast_host_floats

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    called = {}

    def fake_broadcast(arr):
        called["arr"] = np.asarray(arr)
        return np.asarray(arr) + 0  # process-0's view
    monkeypatch.setattr(multihost_utils, "broadcast_one_to_all",
                        fake_broadcast)
    out = broadcast_host_floats([1.0, 2.0])
    np.testing.assert_array_equal(called["arr"], [1.0, 2.0])
    np.testing.assert_array_equal(out, [1.0, 2.0])
    assert out.dtype == np.float32

@pytest.mark.parametrize("mesh_spec", [
    None,  # pure dp over both processes
    # every parameter sharded over all 8 devices: forwards/backwards
    # all-gather ACROSS the process boundary
    {"dp": 1, "fsdp": 8, "tp": 1, "sp": 1},
    # Megatron tp collectives across the process boundary; the worker
    # additionally asserts the sharded forward matches a dense local
    # trainer's logits/values from identical init. (sp is not in the
    # matrix: the 12-token test sequence doesn't divide by a
    # process-spanning sp extent — ring attention is covered
    # single-process in test_ring_attention.py.)
    {"dp": 1, "fsdp": 1, "tp": 8, "sp": 1},
])
def test_two_process_distributed_cpu(tmp_path, mesh_spec):
    """Bring up jax.distributed across TWO real processes (the multi-host
    layer everything else only exercises single-process): explicit
    initialize_runtime, a mesh spanning both, broadcast_host_floats
    overriding rank-1's divergent rewards, and bit-identical trained params
    (see tests/distributed_worker.py for the per-process assertions)."""
    import json
    import os
    import socket
    import subprocess
    import sys
    from pathlib import Path

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"
    root = Path(__file__).resolve().parent.parent
    worker = root / "tests" / "distributed_worker.py"

    env = dict(os.environ)
    # the worker pins its own JAX env before importing jax
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    # sys.path[0] for a script is its own directory, not the cwd
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(root), env.get("PYTHONPATH", "")) if p
    )

    # write child output to files, not pipes: a verbose failing rank can
    # fill a pipe buffer and deadlock the sibling in a collective while
    # the parent blocks on the other child
    logs = [tmp_path / f"rank{rank}.log" for rank in (0, 1)]
    argv_tail = [] if mesh_spec is None else [json.dumps(mesh_spec)]
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), coordinator, str(rank)]
            + argv_tail,
            cwd=root, env=env,
            stdout=open(log, "w"), stderr=subprocess.STDOUT,
        )
        for rank, log in zip((0, 1), logs)
    ]
    try:
        for p in procs:
            p.wait(timeout=600)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for p in procs:
            p.wait()
    outs = [log.read_text() for log in logs]
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"rank {rank} failed (rc={p.returncode}):\n{out[-4000:]}"
        )
        assert f"DIST OK {rank}" in out, f"rank {rank} output:\n{out[-2000:]}"

# --------------------------------------------------------------------- #
# pipeline parallelism (beyond-parity: the reference has no PP)
# --------------------------------------------------------------------- #


def test_pp_forward_matches_dense(devices):
    """GPipe forward over pp=4 (composed with dp=2) must equal the dense
    stacked-layer scan — values AND gradients; the schedule is an
    execution detail, not a numerics change."""
    from trlx_tpu.data.configs import ModelSpec
    from trlx_tpu.models.transformer import (
        apply_blocks,
        causal_mask_bias,
        init_block_params,
        positions_from_mask,
    )
    from trlx_tpu.ops.pipeline_parallel import (
        pp_apply_blocks,
        shard_blocks_pp,
    )

    spec = ModelSpec(vocab_size=31, n_layer=8, n_head=4, d_model=32,
                     n_positions=16)
    blocks = init_block_params(jax.random.PRNGKey(0), spec, 8, jnp.float32)
    B, T = 8, 10
    r = np.random.default_rng(0)
    h = jnp.asarray(r.normal(size=(B, T, 32)).astype(np.float32))
    mask = np.ones((B, T), np.int32)
    mask[:2, -3:] = 0  # some padding rows
    mask = jnp.asarray(mask)
    bias = causal_mask_bias(mask)
    positions = positions_from_mask(mask)

    dense = apply_blocks(blocks, spec, h, bias, positions)

    mesh = build_mesh({"pp": 4, "dp": 2})
    pp_blocks = shard_blocks_pp(mesh, blocks)
    out = pp_apply_blocks(
        mesh, pp_blocks, spec, h, bias, positions, n_micro=4
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), rtol=2e-5, atol=2e-5
    )

    # gradients through the pipeline schedule (ppermute transposes to the
    # reverse hop — the GPipe backward — under plain jax.grad)
    def loss_dense(b):
        return (apply_blocks(b, spec, h, bias, positions) ** 2).sum()

    def loss_pp(b):
        return (
            pp_apply_blocks(mesh, b, spec, h, bias, positions, n_micro=4)
            ** 2
        ).sum()

    g_dense = jax.grad(loss_dense)(blocks)
    # grad-of-shard_map requires jit (trainers always jit the train step)
    g_pp = jax.jit(jax.grad(loss_pp))(pp_blocks)
    flat_pp = dict(
        (jax.tree_util.keystr(kp), x)
        for kp, x in jax.tree_util.tree_leaves_with_path(g_pp)
    )
    for kp, a in jax.tree_util.tree_leaves_with_path(g_dense):
        b = flat_pp[jax.tree_util.keystr(kp)]
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=jax.tree_util.keystr(kp),
        )


def test_pp_single_stage_passthrough(devices):
    """pp=1 must reduce to the plain dense scan (no shard_map overhead)."""
    from trlx_tpu.data.configs import ModelSpec
    from trlx_tpu.models.transformer import (
        apply_blocks,
        causal_mask_bias,
        init_block_params,
        positions_from_mask,
    )
    from trlx_tpu.ops.pipeline_parallel import pp_apply_blocks

    spec = ModelSpec(vocab_size=31, n_layer=2, n_head=4, d_model=32,
                     n_positions=16)
    blocks = init_block_params(jax.random.PRNGKey(1), spec, 2, jnp.float32)
    h = jnp.asarray(
        np.random.default_rng(1).normal(size=(4, 6, 32)).astype(np.float32)
    )
    mask = jnp.ones((4, 6), jnp.int32)
    bias = causal_mask_bias(mask)
    pos = positions_from_mask(mask)
    mesh = build_mesh({"dp": 8})
    # n_micro deliberately does NOT divide B: the pp=1 passthrough has no
    # microbatching constraints
    out = pp_apply_blocks(mesh, blocks, spec, h, bias, pos, n_micro=3)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(apply_blocks(blocks, spec, h, bias, pos)),
        rtol=1e-6,
    )


def test_trainer_pp_uneven_trunk_fails_loudly(devices):
    """Trainers CONSUME pp > 1 since round 5 — but a frozen trunk that
    doesn't split into pp stages (here: the tiny 2-layer model leaves 1
    frozen layer for pp=2) must fail at construction with the
    stage-divisibility error, not a shape error three jit frames deep."""
    with pytest.raises(ValueError, match="stages"):
        _tiny_trainer({"pp": 2, "dp": 4})


# --------------------------------------------------------------------- #
# pipeline parallelism consumed by the trainers (round 5)
# --------------------------------------------------------------------- #


def _pp_trainer(mesh_cfg, n_layer=3):
    """3-layer model, 1 unfrozen top -> a 2-layer frozen trunk that splits
    into pp=2 stages."""
    config = make_config(num_layers_unfrozen=1, batch_size=16)
    config.model.model_spec["n_layer"] = n_layer
    config.train.mesh = mesh_cfg
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    return config, trainer


def test_pp_trainer_train_step_matches_single_device(devices):
    """train.mesh pp > 1 now drives the trainers' forward:
    the GPipe'd frozen trunk produces the same loss and updated params as
    the dense single-device step."""
    config_s, single = _pp_trainer(None)
    batch = _rollout_batch(single, config_s)

    config_m, meshed = _pp_trainer({"pp": 2, "dp": 2, "fsdp": 2})
    assert meshed.policy.pp_mesh is not None

    np.testing.assert_array_equal(
        np.asarray(single.params["trainable"]["blocks"]["attn"]["wq"]),
        np.asarray(meshed.params["trainable"]["blocks"]["attn"]["wq"]),
    )
    # the frozen trunk's layer axis is stage-sharded: each device holds
    # L/pp layers — the parameter split pp exists for
    wq_f = meshed.params["frozen_base"]["blocks"]["attn"]["wq"]
    assert wq_f.sharding.spec[0] == "pp"
    assert wq_f.addressable_shards[0].data.shape[0] == 1

    p1, o1, stats1 = single._train_step(
        single.params, single.opt_state,
        jax.tree_util.tree_map(jnp.asarray, batch),
    )
    p2, o2, stats2 = meshed._train_step(
        meshed.params, meshed.opt_state, shard_batch(meshed.mesh, batch)
    )
    np.testing.assert_allclose(
        float(stats1["loss"]), float(stats2["loss"]), rtol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(p1["trainable"]["v_head"]["w2"]),
        np.asarray(p2["trainable"]["v_head"]["w2"]),
        rtol=2e-3, atol=2e-5,
    )


def test_pp_trainer_full_loop_runs(devices):
    """make_experience + learn() under a pp mesh: rollout scoring and the
    update both route the frozen trunk through the GPipe op."""
    config, trainer = _pp_trainer({"pp": 2, "dp": 2, "fsdp": 2})
    config.train.total_steps = 4
    config.train.epochs = 2
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    orch.make_experience(config.method.num_rollouts)
    trainer.learn(log_fn=lambda s: None)
    assert trainer.iter_count == 4


def test_pp_rejects_uneven_stage_split(devices):
    with pytest.raises(ValueError, match="stages"):
        _pp_trainer({"pp": 2, "dp": 2, "fsdp": 2}, n_layer=2)


def test_pp_rejects_sp_combination(devices):
    config = make_config(num_layers_unfrozen=1)
    config.model.model_spec["n_layer"] = 3
    config.train.mesh = {"pp": 2, "sp": 2, "dp": 2}
    with pytest.raises(ValueError, match="sp"):
        get_model(config.model.model_type)(config)


def test_relayout_for_decode_is_noop_on_cpu(devices):
    """On the CPU backend relayout_for_decode must return the tree
    UNTOUCHED — CPU accepts custom layouts but mishandles them downstream
    (an Orbax round trip of relayouted params came back with transposed
    values), so the gate is itself the contract under test. The TPU-side
    value-preservation property is exercised on hardware by the 6B bench
    leg (bench_gptj6b_train learns with relayouted params) — it cannot be
    asserted here without the buggy CPU layout path."""
    from trlx_tpu.parallel import relayout_for_decode

    config, trainer = _tiny_trainer()
    wq_before = trainer.params["frozen_base"]["blocks"]["attn"]["wq"]
    after_params = relayout_for_decode(trainer.params)
    # identical OBJECTS: no relayout, no donation, nothing invalidated
    assert after_params["frozen_base"]["blocks"]["attn"]["wq"] is wq_before
    assert after_params["trainable"] is trainer.params["trainable"]
    np.testing.assert_array_equal(
        np.asarray(wq_before),
        np.asarray(after_params["frozen_base"]["blocks"]["attn"]["wq"]),
    )
