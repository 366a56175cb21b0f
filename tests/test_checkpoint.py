"""Checkpoint/resume tests: save -> restore -> next step must be identical.

The reference's checkpointing is dead code (declared intervals, save never
called, exceptions swallowed — SURVEY §3.6); here resume is a real feature
and this is its contract test.
"""

import numpy as np
import pytest

from tests.test_ppo_e2e import PROMPTS, make_config, reward_fn
from trlx_tpu.utils.loading import get_model, get_orchestrator, get_pipeline
from trlx_tpu.utils.tokenizer import ByteTokenizer


def _built_trainer(tmp_path, seed=0):
    config = make_config(total_steps=8, epochs=2, num_rollouts=16,
                         chunk_size=16, batch_size=16, ppo_epochs=1)
    config.train.seed = seed
    config.train.checkpoint_dir = str(tmp_path / "ckpt")
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    pipeline = get_pipeline(config.train.pipeline)(
        PROMPTS, trainer.tokenizer, config
    )
    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    return config, trainer, orch


def _leaves(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def test_ppo_save_restore_next_step_identical(tmp_path):
    """Train 2 steps, checkpoint, train 2 more; a fresh trainer restoring
    the checkpoint must reproduce the last 2 steps bit-for-bit (params,
    opt state, RNG stream, KL coefficient)."""
    config, trainer, orch = _built_trainer(tmp_path)
    orch.make_experience(config.method.num_rollouts)

    batch = next(iter(trainer.store.create_loader(16, shuffle=False)))
    batch = trainer._put(batch)
    for _ in range(2):
        trainer.params, trainer.opt_state, _ = trainer._train_step(
            trainer.params, trainer.opt_state, batch
        )
    trainer.iter_count = 2
    trainer.kl_ctl.value = 0.123
    trainer.save()

    for _ in range(2):
        trainer.params, trainer.opt_state, _ = trainer._train_step(
            trainer.params, trainer.opt_state, batch
        )
    rng_after = trainer.next_rng()

    # fresh trainer from a different seed: every piece must come from the
    # checkpoint, not construction
    config2, resumed, _ = _built_trainer(tmp_path, seed=7)
    resumed.load(config.train.checkpoint_dir)
    assert resumed.iter_count == 2
    assert resumed.kl_ctl.value == pytest.approx(0.123)
    for _ in range(2):
        resumed.params, resumed.opt_state, _ = resumed._train_step(
            resumed.params, resumed.opt_state, batch
        )
    rng_after2 = resumed.next_rng()

    import jax

    rng_after = jax.random.key_data(rng_after)
    rng_after2 = jax.random.key_data(rng_after2)

    for a, b in zip(_leaves(trainer.params), _leaves(resumed.params)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_leaves(trainer.opt_state), _leaves(resumed.opt_state)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(rng_after), np.asarray(rng_after2))


def test_restore_missing_checkpoint_raises(tmp_path):
    config, trainer, _ = _built_trainer(tmp_path)
    with pytest.raises(FileNotFoundError):
        trainer.load(str(tmp_path / "nope"))


def test_pretrained_load_failure_raises_not_silently_randomizes(tmp_path):
    """A bad model_path must fail loudly, not train a from-scratch model
    (the round-1 behavior silently swallowed it)."""
    config = make_config()
    config.model.model_spec = None
    config.model.model_path = "definitely/not-a-real-checkpoint"
    with pytest.raises(RuntimeError, match="could not load pretrained"):
        get_model(config.model.model_type)(config)


def test_sharded_save_restore_preserves_shardings(devices, tmp_path):
    """Save a mesh-sharded trainer, restore into a fresh trainer on the
    same mesh: values identical AND arrays land sharded on the mesh (not
    replicated host arrays), including onto a different topology."""
    from jax.sharding import PartitionSpec as P

    from tests.test_ppo_e2e import make_config
    from trlx_tpu.utils.checkpoint import restore_components, save_components
    from trlx_tpu.utils.loading import get_model
    from trlx_tpu.utils.tokenizer import ByteTokenizer

    config = make_config(total_steps=1, epochs=1)
    config.train.mesh = {"dp": 2, "fsdp": 2, "tp": 2}
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    save_components(trainer.get_components(), str(tmp_path / "ck"))

    config2 = make_config(total_steps=1, epochs=1)
    config2.train.mesh = {"dp": 1, "fsdp": 4, "tp": 2}  # different topology
    config2.train.seed = 1  # different init, so value equality below can
    # only come from actually reading the checkpoint
    trainer2 = get_model(config2.model.model_type)(config2)
    trainer2.tokenizer = ByteTokenizer()
    restored = restore_components(
        trainer2.get_components(), str(tmp_path / "ck")
    )
    trainer2.set_components(restored)

    wq = trainer2.params["trainable"]["blocks"]["attn"]["wq"]
    assert wq.sharding.spec == P(None, "fsdp", "tp")
    assert wq.sharding.mesh.shape["fsdp"] == 4  # the NEW topology
    np.testing.assert_array_equal(
        np.asarray(wq),
        np.asarray(trainer.params["trainable"]["blocks"]["attn"]["wq"]),
    )


def test_resume_from_kill_and_continue(tmp_path):
    """A run killed mid-training continues from its checkpoint via
    config.train.resume_from: the resumed learn() must pick up iter_count /
    params / KL state from disk (not construction) and run to total_steps."""
    # run 1: train 4 steps with checkpointing every 2, then "die"
    config, trainer, orch = _built_trainer(tmp_path)
    config.train.checkpoint_interval = 2
    config.train.total_steps = 4
    config.train.epochs = 100  # bound the run by total_steps, not epochs
    orch.make_experience(config.method.num_rollouts)
    trainer.learn(log_fn=lambda s: None)
    assert trainer.iter_count == 4
    saved_kl = trainer.kl_ctl.value

    # run 2: fresh process-equivalent (different seed), resume_from set
    config2, resumed, orch2 = _built_trainer(tmp_path, seed=9)
    config2.train.resume_from = config.train.checkpoint_dir
    config2.train.checkpoint_interval = 10**9
    config2.train.total_steps = 8
    config2.train.epochs = 100
    orch2.make_experience(config2.method.num_rollouts)
    resumed.learn(log_fn=lambda s: None)

    # resumed from step 4 (not 0): exactly 4 more steps to total_steps=8
    assert resumed.iter_count == 8
    assert resumed._resumed
    # resume restored the checkpointed KL controller, then kept updating it
    # from live rollouts; construction default would be init_kl_coef
    saved_state = resumed.get_components()["state"]
    assert saved_state["iter_count"] == 8

    # a second learn() must NOT re-restore (resume is once per process)
    resumed.config.train.total_steps = 12
    orch2.make_experience(config2.method.num_rollouts)
    resumed.learn(log_fn=lambda s: None)
    assert resumed.iter_count == 12


def test_save_restore_preserves_mixed_param_dtypes(tmp_path):
    """param_dtype=bfloat16 stores the frozen trunk/ref narrow while the
    trainable branch stays fp32; a checkpoint round-trip must restore the
    exact mixed-dtype layout and values."""
    import jax
    import jax.numpy as jnp

    def bf16_config(seed):
        config = make_config(total_steps=8, epochs=2, num_rollouts=16,
                             chunk_size=16, batch_size=16, ppo_epochs=1)
        config.train.seed = seed
        config.train.checkpoint_dir = str(tmp_path / "ckpt")
        config.model.param_dtype = "bfloat16"
        return config

    config = bf16_config(0)
    trainer = get_model(config.model.model_type)(config)
    trainer.tokenizer = ByteTokenizer()
    trainer.save()

    resumed = get_model(config.model.model_type)(bf16_config(3))
    resumed.tokenizer = ByteTokenizer()
    resumed.load(config.train.checkpoint_dir)

    for part, want in (("frozen_base", jnp.bfloat16),
                       ("ref", jnp.bfloat16),
                       ("trainable", jnp.float32)):
        leaves = jax.tree_util.tree_leaves(resumed.params[part])
        assert all(x.dtype == want for x in leaves), part
        for a, b in zip(jax.tree_util.tree_leaves(trainer.params[part]),
                        leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

def test_sigterm_preemption_saves_and_resumes(tmp_path):
    """SIGTERM mid-learn() must checkpoint at the next step boundary and
    return cleanly (no death, handler restored); a fresh trainer with
    resume_from must restore that checkpoint bit-exact and finish the run
    (the preemptible-VM / node-drain story — trlx_tpu.utils.preemption)."""
    import os
    import signal

    prev_handler = signal.getsignal(signal.SIGTERM)
    config, trainer, orch = _built_trainer(tmp_path)
    config.train.epochs = 100
    config.train.total_steps = 8
    config.train.checkpoint_interval = 10**9  # only the preemption save
    config.train.log_interval = 1
    orch.make_experience(config.method.num_rollouts)

    logs = []
    sent = []

    def log_fn(stats):
        logs.append(stats)
        # "kill" the run right after the 2nd optimizer step's log line
        # (one step per epoch here, so a rollout refresh sits in between)
        if stats.get("iter") == 2 and "loss" in stats and not sent:
            sent.append(1)
            os.kill(os.getpid(), signal.SIGTERM)

    trainer.learn(log_fn=log_fn)  # returns instead of dying
    assert sent, "kill point never reached"
    assert trainer.iter_count == 2
    assert any(s.get("preempted") for s in logs)
    # the trap is scoped to learn(): previous handler back in place
    assert signal.getsignal(signal.SIGTERM) is prev_handler

    saved = _leaves(trainer.params["trainable"])

    # fresh "process" (different seed) resumes from the preemption save
    config2, resumed, orch2 = _built_trainer(tmp_path, seed=9)
    config2.train.resume_from = config.train.checkpoint_dir
    config2.train.epochs = 100
    config2.train.total_steps = 8
    config2.train.checkpoint_interval = 10**9
    # _built_trainer constructed before resume_from was set; restore now
    # (a real run sets resume_from in the config and restores at
    # construction — test_resume_from_kill_and_continue covers that)
    assert resumed.maybe_resume()
    for a, b in zip(saved, _leaves(resumed.params["trainable"])):
        np.testing.assert_array_equal(a, b)

    orch2.make_experience(config2.method.num_rollouts)
    resumed.learn(log_fn=lambda s: None)
    assert resumed.iter_count == 8


def test_resume_from_auto_with_retention_end_to_end(tmp_path):
    """The fire-and-forget preemptible-job config: resume_from "auto" +
    keep_checkpoints. Run 1 saves step checkpoints (only the newest N
    kept); run 2 with the SAME config line resumes from the newest at
    construction; a run pointed at an empty dir starts fresh."""
    import os

    config, trainer, orch = _built_trainer(tmp_path)
    config.train.checkpoint_interval = 2
    config.train.total_steps = 6
    config.train.epochs = 100
    config.train.keep_checkpoints = 2
    orch.make_experience(config.method.num_rollouts)
    trainer.learn(log_fn=lambda s: None)
    assert trainer.iter_count == 6

    # retention: steps 2, 4, 6 were saved; only the newest 2 remain
    steps = sorted(e for e in os.listdir(config.train.checkpoint_dir)
                   if e.startswith("step_"))
    assert steps == ["step_4", "step_6"]

    config2, resumed, orch2 = _built_trainer(tmp_path, seed=5)
    config2.train.resume_from = "auto"
    # construction already consumed resume_from="" — exercise the auto
    # resolution explicitly, as a fresh construction would
    assert resumed.maybe_resume()
    assert resumed.iter_count == 6
    for a, b in zip(_leaves(trainer.params["trainable"]),
                    _leaves(resumed.params["trainable"])):
        np.testing.assert_array_equal(a, b)

    # empty checkpoint_dir + auto = fresh start, not an error
    config3, fresh, _ = _built_trainer(tmp_path / "elsewhere", seed=3)
    config3.train.resume_from = "auto"
    assert not fresh.maybe_resume()
    assert fresh.iter_count == 0


def test_preemption_guard_disabled_by_config(tmp_path):
    """train.save_on_preemption=false keeps the default SIGTERM behavior:
    the guard never installs a handler during learn()."""
    import signal

    from trlx_tpu.utils.preemption import PreemptionGuard

    prev = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard(enabled=False) as guard:
        assert signal.getsignal(signal.SIGTERM) is prev
        assert not guard.requested


def test_preemption_poll_interval_skips_collectives(monkeypatch):
    """Multi-process poll() runs its allgather only every poll_interval-th
    call (ADVICE r04: a per-step collective plus host sync stalls a
    small model's step pipeline). Between collective boundaries it returns
    False even with the local flag set — a rank acting on local state alone
    would exit mid-collective and deadlock the survivors."""
    import numpy as np

    from trlx_tpu.utils.preemption import PreemptionGuard

    calls = {"allgather": 0}

    def fake_allgather(x):
        calls["allgather"] += 1
        return np.stack([np.asarray(x), np.asarray([1.0], np.float32)])

    import jax
    from jax.experimental import multihost_utils

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(
        multihost_utils, "process_allgather", fake_allgather
    )

    guard = PreemptionGuard(poll_interval=4)
    guard.requested = True
    # call 1 is a collective boundary (fires, sees the remote flag);
    # calls 2-4 are skipped entirely; call 5 fires again
    results = [guard.poll() for _ in range(5)]
    assert results == [True, False, False, False, True]
    assert calls["allgather"] == 2


def test_preemption_guard_off_main_thread_stays_inert():
    """Python only allows signal handlers on the main thread; a guard
    constructed/entered anywhere else must stay inert (no handler change,
    no exception) rather than crashing a worker-thread learn() call."""
    import signal
    import threading

    from trlx_tpu.utils.preemption import PreemptionGuard

    prev = signal.getsignal(signal.SIGTERM)
    results = {}

    def run():
        with PreemptionGuard() as guard:
            results["installed"] = guard._installed
            results["poll"] = guard.poll()

    t = threading.Thread(target=run)
    t.start()
    t.join()
    assert results == {"installed": False, "poll": False}
    assert signal.getsignal(signal.SIGTERM) is prev


def test_preemption_poll_interval_boundaries(monkeypatch):
    """Rank-agreement arithmetic at the interval edges: calls 1, N+1,
    2N+1 are the collective boundaries ((polls - 1) % N == 0) — call N
    itself is NOT one, and poll_interval=1 makes every call collective.
    All ranks count calls identically, so they agree on which boundaries
    run the allgather."""
    import numpy as np

    import jax
    from jax.experimental import multihost_utils

    from trlx_tpu.utils.preemption import PreemptionGuard

    calls = {"allgather": 0}

    def fake_allgather(x):
        calls["allgather"] += 1
        return np.stack([np.asarray(x), np.asarray([0.0], np.float32)])

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather", fake_allgather)

    guard = PreemptionGuard(poll_interval=3)
    boundaries = []
    for i in range(1, 8):
        before = calls["allgather"]
        guard.poll()
        if calls["allgather"] > before:
            boundaries.append(i)
    assert boundaries == [1, 4, 7]

    calls["allgather"] = 0
    every = PreemptionGuard(poll_interval=1)
    for _ in range(5):
        every.poll()
    assert calls["allgather"] == 5

    # sub-1 intervals clamp to 1 rather than dividing by zero
    assert PreemptionGuard(poll_interval=0)._poll_interval == 1


@pytest.fixture(scope="module")
def pristine_checkpoint(tmp_path_factory):
    """ONE real orbax-backed checkpoint of a tiny array tree, shared by
    every integrity test below — each copies it (copytree is ~free; an
    orbax save is seconds on 1 CPU) and corrupts the COPY. Returns
    (path, components). tests/test_defense.py covers the same machinery
    on hand-built dirs without orbax."""
    from trlx_tpu.utils.checkpoint import save_components

    components = {
        "params": {"w": np.arange(64, dtype=np.float32).reshape(8, 8),
                   "b": np.ones((8,), np.float32)},
    }
    directory = str(tmp_path_factory.mktemp("integrity") / "pristine")
    save_components(components, directory)
    return directory, components


def _integrity_copy(pristine, destination):
    import shutil

    shutil.copytree(pristine[0], destination)
    return destination


def _template():
    return {"params": {"w": np.zeros((8, 8), np.float32),
                       "b": np.zeros((8,), np.float32)}}


def _largest_file(directory):
    """The biggest non-marker file under the checkpoint — the orbax
    array data (meta.json is excluded: in a tiny checkpoint the
    embedded manifest makes IT the largest file, and the torn-marker
    path has its own test)."""
    import os

    best, size = None, -1
    for root, _, files in os.walk(directory):
        for fname in files:
            if fname == "meta.json":
                continue
            path = os.path.join(root, fname)
            if os.path.getsize(path) > size:
                best, size = path, os.path.getsize(path)
    return best


def test_restore_detects_bitflipped_orbax_array_file(
        tmp_path, pristine_checkpoint):
    """A single flipped byte in the orbax-written array data must raise
    the typed CheckpointCorrupt (and quarantine the dir) instead of
    restoring wrong-but-finite weights silently."""
    import os

    from trlx_tpu import telemetry
    from trlx_tpu.utils.checkpoint import CheckpointCorrupt, restore_components

    telemetry.start()
    ck = _integrity_copy(pristine_checkpoint, str(tmp_path / "ck"))
    target = _largest_file(ck)
    with open(target, "r+b") as f:
        f.seek(os.path.getsize(target) // 2)
        byte = f.read(1)
        f.seek(os.path.getsize(target) // 2)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(CheckpointCorrupt, match="hash mismatch"):
        restore_components(_template(), ck)
    assert not os.path.isdir(ck), "corrupt checkpoint must be quarantined"
    assert telemetry.current().registry.counters[
        "checkpoint/quarantined"] == 1.0


def test_restore_detects_truncated_array_and_torn_meta(
        tmp_path, pristine_checkpoint):
    import os

    from trlx_tpu import telemetry
    from trlx_tpu.utils.checkpoint import (
        META_NAME,
        CheckpointCorrupt,
        restore_components,
    )

    telemetry.start()
    ck = _integrity_copy(pristine_checkpoint, str(tmp_path / "ck"))
    target = _largest_file(ck)
    with open(target, "r+b") as f:
        f.truncate(max(os.path.getsize(target) // 2, 1))
    with pytest.raises(CheckpointCorrupt, match="truncated"):
        restore_components(_template(), ck)

    ck2 = _integrity_copy(pristine_checkpoint, str(tmp_path / "ck2"))
    with open(os.path.join(ck2, META_NAME), "w") as f:
        f.write('{"params": {"w"')  # torn mid-json.dump
    with pytest.raises(CheckpointCorrupt, match="commit marker"):
        restore_components(_template(), ck2)


def test_run_dir_restore_falls_back_past_corrupt_step(
        tmp_path, pristine_checkpoint):
    """Auto-resume degrades to last-known-good: the newest step's bytes
    are corrupt, so restore quarantines it and loads the previous
    committed step instead of failing the run."""
    import os

    from trlx_tpu import telemetry
    from trlx_tpu.utils.checkpoint import (
        find_latest_checkpoint,
        restore_components,
    )

    telemetry.start()
    run = str(tmp_path / "run")
    os.makedirs(run)
    good = pristine_checkpoint[1]
    _integrity_copy(pristine_checkpoint, os.path.join(run, "step_1"))
    _integrity_copy(pristine_checkpoint, os.path.join(run, "step_2"))
    target = _largest_file(os.path.join(run, "step_2"))
    with open(target, "r+b") as f:
        f.seek(0)
        byte = f.read(1)
        f.seek(0)
        f.write(bytes([byte[0] ^ 0xFF]))

    restored = restore_components(_template(), run)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.asarray(good["params"]["w"])
    )
    registry = telemetry.current().registry
    assert registry.counters["checkpoint/quarantined"] == 1.0
    assert registry.counters["checkpoint/verified"] >= 1.0
    assert any(".corrupt-" in e for e in os.listdir(run)), (
        "the corrupt step must survive as quarantined evidence"
    )
    latest = find_latest_checkpoint(run)
    assert latest and latest.endswith("step_1")


def test_premanifest_checkpoint_restores_with_verify_skipped(
        tmp_path, pristine_checkpoint):
    """Checkpoints written before the manifest existed restore as
    before (backward compatibility) — counted, not rejected."""
    import json
    import os

    from trlx_tpu import telemetry
    from trlx_tpu.utils.checkpoint import MANIFEST_KEY, META_NAME, restore_components

    telemetry.start()
    ck = _integrity_copy(pristine_checkpoint, str(tmp_path / "ck"))
    saved = pristine_checkpoint[1]
    meta_path = os.path.join(ck, META_NAME)
    with open(meta_path) as f:
        meta = json.load(f)
    meta.pop(MANIFEST_KEY)
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    restored = restore_components(_template(), ck)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]),
        np.asarray(saved["params"]["w"]),
    )
    assert telemetry.current().registry.counters[
        "checkpoint/verify_skipped"] == 1.0


def test_preemption_guard_restores_sig_dfl_for_c_handlers(monkeypatch):
    """When the previous SIGTERM handler was installed at the C level
    (getsignal() -> None), __exit__ restores SIG_DFL rather than leaving
    the guard's recording handler live (ADVICE r04: a swallowed SIGTERM
    after learn() returns makes the process undrainable)."""
    import signal

    from trlx_tpu.utils.preemption import PreemptionGuard

    real_getsignal = signal.getsignal
    monkeypatch.setattr(signal, "getsignal", lambda sig: None)
    try:
        with PreemptionGuard():
            pass
        monkeypatch.setattr(signal, "getsignal", real_getsignal)
        assert signal.getsignal(signal.SIGTERM) is signal.SIG_DFL
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
