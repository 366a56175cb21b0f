"""``correct`` has to be able to come out false.

The control: the plain reference in fp8 (the nearest precision below the
bfloat16 the cells state), put in the program's place at the rehearsal
size, fails the cell's limits. The faults: the whole run is driven (the
harness's look for a chip is skipped by ``--rehearse``) with the timed path
broken underneath, and the result line says ``"correct": false``."""
import json

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.drivers import common
from benchmarks.drivers.ppo_cycle import Cell as PPOCell
from benchmarks.lib import reference as R

PPO, SERVE = "gpt2-xl.ppo-sentiments", "gpt-j-6b.serve-closed16"


def rehearsal(workload):
    cell = bench_run.load("workloads", workload)
    config = bench_run.load("configs", cell["config"])
    mix = bench_run.load("traffic", cell["traffic"])
    return (common.deep_update(cell, cell["rehearse"]), config["rehearse"], common.deep_update(mix, mix["rehearse"]))


def drive(workload, capsys, seed=11):
    assert bench_run.main(["--workload", workload, "--seed", str(seed), "--seconds", "1", "--trace", "0",
                           "--rehearse", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metrics"] == {}  # a rehearsal prints no device metric
    return line


# ------------------------------------------------------------ the control
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_ppo_control_fp8_is_not_correct(seed):
    cell, spec, mix = rehearsal(PPO)
    c = PPOCell({"cell": cell, "spec": spec, "mix": mix, "seed": seed})
    tokens = np.random.default_rng(seed).integers(0, spec["vocab_size"], (mix["batch"], c.P + c.G))
    ref = c._reference(tokens)
    keep = R.moving_leaves(ref["g1"])
    control = c.gaps(c._as_got(c._reference(tokens, mm="fp8")), ref, keep)
    failed = [n for n, v in control.items() if n in cell["correct"] and v > cell["correct"][n]]
    assert failed, control
    same = c.gaps(c._as_got(ref), ref, keep)
    assert all(v == 0 for v in same.values())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fp8_is_not_correct(seed):
    cell, spec, mix = rehearsal(SERVE)
    rng = np.random.default_rng(seed)
    rows = [(rng.integers(1, 500, 24).tolist(), rng.integers(1, 500, 16).tolist()) for _ in range(8)]
    _, control = R.served_gaps(spec, seed, rows, common.DTYPES["bfloat16"], control="fp8")
    assert control.max() > cell["correct"]["served_gap_max"]


# ------------------------------------------------------------ the faults, through the whole run
def test_sound_runs_are_correct(capsys):
    assert drive(PPO, capsys)["correct"] is True
    assert drive(SERVE, capsys)["correct"] is True


def break_train_step(monkeypatch, broken):
    from trlx_tpu.trainers.ppo_trainer import JaxPPOTrainer

    build = JaxPPOTrainer._build_jitted_fns

    def patched(self):
        build(self)
        self._train_multi_indexed = broken(self._train_multi_indexed)

    monkeypatch.setattr(JaxPPOTrainer, "_build_jitted_fns", patched)


def test_fault_step_returns_state_unchanged(monkeypatch, capsys):
    def broken(step):
        def run(params, opt_state, store, idx):
            import jax
            import jax.numpy as jnp

            copies = jax.tree_util.tree_map(jnp.copy, (params, opt_state))  # the step donates what it is given
            _, _, stats = step(*copies, store, idx)  # the step ran; its state is thrown away
            return params, opt_state, stats
        return run

    break_train_step(monkeypatch, broken)
    line = drive(PPO, capsys)
    assert line["correct"] is False and not line["compared"]["delta_leaf"]["ok"]


def test_fault_half_of_the_batch_left_out(monkeypatch, capsys):
    def broken(step):
        return lambda params, opt_state, store, idx: step(params, opt_state, store, idx[: len(idx) // 2])

    break_train_step(monkeypatch, broken)
    line = drive(PPO, capsys)
    assert line["correct"] is False and not line["compared"]["loss_last"]["ok"]


def test_fault_token_altered_in_the_rollout(monkeypatch, capsys):
    from trlx_tpu.trainers.ppo_trainer import JaxPPOTrainer

    rollout = JaxPPOTrainer.rollout

    def altered(self, *a):
        out, *rest = rollout(self, *a)
        tokens = out.gen_tokens.at[:, 2].add(1) % self.policy.spec.vocab_size
        return (out._replace(gen_tokens=tokens), *rest)  # stored tokens are not the ones that were scored

    monkeypatch.setattr(JaxPPOTrainer, "rollout", altered)
    line = drive(PPO, capsys)
    assert line["correct"] is False and not line["compared"]["roll_logp"]["ok"]


def test_fault_served_token_altered(monkeypatch, capsys):
    from trlx_tpu.serve.slots import SlotPoolRuntime

    step = SlotPoolRuntime.step

    def altered(self, seed):
        tok, emitted, finished = step(self, seed)
        # every slot hands out another token than it decoded (one slot alone can miss the sample of answers checked)
        return (np.array(tok) + 7) % self.engine.spec.vocab_size, emitted, finished

    monkeypatch.setattr(SlotPoolRuntime, "step", altered)
    line = drive(SERVE, capsys)
    assert line["correct"] is False and not line["compared"]["served_gap_max"]["ok"]
