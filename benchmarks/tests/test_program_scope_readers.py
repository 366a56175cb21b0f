"""``readers/program_scope_ms.py`` on a hand-made trace, with the program's own
op-to-scope map registered as the program registers it
(``trlx_tpu.telemetry.programs``): the data files' own patterns, the division
per run and per decode step, the split in the notes, and the nothing-to-read
cases (no trace, no map, a program older than the record)."""
import builtins
import json
import os

import pytest

from benchmarks.readers import program_scope_ms
from trlx_tpu.telemetry import programs

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
MS = 1_000_000  # ns
G = 4  # the mix's gen_tokens
NEW = {"gpt2-xl.ppo-sentiments": ["rollout_score_s.train", "update_trunk_s.train", "update_top_s.train",
                                  "decode_mlp_ms_per_step.train", "decode_attn_ms_per_step.train",
                                  "decode_head_ms_per_step.train"],
       "gpt-j-6b.serve-closed16": ["attn_ms_per_step.serve", "mlp_ms_per_step.serve", "head_ms_per_step.serve"]}

ROLL, STEP = "jit(rollout_fn)/rollout/generate", "jit(rollout_fn)/rollout/generate/decode/while/body/closed_call"
UPD = "jit(train_multi_indexed)/while/body/closed_call"
# (instruction, op_name or None for no metadata, ms a run)
PROGRAMS = {
    "jit_rollout_fn": [
        ("gather.1", "jit(rollout_fn)/gather", 1),  # the prompts' gather: no part
        ("fusion.2", f"{ROLL}/prefill/attn/dot_general", 20),
        ("fusion.3", f"{ROLL}/prefill/mlp/dot_general", 30),
        ("fusion.4", f"{STEP}/sample/reduce_max", 4),
        ("fusion.5", f"{STEP}/attn/dot_general", 40),
        ("copy.6", None, 8),  # a copy the compiler made: the scope before it
        ("fusion.7", f"{STEP}/kv_write/dynamic-update-slice", 4),
        ("fusion.8", f"{STEP}/mlp/dot_general", 60),
        ("fusion.9", f"{STEP}/add", 4),  # the step's own bookkeeping: decode, no part
        ("fusion.10", f"{STEP}/head/dot_general", 12),
        ("fusion.11", "jit(rollout_fn)/rollout/score/trunk/while/body/mlp/dot_general", 100),
        ("fusion.12", "jit(rollout_fn)/rollout/score/top/mlp/dot_general", 10),
        ("fusion.13", "jit(rollout_fn)/rollout/score/logprobs/reduce_max", 5),
    ],
    "jit_train_multi_indexed": [
        ("fusion.1", "jit(train_multi_indexed)/gather", 1),
        ("fusion.2", "jit(train_multi_indexed)/update/trunk/while/body/mlp/dot_general", 170),
        ("fusion.3", f"{UPD}/jvp(update/top)/mlp/dot_general", 40),
        ("fusion.4", f"{UPD}/transpose(jvp(update/top))/mlp/dot_general", 80),
        ("fusion.5", f"{UPD}/jvp(update/loss)/reduce_max", 40),
        ("fusion.6", f"{UPD}/update/opt/add", 10),
    ],
    "jit_run_decode_step": [
        ("fusion.1", "jit(run_decode_step)/layer0/attn/dot_general", 4),
        ("fusion.2", "jit(run_decode_step)/layer0/kv_write/scatter", 1),
        ("fusion.3", "jit(run_decode_step)/layer0/kv_read/gather", 3),
        ("fusion.4", "jit(run_decode_step)/layer0/mlp/dot_general", 10),
        ("fusion.5", "jit(run_decode_step)/layer1/attn/dot_general", 3),
        ("fusion.6", "jit(run_decode_step)/layer1/mlp/dot_general", 9),
        ("fusion.7", "jit(run_decode_step)/head/dot_general", 2),
        ("fusion.8", "jit(run_decode_step)/add", 1),
    ],
}


def hlo_text(module):
    lines = [f"HloModule {module}, is_scheduled=true", "", "ENTRY %main.1 () -> f32[] {"]
    for name, op_name, _ in PROGRAMS[module]:
        meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
        lines.append(f"  %{name} = f32[4]{{0}} fusion(){meta}")
    return "\n".join(lines + ["}"])


def trace():
    """Each program run twice; the decode step's ops run again in a prefill between, which the reader leaves out."""
    modules, ops, t = [], [], 0
    for _ in range(2):
        for module, listing in PROGRAMS.items():
            start = t
            ops.append(("%while.0 = x", t, sum(d for *_, d in listing) * MS))  # a loop: its body's ops again
            for name, _, d in listing:
                ops.append((f"%{name} = x", t, d * MS))
                t += d * MS
            modules.append((f"{module}(7)", start, t - start))
            t += MS
        modules.append(("jit_run_prefill_b1p16(8)", t, 5 * MS))
        ops.append(("%fusion.4 = x", t, 5 * MS))
        t += 6 * MS
    return {"devices": {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops}}, "host": []}


@pytest.fixture
def registered():
    for module in PROGRAMS:
        programs.register(module, lambda module=module: hlo_text(module))
    yield
    for module in PROGRAMS:
        programs.register(module, lambda: "")


def ctx(**kw):
    return {"trace": trace(), "mix": {"gen_tokens": G}, "notes": {}, **kw}


def spec(name):
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def read(name, c):
    return program_scope_ms.read(c, **spec(name)["args"])


def test_the_rollout_by_part_per_run(registered):
    c = ctx()
    assert read("rollout_score_s.train", c) == pytest.approx(0.115)  # trunk + top + logprobs, s a run
    assert c["notes"]["rollout_ms.train"] == pytest.approx(
        {"trunk": 100.0, "top": 10.0, "logprobs": 5.0, "prefill": 50.0, "decode": 132.0, "other_ms": 1.0, "runs": 2})
    assert set(c["notes"]["op_scopes_s"]) == {"jit_rollout_fn"}


def test_the_decode_loop_by_part_per_step_with_its_remainder(registered):
    c = ctx()
    steps = 2 * G
    assert read("decode_mlp_ms_per_step.train", c) == pytest.approx(60 / G)
    assert read("decode_attn_ms_per_step.train", c) == pytest.approx((40 + 8 + 4) / G)  # the copy inherits attn
    assert read("decode_head_ms_per_step.train", c) == pytest.approx((12 + 4) / G)
    split = c["notes"]["decode_ms_per_step.train"]
    assert split == pytest.approx({"mlp": 15.0, "attn": 12.0, "kv_write": 1.0, "head": 3.0, "sample": 1.0,
                                   "other_ms": 1.0, "decode_steps": steps})
    # the three metrics and the remainder are the decode loop's time a step, whichever read first
    assert sum(v for k, v in split.items() if k != "decode_steps") == pytest.approx(132 / G)
    again = ctx()
    for name in ("decode_head_ms_per_step.train", "decode_attn_ms_per_step.train", "decode_mlp_ms_per_step.train"):
        read(name, again)
    assert again["notes"]["decode_ms_per_step.train"] == pytest.approx(split)


def test_the_update_by_part_forward_and_backward(registered):
    c = ctx()
    assert read("update_trunk_s.train", c) == pytest.approx(0.170)
    assert read("update_top_s.train", c) == pytest.approx(0.170)  # top forward + backward, loss, opt
    assert c["notes"]["update_ms.train"] == pytest.approx(
        {"trunk": 170.0, "top": 120.0, "loss": 40.0, "opt": 10.0, "other_ms": 1.0, "runs": 2})


def test_the_serve_step_by_part_and_what_is_left(registered):
    c = ctx()
    assert read("attn_ms_per_step.serve", c) == pytest.approx(4 + 1 + 3 + 3)
    assert read("mlp_ms_per_step.serve", c) == pytest.approx(19)
    assert read("head_ms_per_step.serve", c) == pytest.approx(2)
    split = c["notes"]["decode_ms_per_step.serve"]
    assert split == pytest.approx({"attn": 7.0, "kv_write": 1.0, "kv_read": 3.0, "mlp": 19.0, "head": 2.0,
                                   "other_ms": 1.0, "runs": 2})


def test_nothing_to_read(registered, monkeypatch):
    assert read("mlp_ms_per_step.serve", ctx(trace=None)) is None
    programs.register("jit_run_decode_step", lambda: "HloModule jit_run_decode_step\n")  # a map with no scope
    assert read("mlp_ms_per_step.serve", ctx()) is None
    assert program_scope_ms.read(ctx(), module="jit_never_compiled", pattern="(mlp)") is None
    assert program_scope_ms.read(ctx(), module="jit_rollout_fn", pattern="/(nowhere)/") is None
    real_import = builtins.__import__

    def older_program(name, *args, **kw):  # a program without telemetry.programs
        if name == "trlx_tpu.telemetry" and "programs" in (args[2] if len(args) > 2 else kw.get("fromlist") or ()):
            raise ImportError(name)
        return real_import(name, *args, **kw)

    monkeypatch.setattr(builtins, "__import__", older_program)
    assert read("rollout_score_s.train", ctx()) is None


@pytest.mark.parametrize("name", [n for names in NEW.values() for n in names])
def test_each_metric_file_matches_its_entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    f = spec(name)
    assert {k: f[k] for k in entry} == entry
    assert f["reader"] == "program_scope_ms" and f["source"] == "device_trace"
    cell = next(c for c, names in NEW.items() if name in names)
    assert entry["workloads"] == [cell]
    assert entry["moves"] == ("ppo_samples_per_s" if cell.startswith("gpt2-xl") else "serve_tokens_per_s")
