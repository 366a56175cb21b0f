"""The process's record of its named device programs
(``trlx_tpu.telemetry.programs``): where a program compiles it registers
under the module name a profile gives it, a request reads the op-to-scope
map off the very executable the dispatches ran and compiles nothing, the
record outlives the program's owner, and the PPO trainer's two programs
carry the scopes the benchmark's readers split them by."""

import gc
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_ppo_e2e import build
from trlx_tpu.telemetry import programs
from trlx_tpu.utils.aotjit import aot_jit
from trlx_tpu.utils.hlo_text import op_names

_COMPILES = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **kw: _COMPILES.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None
)

WRAPPERS = {"jit": programs.jit, "aot_jit": aot_jit}


def scoped_step(w, x, scale):
    with jax.named_scope("outer"):
        with jax.named_scope("inner"):
            y = jnp.tanh(x @ w)
        return y * scale, {"sum": y.sum()}


def arguments(kind):
    """Every kind of leaf a trainer hands its programs: uncommitted and
    committed device arrays, host arrays, a Python scalar (weak type)."""
    w = jnp.ones((8, 8), jnp.float32)
    x = np.ones((4, 8), np.float32)
    if kind == "committed":
        w = jax.device_put(w, jax.devices()[0])
        x = jax.device_put(x, jax.devices()[0])
    elif kind == "device":
        x = jnp.asarray(x)
    return w, x, 0.5


@pytest.mark.parametrize("wrap", list(WRAPPERS))
def test_a_program_registers_under_its_module_name_when_it_compiles(wrap):
    def step(w, x, scale):
        return scoped_step(w, x, scale)

    step.__name__ = module = f"scoped_{wrap}"
    fn = WRAPPERS[wrap](step)
    assert programs.op_scopes(f"jit_{module}") is None  # nothing compiled yet
    fn(*arguments("device"))
    text = fn.lower(*arguments("device")).compile().as_text()
    assert text.split()[1].rstrip(",") == f"jit_{module}"  # the profile's name
    scopes = programs.op_scopes(f"jit_{module}")
    assert any(v.startswith(f"jit({module})/outer/inner/") for v in scopes.values())
    assert scopes == op_names(text)


@pytest.mark.parametrize("kind", ["device", "committed", "host"])
@pytest.mark.parametrize("wrap", list(WRAPPERS))
def test_asking_for_a_map_compiles_nothing_after_the_owner_is_gone(wrap, kind):
    fn = WRAPPERS[wrap](scoped_step)
    fn(*arguments(kind))
    fn(*arguments(kind))
    del fn
    gc.collect()
    before = len(_COMPILES)
    scopes = programs.op_scopes("jit_scoped_step")
    assert len(_COMPILES) == before
    assert any("outer/inner" in v for v in scopes.values())
    assert programs.op_scopes("jit_scoped_step") is scopes  # read once, kept


def test_module_names_follow_jax():
    import functools

    def run_prefill_b4p128():
        return jnp.zeros(())

    named = functools.partial(run_prefill_b4p128)
    assert programs.module_name(run_prefill_b4p128) == "jit_run_prefill_b4p128"
    assert programs.module_name(named) == "jit_run_prefill_b4p128"
    anonymous = lambda: jnp.zeros(())  # noqa: E731
    assert programs.module_name(anonymous) == "jit__lambda"
    assert jax.jit(anonymous).lower().compile().as_text().startswith("HloModule jit__lambda,")
    assert programs.op_scopes("jit_never_compiled_here") is None


def test_op_names_reads_every_instruction_that_carries_one():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %tanh.1 = f32[4]{0} tanh(%p), metadata={op_name="jit(f)/sc/tanh"}
}

ENTRY %main.3 (x: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %copy.2 = f32[4]{0} copy(%x.1)
  ROOT %fusion.7 = f32[4]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/sc/mul" source_file="f.py" source_line=3}
}
"""
    assert op_names(text) == {"tanh.1": "jit(f)/sc/tanh", "x.1": "x", "fusion.7": "jit(f)/sc/mul"}


# ------------------------------------------------------------ the PPO trainer
@pytest.fixture(scope="module")
def trainer():
    config, trainer, pipeline, orch = build()
    return trainer, orch


TRAINER_PROGRAMS = ["_rollout_fn", "_generate_fn", "_train_step", "_train_multi", "_train_multi_indexed"]


@pytest.mark.parametrize("name", TRAINER_PROGRAMS)
def test_a_trainers_program_keeps_the_jit_surface(trainer, name):
    """What the benchmark's ``jit_cache_growth`` check and the tests read:
    ``_cache_size``, ``lower``, ``trace``."""
    program = getattr(trainer[0], name)
    assert isinstance(program, programs.RecordedJit)
    assert isinstance(program._cache_size(), int)
    assert callable(program.lower) and callable(program.trace)


@pytest.fixture(scope="module")
def maps(trainer):
    """The two programs of one PPO cycle, read after the trainer is gone."""
    tr, orch = trainer
    orch.make_experience(tr.config.method.num_rollouts)
    assert tr._rollout_fn._cache_size() == 1
    tr.config.train.total_steps = 1
    tr.learn()
    before = len(_COMPILES)
    found = {m: programs.op_scopes(m) for m in ("jit_rollout_fn", "jit_train_multi_indexed")}
    assert len(_COMPILES) == before
    return found


PARTS = [
    ("jit_rollout_fn", r"/rollout/generate/prefill/"),
    ("jit_rollout_fn", r"/rollout/generate/decode/(.*/)?attn/"),
    ("jit_rollout_fn", r"/rollout/generate/decode/(.*/)?kv_write/"),
    ("jit_rollout_fn", r"/rollout/generate/decode/(.*/)?mlp/"),
    ("jit_rollout_fn", r"/rollout/generate/decode/(.*/)?head/"),
    ("jit_rollout_fn", r"/rollout/generate/decode/(.*/)?sample/"),
    ("jit_rollout_fn", r"/rollout/score/trunk/"),
    ("jit_rollout_fn", r"/rollout/score/top/"),
    ("jit_rollout_fn", r"/rollout/score/logprobs/"),
    ("jit_train_multi_indexed", r"\bupdate/trunk\b"),
    ("jit_train_multi_indexed", r"\bupdate/top\b"),
    ("jit_train_multi_indexed", r"\bupdate/loss\b"),
    ("jit_train_multi_indexed", r"\bupdate/opt\b"),
]


@pytest.mark.parametrize("module, part", PARTS, ids=[p for _, p in PARTS])
def test_the_cycles_programs_carry_their_parts(maps, module, part):
    assert any(re.search(part, v) for v in maps[module].values())
