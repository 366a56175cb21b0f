"""The PPO update runs its frozen trunk once a batch, not once an epoch.

XLA:CPU moves a loop whose inputs never change out of the scan around
it by itself; XLA:TPU does not (measured on v5e at gpt2-xl: three of
the update's four 46-layer forwards recomputed the same array). So no
timing and no compiled text on this backend can tell the hoisted
program from the one that scans the whole forward: these tests read
the jaxpr, which says what the program wrote, and hold the two halves
of the forward and the two ways of running the passes to the same
numbers. The backend that has the problem is held compile-only in
``tests/test_zz_chip_smoke.py``.
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tests.test_ppo_e2e import make_config
from trlx_tpu.data.configs import ModelSpec
from trlx_tpu.data.ppo_types import PPORLBatch
from trlx_tpu.models.policy import HydraPolicy
from trlx_tpu.trainers.ppo_trainer import ppo_update_fns, trunk_passes
from trlx_tpu.utils.jaxpr_scans import scan_sites
from trlx_tpu.utils.loading import get_model

B, P, G = 4, 4, 8
# every loop of the update a length of its own: 2 frozen layers, 1 trained,
# 3 epochs, GAE over the 8 response tokens
L, K, EPOCHS = 3, 1, 3


def batch(masked=False, rows=B, gen=G):
    rng = np.random.default_rng(3)
    query_masks = np.ones((rows, P), np.int32)
    if masked:
        query_masks[0, :2] = 0  # a left-padded prompt
    return PPORLBatch(
        query_tensors=jnp.asarray(rng.integers(1, 96, (rows, P)), jnp.int32),
        response_tensors=jnp.asarray(rng.integers(1, 96, (rows, gen)), jnp.int32),
        logprobs=jnp.asarray(rng.normal(-3.0, 0.3, (rows, gen)), jnp.float32),
        values=jnp.asarray(rng.normal(0.0, 0.5, (rows, gen)), jnp.float32),
        rewards=jnp.asarray(rng.normal(0.0, 0.2, (rows, gen)), jnp.float32),
        response_masks=jnp.ones((rows, gen), jnp.int32),
        query_masks=jnp.asarray(query_masks),
    )


def ppo_method(epochs):
    """The fields of ``config.method`` an update program reads."""
    return types.SimpleNamespace(
        gamma=1.0, lam=0.95, cliprange=0.2, cliprange_value=0.2,
        vf_coef=1.0, ppo_epochs=epochs,
    )


def scanned_whole(train_step, epochs):
    """The update as it stood before the trunk was taken out of it: the
    whole pass, trunk and all, as the scanned body."""
    def run(params, opt_state, b):
        def one(carry, _):
            return train_step(*carry, b)[:2], None

        return jax.lax.scan(one, (params, opt_state), None, length=epochs)[0]

    return run


def tiny_update():
    spec = ModelSpec(vocab_size=97, n_layer=L, n_head=4, d_model=32,
                     n_positions=32)
    policy = HydraPolicy(spec=spec, num_layers_unfrozen=K,
                         compute_dtype=jnp.float32)
    opt = optax.adamw(1e-3)
    params = policy.init(jax.random.PRNGKey(0))
    fns = ppo_update_fns(policy, ppo_method(EPOCHS), opt)
    return fns, params, opt.init(params["trainable"])


def test_the_epochs_scan_holds_no_trunk_and_one_stands_beside_it():
    (train_step, train_multi, _), params, opt_state = tiny_update()
    hoisted = jax.make_jaxpr(train_multi)(params, opt_state, batch())
    sites = list(scan_sites(hoisted))
    epochs = [s for s in sites if s.length == EPOCHS]
    assert len(epochs) == 1 and epochs[0].enclosing == ()
    trunks = [s for s in sites if s.length == L - K]
    assert [(s.enclosing, s.scope) for s in trunks] == [((), "update/trunk")]
    # the trained block's forward and backward are what the epochs repeat
    assert {s.length for s in sites if epochs[0] in s.enclosing} == {K}
    assert trunk_passes(hoisted) == 1

    # the structure this one replaced reads otherwise
    whole = jax.make_jaxpr(scanned_whole(train_step, EPOCHS))(
        params, opt_state, batch()
    )
    inside = [s for s in scan_sites(whole) if s.length == L - K]
    assert [s.runs for s in inside] == [EPOCHS]
    assert trunk_passes(whole) == EPOCHS


def test_the_trainers_own_update_programs_run_the_trunk_once(capsys):
    """Through the registry, both programs the learn loop dispatches, and
    what the operator sees when they are built: the log line and the
    gauge."""
    from trlx_tpu import telemetry

    config = make_config(total_steps=4, batch_size=B, ppo_epochs=2)
    config.model.model_spec["n_layer"] = 4
    trainer = get_model(config.model.model_type)(config)
    said = capsys.readouterr().err
    assert said.count("the frozen trunk (3 of 4 layers) runs in 1 of 2 epochs") == 1
    registry = telemetry.current().registry
    assert registry.gauges["ppo/update_trunk_passes"] == 1
    args = (trainer.params, trainer.opt_state, batch())
    assert trunk_passes(trainer._train_multi.trace(*args).jaxpr) == 1
    stored = jax.tree_util.tree_map(lambda x: jnp.tile(x, (2,) + (1,) * (x.ndim - 1)), batch())
    indexed = (*args[:2], stored, jnp.arange(B, dtype=jnp.int32))
    assert trunk_passes(trainer._train_multi_indexed.trace(*indexed).jaxpr) == 1


@pytest.mark.parametrize("program", ["_train_multi_indexed", "_train_multi"])
def test_the_dispatch_asks_nothing_of_the_attribute_but_a_call(program):
    """`benchmarks/tests/test_correct.py` plants its faults by putting a
    plain function where the jitted program was (`break_train_step`): the
    learn loop's `run` has to call whatever the attribute holds."""
    config = make_config(total_steps=4, batch_size=B, ppo_epochs=2)
    trainer = get_model(config.model.model_type)(config)
    rows = batch()
    if program == "_train_multi":  # host-side rollouts take the loader's path
        rows = jax.tree_util.tree_map(np.asarray, rows)
    trainer.store.push(rows)
    jitted, calls = getattr(trainer, program), []
    setattr(trainer, program, lambda *a: calls.append(len(a)) or jitted(*a))
    iterator, run, _ = trainer._batch_runner(config.train)
    _, _, stats = run(next(iter(iterator)))
    assert calls == [4 if program == "_train_multi_indexed" else 3]
    assert np.isfinite(float(stats["loss"]))


GPT2 = dict(vocab_size=97, n_layer=3, n_head=4, d_model=32, n_positions=32)
GPTJ = dict(arch="gptj", vocab_size=97, n_layer=3, n_head=4, d_model=32,
            n_positions=32, rotary_dim=4, tie_lm_head=False)


@pytest.mark.parametrize("with_ref", [True, False], ids=["ref", "noref"])
@pytest.mark.parametrize("half", ["forward", "forward_hidden"])
@pytest.mark.parametrize("spec", [GPT2, GPTJ], ids=["gpt2", "gptj"])
def test_the_forward_from_the_trunks_output_is_the_forward(spec, half, with_ref):
    """Bit for bit in float32, the trunk's output crossing from one
    compiled program into another as it does from the update's prologue
    into its epochs."""
    policy = HydraPolicy(spec=ModelSpec(**spec), num_layers_unfrozen=1,
                         compute_dtype=jnp.float32)
    params = policy.init(jax.random.PRNGKey(1))
    b = batch(masked=True)
    tokens = jnp.concatenate([b.query_tensors, b.response_tensors], axis=1)
    mask = jnp.concatenate([b.query_masks, b.response_masks], axis=1)
    whole = jax.jit(
        lambda p: getattr(policy, half)(p, tokens, mask, with_ref)
    )(params)
    trunk_out = jax.jit(lambda p: policy.trunk(p, tokens, mask))(params)
    halves = jax.jit(
        lambda p, out: getattr(policy, half + "_from_trunk")(p, *out, with_ref)
    )(params, trunk_out)
    assert (whole[1] is None) == (not with_ref)
    for a, b_ in zip(jax.tree_util.tree_leaves(whole),
                     jax.tree_util.tree_leaves(halves)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("guard", [0, 2], ids=["unguarded", "guarded"])
def test_two_scanned_passes_are_two_single_steps(guard):
    config = make_config(total_steps=4, batch_size=B, ppo_epochs=2)
    config.model.model_spec["n_layer"] = 3
    config.train.max_bad_steps = guard
    trainer = get_model(config.model.model_type)(config)
    b = batch()
    copy = lambda tree: jax.tree_util.tree_map(jnp.array, tree)  # both donate
    params, opt_state = copy(trainer.params), copy(trainer.opt_state)
    for _ in range(2):
        params, opt_state, stepped = trainer._train_step(params, opt_state, b)
    multi, multi_opt, scanned = trainer._train_multi(
        copy(trainer.params), copy(trainer.opt_state), b
    )
    assert ("bad_step" in scanned) == bool(guard)
    moved = False
    for (path, a), c, start in zip(
        jax.tree_util.tree_leaves_with_path(multi["trainable"]),
        jax.tree_util.tree_leaves(params["trainable"]),
        jax.tree_util.tree_leaves(trainer.params["trainable"]),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(c), rtol=1e-6, atol=1e-7,
            err_msg=jax.tree_util.keystr(path),
        )
        moved |= bool(np.any(np.asarray(a) != np.asarray(start)))
    assert moved
    for a, c in zip(jax.tree_util.tree_leaves(multi_opt),
                    jax.tree_util.tree_leaves(opt_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-8)
    for name in stepped:
        np.testing.assert_allclose(float(scanned[name]), float(stepped[name]),
                                   rtol=1e-5, atol=1e-7, err_msg=name)


HLO = """HloModule jit_train_multi_indexed, is_scheduled=true

%wide.region_6.20.clone.sunk (arg.1: (s32[], bf16[8,52,64], bf16[6,64,256])) -> (s32[], bf16[8,52,64], bf16[6,64,256]) {
  %arg.1 = (s32[]{:T(128)}, bf16[8,52,64]{2,1,0}, bf16[6,64,256]{2,1,0}) parameter(0)
  ROOT %tuple.9 = (s32[]{:T(128)}, bf16[8,52,64]{2,1,0}, bf16[6,64,256]{2,1,0}) tuple(%arg.1)
}

%cond.1 (arg.2: (s32[], bf16[8,52,64], bf16[6,64,256])) -> pred[] {
  %arg.2 = (s32[]{:T(128)}, bf16[8,52,64]{2,1,0}, bf16[6,64,256]{2,1,0}) parameter(0)
  ROOT %lt.1 = pred[]{:T(512)} constant(false)
}

%wide.region_0.164.sunk (arg.3: (s32[], f32[2,64,256], bf16[6,64,256])) -> (s32[], f32[2,64,256], bf16[6,64,256]) {
  %arg.3 = (s32[]{:T(128)}, f32[2,64,256]{2,1,0}, bf16[6,64,256]{2,1,0}) parameter(0)
  %h.0 = bf16[8,52,64]{2,1,0} constant(0)
  %while.497 = (s32[]{:T(128)}, bf16[8,52,64]{0,2,1:T(8,128)(2,1)S(1)}, /*index=2*/bf16[6,64,256]{2,1,0:T(8,128)(2,1)}) while(%tuple.3), condition=%cond.1, body=%wide.region_6.20.clone.sunk, metadata={op_name="jit(train_multi_indexed)/while/body/update/trunk/while"}
  ROOT %tuple.4 = (s32[]{:T(128)}, f32[2,64,256]{2,1,0}, bf16[6,64,256]{2,1,0}) tuple(%arg.3)
}

ENTRY %main.166 (p.0: bf16[6,64,256], p.1: f32[2,64,256]) -> f32[2,64,256] {
  %p.0 = bf16[6,64,256]{2,1,0} parameter(0)
  %p.1 = f32[2,64,256]{2,1,0} parameter(1)
  %while.496 = (s32[]{:T(128)}, f32[2,64,256]{2,1,0:T(8,128)}, bf16[6,64,256]{2,1,0:T(8,128)(2,1)}) while(%tuple.1), condition=%cond.1, body=%wide.region_0.164.sunk, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %gte.1 = f32[2,64,256]{2,1,0} get-tuple-element(%while.496), index=1
}
"""


def test_whiles_by_computation_reads_a_compiled_programs_text():
    """The shape of the text at this PR's parent: the epochs' loop in the
    entry computation, the loop over six stacked layers in its body."""
    from trlx_tpu.utils.hlo_text import ENTRY, whiles_by_computation

    whiles = whiles_by_computation(HLO)
    assert set(whiles) == {ENTRY, "wide.region_0.164.sunk"}
    (epochs,), (trunk,) = whiles[ENTRY], whiles["wide.region_0.164.sunk"]
    assert (epochs.name, epochs.body) == ("while.496", "wide.region_0.164.sunk")
    assert (trunk.name, trunk.body) == ("while.497", "wide.region_6.20.clone.sunk")
    assert trunk.body not in whiles  # it holds no loop of its own
    assert trunk.carry == (("s32", ()), ("bf16", (8, 52, 64)),
                           ("bf16", (6, 64, 256)))
    assert ("bf16", (6, 64, 256)) in epochs.carry
    assert whiles_by_computation("HloModule none\n") == {}
