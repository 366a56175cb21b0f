"""The rollout's decode step reads only the cache slots written so far.

``generate()``'s decode loop is one ``lax.scan`` over the new tokens (a
device trace reads the decode loop as the longest ``while`` of the
rollout program, so it must stay one loop). Inside a step, each layer's
attention picks, by the step's own position, the least extent of the
read plan that holds every written slot, and its branch reads that many
slots of the cache and no more. These tests read the jaxpr at a tiny
size with a plan of three extents; the layout the read keeps on the
chip is held compile-only in ``tests/test_zz_chip_smoke.py``.
"""

import jax
import jax.numpy as jnp
import pytest

import trlx_tpu.models.generation as gen_mod
from trlx_tpu import telemetry
from trlx_tpu.data.configs import ModelSpec
from trlx_tpu.models.generation import (
    GenerationConfig,
    decode_read_share,
    generate,
)
from trlx_tpu.models.policy import HydraPolicy
from trlx_tpu.ops.sampling import SamplingParams
from trlx_tpu.utils.jaxpr_scans import scan_sites

B, P, G, L = 2, 5, 6, 3
HKV, HD = 4, 16
S = P + G  # 11: no other dimension of the model is 11
PLAN = (4, 8, S)


def traced(plan, monkeypatch):
    """The jaxpr of one generate() call with the decode read plan
    ``plan`` (``()``: the whole buffer every step), and the plan's gauge
    as the call set it."""
    spec = ModelSpec(arch="gpt2", vocab_size=97, n_layer=L, n_head=4,
                     d_model=64, n_positions=64)
    policy = HydraPolicy(spec=spec, num_layers_unfrozen=1,
                         compute_dtype=jnp.float32)
    params = policy.init(jax.random.PRNGKey(0))
    blocks = policy.all_blocks(params)
    embed, ln_f = policy.head_params_for_decode(params)
    cfg = GenerationConfig(gen_size=G, sampling=SamplingParams(do_sample=True))
    monkeypatch.setattr(gen_mod, "decode_read_plan",
                        lambda spec_, batch, buffer_len, dtype: plan)
    telemetry.start()
    try:
        jaxpr = jax.make_jaxpr(
            lambda b, e, lf, p, m, rng: generate(
                spec, b, e, lf, p, m, rng, cfg, compute_dtype=jnp.float32,
                cache_dtype=jnp.float32,
            )
        )(blocks, embed, ln_f, jnp.ones((B, P), jnp.int32),
          jnp.ones((B, P), jnp.int32), jax.random.PRNGKey(1))
        share = telemetry.current().registry.gauges[
            "rollout/decode_kv_read_share"]
    finally:
        telemetry.stop()
    return jaxpr, share


def decode_body(jaxpr):
    """The body of the decode scan: the one scan of length ``G``."""
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == G]
    assert len(scans) == 1, scans
    return scans[0].params["jaxpr"].jaxpr


def eqns(jaxpr, into_conds=True):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it (but
    the branches of a switch, without ``into_conds``)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond" and not into_conds:
            continue
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from eqns(inner, into_conds)


def whole_buffer_reads(body):
    """The products of the decode step, outside a switch's branches, with
    the whole ``S``-slot cache as an operand, however it is laid out."""
    return [e for e in eqns(body, into_conds=False)
            if e.primitive.name == "dot_general"
            and any(S in v.aval.shape for v in e.invars)]


def extents_read(cond):
    """The slots of the rows-last cache view ``[slots, Hkv, hd, B]`` that
    the products of each branch of one layer's switch read."""
    out = []
    for branch in cond.params["branches"]:
        read = {v.aval.shape[0] for e in eqns(branch.jaxpr)
                if e.primitive.name == "dot_general" for v in e.invars
                if v.aval.shape[1:] == (HKV, HD, B)}
        assert len(read) == 1, read  # K and V alike
        out.append(read.pop())
    return tuple(out)


def test_the_decode_loop_stays_one_loop_over_every_step(monkeypatch):
    """One scan of ``G`` steps under ``decode``, and no loop inside it:
    the plan's choice is a switch inside the one step, not a loop per
    extent (each such loop would cover a part of the steps, and the
    trace's longest ``while`` would no longer be the decode loop)."""
    jaxpr, _ = traced(PLAN, monkeypatch)
    decode = [s for s in scan_sites(jaxpr) if "decode" in s.scope]
    assert [(s.length, s.enclosing) for s in decode] == [(G, ())]
    body = decode_body(jaxpr)
    assert not [e for e in eqns(body)
                if e.primitive.name in ("scan", "while")]


def test_each_layer_reads_the_extent_that_holds_the_written_slots(
        monkeypatch):
    """Inside a step, every layer's attention is one switch over the
    plan's extents, each branch reading its extent of the cache (the
    last the whole buffer, once every slot is written), and no product
    of the step outside the switch reads the whole buffer. The control,
    the plan empty, reads the whole buffer in every layer and has no
    switch."""
    jaxpr, _ = traced(PLAN, monkeypatch)
    body = decode_body(jaxpr)
    conds = [e for e in eqns(body) if e.primitive.name == "cond"]
    assert len(conds) == L
    assert all(extents_read(c) == PLAN for c in conds)
    assert whole_buffer_reads(body) == []

    whole, _ = traced((), monkeypatch)
    body = decode_body(whole)
    assert not [e for e in eqns(body) if e.primitive.name == "cond"]
    assert len(whole_buffer_reads(body)) == 2 * L  # scores and sum, a layer


@pytest.mark.parametrize("plan", [PLAN, ()], ids=["plan", "whole"])
def test_the_gauge_reads_the_plans_share(plan, monkeypatch):
    """``rollout/decode_kv_read_share``: the slots the plan reads over the
    loop over ``G`` whole buffers, set when generate() builds its plan.
    Here steps 0-2 read 8 slots and steps 3-5 all 11: 57 of 66."""
    _, share = traced(plan, monkeypatch)
    assert share == decode_read_share(plan, P, G)
    assert share == pytest.approx(57 / 66 if plan else 1.0)
