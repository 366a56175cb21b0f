"""Weights from the seed for ``arch: cohere2_moe``, made by the benchmark (not
by the program), as ``lib/weights.py`` makes the dense families'.

Every tensor has a key of its own: (seed, tensor name, layer), and an expert's
or a shared expert's name carries its index in the UNCUT model
(``moe/w_gate/37``). So a chip's share (``experts_held`` from
``expert_offset``) holds the very experts the uncut model has at those
indices, the reference makes one layer at a time, and the test that the shares
add up can make all 128.

Scales: every matrix is normal with standard deviation ``GAIN / sqrt(fan_in)``
(0.02 for an input of 4096: the dense families' constant at the published
width), the two projections back into the residual stream divided by
``sqrt(2 L)`` besides. So a product of a unit-variance input has standard
deviation 1.28 at ANY width, and the tiny sizes of the tests and rehearsals
behave as the published one does: the blocks' outputs, not the token's own
embedding, decide the logits (with a constant 0.02 at width 64 the tied head
puts the input token first at every position, and no fault can move it).

The router's scale is the same: its logits ``x . Wr`` have standard deviation
1.28, sigmoid scores spread over (0.05, 0.95), no expert far ahead of the
others. Routing is then neither uniform (every token differs) nor collapsed;
PERF.md has the measured ``expert_load_max_over_mean``."""

import jax
import jax.numpy as jnp

from . import weights as W

GAIN = 1.28


def dims(spec):
    d, f = spec["d_model"], spec.get("expert_width") or spec["d_ff"]
    hd = spec.get("head_size") or d // spec["n_head"]
    return d, f, spec["n_head"] * hd, (spec.get("n_kv_heads") or spec["n_head"]) * hd


def layer_table(spec, experts=None):
    """{name: (shape, kind, scale)} of one block; ``experts`` (default: those
    held) lists the routed experts to make, by their index in the uncut model."""
    d, f, dq, dkv = dims(spec)
    into = lambda fan_in: GAIN / fan_in ** 0.5
    back = lambda fan_in: into(fan_in) / (2 * spec["n_layer"]) ** 0.5  # a projection back into the residual stream
    if experts is None:
        lo = spec.get("expert_offset", 0)
        experts = range(lo, lo + (spec.get("experts_held") or spec["n_experts"]))
    t = {
        "ln_1/scale_centred": ((d,), "one", 0.02),
        "attn/wq": ((d, dq), "normal", into(d)), "attn/wk": ((d, dkv), "normal", into(d)),
        "attn/wv": ((d, dkv), "normal", into(d)), "attn/wo": ((dq, d), "normal", back(dq)),
        "moe/router": ((d, spec["n_experts"]), "normal", into(d)),
    }
    for family, members in (("moe", experts), ("shared", range(spec.get("n_shared_experts", 0)))):
        for e in members:
            t[f"{family}/w_gate/{e}"] = ((d, f), "normal", into(d))
            t[f"{family}/w_up/{e}"] = ((d, f), "normal", into(d))
            t[f"{family}/w_down/{e}"] = ((f, d), "normal", back(f))
    return t


def top_table(spec):
    d = spec["d_model"]
    return {"embed/wte": ((spec["vocab_size"], d), "normal", GAIN / d ** 0.5),
            "ln_f/scale_centred": ((d,), "one", 0.02)}


def layer_flat(spec, key, layer, store_dtype=jnp.float32, out_dtype=jnp.float32, experts=None) -> dict:
    """{name: tensor} of one block, every expert under its own name (the reference's view)."""
    return {n: W.tensor(key, n, layer, e, store_dtype, out_dtype)
            for n, e in layer_table(spec, experts).items()}


def program_layer(flat: dict) -> dict:
    """One block in the program's layout: the experts held stacked in index
    order, the shared experts side by side as one SwiGLU (gate and up along
    their columns, down along its rows)."""
    def family(prefix):
        names = sorted((n for n in flat if n.startswith(prefix + "/")), key=lambda n: int(n.rsplit("/", 1)[1]))
        return [flat[n] for n in names]

    out = {n: v for n, v in flat.items() if n.count("/") == 1}
    for part in ("w_gate", "w_up", "w_down"):
        out[f"moe/{part}"] = jnp.stack(family(f"moe/{part}"))
        shared = family(f"shared/{part}")
        if shared:
            out[f"shared/{part}"] = jnp.concatenate(shared, axis=0 if part == "w_down" else 1)
    return W.nest(out)


def stacked_layers(spec, key, lo: int, hi: int, store_dtype, out_dtype) -> dict:
    """Blocks lo..hi-1 in the program's layout with a leading layer axis."""
    one = lambda l: program_layer(layer_flat(spec, key, l, store_dtype, out_dtype))
    return jax.vmap(one)(jnp.arange(lo, hi))


def top_params(spec, key, store_dtype=jnp.float32, out_dtype=jnp.float32, only=None) -> dict:
    return W.nest({n: W.tensor(key, n, -1, e, store_dtype, out_dtype)
                   for n, e in top_table(spec).items() if only is None or n.split("/")[0] in only})


def hydra_weights(spec: dict, seed: int, k: int, store_dtype):
    """The program's serve tree (frozen_base + trainable, no reference branch,
    no value head), made on the device in one jitted call from the seed."""
    key = W.base_key(seed)
    L = spec["n_layer"]

    @jax.jit
    def make(key):  # an argument: closed over, every seed would be a compile
        top = top_params(spec, key, store_dtype, store_dtype)
        return {"frozen_base": {"embed": top["embed"],
                                "blocks": stacked_layers(spec, key, 0, L - k, store_dtype, store_dtype)},
                "trainable": {"blocks": stacked_layers(spec, key, L - k, L, store_dtype, store_dtype),
                              "ln_f": top["ln_f"]}}

    return make(key)
