"""Weights from the seed for ``arch: sarvam_mla``, made by the benchmark (not
by the program), as ``lib/weights_cohere2_moe.py`` makes its model's.

Every tensor has a key of its own: (seed, tensor name, layer); an expert's
name carries its index in the UNCUT model (``moe/w_gate/37``), so a chip's
share (``experts_held`` from ``expert_offset``) holds the very experts the
uncut model has there, the reference makes one layer at a time, and the test
that the shares add up can make every expert.

Scales follow the fan-in (PERF.md section 6, PR 29, finding 3): every matrix
is normal with standard deviation ``GAIN / sqrt(fan_in)``, the two
projections back into the residual stream divided by ``sqrt(2 L)`` besides,
so the tiny sizes of tests and rehearsals behave as the published one does;
the query projection carries ``Q_GAIN`` besides (below).

**The router's bias is seeded non-zero** (float32, standard deviation
``BIAS_GAIN / n_experts``): the scores of 128 experts lie about 0.009 apart
near the eighth, so a bias of 0.0016 changes the chosen set in a few percent
of (token, layer) pairs (the share is measured in
``tests/test_sarvam_mla.py``). A program that chooses by the scores alone,
or weighs by score + bias, then differs from the reference where it
matters."""

import jax
import jax.numpy as jnp

from . import weights as W

GAIN = 1.28
BIAS_GAIN = 0.2
#: the query projection alone is scaled back by GAIN^2, so that a query-key product has the unit variance a term
#: that the score scale ``(dn + dr)^-1/2`` presumes, and YaRN's ``m^2`` = 1.87 on top of it is all that sharpens
#: the softmax: scores of standard deviation 1.87. With GAIN on both sides they read 3.07 and a row looks at a
#: handful of its 25k keys; every rounding of a latent then moves its output, and bfloat16 serving read 37% of its
#: tokens off the float32 reference's best (PERF.md section 6, PR 34, has the readings by gain)
Q_GAIN = GAIN ** -2


def dims(spec) -> dict:
    return {"d": spec["d_model"], "H": spec["n_head"], "r": spec["kv_lora_rank"], "dn": spec["qk_nope_head_dim"],
            "dr": spec["qk_rope_head_dim"], "dv": spec["v_head_dim"], "f": spec["d_ff"],
            "fe": spec.get("expert_width") or spec["d_ff"], "E": spec["n_experts"], "L": spec["n_layer"]}


def is_dense(spec, layer: int) -> bool:
    return layer < spec.get("first_dense_layers", 0)


def layer_table(spec, dense: bool, experts=None):
    """{name: (shape, kind, scale)} of one block, a leading dense one or one
    with experts; ``experts`` (default: those held) lists the routed experts to
    make, by their index in the uncut model."""
    s = dims(spec)
    d, H, r = s["d"], s["H"], s["r"]
    into = lambda fan_in: GAIN / fan_in ** 0.5
    back = lambda fan_in: into(fan_in) / (2 * s["L"]) ** 0.5  # a projection back into the residual stream
    t = {
        "ln_1/scale": ((d,), "one", 0.02), "ln_2/scale": ((d,), "one", 0.02),
        "attn/wq": ((d, H * (s["dn"] + s["dr"])), "normal", Q_GAIN * into(d)),
        "attn/w_dkv": ((d, r + s["dr"]), "normal", into(d)),
        "attn/kv_norm/scale": ((r,), "one", 0.02),
        "attn/w_uk": ((r, H, s["dn"]), "normal", into(r)),  # by head, as the program keeps them
        "attn/w_uv": ((r, H, s["dv"]), "normal", into(r)),
        "attn/wo": ((H * s["dv"], d), "normal", back(H * s["dv"])),
    }
    if dense:
        t.update({"mlp/w_gate": ((d, s["f"]), "normal", into(d)), "mlp/w_in": ((d, s["f"]), "normal", into(d)),
                  "mlp/w_out": ((s["f"], d), "normal", back(s["f"]))})
        return t
    if experts is None:
        lo = spec.get("expert_offset", 0)
        experts = range(lo, lo + (spec.get("experts_held") or s["E"]))
    t["moe/router"] = ((d, s["E"]), "normal", into(d))
    t["moe/router_bias"] = ((s["E"],), "normal", BIAS_GAIN / s["E"])
    for family, members in (("moe", experts), ("shared", range(spec.get("n_shared_experts", 0)))):
        for e in members:
            t[f"{family}/w_gate/{e}"] = ((d, s["fe"]), "normal", into(d))
            t[f"{family}/w_up/{e}"] = ((d, s["fe"]), "normal", into(d))
            t[f"{family}/w_down/{e}"] = ((s["fe"], d), "normal", back(s["fe"]))
    return t


def top_table(spec):
    d, v = spec["d_model"], spec["vocab_size"]
    return {"embed/wte": ((v, d), "normal", GAIN / d ** 0.5), "ln_f/scale": ((d,), "one", 0.02),
            # the published head has no bias; the program's untied head keeps one, and it is given noughts
            "lm_head/w": ((d, v), "normal", GAIN / d ** 0.5), "lm_head/b": ((v,), "normal", 0.0)}


def _tensor(key, name, layer, entry, store_dtype, out_dtype):
    if name.endswith("router_bias"):  # kept in float32 beside the router, whatever the model is stored in
        return W.tensor(key, name, layer, entry, jnp.float32, jnp.float32)
    return W.tensor(key, name, layer, entry, store_dtype, out_dtype)


def layer_flat(spec, key, layer, dense: bool, store_dtype=jnp.float32, out_dtype=jnp.float32, experts=None) -> dict:
    """{name: tensor} of one block, every expert under its own name (the reference's view)."""
    return {n: _tensor(key, n, layer, e, store_dtype, out_dtype) for n, e in layer_table(spec, dense, experts).items()}


def program_layer(flat: dict) -> dict:
    """One block in the program's layout: the experts held stacked in index order, the shared experts
    side by side as one SwiGLU (gate and up along their columns, down along its rows)."""
    def family(prefix):
        names = sorted((n for n in flat if n.startswith(prefix + "/")), key=lambda n: int(n.rsplit("/", 1)[1]))
        return [flat[n] for n in names]

    numbered = lambda n: n.rsplit("/", 1)[1].isdigit()
    out = {n: v for n, v in flat.items() if not numbered(n)}
    if "moe/router" in flat:
        for part in ("w_gate", "w_up", "w_down"):
            out[f"moe/{part}"] = jnp.stack(family(f"moe/{part}"))
            shared = family(f"shared/{part}")
            if shared:
                out[f"shared/{part}"] = jnp.concatenate(shared, axis=0 if part == "w_down" else 1)
    return W.nest(out)


def stacked_layers(spec, key, lo: int, hi: int, store_dtype, out_dtype):
    """Blocks lo..hi-1 in the program's layout: one tree with a leading layer axis where they are all alike,
    a (dense, experts) pair of such trees where the range crosses the last leading dense layer."""
    cut = min(max(spec.get("first_dense_layers", 0), lo), hi)
    runs = [(a, b, dense) for a, b, dense in ((lo, cut, True), (cut, hi, False)) if a < b]
    if not runs:  # no layer: the leaves of the range's kind at zero layers
        runs = [(lo, lo, is_dense(spec, lo) and lo < spec["n_layer"])]
    trees = tuple(
        jax.vmap(lambda l, dense=dense: program_layer(layer_flat(spec, key, l, dense, store_dtype, out_dtype)))(
            jnp.arange(a, b)) for a, b, dense in runs)
    return trees[0] if len(trees) == 1 else trees


def top_params(spec, key, store_dtype=jnp.float32, out_dtype=jnp.float32, only=None) -> dict:
    return W.nest({n: W.tensor(key, n, -1, e, store_dtype, out_dtype)
                   for n, e in top_table(spec).items() if only is None or n.split("/")[0] in only})


def hydra_weights(spec: dict, seed: int, k: int, store_dtype):
    """The program's serve tree (frozen_base + trainable, no reference branch, no value head), made on the
    device in one jitted call from the seed."""
    key = W.base_key(seed)
    L = spec["n_layer"]

    @jax.jit
    def make(key):  # an argument: closed over, every seed would be a compile
        top = top_params(spec, key, store_dtype, store_dtype)
        return {"frozen_base": {"embed": top["embed"],
                                "blocks": stacked_layers(spec, key, 0, L - k, store_dtype, store_dtype)},
                "trainable": {"blocks": stacked_layers(spec, key, L - k, L, store_dtype, store_dtype),
                              "ln_f": top["ln_f"], "lm_head": top["lm_head"]}}

    return make(key)
