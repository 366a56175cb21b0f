"""Weights from the seed, made by the benchmark (not by the program).

One table per architecture says which tensors the published model has, with
shape and initial scale. Every tensor of every layer has a key of its own
(seed, tensor name, layer), so the reference can make one layer at a time
and gets the very numbers the program was given, whatever order or grouping
the program stores them in."""

import zlib

import jax
import jax.numpy as jnp


def _dims(spec):
    d = spec["d_model"]
    return d, spec.get("d_ff") or 4 * d, spec["vocab_size"], spec["n_layer"]


def layer_table(spec):
    """{name: (shape, kind, scale)} of one block. kind: normal | one (1 + noise)."""
    d, f, _, L = _dims(spec)
    resid = 0.02 / (2 * L) ** 0.5
    t = {
        "ln_1/scale": ((d,), "one", 0.02), "ln_1/bias": ((d,), "normal", 0.02),
        "attn/wq": ((d, d), "normal", 0.02), "attn/wk": ((d, d), "normal", 0.02),
        "attn/wv": ((d, d), "normal", 0.02), "attn/wo": ((d, d), "normal", resid),
        "mlp/w_in": ((d, f), "normal", 0.02), "mlp/b_in": ((f,), "normal", 0.02),
        "mlp/w_out": ((f, d), "normal", resid), "mlp/b_out": ((d,), "normal", 0.02),
    }
    if spec["arch"] == "gpt2":
        for b in ("bq", "bk", "bv", "bo"):
            t[f"attn/{b}"] = ((d,), "normal", 0.02)
        t["ln_2/scale"] = ((d,), "one", 0.02)
        t["ln_2/bias"] = ((d,), "normal", 0.02)
    elif spec["arch"] != "gptj":
        raise ValueError(f"no weight table for arch {spec['arch']!r}")
    return t


def top_table(spec, value_head: bool):
    """Tensors outside the blocks: embeddings, final norm, heads."""
    d, _, v, _ = _dims(spec)
    t = {"embed/wte": ((v, d), "normal", 0.02),
         "ln_f/scale": ((d,), "one", 0.02), "ln_f/bias": ((d,), "normal", 0.02)}
    if spec["arch"] == "gpt2":
        t["embed/wpe"] = ((spec["n_positions"], d), "normal", 0.01)
    if not spec.get("tie_lm_head", True):
        t["lm_head/w"] = ((d, v), "normal", 0.02)
        t["lm_head/b"] = ((v,), "normal", 0.02)
    if value_head:
        t["v_head/w1"] = ((d, 2 * d), "normal", d ** -0.5 / 3 ** 0.5)
        t["v_head/b1"] = ((2 * d,), "normal", 0.02)
        t["v_head/w2"] = ((2 * d, 1), "normal", (2 * d) ** -0.5 / 3 ** 0.5)
        t["v_head/b2"] = ((1,), "normal", 0.02)
    return t


def base_key(seed: int):
    """--seed may pass 2**31: fold its two halves into one key."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def tensor(key, name: str, layer, entry, store_dtype, out_dtype):
    """One tensor. ``layer`` is -1 outside the blocks (may be traced).
    Values are rounded through ``store_dtype`` (the type the model is kept in)."""
    shape, kind, scale = entry
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF), layer + 1)
    x = scale * jax.random.normal(k, shape, jnp.float32)
    if kind == "one":
        x = 1.0 + x
    return x.astype(store_dtype).astype(out_dtype)


def nest(flat: dict) -> dict:
    out = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten(tree, prefix="") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def layer_params(spec, key, layer, store_dtype=jnp.float32, out_dtype=jnp.float32) -> dict:
    """Nested params of one block (``layer`` may be traced)."""
    return nest({n: tensor(key, n, layer, e, store_dtype, out_dtype) for n, e in layer_table(spec).items()})


def stacked_layers(spec, key, lo: int, hi: int, store_dtype, out_dtype) -> dict:
    """Blocks lo..hi-1 with a leading layer axis, as the program stacks them."""
    ids = jnp.arange(lo, hi)
    return nest({
        n: jax.vmap(lambda l, n=n, e=e: tensor(key, n, l, e, store_dtype, out_dtype))(ids)
        for n, e in layer_table(spec).items()
    })


def top_params(spec, key, value_head: bool, store_dtype=jnp.float32, out_dtype=jnp.float32, only=None) -> dict:
    table = top_table(spec, value_head)
    return nest({n: tensor(key, n, -1, e, store_dtype, out_dtype)
                 for n, e in table.items() if only is None or n.split("/")[0] in only})
