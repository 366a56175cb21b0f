"""Operations and bytes the work of ``arch: sarvam_mla`` needs, from the
configuration, the shapes and the routing made (never from the
implementation). ``spec`` is the ``model_spec`` dict of a file under
``benchmarks/configs``: the chip's share of the stated deployment, so the
experts counted are the pairs computed HERE and the head is over the slice.

Conventions, as ``lib/counts_cohere2_moe.py``: a multiply-add is 2 FLOPs; a
token at context position c scores against c + 1 keys; norm, softmax,
activation, rotary and the top-k are left out; weights and latents are 2
bytes an element (bfloat16, as the configuration states).

Latent attention has two orders and they cost differently. **Up-projected**
(the reference's, a prefill chunk's): a (query, key) pair costs ``2 H (dn +
dr + dv)`` (41k at the published sizes) and a key is up-projected,
``2 r H (dn + dv)`` (16.8M), once for every chunk that reads it. **Absorbed**
(a decode step's, a short suffix's): a pair costs ``2 H (2 r + dr)`` (139k):
the scores over ``r + dr`` and the weighted sum over ``r``; no key is
up-projected. The share of the chip's peak (``step_mfu_pct``) counts a pair at
the up-projected price and every key's up-projection once, with the token's
other parameters: the least any order needs, so a program that recomputes or
absorbs is credited nothing for it. The kernel's and the decode step's
rooflines count what a decode step over a cache of latents has to do: the
absorbed price, on ``2 (r + dr)`` bytes a cached token a layer (1,152; a
page holds them in 1,280, which ``kv_live_gib`` counts and no roofline
does)."""


def dims(spec) -> dict:
    return {"d": spec["d_model"], "H": spec["n_head"], "r": spec["kv_lora_rank"], "dn": spec["qk_nope_head_dim"],
            "dr": spec["qk_rope_head_dim"], "dv": spec["v_head_dim"], "f": spec["d_ff"],
            "fe": spec.get("expert_width") or spec["d_ff"], "E": spec["n_experts"],
            "shared": spec.get("n_shared_experts", 0), "V": spec["vocab_size"], "L": spec["n_layer"],
            "Ld": spec.get("first_dense_layers", 0)}


def expert_layers(spec) -> int:
    s = dims(spec)
    return s["L"] - s["Ld"]


# ------------------------------------------------------------ parameters a token meets, by part of a layer
def attn_params(spec) -> int:
    s = dims(spec)
    return (s["d"] * s["H"] * (s["dn"] + s["dr"]) + s["d"] * (s["r"] + s["dr"])
            + s["r"] * s["H"] * (s["dn"] + s["dv"]) + s["H"] * s["dv"] * s["d"])


def absorb_params(spec) -> int:
    """Wuk and Wuv: the dots under the scope ``absorb`` at a decode step."""
    s = dims(spec)
    return s["r"] * s["H"] * (s["dn"] + s["dv"])


def dense_ffn_params(spec) -> int:
    s = dims(spec)
    return 3 * s["d"] * s["f"]


def router_params(spec) -> int:
    s = dims(spec)
    return s["d"] * s["E"]


def shared_params(spec) -> int:
    s = dims(spec)
    return 3 * s["d"] * s["fe"] * s["shared"]


def expert_params(spec) -> int:
    s = dims(spec)
    return 3 * s["d"] * s["fe"]


# ------------------------------------------------------------ FLOPs
def pairs_seen(start: int, n: int) -> int:
    """(query, key) pairs of n consecutive tokens, the first at context position ``start``."""
    return n * start + n * (n + 1) // 2


def pair_flops_up_projected(spec) -> float:
    s = dims(spec)
    return 2.0 * s["H"] * (s["dn"] + s["dr"] + s["dv"])


def pair_flops_absorbed(spec) -> float:
    s = dims(spec)
    return 2.0 * s["H"] * (2 * s["r"] + s["dr"])


def key_up_projection_flops(spec) -> float:
    s = dims(spec)
    return 2.0 * s["r"] * s["H"] * (s["dn"] + s["dv"])


def token_flops(spec) -> float:
    """Per token, all layers: the projections (a key's up-projection once), the dense layers' FFN, the router
    and the shared expert (every token meets them)."""
    s = dims(spec)
    return 2.0 * (s["L"] * attn_params(spec) + s["Ld"] * dense_ffn_params(spec)
                  + expert_layers(spec) * (router_params(spec) + shared_params(spec)))


def pair_flops(spec) -> float:
    """One (token, expert) pair computed here."""
    return 2.0 * expert_params(spec)


def head_flops(spec, n_positions: float) -> float:
    s = dims(spec)
    return 2.0 * s["d"] * s["V"] * n_positions


def forward_flops(spec, start: int, n: int, n_logits: float) -> float:
    """n tokens from context position ``start`` on, WITHOUT the routed pairs (they are counted as made): the
    least any order needs (module docstring)."""
    return (token_flops(spec) * n + dims(spec)["L"] * pair_flops_up_projected(spec) * pairs_seen(start, n)
            + head_flops(spec, n_logits))


def chunk_attention_flops(spec, start: int, n: int, absorbed: bool) -> float:
    """What one prefill chunk's attention does in either order, all layers: absorbed, its pairs at the absorbed
    price and the absorption of its n queries and outputs; up-projected, its pairs at the up-projected price and
    the up-projection of every key it reads (``start + n``)."""
    s = dims(spec)
    if absorbed:
        per_layer = pair_flops_absorbed(spec) * pairs_seen(start, n) + 2.0 * absorb_params(spec) * n
    else:
        per_layer = pair_flops_up_projected(spec) * pairs_seen(start, n) + key_up_projection_flops(spec) * (start + n)
    return s["L"] * per_layer


# ------------------------------------------------------------ bytes
BYTES = 2.0


def weights_outside_experts_bytes(spec) -> float:
    """What every decode step reads whatever the routing: attention and norms of every layer, the dense layers'
    FFN, router and shared expert of the others, the final norm and the head over the slice."""
    s = dims(spec)
    per_layer = attn_params(spec) + 2 * s["d"] + s["r"]
    return (s["L"] * per_layer + s["Ld"] * dense_ffn_params(spec)
            + expert_layers(spec) * (router_params(spec) + s["E"] + shared_params(spec))
            + s["d"] * s["V"] + s["d"]) * BYTES


def moe_fixed_bytes(spec) -> float:
    """The expert layers' weights that do not depend on the routing: router and shared expert."""
    return expert_layers(spec) * (router_params(spec) + shared_params(spec)) * BYTES


def expert_bytes(spec) -> float:
    return expert_params(spec) * BYTES


def latent_bytes_per_key(spec, as_stored: bool = False) -> float:
    """The latent of one position in one layer: ``r + dr`` numbers read; ``as_stored``, out to whole lane tiles
    of 128, which is what a page takes (1,152 and 1,280 bytes at the published sizes)."""
    s = dims(spec)
    width = s["r"] + s["dr"]
    return (-(-width // 128) * 128 if as_stored else width) * BYTES


def live_latent_bytes(spec, contexts, page_size: int = 1, as_stored: bool = False) -> float:
    """Bytes of the latents a decode step reads for rows whose contexts hold ``contexts`` tokens, all layers;
    ``page_size`` > 1 counts whole pages that hold a visible key."""
    keys = sum(-(-c // page_size) * page_size for c in contexts)
    return dims(spec)["L"] * keys * latent_bytes_per_key(spec, as_stored)


def floor(nbytes: float, flops: float, peaks: dict) -> dict:
    """Least time for ``nbytes`` and ``flops`` on a chip of ``peaks``, and which of the two bounds it."""
    t_mem, t_flop = nbytes / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"]
    return {"seconds": max(t_mem, t_flop), "bound": "hbm" if t_mem >= t_flop else "flops",
            "bytes": nbytes, "flops": flops}


def decode_step_floor(spec, contexts, experts_hit: float, pairs: float, peaks: dict) -> dict:
    """One decode step of ``len(contexts)`` rows: ``experts_hit`` distinct experts read (summed over the expert
    layers), ``pairs`` (token, expert) pairs computed here (summed likewise); attention at the absorbed price."""
    rows, s = len(contexts), dims(spec)
    nbytes = weights_outside_experts_bytes(spec) + experts_hit * expert_bytes(spec) + live_latent_bytes(spec, contexts)
    flops = (token_flops(spec) * rows + pairs * pair_flops(spec) + head_flops(spec, rows)
             + s["L"] * pair_flops_absorbed(spec) * sum(contexts))
    return floor(nbytes, flops, peaks)


def moe_step_floor(spec, rows: float, experts_hit: float, pairs: float, peaks: dict) -> dict:
    """The expert layers of one decode step (router, shared expert, the pairs made)."""
    nbytes = moe_fixed_bytes(spec) + experts_hit * expert_bytes(spec)
    flops = expert_layers(spec) * 2.0 * (router_params(spec) + shared_params(spec)) * rows + pairs * pair_flops(spec)
    return floor(nbytes, flops, peaks)


def latent_attn_step_floor(spec, contexts, page_size: int, peaks: dict) -> dict:
    """The absorbed decode kernel of one step, all layers: the latents of the pages that hold a key a row's query
    sees (``r + dr`` numbers each: a page's padding is not work), scored and summed at the absorbed price."""
    nbytes = live_latent_bytes(spec, contexts, page_size)
    flops = dims(spec)["L"] * pair_flops_absorbed(spec) * sum(contexts)
    return floor(nbytes, flops, peaks)
