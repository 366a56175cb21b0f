"""Operations and bytes the work of ``arch: cohere2_moe`` needs, from the
configuration, the shapes and the routing made (never from the
implementation). ``spec`` is the ``model_spec`` dict of a file under
``benchmarks/configs``: the chip's share of the stated deployment, so the
experts counted are the pairs computed HERE and the head is over the slice.

Conventions, as ``lib/counts.py``: a multiply-add is 2 FLOPs; a token at
context position c scores against c + 1 keys in a full layer and against
``min(c + 1, window)`` in a window layer (the window cap); norm, softmax,
activation, rotary and the top-k are left out. Weights and KV are 2 bytes an
element (bfloat16, as the configuration states)."""


def dims(spec) -> dict:
    d = spec["d_model"]
    hd = spec.get("head_size") or d // spec["n_head"]
    return {"d": d, "dq": spec["n_head"] * hd, "dkv": (spec.get("n_kv_heads") or spec["n_head"]) * hd,
            "f": spec.get("expert_width") or spec["d_ff"], "E": spec["n_experts"],
            "shared": spec.get("n_shared_experts", 0), "V": spec["vocab_size"], "L": spec["n_layer"],
            "window": spec.get("window", 0)}


def layers_of(spec) -> dict:
    """{"full": n, "window": n}: how many layers of each kind the model has."""
    pattern = spec.get("layer_pattern") or ["full"]
    kinds = [pattern[i % len(pattern)] for i in range(spec["n_layer"])]
    return {k: kinds.count(k) for k in ("full", "window")}


# ------------------------------------------------------------ parameters a token meets, by part of a layer
def attn_params(spec) -> int:
    s = dims(spec)
    return 2 * s["d"] * s["dq"] + 2 * s["d"] * s["dkv"]  # q and o; k and v


def router_params(spec) -> int:
    s = dims(spec)
    return s["d"] * s["E"]


def shared_params(spec) -> int:
    s = dims(spec)
    return 3 * s["d"] * s["f"] * s["shared"]


def expert_params(spec) -> int:
    s = dims(spec)
    return 3 * s["d"] * s["f"]


# ------------------------------------------------------------ FLOPs
def keys_seen(spec, kind: str, start: int, n: int) -> int:
    """Keys attended by n consecutive tokens, the first at context position ``start``."""
    w = dims(spec)["window"]
    if kind == "full" or not w:
        return n * start + n * (n + 1) // 2
    ramp = max(min(start + n, w) - start, 0)  # tokens whose context is still shorter than the window
    return ramp * start + ramp * (ramp + 1) // 2 + (n - ramp) * w


def attention_flops(spec, start: int, n: int) -> float:
    """Scores and the weighted sum (2 products) of n tokens from ``start`` on, in every layer."""
    s, by_kind = dims(spec), layers_of(spec)
    return sum(by_kind[k] * 4.0 * s["dq"] * keys_seen(spec, k, start, n) for k in by_kind)


def token_flops(spec) -> float:
    """Per token, all layers: the projections, the router and the shared experts (every token meets them)."""
    return dims(spec)["L"] * 2.0 * (attn_params(spec) + router_params(spec) + shared_params(spec))


def pair_flops(spec) -> float:
    """One (token, expert) pair computed here."""
    return 2.0 * expert_params(spec)


def head_flops(spec, n_positions: float) -> float:
    s = dims(spec)
    return 2.0 * s["d"] * s["V"] * n_positions


def forward_flops(spec, start: int, n: int, n_logits: float) -> float:
    """n tokens from context position ``start`` on, WITHOUT the routed pairs (they are counted as made)."""
    return token_flops(spec) * n + attention_flops(spec, start, n) + head_flops(spec, n_logits)


# ------------------------------------------------------------ bytes
BYTES = 2.0


def weights_outside_experts_bytes(spec) -> float:
    """What every decode step reads whatever the routing: attention, router, shared experts and norm
    of every layer, the final norm and the head (the tied embedding's slice, as a matrix)."""
    s = dims(spec)
    per_layer = attn_params(spec) + router_params(spec) + shared_params(spec) + s["d"]
    return (s["L"] * per_layer + s["d"] * s["V"] + s["d"]) * BYTES


def moe_fixed_bytes(spec) -> float:
    """The expert layer's weights that do not depend on the routing, all layers: router and shared experts."""
    return dims(spec)["L"] * (router_params(spec) + shared_params(spec)) * BYTES


def expert_bytes(spec) -> float:
    return expert_params(spec) * BYTES


def kv_bytes_per_key(spec) -> float:
    """K and V of one position in one layer."""
    return 2.0 * dims(spec)["dkv"] * BYTES


def live_kv_bytes(spec, contexts, page_size: int = 1) -> dict:
    """Bytes of the keys and values a decode step reads for rows whose contexts hold ``contexts`` tokens,
    by class of layer, with the window cap; ``one_class`` is what the same rows read (and hold) where
    every layer keeps the whole context. ``page_size`` > 1 counts whole pages that hold a visible key."""
    s, by_kind, per = dims(spec), layers_of(spec), kv_bytes_per_key(spec)

    def paged(n_keys, ctx):
        if page_size <= 1:
            return n_keys
        first = max(ctx - n_keys, 0) // page_size  # pages from the first visible key's to the last one's
        return (-(-ctx // page_size) - first) * page_size

    full = sum(paged(c, c) for c in contexts)
    capped = sum(paged(min(c, s["window"]) if s["window"] else c, c) for c in contexts)
    return {"full": by_kind["full"] * full * per, "window": by_kind["window"] * capped * per,
            "one_class": s["L"] * full * per}


def floor(nbytes: float, flops: float, peaks: dict) -> dict:
    """Least time for ``nbytes`` and ``flops`` on a chip of ``peaks``, and which of the two bounds it."""
    t_mem, t_flop = nbytes / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"]
    return {"seconds": max(t_mem, t_flop), "bound": "hbm" if t_mem >= t_flop else "flops",
            "bytes": nbytes, "flops": flops}


def decode_step_floor(spec, contexts, experts_hit: float, pairs: float, peaks: dict) -> dict:
    """One decode step of ``len(contexts)`` rows: ``experts_hit`` distinct experts read (summed over
    layers), ``pairs`` (token, expert) pairs computed here (summed over layers)."""
    rows = len(contexts)
    kv = live_kv_bytes(spec, contexts)
    nbytes = weights_outside_experts_bytes(spec) + experts_hit * expert_bytes(spec) + kv["full"] + kv["window"]
    flops = (token_flops(spec) * rows + pairs * pair_flops(spec) + head_flops(spec, rows)
             + sum(attention_flops(spec, c - 1, 1) for c in contexts))
    return floor(nbytes, flops, peaks)


def moe_step_floor(spec, rows: float, experts_hit: float, pairs: float, peaks: dict) -> dict:
    """The expert layers of one decode step (router, shared experts, the pairs made), all layers."""
    s = dims(spec)
    nbytes = moe_fixed_bytes(spec) + experts_hit * expert_bytes(spec)
    flops = s["L"] * 2.0 * (router_params(spec) + shared_params(spec)) * rows + pairs * pair_flops(spec)
    return floor(nbytes, flops, peaks)


def paged_attn_step_floor(spec, contexts, page_size: int, peaks: dict) -> dict:
    """The paged attention of one decode step, all layers: the pages that hold a key the row's query sees."""
    kv = live_kv_bytes(spec, contexts, page_size)
    flops = sum(attention_flops(spec, c - 1, 1) for c in contexts)
    return floor(kv["full"] + kv["window"], flops, peaks)
