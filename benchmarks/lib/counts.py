"""Operations and bytes a model's work needs, from the configuration and the
shapes alone (never from the implementation). ``spec`` is the ``model_spec``
dict of a file under ``benchmarks/configs``.

Conventions: a multiply-add is 2 FLOPs; attention is counted causally (a
token at context position c scores against c + 1 keys); layernorm, softmax,
activation and rotary FLOPs are left out (well under 1% at these widths)."""


def _dims(spec):
    d = spec["d_model"]
    f = spec.get("d_ff") or 4 * d
    return spec["n_layer"], d, f, spec["vocab_size"]


def layer_matmul_params(spec) -> int:
    """Weights of one block that a token is multiplied by: q, k, v, o and the MLP."""
    _, d, f, _ = _dims(spec)
    return 4 * d * d + 2 * d * f


def layers_flops(spec, n_layers: int, n_tokens: int, context_sum: int) -> float:
    """Forward FLOPs of ``n_tokens`` tokens through ``n_layers`` blocks.
    ``context_sum`` is the sum over those tokens of the keys each attends to."""
    _, d, _, _ = _dims(spec)
    return n_layers * (2.0 * layer_matmul_params(spec) * n_tokens + 4.0 * d * context_sum)


def head_flops(spec, n_positions: int) -> float:
    _, d, _, v = _dims(spec)
    return 2.0 * d * v * n_positions


def value_head_flops(spec, n_positions: int) -> float:
    d = spec["d_model"]
    return 2.0 * (d * 2 * d + 2 * d) * n_positions


def causal_context_sum(start: int, n: int) -> int:
    """Keys attended by n consecutive tokens, the first at context position ``start``."""
    return n * start + n * (n + 1) // 2


def ppo_cycle_flops(spec, batch: int, prompt: int, gen: int, k_unfrozen: int, ppo_epochs: int) -> dict:
    """FLOPs one PPO cycle needs, by phase. Nothing is counted twice that the
    algorithm could keep: the frozen trunk's forward is counted once for the
    update phase, not once per epoch (its output does not change)."""
    L = spec["n_layer"]
    T = prompt + gen
    full_ctx = causal_context_sum(0, T)
    # rollout: every token but the last goes once through all layers; a
    # distribution is needed at each of the ``gen`` sampled positions.
    decode = batch * (layers_flops(spec, L, T - 1, causal_context_sum(0, T - 1)) + head_flops(spec, gen))
    # scoring: trunk + policy top + reference top, both heads and the value head over the response.
    score = batch * (
        layers_flops(spec, L + k_unfrozen, T, full_ctx)
        + 2 * head_flops(spec, gen) + value_head_flops(spec, gen)
    )
    top_fwd = batch * (layers_flops(spec, k_unfrozen, T, full_ctx) + head_flops(spec, gen) + value_head_flops(spec, gen))
    update = batch * layers_flops(spec, L - k_unfrozen, T, full_ctx) + ppo_epochs * 3.0 * top_fwd
    return {"decode": decode, "score": score, "update": update, "total": decode + score + update}


def serve_request_flops(spec, prompt_len: int, n_out: int) -> float:
    """Prefill of the prompt (one distribution, for the first token) and one
    forward per further output token against the growing context."""
    L = spec["n_layer"]
    prefill = layers_flops(spec, L, prompt_len, causal_context_sum(0, prompt_len)) + head_flops(spec, 1)
    n_dec = max(n_out - 1, 0)  # the last token is never fed back
    decode = layers_flops(spec, L, n_dec, causal_context_sum(prompt_len, n_dec)) + head_flops(spec, n_dec)
    return prefill + decode


def weight_bytes(spec, bytes_per_param: float = 2.0, head_bytes_per_param: float = None) -> float:
    """Bytes of the weights one decode step has to read: every block, the final
    norm and the output head (the embedding is read row-wise: negligible)."""
    L, d, f, v = _dims(spec)
    per_layer = layer_matmul_params(spec) + 4 * d + f + (4 * d if spec["arch"] == "gpt2" else 0)
    head = d * v
    hb = bytes_per_param if head_bytes_per_param is None else head_bytes_per_param
    return L * per_layer * bytes_per_param + head * hb


def kv_bytes_per_token(spec, bytes_per_elem: float = 2.0) -> float:
    L, d, _, _ = _dims(spec)
    return 2.0 * L * d * bytes_per_elem


def decode_step_floor_s(spec, rows: float, live_kv_tokens: float, peaks: dict,
                        weight_bytes_per_param: float = 2.0, head_bytes_per_param: float = None) -> dict:
    """Least time of one decode step of ``rows`` sequences whose caches hold
    ``live_kv_tokens`` tokens together: the larger of bytes over bandwidth
    and FLOPs over peak, and which of the two it is."""
    L = spec["n_layer"]
    nbytes = weight_bytes(spec, weight_bytes_per_param, head_bytes_per_param) + kv_bytes_per_token(spec) * live_kv_tokens
    flops = layers_flops(spec, L, rows, live_kv_tokens) + head_flops(spec, rows)
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["flops_bf16"]
    return {"seconds": max(t_mem, t_flop), "bound": "hbm" if t_mem >= t_flop else "flops",
            "bytes": nbytes, "flops": flops}
