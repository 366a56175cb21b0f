"""Analytic model FLOPs + hardware peak: the MFU denominator/numerator.

The standard yardstick for "as fast as the hardware allows" is model
FLOPs utilization — achieved matmul flops over the chip's peak (the
hardware-utilization accounting popularized by PaLM-scale training
reports). These helpers are shared by the learn loops' per-iteration
``throughput/mfu`` estimate and the serve engine's KV sizing; one
formula, one place.

All estimates count matmul flops only and exclude the attention
quadratic terms (negligible against the projections at the short
RLHF sequence lengths these loops run); they slightly UNDERSTATE flops,
so MFU is conservative.
"""

from typing import Optional

#: bf16 peak matmul throughput per chip, keyed by the string JAX reports
#: as ``jax.devices()[0].device_kind``. Peaks: Google Cloud TPU
#: documentation (system architecture pages "TPU v4", "TPU v5e",
#: "TPU v5p", "TPU v6e"); "TPU v5 lite" was read off a v5e chip by
#: chip_smoke.py, the other spellings are the ones jax's own
#: mesh_utils / pallas tpu_info tables match on.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e
}


def peak_flops() -> Optional[float]:
    """Per-chip bf16 peak of the device JAX is running on. None on the
    CPU backend (tests, local drives — callers then omit the MFU figure);
    a TPU whose ``device_kind`` is not in the table is an error, not a
    default: an MFU divided by a guessed peak is worse than none."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    try:
        return PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak recorded for TPU device_kind "
            f"'{device.device_kind}'; add it to "
            f"trlx_tpu.telemetry.flops.PEAK_FLOPS with its source "
            f"(known: {sorted(PEAK_FLOPS)})"
        ) from None


def ppo_train_flops_per_token(spec, num_layers_unfrozen: int) -> int:
    """Matmul flops per (batch x seq) token of one PPO optimization step.

    Forward runs the full depth; backward only reaches the trainable top
    (gradients stop at the frozen-trunk boundary — the hydra split).
    """
    d, f, L, V = spec.d_model, spec.d_ff, spec.n_layer, spec.vocab_size
    per_layer = 2 * (4 * d * d + 2 * d * f)  # qkv+o projections, mlp in/out
    fwd = L * per_layer + 2 * d * V  # + logits projection
    k = num_layers_unfrozen if num_layers_unfrozen >= 0 else L
    bwd = 2 * (k * per_layer + 2 * d * V)
    return fwd + bwd


def decode_flops_per_token(spec) -> int:
    d, f, L, V = spec.d_model, spec.d_ff, spec.n_layer, spec.vocab_size
    return L * 2 * (4 * d * d + 2 * d * f) + 2 * d * V


def kv_bytes_per_token(spec, kv_dtype: str = "bf16") -> int:
    """Resident KV-pool bytes one committed token costs, by tier.

    ``bf16``: k+v, each ``head_dim`` 2-byte elements per kv-head per
    layer. ``int8`` (serve.kv_dtype): ``head_dim`` 1-byte codes plus one
    f32 scale per (token, kv-head) — the quantize_kv layout. The single
    source of truth for pool sizing: slots.pool_stats and the
    ``serve/kv_bytes_per_token`` gauge both read this. A latent-attention
    model keeps one latent a layer and no V: ``latent_page_width`` 2-byte
    numbers, the latent out to whole lane tiles, which is what a page
    takes (640 for the 576 that are read).
    """
    if spec.kv_lora_rank:
        return 2 * spec.n_layer * spec.latent_page_width
    per_head = (
        spec.head_dim + 4 if kv_dtype == "int8" else 2 * spec.head_dim
    )
    return 2 * spec.n_layer * spec.kv_heads * per_head


def ilql_train_flops_per_token(
    spec, num_layers_unfrozen: int, two_qs: bool = True
) -> int:
    """Matmul flops per token of one ILQL step: trunk forward + the
    vocab-wide LM/Q/target-Q/V head projections, backward through the
    trainable top + LM/Q/V heads (target-Q copies are frozen)."""
    d, f, L, V = spec.d_model, spec.d_ff, spec.n_layer, spec.vocab_size
    n_q = 2 if two_qs else 1
    per_layer = 2 * (4 * d * d + 2 * d * f)
    heads_fwd = (1 + 2 * n_q) * 2 * d * V + 2 * d  # lm + q + target_q, v
    k = num_layers_unfrozen if num_layers_unfrozen >= 0 else L
    fwd = L * per_layer + heads_fwd
    bwd = 2 * (k * per_layer + (1 + n_q) * 2 * d * V + 2 * d)
    return fwd + bwd


def mfu_estimate(
    tokens_per_sec: float, flops_per_token: float
) -> Optional[float]:
    """Achieved / peak flops, or None when either side is unknown."""
    peak = peak_flops()
    if not peak or not flops_per_token or not tokens_per_sec:
        return None
    return tokens_per_sec * flops_per_token / peak
