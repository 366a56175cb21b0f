"""The plain reference of ``arch: cohere2_moe``: the layer equations of the
public config (CohereLabs/command-a-plus-05-2026, ``model_type: cohere2_moe``)
in straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision.
No cache, no kernel, no batching (one sequence at a time), one layer's weights
at a time; nothing is imported from the program and the weights come from
``lib.weights_cohere2_moe``. For hidden state h [T, d] of one sequence:

    x  = LN(h)                      mean-centred, scale only, eps 1e-5
    a  = Wo . Attn(Wq x, Wk x, Wv x)     128 q heads over 8 kv heads of 128
           window layer: RoPE (theta, interleaved pairs, all dims), key j seen
                         by query i iff i - window < j <= i
           full layer:   no positions at all, causal
    s  = sigmoid(Wr x)              float32, over all experts
    S  = top-k of s;  g_e = s_e / sum_{j in S} s_j
    m  = sum_{e in S, e held} g_e E_e(x)  +  (1/n_shared) sum_i Sh_i(x)
    h' = h + a + m                  parallel block
    logits = logit_scale . LN_f(h_L) . Wte^T     over the vocabulary slice held

Departures from the published model, each the configuration's: the experts not
held here add nothing (``experts_held`` from ``expert_offset``); the vocabulary
is the slice held. ``m``'s average of the shared experts is read as their mean
added to the routed sum (the configuration's ``assumed``).

Attention and experts run in blocks so that a 29k context fits one chip: the
queries of one kv head's group, 256 at a time, against the keys they can see.
``mm`` is the matrix multiplication every product goes through; the control of
``correct`` swaps in ``lib.reference.mm_fp8``."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from . import weights_cohere2_moe as WC
from .reference import HI, MATMULS, NEG, frozen


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def mm_bf16(a, b):
    """Operands rounded to bfloat16, float32 accumulation: the precision the
    configuration states, for the note on router near-ties (compared by nothing)."""
    return jnp.matmul(_bf16(a), _bf16(b), precision=HI)


MATMULS = {**MATMULS, "bf16": mm_bf16}
Q_BLOCK = 256


def layer_norm(x, scale, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale


def rotary(x, positions, theta):
    """Interleaved pairs (2i, 2i+1) over every dim of each head. x [T, H, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv  # [T, hd/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def attention(q, k, v, window, mm):
    """q [T, H, hd], k/v [T, Hkv, hd] of one sequence at positions 0..T-1 ->
    [T, H, hd]. ``window`` 0: causal over everything; else the last ``window``
    keys. One block of queries of one kv head's group at a time."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    qb = min(Q_BLOCK, T)
    nb = -(-T // qb)
    pad = nb * qb - T
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    # a window layer's block sees the ``back`` keys before it and its own
    back = min(window, nb * qb) if window else 0
    k = jnp.pad(k, ((back, pad), (0, 0), (0, 0)))
    v = jnp.pad(v, ((back, pad), (0, 0), (0, 0)))

    def block(args):
        i, g = args
        qs = jax.lax.dynamic_slice(q, (i * qb, g * G, 0), (qb, G, hd))
        qpos = i * qb + jnp.arange(qb)
        if window:
            ks = jax.lax.dynamic_slice(k, (i * qb, g, 0), (back + qb, 1, hd))[:, 0]
            vs = jax.lax.dynamic_slice(v, (i * qb, g, 0), (back + qb, 1, hd))[:, 0]
            kpos = i * qb - back + jnp.arange(back + qb)
            seen = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] > qpos[:, None] - window)
        else:
            ks, vs = k[:, g], v[:, g]
            kpos = jnp.arange(k.shape[0])
            seen = kpos[None, :] <= qpos[:, None]
        s = mm(qs.transpose(1, 0, 2), ks.T) / math.sqrt(hd)  # [G, qb, keys]
        p = jax.nn.softmax(jnp.where(seen[None], s, NEG), -1)
        return mm(p, vs).transpose(1, 0, 2)  # [qb, G, hd]

    ii, gg = jnp.meshgrid(jnp.arange(nb), jnp.arange(Hkv), indexing="ij")
    out = jax.lax.map(block, (ii.reshape(-1), gg.reshape(-1)))  # [nb*Hkv, qb, G, hd]
    out = out.reshape(nb, Hkv, qb, G, hd).transpose(0, 2, 1, 3, 4).reshape(nb * qb, H, hd)
    return out[:T]


def route(spec, x, router, mm):
    """Scores over ALL experts (float32), the top-k and their normalised gates."""
    scores = jax.nn.sigmoid(mm(x, router))
    top_s, top_e = jax.lax.top_k(scores, spec["experts_per_token"])
    return top_e, top_s / top_s.sum(-1, keepdims=True)


def swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def experts(spec, p, x, mm, held):
    """The routed part that the experts ``held`` (indices in the uncut model)
    give, and the shared experts' mean. Returns (routed, shared, top_e)."""
    top_e, gates = route(spec, x, p["moe/router"], mm)
    routed = jnp.zeros_like(x)
    for e in held:
        g_e = jnp.where(top_e == e, gates, 0.0).sum(-1)  # 0 where e was not chosen
        routed = routed + g_e[:, None] * swiglu(x, p[f"moe/w_gate/{e}"], p[f"moe/w_up/{e}"], p[f"moe/w_down/{e}"], mm)
    n = spec.get("n_shared_experts", 0)
    shared = jnp.zeros_like(x)
    for i in range(n):
        shared = shared + swiglu(x, p[f"shared/w_gate/{i}"], p[f"shared/w_up/{i}"], p[f"shared/w_down/{i}"], mm) / n
    return routed, shared, top_e


def kind_of(spec, layer: int) -> str:
    pattern = spec.get("layer_pattern") or ("full",)
    return pattern[layer % len(pattern)]


def held_experts(spec):
    lo = spec.get("expert_offset", 0)
    return tuple(range(lo, lo + (spec.get("experts_held") or spec["n_experts"])))


def block(spec, kind, p, h, mm, held=None):
    """One layer on h [T, d]. Returns (h', top_e [T, k])."""
    T, d = h.shape
    H = spec["n_head"]
    hd = spec.get("head_size") or d // H
    Hkv = spec.get("n_kv_heads") or H
    x = layer_norm(h, p["ln_1/scale_centred"], spec.get("layer_norm_epsilon", 1e-5))
    q = mm(x, p["attn/wq"]).reshape(T, H, hd)
    k = mm(x, p["attn/wk"]).reshape(T, Hkv, hd)
    v = mm(x, p["attn/wv"]).reshape(T, Hkv, hd)
    if kind in tuple(spec.get("rope_kinds", ("full", "window"))):
        pos = jnp.arange(T)
        q, k = rotary(q, pos, spec.get("rope_theta", 10000.0)), rotary(k, pos, spec.get("rope_theta", 10000.0))
    a = mm(attention(q, k, v, spec["window"] if kind == "window" else 0, mm).reshape(T, H * hd), p["attn/wo"])
    routed, shared, top_e = experts(spec, p, x, mm, held_experts(spec) if held is None else held)
    return h + a + routed + shared, top_e


@functools.partial(jax.jit, static_argnames=("spec_t", "store_dtype"))
def _layer_weights(spec_t, store_dtype, key, layer):
    return WC.layer_flat(dict(spec_t), key, layer, store_dtype)


@functools.partial(jax.jit, static_argnames=("spec_t", "kind", "mm_name"))
def _block(spec_t, kind, mm_name, p, h):
    return block(dict(spec_t), kind, p, h, MATMULS[mm_name])


@functools.partial(jax.jit, static_argnames=("spec_t", "mm_name"))
def _logits(spec_t, mm_name, top, h):
    spec = dict(spec_t)
    hn = layer_norm(h, top["ln_f"]["scale_centred"], spec.get("layer_norm_epsilon", 1e-5))
    return spec.get("logit_scale", 1.0) * MATMULS[mm_name](hn, top["embed"]["wte"].T)


def spec_key(spec: dict) -> tuple:
    """The spec as a hashable static argument (its lists as tuples)."""
    return frozen({k: tuple(v) if isinstance(v, list) else v for k, v in spec.items()})


def trunk(spec, key, tokens, store_dtype, mm_name):
    """(hidden states [T, d] after every layer, top_e [L, T, k]) of one
    sequence, one layer at a time; a layer's weights are made in a call of
    their own and never kept."""
    spec_t, store = spec_key(spec), jnp.dtype(store_dtype).name
    h = WC.top_params(spec, key, store_dtype, only=("embed",))["embed"]["wte"][tokens]
    chosen = []
    for layer in range(spec["n_layer"]):
        h, top_e = _block(spec_t, kind_of(spec, layer), mm_name,
                          _layer_weights(spec_t, store, key, jnp.int32(layer)), h)
        chosen.append(top_e)
    return h, jnp.stack(chosen)


def forward_logits(spec, seed, tokens, store_dtype=jnp.float32, mm_name="float32", positions=None):
    """Logits [len(positions) or T, V] of one sequence (numpy)."""
    key = W.base_key(seed)
    h, _ = trunk(spec, key, jnp.asarray(tokens, jnp.int32), store_dtype, mm_name)
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return np.asarray(_logits(spec_key(spec), mm_name, WC.top_params(spec, key, store_dtype), h))


def served_gaps(spec, seed, rows, store_dtype, control=None, pad_to=1024, router_note=False):
    """rows: [(prompt tokens, served tokens)], one sequence at a time. For every
    served token, how far its reference logit lies below the reference's best
    at that position; with ``control`` (a name in ``MATMULS``) the same gap for
    the token the control puts first. ``router_note`` also counts the (token,
    layer) pairs whose top-k set a bfloat16-operand run of these equations
    picks otherwise than float32 does. A sequence is padded at its end to a
    multiple of ``pad_to`` (later tokens change nothing before them), so the
    sample compiles a few shapes. Returns (gaps, control gaps | None, note)."""
    key = W.base_key(seed)
    top = WC.top_params(spec, key, store_dtype)
    spec_t = spec_key(spec)
    gaps, cgaps, differ, pairs = [], [], 0, 0
    for prompt, served in rows:
        seq = list(prompt) + list(served)
        T = -(-len(seq) // pad_to) * pad_to
        tokens = jnp.asarray(seq + [seq[-1]] * (T - len(seq)), jnp.int32)
        pos = np.arange(len(prompt) - 1, len(seq) - 1)  # position t predicts token t+1
        h, top_e = trunk(spec, key, tokens, store_dtype, "float32")
        ref = np.asarray(_logits(spec_t, "float32", top, h[pos]))
        best = ref.max(-1)
        gaps.append(best - ref[np.arange(len(pos)), np.asarray(served)])
        if control is not None:
            hc, _ = trunk(spec, key, tokens, store_dtype, control)
            ctl = np.asarray(jnp.argmax(_logits(spec_t, control, top, hc[pos]), -1))
            cgaps.append(best - ref[np.arange(len(pos)), ctl])
        if router_note:
            _, top_b = trunk(spec, key, tokens, store_dtype, "bf16")
            same = (jnp.sort(top_e[:, :len(seq)], -1) == jnp.sort(top_b[:, :len(seq)], -1)).all(-1)
            differ += int((~same).sum())
            pairs += int(same.size)
    note = {"router_topk_set_differs_share": differ / pairs, "token_layer_pairs": pairs} if pairs else {}
    return np.concatenate(gaps), (np.concatenate(cgaps) if cgaps else None), note
