"""The plain reference of ``arch: sarvam_mla``: the layer equations of the
public config (sarvamai/sarvam-105b, ``model_type: sarvam_mla``) in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision, in
the **up-projected order only**: a head's keys and values are formed from the
latents and scored as any attention's are. No cache, no kernel, no absorbed
form, no batching (one sequence at a time), one layer's weights at a time;
nothing is imported from the program and the weights come from
``lib.weights_sarvam_mla``. For hidden state h [T, d] of one sequence at
positions 0..T-1:

    x   = RMSNorm(h)                                  eps 1e-6, scale only
    q_i = Wq_i x = [qn_i (dn) ; qr_i (dr)],  qr_i <- RoPE(qr_i)      no q compression
    [c ; kr] = Wdkv x ;  c <- RMSNorm_kv(c) ;  kr <- RoPE(kr)         one rotated key part, shared by all heads
    kn_i(j) = Wuk_i c_j ;  v_i(j) = Wuv_i c_j
    s_ij = scale * (qn_i . kn_i(j) + qr_i . kr_j),  j <= i
           scale = (dn + dr)^-1/2 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
    a   = Wo [softmax_j(s_ij) v_i(j)]_i ;  h1 = h + a ;  y = RMSNorm(h1)
    leading dense layers:  f = Wd (silu(Wg y) * Wu y)
    the others:            s = sigmoid(Wr y) (float32) ;  S = top-k of (s + b)       b chooses, does not weigh
                           g_e = factor_r * s_e / sum_{j in S} s_j
                           f = sum_{e in S, e held} g_e E_e(y) + Sh(y)                one shared expert, added
    h'  = h1 + f ;  logits = RMSNorm_f(h_L) Whead                     untied, over the vocabulary slice held
    RoPE: interleaved pairs, theta on dr dims, YaRN ("deepseek_yarn"): inv_freq_k blended between
          theta^(-2k/dr) and the same over ``factor`` between the dims where beta_fast and beta_slow turns
          fit the original context; cos/sin times mscale / mscale_all_dim = 1

Departures from the published model, each the configuration's: the experts not
held here add nothing (``experts_held`` from ``expert_offset``); the
vocabulary is the slice held; the program's untied head keeps a bias, which
the weights give as noughts and the reference leaves out. What is assumed of
the public description (the latent norm for ``use_qk_norm``, the bias for the
choice alone, normalised gates before the factor, no expert groups,
interleaved pairs, a float32 router) stands in the configuration's file.

Attention runs a segment of ``seg`` queries at a time against the keys up to
the segment's end, a group of heads and a block of 256 queries at a time, so
that a 41k context fits one chip and no key past a query is scored. ``mm`` is
the matrix multiplication every product goes through; the control of
``correct`` swaps in ``lib.reference.mm_fp8``. ``fault`` plants one of the
faults ``benchmarks/tests`` drive to ``"correct": false``: the reference then
computes what a program WITHOUT the fault computes, so the tests plant the
fault in the reference's place by the mirror of it."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from . import weights_sarvam_mla as WS
from .reference import HI, MATMULS, NEG, frozen


def mm_bf16(a, b):
    """Operands rounded to bfloat16, float32 accumulation: the precision the configuration states. Compared by
    nothing: what it reads against float32 says how far rounding alone moves this model (PERF.md section 6)."""
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.matmul(r(a), r(b), precision=HI)


MATMULS = {**MATMULS, "bf16": mm_bf16}

Q_BLOCK = 256
HEAD_GROUP = 8

#: what a planted fault changes in these equations (tests only): the bias left out of the choice, the
#: latent norm left out, the score scale without YaRN's m^2
FAULTS = ("bias_not_in_choice", "no_latent_norm", "scale_without_mscale")


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def inv_freq(spec):
    d, theta = spec["qk_rope_head_dim"], spec.get("rope_theta", 10000.0)
    base = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    factor = spec.get("rope_factor", 0.0)
    if factor <= 1.0:
        return base.astype(np.float32)
    dim_of = lambda turns: d * math.log(spec["rope_original_positions"] / (turns * 2 * math.pi)) / (2 * math.log(theta))
    lo = max(math.floor(dim_of(spec.get("rope_beta_fast", 32.0))), 0)
    hi = min(math.ceil(dim_of(spec.get("rope_beta_slow", 1.0))), d - 1)
    r_k = 1.0 - np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((1.0 - r_k) * base / factor + r_k * base).astype(np.float32)


def score_scale(spec, fault=None) -> float:
    scale = (spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]) ** -0.5
    factor, all_dim = spec.get("rope_factor", 0.0), spec.get("rope_mscale_all_dim", 0.0)
    if factor > 1.0 and all_dim and fault != "scale_without_mscale":
        scale *= (0.1 * all_dim * math.log(factor) + 1.0) ** 2
    return scale


def rotary(x, positions, freqs):
    """Interleaved pairs (2i, 2i+1) of the last axis. x [T, ..., d]."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs)  # [T, d/2]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def attend_segment(spec, p, qn, qr, c, kr, q0, mm, fault=None):
    """The queries qn [Q, H, dn], qr [Q, H, dr] at positions q0.. against the latents c [E, r], kr [E, dr] at
    positions 0..E-1 (E >= q0 + Q) -> [Q, H, dv]: keys and values up-projected a group of heads at a time."""
    Q, H, dn = qn.shape
    E, r = c.shape
    dv, G = spec["v_head_dim"], min(HEAD_GROUP, H)
    qb = min(Q_BLOCK, Q)
    nb = -(-Q // qb)
    pad = nb * qb - Q
    qn, qr = jnp.pad(qn, ((0, pad), (0, 0), (0, 0))), jnp.pad(qr, ((0, pad), (0, 0), (0, 0)))
    w_uk, w_uv = p["attn/w_uk"].reshape(r, H // G, G * dn), p["attn/w_uv"].reshape(r, H // G, G * dv)
    scale, kpos = score_scale(spec, fault), jnp.arange(E)

    def group(g):
        kn = mm(c, w_uk[:, g]).reshape(E, G, dn).transpose(1, 2, 0)  # [G, dn, E]
        v = mm(c, w_uv[:, g]).reshape(E, G, dv).transpose(1, 0, 2)  # [G, E, dv]

        def block(i):
            qn_b = jax.lax.dynamic_slice(qn, (i * qb, g * G, 0), (qb, G, dn)).transpose(1, 0, 2)
            qr_b = jax.lax.dynamic_slice(qr, (i * qb, g * G, 0), (qb, G, qr.shape[-1])).transpose(1, 0, 2)
            s = (mm(qn_b, kn) + mm(qr_b, kr.T)) * scale  # [G, qb, E]
            seen = kpos[None, :] <= (q0 + i * qb + jnp.arange(qb))[:, None]
            return mm(jax.nn.softmax(jnp.where(seen[None], s, NEG), -1), v)  # [G, qb, dv]

        return jax.lax.map(block, jnp.arange(nb))  # [nb, G, qb, dv]

    out = jax.lax.map(group, jnp.arange(H // G))  # [H/G, nb, G, qb, dv]
    return out.transpose(1, 3, 0, 2, 4).reshape(nb * qb, H, dv)[:Q]


def route(spec, y, router, bias, mm, fault=None):
    """Scores over ALL experts (float32), the top-k CHOSEN by score + bias, gates from the scores alone."""
    scores = jax.nn.sigmoid(mm(y, router))
    choose = scores if fault == "bias_not_in_choice" else scores + bias
    _, top_e = jax.lax.top_k(choose, spec["experts_per_token"])
    top_s = jnp.take_along_axis(scores, top_e, -1)
    return top_e, spec.get("routed_scaling_factor", 1.0) * top_s / top_s.sum(-1, keepdims=True)


def swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def held_experts(spec):
    lo = spec.get("expert_offset", 0)
    return tuple(range(lo, lo + (spec.get("experts_held") or spec["n_experts"])))


def experts(spec, p, y, mm, held, fault=None, rows=None):
    """(the routed part the experts ``held`` give, the shared expert's output, top_e, whether ``rows`` was too
    few). An expert runs on every token and its output counts where the token chose it; with ``rows`` (a static
    count under the tokens) it runs on the ``rows`` tokens sorted first by having chosen it, which is the same
    sum as long as no expert was chosen by more (the caller then asks again without ``rows``)."""
    top_e, gates = route(spec, y, p["moe/router"], p["moe/router_bias"], mm, fault)
    routed, over = jnp.zeros_like(y), jnp.bool_(False)
    for e in held:
        g_e = jnp.where(top_e == e, gates, 0.0).sum(-1)  # 0 where e was not chosen
        w = (p[f"moe/w_gate/{e}"], p[f"moe/w_up/{e}"], p[f"moe/w_down/{e}"])
        if rows is None:
            routed = routed + g_e[:, None] * swiglu(y, *w, mm)
        else:
            chose = (top_e == e).any(-1)
            idx = jnp.argsort(~chose, stable=True)[:rows]  # the tokens that chose e first, in order
            routed = routed.at[idx].add(g_e[idx, None] * swiglu(y[idx], *w, mm))
            over = over | (chose.sum() > rows)
    shared = jnp.zeros_like(y)
    for i in range(spec.get("n_shared_experts", 0)):  # added, each whole
        shared = shared + swiglu(y, p[f"shared/w_gate/{i}"], p[f"shared/w_up/{i}"], p[f"shared/w_down/{i}"], mm)
    return routed, shared, top_e, over


def project(spec, p, h, mm, fault=None, q0=0):
    """(qn [T, H, dn], qr [T, H, dr] rotated, c [T, r] normed, kr [T, dr] rotated) of the T tokens of one
    sequence from position ``q0`` on."""
    T = h.shape[0]
    H, r, dn, dr = spec["n_head"], spec["kv_lora_rank"], spec["qk_nope_head_dim"], spec["qk_rope_head_dim"]
    eps, pos, freqs = spec.get("layer_norm_epsilon", 1e-6), q0 + jnp.arange(T), inv_freq(spec)
    x = rms_norm(h, p["ln_1/scale"], eps)
    q = mm(x, p["attn/wq"]).reshape(T, H, dn + dr)
    ckr = mm(x, p["attn/w_dkv"])
    c = ckr[:, :r] if fault == "no_latent_norm" else rms_norm(ckr[:, :r], p["attn/kv_norm/scale"], eps)
    return q[..., :dn], rotary(q[..., dn:], pos, freqs), c, rotary(ckr[:, r:], pos, freqs)


def ffn(spec, p, h1, mm, held=None, fault=None, rows=None):
    """(f, top_e | None, whether ``rows`` was too few) on the attention's output h1 [T, d]."""
    y = rms_norm(h1, p["ln_2/scale"], spec.get("layer_norm_epsilon", 1e-6))
    if "mlp/w_in" in p:
        return swiglu(y, p["mlp/w_gate"], p["mlp/w_in"], p["mlp/w_out"], mm), None, jnp.bool_(False)
    routed, shared, top_e, over = experts(spec, p, y, mm, held_experts(spec) if held is None else held, fault, rows)
    return routed + shared, top_e, over


@functools.partial(jax.jit, static_argnames=("spec_t", "store_dtype", "dense"))
def _layer_weights(spec_t, store_dtype, dense, key, layer):
    return WS.layer_flat(dict(spec_t), key, layer, dense, store_dtype)


@functools.partial(jax.jit, static_argnames=("spec_t", "mm_name", "fault"))
def _project(spec_t, mm_name, fault, p, h, q0):
    return project(dict(spec_t), p, h, MATMULS[mm_name], fault, q0)


@functools.partial(jax.jit, static_argnames=("spec_t", "mm_name", "fault"))
def _attend(spec_t, mm_name, fault, p, qn, qr, c, kr, q0):
    return attend_segment(dict(spec_t), p, qn, qr, c, kr, q0, MATMULS[mm_name], fault)


@functools.partial(jax.jit, static_argnames=("spec_t", "mm_name", "fault", "rows"))
def _finish(spec_t, mm_name, fault, rows, p, h, a):
    spec, mm = dict(spec_t), MATMULS[mm_name]
    h1 = h + mm(a.reshape(a.shape[0], -1), p["attn/wo"])
    f, top_e, over = ffn(spec, p, h1, mm, fault=fault, rows=rows)
    return h1 + f, top_e, over


def finish(spec_t, mm_name, fault, p, h, a):
    """The rest of a layer on a segment: each expert on a quarter of the segment's tokens (four times what the
    router sends it on average); on every token where one expert was chosen by more."""
    spec = dict(spec_t)
    rows = None
    if h.shape[0] >= 1024 and spec["experts_per_token"] * 16 <= spec["n_experts"]:
        rows = h.shape[0] // 4
    out, top_e, over = _finish(spec_t, mm_name, fault, rows, p, h, a)
    if rows is not None and bool(over):
        out, top_e, _ = _finish(spec_t, mm_name, fault, None, p, h, a)
    return out, top_e


@functools.partial(jax.jit, static_argnames=("spec_t", "mm_name"))
def _logits(spec_t, mm_name, top, h):
    spec = dict(spec_t)
    return MATMULS[mm_name](rms_norm(h, top["ln_f"]["scale"], spec.get("layer_norm_epsilon", 1e-6)), top["lm_head"]["w"])


def spec_key(spec: dict) -> tuple:
    return frozen({k: tuple(v) if isinstance(v, list) else v for k, v in spec.items()})


def trunk(spec, key, tokens, store_dtype, mm_name, seg=4096, fault=None):
    """(hidden states [T, d] after the last layer, top_e [layers with experts, T, k]) of one sequence, one
    layer at a time and, inside a layer, a segment of ``seg`` tokens at a time (every part but attention is
    token by token, and attention reads the latents of all segments up to its own), so a sample of sequences
    compiles one shape of each part and one of attention per extent of keys; a layer's weights are made in a
    call of their own and never kept."""
    spec_t, store = spec_key(spec), jnp.dtype(store_dtype).name
    h = WS.top_params(spec, key, store_dtype, only=("embed",))["embed"]["wte"][tokens]
    T, chosen = h.shape[0], []
    for layer in range(spec["n_layer"]):
        p = _layer_weights(spec_t, store, WS.is_dense(spec, layer), key, jnp.int32(layer))
        starts = range(0, T, seg)
        parts = [_project(spec_t, mm_name, fault, p, h[q0:q0 + seg], jnp.int32(q0)) for q0 in starts]
        c, kr = (jnp.concatenate([part[i] for part in parts]) for i in (2, 3))
        done = [finish(spec_t, mm_name, fault, p, h[q0:q0 + seg],
                        _attend(spec_t, mm_name, fault, p, qn, qr, c[:q0 + seg], kr[:q0 + seg], jnp.int32(q0)))
                for q0, (qn, qr, _, _) in zip(starts, parts)]
        h = jnp.concatenate([d[0] for d in done])
        if done[0][1] is not None:
            chosen.append(jnp.concatenate([d[1] for d in done]))
    return h, jnp.stack(chosen)


def forward_logits(spec, seed, tokens, store_dtype=jnp.float32, mm_name="float32", positions=None, fault=None):
    """Logits [len(positions) or T, V] of one sequence (numpy)."""
    key = W.base_key(seed)
    h, _ = trunk(spec, key, jnp.asarray(tokens, jnp.int32), store_dtype, mm_name, fault=fault)
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return np.asarray(_logits(spec_key(spec), mm_name, WS.top_params(spec, key, store_dtype), h))


def served_gaps(spec, seed, rows, store_dtype, control=None, pad_to=4096, fault=None):
    """rows: [(prompt tokens, served tokens)], one sequence at a time. For every served token, how far its
    reference logit lies below the reference's best at that position; with ``control`` (a name in
    ``MATMULS``) the same gap for the token the control puts first. A sequence is padded at its end to a
    multiple of ``pad_to`` (later tokens change nothing before them), which is also the segment of queries
    attention runs at a time, so the sample compiles a few shapes. Returns (gaps, control gaps | None)."""
    key = W.base_key(seed)
    top = WS.top_params(spec, key, store_dtype)
    spec_t = spec_key(spec)
    gaps, cgaps = [], []
    for prompt, served in rows:
        seq = list(prompt) + list(served)
        T = -(-len(seq) // pad_to) * pad_to
        tokens = jnp.asarray(seq + [seq[-1]] * (T - len(seq)), jnp.int32)
        pos = np.arange(len(prompt) - 1, len(seq) - 1)  # position t predicts token t+1
        h, _ = trunk(spec, key, tokens, store_dtype, "float32", pad_to, fault)
        ref = np.asarray(_logits(spec_t, "float32", top, h[pos]))
        best = ref.max(-1)
        gaps.append(best - ref[np.arange(len(pos)), np.asarray(served)])
        if control is not None:
            hc, _ = trunk(spec, key, tokens, store_dtype, control, pad_to)
            ctl = np.asarray(jnp.argmax(_logits(spec_t, control, top, hc[pos]), -1))
            cgaps.append(best - ref[np.arange(len(pos)), ctl])
    return np.concatenate(gaps), (np.concatenate(cgaps) if cgaps else None)
