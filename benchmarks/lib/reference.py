"""The plain reference: the published equations of the two architectures
(GPT-2 and GPT-J blocks), PPO's loss and AdamW, in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision. No cache, no
batching tricks, no kernels; nothing is imported from the program and the
weights come from ``lib.weights`` (one block at a time, so 6B fits).

``mm`` is the matrix multiplication every product goes through. The control
of ``correct`` swaps in ``mm_fp8`` (operands rounded to e4m3 with one scale
per tensor): the nearest precision below the bfloat16 the cells state."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HI = jax.lax.Precision.HIGHEST
NEG = -1e9


def mm_f32(a, b):
    return jnp.matmul(a, b, precision=HI)


def _e4m3(x):
    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm_fp8(a, b):
    return jnp.matmul(_e4m3(a), _e4m3(b), precision=HI)


MATMULS = {"float32": mm_f32, "fp8": mm_fp8}


# ---------------------------------------------------------------- the model

def layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rotary_interleaved(x, positions, rot, theta=10000.0):
    """GPT-J: pairs (2i, 2i+1) of the first ``rot`` dims of each head are rotated."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    xr = x[..., :rot]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(xr.shape)
    return jnp.concatenate([out, x[..., rot:]], -1)


def block(spec, p, h, bias, positions, mm):
    B, T, D = h.shape
    H = spec["n_head"]
    hd = D // H
    eps = spec.get("layer_norm_epsilon", 1e-5)
    gptj = spec["arch"] == "gptj"
    a, m = p["attn"], p["mlp"]
    x = layer_norm(h, p["ln_1"], eps)
    q, k, v = (
        (mm(x, a[w]) + (a[b] if b in a else 0.0)).reshape(B, T, H, hd)
        for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv"))
    )
    if gptj:
        rot = spec.get("rotary_dim") or hd
        q, k = rotary_interleaved(q, positions, rot), rotary_interleaved(k, positions, rot)
    s = mm(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) / math.sqrt(hd) + bias
    o = mm(jax.nn.softmax(s, -1), v.transpose(0, 2, 1, 3))
    att = mm(o.transpose(0, 2, 1, 3).reshape(B, T, D), a["wo"]) + (a["bo"] if "bo" in a else 0.0)

    def mlp(u):
        return mm(gelu_new(mm(u, m["w_in"]) + m["b_in"]), m["w_out"]) + m["b_out"]

    if gptj:  # parallel block: attention and MLP both read ln_1's output
        return h + att + mlp(x)
    h = h + att
    return h + mlp(layer_norm(h, p["ln_2"], eps))


def mask_bias(mask):
    T = mask.shape[1]
    allowed = jnp.tril(jnp.ones((T, T), bool))[None] & (mask[:, None, :] > 0)
    return jnp.where(allowed, 0.0, NEG)[:, None]


def positions_of(mask):
    return jnp.maximum(jnp.cumsum(mask, -1) - 1, 0)


def embed(emb, tokens, positions):
    h = emb["wte"][tokens]
    return h + emb["wpe"][positions] if "wpe" in emb else h


def logits_of(spec, top, h, mm):
    hn = layer_norm(h, top["ln_f"], spec.get("layer_norm_epsilon", 1e-5))
    if spec.get("tie_lm_head", True):
        return hn, mm(hn, top["embed"]["wte"].T)
    return hn, mm(hn, top["lm_head"]["w"]) + top["lm_head"]["b"]


def frozen(d: dict) -> tuple:
    """A dict of plain values as a hashable static argument (and back: ``dict(t)``)."""
    return tuple(sorted(d.items()))


@functools.partial(jax.jit, static_argnames=("spec_t", "store_dtype"))
def _layer_weights(spec_t, store_dtype, key, layer):
    return W.layer_params(dict(spec_t), key, layer, store_dtype)


@functools.partial(jax.jit, static_argnames=("spec_t", "mm_name"))
def _block(spec_t, mm_name, p, h, bias, positions):
    return block(dict(spec_t), p, h, bias, positions, MATMULS[mm_name])


@functools.partial(jax.jit, static_argnames=("spec_t", "mm_name"))
def _logits(spec_t, mm_name, top, h):
    return logits_of(dict(spec_t), top, h, MATMULS[mm_name])[1]


def trunk(spec, key, tokens, mask, lo, hi, store_dtype, mm_name):
    """Hidden states after blocks lo..hi-1, one block at a time; each block's
    weights are made in a call of their own and never kept (made inside the
    block's program, XLA:TPU wants 13.5 GiB of temporaries at 6B widths; apart,
    under 1 GiB). Every array is an argument, so all layers, rows and seeds
    share two compiled programs (and the persistent cache finds them again)."""
    positions = positions_of(mask)
    bias = mask_bias(mask)
    h = embed(W.top_params(spec, key, False, store_dtype, only=("embed",))["embed"], tokens, positions)
    spec_t, store = frozen(spec), jnp.dtype(store_dtype).name
    for layer in range(lo, hi):
        h = _block(spec_t, mm_name, _layer_weights(spec_t, store, key, jnp.int32(layer)), h, bias, positions)
    return h


# ---------------------------------------------------------------- serving

def served_gaps(spec, seed, rows, store_dtype, control=None, row_block=8):
    """rows: [(prompt tokens, served tokens)]. For every served token, how far
    its reference logit lies below the reference's best at that position.
    With ``control`` (the name of a matmul in ``MATMULS``), also the same gap
    for the token the control puts first. Returns (gaps, control_gaps | None)
    as flat numpy arrays."""
    key = W.base_key(seed)
    top = W.top_params(spec, key, False, store_dtype)
    spec_t = frozen(spec)
    T = max(len(p) + len(s) for p, s in rows)
    T = -(-T // 64) * 64  # few distinct shapes from run to run: the compile cache finds them
    gaps, cgaps = [], []
    for i in range(0, len(rows), row_block):
        part = rows[i:i + row_block]
        toks = np.zeros((row_block, T), np.int32)
        mask = np.zeros((row_block, T), np.int32)
        mask[len(part):, 0] = 1  # rows that only fill the block
        for j, (p, s) in enumerate(part):
            seq = list(p) + list(s)
            toks[j, :len(seq)] = seq
            mask[j, :len(seq)] = 1
        toks, mask = jnp.asarray(toks), jnp.asarray(mask)
        ref = np.asarray(_logits(spec_t, "float32", top,
                                 trunk(spec, key, toks, mask, 0, spec["n_layer"], store_dtype, "float32")))
        ctl = None
        if control is not None:
            hc = trunk(spec, key, toks, mask, 0, spec["n_layer"], store_dtype, control)
            ctl = np.asarray(jnp.argmax(_logits(spec_t, control, top, hc), -1))
        for j, (p, s) in enumerate(part):
            pos = np.arange(len(p) - 1, len(p) + len(s) - 1)  # position t predicts token t+1
            best = ref[j, pos].max(-1)
            gaps.append(best - ref[j, pos, np.asarray(s)])
            if ctl is not None:
                cgaps.append(best - ref[j, pos, ctl[j, pos]])
    return np.concatenate(gaps), (np.concatenate(cgaps) if cgaps else None)


# ---------------------------------------------------------------- PPO

def gae(values, rewards, gamma, lam):
    """Reverse recurrence A_t = delta_t + gamma*lam*A_{t+1}, V beyond the end = 0."""
    T = values.shape[1]
    v_next = jnp.concatenate([values[:, 1:], jnp.zeros_like(values[:, :1])], 1)
    deltas = rewards + gamma * v_next - values
    adv, out = jnp.zeros_like(values[:, 0]), []
    for t in reversed(range(T)):
        adv = deltas[:, t] + gamma * lam * adv
        out.append(adv)
    adv = jnp.stack(out[::-1], 1)
    return adv, adv + values


def whiten(x):
    mean = x.mean()
    var = ((x - mean) ** 2).sum() / (x.size - 1)
    return (x - mean) / jnp.sqrt(var + 1e-8)


def ppo_loss(logp, vpred, old_logp, old_values, adv, returns, hp):
    vclip = jnp.clip(vpred, old_values - hp["cliprange_value"], old_values + hp["cliprange_value"])
    vf = 0.5 * jnp.maximum((vpred - returns) ** 2, (vclip - returns) ** 2).mean()
    ratio = jnp.exp(logp - old_logp)
    pg = jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 1 - hp["cliprange"], 1 + hp["cliprange"])).mean()
    return pg + hp["vf_coef"] * vf


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(lambda v: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))), tree)


def leaf_norms(tree) -> dict:
    return {k: float(v) for k, v in W.flatten(jax.device_get(_norms(tree))).items()}


def _ppo_forward(spec, hp, mm, theta, c):
    """Log-probabilities and values of the response from the trunk's output ``c['h']`` through the trainable top."""
    P, G, k = hp["prompt"], hp["gen"], hp["k_unfrozen"]
    h = c["h"]
    for i in range(k):
        h = block(spec, jax.tree_util.tree_map(lambda x: x[i], theta["blocks"]), h, c["bias"], c["positions"], mm)
    hn, logits = logits_of(spec, {"embed": c["embed"], **theta}, h[:, P - 1:P + G - 1], mm)
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1), c["response"][..., None], -1)[..., 0]
    vh = theta["v_head"]
    values = (mm(jax.nn.relu(mm(hn, vh["w1"]) + vh["b1"]), vh["w2"]) + vh["b2"])[..., 0]
    return logp, values


@functools.partial(jax.jit, static_argnames=("spec_t", "hp_t", "mm_name"))
def _ppo_score(spec_t, hp_t, mm_name, theta, c):
    return _ppo_forward(dict(spec_t), dict(hp_t), MATMULS[mm_name], theta, c)


@functools.partial(jax.jit, static_argnames=("spec_t", "hp_t", "mm_name"))
def _ppo_step(spec_t, hp_t, mm_name, theta, mu, nu, t, c, old):
    """One AdamW step on the PPO loss, gradient clipped by its global norm first."""
    spec, hp, mm = dict(spec_t), dict(hp_t), MATMULS[mm_name]

    def loss_fn(th):
        logp, values = _ppo_forward(spec, hp, mm, th, c)
        return ppo_loss(logp, values, old["logp"], old["values"], old["adv"], old["returns"], hp)

    loss, g = jax.value_and_grad(loss_fn)(theta)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
    g = jax.tree_util.tree_map(lambda x: x * jnp.minimum(1.0, hp["grad_clip"] / (gnorm + 1e-30)), g)
    mu = jax.tree_util.tree_map(lambda m_, x: hp["b1"] * m_ + (1 - hp["b1"]) * x, mu, g)
    nu = jax.tree_util.tree_map(lambda n_, x: hp["b2"] * n_ + (1 - hp["b2"]) * x * x, nu, g)
    c1, c2 = 1 - hp["b1"] ** t, 1 - hp["b2"] ** t
    theta = jax.tree_util.tree_map(
        lambda p, m_, n_: p - hp["lr"] * ((m_ / c1) / (jnp.sqrt(n_ / c2) + hp["eps"]) + hp["weight_decay"] * p),
        theta, mu, nu)
    return theta, mu, nu, loss, g


def ppo_reference(spec, seed, tokens, scores, hp, mm="float32", rows=None, frozen_dtype=jnp.float32):
    """Follow the program's first cycle from its sampled tokens alone: the
    scoring forward (log-probabilities and values of the response), the
    rewards, GAE, and ``ppo_epochs`` AdamW steps on the trainable top.
    ``mm`` names the matmul (``MATMULS``). ``rows`` keeps a subset of the batch
    (the half-batch fault). ``frozen_dtype`` is the type the configuration keeps
    the frozen trunk, the embedding and the reference branch in; the trainable
    top is float32. Returns numpy readings; per-leaf norms keyed like the
    program's tree."""
    key = W.base_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    scores = jnp.asarray(scores, jnp.float32)
    if rows is not None:
        tokens, scores = tokens[rows], scores[rows]
    P, k, L = hp["prompt"], hp["k_unfrozen"], spec["n_layer"]
    spec_t, hp_t = frozen(spec), frozen(hp)
    mask = jnp.ones_like(tokens)
    top = W.top_params(spec, key, True)
    theta = {"blocks": W.stacked_layers(spec, key, L - k, L, jnp.float32, jnp.float32),
             "ln_f": top["ln_f"], "v_head": top["v_head"]}
    if "lm_head" in top:
        theta["lm_head"] = top["lm_head"]
    c = {"h": trunk(spec, key, tokens, mask, 0, L - k, frozen_dtype, mm),
         "bias": mask_bias(mask), "positions": positions_of(mask), "response": tokens[:, P:],
         "embed": W.top_params(spec, key, False, frozen_dtype, only=("embed",))["embed"]}
    old_logp, old_values = _ppo_score(spec_t, hp_t, mm, theta, c)
    # the reference branch: the same top, as the configuration keeps it (rounded to frozen_dtype)
    theta_ref = jax.tree_util.tree_map(lambda x: x.astype(frozen_dtype).astype(jnp.float32), theta)
    ref_logp, _ = _ppo_score(spec_t, hp_t, mm, theta_ref, c)
    rewards = (-hp["kl_coef"] * (old_logp - ref_logp)).at[:, -1].add(scores)
    adv, returns = gae(old_values, rewards, hp["gamma"], hp["lam"])
    old = {"logp": old_logp, "values": old_values, "adv": whiten(adv), "returns": returns}

    zeros = jax.tree_util.tree_map(jnp.zeros_like, theta)
    th, mu, nu, losses, g1 = theta, zeros, zeros, [], None
    for t in range(1, hp["ppo_epochs"] + 1):
        th, mu, nu, loss, g = _ppo_step(spec_t, hp_t, mm, th, mu, nu, jnp.float32(t), c, old)
        losses.append(float(loss))
        if g1 is None:
            g1 = leaf_norms(g)
    delta = jax.tree_util.tree_map(lambda a, b: a - b, th, theta)
    return {"old_logp": np.asarray(old_logp), "old_values": np.asarray(old_values), "rewards": np.asarray(rewards),
            "losses": losses, "g1": g1, "mu": leaf_norms(mu), "delta": leaf_norms(delta)}


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, |norm_prog - norm_ref| over the larger of the reference's
    norm of that leaf and of the median leaf."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30) for n in names}


def worst_leaf_gap(prog: dict, ref: dict, keep=None):
    """The largest of ``leaf_gaps`` and its leaf: (gap, leaf)."""
    gaps = leaf_gaps(prog, ref, keep)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def moving_leaves(g1: dict, floor=1e-3) -> set:
    """Leaves whose first reference gradient is at least ``floor`` of the median
    leaf's: the others (a key's bias under softmax) move by round-off alone."""
    med = float(np.median(list(g1.values())))
    return {n for n, v in g1.items() if v >= floor * med}
