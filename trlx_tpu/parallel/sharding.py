"""Partition specs for the framework's parameter pytrees and data batches.

The param trees (trlx_tpu.models.policy / trlx_tpu.models.ilql) stack
per-layer tensors on a leading layer axis, so specs are assigned by leaf
*name* and rank, one rule set for every model family:

- Megatron-style tensor parallelism over ``tp``: in-projections
  (wq/wk/wv, mlp w_in, head w1) are column-parallel (output dim sharded);
  out-projections (wo, mlp w_out, head w2) are row-parallel (input dim
  sharded). XLA GSPMD inserts the psum after row-parallel matmuls.
- ZeRO-equivalent sharding over ``fsdp``: the other big dim of each matrix
  is sharded; XLA all-gathers on use and reduce-scatters gradients —
  functionally the reference's DeepSpeed ZeRO-3
  (reference: trlx/model/nn/ilql_models.py:38-41,201-214) without an engine.
- Batches shard over ``(dp, fsdp)`` on the leading (batch) dim, so fsdp
  devices double as data-parallel workers.

Optimizer state is NOT spec'd here: trainers build it with
``jax.jit(opt.init)(sharded_params)`` and GSPMD propagates the param
shardings into the adam moments automatically.
"""

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Dict[str, Any]

# (leaf name, rank) -> PartitionSpec. Rank-3 entries are stacked per-layer
# tensors [L, in, out]; the layer (scan) axis is never sharded — lax.scan
# slices it every step, and sharding it would force a per-step all-gather.
_MATRIX_RULES = {
    # attention projections [L, D, D]
    "wq": P(None, "fsdp", "tp"),
    "wk": P(None, "fsdp", "tp"),
    "wv": P(None, "fsdp", "tp"),
    "wo": P(None, "tp", "fsdp"),
    # mlp [L, D, F] / [L, F, D]
    "w_in": P(None, "fsdp", "tp"),
    "w_gate": P(None, "fsdp", "tp"),  # llama swiglu gate, column-parallel
    "w_out": P(None, "tp", "fsdp"),
}

_VECTOR_RULES = {
    # column-parallel biases live on the tp-sharded output dim
    "bq": P(None, "tp"),
    "bk": P(None, "tp"),
    "bv": P(None, "tp"),
    "b_in": P(None, "tp"),
    # row-parallel biases are added after the psum — replicated
    "bo": P(None, None),
    "b_out": P(None, None),
}


def spec_for_leaf(path_names: Tuple[str, ...], ndim: int) -> P:
    """PartitionSpec for one leaf, by its key path and rank."""
    name = path_names[-1] if path_names else ""
    parent = path_names[-2] if len(path_names) > 1 else ""

    if name in _MATRIX_RULES and ndim == 3:
        return _MATRIX_RULES[name]
    if name in _VECTOR_RULES and ndim == 2:
        return _VECTOR_RULES[name]

    # embeddings
    if name == "wte":  # [V, D] — the largest single matrix
        return P("tp", "fsdp")
    if name == "wpe":  # [N_pos, D]
        return P(None, "fsdp")

    # untied lm head {w: [D, V], b: [V]}
    if parent == "lm_head":
        if name == "w" and ndim == 2:
            return P("fsdp", "tp")
        if name == "b" and ndim == 1:
            return P("tp")

    # MLP heads (value / Q): w1 [D, 2D] column-parallel, w2 [2D, out]
    # row-parallel (out is 1 for V, vocab for Q)
    if parent.endswith("_head"):
        if name == "w1" and ndim == 2:
            return P("fsdp", "tp")
        if name == "b1" and ndim == 1:
            return P("tp")
        if name == "w2" and ndim == 2:
            return P("tp", None)
        if name == "b2" and ndim == 1:
            return P(None)

    # layernorms, scalars, anything unmatched: replicated
    return P()


def _path_names(key_path) -> Tuple[str, ...]:
    names = []
    for k in key_path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):  # namedtuple fields (optax states)
            names.append(str(k.name))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return tuple(names)


def _fit_spec_to_shape(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes from dims they don't divide evenly.

    XLA's device_put requires even partitions; odd vocab sizes (50257, 257)
    and narrow head outputs would otherwise reject the whole tree. Dropping
    the axis replicates that dim — correct, just less sharded.
    """
    dims = []
    for i, entry in enumerate(spec):
        if entry is None:
            dims.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for ax in axes:
            size *= mesh.shape[ax]
        dims.append(entry if shape[i] % size == 0 else None)
    return P(*dims)


def param_sharding_specs(params: Params, mesh: Optional[Mesh] = None) -> Params:
    """Pytree of PartitionSpec matching `params`' structure. With a mesh,
    specs are validated against leaf shapes (non-divisible dims fall back
    to replication)."""

    def leaf_spec(kp, x):
        names = _path_names(kp)
        ndim = getattr(x, "ndim", 0)
        spec = spec_for_leaf(names, ndim)
        # frozen-trunk blocks under a pipelined mesh: the stacked layer
        # axis shards over pp (each stage HOLDS only its L/pp layers —
        # the parameter split is what pp buys; pp_apply_blocks consumes
        # exactly this placement). Overlays the leading dim of whatever
        # rule matched; layernorm leaves (catch-all P()) widen to rank.
        if (
            mesh is not None
            and mesh.shape.get("pp", 1) > 1
            and "frozen_base" in names
            and "blocks" in names
            and ndim >= 1
        ):
            entries = list(spec) + [None] * (ndim - len(spec))
            entries[0] = "pp"
            spec = P(*entries)
        if mesh is not None:
            spec = _fit_spec_to_shape(spec, x.shape, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def param_shardings(mesh: Mesh, params: Params) -> Params:
    """Pytree of NamedSharding matching `params`' structure."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        param_sharding_specs(params, mesh),
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(mesh: Mesh, params: Params) -> Params:
    """Place `params` on the mesh under the framework's specs."""
    return jax.device_put(params, param_shardings(mesh, params))


def sharded_opt_init(opt, mesh: Optional[Mesh], trainable: Params):
    """Build optimizer state with the params' shardings (ZeRO-equivalent
    optimizer-state sharding, reference: DeepSpeed ZeRO via Accelerate).

    `jit(opt.init)` alone won't do: the moments are zeros, value-independent
    of the params, so XLA places them wherever it likes. The moment subtrees
    (mu/nu) structurally mirror the param tree — leaf key paths end in the
    same names — so the same path-based rules produce their specs, passed as
    explicit out_shardings. Scalar counts come out replicated.
    """
    if mesh is None:
        return opt.init(trainable)
    abstract = jax.eval_shape(opt.init, trainable)
    out_shardings = param_shardings(mesh, abstract)
    return jax.jit(opt.init, out_shardings=out_shardings)(trainable)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a data array: leading (batch) dim over (dp, fsdp)."""
    return NamedSharding(mesh, P(("dp", "fsdp")))


def shard_batch(mesh: Mesh, tree):
    """Place every array in `tree` with its batch dim over (dp, fsdp).

    Works for token/mask arrays and whole PPORLBatch/ILQLBatch pytrees;
    leaves must share a common leading batch dimension, divisible by
    dp * fsdp (validated here with a config-level error rather than a
    device_put failure mid-rollout).

    Multi-host: every process must pass the SAME global array —
    guaranteed here because the framework's loaders are seed-deterministic
    (each host materializes the identical batch and device_put places only
    its addressable shards). This replicated-loading design replaces the
    reference's per-rank split DataLoaders (Accelerate's prepare).
    """
    n_data = mesh.shape["dp"] * mesh.shape["fsdp"]
    for leaf in jax.tree_util.tree_leaves(tree):
        b = leaf.shape[0] if getattr(leaf, "ndim", 0) else 0
        if b % n_data != 0:
            raise ValueError(
                f"batch dimension {b} is not divisible by dp*fsdp = "
                f"{n_data} (mesh {dict(mesh.shape)}); pick batch_size / "
                f"chunk_size / eval n as a multiple of {n_data}"
            )
    # one device_put for the whole tree (a single sharding broadcasts over
    # all leaves) — per-leaf puts each pay a host<->device round trip
    return jax.device_put(tree, batch_sharding(mesh))


def relayout_for_decode(params: Params,
                        min_bytes: int = 2 << 30) -> Params:
    """Frozen-trunk attention projections (wq/wk/wv) moved to the
    transposed at-rest layout (major_to_minor (0, 2, 1)) the decode
    matvecs want.

    Measured on v5e via AOT memory_analysis (gptj-shape d2048/L24):
    with default row-major storage the fused rollout materializes
    full-stack layout copies of all three projections as HLO temps
    (1.05 GB -> 0.48 GB once relayouted; at gpt-j-6B the copies are
    ~2.5 GB — the single-chip OOM margin). The train-side cost is at
    most one stack copied back under full fwd+bwd, and the hydra split
    makes the frozen trunk forward-only in the train step, so in
    practice it's free. Decode throughput also gains: the per-program
    copies are re-materialized HBM traffic on every rollout dispatch.

    Only the AOT compile path honors custom layouts, and its
    Compiled.call dispatch skips jit's C++ fastpath (a Python-side
    signature hash per call). That trade only pays when the copies
    rival HBM headroom, so the pass is SIZE-GATED: a no-op (same object
    returned — callers key the aot_jit decision on identity) unless the
    target stacks total at least `min_bytes` (default 2 GiB: gpt-j-6B's
    2.6 GB qualifies; gpt2-xl's 1.4 GB and the 124M headline stay on
    default layouts + fast jit dispatch). Donated train steps pass the
    frozen subtree through unchanged, so the layout survives updates.
    Checkpoint restore rebuilds default layouts — callers re-apply after
    a restore if they care. DONATES the source stacks (the caller's
    input tree must be re-bound from the return value). A runtime that
    refuses the relayout raises: past the size gate the layout is the
    margin between fitting and OOM, so a silent default-layout run
    would only fail later and further from the cause."""
    from jax.experimental.layout import Format, Layout

    blocks = params.get("frozen_base", {}).get("blocks")
    if not blocks or "attn" not in blocks:
        return params
    attn = blocks["attn"]
    try:
        platform = next(iter(attn["wq"].devices())).platform
    except Exception:
        platform = "cpu"
    if platform == "cpu":
        # The CPU backend ACCEPTS custom layouts but mishandles them
        # downstream: an Orbax save/restore round trip of relayouted
        # params came back with transposed VALUES (bytes reinterpreted
        # as row-major), and lr=0 train steps stopped being bit-stable.
        # The optimization only matters on TPU-class backends; CPU keeps
        # default layouts.
        return params
    targets = {
        name: attn[name]
        for name in ("wq", "wk", "wv")
        if name in attn and getattr(attn[name], "ndim", 0) == 3
    }
    if not targets:
        return params
    total = sum(x.size * x.dtype.itemsize for x in targets.values())
    if total < min_bytes:
        return params
    # one leaf at a time WITH source donation: near the HBM limit the
    # whole-tree form holds old + new copies of all three stacks at once
    # (+2.6 GB at gpt-j-6B — itself an OOM); donating bounds the peak to
    # one extra stack.
    moved = {
        name: jax.device_put(
            x, Format(Layout(major_to_minor=(0, 2, 1)), x.sharding),
            donate=True,
        )
        for name, x in targets.items()
    }
    new_attn = {**attn, **moved}
    return {
        **params,
        "frozen_base": {
            **params["frozen_base"],
            "blocks": {**blocks, "attn": new_attn},
        },
    }
