"""Decode-time partition rules: the serving stack's own at-rest layouts.

Training shards for gradient math (parallel/sharding.py); decode has a
different steady state — a batch of single-token matvecs against resident
weights and a paged KV pool — so serve/ carries its own rule set instead
of reusing the training specs:

- **Weights** follow Megatron tensor parallelism over ``tp``: the
  in-projections (wq/wk/wv, w_in/w_gate) are column-parallel, the
  out-projections (wo, w_out) row-parallel, so every block costs one
  psum per sublayer and attention heads split cleanly across chips. The
  second big dim either shards over ``fsdp`` (``serve.mesh_weights:
  "fsdp"`` — a 6B policy fits a v5e-4 slice) or stays replicated
  (``"replicated"`` — no all-gathers on the decode critical path when
  per-chip HBM affords it).
- **KV pages** shard on the *head* dimension (axis 2 of each layer's
  ``[pages, page_size, Hkv, hd]`` leaf) under ``tp`` — the same split as
  the attention projections, so gather→score→scatter needs no KV
  collectives at all. Crucially the page *tables* stay host-side int32
  data (replicated), never shape: the radix cache, allocator, and
  journal/replay logic are mesh-oblivious and ``compile/recompiles == 0``
  survives sharding.
- **Slot lanes** (valid/offset/logits/pages — the scheduler's view of
  device state) are replicated: they are tiny, host-read every step, and
  replication keeps the one SlotScheduler loop driving a pjit'd step
  without per-axis bookkeeping.

The single-device mesh is the identity of this scheme, not a fork: with
``serve.mesh`` unset the same NamedShardings land on a 1-device mesh and
behave exactly like today's eager placement.

Non-dividing dims (odd vocab, Hkv < tp) fall back to replication per
axis via the same fit rule as training — correct, just less sharded.
"""

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from trlx_tpu.parallel.mesh import build_mesh, single_device_mesh
from trlx_tpu.parallel.sharding import _fit_spec_to_shape, _path_names

#: mesh axes serving understands; dp/pp/sp belong to training (serve's
#: data parallelism is replica processes — ROADMAP item 3 — not an axis)
SERVE_AXES = ("tp", "fsdp")

#: KV pool leaf specs by rank — the pool is per-layer leaves
#: (generation.init_page_pool), every one with heads on axis 2: pages
#: [pages, page_size, Hkv, hd], int8 scale planes [pages, page_size, Hkv]
KV_POOL_SPECS = {4: P(None, None, "tp", None), 3: P(None, None, "tp")}


def build_serve_mesh(mesh_config: Optional[Dict[str, int]]) -> Mesh:
    """The serve mesh from ``serve.mesh`` ({axis: size} over tp/fsdp).

    None/empty (the default) is the single-device mesh — today's
    behavior, expressed on the always-on sharded path. The mesh uses the
    first tp*fsdp devices; leftover devices simply don't serve (a v5e-8
    can run a tp=4 engine next to other work).
    """
    if not mesh_config:
        return single_device_mesh()
    unknown = set(mesh_config) - set(SERVE_AXES)
    if unknown:
        raise ValueError(
            f"serve.mesh axes {sorted(unknown)} are not serveable; the "
            f"decode mesh takes {SERVE_AXES} only (dp/pp/sp are training "
            f"axes — serve replicas scale horizontally instead)"
        )
    sizes = {ax: int(mesh_config.get(ax, 1)) for ax in SERVE_AXES}
    bad = {ax: v for ax, v in sizes.items() if v < 1}
    if bad:
        raise ValueError(
            f"serve.mesh axis sizes must be >= 1, got {bad} (wildcards "
            f"don't apply: a serve slice is sized explicitly)"
        )
    need = sizes["tp"] * sizes["fsdp"]
    avail = len(jax.devices())
    if need > avail:
        raise ValueError(
            f"serve.mesh {dict(mesh_config)} needs {need} devices but "
            f"only {avail} are visible"
        )
    return build_mesh(dict(sizes), devices=jax.devices()[:need])


def is_single_device(mesh: Mesh) -> bool:
    return mesh.size == 1


def decode_spec_for_leaf(path_names: Tuple[str, ...], ndim: int,
                         weights: str = "fsdp") -> P:
    """PartitionSpec for one decode-view leaf, by key path and rank.

    ``weights`` picks the second-axis treatment of the big matrices:
    ``"fsdp"`` shards it (capacity), ``"replicated"`` keeps it whole
    (no gather on the matvec path). The tp split is always on.
    """
    W = "fsdp" if weights == "fsdp" else None
    # serve-only int8 weights (serve.weights_dtype) turn matrix leaves
    # into (codes, scale) pairs, so the key path ends in a sequence
    # index — strip digits so the "wq"/"w_out" rules still match both
    # members (the scale's non-dividing [L, 1, out] dims fall back per
    # axis in _fit_spec_to_shape)
    path_names = tuple(n for n in path_names if not n.isdigit())
    name = path_names[-1] if path_names else ""
    parent = path_names[-2] if len(path_names) > 1 else ""

    # stacked per-layer matrices [L, in, out] — layer axis never sharded
    # (lax.scan slices it every step)
    if ndim == 3:
        if name in ("wq", "wk", "wv", "w_in", "w_gate"):
            return P(None, W, "tp")  # column-parallel
        if name in ("wo", "w_out"):
            return P(None, "tp", W)  # row-parallel (psum after)
    if ndim == 2:
        if name in ("bq", "bk", "bv", "b_in"):
            return P(None, "tp")  # live on the tp-sharded output dim
        if name in ("bo", "b_out"):
            return P(None, None)  # added after the psum
    if name == "wte":  # [V, D]: gather by token id, then tied lm head
        return P("tp", W)
    if name == "wpe":  # [N_pos, D]
        return P(None, W)
    if parent == "lm_head":
        if name == "w" and ndim == 2:  # [D, V]
            return P(W, "tp")
        if name == "b" and ndim == 1:
            return P("tp")
    # layernorms, scalars, anything unmatched: replicated
    return P()


def decode_param_shardings(mesh: Mesh, views: Any,
                           weights: str = "fsdp") -> Any:
    """NamedSharding pytree for decode views (or a ShapeDtypeStruct
    template of them) — non-dividing dims fall back per axis."""

    def leaf(kp, x):
        spec = decode_spec_for_leaf(_path_names(kp), getattr(x, "ndim", 0))
        spec = _fit_spec_to_shape(spec, x.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, views)


def kv_pool_shardings(mesh: Mesh, pool: Any) -> Any:
    """NamedSharding pytree for a KV pool: heads
    (axis 2 of every per-layer leaf) over tp, everything else
    replicated. Works on arrays or ShapeDtypeStructs; an Hkv that tp
    doesn't divide replicates."""

    def leaf(x):
        spec = KV_POOL_SPECS.get(getattr(x, "ndim", 0), P())
        spec = _fit_spec_to_shape(spec, x.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(leaf, pool)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def replicated_like(mesh: Mesh, tree: Any) -> Any:
    """A replicated-NamedSharding pytree matching ``tree``'s structure
    (slot lanes, page tables, host scalars — scheduler-visible data)."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda _: rep, tree)


def shard_decode_views(mesh: Mesh, views, weights: str = "fsdp"):
    """Place (blocks, embed, ln_f) decode views on the serve mesh."""
    return jax.device_put(views, decode_param_shardings(
        mesh, views, weights=weights))


def tree_bytes_per_device(tree: Any) -> int:
    """Per-device resident bytes of a sharded pytree — each leaf counts
    its local shard (``sharding.shard_shape``), so a tp=2-sharded matrix
    counts half. Host numpy (no sharding) counts whole."""
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        nbytes = int(np.prod(x.shape)) * x.dtype.itemsize
        sharding = getattr(x, "sharding", None)
        if sharding is not None and x.ndim > 0:
            local = sharding.shard_shape(x.shape)
            local_n = int(np.prod(local)) if local else 1
            global_n = int(np.prod(x.shape))
            if global_n:
                nbytes = nbytes * local_n // global_n
        total += nbytes
    return total


def mesh_info(mesh: Mesh, weights: str = "fsdp") -> Dict[str, Any]:
    """The /healthz- and /debug/state-facing description of the serve
    mesh: axis names/sizes (non-trivial axes only), device count, and
    the weights-placement knob."""
    axes = {ax: int(n) for ax, n in mesh.shape.items() if int(n) > 1}
    return {
        "devices": int(mesh.size),
        "axes": axes or {"tp": 1},
        "weights": weights,
    }
