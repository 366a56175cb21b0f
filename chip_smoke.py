#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that trlx_tpu still starts on the chip.

Drives the system's main path once, through the entry points a user
calls, at the full width of gpt2-124M (vocab 50257, 12 layers, 12 heads,
d_model 768, n_positions 1024, bf16 compute, 2 layers unfrozen — the
geometry bench.build() uses; weights random from a seed, no download):

  trainer   get_model / get_pipeline / get_orchestrator, batch 128,
            4 prompt + 48 generated tokens: make_experience(128) then
            learn() for total_steps 4, twice (the second cycle must not
            compile anything), then trainer.save(<dir>)
  kernels   both Pallas kernel files compiled by Mosaic (never
            interpreted) at the preset head shapes 12x64, 16x256 and
            32q/8kv x 128, against the jnp path on the chip: flash
            attention forward + backward, paged decode at bf16 and int8
            pages, page sizes 64 and 16; plus the flash kernel's
            auto-selection inside a real PPO train step at T = 1024 and
            the custom-layout relayout + AOT dispatch path
  server    ``python -m trlx_tpu.serve --checkpoint <dir>`` on that
            checkpoint (defaults: slots scheduler, paged KV; a 16-slot
            lattice), a few POST /generate with different
            max_new_tokens, /healthz, /metrics, SIGTERM -> "drained
            (clean)" -> exit 0; then once more with --attention pallas,
            whose greedy tokens must equal the jnp server's
  --chips 4 the trainer under train.mesh {dp:1, fsdp:2, tp:2} and
            {dp:2, fsdp:2}, the server under --mesh tp=2,fsdp=2 and
            --mesh tp=4 against the one-chip server's greedy tokens,
            and __graft_entry__.dryrun_multichip(4) in-process on the
            real devices. Without the flag the run says "not requested".

Greedy token parity (pallas vs jnp, mesh vs one chip) compares argmax
streams of bf16 programs that round differently, and a random-weight
model's first generated token is often a near-tie (its top-2 logits one
or two bf16 ulps apart — the first four-chip run flipped exactly there).
So the trainer phase picks the prompts: of 32 seeded candidates it keeps
the four whose greedy path is decisive (top-2 gap >= DECISIVE_MARGIN at
every generated position, measured with the trained policy's own
forward) and every server answers those. A parity mismatch then means a
wrong program, not a coin toss.

PROCESS PLAN — one process per chip. This file's ``main`` is the only
parent; it imports neither jax nor any trlx_tpu module and never touches
the device. Each phase that needs the chip is a child
(``python chip_smoke.py --phase ...`` or ``python -m trlx_tpu.serve``)
started only after the previous child has exited, so exactly one process
holds the chip at any time. The server children are driven over HTTP
from the parent.

Every failure is an exit code: no phase is wrapped in a catch-and-log;
the first failed check ends the run non-zero with the phase named, and
no result line is printed. On success the last line of stdout is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it. Cold-compile seconds, steady step
seconds and the host<->device fetch round trip are log lines, not
metrics.
"""

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

#: gpt2-124M — the geometry bench.build() uses
GPT2_124M = {
    "model_spec": {
        "vocab_size": 50257, "n_layer": 12, "n_head": 12, "d_model": 768,
        "n_positions": 1024,
    },
    "compute_dtype": "bfloat16",
    "batch": 128,
    "prompt_tokens": 4,
    "gen_tokens": 48,
}

#: serve lattice: 16 slots, 128-token slot buffers = two 64-token pages
SERVE_BUCKETS = "4x32x96,16x32x96"

#: max_new_tokens of the requests each server answers — every answer
#: must carry exactly its own count
SERVE_MAX_NEW = (2, 5, 9, 16)

#: least top-2 logit gap along a prompt's greedy path for it to be sent
#: to the parity servers: ~6 bf16 ulps at the logits' magnitude, an
#: order above what two correct bf16 programs differ by
DECISIVE_MARGIN = 0.1


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class SmokeFailure(Exception):
    """A check that did not hold; carries the phase for the exit line."""

    def __init__(self, phase: str, detail: str):
        super().__init__(f"phase={phase}: {detail}")
        self.phase = phase


def check(cond, phase: str, detail: str) -> None:
    if not cond:
        raise SmokeFailure(phase, detail)


# --------------------------------------------------------------------- #
# phases that hold the chip (run in a child; importable for CPU tests)
# --------------------------------------------------------------------- #


def device_report(min_devices: int = 1) -> dict:
    """Device first: fail unless JAX runs on a TPU with enough chips."""
    import jax
    import jaxlib

    devices = jax.devices()
    d0 = devices[0]
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    log(f"platform={d0.platform} device_kind={d0.device_kind!r} "
        f"devices={len(devices)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version}")
    check(d0.platform == "tpu", "device",
          f"jax.devices()[0].platform is '{d0.platform}', not 'tpu'")
    check(len(devices) >= min_devices, "device",
          f"{min_devices} devices asked for, JAX reports {len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def smoke_config(width: dict, mesh=None):
    from trlx_tpu.data.configs import TRLConfig

    B, P, G = width["batch"], width["prompt_tokens"], width["gen_tokens"]
    return TRLConfig.from_dict({
        "model": {
            "model_path": "from-config",
            "tokenizer_path": "byte",
            "model_type": "JaxPPOTrainer",
            "num_layers_unfrozen": 2,
            "model_spec": dict(width["model_spec"]),
            "compute_dtype": width["compute_dtype"],
        },
        "train": {
            "n_ctx": 512, "epochs": 1, "total_steps": 4, "batch_size": B,
            "grad_clip": 1.0, "lr_ramp_steps": 100,
            "lr_decay_steps": 79000, "weight_decay": 1.0e-6,
            "learning_rate_init": 1.412e-4,
            "learning_rate_target": 1.412e-4,
            "log_interval": 4, "checkpoint_interval": 10**9,
            "eval_interval": 10**9, "pipeline": "PPOPipeline",
            "orchestrator": "PPOOrchestrator", "input_size": P,
            "gen_size": G, "seed": 0, "mesh": mesh,
        },
        "method": {
            "name": "ppoconfig", "num_rollouts": B, "chunk_size": B,
            "ppo_epochs": 4, "init_kl_coef": 0.2, "target": 6,
            "horizon": 10000, "gamma": 1, "lam": 0.95, "cliprange": 0.2,
            "cliprange_value": 0.2, "vf_coef": 2.3,
            "gen_kwargs": {"max_length": G, "min_length": G, "top_k": 0,
                           "top_p": 1.0, "do_sample": True},
        },
    })


def log_fetch_round_trip(reps: int = 20) -> None:
    """One host<->device round trip, two ways: a dispatched scalar
    program whose result the host reads, and a put-then-get of 4 bytes.
    Medians over ``reps`` after a warm call."""
    import jax
    import numpy as np

    step = jax.jit(lambda x: x + 1)
    x = jax.device_put(np.float32(0))
    float(step(x))
    dispatch, putget = [], []
    for i in range(reps):
        t0 = time.perf_counter()
        float(step(x))
        dispatch.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(jax.device_put(np.float32(i)))
        putget.append(time.perf_counter() - t0)
    log(f"host<->device round trip: dispatch+fetch "
        f"{sorted(dispatch)[reps // 2] * 1e3:.3f} ms, put+get "
        f"{sorted(putget)[reps // 2] * 1e3:.3f} ms (medians of {reps})")


def trainer_phase(workdir: str, width: dict = GPT2_124M, mesh=None,
                  tag: str = "trainer") -> dict:
    """The PPO main path: two rollout+update cycles through the
    registries, checked, then ``trainer.save``. Returns what the server
    phases need: the checkpoint, its greedy config, the requests."""
    import jax
    import numpy as np
    import yaml

    from trlx_tpu import telemetry
    from trlx_tpu.utils.loading import (
        get_model,
        get_orchestrator,
        get_pipeline,
    )

    config = smoke_config(width, mesh=mesh)
    t0 = time.perf_counter()
    trainer = get_model(config.model.model_type)(config)
    log(f"{tag}: trainer built in {time.perf_counter() - t0:.1f}s "
        f"(mesh={mesh})")
    rng = np.random.default_rng(0)
    prompts = ["".join(chr(c) for c in rng.integers(97, 123, size=16))
               for _ in range(2 * width["batch"])]
    pipeline = get_pipeline(config.train.pipeline)(
        prompts, trainer.tokenizer, config
    )

    def reward_fn(texts):  # synthetic host reward: lowercase share
        return [float(np.mean([c.islower() for c in t] or [0.0]))
                for t in texts]

    orch = get_orchestrator(config.train.orchestrator)(
        trainer, pipeline, reward_fn=reward_fn,
        chunk_size=config.method.chunk_size,
    )
    if mesh is not None:
        _check_spread(trainer, tag)

    before = jax.device_get(trainer.params["trainable"]["ln_f"])
    cycles = []
    for cycle in range(2):
        trainer.store.clear_history()
        trainer.iter_count = 0
        trainer.epoch = 0
        logs = []
        t0 = time.perf_counter()
        info = orch.make_experience(config.method.num_rollouts)
        t_exp = time.perf_counter() - t0
        trainer.learn(log_fn=logs.append)
        jax.block_until_ready(trainer.params["trainable"])
        t_all = time.perf_counter() - t0
        stats = next((s for s in logs if "loss" in s), None)
        check(stats is not None, tag, f"learn() logged no step stats: {logs}")
        check(trainer.iter_count == config.train.total_steps, tag,
              f"iter_count {trainer.iter_count} != "
              f"{config.train.total_steps}")
        for name, value in (("loss", stats["loss"]),
                            ("mean_kl", info["mean_kl"]),
                            ("mean_score", info["mean_score"])):
            check(np.isfinite(value), tag,
                  f"cycle {cycle}: {name} = {value} is not finite")
        recompiles = telemetry.current().registry.counters.get(
            "compile/recompiles", 0.0
        )
        jit_sizes = {
            name: getattr(trainer, name)._cache_size()
            for name in ("_rollout_fn", "_train_multi",
                         "_train_multi_indexed")
            if hasattr(getattr(trainer, name), "_cache_size")
        }
        log(f"{tag}: cycle {cycle} {'(cold, compile included)' if not cycle else '(steady)'}: "
            f"{t_all:.2f}s = rollout {t_exp:.2f}s + update "
            f"{t_all - t_exp:.2f}s; loss={stats['loss']:.4f} "
            f"mean_kl={info['mean_kl']:.5f} "
            f"mean_score={info['mean_score']:.4f} "
            f"compile/recompiles={recompiles:.0f} jit_cache={jit_sizes}")
        cycles.append((t_all, t_exp, jit_sizes))
        if cycle:
            check(recompiles == 0, tag,
                  f"compile/recompiles = {recompiles} after the first "
                  f"cycle")
            check(jit_sizes == cycles[0][2], tag,
                  f"a jitted program retraced in the steady cycle: "
                  f"{cycles[0][2]} -> {jit_sizes}")
    after = jax.device_get(trainer.params["trainable"]["ln_f"])
    moved = max(
        float(np.abs(np.asarray(a, np.float32)
                     - np.asarray(b, np.float32)).max())
        for a, b in zip(jax.tree_util.tree_leaves(after),
                        jax.tree_util.tree_leaves(before))
    )
    check(moved > 0, tag, "trainable params did not change over 8 steps")
    cold, (steady, steady_rollout, _) = cycles[0][0], cycles[1]
    log(f"{tag}: cold cycle {cold:.2f}s, steady cycle {steady:.2f}s "
        f"(compile ~{cold - steady:.1f}s), steady step "
        f"{(steady - steady_rollout) / config.method.ppo_epochs:.3f}s "
        f"x {config.method.ppo_epochs} ppo_epochs, max |d ln_f| = {moved:.2e}")

    ckpt = os.path.join(workdir, f"ckpt_{tag}")
    t0 = time.perf_counter()
    trainer.save(ckpt)
    log(f"{tag}: saved {ckpt} in {time.perf_counter() - t0:.1f}s")
    # the serve CLI reads the embedded config; its greedy twin (same
    # model, do_sample off) is what the parity servers are launched on
    greedy = config.to_nested_dict()
    greedy["method"]["gen_kwargs"]["do_sample"] = False
    greedy_path = os.path.join(workdir, f"serve_greedy_{tag}.yml")
    with open(greedy_path, "w") as f:
        yaml.safe_dump(greedy, f)
    return {"checkpoint": ckpt, "greedy_config": greedy_path,
            "requests": decisive_requests(trainer, tag)}


def decisive_requests(trainer, tag: str, candidates: int = 32) -> list:
    """``[(prompt tokens, max_new_tokens), ...]`` for the parity servers:
    the candidates whose greedy continuation the trained policy is surest
    of (module docstring). Left-padded prompts in one fixed-shape buffer;
    each step is the same jitted forward reading one position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    policy, params = trainer.policy, trainer.params
    P, G = 13, max(SERVE_MAX_NEW)
    rng = np.random.default_rng(1)
    tokens = np.zeros((candidates, P + G), np.int32)
    mask = np.zeros((candidates, P + G), np.int32)
    lengths = rng.integers(3, P + 1, size=candidates)
    for i, n in enumerate(lengths):
        tokens[i, P - n:P] = rng.integers(32, 127, size=n)
        mask[i, P - n:P] = 1

    @jax.jit
    def step(params, tokens, mask, pos):
        logits = policy.forward(params, tokens, mask, with_ref=False)[0]
        row = jax.lax.dynamic_index_in_dim(logits, pos, 1, keepdims=False)
        top, idx = jax.lax.top_k(row.astype(jnp.float32), 2)
        return idx[:, 0], top[:, 0] - top[:, 1]

    margins = []
    for i in range(G):
        nxt, gap = step(params, tokens, mask, P + i - 1)
        tokens[:, P + i] = np.asarray(nxt)
        mask[:, P + i] = 1
        margins.append(np.asarray(gap))
    surest = np.stack(margins, axis=1).min(axis=1)
    order = np.argsort(-surest)[:len(SERVE_MAX_NEW)]
    log(f"{tag}: decisive prompts: least top-2 gap "
        f"{' '.join(f'{surest[i]:.3f}' for i in order)} (median over "
        f"{candidates} candidates {np.median(surest):.3f})")
    check(surest[order[-1]] >= DECISIVE_MARGIN, tag,
          f"fewer than {len(SERVE_MAX_NEW)} of {candidates} candidate "
          f"prompts have a greedy path with top-2 gaps >= "
          f"{DECISIVE_MARGIN}: best {np.sort(surest)[::-1][:8]}")
    return [
        (tokens[i, P - lengths[i]:P].tolist(), max_new)
        for i, max_new in zip(order, SERVE_MAX_NEW)
    ]


def _check_spread(trainer, tag: str) -> None:
    """The mesh really spreads the model: every device holds bytes and
    every large leaf's local shard is smaller than the leaf."""
    import jax

    for d in trainer.mesh.devices.flat:
        in_use = d.memory_stats()["bytes_in_use"]
        check(in_use > 0, tag, f"device {d} holds no bytes")
        log(f"{tag}: {d} bytes_in_use={in_use / 2**20:.1f} MiB")

    def leaf(kp, x):
        if x.size < 2**20:
            return
        shard = x.addressable_shards[0].data
        check(shard.size < x.size, tag,
              f"{jax.tree_util.keystr(kp)} {x.shape} is replicated "
              f"({x.sharding.spec})")

    jax.tree_util.tree_map_with_path(leaf, trainer.params)
    wte = trainer.params["frozen_base"]["embed"]["wte"]
    log(f"{tag}: wte {wte.shape} spec={wte.sharding.spec} local shard "
        f"{wte.addressable_shards[0].data.shape}")


# -- kernels ------------------------------------------------------------ #

#: (label, query heads, kv heads, head_dim) of the presets in
#: trlx_tpu/data/configs.py: gpt2, gpt-j-6b, llama-3-8b
HEAD_SHAPES = (("gpt2", 12, 12, 64), ("gpt-j", 16, 16, 256),
               ("llama-3", 32, 8, 128))

#: max |kernel - jnp| / max |jnp|, bf16 operands. Both sides round to
#: bf16 (2^-8 relative) at different points, so a few ulps of the
#: largest value is the honest bound; a wrong kernel is off by O(1).
KERNEL_REL_TOL = 3e-2


def _rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _mosaic_compiled(fn, *args):
    """jit + compile ``fn``; the executable must hold a Mosaic custom
    call (an interpreted pallas_call lowers to plain HLO and has none)."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(), "kernels",
          "no tpu_custom_call in the compiled program: the pallas_call "
          "did not go through Mosaic")
    return compiled


def _check_paged(label, S, H, Hkv, hd, page_size, max_pages, num_pages,
                 lengths, tiers) -> None:
    """The paged decode kernel, compiled by Mosaic, against the jnp
    gather + score path on one random pool: ``lengths`` [S] visible
    positions a slot, its table sentinel past them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.models.transformer import (
        attention_scores,
        dequantize_kv,
        quantize_kv,
    )
    from trlx_tpu.ops.paged_attention import (
        block_plan,
        paged_decode_attention,
    )

    rng = np.random.default_rng(page_size + hd)
    kq, kk_, kv_ = jax.random.split(jax.random.PRNGKey(hd), 3)
    q1 = jax.random.normal(kq, (S, H, hd), jnp.bfloat16)
    pool_shape = (num_pages, page_size, Hkv, hd)
    k_pool = jax.random.normal(kk_, pool_shape, jnp.bfloat16)
    v_pool = jax.random.normal(kv_, pool_shape, jnp.bfloat16)
    table = rng.integers(0, num_pages, size=(S, max_pages)).astype(np.int32)
    need = -(-np.asarray(lengths) // page_size)
    table[np.arange(max_pages)[None, :] >= need[:, None]] = 2**30
    T_buf = max_pages * page_size
    bias = jnp.where(
        jnp.arange(T_buf)[None, :] < jnp.asarray(lengths)[:, None],
        0.0, -1e9,
    ).astype(jnp.float32)
    table = jnp.asarray(table)

    def jnp_path(q1, k_pool, v_pool):
        ctx = jnp.clip(table, 0, num_pages - 1)
        k_ctx = k_pool[ctx].reshape(S, T_buf, Hkv, hd)
        v_ctx = v_pool[ctx].reshape(S, T_buf, Hkv, hd)
        return attention_scores(
            q1[:, None], k_ctx, v_ctx, bias[:, None, None, :]
        )[:, 0]

    for tier in tiers:
        if tier == "int8":
            k_in, v_in = quantize_kv(k_pool), quantize_kv(v_pool)
            k_ref = dequantize_kv(*k_in, jnp.bfloat16)
            v_ref = dequantize_kv(*v_in, jnp.bfloat16)
        else:
            k_in, v_in, k_ref, v_ref = k_pool, v_pool, k_pool, v_pool

        def kernel(q1, k_in, v_in):
            return paged_decode_attention(q1, k_in, v_in, table, bias)

        got = _mosaic_compiled(kernel, q1, k_in, v_in)(q1, k_in, v_in)
        ref = jax.jit(jnp_path)(q1, k_ref, v_ref)
        check(bool(jnp.isfinite(got.astype(jnp.float32)).all()),
              "kernels", f"paged {label} {tier}: non-finite output")
        err = _rel_err(got, ref)
        plan = block_plan(pool_shape, jax.tree_util.tree_leaves(k_in)[0].dtype,
                          max_pages)
        log(f"kernels: paged {label} {H}q/{Hkv}kv x{hd} {tier} "
            f"{plan[0]} pages a block x {plan[1]} rel err {err:.1e}")
        check(err <= KERNEL_REL_TOL, "kernels",
              f"paged decode {label} {tier}: rel err {err} > "
              f"{KERNEL_REL_TOL}")


def _check_latent() -> None:
    """The absorbed latent decode kernel at the shared-documents cell's own
    size (64 query rows of 640 over pages of 64 tokens, a table of 648)
    against the jnp absorbed form: a row of a whole table, rows that end
    inside a block, a row with nothing live."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.ops.latent_attention import (
        NEG_INF,
        block_plan,
        latent_decode_attention,
    )

    S, H, W, r, page_size, table, num_pages = 8, 64, 640, 512, 64, 648, 2048
    rng = np.random.default_rng(0)
    lengths = np.array([table * 64, 40_000, 16_385, 1, 0, 24_576, 25, 3_000])
    pages = jax.random.normal(jax.random.PRNGKey(0),
                              (num_pages, page_size, W), jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(1), (S, H, W), jnp.bfloat16)
    ids = rng.integers(0, num_pages, size=(S, table)).astype(np.int32)
    for s, n in enumerate(lengths):
        ids[s, -(-n // page_size):] = num_pages  # the sentinel
    bias = jnp.where(jnp.arange(table * page_size)[None, :]
                     < lengths[:, None], 0.0, NEG_INF).astype(jnp.float32)
    ids = jnp.asarray(ids)

    def kernel(q, pages):
        return latent_decode_attention(q, pages, ids, bias, r, 0.135)

    def jnp_path(q, pages):
        lat = pages[jnp.clip(ids, 0, num_pages - 1)].reshape(S, -1, W)
        s = jnp.einsum("shw,skw->shk", q, lat).astype(jnp.float32)
        p = jax.nn.softmax(s * 0.135 + bias[:, None, :], -1)
        out = jnp.einsum("shk,skr->shr", p.astype(lat.dtype), lat[..., :r])
        return jnp.where((lengths > 0)[:, None, None], out, 0)

    got = _mosaic_compiled(kernel, q, pages)(q, pages)
    err = _rel_err(got, jax.jit(jnp_path)(q, pages))
    plan = block_plan(pages.shape, pages.dtype, table)
    log(f"kernels: latent decode 64x640 table={table}: {plan[0]} pages a "
        f"block x {plan[1]} rel err {err:.1e}")
    check(err <= KERNEL_REL_TOL, "kernels",
          f"latent decode: rel err {err} > {KERNEL_REL_TOL}")


def kernels_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.models.transformer import (
        attention_scores,
        causal_mask_bias,
    )
    from trlx_tpu.ops import pallas_mode
    from trlx_tpu.ops.pallas_attention import flash_attention

    check(not pallas_mode.interpret(), "kernels",
          "pallas_mode.interpret() is True on this backend")
    for label, H, Hkv, hd in HEAD_SHAPES:
        # -- flash attention forward + backward (H-wide K/V, as
        # block_apply hands them to the kernel) ------------------------
        B, T = 2, 1024
        keys = jax.random.split(jax.random.PRNGKey(H * hd), 4)
        q, k, v, g = (jax.random.normal(kk, (B, T, H, hd), jnp.bfloat16)
                      for kk in keys)
        mask = jnp.ones((B, T), jnp.int32).at[1, T - 100:].set(0)

        def flash(q, k, v):
            return flash_attention(q, k, v, mask, 128, 128, True)

        def dense(q, k, v):
            return attention_scores(q, k, v, causal_mask_bias(mask))

        def vjp_of(fn):
            def run(q, k, v, g):
                out, pull = jax.vjp(fn, q, k, v)
                return (out, *pull(g))
            return run

        t0 = time.perf_counter()
        got = _mosaic_compiled(vjp_of(flash), q, k, v, g)(q, k, v, g)
        ref = jax.jit(vjp_of(dense))(q, k, v, g)
        # padded keys' dk/dv rows are exactly zero on both sides
        errs = [_rel_err(a, b) for a, b in zip(got, ref)]
        log(f"kernels: flash {label} {H}x{hd} T={T} fwd/dq/dk/dv rel err "
            f"{' '.join(f'{e:.1e}' for e in errs)} "
            f"({time.perf_counter() - t0:.1f}s)")
        check(max(errs) <= KERNEL_REL_TOL, "kernels",
              f"flash attention {label}: rel err {errs} > "
              f"{KERNEL_REL_TOL}")

        # -- paged decode ----------------------------------------------
        for page_size in (64, 16):
            rng = np.random.default_rng(page_size + hd)
            _check_paged(
                f"{label} page_size={page_size}", 16, H, Hkv, hd, page_size,
                4, 80, rng.integers(1, 4 * page_size + 1, size=16),
                ("bf16", "int8"),
            )
    # the long-context serve cell's own size: 32 slots, half of them long
    # sessions, each class of page with its own table and pages a block
    rng = np.random.default_rng(0)
    for table, num_pages in ((454, 7168), (66, 2560)):
        pages = np.stack([rng.integers(190, 451, size=16),
                          rng.integers(2, 20, size=16)], 1).reshape(-1)
        lengths = np.minimum(pages, table) * 64 - rng.integers(0, 64, size=32)
        _check_paged(f"command-a-plus table={table}", 32, 128, 8, 128, 64,
                     table, num_pages, lengths, ("bf16",))
    _check_latent()
    _flash_in_train_step()
    _relayout_aot()


def _flash_in_train_step() -> None:
    """The trainer picks the flash kernel by itself on a TPU once the
    sequence reaches FUSED_ATTENTION_MIN_T; one real PPO train step
    through it (forward + both backward kernels) must come out finite."""
    import jax
    import numpy as np

    from trlx_tpu.data.ppo_types import PPORLBatch
    from trlx_tpu.utils.loading import get_model

    width = dict(GPT2_124M, batch=2, prompt_tokens=64, gen_tokens=960)
    width["model_spec"] = dict(width["model_spec"], n_layer=4)  # depth cut
    config = smoke_config(width)
    trainer = get_model(config.model.model_type)(config)
    check(trainer.policy.attention_fn is not None, "kernels",
          "the trainer did not select the flash kernel at T = 1024 on "
          "this backend")
    B, P, G = 2, 64, 960
    rng = np.random.default_rng(0)
    batch = PPORLBatch(
        query_tensors=rng.integers(0, 50257, (B, P)).astype(np.int32),
        response_tensors=rng.integers(0, 50257, (B, G)).astype(np.int32),
        logprobs=rng.normal(size=(B, G)).astype(np.float32),
        values=rng.normal(size=(B, G)).astype(np.float32),
        rewards=(rng.normal(size=(B, G)) * 0.01).astype(np.float32),
        response_masks=np.ones((B, G), np.int32),
        query_masks=np.ones((B, P), np.int32),
    )
    t0 = time.perf_counter()
    _, _, stats = trainer._train_step(
        trainer.params, trainer.opt_state, trainer._put(batch)
    )
    stats = jax.device_get(stats)
    log(f"kernels: flash kernel inside a PPO train step at T={P + G} "
        f"(4 layers): loss={float(stats['loss']):.4f} "
        f"grad_norm={float(stats['grad_norm']):.4f} "
        f"({time.perf_counter() - t0:.1f}s)")
    check(np.isfinite(stats["loss"]) and np.isfinite(stats["grad_norm"]),
          "kernels", f"flash train step not finite: {stats}")


def _relayout_aot() -> None:
    """relayout_for_decode + aot_jit, the 6B-class path the size gate
    hides at 124M: the transposed at-rest layout must be granted by the
    runtime, survive into the AOT executable, and leave values intact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.parallel import relayout_for_decode
    from trlx_tpu.utils.aotjit import aot_jit

    L, D = 4, 768
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    attn = {name: jax.random.normal(kk, (L, D, D), jnp.bfloat16)
            for name, kk in zip(("wq", "wk", "wv"), keys)}
    expect = np.asarray(attn["wq"][1, :8, :8], np.float32)
    params = {"frozen_base": {"blocks": {"attn": attn}}}
    moved = relayout_for_decode(params, min_bytes=0)
    wq = moved["frozen_base"]["blocks"]["attn"]["wq"]
    m2m = wq.format.layout.major_to_minor
    check(tuple(m2m) == (0, 2, 1), "kernels",
          f"relayout_for_decode left wq at layout {m2m}")
    x = jnp.ones((8, D), jnp.bfloat16)
    fn = aot_jit(lambda p, x: x @ p["frozen_base"]["blocks"]["attn"]["wq"][1])
    got = fn(moved, x)
    fn(moved, x)  # second dispatch: same signature, no new executable
    check(len(fn._cache) == 1, "kernels",
          "aot_jit compiled twice for one argument signature")
    np.testing.assert_array_equal(
        np.asarray(wq[1, :8, :8], np.float32), expect
    )
    check(bool(jnp.isfinite(got.astype(jnp.float32)).all()), "kernels",
          "aot_jit matvec over the relayouted stack is not finite")
    log(f"kernels: relayout_for_decode -> major_to_minor {tuple(m2m)}, "
        f"aot_jit dispatch OK")


def multichip_dryrun_phase() -> None:
    """__graft_entry__.dryrun_multichip(4) in-process on the real
    devices: tp=2 x sp=2 ring attention, pp=4 GPipe and the tp=2,fsdp=2
    serve mesh over ICI at toy shapes."""
    import jax

    check(len(jax.devices()) >= 4, "multichip",
          f"dryrun_multichip(4) needs 4 devices, JAX reports "
          f"{len(jax.devices())}")
    import __graft_entry__

    t0 = time.perf_counter()
    __graft_entry__.dryrun_multichip(4)
    log(f"multichip: dryrun_multichip(4) on real devices in "
        f"{time.perf_counter() - t0:.1f}s")


# --------------------------------------------------------------------- #
# the server phase: stdlib only, drives a child over HTTP
# --------------------------------------------------------------------- #


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body=None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _tail(path: str, n: int = 2000) -> str:
    with open(path, errors="replace") as f:
        return f.read()[-n:]


def server_phase(checkpoint: str, workdir: str, tag: str, requests,
                 extra_args=(), buckets: str = SERVE_BUCKETS,
                 boot_timeout: float = 600.0, env=None) -> dict:
    """Launch ``python -m trlx_tpu.serve`` on ``checkpoint``, answer
    ``requests``, read /healthz and /metrics, SIGTERM, expect a clean
    drain and exit 0. Returns the tokens each request got."""
    port = _free_port()
    log_path = os.path.join(workdir, f"serve_{tag}.log")
    cmd = [sys.executable, "-m", "trlx_tpu.serve",
           "--checkpoint", checkpoint, "--buckets", buckets,
           "--port", str(port), *extra_args]
    child_env = dict(os.environ if env is None else env)
    child_env.setdefault("HF_HUB_OFFLINE", "1")
    log(f"{tag}: {' '.join(cmd[1:])}")
    t0 = time.perf_counter()
    with open(log_path, "w") as log_file:
        proc = subprocess.Popen(cmd, cwd=HERE, env=child_env,
                                stdout=log_file, stderr=subprocess.STDOUT)
    try:
        base = f"http://127.0.0.1:{port}"
        health = None
        while health is None:
            check(proc.poll() is None, tag,
                  f"server exited with {proc.returncode} before "
                  f"listening:\n{_tail(log_path)}")
            check(time.perf_counter() - t0 < boot_timeout, tag,
                  f"server not listening after {boot_timeout:.0f}s:\n"
                  f"{_tail(log_path)}")
            try:
                health = _http("GET", f"{base}/healthz", timeout=5.0)
            except (urllib.error.URLError, ConnectionError, TimeoutError):
                time.sleep(0.5)
        boot_s = time.perf_counter() - t0
        check(health.get("status") == "ok" and health.get("warmed"), tag,
              f"/healthz not ok/warmed: {health}")
        check(health.get("scheduler") == "slots"
              and "pages_free" in health.get("kv", {}), tag,
              f"server is not on its defaults (slots + paged): {health}")
        log(f"{tag}: listening after {boot_s:.1f}s (boot + warm-up "
            f"compile), slots={health.get('slots')} "
            f"mesh={health.get('mesh')}")

        answers = []
        for tokens, max_new in requests:
            t1 = time.perf_counter()
            out = _http("POST", f"{base}/generate",
                        {"tokens": list(tokens), "max_new_tokens": max_new})
            check(len(out["tokens"]) == max_new, tag,
                  f"asked for {max_new} tokens, got {len(out['tokens'])}: "
                  f"{out}")
            answers.append(out["tokens"])
            log(f"{tag}: /generate max_new_tokens={max_new} -> "
                f"{out['tokens']} in {time.perf_counter() - t1:.3f}s")

        metrics = _http("GET", f"{base}/metrics")
        counters = metrics["counters"]
        n = float(len(requests))
        for name, want in (("compile/recompiles", 0.0),
                           ("serve/responses", n),
                           ("serve/admissions", n),
                           ("serve/evictions", n)):
            check(counters.get(name) == want, tag,
                  f"/metrics {name} = {counters.get(name)}, expected {want}")
        step = metrics["timings"].get("time/serve/slot_step", {})
        compile_s = sum(v for k, v in metrics["gauges"].items()
                        if k.startswith("compile/") and k.endswith("_first_s"))
        log(f"{tag}: compile/recompiles=0, admissions=evictions="
            f"responses={n:.0f}; first calls {compile_s:.1f}s, steady "
            f"slot_step p50 {step.get('p50_s', float('nan')) * 1e3:.2f} ms "
            f"over {step.get('count')} steps")

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120.0)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                tag, f"server still running 120s after SIGTERM:\n"
                     f"{_tail(log_path)}"
            ) from None
        text = _tail(log_path, 10**6)
        check(rc == 0, tag, f"server exited {rc} after SIGTERM:\n"
                            f"{text[-2000:]}")
        check("drained (clean)" in text, tag,
              f"no 'drained (clean)' in the server log:\n{text[-2000:]}")
        for line in text.splitlines():
            if "platform=" in line or "drained" in line:
                log(f"{tag}: | {line.strip()}")
        return {"tokens": answers, "boot_s": round(boot_s, 1)}
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# --------------------------------------------------------------------- #
# parent: starts one child at a time, never imports jax
# --------------------------------------------------------------------- #


def _child_main(args) -> int:
    """A phase that holds the chip; the result goes to --result."""
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    from trlx_tpu.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    result = {"device": device_report(args.min_devices)}
    if args.phase == "trainer":
        mesh = json.loads(args.mesh) if args.mesh else None
        log_fetch_round_trip()
        result.update(trainer_phase(args.workdir, mesh=mesh, tag=args.tag))
    elif args.phase == "kernels":
        kernels_phase()
    elif args.phase == "dryrun":
        multichip_dryrun_phase()
    with open(args.result, "w") as f:
        json.dump(result, f)
    return 0


def run_child(phase: str, workdir: str, tag: str, min_devices: int = 1,
              mesh=None, timeout: float = 900.0) -> dict:
    result_path = os.path.join(workdir, f"result_{tag}.json")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--workdir", workdir, "--result", result_path, "--tag", tag,
           "--min-devices", str(min_devices)]
    if mesh is not None:
        cmd += ["--mesh", json.dumps(mesh)]
    t0 = time.perf_counter()
    try:
        rc = subprocess.run(cmd, cwd=HERE, timeout=timeout).returncode
    except subprocess.TimeoutExpired:
        raise SmokeFailure(tag, f"child exceeded {timeout:.0f}s") from None
    check(rc == 0, tag, f"child exited {rc}")
    with open(result_path) as f:
        result = json.load(f)
    log(f"{tag}: child done in {time.perf_counter() - t0:.1f}s")
    return result


def run_smoke(chips: int, workdir: str) -> dict:
    if chips == 1:
        trained = run_child("trainer", workdir, "trainer")
        run_child("kernels", workdir, "kernels")
        ckpt, requests = trained["checkpoint"], trained["requests"]
        greedy = ("--config", trained["greedy_config"])
        server_phase(ckpt, workdir, "server", requests)
        runs = {
            tag: server_phase(ckpt, workdir, tag, requests, (*greedy, *extra))
            for tag, extra in (
                ("server-greedy-jnp", ()),
                ("server-greedy-pallas", ("--attention", "pallas")),
            )
        }
        _check_parity(runs, "server-greedy-jnp")
        log("multichip: not requested (run with --chips 4 on a four-chip "
            "host)")
        return trained["device"]
    trained = run_child("trainer", workdir, "trainer-fsdp2-tp2", chips,
                        mesh={"dp": 1, "fsdp": 2, "tp": 2})
    run_child("trainer", workdir, "trainer-dp2-fsdp2", chips,
              mesh={"dp": 2, "fsdp": 2})
    greedy = ("--config", trained["greedy_config"])
    runs = {
        tag: server_phase(trained["checkpoint"], workdir, tag,
                          trained["requests"], (*greedy, *extra))
        for tag, extra in (
            ("server-one-chip", ()),
            ("server-tp2-fsdp2", ("--mesh", "tp=2,fsdp=2")),
            ("server-tp4", ("--mesh", "tp=4")),
        )
    }
    _check_parity(runs, "server-one-chip")
    run_child("dryrun", workdir, "dryrun-multichip", chips)
    return trained["device"]


def _check_parity(runs: dict, reference: str) -> None:
    want = runs[reference]["tokens"]
    for tag, run in runs.items():
        check(run["tokens"] == want, tag,
              f"greedy tokens {run['tokens']} != {reference}'s {want}")
        if tag != reference:
            log(f"{tag}: greedy tokens identical to {reference}'s")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 = the four-chip phases (fails on fewer "
                             "devices); default: the one-chip run")
    parser.add_argument("--phase", choices=("trainer", "kernels", "dryrun"),
                        help=argparse.SUPPRESS)  # child entry
    parser.add_argument("--workdir", help=argparse.SUPPRESS)
    parser.add_argument("--result", help=argparse.SUPPRESS)
    parser.add_argument("--tag", default="", help=argparse.SUPPRESS)
    parser.add_argument("--mesh", default="", help=argparse.SUPPRESS)
    parser.add_argument("--min-devices", type=int, default=1,
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    try:
        if args.phase:
            return _child_main(args)
        t0 = time.perf_counter()
        workdir = tempfile.mkdtemp(prefix="chip_smoke_")
        try:
            device = run_smoke(args.chips, workdir)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        log(f"all phases passed in {time.perf_counter() - t0:.0f}s")
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
