"""chip_smoke.py's phases at toy widths on the CPU, its refusal to pass
without a TPU, and the compile-cache helper it (and every other entry
point) calls.

The real run — gpt2-124M widths, Mosaic-compiled kernels — needs the
chip and goes through the builder's chip tool; what tier-1 can hold is
that the phases still wire up through the registries and the serve CLI,
that the script cannot exit 0 on a CPU backend or away from the repo,
and that the cache lands where the contract says.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = {
    "model_spec": {"vocab_size": 257, "n_layer": 2, "n_head": 4,
                   "d_model": 64, "n_positions": 64},
    "compute_dtype": "float32",
    "batch": 8,
    "prompt_tokens": 4,
    "gen_tokens": 8,
}


def test_trainer_and_server_phases_at_toy_width(tmp_path):
    """trainer_phase (two checked PPO cycles + save) feeds server_phase
    (serve CLI child: exact token counts, /metrics invariants, SIGTERM
    drain) — the same functions main() runs on the chip."""
    trained = chip_smoke.trainer_phase(str(tmp_path), width=TOY)
    assert os.path.isdir(trained["checkpoint"])
    requests = [(tokens, min(max_new, 8))
                for tokens, max_new in trained["requests"]]
    assert len(requests) == len(chip_smoke.SERVE_MAX_NEW)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # one device is enough for the child; 8 virtual ones only slow its boot
    env.pop("XLA_FLAGS", None)
    served = chip_smoke.server_phase(
        trained["checkpoint"], str(tmp_path), "toy", requests,
        ("--config", trained["greedy_config"]),
        buckets="2x16x8,4x16x8", boot_timeout=120.0, env=env,
    )
    assert [len(t) for t in served["tokens"]] == [2, 5, 8, 8]


def test_failed_check_names_its_phase():
    with pytest.raises(chip_smoke.SmokeFailure, match="phase=server"):
        chip_smoke.check(False, "server", "made to fail")


def test_main_exits_nonzero_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "FAILED phase=device" in proc.stderr


def test_main_exits_nonzero_away_from_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# -- the compile-cache helper ------------------------------------------- #


def _cache_dir_from(cwd, env_value=None):
    """What enable_compile_cache() returns and what it left in
    jax.config, from a fresh interpreter started in ``cwd``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    code = (
        "import jax\n"
        "from trlx_tpu.utils.compile_cache import enable_compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "print(enable_compile_cache())\n"
        "print(before == jax.config.jax_compilation_cache_dir)\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout.split("\n")
    return out[0], out[1] == "True", out[2]


def test_cache_dir_is_fixed_under_the_checkout_from_any_cwd(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    for cwd in (REPO, str(tmp_path)):
        returned, untouched, configured = _cache_dir_from(cwd)
        assert returned == configured == want
        assert not untouched


def test_cache_env_var_wins_and_code_sets_nothing(tmp_path):
    placed = str(tmp_path / "placed")
    returned, untouched, configured = _cache_dir_from(REPO, placed)
    assert returned == placed
    # JAX read the variable itself; the helper changed no config
    assert untouched and configured == placed


def test_importing_the_package_does_not_enable_the_cache():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, trlx_tpu, trlx_tpu.utils, trlx_tpu.serve\n"
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    ).stdout.strip()
    assert out == "None"


# -- Mosaic itself, without a chip --------------------------------------- #


@pytest.fixture(scope="module")
def on_chip():
    """One chip of a described v5e as a sharding: what a compile-only
    lowering places its arguments on. Made inside a fixture, never at
    import: only the worker that runs this file loads libtpu."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu on this host: nothing to compile with
        pytest.skip(f"no compile-only TPU topology here: {e!r}")
    return SingleDeviceSharding(topology.devices[0])


def test_kernels_compile_under_mosaic_for_a_v5e_from_this_host(
        on_chip, monkeypatch):
    """libtpu ships the compiler, and jax can hand it a compile-only v5e
    topology with no TPU attached: the same Mosaic passes the chip run
    goes through, minus the numbers. tests/test_kernel_lowering.py stops
    at Pallas' block rules; this goes on to Mosaic's own objections (int8
    tiles are (32, 128), so a 16-row int8 page; hd = 64 under 128 lanes;
    the strided per-head load) — each a chip-minute saved when it
    breaks."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops import pallas_mode
    from trlx_tpu.ops.paged_attention import paged_decode_attention
    from trlx_tpu.ops.pallas_attention import flash_attention

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    def compile_for_v5e(fn, *args):
        # conftest's "highest" matmul precision is for f32 CPU parity; on
        # bf16 MXU operands Mosaic refuses it ("Bad lhs type"), and no
        # TPU entry point sets it
        with jax.default_matmul_precision("default"):
            text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text

    S, max_pages, num_pages = 16, 4, 80
    for H, Hkv, hd in ((12, 12, 64), (16, 16, 256), (32, 8, 128)):
        for page_size in (16, 64):
            pool = (num_pages, page_size, Hkv, hd)
            for pages in (
                sds(pool, jnp.bfloat16),
                (sds(pool, jnp.int8), sds(pool[:3], jnp.float32)),
            ):
                compile_for_v5e(
                    paged_decode_attention,
                    sds((S, H, hd), jnp.bfloat16), pages, pages,
                    sds((S, max_pages), jnp.int32),
                    sds((S, max_pages * page_size), jnp.float32),
                )
    qkv = sds((2, 1024, 12, 64), jnp.bfloat16)
    mask = sds((2, 1024), jnp.int32)
    compile_for_v5e(
        jax.grad(
            lambda q, k, v, m: flash_attention(
                q, k, v, m, 128, 128, True
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ),
        qkv, qkv, qkv, mask,
    )


@pytest.mark.parametrize("max_pages,num_pages", [(454, 7168), (66, 2560)],
                         ids=["full-table-454", "window-ring-66"])
def test_paged_decode_compiles_at_the_long_context_cells_size(
        on_chip, monkeypatch, max_pages, num_pages):
    """The block walk as command-a-plus-05-2026.serve-longshort32 runs it
    (128 query heads over 8 kv heads of 128, pages of 64, 32 slots, the
    pool left in HBM and fetched a block of pages ahead), through Mosaic
    for a v5e: the sizes the small cases above never reach (VMEM for 8
    pages of K and V twice over, the fold of 64 x 8 keys, a table of 454
    in scalar memory)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops import pallas_mode
    from trlx_tpu.ops.paged_attention import (
        block_plan,
        paged_decode_attention,
    )

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    S, H, Hkv, hd, page_size = 32, 128, 8, 128, 64
    pool = (num_pages, page_size, Hkv, hd)
    assert block_plan(pool, jnp.bfloat16, max_pages)[0] > 1
    pages = sds(pool, jnp.bfloat16)
    with jax.default_matmul_precision("default"):  # as on the chip
        text = jax.jit(paged_decode_attention).lower(
            sds((S, H, hd), jnp.bfloat16), pages, pages,
            sds((S, max_pages), jnp.int32),
            sds((S, max_pages * page_size), jnp.float32),
        ).compile().as_text()
    assert "tpu_custom_call" in text
    # the pools reach the kernel as they are stored: no copy of either
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"[{num_pages}," in line]


# -- what XLA:TPU makes of the decode step's projections ------------------ #


@pytest.mark.parametrize(
    "arch, heads, head_dim, rows",
    [("gptj", 16, 256, 16), ("gpt2", 25, 64, 128)],
    ids=["16x256-gptj6b", "25x64-gpt2xl"],
)
def test_decode_step_streams_its_qkv_weights_as_stored(
        on_chip, monkeypatch, arch, heads, head_dim, rows):
    """The paged decode step at gpt-j-6B's and gpt2-xl's attention widths,
    compiled for a v5e: no ``copy`` / ``transpose`` /
    ``slice_bitcast_fusion`` as large as a q, k or v matrix (on the chip
    they were a third of gpt-j-6B's step: three matrices a layer written
    out again in the order a folded dot wants). The control is the
    projection as it stood (test_decode_qkv.qkv_folded): it must still
    show them, or this probe has stopped seeing what it guards."""
    import jax
    import jax.numpy as jnp

    from test_decode_qkv import qkv_folded
    from trlx_tpu.data.configs import ModelSpec
    from trlx_tpu.models import generation as G
    from trlx_tpu.models import transformer as T
    from trlx_tpu.models.policy import HydraPolicy
    from trlx_tpu.utils.hlo_text import large_moves

    d = heads * head_dim
    spec = ModelSpec(arch=arch, vocab_size=512, n_layer=3, n_head=heads,
                     d_model=d, d_ff=d, n_positions=256,
                     rotary_dim=64 if arch == "gptj" else 0)
    policy = HydraPolicy(spec=spec, num_layers_unfrozen=1,
                         compute_dtype=jnp.bfloat16)
    page_size, max_pages = 8, 1  # a pool leaf stays under one matrix
    config = G.GenerationConfig(
        gen_size=1, sampling=G.SamplingParams(do_sample=False),
        eos_token_id=256, pad_token_id=0, min_new_tokens=0,
    )

    def arguments():
        params = jax.tree_util.tree_map(  # kept in bfloat16, as served
            lambda x: x.astype(jnp.bfloat16),
            policy.init(jax.random.PRNGKey(0)),
        )
        blocks = policy.all_blocks(params)  # stacked [2, ..] + top [1, ..]
        embed, ln_f = policy.head_params_for_decode(params)
        _, seg_sizes = G._segments_of(blocks)
        pool = G.init_page_pool(spec, seg_sizes, rows * max_pages,
                                page_size)
        state = G.init_slot_state(rows, max_pages * page_size,
                                  spec.vocab_size, max_pages=max_pages)
        return blocks, embed, ln_f, pool, state, jnp.int32(0)

    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip),
        jax.eval_shape(arguments),
    )

    def moves():
        def run_decode_step(blocks, embed, ln_f, pool, state, seed):
            return G.decode_step(spec, blocks, embed, ln_f, pool, state,
                                 seed, config, compute_dtype=jnp.bfloat16)

        with jax.default_matmul_precision("default"):  # as on the chip
            text = jax.jit(run_decode_step, donate_argnums=(3, 4)).lower(
                *args).compile().as_text()
        return large_moves(text, d * d * 2)

    assert moves() == []
    monkeypatch.setattr(T, "_qkv", qkv_folded)
    folded = moves()
    assert len(folded) >= 3, folded  # q, k and v, in every layer
    assert all("attn" in m.op_name or not m.op_name for m in folded), folded


def test_update_program_runs_its_frozen_trunk_outside_the_epochs_loop(
        on_chip):
    """The PPO update at gpt2-xl's widths (8 layers, two of them trained,
    2 epochs), compiled for a v5e: the loop that carries the six stacked
    frozen layers stands in the entry computation, and the epochs' loop
    holds the top's forward and backward alone. XLA:TPU does not move a
    loop out of the loop around it (on the chip three of gpt2-xl's four
    46-layer forwards an update recomputed the same array), and XLA:CPU
    does, so this is the one test that reads the compiler that has the
    problem. The control is the whole pass scanned, as the update stood:
    it must still show the trunk's loop inside, or the compiler has
    learned to move it and this probe guards nothing."""
    import jax
    import jax.numpy as jnp
    import optax

    from test_ppo_update_structure import batch, ppo_method, scanned_whole
    from trlx_tpu.data.configs import ModelSpec
    from trlx_tpu.models.policy import HydraPolicy
    from trlx_tpu.trainers.ppo_trainer import ppo_update_fns
    from trlx_tpu.utils.hlo_text import ENTRY, whiles_by_computation

    L, k, epochs = 8, 2, 2
    spec = ModelSpec(arch="gpt2", vocab_size=50257, n_layer=L, n_head=25,
                     d_model=1600, d_ff=6400, n_positions=1024)
    policy = HydraPolicy(spec=spec, num_layers_unfrozen=k,
                         compute_dtype=jnp.bfloat16)
    opt = optax.adamw(1e-6)
    train_step, train_multi, _ = ppo_update_fns(
        policy, ppo_method(epochs), opt
    )

    def arguments():
        params = policy._init(jax.random.PRNGKey(0), jnp.float32,
                              jnp.bfloat16)  # a bf16 trunk under a f32 top
        return (params, opt.init(params["trainable"]),
                batch(rows=16, gen=48))

    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip),
        jax.eval_shape(arguments),
    )

    def trunk_loops(fn):
        """(in the entry computation, inside another loop's body)"""
        with jax.default_matmul_precision("default"):  # as on the chip
            text = jax.jit(fn, donate_argnums=(0, 1)).lower(
                *args).compile().as_text()
        whiles = whiles_by_computation(text)
        is_trunk = lambda w: (
            ("bf16", (L - k, 1600, 6400)) in w.carry
            and w.body not in whiles  # the layers' own loop, not one around it
        )
        inside = [w for where, ws in whiles.items() if where != ENTRY
                  for w in ws if is_trunk(w)]
        return [w for w in whiles[ENTRY] if is_trunk(w)], inside

    at_entry, inside = trunk_loops(train_multi)
    assert len(at_entry) == 1 and inside == [], (at_entry, inside)
    at_entry, inside = trunk_loops(scanned_whole(train_step, epochs))
    assert at_entry == [] and len(inside) == 1, (at_entry, inside)


def test_latent_decode_step_compiles_for_a_v5e_and_streams_its_projections(
        on_chip, monkeypatch):
    """The decode step of a latent-attention model at the published
    attention widths (64 heads of 128 + 64 / 128 over a latent of 512 +
    64), compiled for a v5e with the absorbed Pallas kernel: Mosaic takes
    the latent page (a latent of 576 kept out to 640: at 576 it says
    "Slice shape along dimension 2 must be aligned to tiling (128)"), the
    pool is written in place, and none of the five projections is written
    out again in another layout (``w_uk`` / ``w_uv`` are kept by head for
    that: as [r, H * dn] matrices each was re-laid, 8 MiB a layer, for the
    absorption's small dots)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.data.configs import ModelSpec
    from trlx_tpu.models import generation as G
    from trlx_tpu.models import transformer as T
    from trlx_tpu.ops import pallas_mode
    from trlx_tpu.ops.latent_attention import latent_decode_attention
    from trlx_tpu.utils.hlo_text import large_moves

    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)
    spec = ModelSpec(
        arch="sarvam_mla", vocab_size=512, n_layer=2, n_head=64,
        d_model=4096, d_ff=256, n_positions=4096, tie_lm_head=False,
        layer_norm_epsilon=1e-6, n_experts=16, experts_per_token=2,
        n_shared_experts=1, expert_width=128, experts_held=4,
        router_bias=True, routed_scaling_factor=2.5, first_dense_layers=1,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_factor=40.0, rope_mscale_all_dim=1.0,
        rope_original_positions=4096,
    )
    assert spec.latent_page_width == 640
    rows, page_size, max_pages, num_pages = 32, 64, 40, 600
    config = G.GenerationConfig(
        gen_size=1, sampling=G.SamplingParams(do_sample=False),
        eos_token_id=256, pad_token_id=0, min_new_tokens=0,
    )

    def arguments():
        blocks = T.init_block_params(jax.random.PRNGKey(0), spec, 2,
                                     jnp.bfloat16)
        embed = T.init_embed_params(jax.random.PRNGKey(1), spec, jnp.bfloat16)
        pool = G.init_page_pool(spec, [1, 1], num_pages, page_size)
        state = G.init_slot_state(rows, max_pages * page_size,
                                  spec.vocab_size, max_pages=max_pages)
        return (blocks, embed, T.init_ln_f_params(spec, jnp.bfloat16), pool,
                state, jnp.int32(0))

    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip),
        jax.eval_shape(arguments),
    )

    def run_decode_step(blocks, embed, ln_f, pool, state, seed):
        return G.decode_step(spec, blocks, embed, ln_f, pool, state, seed,
                             config, compute_dtype=jnp.bfloat16,
                             paged_decode_fn=latent_decode_attention)

    with jax.default_matmul_precision("default"):  # as on the chip
        compiled = jax.jit(run_decode_step, donate_argnums=(3, 4)).lower(
            *args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 2
    assert "latent_decode_attention" in text
    smallest = 4096 * (512 + 64) * 2  # w_dkv: the smallest of the five
    assert large_moves(text, smallest) == []
    pool_bytes = 2 * num_pages * page_size * 640 * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
