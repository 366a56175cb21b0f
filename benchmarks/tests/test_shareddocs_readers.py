"""The counts and readers of the ``.shareddocs`` per-layer metrics, on a
hand-made trace, flight list and request list with known answers, the
nothing-to-read cases included (a program without the counters or the op
metadata, as the parent commit is; a run that was not traced), and every
metric's data file read once through the harness's own loop."""
import importlib
import json
import os

import pytest

from benchmarks.lib import counts_sarvam_mla as C
from benchmarks.readers import (decode_roofline_latent, flight_per_expert_layer, kv_live_latent, scope_ms,
                                scope_roofline_latent, serve_mfu_latent, shared_page_reads)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = json.load(open(os.path.join(HERE, "configs", "sarvam-105b.json")))["model_spec"]
CELL = "sarvam-105b.serve-shareddocs32"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns
DEC = "jit_run_decode_step"


# ------------------------------------------------------------ the counts
def test_the_configurations_own_arithmetic():
    assert C.attn_params(SPEC) == 94_633_984 and C.dense_ffn_params(SPEC) == 201_326_592  # ISSUE 34: 94.63M, dense layer 295.97M
    assert C.attn_params(SPEC) + C.dense_ffn_params(SPEC) + 2 * 4096 + 512 == 295_969_280
    assert C.expert_params(SPEC) == 25_165_824 and C.shared_params(SPEC) == 25_165_824 and C.router_params(SPEC) == 524_288
    held = (C.attn_params(SPEC) * 5 + C.dense_ffn_params(SPEC) + 4 * (C.router_params(SPEC) + C.shared_params(SPEC)
            + 32 * C.expert_params(SPEC)) + 2 * 4096 * 65536)
    assert round(held / 1e9, 3) == 4.535  # ISSUE 34: 4.535B parameters held
    assert C.pair_flops_absorbed(SPEC) == 64 * 1088 * 2 == 139_264 and C.pair_flops_up_projected(SPEC) == 40_960
    assert C.key_up_projection_flops(SPEC) == 16_777_216
    # the orders cross where a key's up-projection equals what absorbing adds to each query: T = 171
    assert int(C.key_up_projection_flops(SPEC) / (C.pair_flops_absorbed(SPEC) - C.pair_flops_up_projected(SPEC))) == 170
    assert C.latent_bytes_per_key(SPEC) == 1152 and C.latent_bytes_per_key(SPEC, as_stored=True) == 1280


def test_a_chunk_in_either_order():
    short, long = (C.chunk_attention_flops(SPEC, 24576, 128, a) for a in (True, False))
    assert short < long  # a question of 128 over a cached document is cheaper absorbed
    short, long = (C.chunk_attention_flops(SPEC, 24576, 1024, a) for a in (True, False))
    assert long < short  # a chunk of 1,024 up-projected
    assert C.chunk_attention_flops(SPEC, 100, 1, True) == 5 * (139_264 * 101 + 2 * C.absorb_params(SPEC))
    assert C.forward_flops(SPEC, 10, 2, 2) == pytest.approx(
        2 * C.token_flops(SPEC) + 5 * 40_960 * (11 + 12) + C.head_flops(SPEC, 2))


def test_floors_of_a_step_the_kernel_and_the_expert_layers():
    contexts = [25_000] * 32
    k = C.latent_attn_step_floor(SPEC, contexts, 64, PEAKS)
    pages = -(-25_000 // 64) * 64
    assert k["bytes"] == 5 * 32 * pages * 1152 and k["flops"] == 5 * 139_264 * 32 * 25_000
    assert k["bound"] == "hbm" and k["seconds"] == pytest.approx(k["bytes"] / 819e9)  # 121 operations a byte against 240
    assert 5.5e-3 < k["seconds"] < 5.9e-3  # ISSUE 34: 4.7 GB over 5 layers = 5.8 ms
    moe = C.moe_step_floor(SPEC, 32, 4 * 27.7, 4 * 64, PEAKS)
    assert moe["bound"] == "hbm" and 5.7e9 < moe["bytes"] < 5.9e9
    step = C.decode_step_floor(SPEC, contexts, 4 * 27.7, 4 * 64, PEAKS)
    assert step["bytes"] == pytest.approx(C.weights_outside_experts_bytes(SPEC) + 4 * 27.7 * C.expert_bytes(SPEC)
                                          + 5 * 32 * 25_000 * 1152)
    assert 7.6e9 < step["bytes"] - 5 * 32 * 25_000 * 1152 < 7.8e9  # ISSUE 34: 7.7 GB of weights a step
    assert step["bound"] == "hbm" and 14e-3 < step["seconds"] < 16e-3


# ------------------------------------------------------------ a hand-made window
def measured(traced=True, **drop):
    flight = [{"t": 10.0 + 0.1 * i, "active": 2, "experts_hit": 40, "pairs_step": 8, "pairs_here": 8 + 100 * (i == 2),
               "moe_load": 2.0, "pages_read": 700 + i, "shared_pages_read": 600, "step": i, "admit_ms": 0.5,
               "harvest_ms": 0.25, "fetch_ms": 20.0} for i in range(6)]
    for rec in flight:
        for k in drop:
            rec.pop(k, None)
    long = {"first_token": 9.0, "harvested": 11.0, "prompt_len": 20000, "n_out": 201, "prefix_blocks_hit": 312}
    other = {"first_token": 10.15, "harvested": 12.0, "prompt_len": 20030, "n_out": 100, "prefix_blocks_hit": 312}
    gone = {"first_token": 8.0, "harvested": 9.5, "prompt_len": 500, "n_out": 10, "prefix_blocks_hit": 0}
    m = {"t0": 10.0, "t1": 10.5, "seconds": 0.5, "flight": flight, "requests": [long, other, gone], "page_size": 64,
         "prompt_tokens": 1000, "prefix_tokens_saved": 900, "slots": 2,
         "decode_scopes": {"fusion.1": "jit(run_decode_step)/layer1/attn/q_proj/dot_general",
                           "fusion.2": "jit(run_decode_step)/layer1/attn/absorb/dot_general",
                           "latent_decode_attention.3": "jit(run_decode_step)/layer1/attn/latent_decode_attention/pallas_call",
                           "copy.4": "", "fusion.5": "jit(run_decode_step)/layer1/mlp/router/dot_general",
                           "fusion.6": "jit(run_decode_step)/layer1/mlp/experts/gather",
                           "ragged-dot-none.7": "ragged-dot-none",
                           "fusion.8": "jit(run_decode_step)/layer1/mlp/shared/dot_general",
                           "fusion.9": "jit(run_decode_step)/layer0/mlp/dot_general",
                           "fusion.10": "jit(run_decode_step)/head/dot_general"}}
    if traced:
        m["traced"] = (10.05, 10.35)
    return m


def trace():
    """Two runs of the decode program (10 ms each) and a prefill between them whose operations carry the same names."""
    names = [f"%{n} = x" for n in measured()["decode_scopes"]]
    run = lambda t0: [(n, t0 + i * MS, MS) for i, n in enumerate(names)]
    modules = [(f"{DEC}(1)", 0, 10 * MS), ("jit_run_prefill_sfx_b1p128(2)", 12 * MS, 5 * MS), (f"{DEC}(1)", 20 * MS, 10 * MS)]
    ops = run(0) + [("%fusion.6 = x", 12 * MS, 5 * MS)] + run(20 * MS)
    return {"devices": {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops}}, "host": []}


def ctx(**kw):
    base = {"measured": measured(), "trace": trace(), "spec": SPEC, "peaks": PEAKS, "notes": {},
            "device": {"count": 1}, "cell": {}, "mix": {}}
    base["reduced"] = {"modules": {f"{DEC}(1)": {"count": 2, "seconds": 0.020},
                                   "jit_run_prefill_sfx_b1p128(2)": {"count": 1, "seconds": 0.005}},
                       "busy_s": 0.025, "window_s": 0.03}
    return {**base, **kw}


def metric_file(name):
    with open(os.path.join(HERE, "layer_metrics", f"{name}.shareddocs.json")) as f:
        return json.load(f)


def read_metric(name, c):
    spec = metric_file(name)
    return importlib.import_module(f"benchmarks.readers.{spec['reader']}").read(c, **spec.get("args", {}))


def test_the_scope_metrics_read_the_new_scopes():
    c = ctx()
    # router 1 + experts (gather 1, the grouped product it hands its scope to 1) + shared 1; layer 0's dense FFN is none of them
    assert read_metric("moe_ms_per_step", c) == pytest.approx(4.0)
    assert c["notes"]["moe_ms_per_step"] == {"router": 1.0, "experts": 2.0, "shared": 1.0, "other_ms": pytest.approx(6.0), "steps": 2.0}
    assert read_metric("latent_attn_ms_per_step", c) == pytest.approx(1.0)  # the custom call alone: the copy after it inherits nothing
    assert read_metric("absorb_ms_per_step", c) == pytest.approx(1.0)
    assert read_metric("attn_ms_per_step", c) == pytest.approx(3.0)  # q_proj, absorb, the kernel
    assert c["notes"]["attn_ms_per_step"]["latent_decode_attention"] == 1.0
    lacking = ctx(measured={**measured(), "decode_scopes": {"fusion.1": "jit(run_decode_step)/layer1.full/attn/dot_general"}})
    for name in ("moe_ms_per_step", "latent_attn_ms_per_step", "absorb_ms_per_step", "attn_ms_per_step"):
        assert read_metric(name, lacking) is None  # a program without the scopes, as the parent commit is
        assert read_metric(name, ctx(trace=None)) is None


def test_rooflines_are_floor_over_traced_time():
    c = ctx()
    got = read_metric("moe_roofline_pct", c)
    floors = [C.moe_step_floor(SPEC, n, 40, 8, PEAKS)["seconds"] for n in (1, 2, 2)]
    assert got == pytest.approx(100.0 * sum(floors) / 3 / 0.004) and c["notes"]["moe_floor"]["bound"] == "hbm"
    got = read_metric("latent_attn_roofline_pct", c)
    note = c["notes"]["latent_attn_floor"]
    assert got == pytest.approx(100.0 * note["floor_ms"] / 1.0) and 0 < got < 100 and note["bound"] == "hbm"
    assert note["bytes_per_step"] == pytest.approx(sum(
        C.latent_attn_step_floor(SPEC, rows, 64, PEAKS)["bytes"] for rows in (
            [20111], [20121, 20033], [20131, 20038])) / 3)
    got = read_metric("decode_roofline_pct", c)
    note = c["notes"]["decode_floor.latent"]
    assert got == pytest.approx(100.0 * note["floor_ms"] / 10.0) and note["rows"] == pytest.approx(5 / 3)
    lacking = ctx(measured=measured(experts_hit=True))  # a program without the counter
    for name in ("moe_roofline_pct", "latent_attn_roofline_pct", "decode_roofline_pct"):
        assert read_metric(name, lacking) is None
    assert decode_roofline_latent.read(ctx(reduced=None), module=DEC) is None
    assert scope_roofline_latent.read(ctx(trace=None), part="moe", module=DEC, pattern="x") is None


def test_counters_of_the_flight_record():
    c = ctx()
    assert read_metric("experts_hit_per_layer", c) == pytest.approx(40 / 4)  # four layers route, the first is dense
    assert read_metric("expert_load_max_over_mean", c) == pytest.approx(2.0)
    assert read_metric("shared_page_reads_pct", c) == pytest.approx(100.0 * 3600 / (4200 + 15))
    assert c["notes"]["page_reads"]["shared_per_step"] == 600
    assert read_metric("prefix_hit_token_pct", c) == pytest.approx(90.0)
    assert read_metric("slot_occupancy_pct", c) == pytest.approx(100.0)
    for field, name in (("shared_pages_read", "shared_page_reads_pct"), ("experts_hit", "experts_hit_per_layer")):
        assert read_metric(name, ctx(measured=measured(**{field: True}))) is None
    assert flight_per_expert_layer.read(ctx(measured={**measured(), "flight": []}), field="experts_hit") is None
    assert shared_page_reads.read(ctx(measured={**measured(), "flight": []})) is None


def test_kv_live_counts_what_the_pool_takes():
    c = ctx()
    got = read_metric("kv_live_gib", c)
    note = c["notes"]["kv_live_gib"]
    assert got == note["held"] == pytest.approx(note["read"] * 1280 / 1152) and note["steps"] == 3
    assert note["per_head_kv_would_hold"] == pytest.approx(note["read"] * 64 * 320 / 576)
    assert kv_live_latent.read(ctx(measured={k: v for k, v in measured().items() if k != "page_size"})) is None


def test_mfu_is_the_whole_steps_share():
    c = ctx()
    got = read_metric("step_mfu_pct", c)
    parts = c["notes"]["window_flops"]
    # the other client's first token fell inside: its 62 tokens over 19,968 cached ones; the long one's did not
    assert parts["prefill"] == C.forward_flops(SPEC, 312 * 64, 20030 - 312 * 64, 1)
    assert parts["pairs"] == (6 * 8 + 100) * C.pair_flops(SPEC)
    long_dec = C.forward_flops(SPEC, 20000, 200, 200) * 0.5 / 2.0
    other_dec = C.forward_flops(SPEC, 20030, 99, 99) * 0.35 / 1.85
    assert parts["decode"] == pytest.approx(long_dec + other_dec)
    assert got == pytest.approx(100.0 * sum(parts.values()) / 0.5 / 197e12)
    assert serve_mfu_latent.read(ctx(measured=measured(pairs_here=True))) is None


def test_every_shareddocs_metric_is_declared_and_reads_the_fixture():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = [m for m in bench["per_layer"] if m["name"].endswith(".shareddocs")]
    assert len(declared) == 21 and all(m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" for m in declared)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    c = ctx(device={"count": 1, "memory_peak_bytes": 12 * 2**30}, cell={"chips": 1})
    unread = []
    for m in declared:
        spec = metric_file(m["name"][:-len(".shareddocs")])
        assert {k: spec[k] for k in m} == m  # the data file says what BENCHMARK.json says
        try:
            value = importlib.import_module(f"benchmarks.readers.{spec['reader']}").read(c, **spec.get("args", {}))
        except (KeyError, TypeError):  # readers of the real trace's planes (idle share, gaps between programs)
            value = "needs a real trace"
        if value is None:
            unread.append(m["name"])
    assert not unread
