"""The counts and readers of the ``.longshort`` per-layer metrics, on a
hand-made trace, flight list and request list with known answers, the
nothing-to-read cases included (a program without the counters or the op
metadata, a run that was not traced)."""
import json
import os

import pytest

from benchmarks.lib import counts_cohere2_moe as C
from benchmarks.lib import steps_longshort as S
from benchmarks.readers import (decode_roofline_moe, flight_per, kv_live, prefix_hit, scope_ms, scope_roofline,
                                serve_mfu_moe)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = json.load(open(os.path.join(HERE, "configs", "command-a-plus-05-2026.json")))["model_spec"]
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns
DEC = "jit_run_decode_step"
MOE = r"/layer\d+\.\w+/(router|experts|shared)(?:/|$)"
KER = r"/layer\d+\.(win|full)/attn/paged_decode_attention(?:/|$)"


# ------------------------------------------------------------ the counts
def test_the_configurations_own_arithmetic():
    outside = C.attn_params(SPEC) + C.shared_params(SPEC) + C.router_params(SPEC)
    assert C.attn_params(SPEC) == 142_606_336 and C.shared_params(SPEC) == 201_326_592
    assert C.router_params(SPEC) == 524_288 and C.expert_params(SPEC) == 50_331_648
    assert round(outside / 1e6, 1) == 344.5  # ISSUE 29: 344.5M a layer beside 128 experts of 50.33M
    assert C.layers_of(SPEC) == {"full": 1, "window": 3}
    assert C.kv_bytes_per_key(SPEC) == 4096  # 4 KiB a position a layer


@pytest.mark.parametrize("start, n", [(0, 1), (0, 5000), (4000, 300), (4095, 2), (20000, 128), (4096, 1)])
def test_keys_seen_is_the_sum_it_says(start, n):
    w = SPEC["window"]
    assert C.keys_seen(SPEC, "full", start, n) == sum(p + 1 for p in range(start, start + n))
    assert C.keys_seen(SPEC, "window", start, n) == sum(min(p + 1, w) for p in range(start, start + n))


def test_live_kv_by_class_and_by_page():
    kv = C.live_kv_bytes(SPEC, [20000, 300])
    assert kv["full"] == (20000 + 300) * 4096 and kv["window"] == 3 * (4096 + 300) * 4096
    assert kv["one_class"] == 4 * 20300 * 4096
    paged = C.live_kv_bytes(SPEC, [20000, 300], page_size=64)
    # 20000 keys: 313 pages; its window's first key 15904 lies in page 248, its last in page 312: 65 pages
    assert paged["full"] == (313 + 5) * 64 * 4096 and paged["window"] == 3 * (65 + 5) * 64 * 4096


def test_a_decode_steps_floor_is_its_bytes():
    f = C.decode_step_floor(SPEC, [20000] * 16 + [400] * 16, experts_hit=4 * 14, pairs=128, peaks=PEAKS)
    assert f["bound"] == "hbm"
    weights = C.weights_outside_experts_bytes(SPEC) + 56 * C.expert_bytes(SPEC)
    assert 8.5e9 < weights < 8.8e9  # ISSUE 29: 8.6 GB of weights a step of 32 rows
    assert f["bytes"] == weights + sum(C.live_kv_bytes(SPEC, [20000] * 16 + [400] * 16)[k] for k in ("full", "window"))
    assert f["seconds"] == f["bytes"] / 819e9


# ------------------------------------------------------------ a hand-made window
def measured(traced=True, **drop):
    flight = [{"t": 10.0 + 0.1 * i, "active": 2, "experts_hit": 20, "pairs_step": 8, "pairs_here": 8 + 100 * (i == 2),
               "moe_load": 2.0, "window_freed": i % 2, "step": i} for i in range(6)]
    for rec in flight:
        for k in drop:
            rec.pop(k, None)
    long = {"first_token": 9.0, "harvested": 11.0, "prompt_len": 20000, "n_out": 201, "prefix_blocks_hit": 312}
    short = {"first_token": 10.15, "harvested": 12.0, "prompt_len": 300, "n_out": 100, "prefix_blocks_hit": 0}
    gone = {"first_token": 8.0, "harvested": 9.5, "prompt_len": 500, "n_out": 10, "prefix_blocks_hit": 0}
    m = {"t0": 10.0, "t1": 10.5, "seconds": 0.5, "flight": flight, "requests": [long, short, gone], "page_size": 64,
         "prompt_tokens": 1000, "prefix_tokens_saved": 900,
         "decode_scopes": {"fusion.1": "jit(run_decode_step)/layer0.win/attn/dot_general",
                           "paged_decode_attention.2": "jit(run_decode_step)/layer0.win/attn/paged_decode_attention/pallas_call",
                           "copy.3": "", "fusion.4": "jit(run_decode_step)/layer0.win/router/dot_general",
                           "fusion.5": "jit(run_decode_step)/layer0.win/experts/gather",
                           "ragged-dot-none.6": "ragged-dot-none",
                           "fusion.7": "jit(run_decode_step)/layer0.win/shared/dot_general",
                           "fusion.8": "jit(run_decode_step)/head/dot_general"}}
    if traced:
        m["traced"] = (10.05, 10.35)
    return m


def trace():
    """Two runs of the decode program (8 ms each) and a prefill between them whose operations carry the same names."""
    def run(t0):
        names = ["%fusion.1 = x", "%paged_decode_attention.2 = x", "%copy.3 = x", "%fusion.4 = x", "%fusion.5 = x",
                 "%ragged-dot-none.6 = x", "%fusion.7 = x", "%fusion.8 = x"]
        return [(n, t0 + i * MS, MS) for i, n in enumerate(names)]
    modules = [(f"{DEC}(1)", 0, 8 * MS), ("jit_run_prefill_sfx_b1p128(2)", 10 * MS, 5 * MS), (f"{DEC}(1)", 20 * MS, 8 * MS)]
    ops = run(0) + [("%fusion.5 = x", 10 * MS, 5 * MS)] + run(20 * MS)
    return {"devices": {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops}}, "host": []}


def ctx(**kw):
    base = {"measured": measured(), "trace": trace(), "spec": SPEC, "peaks": PEAKS, "notes": {},
            "device": {"count": 1}}
    base["reduced"] = {"modules": {f"{DEC}(1)": {"count": 2, "seconds": 0.016},
                                   "jit_run_prefill_sfx_b1p128(2)": {"count": 1, "seconds": 0.005}}}
    return {**base, **kw}


def test_steps_and_contexts_of_the_traced_interval():
    m = measured()
    steps = S.steps_in(m)
    assert [b["step"] for _, b in steps] == [1, 2, 3]  # records stamped inside (10.05, 10.35)
    # at the record of 10.1 the short request had no token out; at 10.2 it has
    assert S.contexts_at(m, *steps[0]) == [20000 + int((10.1 - 9.0) / 2.0 * 200) + 1]
    assert S.contexts_at(m, *steps[1]) == [20000 + int((10.2 - 9.0) / 2.0 * 200) + 1, 300 + int((10.2 - 10.15) / 1.85 * 99) + 1]
    assert len(S.steps_in(measured(traced=False))) == 5  # every record of the window with one before it


def test_scope_ms_joins_the_metadata_and_hands_scopes_on():
    c = ctx()
    # router 1 + experts (gather 1, the grouped product it hands its scope to 1) + shared 1, the prefill's ops left out
    assert scope_ms.read(c, module=DEC, pattern=MOE, note="moe") == pytest.approx(4.0)
    assert c["notes"]["moe"] == {"router": 1.0, "experts": 2.0, "shared": 1.0, "other_ms": 4.0, "steps": 2.0}
    # the kernel alone: the copy after it inherits nothing
    assert scope_ms.read(c, module=DEC, pattern=KER, inherit=False) == pytest.approx(1.0)
    assert scope_ms.read(ctx(trace=None), module=DEC, pattern=MOE) is None
    assert scope_ms.read(ctx(measured={**measured(), "decode_scopes": None}), module=DEC, pattern=MOE) is None
    assert scope_ms.read(c, module="jit_other", pattern=MOE) is None


def test_rooflines_are_floor_over_traced_time():
    c = ctx()
    got = scope_roofline.read(c, part="moe", module=DEC, pattern=MOE)
    floors = [C.moe_step_floor(SPEC, n, 20, 8, PEAKS)["seconds"] for n in (1, 2, 2)]
    assert got == pytest.approx(100.0 * sum(floors) / 3 / 0.004)
    assert c["notes"]["moe_floor"]["bound"] == "hbm"
    got = scope_roofline.read(c, part="paged_attn", module=DEC, pattern=KER, inherit=False)
    assert 0 < got < 100 and c["notes"]["paged_attn_floor"]["device_ms_per_step"] == pytest.approx(1.0)
    got = decode_roofline_moe.read(c, module=DEC)
    note = c["notes"]["decode_floor.moe"]
    assert got == pytest.approx(100.0 * note["floor_ms"] / 8.0) and note["rows"] == pytest.approx(5 / 3)
    assert note["rows_by_flight"] == 2 and note["experts_hit_per_step"] == 20
    lacking = ctx(measured=measured(experts_hit=True))  # a program without the counter
    assert scope_roofline.read(lacking, part="moe", module=DEC, pattern=MOE) is None
    assert decode_roofline_moe.read(lacking, module=DEC) is None
    assert decode_roofline_moe.read(ctx(reduced=None), module=DEC) is None


def test_counters_per_second_step_and_layer():
    c = ctx()
    assert flight_per.read(c, field="window_freed", per="second") == pytest.approx(3 / 0.5)
    assert flight_per.read(c, field="experts_hit", per="step_layer") == pytest.approx(20 / 4)
    assert flight_per.read(c, field="moe_load", per="step") == pytest.approx(2.0)
    assert flight_per.read(ctx(measured=measured(moe_load=True)), field="moe_load", per="step") is None
    assert prefix_hit.read(c) == pytest.approx(90.0)
    assert prefix_hit.read(ctx(measured={**measured(), "prompt_tokens": 0})) is None


def test_kv_live_by_class():
    c = ctx()
    got = kv_live.read(c)
    note = c["notes"]["kv_live_gib"]
    assert got == pytest.approx(note["full"] + note["window"]) and note["one_class"] > 2 * got
    assert note["steps"] == 3


def test_mfu_counts_the_prefill_the_forwards_and_the_pairs_made():
    c = ctx()
    got = serve_mfu_moe.read(c)
    parts = c["notes"]["window_flops"]
    # the short request's first token fell inside: its 300 tokens, no hit; the long one's did not
    assert parts["prefill"] == C.forward_flops(SPEC, 0, 300, 1)
    assert parts["pairs"] == (6 * 8 + 100) * C.pair_flops(SPEC)
    long_dec = C.forward_flops(SPEC, 20000, 200, 200) * 0.5 / 2.0
    short_dec = C.forward_flops(SPEC, 300, 99, 99) * 0.35 / 1.85
    assert parts["decode"] == pytest.approx(long_dec + short_dec)
    assert got == pytest.approx(100.0 * sum(parts.values()) / 0.5 / 197e12)
    assert serve_mfu_moe.read(ctx(measured=measured(pairs_here=True))) is None
