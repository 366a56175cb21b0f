"""The trace reduction on a recorded trace, the counts by hand, the traffic
generator, the result line and the data files."""
import json
import os
import re

import pytest

from benchmarks.lib import counts, lastline, peaks, trace_reduce, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


# ------------------------------------------------------------ trace reduction
@pytest.fixture(scope="module")
def tiny():
    # recorded on a TPU v5e (PR 24): three runs of one jitted tanh(x @ x) at 512x512, 10 ms apart
    return trace_reduce.load(os.path.join(HERE, "data", "tiny.xplane.pb"))


def test_trace_has_one_device_and_its_programs(tiny):
    assert list(tiny["devices"]) == ["/device:TPU:0"]
    r = trace_reduce.reduce(tiny)
    (name, m), = r["modules"].items()
    assert name.startswith("jit_tiny_step(") and m["count"] == 3
    assert m["seconds"] == pytest.approx(7.279e-6, rel=1e-3)  # 2427 + 2427 + 2425 ns
    assert trace_reduce.module_time(r, "jit_tiny_step", most_run=True) == (3, pytest.approx(7.279e-6, rel=1e-3))
    assert trace_reduce.module_time(r, "jit_absent") is None


def test_busy_union_and_idle_share(tiny):
    r = trace_reduce.reduce(tiny)
    assert r["busy_s"] == pytest.approx(7.26e-6, rel=1e-3)  # the operations' union, a little under the programs' sum
    assert r["window_s"] == pytest.approx(0.023446622, rel=1e-6)
    assert 1 - r["busy_s"] / r["window_s"] > 0.999
    assert r["device_ops"][0][0] == "kind:convolution_tanh_fusion" and len(r["device_ops"]) <= 10
    named = dict(r["idle_gaps"])
    # the two long gaps are the 10 ms sleeps (this trace was recorded with the Python tracer on)
    assert named["$time sleep"] == pytest.approx(0.0234, rel=0.02)


def test_union_and_loops():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    trace = {"devices": {"d": {"XLA Modules": [("jit_f(1)", 0, 100), ("jit_f(1)", 200, 100), ("jit_g(2)", 400, 50)],
                               "XLA Ops": [("%while.1 = ...", 10, 50), ("%while.2 = ...", 20, 10), ("%fusion.3 = f32[]", 25, 5),
                                           ("%while.1 = ...", 210, 70), ("%while.9 = ...", 400, 50)]}}, "host": []}
    assert trace_reduce.longest_loop_in(trace, "jit_f") == (2, pytest.approx(120e-9))
    assert trace_reduce.longest_loop_in(trace, "jit_h") is None
    r = trace_reduce.reduce(trace)
    assert r["device_ops"] == [["kind:fusion", 5e-9], ["fusion.3", 5e-9]]  # loops are not counted beside their bodies
    assert trace_reduce.kind("slice_bitcast_fusion.54.remat5") == "slice_bitcast_fusion" and trace_reduce.kind("copy.302") == "copy"
    assert r["busy_s"] == pytest.approx((50 + 70 + 50) * 1e-9)


# ------------------------------------------------------------ counts, by hand
def test_counts_gpt2_xl():
    spec = load("configs", "gpt2-xl.json")["model_spec"]
    assert counts.layer_matmul_params(spec) == 4 * 1600 * 1600 + 2 * 1600 * 6400 == 30_720_000
    # one token at context 10 through one block: 2 * 30.72e6 + 4 * 1600 * 10
    assert counts.layers_flops(spec, 1, 1, 10) == 2 * 30_720_000 + 64_000
    assert counts.head_flops(spec, 1) == 2 * 1600 * 50257
    assert counts.causal_context_sum(0, 52) == 52 * 53 // 2 and counts.causal_context_sum(4, 3) == 5 + 6 + 7
    assert counts.kv_bytes_per_token(spec) == 2 * 48 * 1600 * 2 == 307_200
    f = counts.ppo_cycle_flops(spec, 128, 4, 48, 2, 4)
    assert f["total"] == f["decode"] + f["score"] + f["update"]
    decode = 128 * (48 * (2 * 30_720_000 * 51 + 4 * 1600 * (51 * 52 // 2)) + 2 * 1600 * 50257 * 48)
    assert f["decode"] == pytest.approx(decode)
    ctx = 52 * 53 // 2
    top_fwd = 128 * (2 * (2 * 30_720_000 * 52 + 4 * 1600 * ctx) + 2 * 1600 * 50257 * 48 + 2 * (1600 * 3200 + 3200) * 48)
    update = 128 * 46 * (2 * 30_720_000 * 52 + 4 * 1600 * ctx) + 4 * 3 * top_fwd  # the trunk once, the top 4 x (fwd + 2 bwd)
    assert f["update"] == pytest.approx(update)
    assert 8.0e13 < f["total"] < 9.0e13


def test_counts_gpt_j():
    spec = load("configs", "gpt-j-6b.json")["model_spec"]
    assert counts.layer_matmul_params(spec) == 4 * 4096 ** 2 + 2 * 4096 * 16384 == 201_326_592
    wb = counts.weight_bytes(spec, 2.0)
    assert wb == pytest.approx((28 * (201_326_592 + 4 * 4096 + 16384) + 4096 * 50400) * 2)
    assert 11.2e9 < wb < 12.0e9
    assert counts.kv_bytes_per_token(spec) == 2 * 28 * 4096 * 2 == 458_752  # 0.4375 MiB
    floor = counts.decode_step_floor_s(spec, 16, 16 * 200, peaks.peaks_for("TPU v5 lite"))
    assert floor["bound"] == "hbm"
    assert floor["seconds"] == pytest.approx((wb + 458_752 * 3200) / 819e9)
    # a request: 10 prompt tokens, 3 answered; the last answer is never fed back
    one = counts.serve_request_flops(spec, 10, 3)
    by_hand = (28 * (2 * 201_326_592 * 10 + 4 * 4096 * 55) + 2 * 4096 * 50400
               + 28 * (2 * 201_326_592 * 2 + 4 * 4096 * (11 + 12)) + 2 * 4096 * 50400 * 2)
    assert one == pytest.approx(by_hand)


def test_peaks_table():
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


# ------------------------------------------------------------ traffic
def test_traffic_same_seed_same_requests_other_seed_other_tokens():
    mix = load("traffic", "closed16-short.json")
    a, b, c = (traffic.serve_requests(mix, s) for s in (7, 7, 3_000_000_019))
    assert a == b and a != c
    sizes = lambda clients: [[(len(t), n) for t, n in reqs] for reqs in clients]
    assert sizes(a) == sizes(c)  # every seed: the same sizes, sent by the same clients in the same order
    assert sorted(x for reqs in sizes(a) for x in reqs) == sorted(traffic.size_pool(mix))
    assert len(a) == mix["clients"] and sum(len(r) for r in a) == mix["pool"]
    for p, n in traffic.size_pool(mix):
        assert 16 <= p <= 256 and 32 <= n <= 128 and p + n <= mix["sum_max"]
    lens = sorted(p for p, _ in traffic.size_pool(mix))
    assert 56 <= lens[len(lens) // 2] <= 72  # median about 64


def test_shared_prefix_and_prompts():
    mix = dict(load("traffic", "closed16-short.json"), shared_prefix={"groups": 2, "len": 16})
    heads = {tuple(t[:16]) for reqs in traffic.serve_requests(mix, 5) for t, _ in reqs}
    assert len(heads) == 2
    ppo = load("traffic", "ppo-sentiments.json")
    assert traffic.ppo_prompts(ppo, 1) == traffic.ppo_prompts(ppo, 1) != traffic.ppo_prompts(ppo, 2)
    assert traffic.decode_bytes([104, 105, 50000, 256]) == "hi"
    assert -1.0 <= traffic.reward("hi") <= 1.0 and traffic.reward("hi") != traffic.reward("ho")


# ------------------------------------------------------------ the result line
def test_last_line_keys(capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1}
    line = lastline.build(True, 3, 0, {"setup_s": {"value": 1.5, "unit": "s"}}, device,
                          {"x": {"value": 0.1, "limit": 0.2, "ok": True}})
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"] and list(line)[-1] == "compared"
    lastline.emit(line, trace=False)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["metrics"]["setup_s"] == {"value": 1.5, "unit": "s"}
    assert err.strip().splitlines()[-1] == "compared x = 0.1 limit 0.2 ok"
    with pytest.raises(ValueError):
        lastline.validate(line, trace=True)  # a traced line needs busy_s and window_s


# ------------------------------------------------------------ the data files
def test_benchmark_json_and_the_files_it_names():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1 for m in e2e.values())
    cells = {w["name"]: w for w in bench["workloads"]}
    for c in bench["configs"]:
        spec = json.load(open(os.path.join(ROOT, c["file"])))
        assert spec["name"] == c["name"] and spec["source"] == c["source"] and spec["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert name.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = load("workloads", w["name"] + ".json")
        assert (cell["config"], cell["traffic"], cell["chips"]) == (w["config"], w["traffic"], w["chips"])
        assert os.path.exists(os.path.join(BENCH, "drivers", load("traffic", w["traffic"] + ".json")["driver"] + ".py"))
    for m in bench["per_layer"]:
        f = load("layer_metrics", m["name"] + ".json")
        assert {k: f[k] for k in m} == m, m["name"]
        assert os.path.exists(os.path.join(BENCH, "readers", f["reader"] + ".py"))
        assert m["moves"] in e2e and all(w in cells for w in m["workloads"])
        moved = e2e[m["moves"]]
        assert all(w in moved.get("workloads", cells) for w in m["workloads"])
    for w in cells:  # every cell: setup_s, another end-to-end metric, a per-layer metric
        assert any(w in m.get("workloads", cells) for m in bench["end_to_end"] if m["name"] != "setup_s")
        assert any(w in m["workloads"] for m in bench["per_layer"])
