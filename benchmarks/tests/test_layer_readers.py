"""The readers PR 25 and PR 28 added, on a hand-made trace, flight list and
gap list with known answers, the nothing-to-read cases included; both cells
at rehearsal size."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.readers import flight_sum, gap_stat, host_span, program_gap, program_ms

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000  # ns


def serve_trace(decode="jit_run_decode_step(1)", prefill="jit_run_prefill_b4p128(2)"):
    """Three decode steps of 50 ms and one prefill of 20 ms on one device; the host's spans over the gaps."""
    modules = [(decode, 0, 50 * MS), (prefill, 52 * MS, 20 * MS), (decode, 72 * MS, 50 * MS), (decode, 126 * MS, 50 * MS)]
    host = [
        ("worker", "serve/slot_step", -2 * MS, 53 * MS), ("worker", "serve/step_fetch", -1 * MS, 52 * MS),   # ends at 51
        ("worker", "serve/harvest", 51 * MS, int(0.5 * MS)),                                                  # 51 .. 51.5
        ("worker", "serve/admit", int(51.5 * MS), int(0.7 * MS)),                                             # 51.5 .. 52.2
        ("worker", "serve/prefill_b4p128", int(51.6 * MS), int(0.5 * MS)),                                    # 51.6 .. 52.1
        ("worker", "serve/slot_step", int(52.2 * MS), 71 * MS), ("worker", "serve/step_fetch", 53 * MS, 70 * MS),  # fetch ends at 123
        ("worker", "serve/harvest", 123 * MS, 1 * MS),                                                        # 123 .. 124
        ("worker", "serve/slot_step", 125 * MS, 60 * MS), ("worker", "serve/step_fetch", int(125.5 * MS), 59 * MS),
        ("client", "bench/submit", 0, 200 * MS), ("worker", "np.asarray(jax.Array)", 0, 51 * MS),
    ]
    return {"devices": {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": []}}, "host": host}


def test_program_gap_mean_and_split():
    ctx = {"trace": serve_trace(), "notes": {}}
    # gaps: 50 -> 52 (2 ms), 72 -> 72 (0), 122 -> 126 (4 ms): 6 ms between 4 programs
    assert program_gap.read(ctx, module="jit_run_", spans="serve/") == pytest.approx(6.0 / 3)
    note = ctx["notes"]["step_gap"]
    assert note["gap_s"] == pytest.approx(0.006) and note["between_programs"] == 3
    by = {k: v * 1e3 for k, v in note["by_span_s"].items()}
    # first gap: fetch to 51, harvest to 51.5, admit to 51.6, the prefill's dispatch to 52 (innermost under admit)
    # last gap: fetch to 123, harvest to 124, nothing to 125, the dispatch (slot_step) to 125.5, fetch to 126
    assert by == {"serve/step_fetch": pytest.approx(1.0 + 1.0 + 0.5), "serve/harvest": pytest.approx(0.5 + 1.0),
                  "serve/admit": pytest.approx(0.1), "serve/prefill*": pytest.approx(0.4),
                  "none": pytest.approx(1.0), "serve/slot_step": pytest.approx(0.5)}
    assert sum(by.values()) == pytest.approx(6.0) and note["named_share"] == pytest.approx(5.0 / 6.0)
    assert program_gap.fold("serve/prefill_sfx_b1p64") == "serve/prefill_sfx*" and program_gap.fold("serve/admit") == "serve/admit"


def test_program_gap_finds_nothing_without_named_programs_or_a_trace():
    parent = serve_trace(decode="jit_run(1)", prefill="jit_run(2)")  # the programs before they had names
    assert program_gap.read({"trace": parent, "notes": {}}, module="jit_run_", spans="serve/") is None
    assert program_gap.read({"trace": None, "notes": {}}, module="jit_run_", spans="serve/") is None
    one = {"devices": {"d": {"XLA Modules": [("jit_run_decode_step(1)", 0, 5)]}}, "host": []}
    assert program_gap.read({"trace": one, "notes": {}}, module="jit_run_", spans="serve/") is None
    # programs with names and a program without spans: the whole gap goes to "none"
    bare = dict(serve_trace(), host=[])
    ctx = {"trace": bare, "notes": {}}
    assert program_gap.read(ctx, module="jit_run_", spans="serve/") == pytest.approx(2.0)
    assert ctx["notes"]["step_gap"]["named_share"] == 0.0


def test_decode_program_by_its_name():
    reduced = {"modules": {"jit_run_decode_step(1)": {"count": 3, "seconds": 0.150},
                           "jit_run_prefill_b4p128(2)": {"count": 5, "seconds": 0.1}}}
    # the decode program by its name, even where a prefill ran more often
    assert program_ms.read({"reduced": reduced}, module="jit_run_decode_step") == pytest.approx(50.0)
    assert program_ms.read({"reduced": reduced}, module="jit_run", which="others") == pytest.approx(50.0)  # the guess fails here
    assert program_ms.read({"reduced": {"modules": {"jit_run(1)": {"count": 3, "seconds": 0.1}}}},
                           module="jit_run_decode_step") is None


def test_flight_sum():
    flight = [{"t": 10.0, "admit_ms": 0.0, "harvest_ms": 0.5, "fetch_ms": 58.0, "step_ms": 59.0},
              {"t": 10.061, "admit_ms": 2.0, "harvest_ms": 0.7, "fetch_ms": 60.0, "step_ms": 61.0},
              {"t": 10.12, "admit_ms": 0.1, "harvest_ms": 0.3, "fetch_ms": 59.0, "step_ms": 60.0}]
    ctx = {"measured": {"flight": flight}, "notes": {}}
    assert flight_sum.read(ctx, fields=["admit_ms", "harvest_ms"]) == pytest.approx(3.6 / 3)
    assert flight_sum.read(ctx, fields=["fetch_ms"]) == pytest.approx(59.0)
    assert ctx["notes"]["sched_iteration_ms"] == pytest.approx(60.0)
    old = [{"t": 1.0, "step_ms": 59.0}]  # a program whose records lack the phases
    assert flight_sum.read({"measured": {"flight": old}, "notes": {}}, fields=["fetch_ms"]) is None
    assert flight_sum.read({"measured": {"flight": []}, "notes": {}}, fields=["fetch_ms"]) is None
    assert flight_sum.read({"measured": {}, "notes": {}}, fields=["fetch_ms"]) is None


def test_host_span_per_cycle():
    S = 1_000_000_000
    host = []
    for c in range(3):  # three cycles: dispatch 2 ms, rollout 0.74 s with its children, update 1 ms, fetch 0.889 s
        t = c * 2 * S
        host += [("main", "bench/make_experience", t, int(0.75 * S)), ("main", "rollout_dispatch", t, 2 * MS),
                 ("main", "rollout", t + 2 * MS, int(0.74 * S)), ("main", "rollout_fetch", t + 2 * MS, int(0.73 * S)),
                 ("main", "rollout_decode_text", t + int(0.733 * S), 3 * MS), ("main", "reward_fn", t + int(0.736 * S), 4 * MS),
                 ("main", "bench/reward_fn", t + int(0.736 * S), 4 * MS), ("main", "rollout_store", t + int(0.74 * S), 1 * MS),
                 ("main", "ppo_update", t + S, 1 * MS), ("main", "ppo_stats_fetch", t + S + 1 * MS, int(0.889 * S))]
    ctx = {"trace": {"devices": {}, "host": host}, "notes": {}}
    assert host_span.read(ctx, spans=["rollout", "rollout_dispatch"], per="rollout") == pytest.approx(0.742)
    assert host_span.read(ctx, spans=["ppo_update", "ppo_stats_fetch"], per="rollout") == pytest.approx(0.890)
    assert host_span.read(ctx, spans=["rollout_dispatch", "rollout_decode_text", "reward_fn", "rollout_store"],
                          per="rollout", scale=1e-6) == pytest.approx(10.0)
    assert ctx["notes"]["host_span_ms_per_cycle"]["reward_fn"] == pytest.approx(4.0)
    # the parent: its trace holds rollout and ppo_update, and none of the new names
    parent = {"devices": {}, "host": [h for h in host if h[1] in ("rollout", "ppo_update", "bench/make_experience")]}
    assert host_span.read({"trace": parent, "notes": {}}, spans=["rollout_store"], per="rollout") is None
    assert host_span.read({"trace": parent, "notes": {}}, spans=["rollout", "rollout_dispatch"], per="rollout") is None
    assert host_span.read({"trace": parent, "notes": {}}, spans=["ppo_update", "ppo_stats_fetch"], per="rollout") is None
    assert host_span.read({"trace": {"devices": {}, "host": []}, "notes": {}}, spans=["rollout"], per="rollout") is None
    assert host_span.read({"trace": None, "notes": {}}, spans=["rollout"], per="rollout") is None


def test_gap_stat_reads_a_rank_and_notes_what_it_stands_among():
    gaps = [(0.037, 1.0 + i, "none") for i in range(15)] + [(0.057, 20.0 + i, "b1p64") for i in range(4)] + [(0.08, 30.0, "multi")]
    ctx = {"measured": {"gaps": gaps}, "notes": {}}
    assert gap_stat.read(ctx, q=0.95) == pytest.approx(57.0)  # the 19th of 20
    assert gap_stat.read(ctx, q=0.5) == pytest.approx(37.0)
    note = ctx["notes"]["token_gaps"]
    assert note["n"] == 20 and note["p50_ms"] == pytest.approx(37.0) and note["p99_ms"] == pytest.approx(80.0)
    assert note["labels"]["b1p64"] == {"share": 0.2, "median_ms": pytest.approx(57.0)}
    assert note["p95_at"]["label"] == "b1p64" and note["p95_at"]["cluster_above_ms"] == pytest.approx(80.0)
    assert gap_stat.read({"measured": {"gaps": []}, "notes": {}}) is None
    assert gap_stat.read({"measured": {}, "notes": {}}) is None  # the PPO cell: nothing to read


@pytest.mark.parametrize("cell", ["gpt2-xl.ppo-sentiments", "gpt-j-6b.serve-closed16"])
def test_both_cells_rehearse(cell):
    done = subprocess.run([sys.executable, "benchmarks/run.py", "--workload", cell, "--seed", "3000000019", "--seconds", "3",
                           "--trace", "1", "--rehearse", "1"], cwd=ROOT, capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}  # a rehearsal prints no device metric
    if "serve" in cell:  # the gaps' reader ran and found the window's gaps, every one of them labelled
        note = line["notes"]["token_gaps"]
        assert note["n"] == line["notes"]["timeline"]["token_gaps"]["by_token_times"] > 100
        assert "none" in note["labels"] and "unknown" not in note["labels"]
