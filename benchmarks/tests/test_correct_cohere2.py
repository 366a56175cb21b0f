"""``correct`` of ``command-a-plus-05-2026.serve-longshort32`` has to be able
to come out false, at rehearsal size: the control (the plain reference with
fp8 operands in the program's place) fails the cell's limits, and each
planted fault (the driver's ``fault`` key: a served token altered, an expert's
output left out, a window-class page released a page early) drives the whole
run to ``"correct": false`` by the comparison that should catch it."""
import json

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.drivers import common
from benchmarks.lib import reference_cohere2_moe as R

CELL = "command-a-plus-05-2026.serve-longshort32"


def rehearsal():
    cell = bench_run.load("workloads", CELL)
    config = bench_run.load("configs", cell["config"])
    return common.deep_update(cell, cell["rehearse"]), config["rehearse"]


def drive(capsys, monkeypatch, fault=None, seed=11):
    load = bench_run.load
    monkeypatch.setattr(bench_run, "load", lambda kind, name: {
        **load(kind, name), **({"fault": fault} if kind == "workloads" and fault else {})})
    assert bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", "0",
                           "--rehearse", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metrics"] == {}  # a rehearsal prints no device metric
    return line


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fp8_is_not_correct(seed):
    cell, spec = rehearsal()
    rng = np.random.default_rng(seed)
    rows = [(rng.integers(1, 500, 90).tolist(), rng.integers(1, 500, 12).tolist()) for _ in range(4)]
    own, control, note = R.served_gaps(spec, seed, rows, common.DTYPES["bfloat16"], control="fp8", pad_to=64,
                                       router_note=True)
    limits = cell["correct"]
    assert control.mean() > limits["served_gap_mean"] and (control > 0).mean() > limits["served_off_best_share"]
    assert own.min() >= 0 and np.median(own) > 2.0  # random "served" tokens lie far below the reference's best
    assert 0.0 <= note["router_topk_set_differs_share"] < 0.2 and note["token_layer_pairs"] == 4 * 4 * 102


def test_a_sound_run_is_correct_and_holds_the_sample_asked_for(capsys, monkeypatch):
    line = drive(capsys, monkeypatch)
    assert line["correct"] is True
    c = line["compared"]
    assert c["long_sessions_checked_min"]["value"] <= -2 and c["tokens_behind_window_min"]["value"] == -1
    assert c["answers_checked_min"]["value"] == -5 and c["served_gap_max"]["ok"]


@pytest.mark.parametrize("fault", ["served_token", "expert_left_out", "window_page_early"])
def test_a_planted_fault_is_not_correct(fault, capsys, monkeypatch):
    line = drive(capsys, monkeypatch, fault)
    assert line["correct"] is False
    failed = [n for n in ("served_gap_max", "served_gap_mean", "served_off_best_share") if not line["compared"][n]["ok"]]
    assert failed
    if fault == "served_token":  # one token of 50 altered: its gap alone is over the limit of the largest gap
        assert "served_gap_max" in failed


def test_a_fault_is_refused_at_full_size():
    from benchmarks.drivers.serve_closed_cohere2 import Cell

    cell, spec = rehearsal()
    with pytest.raises(ValueError, match="rehearsal size only"):
        Cell({"cell": {**cell, "fault": "served_token"}, "spec": spec, "mix": {}, "seed": 1, "rehearse": False})
