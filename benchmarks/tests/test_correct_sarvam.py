"""``correct`` of ``sarvam-105b.serve-shareddocs32`` has to be able to come out
false, at rehearsal size: the control (the plain reference with fp8 operands
in the program's place) fails the cell's limits, and each planted fault (the
driver's ``fault`` key: a served token altered, the router's bias left out of
the choice, the latent norm left out, the score scale without YaRN's m^2, a
page of a shared document overwritten) drives the whole run to ``"correct":
false`` by the comparison that should catch it."""
import json

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.drivers import common
from benchmarks.lib import reference_sarvam_mla as R

CELL = "sarvam-105b.serve-shareddocs32"


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
    own, control = R.served_gaps(spec, seed, rows, common.DTYPES["bfloat16"], control="fp8", pad_to=64)
    limits = cell["correct"]
    assert control.mean() > limits["served_gap_mean"] and (control > 0).mean() > limits["served_off_best_share"]
    assert own.min() >= 0 and np.median(own) > 2.0  # random "served" tokens lie far below the reference's best


def test_a_sound_run_is_correct_and_holds_the_sample_asked_for(capsys, monkeypatch):
    line = drive(capsys, monkeypatch)
    assert line["correct"] is True
    c = line["compared"]
    assert c["long_sessions_checked_min"]["value"] <= -2 and c["answers_checked_min"]["value"] == -5
    assert c["shared_prefills_checked_min"]["value"] == -1 and c["tokens_decoded_over_long_context_min"]["value"] == -1
    assert line["notes"]["timeline"]["pool"]["serve/prefill_chunks"] == 0  # every turn of the window a prefix hit
    assert c["served_gap_max"]["ok"] and line["notes"]["shared_prefills_checked"] >= 1


@pytest.mark.parametrize("fault", ["served_token", "router_bias_zeroed", "no_latent_norm", "scale_without_mscale",
                                   "shared_page_overwritten"])
def test_a_planted_fault_is_not_correct(fault, capsys, monkeypatch):
    line = drive(capsys, monkeypatch, fault)
    assert line["correct"] is False
    failed = [n for n in ("served_gap_max", "served_gap_mean", "served_off_best_share") if not line["compared"][n]["ok"]]
    assert failed
    if fault == "served_token":  # one token altered: its gap alone is over the limit of the largest gap
        assert "served_gap_max" in failed


def test_a_fault_is_refused_at_full_size_and_an_unknown_one_by_name():
    from benchmarks.drivers.serve_closed_sarvam import Cell

    cell, spec = rehearsal()
    with pytest.raises(ValueError, match="rehearsal size only"):
        Cell({"cell": {**cell, "fault": "served_token"}, "spec": spec, "mix": {}, "seed": 1, "rehearse": False})
    with pytest.raises(ValueError, match="unknown planted fault"):
        Cell({"cell": {**cell, "fault": "expert_left_out"}, "spec": spec, "mix": {}, "seed": 1, "rehearse": True})


def test_the_mix_replays_one_schedule_and_shares_each_document():
    from benchmarks.lib import traffic_shareddocs as G

    mix = bench_run.load("traffic", "shareddocs-closed32")
    sizes = lambda seed: [[(len(p), o) for p, o in (turns[j] for j in range(len(turns)))]
                          for turns in G.serve_requests(mix["rehearse"] | {k: v for k, v in mix.items() if k in (
                              "pairing_seed",)}, seed)[1]]
    assert sizes(1) == sizes(2**31 + 5)  # the seed draws token ids only
    small = {**mix, **mix["rehearse"]}
    documents, clients = G.serve_requests(small, 3)
    assert len(documents) == 3 and len(clients) == 6 and all(len(d) % 8 == 0 for d in documents)
    for i, turns in enumerate(clients):
        prompt, new = turns[0]
        assert prompt[:len(documents[i % 3])] == documents[i % 3] and 6 <= new <= 16  # clients i and i + 3: one document
    # the cell's own sizes: 16 documents of 16k-41k in whole pages, 32 clients of 32 turns, ids inside the slice held
    docs, clients = G.serve_requests({**mix, "document_len": {**mix["document_len"]}}, 2**31 + 9)
    assert len(docs) == 16 and len(clients) == 32 and all(len(t) == 32 for t in clients)
    assert all(16384 <= len(d) <= 40960 and len(d) % 64 == 0 for d in docs) and 380_000 < sum(map(len, docs)) < 440_000
    prompt, new = clients[17][5]
    assert prompt[:len(docs[1])] == docs[1] and 32 <= len(prompt) - len(docs[1]) <= 128 and 64 <= new <= 192
    assert 1000 <= min(prompt) and max(prompt) < 65000
