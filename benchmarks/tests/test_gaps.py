"""Token gaps: the band mean and the rank statistics on hand-made lists, the
labels against a hand-made flight record, the two spreads, and the count of
tokens by the requests' stamps against the program's own ``serve/itl``."""
import json

import pytest

from benchmarks import run as bench_run
from benchmarks.drivers import serve_closed
from benchmarks.lib import gaps as G


def clusters(*pairs):
    """[(count, ms)] -> a list of gaps, each cluster jittered by up to 0.1 ms so that no rank is a tie."""
    return [ms + 0.1 * i / n for n, ms in pairs for i in range(n)]


def test_a_one_percent_shift_moves_p95_by_a_cluster_and_the_band_mean_by_under_one_percent():
    # 12,000 gaps: plain iterations at 37 ms, steps with a prefill ahead at 57, 66 and 70, two ahead at 82
    before = clusters((9000, 37.0), (1800, 57.0), (660, 66.0), (420, 70.0), (120, 82.0))
    after = clusters((9000, 37.0), (1800, 57.0), (540, 66.0), (540, 70.0), (120, 82.0))  # 1% of the mass went 66 -> 70
    assert G.percentile(before, 0.95) == pytest.approx(66.1, abs=0.1)
    assert G.percentile(after, 0.95) == pytest.approx(70.0, abs=0.1)  # the 95th rank crossed to the next cluster: +6%
    a, b = G.band_mean(before), G.band_mean(after)
    assert b > a and (b - a) / a < 0.01  # 120 of the band's 1,080 gaps moved 4 ms: 0.44 ms of 67
    # the band: ranks 10,800 to 11,880 (the top 1%, here "two ahead", is left out)
    assert a == pytest.approx((660 * 66.05 + 420 * 70.05) / 1080, abs=0.01)


def test_one_stalled_iteration_moves_neither_by_half_a_percent():
    # one stalled iteration is one gap of a second in each of 16 slots: they push 16 ranks of the band down a cluster
    sound = clusters((9000, 37.0), (1800, 57.0), (720, 66.0), (360, 70.0), (120, 82.0))
    stalled = sound[16:] + [1000.0] * 16
    for stat in (G.band_mean, lambda v: G.percentile(v, 0.95)):
        assert stat(stalled) == pytest.approx(stat(sound), rel=5e-3)
    assert G.percentile(stalled, 0.999) == 1000.0  # where such a run shows: the far tail, and tokens/s
    # a rank on a cluster's edge is moved by as little: this list's 90th rank is the last gap at 57 ms
    assert G.percentile(sound, 0.90) == pytest.approx(57.1, abs=0.01) and G.percentile(stalled, 0.90) == pytest.approx(66.0, abs=0.01)


def test_every_gap_five_percent_longer_moves_the_band_mean_five_percent():
    sound = clusters((9000, 37.0), (1800, 57.0), (720, 66.0), (360, 70.0), (120, 82.0))
    assert G.band_mean([g * 1.05 for g in sound]) == pytest.approx(1.05 * G.band_mean(sound))
    assert G.band_mean([5.0]) == 5.0 and G.percentile([3.0, 1.0, 2.0], 0.5) == 2.0


def test_gaps_and_labels_against_a_hand_made_flight_record():
    # five iterations ending at 10.040 .. 10.250; prefills ahead of the second (one), the fourth (two calls) and the fifth (b4)
    flight = [{"t": 10.04, "admitted": 0}, {"t": 10.10, "admitted": 1}, {"t": 10.14, "admitted": 0},
              {"t": 10.21, "admitted": 2}, {"t": 10.25, "admitted": 3}]
    admissions = [(10.0402, (1, 64)), (10.1401, (1, 128)), (10.1650, (1, 256)),
                  (10.2101, (4, 128)), (10.2101, (4, 128)), (10.2101, (4, 128))]
    labels, mismatched = G.step_labels(flight, admissions)
    assert labels == ["none", "b1p64", "none", "multi", "b4p128"] and mismatched == 0
    # a request that got a token in each harvest (0.3 ms before its record's end, which is rounded to 0.1 ms)
    stamps = [r["t"] - 0.0003 for r in flight]
    gaps = G.request_gaps(stamps)
    assert [round(g, 3) for g, _ in gaps] == [0.06, 0.04, 0.07, 0.04] and gaps[0][1] == stamps[1]
    labelled = G.label_gaps(gaps + [(0.04, 10.29)], flight, labels)
    assert [label for _, _, label in labelled] == ["b1p64", "none", "multi", "b4p128", "unknown"]
    assert G.request_gaps([1.0]) == [] and G.request_gaps([]) == []
    # a record whose admitted count the join does not meet is counted, not hidden
    assert G.step_labels(flight, admissions[1:])[1] == 1
    s = G.summary(labelled[:4], scale=1e3)
    assert s["n"] == 4 and s["labels"]["none"]["share"] == 0.25 and s["p95_at"]["label"] == "multi"
    assert s["max_ms"] == pytest.approx(70.0) and G.summary([]) == {}


def test_the_two_spreads():
    runs = [401.2, 401.7, 402.3, 396.5, 401.2, 406.0]
    # the check's: the range without the run farthest from the median (396.5), over the median
    assert G.driver_spread(runs) == pytest.approx((406.0 - 401.2) / 401.45)
    assert G.iqr_spread([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)  # statistics.quantiles, n=4
    assert G.driver_spread([5.0, 5.0, 5.0]) == 0.0


def test_tokens_counted_by_stamps_are_the_programs_itl_observations(monkeypatch, capsys):
    seen = {}
    release = serve_closed.Cell.release

    def counted(self):  # before the engine goes: every request sent has been answered, the scheduler is idle
        seen["by_stamps"] = sum(max(len(r["token_times"]) - 1, 0) for r in self.records)
        seen["by_program"] = self._itl_observed()
        seen["slots"] = self.measured["slots"]
        seen["tokens_emitted"] = self.measured["tokens_emitted"]
        seen["first_tokens"] = sum(1 for r in self.records if r["ok"] and
                                   self.measured["t0"] <= r["first_token"] <= self.measured["t1"])
        release(self)

    monkeypatch.setattr(serve_closed.Cell, "release", counted)
    assert bench_run.main(["--workload", "gpt-j-6b.serve-closed16", "--seed", "2900000017", "--seconds", "1",
                           "--trace", "0", "--rehearse", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert seen["by_stamps"] == seen["by_program"] > 100  # over the whole run: one for one
    window = line["notes"]["timeline"]["token_gaps"]
    # over the window: the program's count is taken when the window opens and closes, the stamps are the
    # harvests' own, so one harvest (a token a slot) may fall on the other side of an edge
    assert abs(window["by_token_times"] - window["by_serve_itl_count"]) <= seen["slots"]
    assert seen["tokens_emitted"] == seen["first_tokens"] + window["by_token_times"]
    assert window["steps_mislabelled"] == 0
    slowest = line["notes"]["timeline"]["longest_step"]
    assert {"step", "at_s", "active", "admitted", "step_ms", "admit_ms", "fetch_ms", "harvest_ms"} <= set(slowest)
