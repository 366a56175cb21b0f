"""The look at a token-gap tail, from ``--dump 1`` files of the serve cell:

    python3 benchmarks/tools/gap_report.py <dir with <set>.<seed>.dump.json files>

Per run: the quantiles and the band mean of all gaps, each label's share and
median, where the 95th rank falls, the rates, and the slowest iteration. Per
candidate statistic: its spread in each set, the check's way (the range
without the run farthest from the median, over the median) and as the
interquartile range over the median. ``--control <file>`` does the
arithmetic control on one run's gaps: every gap 5% longer (a slower step),
and each gap with a prefill ahead longer by 10% of that prefill's time."""
import argparse
import glob
import json
import os
import sys
from statistics import median

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from benchmarks.lib import gaps as G  # noqa: E402

CANDIDATES = {"band_mean": G.band_mean, "p99": lambda v: G.percentile(v, 0.99), "p90": lambda v: G.percentile(v, 0.90),
              "p95": lambda v: G.percentile(v, 0.95)}


def load(path):
    with open(path) as f:
        d = json.load(f)
    d["labelled"] = [(g, end, label) for g, end, label in d["gaps"]]  # gaps are in ms already
    d["values"] = [g for g, _, _ in d["labelled"]]
    return d


def run_lines(name, d):
    s = G.summary(d["labelled"], scale=1.0)
    slow = d["timeline"].get("longest_step") or {}
    counts = d["timeline"]["token_gaps"]
    yield (f"{name}: gaps {s['n']} (serve/itl counted {counts['by_serve_itl_count']}, mislabelled steps "
           f"{counts['steps_mislabelled']}), sent {d['sent']}, tokens/s {d['metrics']['serve_tokens_per_s']:.2f}, "
           f"ttft_mean {d['metrics']['ttft_mean_ms']:.2f}; p50 {s['p50_ms']:.2f} p90 {s['p90_ms']:.2f} p95 {s['p95_ms']:.2f} "
           f"p99 {s['p99_ms']:.2f} band {s['band_mean_ms']:.2f} max {s['max_ms']:.1f}")
    yield "    labels: " + ", ".join(f"{k} {100 * v['share']:.1f}% @{v['median_ms']:.1f}" for k, v in s["labels"].items())
    at = s["p95_at"]
    yield (f"    95th rank in {at['label']}, clusters {at['cluster_below_ms'] and round(at['cluster_below_ms'], 1)} below / "
           f"{at['cluster_above_ms'] and round(at['cluster_above_ms'], 1)} above; slowest iteration "
           f"{slow.get('step_ms')} ms at {slow.get('at_s', 0):.1f} s (admit {slow.get('admit_ms')}, fetch {slow.get('fetch_ms')}, "
           f"harvest {slow.get('harvest_ms')}, admitted {slow.get('admitted')})")


def control(d):
    none = median(g for g, _, label in d["labelled"] if label == "none")
    ahead = {}
    for g, _, label in d["labelled"]:
        if label != "none":
            ahead.setdefault(label, []).append(g)
    prefill = {label: median(v) - none for label, v in ahead.items()}  # what a prefill ahead of the step adds
    slower_step = [g * 1.05 for g in d["values"]]
    slower_prefill = [g + 0.1 * prefill.get(label, 0.0) for g, _, label in d["labelled"]]
    yield "prefill ahead adds (ms): " + ", ".join(f"{k} {v:.1f}" for k, v in sorted(prefill.items(), key=lambda kv: kv[1]))
    for name, f in CANDIDATES.items():
        base = f(d["values"])
        yield (f"{name}: {base:.3f} ms; every gap +5%: {f(slower_step):.3f} ({100 * (f(slower_step) / base - 1):+.2f}%); "
               f"prefills +10%: {f(slower_prefill):.3f} ({100 * (f(slower_prefill) / base - 1):+.2f}%)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("directory", nargs="?")
    ap.add_argument("--control")
    args = ap.parse_args()
    if args.control:
        print("\n".join(control(load(args.control))))
    if not args.directory:
        return
    sets = {}
    for path in sorted(glob.glob(os.path.join(args.directory, "*.dump.json"))):
        name = os.path.basename(path)[:-len(".dump.json")]
        d = load(path)
        sets.setdefault(name.split(".")[0], []).append(d)
        print("\n".join(run_lines(name, d)))
    stats = {**{k: (lambda d, f=f: f(d["values"])) for k, f in CANDIDATES.items()},
             "serve_tokens_per_s": lambda d: d["metrics"]["serve_tokens_per_s"],
             "ttft_mean_ms": lambda d: d["metrics"]["ttft_mean_ms"]}
    print("\nstatistic: per set, median / the check's spread / interquartile spread")
    for name, f in stats.items():
        cols = []
        for key, runs in sorted(sets.items()):
            v = [f(d) for d in runs]
            if len(v) >= 3:
                cols.append(f"{key}: {median(v):.3f} / {100 * G.driver_spread(v):.2f}% / {100 * G.iqr_spread(v):.2f}%  "
                            f"[{', '.join(f'{x:.2f}' for x in v)}]")
        print(f"{name:20s} " + "   ".join(cols))


if __name__ == "__main__":
    main()
