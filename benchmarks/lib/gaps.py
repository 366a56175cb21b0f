"""The arithmetic of token gaps: the gaps of a request from its token stamps,
the label of each gap from the scheduler's step it ended in (what ran ahead
of that step), rank statistics and the band mean, and the two spreads that
bounds are placed from. Plain Python: the drivers, the readers, the report
and the tests share it."""

import math
from bisect import bisect_left
from statistics import median, quantiles

ROUNDING_S = 1e-4  # the flight record rounds ``t`` to this


def percentile(values, q: float) -> float:
    """The q-quantile by rank: the smallest value with at least q of the sample at or below it."""
    s = sorted(values)
    return s[max(math.ceil(q * len(s)) - 1, 0)]


def band_mean(values, lo: float = 0.90, hi: float = 0.99) -> float:
    """The mean of the values above the ``lo`` rank up to the ``hi`` rank: a
    tail that is continuous in the shares of the clusters it spans, where a
    quantile steps from one cluster to the next, and that the few largest
    values (one stalled iteration is one gap in every slot) cannot move."""
    s = sorted(values)
    band = s[math.ceil(lo * len(s)):math.ceil(hi * len(s))]
    return sum(band) / len(band) if band else s[-1]


def request_gaps(token_times):
    """[(gap_s, end_stamp)] of one request: the time between consecutive token stamps."""
    return [(b - a, b) for a, b in zip(token_times, token_times[1:])]


def step_labels(flight, admissions):
    """One label per flight record (a scheduler iteration that ran a step):
    ``none`` where no prefill ran ahead of the step, ``b<B>p<P>`` where one
    did (its bucket), ``multi`` where two or more did. ``admissions`` is
    [(admitted_stamp, (B, P))], one per request; the requests of one prefill
    call share a stamp. An admission belongs to the first record that ends
    after it, so ``flight`` has to reach back to the first admission given.
    Returns (labels, records whose ``admitted`` count the join does not
    meet)."""
    ends = [r["t"] for r in flight]
    calls = [set() for _ in flight]
    joined = [0] * len(flight)
    for stamp, bucket in admissions:
        i = bisect_left(ends, stamp + 2 * ROUNDING_S)
        if i < len(flight):
            calls[i].add((stamp, tuple(bucket)))
            joined[i] += 1
    labels = []
    for c in calls:
        if not c:
            labels.append("none")
        elif len(c) == 1:
            (_, (b, p)), = c
            labels.append(f"b{b}p{p}")
        else:
            labels.append("multi")
    mismatched = sum(1 for r, n in zip(flight, joined) if r.get("admitted", n) != n)
    return labels, mismatched


def label_gaps(gaps, flight, labels):
    """[(gap_s, end_stamp)] -> [(gap_s, end_stamp, label)]: a gap ends in the
    harvest of the first record that ends at or after its stamp (``unknown``
    where no record of the window does)."""
    ends = [r["t"] for r in flight]
    out = []
    for gap, end in gaps:
        i = bisect_left(ends, end - ROUNDING_S)
        out.append((gap, end, labels[i] if i < len(labels) else "unknown"))
    return out


def summary(labelled, scale: float = 1e3):
    """What a run's gaps look like: the quantiles, the band mean, each label's
    share and median, and where the 95th rank falls (its label, and the
    nearest cluster median on either side)."""
    values = [g for g, _, _ in labelled]
    if not values:
        return {}
    by = {}
    for g, _, label in labelled:
        by.setdefault(label, []).append(g)
    p95 = percentile(values, 0.95)
    at_rank = sorted(labelled)[max(math.ceil(0.95 * len(values)) - 1, 0)][2]
    centres = sorted(median(v) for v in by.values())
    below = max((c for c in centres if c <= p95), default=None)
    above = min((c for c in centres if c > p95), default=None)
    return {
        "n": len(values),
        **{f"p{int(q * 100)}_ms": percentile(values, q) * scale for q in (0.50, 0.90, 0.95, 0.99)},
        "band_mean_ms": band_mean(values) * scale,
        "max_ms": max(values) * scale,
        "labels": {k: {"share": len(v) / len(values), "median_ms": median(v) * scale}
                   for k, v in sorted(by.items(), key=lambda kv: median(kv[1]))},
        "p95_at": {"label": at_rank, "cluster_below_ms": below * scale if below is not None else None,
                   "cluster_above_ms": above * scale if above is not None else None},
    }


def driver_spread(values) -> float:
    """The check's spread of one set of runs: the range over the median,
    without the run farthest from the median where that narrows it."""
    mid = median(values)
    kept = sorted(values)
    if len(kept) > 2:
        kept.remove(max(kept, key=lambda v: abs(v - mid)))
    return (kept[-1] - kept[0]) / mid


def iqr_spread(values) -> float:
    """The distance between the first and the third quartile over the median."""
    q = quantiles(values, n=4)
    return (q[2] - q[0]) / median(values)
