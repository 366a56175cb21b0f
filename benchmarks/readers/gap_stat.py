"""A rank statistic of the window's token gaps (every gap between consecutive
output tokens whose end stamp lies in the window, from the requests' own
token stamps). ``notes.token_gaps`` gets what the statistic stands among: the
median, the other quantiles, the band mean, and each label's share and
median (what ran ahead of the step a gap ended in: no prefill, one by its
bucket, two or more)."""
from benchmarks.lib import gaps as G


def read(ctx, q=0.95, scale=1e3):
    gaps = ctx["measured"].get("gaps") or []
    if not gaps:
        return None
    ctx["notes"]["token_gaps"] = G.summary(gaps, scale)
    return scale * G.percentile([g[0] for g in gaps], q)
