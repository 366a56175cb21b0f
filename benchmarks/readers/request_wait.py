"""A percentile, over the requests sent in the window, of the time between two of a request's stamps."""
from benchmarks.lib.gaps import percentile


def read(ctx, start, end, q=0.95, scale=1e3):
    waits = [r[end] - r[start] for r in ctx["measured"].get("done", []) if r.get(end) and r.get(start)]
    return scale * percentile(waits, q) if waits else None
