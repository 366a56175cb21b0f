"""The mean or the median of a list of numbers the driver measured (a span per cycle, every observation of a host span)."""
from statistics import mean, median

STATS = {"mean": mean, "median": median}


def read(ctx, key, stat, scale=1.0):
    values = ctx["measured"].get(key) or []
    return scale * STATS[stat](values) if values else None
