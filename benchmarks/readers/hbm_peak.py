"""memory_stats()['peak_bytes_in_use'] of the fullest chip, read when the window closed."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 2**30 if peak and ctx["peaks"] else None
