"""1 - the union of the device's operation intervals over the traced window."""


def read(ctx):
    r = ctx.get("reduced")
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"]) if r and r["window_s"] > 0 else None
