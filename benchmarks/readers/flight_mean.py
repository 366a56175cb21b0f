"""Mean of a field of the scheduler's per-step records over the window's steps, as a share of the slots."""


def read(ctx, field="active"):
    steps, slots = ctx["measured"].get("flight") or [], ctx["measured"].get("slots")
    return 100.0 * sum(s[field] for s in steps) / (len(steps) * slots) if steps and slots else None
