"""Mean, over the window's scheduler iterations (one flight record each), of
the sum of the named fields of the record (milliseconds as the program wrote
them). Nothing where a record lacks a field: a program without the phase."""


def read(ctx, fields):
    steps = ctx["measured"].get("flight") or []
    if not steps or any(f not in s for s in steps for f in fields):
        return None
    if len(steps) > 1:  # what the phases have to account for: the mean interval between records
        ctx["notes"]["sched_iteration_ms"] = 1e3 * (steps[-1]["t"] - steps[0]["t"]) / (len(steps) - 1)
    return sum(s[f] for s in steps for f in fields) / len(steps)
