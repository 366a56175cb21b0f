"""The sum of a field of the scheduler's per-step records over the window,
per ``per``: "second" of the window, decode "step" (a record with rows
active), or "step_layer" (a step times the model's layers). Nothing where a
record lacks the field: a program without the counter."""


def read(ctx, field, per):
    m = ctx["measured"]
    steps = m.get("flight") or []
    if not steps or any(field not in s for s in steps):
        return None
    n = sum(1 for s in steps if s.get("active"))
    over = {"second": m["seconds"], "step": n, "step_layer": n * ctx["spec"]["n_layer"]}[per]
    return sum(s[field] for s in steps) / over if over else None
