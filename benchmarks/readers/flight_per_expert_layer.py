"""The sum of a field of the scheduler's per-step records over the window's decode steps, per step and per layer
WITH EXPERTS (a model whose leading layers are dense routes in the others alone). Nothing where a record lacks the
field: a program without the counter."""


def read(ctx, field):
    steps, spec = ctx["measured"].get("flight") or [], ctx["spec"]
    if not steps or any(field not in s for s in steps):
        return None
    over = sum(1 for s in steps if s.get("active")) * (spec["n_layer"] - spec.get("first_dense_layers", 0))
    return sum(s[field] for s in steps) / over if over else None
