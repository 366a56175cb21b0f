"""Mean bytes of keys and values the rows of a decode step hold live, over
the steps of the window's traced part (the whole window where the run was
not traced), with the window cap: a window layer holds the last ``window``
keys. ``notes`` has it by class of page, and what one class of page (every
layer the whole context) would hold for the same rows."""
from benchmarks.lib import counts_cohere2_moe as C
from benchmarks.lib import steps_longshort as S


def read(ctx):
    m, spec = ctx["measured"], ctx["spec"]
    steps = S.steps_in(m)
    if not steps or "page_size" not in m:
        return None
    by = [C.live_kv_bytes(spec, S.contexts_at(m, a, b)) for a, b in steps]
    mean = {k: sum(x[k] for x in by) / len(by) / 2**30 for k in ("full", "window", "one_class")}
    ctx["notes"]["kv_live_gib"] = {**mean, "steps": len(by)}
    return mean["full"] + mean["window"]
