"""Mean bytes of latents the rows of a decode step hold live, as the pool keeps
them (a latent out to whole lane tiles: 1,280 bytes a token a layer for the
1,152 that are read), over the steps of the window's traced part (the whole
window where the run was not traced). ``notes`` has the bytes read beside the
bytes held, and what per-head K and V of the same rows would hold."""
from benchmarks.lib import counts_sarvam_mla as C
from benchmarks.lib import steps_longshort as S


def read(ctx):
    m, spec = ctx["measured"], ctx["spec"]
    steps = S.steps_in(m)
    if not steps or "page_size" not in m:
        return None
    rows = [S.contexts_at(m, a, b) for a, b in steps]
    mean = lambda f: sum(f(c) for c in rows) / len(rows) / 2**30
    held = mean(lambda c: C.live_latent_bytes(spec, c, as_stored=True))
    s = C.dims(spec)
    ctx["notes"]["kv_live_gib"] = {
        "held": held, "read": mean(lambda c: C.live_latent_bytes(spec, c)),
        "per_head_kv_would_hold": mean(lambda c: s["L"] * sum(c) * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * C.BYTES),
        "tokens": sum(sum(c) for c in rows) / len(rows), "steps": len(rows)}
    return held
