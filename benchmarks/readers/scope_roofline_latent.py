"""A part of a latent-attention model's decode step over its roofline: the
least time ``lib/counts_sarvam_mla`` gives the part (``part``: "moe" = router,
shared expert and the pairs made, from the routing the steps reported;
"latent_attn" = the absorbed kernel: the latents of the pages that hold a key a
row's query sees, 139k operations on 1,152 bytes a cached token a layer, the
larger of bytes over the bandwidth and operations over the peak), averaged over
the decode steps of the traced interval, over the traced device time per step
of the operations under ``pattern`` (``readers/scope_ms``)."""
from benchmarks.lib import counts_sarvam_mla as C
from benchmarks.lib import steps_longshort as S


def read(ctx, part, module, pattern, inherit=True):
    m, spec, peaks = ctx["measured"], ctx["spec"], ctx["peaks"]
    found = S.scope_seconds(ctx, module, pattern, inherit)
    steps = S.mean_steps(m) if peaks and found else None
    if not steps:
        return None
    runs, by = found
    seconds = sum(v for k, v in by.items() if k != "other") / runs
    if seconds <= 0:
        return None
    if part == "moe":
        floors = [C.moe_step_floor(spec, len(c), hit, pairs, peaks) for c, hit, pairs in steps]
    else:
        floors = [C.latent_attn_step_floor(spec, c, m["page_size"], peaks) for c, _, _ in steps]
    least = sum(f["seconds"] for f in floors) / len(floors)
    ctx["notes"][f"{part}_floor"] = {
        "floor_ms": least * 1e3, "device_ms_per_step": seconds * 1e3, "steps": len(floors),
        "bound": max(("hbm", "flops"), key=lambda b: sum(f["bound"] == b for f in floors)),
        "bytes_per_step": sum(f["bytes"] for f in floors) / len(floors),
        "flops_per_step": sum(f["flops"] for f in floors) / len(floors)}
    return 100.0 * least / seconds
