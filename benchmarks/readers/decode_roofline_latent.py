"""The decode step of a latent-attention model with routed experts over its
roofline: (weights outside the routed experts + the experts the step's routing
hit x one expert's bytes + the live latents, 1,152 bytes a cached token a
layer) over the bandwidth, or its FLOPs (attention at the absorbed price) over
the peak, whichever is larger (``lib/counts_sarvam_mla.decode_step_floor``),
averaged over the decode steps of the traced interval, over the traced device
time of one run of the program ``module*`` that ran most often."""
from benchmarks.lib import counts_sarvam_mla as C
from benchmarks.lib import steps_longshort as S
from benchmarks.lib import trace_reduce


def read(ctx, module):
    m, spec, peaks = ctx["measured"], ctx["spec"], ctx["peaks"]
    if ctx.get("reduced") is None or not peaks:
        return None
    found = trace_reduce.module_time(ctx["reduced"], module, most_run=True)
    steps = S.mean_steps(m)
    if not found or not steps:
        return None
    runs, seconds = found
    floors = [C.decode_step_floor(spec, c, hit, pairs, peaks) for c, hit, pairs in steps]
    least = sum(f["seconds"] for f in floors) / len(floors)
    ctx["notes"]["decode_floor.latent"] = {
        "floor_ms": least * 1e3, "device_ms_per_step": seconds / runs * 1e3, "steps_traced": runs,
        "steps_counted": len(floors), "rows": sum(len(c) for c, _, _ in steps) / len(steps),
        "live_latent_tokens": sum(sum(c) for c, _, _ in steps) / len(steps),
        "experts_hit_per_step": sum(h for _, h, _ in steps) / len(steps),
        "bytes_per_step": sum(f["bytes"] for f in floors) / len(floors),
        "flops_per_step": sum(f["flops"] for f in floors) / len(floors),
        "bound": max(("hbm", "flops"), key=lambda b: sum(f["bound"] == b for f in floors))}
    return 100.0 * least / (seconds / runs)
