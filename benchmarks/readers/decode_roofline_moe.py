"""The decode step of a model with routed experts and window layers over its
roofline: (weights outside the routed experts + the experts the step's
routing hit x one expert's bytes + live KV with the window cap) over the
bandwidth, or its FLOPs over the peak, whichever is larger
(``lib/counts_cohere2_moe.decode_step_floor``), averaged over the decode
steps of the traced interval, over the traced device time of one run of the
program ``module*`` that ran most often. Rows, contexts and routing are those
of the very steps traced (``lib/steps_longshort``)."""
from benchmarks.lib import counts_cohere2_moe as C
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
    ctx["notes"]["decode_floor.moe"] = {
        "floor_ms": least * 1e3, "device_ms_per_step": seconds / runs * 1e3, "steps_traced": runs,
        "steps_counted": len(floors), "rows": sum(len(c) for c, _, _ in steps) / len(steps),
        "rows_by_flight": sum(b["active"] for _, b in S.steps_in(m)) / len(steps),
        "live_kv_tokens": sum(sum(c) for c, _, _ in steps) / len(steps),
        "experts_hit_per_step": sum(h for _, h, _ in steps) / len(steps),
        "bytes_per_step": sum(f["bytes"] for f in floors) / len(floors),
        "bound": max(("hbm", "flops"), key=lambda b: sum(f["bound"] == b for f in floors))}
    return 100.0 * least / (seconds / runs)
