"""Least time of one decode step (lib/counts.py: weights and live KV over the
bandwidth, or FLOPs over the peak, whichever is larger) over the traced
device time of the decode program per step.

mode "loop": the decode loop is the longest ``while`` inside each run of the
program named ``module`` (the trainer's fused rollout); all rows are live and
the cache grows from the prompt to prompt + gen.
mode "program": the decode step is the program named ``module*`` that ran
most often (the serve engine's slot step); rows and live KV are the mean
over the traced window, from the requests' own stamps."""

from benchmarks.lib import trace_reduce


def read(ctx, mode, module, weight_bytes=2.0, head_bytes=None):
    if ctx.get("trace") is None or not ctx["peaks"]:
        return None
    spec, counts, m = ctx["spec"], ctx["counts"], ctx["measured"]
    if mode == "loop":
        found = trace_reduce.longest_loop_in(ctx["trace"], module)
        if not found:
            return None
        runs, seconds = found
        mix = ctx["mix"]
        steps = runs * mix["gen_tokens"]
        rows = mix["batch"]
        live = rows * (mix["prompt_tokens"] + mix["gen_tokens"] / 2.0)
    else:
        found = trace_reduce.module_time(ctx["reduced"], module, most_run=True)
        if not found:
            return None
        steps, seconds = found
        steps_rec = m.get("flight") or []
        if not steps_rec:
            return None
        rows = sum(s["active"] for s in steps_rec) / len(steps_rec)
        # live KV: each finished request held prompt + half its answer, on average, while it ran
        reqs = [r for r in m.get("requests", []) if r["harvested"] > m["t0"] and r["admitted"] < m["t1"]]
        held = sum((r["prompt_len"] + r["n_out"] / 2.0) * (min(r["harvested"], m["t1"]) - max(r["admitted"], m["t0"]))
                   for r in reqs)
        live = held / m["seconds"]
    floor = counts.decode_step_floor_s(spec, rows, live, ctx["peaks"], weight_bytes, head_bytes)
    ctx["notes"][f"decode_floor.{mode}"] = {"bound": floor["bound"], "floor_ms": floor["seconds"] * 1e3,
                                           "device_ms_per_step": seconds / steps * 1e3, "steps": steps,
                                           "rows": rows, "live_kv_tokens": live}
    return 100.0 * floor["seconds"] / (seconds / steps)
