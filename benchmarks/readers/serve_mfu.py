"""Serving's share of the chip's peak: lib/counts.py FLOPs of the work done
inside the window over the window. A request's prefill counts where its first
token fell inside; its decode forwards are spread evenly between its first
token and its last, and the part inside the window counts."""


def read(ctx):
    m, peaks, counts, spec = ctx["measured"], ctx["peaks"], ctx["counts"], ctx["spec"]
    if not peaks or not m.get("requests"):
        return None
    flops = 0.0
    for r in m["requests"]:
        whole = counts.serve_request_flops(spec, r["prompt_len"], r["n_out"])
        prefill = counts.serve_request_flops(spec, r["prompt_len"], 1)
        if m["t0"] <= r["first_token"] <= m["t1"]:
            flops += prefill
        span = r["harvested"] - r["first_token"]
        inside = min(r["harvested"], m["t1"]) - max(r["first_token"], m["t0"])
        if span > 0 and inside > 0:
            flops += (whole - prefill) * inside / span
    return 100.0 * flops / m["seconds"] / (peaks["flops_bf16"] * ctx["device"]["count"]) if flops else None
