"""Serving's share of the chip's peak for a latent-attention model with routed
experts: the share of the WHOLE step's work, ``lib/counts_sarvam_mla`` FLOPs of
everything done inside the window (prefills, decode forwards, the routed pairs)
over the window times the peak. A request's prefill (the part of its prompt
that was no prefix hit, against the context before it) counts where its first
token fell inside; its decode forwards are spread evenly between its first
token and its last, and the part inside counts. A (query, key) pair counts at
the up-projected price and a key's up-projection once (the least any order
needs: the counts' docstring). The routed experts are counted as made: the
(token, expert) pairs the steps of the window reported."""
from benchmarks.lib import counts_sarvam_mla as C


def read(ctx):
    m, peaks, spec = ctx["measured"], ctx["peaks"], ctx["spec"]
    flight = m.get("flight") or []
    if not peaks or not m.get("requests") or not flight or any("pairs_here" not in s for s in flight):
        return None
    parts = {"prefill": 0.0, "decode": 0.0}
    for r in m["requests"]:
        hit = r.get("prefix_blocks_hit", 0) * m["page_size"]
        if m["t0"] <= r["first_token"] <= m["t1"]:
            parts["prefill"] += C.forward_flops(spec, hit, r["prompt_len"] - hit, 1)
        n_dec = max(r["n_out"] - 1, 0)  # the last token is never fed back
        span = r["harvested"] - r["first_token"]
        inside = min(r["harvested"], m["t1"]) - max(r["first_token"], m["t0"])
        if span > 0 and inside > 0 and n_dec:
            parts["decode"] += C.forward_flops(spec, r["prompt_len"], n_dec, n_dec) * inside / span
    parts["pairs"] = sum(s["pairs_here"] for s in flight) * C.pair_flops(spec)
    ctx["notes"]["window_flops"] = parts
    return 100.0 * sum(parts.values()) / m["seconds"] / (peaks["flops_bf16"] * ctx["device"]["count"])
