"""Mean idle time on the device between the end of one program named
``module*`` and the start of the next (the ``XLA Modules`` line), and, in
``notes``, the summed gap split by the program span that lay over it on the
host plane: each gap is cut at the boundaries of the spans named ``spans*``
and each piece goes to the innermost span open over it (``none`` where none
was). Prefill buckets are folded into one name (``serve/prefill*``)."""
import re

from benchmarks.lib import trace_reduce


def fold(name: str) -> str:
    return re.sub(r"_b\d+p\d+$", "*", name)


def split(gap, spans):
    """{span: ns} of one (start, end) gap over properly nested (name, start, end) spans."""
    g0, g1 = gap
    over = [(n, max(s, g0), min(e, g1), s) for n, s, e in spans if s < g1 and e > g0]
    cuts = sorted({g0, g1, *(c for _, a, b, _ in over for c in (a, b))})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        inside = [(began, n) for n, s, e, began in over if s <= a and e >= b]
        name = fold(max(inside)[1]) if inside else "none"  # the span that began last is the innermost
        out[name] = out.get(name, 0) + (b - a)
    return out


def read(ctx, module, spans):
    trace = ctx.get("trace")
    if not trace:
        return None
    host = [(n, s, s + d) for _, n, s, d in trace["host"] if n.startswith(spans) and d > 0]
    total, pairs, by_span = 0, 0, {}
    for lines in trace["devices"].values():
        runs = sorted((s, s + d) for n, s, d in lines.get(trace_reduce.MODULES, []) if n.startswith(module))
        for (_, end), (start, _) in zip(runs, runs[1:]):
            pairs += 1
            if start > end:
                total += start - end
                for name, ns in split((end, start), host).items():
                    by_span[name] = by_span.get(name, 0) + ns
    if not pairs:
        return None
    ctx["notes"]["step_gap"] = {
        "gap_s": total / 1e9, "between_programs": pairs,
        "by_span_s": {k: v / 1e9 for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])},
        "named_share": 1.0 - by_span.get("none", 0) / total if total else None,
    }
    return total / pairs / 1e6
