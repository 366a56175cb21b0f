"""Time per cycle inside the program's own spans, from the trace's host plane
(their ``TraceAnnotation``s): the summed duration of every span named in
``spans`` over the number of spans named ``per`` (one a cycle). ``scale``
takes nanoseconds to the metric's unit. Each span's own mean goes to
``notes``. Nothing where one of the spans never appears: a program that
lacks it would read as a faster one."""


def read(ctx, spans, per, scale=1e-9):
    trace = ctx.get("trace")
    if not trace:
        return None
    total, seen, cycles = {name: 0 for name in spans}, set(), 0
    for _, name, _, dur in trace["host"]:
        if name in total:
            total[name] += dur
            seen.add(name)
        cycles += name == per
    if not cycles or len(seen) < len(total):
        return None
    ctx["notes"].setdefault("host_span_ms_per_cycle", {}).update(
        {name: ns / cycles / 1e6 for name, ns in total.items()})
    return scale * sum(total.values()) / cycles
