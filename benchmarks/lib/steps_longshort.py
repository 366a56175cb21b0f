"""What the ``.longshort`` readers share: the scheduler's steps of the traced
part of the window (the flight records between the tracer's start and stop;
the whole window where the run was not traced), the rows each step decoded
with their contexts, rebuilt from the requests' own stamps, and the device
time of the decode program's operations by named scope."""
import re

from benchmarks.lib import trace_reduce


def steps_in(m: dict):
    """The flight records of the traced interval that ran a decode step, each with the record before it."""
    flight = m.get("flight") or []
    lo, hi = m.get("traced") or (m["t0"], m["t1"])
    return [(a, b) for a, b in zip(flight, flight[1:]) if lo <= b["t"] <= hi and b.get("active")]


def contexts_at(m: dict, before: dict, rec: dict):
    """Keys each row of the step that ended at ``rec`` saw: the requests whose first token was out by
    then and that had not been harvested before the step began; a request's tokens are spread evenly
    between its first and its last."""
    out = []
    for r in m.get("requests", []):
        if r["first_token"] and r["first_token"] <= rec["t"] and r["harvested"] > before["t"]:
            span = r["harvested"] - r["first_token"]
            done = min(max((rec["t"] - r["first_token"]) / span, 0.0), 1.0) if span > 0 else 1.0
            out.append(r["prompt_len"] + int(done * (r["n_out"] - 1)) + 1)
    return out


def mean_steps(m: dict):
    """Per decode step of the traced interval: (contexts, experts hit, pairs made), or nothing to read."""
    steps = steps_in(m)
    if not steps or any("experts_hit" not in b for _, b in steps):
        return None
    return [(contexts_at(m, a, b), b["experts_hit"], b.get("pairs_step", 0)) for a, b in steps]


def scope_seconds(ctx, module: str, pattern: str, inherit: bool = True):
    """(runs, {scope: seconds}) of the program named ``module*`` that ran most often: each of its traced
    operations goes to the first group of ``pattern`` matched against its ``op_name`` (the executable's
    own metadata, ``measured['decode_scopes']``); with ``inherit``, an operation the compiler left
    without a scope (a grouped product's custom calls, a layout copy: no ``jit(...)/`` path in its
    metadata) takes the scope of the operation that ran before it in the same run. Everything else
    goes to ``other``."""
    trace, scopes = ctx.get("trace"), ctx["measured"].get("decode_scopes")
    if not trace or not scopes:
        return None
    rx, runs, out = re.compile(pattern), 0, {}
    for lines in trace["devices"].values():
        mods = [(n, s, s + d) for n, s, d in lines.get(trace_reduce.MODULES, []) if n.startswith(module)]
        if not mods:
            continue
        names = {}
        for n, _, _ in mods:
            names[n] = names.get(n, 0) + 1
        most = max(names, key=names.get)
        spans = sorted((s, e) for n, s, e in mods if n == most)
        runs += len(spans)
        ops = sorted((s, d, trace_reduce.short(n)) for n, s, d in lines.get(trace_reduce.OPS, [])
                     if not n.startswith("%while"))
        i = 0
        for s0, e0 in spans:
            while i < len(ops) and ops[i][0] < s0:
                i += 1
            last = "other"
            while i < len(ops) and ops[i][0] < e0:
                _, d, name = ops[i]
                found = rx.search(scopes.get(name, ""))
                if found:
                    last = found.group(1)
                elif not inherit or scopes.get(name, "").startswith("jit("):
                    last = "other"  # a scoped operation of another part of the program
                out[last] = out.get(last, 0.0) + d / 1e9
                i += 1
    n = max(len(trace["devices"]), 1)
    return (runs / n, {k: v / n for k, v in out.items()}) if runs else None
