"""Device time of the parts of one program, by the program's own named scopes:
the map ``trlx_tpu.telemetry.programs.op_scopes(module)`` keeps (instruction ->
op_name, from the executable the process compiled) handed to
``lib/steps_longshort.scope_seconds``, which joins it to the trace's
operations inside each run of the program ``module*`` that ran most often.

``pattern``'s first group names the part an operation counts under; the value
is the sum over ``parts`` (every part where none are named) per ``per``:
``run`` (one run of the program) or ``decode_step`` (a run's ``gen_tokens``
of the mix), times ``scale``. ``note`` gathers the parts in ms a ``per``
(metrics that share a note add their parts to it) with ``other_ms``: the time
of ``within`` (a pattern; the whole program where none is given) outside
every part of the note. ``notes.op_scopes_s`` holds what reading the map took.
Nothing where the run was not traced, the program registered no map (a
program older than the record) or no operation matched."""
import time

from benchmarks.lib import steps_longshort as S


def op_scopes(ctx, module):
    try:
        from trlx_tpu.telemetry import programs
    except ImportError:
        return None
    took = ctx["notes"].setdefault("op_scopes_s", {})
    t0 = time.perf_counter()
    scopes = programs.op_scopes(module)
    took.setdefault(module, time.perf_counter() - t0)
    return scopes


def read(ctx, module, pattern, per="run", scale=1.0, parts=None, within=None, note=None):
    if not ctx.get("trace"):
        return None
    scopes = op_scopes(ctx, module)
    if not scopes:
        return None
    view = {**ctx, "measured": {"decode_scopes": scopes}}
    found = S.scope_seconds(view, module, pattern)
    if not found:
        return None
    runs, by = found
    total = sum(by.values())
    by.pop("other", None)
    if not by:
        return None
    per_unit = runs * (ctx["mix"]["gen_tokens"] if per == "decode_step" else 1)
    value = scale * sum(v for k, v in by.items() if parts is None or k in parts) / per_unit
    if note:
        if within:
            total = sum(v for k, v in S.scope_seconds(view, module, f"({within})")[1].items() if k != "other")
        count = per + "s"
        split = {k: v for k, v in ctx["notes"].get(note, {}).items() if k not in ("other_ms", count)}
        split.update({k: 1e3 * v / per_unit for k, v in by.items()})
        ctx["notes"][note] = {**split, "other_ms": 1e3 * total / per_unit - sum(split.values()), count: per_unit}
    return value
