"""Device time per decode step of the operations under named scopes of the
program ``module*`` that ran most often: the executable's own op metadata
(instruction -> ``layer<n>.<kind>/<part>``, kept by the driver at warm-up)
joined to the trace's operations (``lib/steps_longshort.scope_seconds``).
``pattern``'s first group names what an operation counts under; the split
by group goes to ``notes``. Nothing where the program kept no metadata or
the run was not traced."""
from benchmarks.lib import steps_longshort as S


def read(ctx, module, pattern, inherit=True, note=None):
    found = S.scope_seconds(ctx, module, pattern, inherit)
    if not found:
        return None
    runs, by = found
    inside = {k: 1e3 * v / runs for k, v in by.items() if k != "other"}
    if not inside:
        return None
    if note:
        ctx["notes"][note] = {**inside, "other_ms": 1e3 * by.get("other", 0.0) / runs, "steps": runs}
    return sum(inside.values())
