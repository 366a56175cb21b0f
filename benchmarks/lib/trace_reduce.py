"""From a profiler trace (``.xplane.pb``) to numbers. Reads with nothing but
``jax.profiler.ProfileData``: device planes are ``/device:TPU:<n>``, whose
line ``XLA Modules`` has one event per executed program (named
``jit_<function>(<fingerprint>)``) and whose line ``XLA Ops`` has one per
HLO operation (a ``while`` spans its body's operations). Host threads are
lines of the plane ``/host:CPU``; ``TraceAnnotation`` names appear there."""

import glob
import os
import re

MODULES, OPS = "XLA Modules", "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """{'devices': {plane: {line: [(name, start_ns, dur_ns)]}}, 'host': [(thread, name, start, dur)]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines[line.name] = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            if lines.get(MODULES) or lines.get(OPS):
                devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(line.name, e.name, e.start_ns, e.duration_ns) for e in line.events]
    return {"devices": devices, "host": host}


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def short(name: str, n: int = 96) -> str:
    """An operation's own name without its operands: '%fusion.12 = ...' -> 'fusion.12'."""
    m = re.match(r"%?([\w.\-]+)", name)
    return (m.group(1) if m else name)[:n]


def kind(op: str) -> str:
    """An operation's kind: its name without the numbers XLA appends ('copy.302' -> 'copy',
    'slice_bitcast_fusion.54.remat5' -> 'slice_bitcast_fusion')."""
    return re.sub(r"(\.(remat|clone)?\d*)+$", "", op) or op


def reduce(trace: dict, max_gaps: int = 60) -> dict:
    """busy_s and window_s (averaged over the device planes), time per
    program, the operations that took most time (the five heaviest kinds,
    'kind:<stem>', then the five heaviest single operations: a model unrolled
    over its layers spreads one kind over hundreds of names), and the longest
    idle gaps named by the innermost host span that covers most of each
    (the ``max_gaps`` longest gaps are named)."""
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace has no device plane with operations")
    busy, window, modules, ops, gaps = [], [], {}, {}, []
    for lines in devs.values():
        events = lines.get(OPS) or lines.get(MODULES)
        merged = union((s, s + d) for _, s, d in events if d > 0)
        mods = lines.get(MODULES) or events
        start = min(s for _, s, _ in mods)
        end = max(s + d for _, s, d in mods)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        window.append((end - start) / 1e9)
        for name, _, d in lines.get(MODULES, []):
            m = modules.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += d / 1e9
        for name, _, d in lines.get(OPS, []):
            if not name.startswith("%while"):  # a loop is its body's operations again
                ops[short(name)] = ops.get(short(name), 0.0) + d / 1e9
        gaps += [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
    n = len(devs)
    spans = [(name, s, s + d) for _, name, s, d in trace["host"] if d > 0]
    idle = {}
    for length, g0, g1 in sorted(gaps, reverse=True)[:max_gaps]:
        # the innermost host span that covers most of the gap; failing that, the one that covers most of it
        best, best_key = "(no host span)", None
        for name, s, e in spans:
            over = min(e, g1) - max(s, g0)
            if over > 0:
                key = (over >= 0.5 * (g1 - g0), -(e - s) if over >= 0.5 * (g1 - g0) else over)
                if best_key is None or key > best_key:
                    best, best_key = name, key
        idle[best] = idle.get(best, 0.0) + length / 1e9
    top = lambda d, n=10: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
    kinds = {}
    for name, seconds in ops.items():
        kinds["kind:" + kind(name)] = kinds.get("kind:" + kind(name), 0.0) + seconds
    return {
        "busy_s": sum(busy) / n, "window_s": sum(window) / n,
        "modules": {k: {"count": c, "seconds": s / n} for k, (c, s) in modules.items()},
        "device_ops": top(kinds, 5) + top(ops, 5), "idle_gaps": top(idle),
    }


def module_time(reduced: dict, prefix: str = None, most_run: bool = False):
    """(count, seconds) of the programs whose name starts with ``prefix``; with
    ``most_run``, of the single program among them that ran most often."""
    mods = {k: v for k, v in reduced["modules"].items() if prefix is None or k.startswith(prefix)}
    if not mods:
        return None
    if most_run:
        v = max(mods.values(), key=lambda v: v["count"])
        return v["count"], v["seconds"]
    return sum(v["count"] for v in mods.values()), sum(v["seconds"] for v in mods.values())


def longest_loop_in(trace: dict, module_prefix: str):
    """For each run of the programs named ``module_prefix*``, the longest
    ``while`` inside it. Returns (runs, seconds summed over runs) or None."""
    runs, total = 0, 0.0
    for lines in trace["devices"].values():
        whiles = [(s, d) for name, s, d in lines.get(OPS, []) if name.startswith("%while")]
        for name, s, d in lines.get(MODULES, []):
            if name.startswith(module_prefix):
                inside = [wd for ws, wd in whiles if ws >= s and ws + wd <= s + d]
                if inside:
                    runs += 1
                    total += max(inside) / 1e9
    return (runs, total / max(len(trace["devices"]), 1)) if runs else None
