"""The result line the driver reads, and the lines of numbers compared."""

import json
import sys

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")


def compared_lines(compared: dict):
    """{name: {'value': v, 'limit': l}} -> short plain lines, one per number."""
    return [f"compared {name} = {c['value']!r} limit {c['limit']!r} {'ok' if c['ok'] else 'FAIL'}"
            for name, c in compared.items()]


def build(correct, attempted, failed, metrics, device, compared, breakdown=None, notes=None) -> dict:
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": {k: {"value": float(v["value"]), "unit": v["unit"]} for k, v in metrics.items()},
        "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    if notes:
        line["notes"] = notes
    line["compared"] = compared  # comes last in the line
    return line


def validate(line: dict, trace: bool):
    missing = [k for k in REQUIRED if k not in line]
    if missing:
        raise ValueError(f"result line lacks {missing}")
    dev = line["device"]
    for k in ("platform", "kind", "count", "memory_peak_bytes") + (("busy_s", "window_s") if trace else ()):
        if k not in dev:
            raise ValueError(f"device lacks {k!r}")
    for name, m in line["metrics"].items():
        if set(m) != {"value", "unit"} or m["value"] != m["value"]:
            raise ValueError(f"metric {name!r} is malformed: {m}")
    if list(line)[-1] != "compared":
        raise ValueError("'compared' has to come last in the line")


def emit(line: dict, trace: bool):
    validate(line, trace)
    for text in compared_lines(line["compared"]):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
