"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chip: the compile cache is switched on,
the cell is built from its data files (``workloads/<cell>.json`` names the
configuration, the traffic mix and the driver), weights are made on the
device from the seed, the cell's own shapes are warmed, the window is
measured, ``correct`` is decided against the plain reference, and the last
line of standard output is the result. Nothing of one cell is written here:
a later PR adds files and an entry in ``BENCHMARK.json``.

``--rehearse 1`` runs the same path at the tiny sizes the files give under
``rehearse``, on whatever backend there is, and prints no device metric.
``--probe 1`` also reads the control and the planted faults (for setting
limits; the driver never passes it). ``--dump 1`` writes what the driver's
``dump()`` keeps of the window (the serve cell: every token gap with its
label, the flight records) under ``chiprun_out/dump/``, for a look at a tail."""

import argparse
import importlib
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def load(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def write_out(kind: str, name: str, payload: dict, indent=1):
    """What ``--probe`` and ``--dump`` keep: ``chiprun_out/<kind>/<name>.json`` (the chip tool brings that directory back)."""
    os.makedirs(os.path.join(ROOT, "chiprun_out", kind), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", kind, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=indent)


def say(text: str):
    print(f"[bench +{time.perf_counter() - T_START:7.1f}s] {text}", file=sys.stderr, flush=True)


class Tracer:
    """jax.profiler around part of the window, without the Python tracer
    (it doubles the host's time per step); the program's own annotations are
    switched on for as long as the trace runs."""

    def __init__(self, directory: str):
        self.dir = directory
        shutil.rmtree(directory, ignore_errors=True)

    def start(self):
        import jax
        from trlx_tpu.utils import profiling

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        profiling._tracing_active = True
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self):
        import jax
        from trlx_tpu.utils import profiling

        jax.profiler.stop_trace()
        profiling._tracing_active = False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--probe", type=int, default=0)
    ap.add_argument("--dump", type=int, default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = load("workloads", args.workload)
    config = load("configs", cell["config"])
    mix = load("traffic", cell["traffic"])
    spec = config["model_spec"]
    if args.rehearse:
        from benchmarks.drivers.common import deep_update

        cell, mix, spec = deep_update(cell, cell.get("rehearse")), deep_update(mix, mix.get("rehearse")), config["rehearse"]

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from trlx_tpu.utils.compile_cache import enable_compile_cache  # the program's own switch

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device {device}, compile cache {cache_dir}")
    if not args.rehearse:
        if device["platform"] != "tpu" or device["count"] < cell["chips"]:
            print(f"no accelerator for this cell: {device}, needs {cell['chips']} TPU chip(s)", file=sys.stderr)
            return 2
        from benchmarks.lib.peaks import peaks_for

        peaks = peaks_for(device["kind"])
    else:
        peaks = None

    driver = importlib.import_module(f"benchmarks.drivers.{mix['driver']}")
    run = driver.Cell({"cell": cell, "spec": spec, "mix": mix, "seed": args.seed, "rehearse": bool(args.rehearse),
                       "say": say})
    run.setup()
    setup_s = time.perf_counter() - T_START
    say(f"set up in {setup_s:.1f} s; measuring {args.seconds} s")

    trace_dir = os.path.join(HERE, ".run", "trace")
    tracing = bool(args.trace) and not args.rehearse  # a CPU trace has no device plane to reduce
    measured = run.window(args.seconds, Tracer(trace_dir) if tracing else None)
    metrics, attempted, failed = run.end_to_end()
    metrics["setup_s"] = setup_s
    device["memory_peak_bytes"] = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    say("window closed" + ("" if args.rehearse else f": {metrics}"))  # a CPU rehearsal's rates are not device numbers
    if args.dump and hasattr(run, "dump"):
        write_out("dump", f"{args.workload}.{args.seed}", {"metrics": metrics, **run.dump()}, indent=None)
    run.release()
    correct, compared, extra = run.check(probe=bool(args.probe))
    say(f"correct={correct}")

    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    breakdown = notes = None
    if args.trace:
        from benchmarks.lib import counts, trace_reduce

        trace = reduced = None
        if tracing:
            trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
            reduced = trace_reduce.reduce(trace)
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        ctx = {"cell": cell, "spec": spec, "mix": mix, "measured": measured, "trace": trace, "reduced": reduced,
               "peaks": peaks, "device": device, "counts": counts, "notes": {}}
        out = {}
        for m in bench["per_layer"]:
            if "workloads" in m and args.workload not in m["workloads"]:
                continue
            spec_m = load("layer_metrics", m["name"])
            reader = importlib.import_module(f"benchmarks.readers.{spec_m['reader']}")
            value = reader.read(ctx, **spec_m.get("args", {}))
            if value is not None:  # a reader that finds nothing to read returns nothing
                out[m["name"]] = value
        notes = ctx["notes"]
        if reduced:  # the device programs by time: the names a reader's ``module`` argument has to match
            notes["programs"] = sorted(([k, v["count"], v["seconds"]] for k, v in reduced["modules"].items()),
                                       key=lambda r: -r[2])[:12]
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        out = {k: v for k, v in metrics.items()
               if any(m["name"] == k and ("workloads" not in m or args.workload in m["workloads"])
                      for m in bench["end_to_end"])}
    if args.rehearse:  # a rehearsal's timings are not device metrics: none is printed
        out = {}
    if args.probe:
        write_out("probe", f"{args.workload}.{args.seed}",
                  {"compared": compared, "extra": extra, "metrics": metrics, "device": device})

    from benchmarks.lib import lastline

    line = lastline.build(correct, attempted, failed, {k: {"value": v, "unit": units[k]} for k, v in out.items()},
                          device, compared, breakdown,
                          {**(notes or {}), **{k: v for k, v in extra.items() if k != "probe"}})
    lastline.emit(line, tracing)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # daemon threads and the profiler's server must not hold the process
