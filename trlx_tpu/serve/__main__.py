"""``python -m trlx_tpu.serve`` — checkpoint dir in, HTTP endpoint out.

The config (architecture, tokenizer, sampling) defaults to the one the
trainer embedded in the checkpoint's meta.json, so the minimal launch is
just ``--checkpoint``; ``--config`` overrides it, and the ``serve:``
section of that YAML (or the flags below, which win) sizes the bucket
lattice and the scheduler. See docs/source/serving.rst.
"""

import argparse
import sys

import yaml

from trlx_tpu.serve.engine import InferenceEngine, ServeConfig
from trlx_tpu.serve.server import InferenceServer


def parse_buckets(spec: str):
    """"8x32x16,16x64x32" -> [[8, 32, 16], [16, 64, 32]] (BxPxG)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        dims = part.lower().split("x")
        if len(dims) != 3:
            raise ValueError(
                f"bucket '{part}' is not BATCHxPROMPTxGEN (e.g. 8x32x16)"
            )
        out.append([int(d) for d in dims])
    return out


def parse_mesh(spec: str):
    """"tp=2,fsdp=2" -> {"tp": 2, "fsdp": 2} ("" -> single-device)."""
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"mesh axis '{part}' is not AXIS=SIZE (e.g. tp=2,fsdp=2)"
            )
        axis, _, size = part.partition("=")
        out[axis.strip()] = int(size)
    return out


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m trlx_tpu.serve",
        description="Serve a trained trlx_tpu policy checkpoint over HTTP.",
    )
    p.add_argument("--checkpoint", required=True,
                   help="checkpoint dir, or a run dir of step_<N> dirs "
                        "(the newest committed one is used)")
    p.add_argument("--config", default=None,
                   help="training YAML; default: the config embedded in "
                        "the checkpoint's meta.json")
    p.add_argument("--buckets", default=None,
                   help="comma-separated BATCHxPROMPTxGEN lattice, e.g. "
                        "'8x32x16,16x64x32' (overrides the serve: section)")
    p.add_argument("--host", default=None)
    p.add_argument("--port", type=int, default=None)
    p.add_argument("--max-queue", type=int, default=None,
                   help="admission-control queue bound (429 past it)")
    p.add_argument("--request-timeout", type=float, default=None,
                   help="per-request walltime bound (503 past it)")
    p.add_argument("--stall-timeout", type=float, default=None,
                   help="watchdog budget per admission/decode step "
                        "(0 = off)")
    p.add_argument("--slots", type=int, default=None,
                   help="slot-pool size (0 = largest compiled batch "
                        "extent)")
    p.add_argument("--page-size", type=int, default=None,
                   help="tokens per KV page (also the prefix-cache "
                        "sharing granularity)")
    p.add_argument("--pages", type=int, default=None,
                   help="page-pool size (0 = slots x pages-per-slot)")
    p.add_argument("--attention", choices=("jnp", "pallas"), default=None,
                   help="decode attention path: 'jnp' (default) = HBM "
                        "gather + dense attention, the parity oracle; 'pallas' = the fused "
                        "paged-attention kernel (page table scalar-"
                        "prefetched, online softmax in VMEM, greedy "
                        "bit-identical at bf16)")
    p.add_argument("--kv-dtype", choices=("bf16", "int8"), default=None,
                   help="KV-page storage tier: 'int8' stores codes + "
                        "per-(token, kv-head) f32 scales, ~1.9x pages per GB (lossy — greedy "
                        "parity on tested traces, not exact logits)")
    p.add_argument("--weights-dtype", choices=("bf16", "int8"),
                   default=None,
                   help="serve-only weight tier: 'int8' quantizes block "
                        "weights per output channel at strip-for-serve "
                        "(~halves serve/model_gb; embeddings stay bf16)")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="TTFT objective for serve/goodput (fraction of "
                        "requests whose first token beat it; 0 = all "
                        "count good)")
    p.add_argument("--flight-recorder-steps", type=int, default=None,
                   help="engine-step black-box ring size dumped on "
                        "stalls and served at /debug/state (0 = off)")
    p.add_argument("--max-replays", type=int, default=None,
                   help="crash-only replay budget per request: poisoned "
                        "steps re-queue in-flight requests this many "
                        "times before a 503 (0 = fail on first fault)")
    p.add_argument("--drain-timeout", type=float, default=None,
                   help="graceful-drain budget (SIGTERM, POST "
                        "/admin/drain): in-flight requests past it are "
                        "shed with 503 + reason")
    p.add_argument("--watch-checkpoints", type=float, default=None,
                   help="poll the run dir's LATEST every N seconds and "
                        "hot-swap new checkpoints live (0 = off; "
                        "POST /admin/reload always works)")
    p.add_argument("--mesh", default=None,
                   help="serve mesh as AXIS=SIZE pairs over tp/fsdp, "
                        "e.g. 'tp=2,fsdp=2' — weights shard Megatron-"
                        "style and KV pages shard on the head dim so a "
                        "6B+ policy decodes from a slice (default: "
                        "single device; '' forces single-device over a "
                        "YAML serve.mesh)")
    p.add_argument("--mesh-weights", choices=("fsdp", "replicated"),
                   default=None,
                   help="weight placement under --mesh: 'fsdp' shards "
                        "the second matrix axis (capacity), "
                        "'replicated' keeps weights whole per chip (no "
                        "all-gathers on the decode path)")
    p.add_argument("--degrade-step-ms", type=float, default=None,
                   help="adaptive admission: halve the queue bound "
                        "while a decode step exceeds this (0 = off)")
    p.add_argument("--speculation", choices=("off", "lookup", "draft"),
                   default=None,
                   help="speculative decoding tier: 'lookup' proposes "
                        "from a draft-free n-gram index over each "
                        "request's own history + the radix cache, "
                        "'draft' from a small draft model "
                        "(--spec-draft-checkpoint); greedy verification "
                        "keeps output bit-identical to 'off'. Requires "
                        "greedy decode")
    p.add_argument("--spec-k", type=int, default=None,
                   help="proposed tokens verified per slot per "
                        "speculative step (static shape; 3-8 fits most "
                        "traces)")
    p.add_argument("--spec-draft-checkpoint", default=None,
                   help="draft-model checkpoint directory for "
                        "--speculation draft")
    p.add_argument("--no-request-tracing", action="store_true",
                   help="disable per-request lifecycle tracing (the "
                        "serve/ttft|itl|goodput SLO family and the "
                        "'trace': true response payload)")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip lattice precompilation at startup (first "
                        "request per bucket then pays the compile)")
    return p


def serve_config_from_args(args) -> ServeConfig:
    """The serve: YAML section (when --config names a file carrying one)
    with CLI flags layered on top."""
    section = {}
    if args.config:
        with open(args.config) as f:
            section = (yaml.safe_load(f) or {}).get("serve") or {}
    cfg = ServeConfig.from_dict(section)
    if args.buckets is not None:
        cfg.buckets = parse_buckets(args.buckets)
    if args.mesh is not None:
        cfg.mesh = parse_mesh(args.mesh) or None
    if args.mesh_weights is not None:
        cfg.mesh_weights = args.mesh_weights
    for flag, attr in (("host", "host"), ("port", "port"),
                       ("max_queue", "max_queue"),
                       ("request_timeout", "request_timeout"),
                       ("stall_timeout", "stall_timeout"),
                       ("slots", "slots"),
                       ("page_size", "page_size"),
                       ("pages", "pages"),
                       ("attention", "attention"),
                       ("kv_dtype", "kv_dtype"),
                       ("weights_dtype", "weights_dtype"),
                       ("slo_ttft_ms", "slo_ttft_ms"),
                       ("flight_recorder_steps", "flight_recorder_steps"),
                       ("max_replays", "max_replays"),
                       ("drain_timeout", "drain_timeout"),
                       ("watch_checkpoints", "watch_checkpoints"),
                       ("degrade_step_ms", "degrade_step_ms"),
                       ("speculation", "speculation"),
                       ("spec_k", "spec_k"),
                       ("spec_draft_checkpoint", "spec_draft_checkpoint")):
        value = getattr(args, flag)
        if value is not None:
            setattr(cfg, attr, value)
    if args.no_request_tracing:
        cfg.request_tracing = False
    return cfg


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    serve_cfg = serve_config_from_args(args)
    from trlx_tpu.parallel import device_summary
    from trlx_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(f"[trlx_tpu.serve] {device_summary()} compile_cache={cache_dir}",
          file=sys.stderr, flush=True)
    engine = InferenceEngine.from_checkpoint(
        args.checkpoint, config=args.config, serve=serve_cfg
    )
    print(f"[trlx_tpu.serve] restored policy from "
          f"{engine.checkpoint_path}", file=sys.stderr, flush=True)
    server = InferenceServer(engine).start(warmup=not args.no_warmup)
    server.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
