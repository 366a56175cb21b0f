"""Multi-host runtime bootstrap.

Replaces the reference's launcher/process model — `accelerate launch`, one
process per GPU, WORLD_SIZE/LOCAL_RANK env plumbing, NCCL process groups
(reference: README.md:125, trlx/model/accelerate_base_model.py:21-22,54-55):

On TPU pods the model is one process per *host*, each seeing its slice's
local chips; `jax.distributed.initialize()` wires the hosts together and
every `jax.devices()` call then returns the global device list. Collectives
need no further setup — they are compiled into the SPMD program.

`initialize_runtime()` is safe to call unconditionally: it no-ops on single
-process environments (CPU tests, the one-chip bench) and is idempotent.
"""

import os
import sys

import jax

_initialized = False


def device_summary() -> str:
    """``platform=... device_kind=... devices=N`` as JAX reports them —
    the line every entry point logs once at start, so a run that landed
    on the wrong backend says so in its first lines."""
    devices = jax.devices()
    return (
        f"platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} devices={len(devices)}"
    )


def initialize_runtime(coordinator_address: str = None,
                       num_processes: int = None,
                       process_id: int = None) -> None:
    """Initialize multi-host JAX when running on more than one process.

    With no arguments, relies on the TPU pod metadata that
    `jax.distributed.initialize` auto-discovers; explicit arguments support
    manual rigs. No-op (with a note in the env) when single-process.
    """
    global _initialized
    if _initialized:
        return
    explicit = coordinator_address is not None
    # TPU_WORKER_HOSTNAMES lists the pod's hosts; single-host runtimes set
    # it to "localhost", so only a multi-entry list means a real pod.
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    auto_pod = ("," in hostnames) or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    ) or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
    if explicit or auto_pod:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    _initialized = True
    # once per process (the trainers' constructor lands here first)
    print(f"[trlx_tpu] {device_summary()}", file=sys.stderr, flush=True)


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def is_main_process() -> bool:
    """Metrics/checkpoint emission gate (parity: the reference's
    `accelerator.is_main_process`, trlx/model/accelerate_base_model.py:58)."""
    return jax.process_index() == 0


def broadcast_host_floats(values) -> "np.ndarray":
    """Process-0's view of a host-computed float array, identical on every
    process. No-op single-process.

    Replicated-loading SPMD (trlx_tpu.parallel.sharding.shard_batch)
    requires every host to feed bit-identical global batches. Prompts are
    seed-deterministic, but host `reward_fn` outputs (an HF pipeline, a
    service call) are NOT guaranteed bit-identical across hosts — and
    rewards feed device_put shards, so divergent floats would silently fork
    the replicas. Broadcasting from process 0 closes that hole, replacing
    the reference's per-rank loader split + gather
    (reference: trlx/orchestrator/ppo_orchestrator.py:32-35,
    trlx/model/accelerate_ilql_model.py:124).
    """
    import numpy as np

    arr = np.asarray(values, np.float32)
    if jax.process_count() == 1:
        return arr
    from jax.experimental import multihost_utils

    return np.asarray(
        multihost_utils.broadcast_one_to_all(arr), np.float32
    )
