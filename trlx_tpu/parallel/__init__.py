"""SPMD parallelism layer: mesh construction, parameter/batch shardings,
and the multi-host runtime.

This module is the TPU-native replacement for the reference's entire
distribution stack — HF Accelerate's DDP/DeepSpeed wrapping, NCCL
collectives, and the `accelerate launch` process model (reference:
trlx/model/accelerate_base_model.py:52-82, trlx/model/nn/ilql_models.py:38-41,
201-214, README.md:125):

- **dp** (data parallel): batches are sharded over it; XLA turns the loss
  gradient into a psum over ICI — the implicit all-reduce the reference gets
  from `accelerator.backward` (reference: trlx/model/accelerate_ppo_model.py:200).
- **fsdp** (fully-sharded data parallel): parameters/optimizer state are
  sharded over it and all-gathered on use — the ZeRO-3 equivalent
  (reference: DeepSpeed ZeRO via `deepspeed.zero.*`, ilql_models.py:201-214).
  Batches shard over (dp, fsdp) jointly, so fsdp devices also contribute
  data parallelism.
- **tp** (tensor parallel): attention heads and MLP hidden dims are
  partitioned Megatron-style (column-parallel in-projections, row-parallel
  out-projections) — absent in the reference, required for gpt-j-6B scale
  (reference: configs/ppo_gptj.yml:2).
- **sp** (sequence/context parallel): reserved axis for ring attention on
  long sequences; see trlx_tpu.ops.ring_attention.

Everything is expressed through `jax.sharding.NamedSharding` on a
`jax.sharding.Mesh`; XLA GSPMD inserts the collectives (psum / all-gather /
reduce-scatter) and routes them over ICI. No hand-written communication.
"""

from trlx_tpu.parallel.mesh import (  # noqa: F401
    AXES,
    build_mesh,
    mesh_from_config,
    single_device_mesh,
)
from trlx_tpu.parallel.sharding import (  # noqa: F401
    batch_sharding,
    param_sharding_specs,
    param_shardings,
    relayout_for_decode,
    replicated,
    shard_batch,
    shard_params,
    sharded_opt_init,
)
from trlx_tpu.parallel.runtime import (  # noqa: F401
    broadcast_host_floats,
    device_summary,
    initialize_runtime,
    is_main_process,
    process_count,
    process_index,
)
