"""JAX parallelism layer: meshes, sharding helpers, collectives, the
epoch-wise global shuffle, and ring attention for sequence parallelism.

This layer has no counterpart in the reference (its device-side parallelism
is delegated entirely to torch DDP/NCCL, SURVEY §2.2); it is the TPU-native
value-add that connects the host-side store to device meshes.
"""

from .fsdp import fsdp_rules
from .mesh import (batch_sharding, data_parallel_mesh, local_mesh,
                   make_mesh, replicate)
from .pipeline import (interleave_order, interleave_stage_params,
                       pipeline_1f1b, pipeline_apply,
                       pipeline_interleaved, pipeline_interleaved_1f1b,
                       stack_stage_params)
from .ring_attention import (balanced_order, ring_attention,
                             ring_self_attention)
from .shuffle import (all_to_all_rows, exchange_rows,
                      global_shuffle_epoch, host_global_shuffle,
                      permute_rows, ragged_global_shuffle)
from .tp import expert_rules, megatron_rules, shard_pytree, shardings_of

__all__ = [
    "make_mesh",
    "data_parallel_mesh",
    "local_mesh",
    "batch_sharding",
    "replicate",
    "all_to_all_rows",
    "exchange_rows",
    "permute_rows",
    "global_shuffle_epoch",
    "host_global_shuffle",
    "ragged_global_shuffle",
    "balanced_order",
    "ring_attention",
    "ring_self_attention",
    "fsdp_rules",
    "megatron_rules",
    "expert_rules",
    "shard_pytree",
    "shardings_of",
    "pipeline_apply",
    "pipeline_1f1b",
    "pipeline_interleaved",
    "pipeline_interleaved_1f1b",
    "interleave_stage_params",
    "interleave_order",
    "stack_stage_params",
]
