"""Device-mesh parallelism: mesh construction, sharding rules, ring
attention for sequence/context parallelism, and the sharded train step.

The reference has no model-side parallelism of its own — its torch_mp
frontend merely *feeds* Megatron TP/PP groups (reference
``lddl/torch_mp/bert.py:217-223``). Here the training side is first-class:
a ``jax.sharding.Mesh`` with data / fsdp / tensor / sequence axes, XLA
collectives over ICI, and ring attention for long-context scaling.
"""

from .mesh import (MESH_AXES, batch_pspec, canonical_batch_spec, make_mesh,
                   match_partition_rules, mesh_summary, reshard_pytree)
from .ring import ring_attention
from .train import (init_params, make_train_step, shard_batch,
                    snapshot_for_checkpoint)

__all__ = [
    'MESH_AXES', 'batch_pspec', 'canonical_batch_spec', 'make_mesh',
    'match_partition_rules', 'mesh_summary', 'reshard_pytree',
    'ring_attention', 'init_params', 'make_train_step', 'shard_batch',
    'snapshot_for_checkpoint'
]
