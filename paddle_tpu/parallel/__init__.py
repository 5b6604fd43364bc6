"""paddle_tpu.parallel: the SPMD substrate (mesh, shardings, sharded train
steps, pipeline). See parallel/api.py for the design mapping from the
reference's multi-device machinery to GSPMD."""
from .mesh import (  # noqa: F401
    build_mesh, set_global_mesh, get_global_mesh, ensure_global_mesh,
    register_ring, ring_axis, TopologyError,
)
from .api import (  # noqa: F401
    ShardedTrainStep, ShardingStage, shard_activation, shard_batch,
    shard_batch_activation, mark_sharding,
    param_spec,
)
from .ring_attention import ring_attention, ulysses_attention  # noqa: F401
from jax import shard_map  # noqa: F401  (the one import point)
