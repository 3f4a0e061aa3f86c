"""Parallel/distributed layer: mesh, sharded fleet attribution, trainer."""

from kepler_tpu.parallel.aggregator_core import (
    FleetResult,
    fleet_attribution_program,
    make_fleet_program,
    make_temporal_fleet_program,
    put_fleet_batch,
    run_fleet_attribution,
    temporal_fleet_program,
)
from kepler_tpu.parallel.fleet import (
    MODE_MODEL,
    MODE_RATIO,
    FleetBatch,
    NodeReport,
    assemble_fleet_batch,
)
from kepler_tpu.parallel.expert import (
    EXPERT_AXIS,
    make_expert_parallel_moe,
    top1_route,
)
from kepler_tpu.parallel.mesh import (
    MODEL_AXIS,
    NODE_AXIS,
    MultihostInit,
    initialize_multihost,
    make_mesh,
    multihost_status,
    submesh_for_processes,
)
from kepler_tpu.parallel.pipeline import (
    STAGE_AXIS,
    make_pipeline,
    make_pipelined_deep,
)
from kepler_tpu.parallel.ulysses import (
    make_ulysses_attention,
    make_ulysses_temporal_program,
    ulysses_attention_shardmap,
)
from kepler_tpu.parallel.ring import (
    SEQ_AXIS,
    full_attention,
    make_ring_attention,
)
from kepler_tpu.parallel.sequence import (
    make_sequence_parallel_train_step,
    make_temporal_program,
)
from kepler_tpu.parallel.trainer import (
    make_distributed_train_step,
    mlp_param_shardings,
    shard_train_state,
)

__all__ = [
    "EXPERT_AXIS",
    "SEQ_AXIS",
    "STAGE_AXIS",
    "full_attention",
    "make_expert_parallel_moe",
    "make_pipeline",
    "make_pipelined_deep",
    "make_temporal_fleet_program",
    "temporal_fleet_program",
    "make_ring_attention",
    "make_ulysses_attention",
    "make_ulysses_temporal_program",
    "ulysses_attention_shardmap",
    "make_sequence_parallel_train_step",
    "make_temporal_program",
    "top1_route",
    "FleetBatch",
    "FleetResult",
    "MODE_MODEL",
    "MODE_RATIO",
    "MODEL_AXIS",
    "NODE_AXIS",
    "NodeReport",
    "assemble_fleet_batch",
    "fleet_attribution_program",
    "make_distributed_train_step",
    "make_fleet_program",
    "initialize_multihost",
    "make_mesh",
    "submesh_for_processes",
    "MultihostInit",
    "multihost_status",
    "mlp_param_shardings",
    "put_fleet_batch",
    "run_fleet_attribution",
    "shard_train_state",
]
