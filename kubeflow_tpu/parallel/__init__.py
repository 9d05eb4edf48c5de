"""Parallelism core: meshes, sharding rules, collectives, process bootstrap.

This package is the TPU-native replacement for everything the reference
outsourced to TensorFlow's gRPC parameter-server runtime and OpenMPI/Horovod
(SURVEY.md §2.2): parallelism is expressed as axes of a
``jax.sharding.Mesh`` and XLA collectives over ICI (in-slice) and DCN
(cross-slice), not as replica processes pushing gradients over Ethernet.
"""

from kubeflow_tpu.parallel.mesh import (
    AXES,
    MeshSpec,
    build_hybrid_mesh,
    build_mesh,
    local_mesh_spec,
    mesh_spec_of,
    resize_spec,
    step_compiler_options,
)
from kubeflow_tpu.parallel.sharding import (
    LogicalRules,
    batch_shard_count,
    batch_sharding,
    default_rules,
    logical_sharding,
    named_sharding,
    replicated,
    shard_pytree,
)
from kubeflow_tpu.parallel.distributed import (
    ProcessEnv,
    initialize_from_env,
)
from kubeflow_tpu.parallel.pipeline import (
    bubble_fraction,
    pipeline_schedule,
    spmd_pipeline,
)
