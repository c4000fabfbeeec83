"""Sequence/context parallelism + the mesh-aware sharded serving step.

TPU-native re-design of the reference's ``flashinfer/parallel_attention/``
(ParallelAttention parallel_attention.py:12-62; all-to-all wrapper
parallel_wrapper.py:10; ring P2P parallel_wrapper.py:216-242) and of the
decode-context-parallel path (``flashinfer/comm/dcp_alltoall.py``).

``parallel/plan.py`` adds the compile-once SHARDED serving step:
:class:`ShardingPlan` (mesh + dp/tp/ep axes -> NamedShardings per
serving-state leaf) and the sharded fused step, one ``shard_map`` over
the mesh.
"""

from flashinfer_tpu.parallel.attention import (  # noqa: F401
    ParallelAttention,
    ring_attention,
    ulysses_attention,
)
from flashinfer_tpu.parallel.dcp import dcp_decode  # noqa: F401
from flashinfer_tpu.parallel.plan import (  # noqa: F401
    ShardedServingStep,
    ShardingPlan,
    build_sharded_fused_step,
    make_serving_mesh,
    plan_axes,
    split_shard_weights_for_spec,
    validate_dp_page_table,
)
