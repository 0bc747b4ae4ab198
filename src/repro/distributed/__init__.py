from .compression import (
    compress_error_feedback,
    dequantize_int8,
    dequantize_tree,
    quantize_int8,
    quantize_tree,
)
from .fault_tolerance import HeartbeatRegistry, StepWatchdog, plan_remesh
from .sharding import (
    activation_sharding_scope,
    batch_axes,
    batch_shardings,
    cache_shardings,
    constrain_activation,
    constrain_leading_dim,
    make_mesh,
    param_shardings,
    replicated,
    shard_batch,
    spec_for_param,
)

__all__ = [
    "HeartbeatRegistry",
    "StepWatchdog",
    "activation_sharding_scope",
    "batch_axes",
    "batch_shardings",
    "cache_shardings",
    "compress_error_feedback",
    "constrain_activation",
    "constrain_leading_dim",
    "dequantize_int8",
    "dequantize_tree",
    "make_mesh",
    "param_shardings",
    "plan_remesh",
    "quantize_int8",
    "quantize_tree",
    "replicated",
    "shard_batch",
    "spec_for_param",
]
