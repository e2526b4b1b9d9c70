from repro.runtime.fault import (  # noqa: F401
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    ShardLostError,
    corrupt_checkpoint_leaf,
)
