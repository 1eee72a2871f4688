from repro.sharding.policy import (  # noqa: F401
    MeshPolicy,
    constrain,
    current_policy,
    device_local,
    param_specs,
    trace_device_local,
    use_policy,
)
