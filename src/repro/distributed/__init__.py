from repro.distributed.sharding import (  # noqa: F401
    act_spec,
    constrain,
    mesh_axes,
    param_shardings,
    spec_for_param,
)
