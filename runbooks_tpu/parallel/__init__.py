from runbooks_tpu.parallel.distributed import initialize, is_primary
from runbooks_tpu.parallel.mesh import (
    MESH_AXES,
    MeshConfig,
    make_mesh,
    single_device_mesh,
)
from runbooks_tpu.parallel.ring_attention import ring_attention
from runbooks_tpu.parallel.sharding import (
    DEFAULT_RULES,
    spec_for_array,
    tree_shardings,
    with_logical_constraint,
)

__all__ = ["initialize", "is_primary", "MESH_AXES", "MeshConfig",
           "make_mesh", "single_device_mesh", "ring_attention",
           "DEFAULT_RULES", "spec_for_array", "tree_shardings",
           "with_logical_constraint"]
