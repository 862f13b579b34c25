from ..core.shard import wrap_shard_map
from .collectives import bucketed_psum, cross_pod_mean, psum_tree
from .elastic import choose_mesh_shape, make_elastic_mesh, reshard_state
from .sharding import (
    batch_shardings,
    batch_spec,
    cache_spec,
    opt_state_shardings,
    param_layout,
    param_spec,
    params_shardings,
)
