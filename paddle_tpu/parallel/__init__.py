from .mesh import (DEFAULT_AXES, P, axis_size, create_mesh, get_mesh,
                   mesh_scope, named_sharding, replicated, set_mesh)
from .pipeline import (gpipe_spmd, make_pipeline_train_step,
                       partition_blocks, pipeline_forward)
from .ring_attention import (ring_attention, shard_map_ring_attention,
                             ulysses_attention)
from .compression import dgc_compress, dgc_init
from .localsgd import local_write_back, make_local_train_step
from .spmd import (batch_placement, batch_sharding,
                   make_sharded_train_step, param_sharding, shard_params,
                   tp_mesh, write_back, zero_sharding)
