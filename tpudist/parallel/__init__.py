"""Parallelism toolkit: meshes, shardings, and sequence/context parallelism.

The reference implements only data parallelism (SURVEY.md §2.2); this package
holds the mesh/sharding machinery that expresses it — and the extra axes
(sequence/context via ring attention, model) the TPU design keeps open.
"""

from tpudist.dist import (make_mesh, batch_sharding,            # noqa: F401
                          replicated_sharding, shard_host_batch)
from tpudist.parallel.tensor_parallel import (                  # noqa: F401
    VIT_RULES, CONVNEXT_RULES, SWIN_RULES, RESNET_RULES, VGG_RULES,
    DENSENET_RULES, DEFAULT_RULES, NO_TP_FAMILIES, rules_for,
    require_rules, tree_specs, tree_shardings,
    shard_tree, make_gspmd_train_step, make_gspmd_eval_step)
from tpudist.parallel import plane                              # noqa: F401
from tpudist.parallel.plane import (                            # noqa: F401
    AXIS_BINDING, ParallelPlan, build_mesh, mesh_axis, plan,
    rules_for_mesh, shard_state, state_shardings,
    state_specs as plane_state_specs, validate_mesh_request)
from tpudist.parallel.comm import (                             # noqa: F401
    compressed_pmean, init_comm_state, make_wus_train_step,
    make_wus_eval_step)
from tpudist.parallel.ring_attention import (                   # noqa: F401
    attention, ring_attention, make_ring_attention)
from tpudist.parallel.seq_parallel import make_sp_train_step    # noqa: F401
from tpudist.parallel.expert_parallel import (                  # noqa: F401
    make_ep_train_step, make_ep_eval_step, state_specs as ep_state_specs)
from tpudist.parallel.pipeline_parallel import (                # noqa: F401
    make_pp_train_step, make_pp_eval_step, pp_state_specs)
from tpudist.parallel.pipeline import (                         # noqa: F401
    pipeline_spmd, stack_stage_params, make_pipeline)
from tpudist.parallel.moe import (                              # noqa: F401
    init_moe_params, moe_spmd, moe_dense, make_moe)
