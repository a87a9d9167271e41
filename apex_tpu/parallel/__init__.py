"""apex_tpu.parallel — distributed training over jax.sharding meshes.

Parity with ``apex.parallel`` (ref apex/parallel/__init__.py:10-19):
DistributedDataParallel, Reducer, SyncBatchNorm, convert_syncbn_model,
create_syncbn_process_group (-> syncbn_groups), LARC — over jax.sharding
meshes and XLA collectives instead of NCCL.

TPU extras beyond the reference (which is DP-only, SURVEY.md §2.4):
sequence parallelism (ring_attention — exact long-context attention over
a seq axis via ppermute — and ulysses_attention — the all_to_all
head-reshard construction), tensor parallelism (Megatron-style column/row
sharded layers, one psum per block), expert parallelism (MoEMLP with
all_to_all dispatch), and pipeline parallelism (pipeline_apply — a
scan+ppermute GPipe schedule).  All compose on one mesh.
"""
from apex_tpu.parallel.mesh import (  # noqa: F401
    data_parallel_mesh,
    make_mesh,
    replicate,
    shard_batch,
    syncbn_groups,
)
from apex_tpu.parallel.distributed import (  # noqa: F401
    DistributedDataParallel,
    Reducer,
    data_parallel_step,
    flatten_tree,
    unflatten_tree,
)
from apex_tpu.parallel.sync_batchnorm import (  # noqa: F401
    SyncBatchNorm,
    convert_syncbn_model,
)
from apex_tpu.parallel.multiproc import init_distributed  # noqa: F401
from apex_tpu.parallel.ring_attention import (  # noqa: F401
    ring_attention,
    ring_attention_ref,
)
from apex_tpu.parallel.tensor_parallel import (  # noqa: F401
    ColumnParallelDense,
    RowParallelDense,
    TensorParallelMLP,
    TensorParallelSelfAttention,
    column_parallel_dense,
    replicated_loss,
    row_parallel_dense,
    sync_replicated_grads,
)
from apex_tpu.parallel.ulysses import ulysses_attention  # noqa: F401
from apex_tpu.parallel.moe import (  # noqa: F401
    ExpertShardMLP,
    MoEMLP,
    top_k_routing,
)
from apex_tpu.parallel.pipeline import (  # noqa: F401
    pipeline_apply,
    stack_stage_params,
)
from apex_tpu.optimizers.larc import LARC  # noqa: F401  (ref exports it here)

# ref name: create_syncbn_process_group(group_size) -> process group.
# TPU: groups are index lists fed to collectives, see mesh.syncbn_groups.
create_syncbn_process_group = syncbn_groups
