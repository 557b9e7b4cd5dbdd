"""horovod_tpu: a TPU-native data-parallel training framework.

A from-scratch rebuild of the capabilities of the reference system
(``agileml/horovod`` -- see SURVEY.md): the NCCL/MPI collective op layer is
re-implemented over XLA collectives on the ICI/DCN device mesh, the tensor
fusion buffer is an HBM-resident bucketing pass at trace time, the response
cache is a compiled-executable cache, and the background coordinator thread
disappears entirely under SPMD.

Public API (mirrors ``import horovod.torch as hvd`` surface)::

    import horovod_tpu as hvd
    hvd.init()
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3),
                                   compression=hvd.Compression.bf16)
    step = hvd.make_train_step(loss_fn, opt)
"""

from .core.basics import (  # noqa: F401
    init, shutdown, is_initialized, mesh, reduce_axes,
    size, rank, local_size, local_rank, cross_size, cross_rank,
    is_homogeneous, nccl_built, mpi_built, gloo_built, tpu_built,
    cuda_built, rocm_built, start_timeline, stop_timeline,
    mpi_threads_supported,
)
from .core.exceptions import (  # noqa: F401
    HorovodTpuError, HorovodInternalError, HostsUpdatedInterrupt,
    DesyncError, NotInitializedError, ProcessSetError,
)
from .core.desync import check_desync  # noqa: F401
from .core.process_sets import (  # noqa: F401
    ProcessSet, add_process_set, remove_process_set, get_process_set,
    process_set_names,
)
from .collectives.reduce_op import (  # noqa: F401
    ReduceOp, Average, Sum, Min, Max, Product, Adasum,
)
from .collectives.compression import Compression  # noqa: F401
from .collectives import ops as collective_ops  # noqa: F401  (in-step)
from . import ops  # noqa: F401  (pallas kernels: hvd.ops.flash_attention)
from .collectives.eager import (  # noqa: F401
    allreduce, allreduce_async, grouped_allreduce, grouped_allgather,
    grouped_reducescatter, allgather, allgatherv, broadcast, reducescatter,
    alltoall, alltoallv, barrier, join, synchronize, poll, local_result,
    replicated_stack, local_rank_count,
)
from .optim.distributed import (  # noqa: F401
    DistributedOptimizer, DistributedAdasumOptimizer, allreduce_gradients,
)
from .optim.zero import (  # noqa: F401  (ZeRO-1 sharded optimizer state)
    zero_init, zero_sharding, shard_zero_state, zero_report,
)
from .optim.functions import (  # noqa: F401
    allgather_object, broadcast_parameters, broadcast_optimizer_state,
    broadcast_object,
)
from . import elastic  # noqa: F401
from .utils.checkpoint import (  # noqa: F401
    save_checkpoint, restore_checkpoint, latest_checkpoint, checkpoint_path,
    save_checkpoint_sharded, restore_checkpoint_sharded,
)
from .training import (  # noqa: F401
    make_train_step, make_flax_train_step, make_eval_step, shard_batch,
    shard_batch_from_local, replicate, batch_sharding,
    replicated_sharding, sync_batch_norm,
    make_train_loop, make_flax_train_loop, stack_steps, shard_steps,
    stacked_batch_sharding, steps_per_execution, microbatches,
    mirror_opt_state_specs,
)
from .data import DevicePrefetcher, prefetch_to_device  # noqa: F401
from . import serving  # noqa: F401  (continuous-batching inference)
from .timeline.metrics import (  # noqa: F401  (unified metrics plane)
    StepReport, metrics_snapshot, last_step_report, render_prometheus,
)

__version__ = "0.1.0"
