"""horovod_tpu — a TPU-native distributed training framework with Horovod's
capabilities and API surface (``import horovod_tpu as hvd``).

Built from scratch for JAX/XLA on TPU (see SURVEY.md): the familiar
imperative hvd.* API over an enqueue→negotiate→fuse→execute core, with the
data plane lowered to XLA collectives over ICI instead of NCCL/MPI.
"""

from .wire import (  # noqa: F401
    Average, Sum, Min, Max, Product, Adasum, ReduceOp,
)
from .basics import (  # noqa: F401
    init, shutdown, is_initialized, initialized,
    rank, size, local_rank, local_size, cross_rank, cross_size,
    is_homogeneous, num_devices,
    start_timeline, stop_timeline, start_device_trace, stop_device_trace,
    metrics, metrics_prometheus, flight_record, step_trace, fleet_history,
    mpi_threads_supported, mpi_enabled, mpi_built,
    gloo_enabled, gloo_built, nccl_built, ddl_built, ccl_built,
    cuda_built, rocm_built, tpu_built, native_core_built,
)
from .mpi_ops import (  # noqa: F401
    allreduce, allreduce_, allreduce_async, allreduce_async_,
    grouped_allreduce, grouped_allreduce_, grouped_allreduce_async,
    grouped_allreduce_async_,
    allgather, allgather_async, grouped_allgather, grouped_allgather_async,
    broadcast, broadcast_, broadcast_async, broadcast_async_,
    alltoall, alltoall_async,
    reducescatter, reducescatter_async, grouped_reducescatter,
    grouped_reducescatter_async,
    barrier, join, synchronize, poll,
)
from .process_sets import (  # noqa: F401
    ProcessSet, add_process_set, remove_process_set, global_process_set,
)
from .functions import (  # noqa: F401
    broadcast_parameters, broadcast_optimizer_state, broadcast_object,
    broadcast_object_fn, allgather_object,
)
from .compression import Compression  # noqa: F401
from .utils.step_watch import StepWatch  # noqa: F401
from . import elastic  # noqa: F401
from . import checkpoint  # noqa: F401

try:  # callbacks/sync-BN need optax+flax; keep the core importable without
    from . import callbacks  # noqa: F401
    from .sync_batch_norm import SyncBatchNorm  # noqa: F401
except ImportError:  # pragma: no cover
    pass
from .exceptions import (  # noqa: F401
    HorovodInternalError, HostsUpdatedInterrupt,
)

try:  # optimizer requires optax; keep the core importable without it
    from .optimizer import (  # noqa: F401
        DistributedOptimizer, DistributedGradientTransformation,
        allreduce_gradients, clip_by_global_norm,
    )
except ImportError:  # pragma: no cover
    pass

__version__ = "0.1.0"
