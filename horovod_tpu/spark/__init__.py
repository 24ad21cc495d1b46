"""Spark cluster integration: ``horovod_tpu.spark.run()`` + estimators.

Reference analogs (SURVEY.md §2.6): horovod/spark/__init__.py (run,
run_elastic), horovod/spark/runner.py (barrier-mode task handshake),
horovod/spark/keras|torch/estimator.py, horovod/spark/common/store.py.

Design: Spark supplies *process placement* only — one barrier task per
worker; rank/size and the socket-controller rendezvous ride the same env
contract as every other launcher.  pyspark is an optional dependency;
importing this module is safe without it, constructing entry points raises
with guidance.  The Store abstraction (checkpoint/artifact paths) is
implemented locally since it has no Spark dependency.
"""

from __future__ import annotations

import os
import socket
from typing import Any, Callable, List, Optional

from .store import (Store, LocalStore, FilesystemStore,  # noqa: F401
                    DBFSLocalStore, HDFSStore)


def _require_pyspark():
    try:
        import pyspark  # noqa: F401

        return pyspark
    except ImportError as exc:  # pragma: no cover - env without pyspark
        raise ImportError(
            "horovod_tpu.spark requires 'pyspark'; install it or launch via "
            "horovodrun / horovod_tpu.run()"
        ) from exc


def run(fn: Callable, args=(), kwargs=None, num_proc: Optional[int] = None,
        extra_env: Optional[dict] = None, verbose: bool = False) -> List[Any]:
    """Run ``fn`` on ``num_proc`` Horovod workers inside Spark executors
    (reference: horovod.spark.run).

    Uses a barrier-mode RDD so all workers schedule together; rank 0's task
    binds the rendezvous port and shares it through the barrier context's
    allGather — the Spark-native replacement for the reference's driver/task
    service handshake.
    """
    _require_pyspark()
    from pyspark import BarrierTaskContext
    from pyspark.sql import SparkSession

    spark = SparkSession.builder.getOrCreate()
    sc = spark.sparkContext
    if num_proc is None:
        num_proc = max(int(sc.defaultParallelism), 1)
    env_extra = dict(extra_env or {})

    import cloudpickle

    payload = cloudpickle.dumps((fn, tuple(args), kwargs or {}))

    def _task(_):
        ctx = BarrierTaskContext.get()
        rank = ctx.partitionId()
        host = socket.gethostname()
        if rank == 0:
            # Advertise a routable IP: executor hostnames are not always
            # resolvable from peers, and gethostbyname(hostname) maps to
            # 127.0.1.1 on stock Debian — useless off-host.
            from ..runner.driver_service import local_addresses
            from ..runner.util import find_free_port

            info = f"{local_addresses()[0]}:{find_free_port('0.0.0.0')}"
        else:
            info = ""
        all_info = [i for i in ctx.allGather(info) if i]
        addr, port = all_info[0].rsplit(":", 1)
        hosts = ctx.allGather(host)
        local_rank = sum(1 for h in hosts[:rank] if h == hosts[rank])
        os.environ.update(env_extra)
        os.environ.update({
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": str(num_proc),
            "HOROVOD_LOCAL_RANK": str(local_rank),
            "HOROVOD_LOCAL_SIZE": str(sum(1 for h in hosts if h == host)),
            "HOROVOD_CONTROLLER": "socket",
            "HOROVOD_GLOO_RENDEZVOUS_ADDR": addr,
            "HOROVOD_GLOO_RENDEZVOUS_PORT": port,
        })
        f, a, kw = cloudpickle.loads(payload)
        return [(rank, f(*a, **kw))]

    results = (sc.parallelize(range(num_proc), num_proc)
               .barrier().mapPartitions(_task).collect())
    return [r for _, r in sorted(results)]


def run_elastic(fn: Callable, args=(), kwargs=None,
                num_proc: Optional[int] = None, min_np: int = 1,
                max_np: Optional[int] = None) -> List[Any]:
    """Elastic Spark launch (reference: horovod.spark.run_elastic).  Spark's
    barrier mode cannot resize a running stage, so (like the reference) the
    elastic loop re-submits the barrier job on failure with the surviving
    executor set; state recovery is the worker-side hvd.elastic loop."""
    _require_pyspark()
    last_exc: Optional[BaseException] = None
    for _ in range(3):
        try:
            return run(fn, args=args, kwargs=kwargs, num_proc=num_proc)
        except BaseException as exc:  # noqa: BLE001 - spark job failure
            last_exc = exc
            # Shrink toward min_np when a worker count was pinned; with
            # num_proc=None each retry re-sizes from the (possibly smaller)
            # surviving executor set.
            if num_proc is not None:
                if num_proc <= min_np:
                    break
                num_proc -= 1
    raise last_exc
