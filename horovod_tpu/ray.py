"""Ray cluster integration: RayExecutor / ElasticRayExecutor.

Reference analogs (SURVEY.md §2.6): horovod/ray/runner.py (RayExecutor),
horovod/ray/elastic_v2.py (ElasticRayExecutor), horovod/ray/strategy.py
(placement groups).

Design: each Ray actor hosts one worker process slot; the driver assigns
the same HOROVOD_* env contract the CLI launcher uses (rank/size +
socket-controller rendezvous), so the core runtime is identical whether
workers were launched by ssh, Spark, or Ray.  On TPU pods the actors are
scheduled one per TPU-VM host (``use_gpu`` parity flag maps to requesting
TPU resources).

Ray itself is an optional dependency: constructing an executor without ray
installed raises ImportError with guidance; everything importable stays
import-safe for API-surface parity.
"""

from __future__ import annotations

import os
import socket
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional


def _require_ray():
    try:
        import ray  # noqa: F401

        return ray
    except ImportError as exc:  # pragma: no cover - env without ray
        raise ImportError(
            "horovod_tpu.ray requires the 'ray' package; install ray or use "
            "horovod_tpu.run()/horovodrun for ssh-based launching"
        ) from exc


@dataclass
class RayExecutorSettings:
    """Subset of the reference's Settings relevant on TPU."""

    timeout_s: int = 300
    placement_group_timeout_s: int = 100
    verbose: bool = False


class RayExecutor:
    """Run a function on N Horovod workers scheduled as Ray actors
    (reference: horovod/ray/runner.py RayExecutor API: start/run/run_remote/
    execute/shutdown)."""

    def __init__(self, settings: Optional[RayExecutorSettings] = None,
                 num_workers: int = 1, num_hosts: Optional[int] = None,
                 num_workers_per_host: Optional[int] = None,
                 cpus_per_worker: int = 1, use_gpu: bool = False,
                 gpus_per_worker: int = 0, use_current_placement_group: bool = False):
        self.ray = _require_ray()
        self.settings = settings or RayExecutorSettings()
        if num_hosts and num_workers_per_host:
            num_workers = num_hosts * num_workers_per_host
        self.num_workers = num_workers
        self.cpus_per_worker = cpus_per_worker
        self.use_gpu = use_gpu
        self.gpus_per_worker = gpus_per_worker
        self._actors: List[Any] = []
        self._pg = None

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        ray = self.ray

        @ray.remote(num_cpus=self.cpus_per_worker,
                    num_gpus=self.gpus_per_worker if self.use_gpu else 0)
        class _Worker:
            def __init__(self):
                self._env: Dict[str, str] = {}

            def hostname(self):
                return socket.gethostname()

            def set_env(self, env):
                self._env = dict(env)
                os.environ.update(self._env)

            def execute(self, fn, *args, **kwargs):
                return fn(*args, **kwargs)

        strategy = self._placement_strategy()
        self._actors = [
            _Worker.options(**strategy).remote()
            for _ in range(self.num_workers)
        ]
        hostnames = ray.get([a.hostname.remote() for a in self._actors],
                            timeout=self.settings.timeout_s)
        self._assign_env(hostnames)

    def _placement_strategy(self) -> Dict[str, Any]:
        """PACK workers so intra-host slots share a machine (reference:
        strategy.py ColocatedStrategy)."""
        ray = self.ray
        bundle = {"CPU": self.cpus_per_worker}
        if self.use_gpu and self.gpus_per_worker:
            bundle["GPU"] = self.gpus_per_worker
        try:
            from ray.util.placement_group import placement_group

            self._pg = placement_group([dict(bundle)] * self.num_workers,
                                       strategy="PACK")
            ray.get(self._pg.ready(),
                    timeout=self.settings.placement_group_timeout_s)
            return {"placement_group": self._pg}
        except Exception:
            # Release the reservation before falling back to free scheduling,
            # otherwise the unused group double-books the cluster.
            if self._pg is not None:
                try:
                    from ray.util.placement_group import \
                        remove_placement_group

                    remove_placement_group(self._pg)
                except Exception:
                    pass
                self._pg = None
            return {}

    def _assign_env(self, hostnames: List[str]) -> None:
        """Build the launcher env contract: ranks ordered host-major, a free
        rendezvous port bound on rank 0's host."""
        ray = self.ray
        order = sorted(range(len(hostnames)), key=lambda i: (hostnames[i], i))
        # Reorder the actor list to rank order so run()/execute results are
        # rank-indexed and execute_single targets rank 0.
        self._actors = [self._actors[i] for i in order]
        hostnames = [hostnames[i] for i in order]
        order = list(range(len(hostnames)))
        host_slots: Dict[str, int] = {}
        rank0_host = hostnames[order[0]]
        port = ray.get(self._actors[order[0]].execute.remote(_free_port))
        hosts_uniq = list(dict.fromkeys(hostnames[i] for i in order))
        local_sizes: Dict[str, int] = {}
        for i in order:
            local_sizes[hostnames[i]] = local_sizes.get(hostnames[i], 0) + 1
        futures = []
        for rank, i in enumerate(order):
            h = hostnames[i]
            lr = host_slots.get(h, 0)
            host_slots[h] = lr + 1
            env = {
                "HOROVOD_RANK": str(rank),
                "HOROVOD_SIZE": str(self.num_workers),
                "HOROVOD_LOCAL_RANK": str(lr),
                "HOROVOD_LOCAL_SIZE": str(local_sizes[h]),
                "HOROVOD_CROSS_RANK": str(hosts_uniq.index(h)),
                "HOROVOD_CROSS_SIZE": str(len(hosts_uniq)),
                "HOROVOD_CONTROLLER": "socket",
                "HOROVOD_GLOO_RENDEZVOUS_ADDR": rank0_host,
                "HOROVOD_GLOO_RENDEZVOUS_PORT": str(port),
            }
            futures.append(self._actors[i].set_env.remote(env))
        ray.get(futures)

    # -- execution ----------------------------------------------------------
    def run(self, fn: Callable, args=None, kwargs=None) -> List[Any]:
        """Run ``fn`` on every worker; returns results ordered by rank."""
        return self.ray.get(self.run_remote(fn, args, kwargs))

    def run_remote(self, fn: Callable, args=None, kwargs=None):
        args, kwargs = args or [], kwargs or {}
        return [a.execute.remote(fn, *args, **kwargs) for a in self._actors]

    def execute(self, fn: Callable) -> List[Any]:
        """Apply ``fn(executable)`` on each worker actor."""
        return self.ray.get([a.execute.remote(fn) for a in self._actors])

    def execute_single(self, fn: Callable) -> Any:
        return self.ray.get(self._actors[0].execute.remote(fn))

    def shutdown(self) -> None:
        for a in self._actors:
            self.ray.kill(a)
        self._actors = []
        if self._pg is not None:
            from ray.util.placement_group import remove_placement_group

            remove_placement_group(self._pg)
            self._pg = None


class ElasticRayExecutor:
    """Elastic variant: discovers hosts from the live Ray cluster and drives
    the same ElasticDriver the CLI uses (reference: elastic_v2.py)."""

    def __init__(self, min_np: int = 1, max_np: Optional[int] = None,
                 cpus_per_worker: int = 1, override_discovery=None):
        self.ray = _require_ray()
        self.min_np = min_np
        self.max_np = max_np
        self.cpus_per_worker = cpus_per_worker
        self._discovery = override_discovery

    def _ray_discovery(self):
        from .runner.elastic_driver import HostDiscovery

        ray = self.ray
        cpus = self.cpus_per_worker

        class _RayHosts(HostDiscovery):
            def find_available_hosts(self):
                hosts = {}
                for node in ray.nodes():
                    if not node.get("Alive"):
                        continue
                    slots = int(node.get("Resources", {}).get("CPU", 0)
                                // cpus)
                    if slots > 0:
                        hosts[node["NodeManagerHostname"]] = slots
                return hosts

        return _RayHosts()

    def run(self, fn: Callable, args=None, kwargs=None) -> List[Any]:
        """Launch an elastic job over the Ray cluster's hosts via the
        elastic driver (workers execute ``fn`` through the pickled-function
        worker entry).  Returns the per-rank results.

        The payload/result directory lives under the driver's CWD, which the
        elastic driver re-enters on every worker host (`cd $CWD` over ssh) —
        multi-node runs therefore require a shared filesystem there, the
        norm on TPU-VM pods.
        """
        import pickle
        import sys
        import tempfile

        import cloudpickle

        from .runner.elastic_driver import ElasticDriver

        workdir = tempfile.mkdtemp(prefix=".hvd_ray_", dir=os.getcwd())
        payload = os.path.join(workdir, "payload.pkl")
        with open(payload, "wb") as f:
            cloudpickle.dump((fn, args or [], kwargs or {}), f)
        command = [sys.executable, "-m", "horovod_tpu.runner._exec_fn",
                   payload, workdir]
        discovery = self._discovery or self._ray_discovery()
        driver = ElasticDriver(discovery, command, self.min_np, self.max_np)
        rc = driver.run()
        if rc != 0:
            raise RuntimeError(f"elastic job failed with exit code {rc}")
        results = []
        for name in sorted(os.listdir(workdir)):
            if name.startswith("result_"):
                with open(os.path.join(workdir, name), "rb") as f:
                    status, value = pickle.load(f)
                if status != "ok":
                    raise RuntimeError(f"worker failed: {value}")
                results.append(value)
        return results


def _free_port() -> int:
    from .runner.util import find_free_port

    return find_free_port("0.0.0.0")
