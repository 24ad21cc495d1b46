"""Worker-side elastic coordination: registration, assignment, host-update
notifications.

Reference analogs (SURVEY.md §2.5, §3.5): horovod/runner/elastic/worker.py
(WorkerNotificationService/Client/Manager) and the rendezvous re-round
machinery in horovod/runner/elastic/rendezvous.py.  The wire protocol here
is JSON lines over a persistent TCP connection to the elastic driver
(``horovod_tpu.runner.elastic_driver``): the worker registers once at
startup, receives a rank assignment per *generation* (rendezvous round),
and the driver pushes ``hosts_updated`` events over the same connection.
"""

from __future__ import annotations


import os
import socket
import threading
from typing import Any, Dict, Optional

from ..utils.logging import get_logger

log = get_logger()


# Wire signing lives with the other launcher security utilities; re-exported
# here because the worker-side protocol uses it too.
from ..runner.util import (find_free_port, signed_dumps,  # noqa: F401,E402
                           verified_loads)


class NotificationManager:
    """Collects driver-pushed host-update events; ``State.check_host_updates``
    drains it (reference: WorkerNotificationManager)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._updates = 0

    def notify(self) -> None:
        with self._lock:
            self._updates += 1

    def drain_updates(self) -> int:
        with self._lock:
            n, self._updates = self._updates, 0
            return n


notification_manager = NotificationManager()


class ElasticCoordinatorClient:
    """Persistent connection to the elastic driver."""

    def __init__(self):
        self._sock: Optional[socket.socket] = None
        self._file = None
        self._secret: Optional[str] = None
        self._lock = threading.Lock()
        self._assign_cv = threading.Condition(self._lock)
        self._assignment: Optional[Dict[str, Any]] = None
        self._assignment_gen = -1
        self._consumed_gen = -1
        self._reader: Optional[threading.Thread] = None
        self._closed = False

    # -- connection ---------------------------------------------------------
    def connect(self) -> None:
        if self._sock is not None:
            return
        addr = os.environ["HOROVOD_ELASTIC_COORD_ADDR"]
        port = int(os.environ["HOROVOD_ELASTIC_COORD_PORT"])
        worker_id = os.environ.get("HOROVOD_ELASTIC_WORKER_ID", "")
        self._secret = os.environ.get("HOROVOD_ELASTIC_SECRET") or None
        self._sock = socket.create_connection((addr, port), timeout=60)
        self._sock.settimeout(None)
        self._file = self._sock.makefile("rw", encoding="utf-8")
        self._send({"type": "register", "worker_id": worker_id,
                    "pid": os.getpid(),
                    "host": socket.gethostname()})
        self._reader = threading.Thread(target=self._read_loop,
                                        name="hvd-elastic-client", daemon=True)
        self._reader.start()

    def close(self) -> None:
        self._closed = True
        try:
            if self._sock:
                self._sock.close()
        except OSError:
            pass

    def _send(self, obj: Dict[str, Any]) -> None:
        self._file.write(signed_dumps(obj, self._secret) + "\n")
        self._file.flush()

    def _read_loop(self) -> None:
        try:
            for line in self._file:
                msg = verified_loads(line, self._secret)
                if msg is None:
                    log.warning("elastic: dropping unverified message")
                    continue
                t = msg.get("type")
                if t == "assign":
                    with self._assign_cv:
                        self._assignment = msg
                        self._assignment_gen = int(msg["generation"])
                        self._assign_cv.notify_all()
                elif t == "hosts_updated":
                    log.info("elastic: driver announced host set change")
                    notification_manager.notify()
                elif t == "shutdown":
                    log.info("elastic: driver requested shutdown")
                    os._exit(143)
        except (OSError, ValueError):
            pass
        if not self._closed:
            # Connection to the driver died: local collectives will fail
            # soon; surface as a host update so the loop re-rendezvouses
            # (and fails cleanly if the driver is truly gone).
            notification_manager.notify()

    # -- rendezvous ---------------------------------------------------------
    def wait_assignment(self, timeout: float = 600.0) -> Dict[str, Any]:
        """Block until the driver sends an assignment for a generation newer
        than the last one consumed; apply it to the environment."""
        with self._assign_cv:
            ok = self._assign_cv.wait_for(
                lambda: self._assignment_gen > self._consumed_gen, timeout)
            if not ok:
                raise TimeoutError("elastic rendezvous timed out")
            a = dict(self._assignment)
            self._consumed_gen = self._assignment_gen
        os.environ["HOROVOD_RANK"] = str(a["rank"])
        os.environ["HOROVOD_SIZE"] = str(a["size"])
        os.environ["HOROVOD_LOCAL_RANK"] = str(a.get("local_rank", 0))
        os.environ["HOROVOD_LOCAL_SIZE"] = str(a.get("local_size", 1))
        os.environ["HOROVOD_CROSS_RANK"] = str(a.get("cross_rank", a["rank"]))
        os.environ["HOROVOD_CROSS_SIZE"] = str(a.get("cross_size", a["size"]))
        os.environ["HOROVOD_CONTROLLER"] = "socket"
        os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"] = a["rendezvous_addr"]
        os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"] = str(a["rendezvous_port"])
        # Generation epoch: forces EVERY process (survivor or respawn) to
        # make the same jax.distributed reuse-vs-reinit decision — a
        # survivor reusing a stale runtime while a replacement freshly
        # initializes against it would hang the pod.
        os.environ["HOROVOD_ELASTIC_GENERATION"] = str(
            a.get("generation", 0))
        # Per-generation jax.distributed coordinator (hosted by the new
        # rank 0) — applied only for jax-distributed jobs; a launch-time
        # static coordinator could live on a preempted host.
        if (a.get("jax_coordinator")
                and os.environ.get("HOROVOD_JAX_DISTRIBUTED") == "1"):
            os.environ["HOROVOD_JAX_COORDINATOR"] = a["jax_coordinator"]
        # Fleet autopilot (driver-side policy loop): rank 0 opens the
        # coordinator's loopback policy listener on this port.  Only present
        # in autopilot mode and only meaningful on rank 0; clear any stale
        # value so a demoted ex-rank-0 never reopens the listener.
        if a.get("policy_port") and int(a["rank"]) == 0:
            os.environ["HOROVOD_AUTOPILOT_PORT"] = str(a["policy_port"])
        else:
            os.environ.pop("HOROVOD_AUTOPILOT_PORT", None)
        # Live cockpit: same rank-0-only rule.  The driver hands out the
        # SAME port every generation, so SSE clients reconnect to a stable
        # address after a re-formation; HOROVOD_COCKPIT itself is the
        # user-facing on/off switch and rides the normal environment.
        if a.get("cockpit_port") and int(a["rank"]) == 0:
            os.environ["HOROVOD_COCKPIT_PORT"] = str(a["cockpit_port"])
        else:
            os.environ.pop("HOROVOD_COCKPIT_PORT", None)
        return a

    def mark_ready(self) -> None:
        """Tell the driver this worker has torn down collectives and awaits
        the next generation's assignment.

        Includes freshly-probed free ports on THIS host: if this worker is
        elected rank 0, the rendezvous server, the per-generation
        jax.distributed coordinator and (in autopilot mode) the policy
        listener bind here, and only a local probe proves a port is
        actually free (the driver may be a different machine)."""
        try:
            ports = [find_free_port("0.0.0.0") for _ in range(3)]
        except OSError:
            ports = []
        self._send({"type": "ready", "ports": ports})


_client: Optional[ElasticCoordinatorClient] = None
_client_lock = threading.Lock()


def is_elastic_worker() -> bool:
    return os.environ.get("HOROVOD_ELASTIC") == "1"


def get_client() -> ElasticCoordinatorClient:
    global _client
    with _client_lock:
        if _client is None:
            _client = ElasticCoordinatorClient()
            _client.connect()
        return _client


def ensure_assignment() -> None:
    """Called from hvd.init() in elastic mode: block for the initial rank
    assignment on first init (registration doubles as readiness).  Re-inits
    after a reset already consumed their assignment in
    ``elastic._reset``, so this is a no-op then."""
    client = get_client()
    with client._lock:
        has_assignment = client._consumed_gen >= 0
    if not has_assignment:
        client.wait_assignment()
