"""Launcher utilities (reference: horovod/runner/common/util/{hosts,network}.py)."""

from __future__ import annotations

import collections
import dataclasses
import os
import shlex
import socket
import threading
from typing import List, Optional, Sequence


@dataclasses.dataclass
class HostSlots:
    hostname: str
    slots: int


def parse_hosts(hosts: str) -> List[HostSlots]:
    """Parse '-H host1:2,host2:4' (reference: hosts.parse_hosts)."""
    out = []
    for part in hosts.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            name, slots = part.rsplit(":", 1)
            out.append(HostSlots(name, int(slots)))
        else:
            out.append(HostSlots(part, 1))
    return out


def assign_ranks(hosts: List[HostSlots], np_: int):
    """Round-robin-free block assignment of ranks to host slots, returning
    a list of (rank, hostname, local_rank, local_size, cross_rank,
    cross_size) like the reference's rank allocation."""
    slots = []
    for h in hosts:
        for local_rank in range(h.slots):
            slots.append((h.hostname, local_rank))
    if np_ > len(slots):
        raise ValueError(
            f"requested -np {np_} exceeds available slots {len(slots)}")
    slots = slots[:np_]
    per_host: dict = {}
    for hostname, _ in slots:
        per_host[hostname] = per_host.get(hostname, 0) + 1
    host_order = list(dict.fromkeys(h for h, _ in slots))
    assignments = []
    for rank, (hostname, local_rank) in enumerate(slots):
        assignments.append({
            "rank": rank,
            "hostname": hostname,
            "local_rank": local_rank,
            "local_size": per_host[hostname],
            "cross_rank": host_order.index(hostname),
            "cross_size": len(host_order),
        })
    return assignments


# find_free_port's candidates start here: below it live other services'
# registered ports.  Successive candidates lie a stride apart, as the kernel's
# are strangers to each other: a caller that derives a port of its own from
# one it was given (tests/integration/test_jax_distributed.py takes port + 1
# and port + 2 for its re-inits) must not land on the next one handed out.
# The cursor is keyed by pid so that a forked child starts a walk of its own.
# A port handed out stays claimed (an abstract unix socket named for it, gone
# with its process) for this process's next 64 calls, or until the process
# ends.
_PORT_FLOOR = 10000
_PORT_STRIDE = 7
_port_cursor: dict = {}
_port_claims: collections.deque = collections.deque(maxlen=64)
_port_lock = threading.Lock()


def _ephemeral_low() -> int:
    """Where the range begins that the kernel draws from for ``bind(0)`` and
    for every outgoing connection."""
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            return int(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return 32768


def _claim_port(addr: str, port: int) -> bool:
    """Nothing is bound to ``port`` on ``addr``, and no live process of this
    host was handed it by ``find_free_port``."""
    claim = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        claim.bind("\0horovod_tpu.port.%d" % port)
        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as probe:
            probe.bind((addr, port))
    except OSError:
        claim.close()
        return False
    _port_claims.append(claim)
    return True


def find_free_port(addr: str = "127.0.0.1") -> int:
    """A port on ``addr`` for a listener that starts seconds later (its
    process imports jax first).  Nothing may be given the port meanwhile, so
    it lies below the kernel's ephemeral range, where no ``bind(0)`` and no
    outgoing connection on this host can land, and the only other taker,
    another caller of this function, is held off by the claim.  The walk
    starts at a point spread by the process id and every call goes on where
    the last one stopped, so callers seldom meet at all."""
    span = _ephemeral_low() - _PORT_FLOOR
    pid = os.getpid()
    with _port_lock:
        for _ in range(max(span, 0)):
            # The golden-ratio sequence: neighbouring pids start far apart.
            at = _port_cursor.get(
                pid, (pid * 2654435761 % 2**32) * span >> 32) % span
            _port_cursor[pid] = at + _PORT_STRIDE
            if _claim_port(addr, _PORT_FLOOR + at):
                return _PORT_FLOOR + at
    # The ephemeral range leaves nothing below it: whatever the kernel gives.
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((addr, 0))
        return s.getsockname()[1]


def local_hostnames() -> List[str]:
    return ["localhost", "127.0.0.1", socket.gethostname()]


def host_hash() -> str:
    """Stable per-host identifier (reference: util/host_hash.py) — used to
    group ranks by physical host."""
    import hashlib

    return hashlib.md5(socket.gethostname().encode()).hexdigest()[:16]


def make_secret() -> str:
    """Random shared secret for signing coordinator RPCs (reference:
    common/util/secret.py)."""
    import secrets

    return secrets.token_hex(16)


def sign_message(secret: str, payload: str) -> str:
    """HMAC-SHA256 signature over a wire payload."""
    import hashlib
    import hmac

    return hmac.new(secret.encode(), payload.encode(),
                    hashlib.sha256).hexdigest()


def verify_message(secret: str, payload: str, signature: str) -> bool:
    import hmac

    return hmac.compare_digest(sign_message(secret, payload), signature)


def signed_dumps(obj, secret) -> str:
    """Serialize a coordinator message, HMAC-signing it when a shared
    secret is configured (reference: runner/common/util/secret.py — the
    driver/worker RPCs are signed so a stray connection can't join or
    reshape the job)."""
    import json

    payload = json.dumps(obj, sort_keys=True, separators=(",", ":"))
    if not secret:
        return payload
    return json.dumps({"p": payload, "sig": sign_message(secret, payload)},
                      separators=(",", ":"))


def verified_loads(line: str, secret):
    """Parse (and verify, when a secret is configured) a wire message;
    returns None for unverifiable messages."""
    import json

    msg = json.loads(line)
    if not secret:
        return msg
    if not (isinstance(msg, dict) and "p" in msg and "sig" in msg):
        return None
    if not verify_message(secret, msg["p"], msg["sig"]):
        return None
    return json.loads(msg["p"])


# Env prefixes both launchers forward to remote (ssh) workers.  The TPU_
# namespace is deliberately NOT a prefix here: a TPU-VM's environment
# carries instance-specific runtime vars (TPU_WORKER_ID, TPU_WORKER_
# HOSTNAMES, ...) that must not clobber the remote VM's own; only the
# pinning vars the launcher itself sets travel, by exact name.
FORWARD_ENV_PREFIXES = ("HOROVOD_", "PYTHONPATH", "PATH", "JAX_", "XLA_")
# TPU_VISIBLE_DEVICES is deliberately NOT forwarded: the launcher never
# sets it, so forwarding would impose the launcher host's own local pin on
# every remote VM.  Pin remote single-worker hosts on the host itself.
FORWARD_ENV_NAMES = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS")


def forwardable_env(k: str) -> bool:
    return k.startswith(FORWARD_ENV_PREFIXES) or k in FORWARD_ENV_NAMES


def ssh_command(ssh_port=None, connect_timeout=None) -> List[str]:
    """Base argv used to exec on a remote host (invoked as
    ``ssh_command() + [host, remote_shell_string]``).

    ``HOROVOD_SSH_COMMAND`` replaces the ENTIRE base argv (shlex-split,
    used verbatim — no extra options are appended, including -p), which
    enables agent-less transports and lets integration tests exercise the
    real remote-spawn path without an sshd (a fake-ssh script that runs
    the command locally).  Default: ssh with host-key checking off, the
    reference's gloo_run ssh contract (SURVEY.md §2.5).
    """
    override = os.environ.get("HOROVOD_SSH_COMMAND")
    if override:
        # Warn only on the user-passed --ssh-port: connect_timeout is an
        # internal default on some call sites (driver_service preflight),
        # so warning on it alone would fire spuriously for every override
        # user.  The message still names both dropped option kinds.
        if ssh_port:
            import warnings

            warnings.warn(
                "HOROVOD_SSH_COMMAND is set; --ssh-port/-p (and any "
                "ConnectTimeout option) are ignored — bake them into the "
                "override command instead.")
        return shlex.split(override)
    cmd = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if connect_timeout:
        cmd += ["-o", f"ConnectTimeout={int(connect_timeout)}"]
    if ssh_port:
        cmd += ["-p", str(ssh_port)]
    return cmd


def tpu_chips_on_host() -> int:
    """How many TPU chips this host exposes, read from the device nodes
    libtpu itself enumerates (``/dev/accel*``, or ``/dev/vfio/<n>`` on newer
    hosts) — without loading libtpu, which would claim them."""
    import glob

    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


# libtpu's process grid (TPU_PROCESS_BOUNDS) over one host's chips when each
# co-located worker owns one chip.  Only what has run on hardware is listed:
# four workers on a v5e 2x2 host.
_HOST_PROCESS_BOUNDS = {4: "2,2,1"}


def pin_tpu_chip(env: dict, local_rank: int, local_size: int,
                 force: bool = False,
                 process_ports: Optional[Sequence[int]] = None) -> None:
    """Pin a co-located worker to its own TPU chip (libtpu is single-owner
    per chip — the GPU analog is the local-rank device pinning the
    reference's launcher relies on).

    With one worker on the host nothing is touched (the worker may use all
    chips, and an explicit user pin is honored) unless ``force`` is set —
    the elastic driver always pins, because a lone worker that claimed the
    whole host would collide with workers spawned by a later scale-up.
    With several co-located workers a single inherited ``TPU_VISIBLE_CHIPS``
    would hand every worker the same chip and crash all but the first
    claim, so it is overridden per worker.

    The visible chip and the one-chip-per-process bounds alone give each
    worker a one-chip world of its own: fine for workers that only meet on
    the host plane.  ``process_ports`` (one free local port per co-located
    worker, the same list for all of them) asks for ONE runtime across the
    workers instead, which ``--jax-distributed`` needs: libtpu then also
    reads the process grid, every process's address, this process's port
    and its task id, and the workers see ``local_size`` devices, one of
    them local, joined over ICI.
    """
    if local_size <= 1 and not force:
        # A lone worker keeps all chips; its explicit pin (if any) is
        # honored as-is.
        return
    if "TPU_VISIBLE_CHIPS" in env or "TPU_VISIBLE_DEVICES" in env:
        import sys

        print(f"horovod_tpu: overriding inherited TPU chip pin for "
              f"local_rank {local_rank} (per-slot pinning is required "
              "here; an inherited global pin cannot be per-worker correct)",
              file=sys.stderr)
        env.pop("TPU_VISIBLE_DEVICES", None)
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
    env["TPU_VISIBLE_CHIPS"] = str(local_rank)
    env.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    if process_ports is None:
        return
    if local_size not in _HOST_PROCESS_BOUNDS:
        raise ValueError(
            f"no known TPU process grid for {local_size} co-located workers "
            f"in one jax runtime (known: {sorted(_HOST_PROCESS_BOUNDS)}); "
            "run one worker per host, or as many as the host has chips")
    if len(process_ports) != local_size:
        raise ValueError(f"need {local_size} process ports, got "
                         f"{len(process_ports)}")
    env["TPU_PROCESS_BOUNDS"] = _HOST_PROCESS_BOUNDS[local_size]
    env["TPU_PROCESS_ADDRESSES"] = ",".join(
        f"localhost:{p}" for p in process_ports)
    env["TPU_PROCESS_PORT"] = str(process_ports[local_rank])
    env["CLOUD_TPU_TASK_ID"] = str(local_rank)
