"""Centralised HOROVOD_* environment-variable parsing.

TPU-native analog of the reference's horovod/common/utils/env_parser.cc
(ParseStallInspectorFromEnv, SetBoolFromEnv, ...; SURVEY.md §2.1).  The same
variable names are kept wherever they are meaningful on TPU so existing
Horovod launch scripts keep working; CUDA/NCCL-only knobs are accepted but
ignored (listed in IGNORED_VARS).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

# Variables that exist in the reference but have no TPU meaning. Parsed and
# ignored (with a debug log) so reference launch scripts run unmodified.
IGNORED_VARS = (
    "HOROVOD_GPU_OPERATIONS",
    "HOROVOD_CPU_OPERATIONS",
    "HOROVOD_NUM_NCCL_STREAMS",
    "HOROVOD_MLSL_BGT_AFFINITY",
    "HOROVOD_GPU_ALLREDUCE",
    "HOROVOD_GPU_ALLGATHER",
    "HOROVOD_GPU_BROADCAST",
    "HOROVOD_GPU_ALLTOALL",
    "HOROVOD_ADASUM_MPI_CHUNK_SIZE",
)

# Robustness knobs consumed natively (C++ getenv) below the ctypes ABI,
# registered here for discoverability (hvd_lint's NATIVE_READ_VARS is the
# enforcement side):
#   HOROVOD_FAULT_INJECT              deterministic fault-injection spec,
#                                     comma-separated site:cycle:rank:action[:arg]
#   HOROVOD_ABORT_PROPAGATION_TIMEOUT seconds a failed worker waits for the
#                                     coordinator's ABORT broadcast before
#                                     raising with a generic reason
#   HOROVOD_RENDEZVOUS_RETRIES        rendezvous connect attempts before
#                                     giving up on the coordinator
#   HOROVOD_RENDEZVOUS_BACKOFF_BASE_MS  base delay of the exponential
#                                     rendezvous retry backoff
#   HOROVOD_CONTROL_TREE              leader-tree control plane (protocol
#                                     v12): auto (default; engages on multi-
#                                     host jobs with size >= 8) | on | off.
#                                     Only the coordinator's value matters —
#                                     its verdict rides the rendezvous book.
#   HOROVOD_CTRL_TREE_FANOUT          per-node fan-in bound of the adaptive-
#                                     depth tree (default 32, min 2): jobs
#                                     spanning more hosts than this insert
#                                     mid-level super-leaders until every
#                                     node gathers at most this many
#                                     aggregate links
#   HOROVOD_CONTROL_TREE_DEPTH        force an exact tree level count (2 =
#                                     the v9 two-level shape, 3+ = always
#                                     insert super-leader layers); 0/unset
#                                     = adaptive from the fanout rule
#   HOROVOD_RENDEZVOUS_ACCEPTORS      coordinator-side rendezvous acceptor
#                                     threads (default 4, clamped to 1..64)
#                                     draining the worker HELLO herd in
#                                     parallel

DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024  # bytes, same default as reference
DEFAULT_CYCLE_TIME_MS = 1.0
DEFAULT_CACHE_CAPACITY = 1024
DEFAULT_STALL_WARNING_S = 60.0
DEFAULT_ELASTIC_TIMEOUT_S = 600.0


def get_bool(name: str, default: bool = False) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def step_watch_file() -> Optional[str]:
    """Where ``hvd.StepWatch`` appends its stall records as JSON lines
    (HOROVOD_STEP_WATCH_FILE; unset = kept in memory and logged only).  A
    literal ``{rank}`` becomes this process's HOROVOD_RANK."""
    path = os.environ.get("HOROVOD_STEP_WATCH_FILE")
    if not path:
        return None
    return path.replace("{rank}", os.environ.get("HOROVOD_RANK", "0"))


def get_int(name: str, default: int) -> int:
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return int(val)
    except ValueError:
        return default


WIRE_COMPRESSION_CODECS = ("none", "bf16", "int8", "int4")
# Codecs the in-jit device plane implements (ops/quantize.py): bf16 stays a
# host-ring-only codec — on-chip a bf16 cast is a plain convert XLA already
# fuses, so only the block-scaled codecs (int8, packed int4) earn a device
# implementation.
DEVICE_WIRE_COMPRESSION_CODECS = ("none", "int8", "int4")

# Ring schedules the device plane's quantized collectives can run
# (ops/collectives.py): 'auto' resolves from the axis size — torus for
# factorizable pod-slice shapes, bidi for rings of 4+, ring otherwise.
DEVICE_SCHEDULES = ("auto", "ring", "bidi", "torus")

# In-jit gradient-exchange planes DistributedOptimizer can run
# (ops/gspmd_plane.py): 'eager' builds explicit psum/ppermute programs,
# 'gspmd' annotates shardings and lets XLA insert + schedule the
# collectives, 'auto' prefers gspmd where it composes and demotes
# deterministically otherwise.
DATA_PLANES = ("auto", "eager", "gspmd")


def get_data_plane() -> str:
    """Data-plane request from HOROVOD_DATA_PLANE (default 'auto').
    Unrecognised values warn and fall back to 'auto' rather than failing
    init — plane resolution (ops/gspmd_plane.py) is deterministic in the
    mesh and the optimizer's codec config, so all ranks fall the same
    way."""
    raw = os.environ.get("HOROVOD_DATA_PLANE", "auto")
    val = raw.strip().lower() or "auto"
    if val in DATA_PLANES:
        return val
    from .logging import get_logger
    get_logger().warning(
        "HOROVOD_DATA_PLANE=%r: not one of %s; using 'auto'",
        raw, "/".join(DATA_PLANES))
    return "auto"


def get_device_schedule() -> str:
    """Ring schedule request from HOROVOD_DEVICE_SCHEDULE (default
    'auto').  Unrecognised values warn and fall back to 'auto' rather
    than failing init — the resolution is deterministic in the axis size,
    so all ranks fall the same way."""
    raw = os.environ.get("HOROVOD_DEVICE_SCHEDULE", "auto")
    val = raw.strip().lower() or "auto"
    if val in DEVICE_SCHEDULES:
        return val
    from .logging import get_logger
    get_logger().warning(
        "HOROVOD_DEVICE_SCHEDULE=%r: not one of %s; using 'auto'",
        raw, "/".join(DEVICE_SCHEDULES))
    return "auto"


def _warn_wire(raw: str, what: str, allowed) -> None:
    from .logging import get_logger

    get_logger().warning(
        "HOROVOD_WIRE_COMPRESSION=%r: %s not one of %s; using 'none'",
        raw, what, "/".join(allowed))


def get_wire_compression_planes() -> "tuple":
    """Parse HOROVOD_WIRE_COMPRESSION into per-plane codecs
    ``(host, device)``.

    Accepted forms:

    - bare codec (``int8``) — host (cross-host ring) plane only, the
      pre-plane-syntax meaning, kept for back-compat;
    - comma-separated ``plane=codec`` assignments
      (``host=bf16,device=int8``, ``device=int8``); planes not named stay
      ``none``.

    Unset / empty / "0" / "off" / "false" all mean "none" so boolean-style
    launch scripts degrade safely; anything else unrecognised falls back to
    "none" with a warning rather than failing init (the coordinator's
    agreed value wins over per-rank divergence on the host plane, and the
    device plane's demotion rules are deterministic in the tensor, so all
    ranks fall the same way).
    """
    raw = os.environ.get("HOROVOD_WIRE_COMPRESSION", "")
    val = raw.strip().lower()
    host, device = "none", "none"
    if val in ("", "0", "off", "false", "no"):
        return host, device
    for token in val.split(","):
        token = token.strip()
        if not token:
            continue
        if "=" in token:
            plane, _, codec = token.partition("=")
            plane, codec = plane.strip(), codec.strip()
            if plane == "host":
                if codec in WIRE_COMPRESSION_CODECS:
                    host = codec
                else:
                    _warn_wire(raw, f"host codec {codec!r}",
                               WIRE_COMPRESSION_CODECS)
            elif plane == "device":
                if codec in DEVICE_WIRE_COMPRESSION_CODECS:
                    device = codec
                else:
                    _warn_wire(raw, f"device codec {codec!r}",
                               DEVICE_WIRE_COMPRESSION_CODECS)
            else:
                _warn_wire(raw, f"plane {plane!r}", ("host", "device"))
        elif token in WIRE_COMPRESSION_CODECS:
            host = token
        else:
            _warn_wire(raw, f"codec {token!r}", WIRE_COMPRESSION_CODECS)
    return host, device


def get_wire_compression() -> str:
    """Host-plane codec from HOROVOD_WIRE_COMPRESSION (see
    :func:`get_wire_compression_planes` for the full per-plane syntax)."""
    return get_wire_compression_planes()[0]


def get_float(name: str, default: float) -> float:
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return float(val)
    except ValueError:
        return default


@dataclasses.dataclass
class Config:
    """Runtime configuration snapshot, one per `hvd.init()`.

    Field-for-field parity with the env vars consumed by the reference core
    (fusion threshold / cycle time / cache / autotune / timeline / stall
    inspector), plus the rendezvous variables set by the launcher.
    """

    # Identity (set by the launcher; single-process defaults otherwise).
    rank: int = 0
    size: int = 1
    local_rank: int = 0
    local_size: int = 1
    cross_rank: int = 0
    cross_size: int = 1

    # Control plane.
    controller: str = "auto"  # auto | local | socket
    rendezvous_addr: str = "127.0.0.1"
    rendezvous_port: int = 0

    # Core tuning.
    fusion_threshold_bytes: int = DEFAULT_FUSION_THRESHOLD
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    cache_capacity: int = DEFAULT_CACHE_CAPACITY
    cache_enabled: bool = True
    autotune: bool = False
    autotune_log: Optional[str] = None
    # HOROVOD_HIERARCHICAL_ALLREDUCE: shm-local reduce -> leader-only
    # cross-host ring -> shm-local broadcast for process sets spanning
    # hosts with co-located ranks.  Off by default (flat ring).
    hierarchical_allreduce: bool = False
    # HOROVOD_WIRE_COMPRESSION: codec for fp32 allreduce payloads on
    # cross-host ring hops ("none" | "bf16" | "int8").  Accumulation stays
    # fp32; the coordinator decides per-response so ranks never diverge.
    # Per-plane syntax ("device=int8", "host=bf16,device=int8") additionally
    # engages the in-jit device-plane codec (ops/quantize.py); a bare codec
    # keeps the historical host-only meaning.
    wire_compression: str = "none"
    # Device-plane codec parsed from the same variable
    # ("none" | "int8" | "int4").
    wire_compression_device: str = "none"
    # HOROVOD_DEVICE_SCHEDULE: ring schedule for the device plane's
    # quantized collectives ("auto" | "ring" | "bidi" | "torus"); 'auto'
    # resolves from the axis size, torus demotes to bidi when the world
    # has no 2-D factorization.
    device_schedule: str = "auto"
    # HOROVOD_DATA_PLANE: which in-jit gradient-exchange plane
    # DistributedOptimizer uses ("auto" | "eager" | "gspmd").  'eager'
    # builds explicit collectives (shard_map + psum); 'gspmd' annotates
    # shardings with with_sharding_constraint and lets jit insert and
    # overlap the collectives; 'auto' resolves per optimizer — gspmd when
    # it composes, demoting to eager (with a counter) otherwise.
    data_plane: str = "auto"
    # HOROVOD_HLO_INSPECT: compiled-collective introspection for the gspmd
    # plane (ops/hlo_inspect.py) — at trace time the lowered module's
    # compiler-inserted collectives are inventoried and fed to the
    # observability pillars (gspmd byte counters, flight type 16, the
    # step-trace plane tag).  On by default: the cost is one extra
    # lower+compile per trace signature, never per-step work; 0 disables
    # inspection entirely.
    hlo_inspect_enabled: bool = True
    # HOROVOD_WIRE_COMPRESSION_MIN_BYTES: payload floor (bytes) below which
    # either plane's codec demotes to the uncompressed path — small tensors
    # are latency- not bandwidth-bound, and the scale overhead erodes the
    # ratio.  Shares the native coordinator's 64 KiB default.
    wire_compression_min_bytes: int = 1 << 16

    # Observability.
    timeline_path: Optional[str] = None
    timeline_mark_cycles: bool = False
    # HOROVOD_METRICS: native counter/histogram registry (negotiation wait,
    # cycle occupancy, fusion efficiency, ring hops, shm fences).  Setting
    # HOROVOD_METRICS_FILE implies enabled; a literal "{rank}" in the path
    # is substituted, otherwise ".<rank>" is appended so ranks never clobber
    # each other on a shared filesystem.
    metrics_enabled: bool = False
    metrics_file: Optional[str] = None
    metrics_interval_s: float = 10.0
    # HOROVOD_FLIGHT_RECORDER: always-on lock-free event black box (ring
    # buffer of compact binary events at the sites the metrics plane
    # instruments).  On by default — the record cost is a few relaxed
    # stores.  HOROVOD_FLIGHT_RECORDER_SLOTS sizes the per-thread ring
    # (rounded up to a power of two).
    flight_recorder_enabled: bool = True
    flight_recorder_slots: int = 4096
    # HOROVOD_POSTMORTEM_DIR: where each rank dumps its flight buffer on
    # abort / fatal init error / fatal signal, and where the coordinator
    # writes the merged postmortem.json.  "{rank}" is substituted like
    # HOROVOD_METRICS_FILE.  Unset = crash dumps disabled (the in-memory
    # recorder still runs for hvd.flight_record()).
    postmortem_dir: Optional[str] = None
    log_level: str = "warning"

    # Stall inspector.
    stall_check_enabled: bool = True
    stall_warning_s: float = DEFAULT_STALL_WARNING_S
    stall_shutdown_s: float = 0.0  # 0 = never shut down

    # Elastic.
    elastic_timeout_s: float = DEFAULT_ELASTIC_TIMEOUT_S
    elastic_enabled: bool = False
    # Zero-downtime state migration (docs/elastic.md): each rank keeps a
    # replicated shard of its committed training state on
    # HOROVOD_MIGRATE_REPLICAS ring-successor ranks (0 disables
    # replication — re-formation always falls back to the checkpoint),
    # refreshed every HOROVOD_MIGRATE_INTERVAL_STEPS commits.
    migrate_replicas: int = 2
    migrate_interval_steps: int = 1

    # Fleet autopilot (driver-internal).  HOROVOD_AUTOPILOT_PORT is set by
    # the elastic driver on rank 0 only: the coordinator opens a loopback
    # policy listener on this port so the driver's autopilot thread can poll
    # straggler verdicts and record eviction decisions.  0 = disabled (the
    # default for every hand-launched job); workers never see it.  The
    # operator-facing knobs (HOROVOD_AUTOPILOT, HOROVOD_AUTOPILOT_EVICT_WINDOWS,
    # HOROVOD_AUTOPILOT_MIN_NP, HOROVOD_AUTOPILOT_COOLDOWN_SECS) are parsed
    # by the driver in runner/autopilot.py — they never cross into worker
    # processes or the native core.
    autopilot_port: int = 0

    # HOROVOD_STEP_TRACE: causal step tracing — per-step phase breakdown
    # (negotiation-wait / fusion / ring / fence / idle) recorded into a
    # per-rank ring and aggregated fleet-wide on the coordinator.  On by
    # default, same cost bar as the flight recorder.
    # HOROVOD_STEP_TRACE_SLOTS sizes the ring (rounded up to a power of
    # two).
    step_trace_enabled: bool = True
    step_trace_slots: int = 256
    # HOROVOD_COCKPIT: the live cluster cockpit — a loopback HTTP endpoint
    # on rank 0 serving /metrics, /state, and /events (SSE) for
    # tools/hvd_top.py.  Off by default: disabled it binds nothing and
    # costs nothing.  HOROVOD_COCKPIT_PORT is driver-internal (assigned
    # per formation, like HOROVOD_AUTOPILOT_PORT); 0 with HOROVOD_COCKPIT
    # on means "pick a free loopback port".
    cockpit_enabled: bool = False
    cockpit_port: int = 0

    # Native core selection (TPU-build specific).
    force_pure_python: bool = False

    @staticmethod
    def from_env() -> "Config":
        env = os.environ
        if env.get("HOROVOD_RANK_FROM_JSRUN") == "1":
            # jsrun-placed workers carry OpenMPI/JSM rank env instead of
            # HOROVOD_RANK (reference: js_run's worker-side env mapping).
            from ..runner.js_run import apply_jsrun_rank_env

            apply_jsrun_rank_env()
        return Config(
            rank=get_int("HOROVOD_RANK", 0),
            size=get_int("HOROVOD_SIZE", 1),
            local_rank=get_int("HOROVOD_LOCAL_RANK", 0),
            local_size=get_int("HOROVOD_LOCAL_SIZE", 1),
            cross_rank=get_int("HOROVOD_CROSS_RANK", 0),
            cross_size=get_int("HOROVOD_CROSS_SIZE", 1),
            controller=env.get("HOROVOD_CONTROLLER", "auto").lower(),
            # Same variable names the reference's Gloo rendezvous uses
            # (SURVEY.md §1 control-plane env vars) so launcher scripts match.
            rendezvous_addr=env.get(
                "HOROVOD_GLOO_RENDEZVOUS_ADDR",
                env.get("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1"),
            ),
            rendezvous_port=get_int(
                "HOROVOD_GLOO_RENDEZVOUS_PORT", get_int("HOROVOD_RENDEZVOUS_PORT", 0)
            ),
            fusion_threshold_bytes=get_int(
                "HOROVOD_FUSION_THRESHOLD", DEFAULT_FUSION_THRESHOLD
            ),
            cycle_time_ms=get_float("HOROVOD_CYCLE_TIME", DEFAULT_CYCLE_TIME_MS),
            cache_capacity=get_int("HOROVOD_CACHE_CAPACITY", DEFAULT_CACHE_CAPACITY),
            cache_enabled=get_int("HOROVOD_CACHE_CAPACITY", DEFAULT_CACHE_CAPACITY) > 0,
            autotune=get_bool("HOROVOD_AUTOTUNE", False),
            autotune_log=env.get("HOROVOD_AUTOTUNE_LOG"),
            hierarchical_allreduce=get_bool(
                "HOROVOD_HIERARCHICAL_ALLREDUCE", False
            ),
            wire_compression=get_wire_compression_planes()[0],
            wire_compression_device=get_wire_compression_planes()[1],
            wire_compression_min_bytes=get_int(
                "HOROVOD_WIRE_COMPRESSION_MIN_BYTES", 1 << 16),
            device_schedule=get_device_schedule(),
            data_plane=get_data_plane(),
            hlo_inspect_enabled=get_bool("HOROVOD_HLO_INSPECT", True),
            timeline_path=env.get("HOROVOD_TIMELINE"),
            timeline_mark_cycles=get_bool("HOROVOD_TIMELINE_MARK_CYCLES", False),
            metrics_enabled=get_bool(
                "HOROVOD_METRICS", bool(env.get("HOROVOD_METRICS_FILE"))
            ),
            metrics_file=env.get("HOROVOD_METRICS_FILE"),
            metrics_interval_s=get_float("HOROVOD_METRICS_INTERVAL", 10.0),
            flight_recorder_enabled=get_bool("HOROVOD_FLIGHT_RECORDER", True),
            flight_recorder_slots=get_int("HOROVOD_FLIGHT_RECORDER_SLOTS",
                                          4096),
            postmortem_dir=env.get("HOROVOD_POSTMORTEM_DIR"),
            log_level=env.get("HOROVOD_LOG_LEVEL", "warning").lower(),
            stall_check_enabled=not get_bool("HOROVOD_STALL_CHECK_DISABLE", False),
            stall_warning_s=get_float(
                "HOROVOD_STALL_CHECK_TIME_SECONDS", DEFAULT_STALL_WARNING_S
            ),
            stall_shutdown_s=get_float("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", 0.0),
            elastic_timeout_s=get_float(
                "HOROVOD_ELASTIC_TIMEOUT", DEFAULT_ELASTIC_TIMEOUT_S
            ),
            elastic_enabled=get_bool("HOROVOD_ELASTIC", False),
            migrate_replicas=max(0, get_int("HOROVOD_MIGRATE_REPLICAS", 2)),
            migrate_interval_steps=max(
                1, get_int("HOROVOD_MIGRATE_INTERVAL_STEPS", 1)),
            autopilot_port=get_int("HOROVOD_AUTOPILOT_PORT", 0),
            step_trace_enabled=get_bool("HOROVOD_STEP_TRACE", True),
            step_trace_slots=get_int("HOROVOD_STEP_TRACE_SLOTS", 256),
            cockpit_enabled=get_bool("HOROVOD_COCKPIT", False),
            cockpit_port=get_int("HOROVOD_COCKPIT_PORT", 0),
            force_pure_python=get_bool("HVD_TPU_PURE_PY", False),
        )
