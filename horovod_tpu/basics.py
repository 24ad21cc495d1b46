"""Runtime lifecycle and identity API.

Reference: horovod/common/basics.py — HorovodBasics (init/shutdown/rank/size/
local_rank/..., built-with queries; SURVEY.md §2.4).  Where the reference
loads a per-framework shared library over ctypes, this module drives the
TPU-native core (native C++ when built, pure-Python local core otherwise)
and additionally owns the global device mesh.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

import numpy as np

from .context import HorovodContext
from .utils.env import Config, get_bool
from .utils.logging import get_logger
from .utils import step_watch
from .parallel import mesh as _mesh

log = get_logger()

# jax.distributed runtime state owned by this module.  The runtime is
# process-level: across hvd shutdown/init cycles with unchanged
# (coordinator, size, rank) it is simply reused; an elastic round that
# reassigns any of them tears it down and re-initializes (clearing XLA
# backends first — jax refuses to re-initialize once a backend exists).
_jax_distributed_up = False
_jax_dist_params = None


def init(comm=None, process_sets: Optional[Sequence] = None,
         config: Optional[Config] = None, build_mesh: bool = True) -> None:
    """Initialize Horovod.

    ``comm`` exists for signature parity with the reference (an MPI
    communicator there); passing a list of ranks restricts the world like a
    root communicator split would.  ``process_sets`` pre-registers process
    sets exactly like the reference's ``hvd.init(process_sets=...)``.
    """
    if HorovodContext.initialized():
        return
    # Elastic mode: the driver assigns rank/size per rendezvous round over
    # the coordinator connection before the core can start (SURVEY.md §3.5).
    if config is None and os.environ.get("HOROVOD_ELASTIC") == "1":
        from .elastic import client as _elastic_client

        _elastic_client.ensure_assignment()
    cfg = config or Config.from_env()
    if comm is not None and not isinstance(comm, (list, tuple)):
        raise ValueError(
            "comm must be None or a list of ranks; MPI communicators do not "
            "exist in the TPU build"
        )
    ctx = HorovodContext.init(cfg)

    # Optional multi-host JAX runtime wiring (TPU pods): the launcher sets
    # HOROVOD_JAX_DISTRIBUTED=1 plus coordinator env; analogous to how the
    # reference's launcher passes rendezvous env to Gloo (SURVEY.md §3.4).
    if get_bool("HOROVOD_JAX_DISTRIBUTED", False):  # pragma: no cover - pod only
        import jax

        # Cross-process collectives on the CPU platform (the no-TPU test
        # harness, SURVEY.md §4) need the gloo transport; TPU pods use ICI
        # and must keep the default.
        if "cpu" in str(getattr(jax.config, "jax_platforms", "") or ""):
            try:
                jax.config.update("jax_cpu_collectives_implementation", "gloo")
            except Exception:
                pass
        global _jax_distributed_up, _jax_dist_params
        # The elastic generation epoch participates so every process of a
        # new generation re-initializes together (a survivor must not keep
        # a runtime whose coordination service already saw a peer die).
        params = (os.environ.get("HOROVOD_JAX_COORDINATOR"), cfg.size,
                  cfg.rank, os.environ.get("HOROVOD_ELASTIC_GENERATION"))
        if not (_jax_distributed_up and _jax_dist_params == params):
            if _jax_distributed_up:
                try:
                    jax.distributed.shutdown()
                except Exception as exc:
                    log.warning("jax.distributed shutdown failed: %s", exc)
                _jax_distributed_up = False
                # Cleared backends let initialize() pass its
                # backends_are_initialized() guard.
                from jax._src import api as _jax_api

                _jax_api.clear_backends()
            jax.distributed.initialize(
                coordinator_address=params[0],
                num_processes=cfg.size,
                process_id=cfg.rank,
            )
            _jax_distributed_up = True
            _jax_dist_params = params
        # Which rank is each jax process?  Asked of the runtime, because on
        # a TPU the process index follows the chips, not process_id.
        from jax.experimental import multihost_utils

        pairs = np.asarray(multihost_utils.process_allgather(
            np.asarray([jax.process_index(), cfg.rank], np.int32)))
        _mesh.set_process_ranks(
            {int(p): int(r) for p, r in pairs.reshape(-1, 2)})

    if build_mesh:
        _mesh.build_global_mesh()

    if process_sets:
        from .process_sets import add_process_set

        for ps in process_sets:
            add_process_set(ps)


def shutdown() -> None:
    # The jax.distributed runtime deliberately survives shutdown: it is
    # process-level, and the next init reuses it when (coordinator, size,
    # rank) are unchanged or re-initializes when they differ (elastic).
    # Step watches first: their thread reads the core's cycle count.
    step_watch.close_all()
    HorovodContext.shutdown()
    _mesh.reset()


def is_initialized() -> bool:
    return HorovodContext.initialized()


def initialized() -> bool:  # reference alias
    return HorovodContext.initialized()


def rank() -> int:
    return HorovodContext.instance().core.rank()


def size() -> int:
    return HorovodContext.instance().core.size()


def local_rank() -> int:
    return HorovodContext.instance().cfg.local_rank


def local_size() -> int:
    return HorovodContext.instance().cfg.local_size


def cross_rank() -> int:
    return HorovodContext.instance().cfg.cross_rank


def cross_size() -> int:
    return HorovodContext.instance().cfg.cross_size


def is_homogeneous() -> bool:
    """True if every host runs the same number of ranks."""
    ctx = HorovodContext.instance()
    return ctx.cfg.size % max(ctx.cfg.local_size, 1) == 0


def num_devices() -> int:
    """Local JAX device count (TPU-build extension)."""
    import jax

    return jax.local_device_count()


# -- metrics ----------------------------------------------------------------

def metrics() -> dict:
    """Local metrics-registry snapshot: counters (cycle occupancy, fusion
    efficiency, stall warnings) and power-of-two-bucket histograms
    (negotiation wait, ring hop latency, shm fence wait).  On rank 0 the
    dict also carries ``cluster`` (per-rank snapshots aggregated by the
    coordinator) and ``straggler_report``.  A non-empty dump additionally
    carries ``plane_counters`` — the gspmd plane's Python-side
    selection/demotion counters (ops/gspmd_plane.py), rendered by
    ``metrics_prometheus()`` as ``hvd_plane_demotions_total{reason=...}``
    / ``hvd_plane_selected_total{plane=...}``.  Empty when the metrics
    plane is disabled or the backend has no native registry."""
    dump = HorovodContext.instance().core.metrics()
    if dump:
        try:
            from .ops.gspmd_plane import plane_counters
            pc = plane_counters()
        except Exception:
            pc = {}
        if pc:
            dump["plane_counters"] = pc
    return dump


def metrics_prometheus() -> str:
    """The same snapshot rendered in Prometheus text exposition format
    (``hvd_*`` families; see docs/observability.md for the naming scheme)."""
    from .utils.metrics import render_prometheus

    return render_prometheus(metrics())


def flight_record() -> dict:
    """Snapshot of this rank's flight-recorder ring — the always-on event
    black box (rendezvous, cycle sends/recvs, verdicts, ring hops, shm
    fences, aggregate frames, fault trips, aborts).  Keys: ``rank``,
    ``host``, ``slots``, ``dropped``, ``types`` (event-type legend) and
    ``events`` as ``[ts_us, seq, type, tid, a, b]`` rows, oldest first.
    Empty when HOROVOD_FLIGHT_RECORDER=off or the backend has no native
    recorder.  On abort the same payload is written per rank under
    HOROVOD_POSTMORTEM_DIR and merged by the coordinator into
    ``postmortem.json`` (render with ``tools/postmortem.py``)."""
    return HorovodContext.instance().core.flight_record()


def step_trace() -> dict:
    """Snapshot of this rank's causal step-trace ring — the fifth
    observability pillar.  Keys: ``rank``, ``world``, ``phases`` (the
    breakdown order: negotiation_wait / fusion / ring / fence / idle),
    ``steps`` as ``[step, start_us, end_us, <5 phase us>]`` rows, and on
    rank 0 ``fleet`` — per-step cross-rank phase sums with
    ``dominant_phase`` / ``dominant_rank`` attribution.  Empty when
    HOROVOD_STEP_TRACE=off or the backend has no native tracer.  The same
    payload is written to HOROVOD_POSTMORTEM_DIR as
    ``steptrace.<rank>.json`` at shutdown/abort for
    ``tools/critical_path.py``."""
    return HorovodContext.instance().core.step_trace()


def fleet_history() -> dict:
    """The coordinator's multi-resolution fleet history + anomaly log —
    the sixth observability pillar (fleet telemetry, protocol v11).
    Keys: ``schema`` (``fleethistory-v1``), ``columns`` (the sample row
    legend: ``[ts_us, step_p99_us, neg_p99_us, goodput_ppm,
    wire_ratio_ppm, steps]``), ``tiers`` (1 s / 10 s / 60 s downsampled
    rings, each ``{"period_s", "samples"}``) and ``anomalies`` (the
    streaming sentinel's log, newest last, each naming the series, the
    dominant rank and the z-score).  Meaningful on rank 0 (the only rank
    that ticks); empty when HOROVOD_FLEET_TELEMETRY=off or the backend
    has no native plane.  Fleet HISTOGRAMS (true cross-rank merges) live
    in ``metrics()["fleet"]``; this call serves their time axis."""
    return HorovodContext.instance().core.fleet_history()


# -- timeline ---------------------------------------------------------------

def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    HorovodContext.instance().core.start_timeline(file_path, mark_cycles)


def stop_timeline() -> None:
    HorovodContext.instance().core.stop_timeline()


def start_device_trace(logdir: str) -> None:
    """Start the XLA profiler (TensorBoard trace) — the on-device half of
    observability: the host timeline covers NEGOTIATE/data-plane phases,
    this covers the compiled XLA programs on the chip (SURVEY.md §5:
    timeline hand-off into jax.profiler).  The eager spine's ``hvd_*`` spans
    and a compiled step's ``hvd_*`` scopes land in the same trace, on its
    clock (docs/observability.md, "Names on the profiler's clock")."""
    import jax

    jax.profiler.start_trace(logdir)


def stop_device_trace() -> None:
    import jax

    jax.profiler.stop_trace()


# -- build-configuration queries (reference API parity) ---------------------

def mpi_threads_supported() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    # The socket controller fills Gloo's role (MPI-free CPU control+data
    # plane); report it under the reference's query for script parity.
    return True


def gloo_built() -> bool:
    return True


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def tpu_built() -> bool:
    """TPU-build extension: the XLA/ICI data plane is always available."""
    return True


def native_core_built() -> bool:
    """True if the C++ core library is importable."""
    try:
        from . import _core  # noqa: F401

        return True
    except Exception:
        return False
