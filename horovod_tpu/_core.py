"""ctypes binding over the native core (libhvd_tpu_core.so).

Python analog of the reference's HorovodBasics ctypes facade
(horovod/common/basics.py; SURVEY.md §2.4), except the library here is the
TPU-native core (horovod_tpu/cpp/) rather than a per-framework build.  The
library is built on demand with `make` the first time it is needed.
"""

from __future__ import annotations

import ctypes
import fcntl
import json
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

from .runtime import PROTOCOL_VERSION, CoreBackend, FusedResponse, TensorEntry
from .utils.env import Config, get_bool
from .wire import DataType, OpType, ReduceOp, wire_dtype

_CPP_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cpp")
_LIB_PATH = os.path.join(_CPP_DIR, "libhvd_tpu_core.so")

_LOG_LEVELS = {"trace": 0, "debug": 1, "info": 2, "warning": 3, "error": 4,
               "fatal": 5}

_build_lock = threading.Lock()
_lib = None


def _load_library() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        # Always invoke make: it's incremental (no-op when up to date) and
        # guarantees source edits are never shadowed by a stale .so.  A
        # failed build is an error even when an older library is lying
        # around: that library is not what the sources say.  One build at a
        # time across processes: the ranks of a job start together, and on
        # a fresh checkout concurrent makes in one directory hand some rank
        # a half-linked library.
        with open(os.path.join(_CPP_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                subprocess.run(
                    ["make", "-s", f"-j{min(8, os.cpu_count() or 1)}"],
                    cwd=_CPP_DIR, check=True, capture_output=True)
            except subprocess.CalledProcessError as exc:
                raise RuntimeError(
                    f"native core build failed in {_CPP_DIR}: "
                    f"{exc.stderr.decode(errors='replace')[-2000:]}") from exc
        lib = ctypes.CDLL(_LIB_PATH)
        _declare(lib)
        _lib = lib
        return lib


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.hvd_init.restype = c.c_int
    lib.hvd_init.argtypes = [
        c.c_int, c.c_int, c.c_int, c.c_int,        # rank size local_rank local_size
        c.c_char_p, c.c_char_p, c.c_int,           # controller addr port
        c.c_double, c.c_longlong, c.c_int, c.c_int,  # cycle fusion cache autotune
        c.c_char_p, c.c_int, c.c_int,              # autotune_log hierarchical wire_comp
        c.c_int,                                   # qdev_comp (-1 = no device plane)
        c.c_int,                                   # qdev_sched (-1 = ring-only plane)
        c.c_int, c.c_char_p, c.c_double,           # metrics metrics_file interval
        c.c_char_p, c.c_int,                       # timeline mark
        c.c_double, c.c_double, c.c_int,           # stall_warn stall_shutdown log
        c.c_int, c.c_int, c.c_char_p,              # flight_on flight_slots postmortem_dir
        c.c_int,                                   # autopilot_port (0 = off)
        c.c_int, c.c_int,                          # step_trace_on step_trace_slots
        c.c_int,                                   # data_plane (-1 = no gspmd mesh)
    ]
    lib.hvd_shutdown.restype = c.c_int
    lib.hvd_is_initialized.restype = c.c_int
    lib.hvd_rank.restype = c.c_int
    lib.hvd_cycle_count.restype = c.c_longlong
    lib.hvd_cycle_count.argtypes = []
    lib.hvd_size.restype = c.c_int
    lib.hvd_local_rank.restype = c.c_int
    lib.hvd_local_size.restype = c.c_int
    lib.hvd_enqueue.restype = c.c_longlong
    lib.hvd_enqueue.argtypes = [
        c.c_longlong, c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_longlong,
        c.POINTER(c.c_longlong), c.c_int, c.c_int, c.c_int, c.c_double,
        c.c_double, c.POINTER(c.c_longlong), c.c_int, c.c_int, c.c_char_p,
        c.c_int,
    ]
    lib.hvd_pop_response.restype = c.c_int
    lib.hvd_pop_response.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.hvd_allreduce_buffer.restype = c.c_int
    lib.hvd_allreduce_buffer.argtypes = [
        c.c_longlong, c.c_void_p, c.c_longlong, c.c_int, c.c_int, c.c_int]
    lib.hvd_reducescatter_buffer.restype = c.c_int
    lib.hvd_reducescatter_buffer.argtypes = [
        c.c_longlong, c.c_void_p, c.c_longlong, c.c_int, c.c_int, c.c_int,
        c.POINTER(c.c_longlong), c.c_int]
    lib.hvd_allgather_buffer.restype = c.c_int
    lib.hvd_allgather_buffer.argtypes = [
        c.c_longlong, c.c_void_p, c.c_longlong, c.c_int,
        c.POINTER(c.c_void_p), c.POINTER(c.c_longlong),
        c.POINTER(c.c_longlong), c.c_int, c.POINTER(c.c_int)]
    lib.hvd_broadcast_buffer.restype = c.c_int
    lib.hvd_broadcast_buffer.argtypes = [
        c.c_longlong, c.c_void_p, c.c_longlong, c.c_int, c.c_int]
    lib.hvd_alltoall_buffer.restype = c.c_int
    lib.hvd_alltoall_buffer.argtypes = [
        c.c_longlong, c.c_void_p, c.POINTER(c.c_longlong), c.c_int,
        c.c_longlong, c.c_int, c.POINTER(c.c_void_p),
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong), c.POINTER(c.c_int)]
    lib.hvd_barrier.restype = c.c_int
    lib.hvd_barrier.argtypes = [c.c_longlong, c.c_int]
    lib.hvd_free.argtypes = [c.c_void_p]
    lib.hvd_add_process_set2.restype = c.c_int
    lib.hvd_add_process_set2.argtypes = [
        c.POINTER(c.c_int), c.c_int, c.c_double]
    lib.hvd_remove_process_set.restype = c.c_int
    lib.hvd_remove_process_set.argtypes = [c.c_int]
    lib.hvd_process_set_ranks.restype = c.c_int
    lib.hvd_process_set_ranks.argtypes = [c.c_int, c.POINTER(c.c_int), c.c_int]
    lib.hvd_negotiation_stats.argtypes = [
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong)]
    lib.hvd_data_plane_stats2.argtypes = [
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong),
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong)]
    lib.hvd_ctrl_plane_stats.argtypes = [
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong),
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong)]
    lib.hvd_start_timeline.argtypes = [c.c_char_p, c.c_int]
    lib.hvd_stop_timeline.argtypes = []
    lib.hvd_metrics_dump.restype = c.c_int
    lib.hvd_metrics_dump.argtypes = [c.c_char_p, c.c_int]
    lib.hvd_last_error.restype = c.c_char_p
    lib.hvd_flight_record.restype = c.c_int
    lib.hvd_flight_record.argtypes = [c.c_char_p, c.c_int]
    lib.hvd_step_trace.restype = c.c_int
    lib.hvd_step_trace.argtypes = [c.c_char_p, c.c_int]
    lib.hvd_fleet_history.restype = c.c_int
    lib.hvd_fleet_history.argtypes = [c.c_char_p, c.c_int]
    lib.hvd_fault_spec_check.restype = c.c_char_p
    lib.hvd_fault_spec_check.argtypes = [c.c_char_p]
    lib.hvd_device_plane_note.restype = None
    lib.hvd_device_plane_note.argtypes = [c.c_longlong, c.c_longlong]
    lib.hvd_device_plane_stats.restype = None
    lib.hvd_device_plane_stats.argtypes = [
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong)]
    lib.hvd_autotune_qdev.restype = c.c_int
    lib.hvd_autotune_qdev.argtypes = []
    lib.hvd_autotune_qsched.restype = c.c_int
    lib.hvd_autotune_qsched.argtypes = []
    lib.hvd_autotune_plane.restype = c.c_int
    lib.hvd_autotune_plane.argtypes = []
    lib.hvd_migrate_note.restype = None
    lib.hvd_migrate_note.argtypes = [c.c_int, c.c_longlong, c.c_int]
    lib.hvd_elastic_generation_set.restype = None
    lib.hvd_elastic_generation_set.argtypes = [c.c_longlong]
    lib.hvd_gspmd_plane_note.restype = None
    lib.hvd_gspmd_plane_note.argtypes = [
        c.c_longlong, c.c_longlong, c.c_longlong]
    lib.hvd_gspmd_plane_stats.restype = None
    lib.hvd_gspmd_plane_stats.argtypes = [
        c.POINTER(c.c_longlong), c.POINTER(c.c_longlong)]
    lib.hvd_step_trace_note_plane.restype = None
    lib.hvd_step_trace_note_plane.argtypes = [c.c_int]


class NativeCoreError(RuntimeError):
    pass


def check_fault_spec(spec: str) -> str:
    """Validate a HOROVOD_FAULT_INJECT spec against the native parser.

    Returns "" when well-formed, else the same actionable message
    hvd.init() would fail with.
    """
    msg = _load_library().hvd_fault_spec_check(spec.encode())
    return msg.decode() if msg else ""


class NativeCore(CoreBackend):
    """The C++ core as a CoreBackend: negotiation, fusion, caching, stall
    inspection and the host data plane all run natively; Python only packs
    fusion buffers and runs device-side XLA programs."""

    name = "native"
    # Per-process-set data channels exist in the socket controller, so
    # responses for different sets may run on concurrent executor lanes.
    parallel_lanes = True

    def __init__(self):
        self._lib = _load_library()
        self._cfg: Optional[Config] = None
        self._seq_tls = threading.local()
        # Reused across pop_response calls (the executor polls every 50ms;
        # a fresh 1MB allocation per poll would churn ~20MB/s at idle).
        self._resp_cap = 1 << 16
        self._resp_buf = ctypes.create_string_buffer(self._resp_cap)

    # -- lifecycle ----------------------------------------------------------
    def start(self, cfg: Config) -> None:
        self._cfg = cfg
        controller = cfg.controller
        if controller in ("auto",):
            controller = "socket" if cfg.size > 1 else "local"
        # Device-plane codec: 0=none, 1=int8, 2=int4 from config;
        # -1 pins the autotuner's qdev arm when no jax device plane can
        # exist here.
        qdev = {"none": 0, "int8": 1, "int4": 2}.get(
            getattr(cfg, "wire_compression_device", "none"), 0)
        # Device-ring schedule: 0=ring, 1=bidi, 2=torus ("auto" resolves
        # from the world size); -1 pins the autotuner's schedule arm when
        # only the unidirectional ring is feasible (or no device plane).
        try:
            from .ops.collectives import resolve_device_schedule
            resolved = resolve_device_schedule(
                cfg.size, getattr(cfg, "device_schedule", "auto"))
        except Exception:
            resolved = "ring"
        qsched = {"ring": 0, "bidi": 1, "torus": 2}.get(resolved, 0)
        if cfg.size < 4:
            qsched = -1  # bidi needs chunks >= 2, torus needs factors
        # In-jit data plane: 0=eager, 1=gspmd from config ("auto" starts
        # eager and lets the tuner flip it); -1 pins the autotuner's plane
        # arm when no gspmd mesh can exist (no jax, a single device) or the
        # quantized device codec owns the traced reduction — the
        # compose-or-demote rule of ops/gspmd_plane.py.
        plane = {"auto": 0, "eager": 0, "gspmd": 1}.get(
            getattr(cfg, "data_plane", "auto"), 0)
        try:
            import jax  # noqa: F401
        except Exception:
            qdev = -1
            qsched = -1
            plane = -1
        else:
            if qdev > 0:
                plane = -1
            elif get_bool("HOROVOD_JAX_DISTRIBUTED", False):
                # jax.device_count() would initialize the backend here,
                # and basics.init() has not yet run
                # jax.distributed.initialize() (which must come first on
                # pods).  A distributed world's mesh spans >= 2 devices
                # whenever the world does, so pin from the world size.
                if cfg.size < 2:
                    plane = -1
            else:
                try:
                    if jax.device_count() < 2:
                        plane = -1
                except Exception:
                    plane = -1
        rc = self._lib.hvd_init(
            cfg.rank, cfg.size, cfg.local_rank, cfg.local_size,
            controller.encode(), cfg.rendezvous_addr.encode(),
            cfg.rendezvous_port, cfg.cycle_time_ms,
            cfg.fusion_threshold_bytes, cfg.cache_capacity,
            1 if cfg.autotune else 0,
            (cfg.autotune_log or "").encode(),
            1 if cfg.hierarchical_allreduce else 0,
            {"none": 0, "bf16": 1, "int8": 2, "int4": 3}.get(
                cfg.wire_compression, 0),
            qdev, qsched,
            1 if cfg.metrics_enabled else 0,
            (cfg.metrics_file or "").encode(),
            cfg.metrics_interval_s,
            (cfg.timeline_path or "").encode(),
            1 if cfg.timeline_mark_cycles else 0,
            cfg.stall_warning_s if cfg.stall_check_enabled else 0.0,
            cfg.stall_shutdown_s,
            _LOG_LEVELS.get(cfg.log_level, 3),
            1 if cfg.flight_recorder_enabled else 0,
            cfg.flight_recorder_slots,
            (cfg.postmortem_dir or "").encode(),
            cfg.autopilot_port,
            1 if cfg.step_trace_enabled else 0,
            cfg.step_trace_slots,
            plane,
        )
        if rc != 0:
            raise NativeCoreError(
                f"native core init failed (rc={rc}, control protocol "
                f"v{PROTOCOL_VERSION}): {self._last_error()}")
        # Publish the elastic generation the driver assigned us (0 for
        # non-elastic jobs) as the hvd_elastic_generation gauge.
        try:
            gen = int(os.environ.get("HOROVOD_ELASTIC_GENERATION", "0"))
        except ValueError:
            gen = 0
        self._lib.hvd_elastic_generation_set(gen)
        if qdev >= 0:
            # Mirror quantized-collective byte deltas into the native
            # metrics registry (hvd.metrics() / Prometheus exposure).
            try:
                from .ops import quantize as _qz
            except Exception:
                pass
            else:
                note = self._lib.hvd_device_plane_note
                _qz.set_native_byte_sink(
                    lambda raw, enc: note(int(raw), int(enc)))
        # Mirror each gspmd trace's HLO collective inventory into the
        # native metrics registry (hvd.metrics() / Prometheus / flight
        # type 16) — once per trace, never per step.
        try:
            from .ops import hlo_inspect as _hi
        except Exception:
            pass
        else:
            gnote = self._lib.hvd_gspmd_plane_note
            _hi.set_native_sink(
                lambda ops, raw, wire: gnote(int(ops), int(raw), int(wire)))

    def step_trace_note_plane(self, plane: int) -> None:
        """Tag the step-trace ring with the data plane running the steps
        (-1 unknown, 0 eager, 1 gspmd)."""
        self._lib.hvd_step_trace_note_plane(int(plane))

    def shutdown(self) -> None:
        if self._lib.hvd_is_initialized():
            self._lib.hvd_shutdown()

    def _last_error(self) -> str:
        msg = self._lib.hvd_last_error()
        return msg.decode() if msg else "unknown"

    def rank(self) -> int:
        return self._lib.hvd_rank()

    def size(self) -> int:
        return self._lib.hvd_size()

    # -- control plane ------------------------------------------------------
    def enqueue(self, entry: TensorEntry) -> None:
        shape = (ctypes.c_longlong * max(len(entry.array.shape), 1))(
            *entry.array.shape)
        if entry.splits is not None:
            splits = (ctypes.c_longlong * len(entry.splits))(
                *[int(s) for s in entry.splits])
            nsplits = len(entry.splits)
        else:
            splits = None
            nsplits = 0
        rc = self._lib.hvd_enqueue(
            entry.handle, entry.name.encode(), int(entry.op),
            int(entry.dtype), int(entry.reduce_op), entry.array.nbytes,
            shape, len(entry.array.shape), entry.process_set_id,
            entry.root_rank, entry.prescale_factor, entry.postscale_factor,
            splits, nsplits, 1 if entry.device_array is not None else 0,
            entry.group_key.encode(), entry.group_size)
        if rc == -2:
            raise ValueError(f"duplicate in-flight tensor name {entry.name!r}")
        if rc != 0:
            raise NativeCoreError(f"enqueue failed rc={rc}")

    def pop_response(self, timeout: float) -> Optional[FusedResponse]:
        n = self._lib.hvd_pop_response(self._resp_buf, self._resp_cap,
                                       int(timeout * 1000))
        while n == -2:  # buffer too small: the response stays queued; grow
            self._resp_cap *= 4
            self._resp_buf = ctypes.create_string_buffer(self._resp_cap)
            n = self._lib.hvd_pop_response(self._resp_buf, self._resp_cap, 0)
        if n <= 0:
            return None
        obj = json.loads(self._resp_buf.raw[:n].decode())
        self.set_current_seq(obj.get("seq", -1))
        return FusedResponse(
            op=OpType(obj["op"]),
            dtype=DataType(obj["dtype"]),
            process_set_id=obj["psid"],
            handles=list(obj["handles"]),
            error=obj["error"] or None,
            counts=obj.get("counts"),
            last_joined=obj.get("last_joined", -1),
            seq=obj.get("seq", -1),
            device=bool(obj.get("device", 0)),
        )

    def set_current_seq(self, seq: int) -> None:
        # thread-local: each executor lane tags its own collective's
        # frames (the C++ side mirrors this with a thread_local).
        self._seq_tls.seq = int(seq)

    @property
    def _current_seq(self) -> int:
        return getattr(self._seq_tls, "seq", -1)

    # -- process sets -------------------------------------------------------
    def add_process_set(self, ranks: Sequence[int],
                        weight: float = 1.0) -> int:
        arr = (ctypes.c_int * len(ranks))(*[int(r) for r in ranks])
        psid = self._lib.hvd_add_process_set2(arr, len(ranks), float(weight))
        if psid < 0:
            raise NativeCoreError("add_process_set failed")
        return psid

    def remove_process_set(self, process_set_id: int) -> None:
        self._lib.hvd_remove_process_set(process_set_id)

    def process_set_ranks(self, process_set_id: int) -> List[int]:
        cap = max(self.size(), 1)
        out = (ctypes.c_int * cap)()
        n = self._lib.hvd_process_set_ranks(process_set_id, out, cap)
        if n < 0:
            raise ValueError(f"unknown process set id {process_set_id}")
        return [out[i] for i in range(n)]

    # -- host data plane ----------------------------------------------------
    def _check(self, rc: int, what: str) -> None:
        if rc != 0:
            from .exceptions import HorovodInternalError

            raise HorovodInternalError(
                f"{what} failed (rc={rc}): {self._last_error()}")

    def allreduce_buffer(self, buf: np.ndarray, psid: int,
                         reduce_op: ReduceOp) -> np.ndarray:
        buf = np.ascontiguousarray(buf)
        rc = self._lib.hvd_allreduce_buffer(
            self._current_seq, buf.ctypes.data_as(ctypes.c_void_p), buf.size,
            int(wire_dtype(buf.dtype)), int(reduce_op), psid)
        self._check(rc, "allreduce")
        return buf

    def reducescatter_buffer(self, buf: np.ndarray, psid: int,
                             reduce_op: ReduceOp, slice_counts) -> np.ndarray:
        """In-place ring reduce-scatter: on return this rank's slice
        (slice_counts[my_pos] elements at its offset) is fully reduced;
        the rest of buf is unspecified."""
        buf = np.ascontiguousarray(buf)
        arr = (ctypes.c_longlong * len(slice_counts))(*slice_counts)
        rc = self._lib.hvd_reducescatter_buffer(
            self._current_seq, buf.ctypes.data_as(ctypes.c_void_p), buf.size,
            int(wire_dtype(buf.dtype)), int(reduce_op), psid, arr,
            len(slice_counts))
        self._check(rc, "reducescatter")
        return buf

    def allgather_buffer(self, buf: np.ndarray, psid: int):
        buf = np.ascontiguousarray(buf)
        d0 = buf.shape[0] if buf.ndim else 1
        row_bytes = (buf.nbytes // d0) if d0 > 0 else int(
            np.prod(buf.shape[1:], dtype=np.int64) * buf.itemsize) or buf.itemsize
        out_ptr = ctypes.c_void_p()
        out_len = ctypes.c_longlong()
        cap = max(self.size(), 1)
        counts = (ctypes.c_longlong * cap)()
        n_counts = ctypes.c_int()
        rc = self._lib.hvd_allgather_buffer(
            self._current_seq, buf.ctypes.data_as(ctypes.c_void_p), buf.nbytes,
            psid, ctypes.byref(out_ptr), ctypes.byref(out_len), counts, cap,
            ctypes.byref(n_counts))
        self._check(rc, "allgather")
        try:
            # One copy, not two, and no per-length ctypes type-cache
            # growth: memmove the C buffer straight into a numpy-owned
            # array (the C side frees right after).
            flat = np.empty(out_len.value // buf.itemsize, dtype=buf.dtype)
            if out_len.value:
                ctypes.memmove(flat.ctypes.data, out_ptr, out_len.value)
        finally:
            self._lib.hvd_free(out_ptr)
        rows = flat.size // (row_bytes // buf.itemsize) if row_bytes else 0
        stacked = flat.reshape(rows, -1) if rows else flat.reshape(0, 1)
        row_counts = np.array(
            [counts[i] // row_bytes for i in range(n_counts.value)],
            dtype=np.int64)
        return stacked, row_counts

    def broadcast_buffer(self, buf: np.ndarray, root_rank: int,
                         psid: int) -> np.ndarray:
        buf = np.ascontiguousarray(buf).copy()
        rc = self._lib.hvd_broadcast_buffer(
            self._current_seq, buf.ctypes.data_as(ctypes.c_void_p), buf.nbytes,
            root_rank, psid)
        self._check(rc, "broadcast")
        return buf

    def alltoall_buffer(self, buf: np.ndarray, splits: np.ndarray,
                        psid: int):
        buf = np.ascontiguousarray(buf)
        d0 = buf.shape[0] if buf.ndim else 1
        row_bytes = (buf.nbytes // d0) if d0 > 0 else buf.itemsize
        csplits = (ctypes.c_longlong * len(splits))(*[int(s) for s in splits])
        out_ptr = ctypes.c_void_p()
        out_len = ctypes.c_longlong()
        cap = max(len(splits), 1)
        recv = (ctypes.c_longlong * cap)()
        n_recv = ctypes.c_int()
        rc = self._lib.hvd_alltoall_buffer(
            self._current_seq, buf.ctypes.data_as(ctypes.c_void_p), csplits,
            len(splits), row_bytes, psid, ctypes.byref(out_ptr),
            ctypes.byref(out_len), recv, ctypes.byref(n_recv))
        self._check(rc, "alltoall")
        try:
            # One copy, not two, and no per-length ctypes type-cache
            # growth: memmove the C buffer straight into a numpy-owned
            # array (the C side frees right after).
            flat = np.empty(out_len.value // buf.itemsize, dtype=buf.dtype)
            if out_len.value:
                ctypes.memmove(flat.ctypes.data, out_ptr, out_len.value)
        finally:
            self._lib.hvd_free(out_ptr)
        recv_splits = np.array([recv[i] for i in range(n_recv.value)],
                               dtype=np.int64)
        total_rows = int(recv_splits.sum())
        out = flat.reshape(total_rows, -1) if total_rows else flat.reshape(0, 1)
        return out, recv_splits

    def barrier(self, process_set_id: int) -> None:
        rc = self._lib.hvd_barrier(self._current_seq, process_set_id)
        self._check(rc, "barrier")

    # -- observability ------------------------------------------------------
    def negotiation_stats(self) -> dict:
        """Cumulative negotiation ctrl-channel payload bytes for this rank
        (the response-cache fast path's measurable effect: hits travel as
        16-byte (id, handle) pairs instead of full request metadata)."""
        sent = ctypes.c_longlong()
        recv = ctypes.c_longlong()
        self._lib.hvd_negotiation_stats(ctypes.byref(sent),
                                        ctypes.byref(recv))
        return {"ctrl_sent": sent.value, "ctrl_recv": recv.value}

    def ctrl_plane_stats(self) -> dict:
        """Cumulative negotiation ctrl-plane frame and payload-byte counters
        for this rank.  On the coordinator, ctrl_msgs_recv per cycle is the
        leader-tree (HOROVOD_CONTROL_TREE, protocol v9) acceptance metric:
        flat mode receives one frame per worker per cycle, tree mode one per
        local child plus one aggregate per remote host."""
        msgs_sent = ctypes.c_longlong()
        msgs_recv = ctypes.c_longlong()
        bytes_sent = ctypes.c_longlong()
        bytes_recv = ctypes.c_longlong()
        self._lib.hvd_ctrl_plane_stats(
            ctypes.byref(msgs_sent), ctypes.byref(msgs_recv),
            ctypes.byref(bytes_sent), ctypes.byref(bytes_recv))
        return {"ctrl_msgs_sent": msgs_sent.value,
                "ctrl_msgs_recv": msgs_recv.value,
                "ctrl_bytes_sent": bytes_sent.value,
                "ctrl_bytes_recv": bytes_recv.value}

    def data_plane_stats(self) -> dict:
        """Cumulative host-data-plane bytes sent by this rank, split by
        locality: to ranks on this host vs. across hosts.  The hierarchical
        allreduce's measurable effect is a shrinking cross-host share; wire
        compression's is wire bytes dropping below the raw (pre-codec)
        bytes, which the data_raw_* counters track.  device_raw /
        device_encoded are the analogous pair for the device plane's
        quantized in-jit ring (HOROVOD_WIRE_COMPRESSION=device=int8);
        gspmd_raw / gspmd_wire are the gspmd plane's — analytic payload
        vs. wire bytes of the compiler-inserted collectives inventoried
        at trace time (ops/hlo_inspect.py)."""
        local = ctypes.c_longlong()
        xhost = ctypes.c_longlong()
        raw_local = ctypes.c_longlong()
        raw_xhost = ctypes.c_longlong()
        self._lib.hvd_data_plane_stats2(
            ctypes.byref(local), ctypes.byref(xhost),
            ctypes.byref(raw_local), ctypes.byref(raw_xhost))
        dev_raw = ctypes.c_longlong()
        dev_enc = ctypes.c_longlong()
        self._lib.hvd_device_plane_stats(ctypes.byref(dev_raw),
                                         ctypes.byref(dev_enc))
        gspmd_raw = ctypes.c_longlong()
        gspmd_wire = ctypes.c_longlong()
        self._lib.hvd_gspmd_plane_stats(ctypes.byref(gspmd_raw),
                                        ctypes.byref(gspmd_wire))
        return {"data_sent_local": local.value,
                "data_sent_xhost": xhost.value,
                "data_raw_local": raw_local.value,
                "data_raw_xhost": raw_xhost.value,
                "device_raw": dev_raw.value,
                "device_encoded": dev_enc.value,
                "gspmd_raw": gspmd_raw.value,
                "gspmd_wire": gspmd_wire.value}

    def cycle_count(self) -> Optional[int]:
        """Cycles of the native background loop since init, counted whether
        or not the metrics plane is on: a second clock, in a C++ thread, for
        ``hvd.StepWatch``."""
        n = self._lib.hvd_cycle_count()
        return n if n >= 0 else None

    @staticmethod
    def _json_dump(dump) -> dict:
        """Call a native ``(buf, cap) -> length`` JSON dump, growing the
        buffer while it answers -2 (too small); {} when it has nothing."""
        cap = 1 << 16
        buf = ctypes.create_string_buffer(cap)
        n = dump(buf, cap)
        while n == -2:
            cap *= 4
            buf = ctypes.create_string_buffer(cap)
            n = dump(buf, cap)
        if n <= 0:
            return {}
        return json.loads(buf.raw[:n].decode())

    def metrics(self) -> dict:
        """Local metrics registry as a dict (counters + power-of-two-bucket
        histograms); on the coordinator the dump also carries the cluster
        view and the last straggler report."""
        return self._json_dump(self._lib.hvd_metrics_dump)

    def migrate_note(self, phase: int, nbytes: int,
                     source_rank: int = -1) -> None:
        """Record one elastic-migration phase natively: the migrate
        counters, a type-14 flight event, and a MIGRATE timeline instant."""
        self._lib.hvd_migrate_note(int(phase), int(nbytes), int(source_rank))

    def flight_record(self) -> dict:
        """Snapshot of this rank's flight-recorder ring (the always-on event
        black box): {"rank", "host", "slots", "dropped", "types", "events"}
        where events are [ts_us, seq, type, tid, a, b] rows, oldest first.
        {} when the recorder is off (HOROVOD_FLIGHT_RECORDER=off)."""
        return self._json_dump(self._lib.hvd_flight_record)

    def step_trace(self) -> dict:
        """Snapshot of this rank's causal step-trace ring: {"schema",
        "rank", "world", "phases", "steps", "fleet"} where steps are
        [step, start_us, end_us, <5 phase us>] rows and fleet (rank 0
        only) carries per-step cross-rank sums with dominant_phase /
        dominant_rank attribution.  {} when tracing is off
        (HOROVOD_STEP_TRACE=off)."""
        return self._json_dump(self._lib.hvd_step_trace)

    def fleet_history(self) -> dict:
        """The coordinator's multi-resolution fleet history + anomaly log
        (fleethistory-v1): {"schema", "columns", "tiers", "anomalies"}
        where tiers are {"period_s", "samples"} rings of
        [ts_us, step_p99_us, neg_p99_us, goodput_ppm, wire_ratio_ppm,
        steps] rows and anomalies is the sentinel's log, newest last.
        {} when the plane is off (HOROVOD_FLEET_TELEMETRY=off) or on
        non-coordinator ranks before any tick."""
        return self._json_dump(self._lib.hvd_fleet_history)

    def start_timeline(self, path: str, mark_cycles: bool) -> None:
        self._lib.hvd_start_timeline(path.encode(), 1 if mark_cycles else 0)

    def stop_timeline(self) -> None:
        self._lib.hvd_stop_timeline()
