"""Global Horovod context: handle table, executor thread, host data plane.

Reference analogs (SURVEY.md §2.1/§3.2): HorovodGlobalState (global_state.h),
HandleManager (torch/handle_manager.cc), the ops layer's fuse/unfuse logic
(ops/collective_operations.cc — MemcpyInFusionBuffer/MemcpyOutFusionBuffer)
and op execution (ops/operation_manager.cc).

The executor thread pops negotiated ``FusedResponse``s from the core backend
and runs the data plane:

- host arrays (numpy) → the core's fused host collectives (identity at np=1,
  TCP in multi-process mode),
- results are converted back to the framework type the caller handed in
  (JAX array in → JAX array out).

For device-resident SPMD collectives inside ``jit`` see
``horovod_tpu.ops.collectives`` — those never pass through this queue; they
compile straight to XLA collectives over ICI.
"""

from __future__ import annotations

import itertools
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from .exceptions import HorovodInternalError
from .ops.device_plane import DevicePlane
from .runtime import CoreBackend, FusedResponse, PyLocalCore, TensorEntry
from .utils.env import Config, get_bool
from .utils.logging import get_logger
from .wire import (DataType, OpType, ReduceOp, numpy_dtype,
                   validate_alltoall_splits, wire_dtype)

log = get_logger()

# Framework bindings register cleanup hooks here (torch/mpi_ops.py sweeps
# its handle table) so shutdown — including the fast-abort path after a
# peer failure — releases their bookkeeping: a post-abort re-init must not
# see stale in-place write-back targets from the dead job.
_shutdown_callbacks: List = []


def register_shutdown_callback(fn) -> None:
    """Register ``fn`` to run during :meth:`HorovodContext.shutdown`.

    Callbacks run after the core is down and pending handles are failed;
    exceptions are logged, never propagated (shutdown must always finish).
    Registration is idempotent by identity."""
    if fn not in _shutdown_callbacks:
        _shutdown_callbacks.append(fn)


_INT_TYPES = (
    DataType.UINT8, DataType.INT8, DataType.UINT16, DataType.INT16,
    DataType.INT32, DataType.INT64, DataType.BOOL,
)

# Pseudo process-set id keying the single shared device-plane executor lane
# (never collides with real psids, which are >= 0).
_DEVICE_LANE = -1


def _scale(arr: np.ndarray, factor: float) -> np.ndarray:
    """Scale a buffer by a scalar without extra copies.

    Integer tensors are rejected at enqueue (reference parity), so normally
    only float dtypes reach here.  f32/f64 scale in place; 16-bit floats
    widen to f32 for the multiply (the reference's CPU scale path also
    computes in higher precision); anything else defensively goes through
    f64 (exact for int64 magnitudes up to 2**53)."""
    if arr.dtype in (np.float32, np.float64):
        np.multiply(arr, arr.dtype.type(factor), out=arr)
        return arr
    if arr.itemsize == 2:
        return (arr.astype(np.float32) * np.float32(factor)).astype(arr.dtype)
    return (arr.astype(np.float64) * factor).astype(arr.dtype)


def _rows2d(a: np.ndarray) -> np.ndarray:
    """View as (rows, row_width) for the row-oriented plane calls.

    Not ``reshape(n, -1)``: numpy cannot infer -1 when n == 0, and a
    zero-row contribution is legal for ragged allgather (a rank whose
    sparse gradient touched no rows still participates)."""
    if a.ndim == 0:
        return a.reshape(1, 1)
    row = int(np.prod(a.shape[1:], dtype=np.int64)) if a.ndim > 1 else 1
    return a.reshape(a.shape[0], row)


class _FusionBuffer:
    """Reusable pack/unpack buffer for the host data plane.

    Reference: fusion_buffer_manager.cc — a preallocated per-device buffer
    that MemcpyInFusionBuffer packs gradients into so each cycle issues one
    collective with no per-cycle allocation.  Grows to the largest bucket
    seen (a single tensor may exceed HOROVOD_FUSION_THRESHOLD; it then forms
    a bucket of one)."""

    def __init__(self, initial_bytes: int = 0):
        self._buf = np.empty(int(initial_bytes), np.uint8)

    def view(self, dtype, count: int) -> np.ndarray:
        """A contiguous `count`-element view of the buffer as `dtype`."""
        dtype = np.dtype(dtype)
        nbytes = int(count) * dtype.itemsize
        if self._buf.nbytes < nbytes:
            self._buf = np.empty(nbytes, np.uint8)
        return self._buf[:nbytes].view(dtype)


def _select_backend(cfg: Config) -> CoreBackend:
    """The native C++ core, or the pure-Python local core when asked for.

    Selection mirrors the reference's controller choice in
    InitializeHorovodOnce (operations.cc): HOROVOD_CONTROLLER=python or
    HVD_TPU_PURE_PY=1 select the pure-Python local core; any other value
    (auto/local/socket) means the native core, and a native core that does
    not build or load is an error, not a reason to run something else.
    """
    if cfg.force_pure_python or cfg.controller == "python":
        if cfg.size > 1:
            raise HorovodInternalError(
                "pure-Python core only supports single-process mode"
            )
        return PyLocalCore()
    try:
        from ._core import NativeCore

        return NativeCore()
    except Exception as exc:
        raise HorovodInternalError(
            f"native core unavailable (size={cfg.size}, "
            f"controller={cfg.controller}): {exc}; set HVD_TPU_PURE_PY=1 "
            "to run the single-process pure-Python core instead"
        ) from exc


class _ExecutorLane:
    """One finalization lane per process set (reference analog:
    thread_pool.cc + per-communicator NCCL streams).

    Responses for the SAME process set finalize strictly in negotiated
    order (single lane thread, FIFO queue); responses for different sets
    proceed concurrently — safe because every registered set rides its own
    data-channel sockets (socket_controller.cc EstablishChannel), so a
    slow host collective on one set cannot head-of-line-block another."""

    def __init__(self, ctx: "HorovodContext", psid: int):
        import queue

        self.psid = psid
        self._ctx = ctx
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._run, name=f"hvd-lane-{psid}", daemon=True)
        self._thread.start()

    def submit(self, resp: FusedResponse) -> None:
        self._q.put(resp)

    def stop(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=5.0)

    def _run(self) -> None:
        while True:
            resp = self._q.get()
            if resp is None or self._ctx._shutdown.is_set():
                return
            self._ctx._process_response(resp)


class HorovodContext:
    """Process-wide singleton created by ``hvd.init()``."""

    _instance: Optional["HorovodContext"] = None
    _instance_lock = threading.Lock()

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.core = _select_backend(cfg)
        self._entries: Dict[int, TensorEntry] = {}
        self._entries_lock = threading.Lock()
        self._inflight_names: set = set()
        self._deferred: Dict[str, List[TensorEntry]] = {}
        self._joined = False  # this rank called join() and awaits the rest
        self._handle_counter = itertools.count(1)
        self._noname_counter = itertools.count(0)
        # Grouped-call counter: unnamed groups need a key that MATCHES
        # across ranks; like the noname counter, determinism follows from
        # every rank issuing grouped calls in the same order.
        self._group_counter = itertools.count(0)
        self._shutdown = threading.Event()
        # One fusion buffer PER EXECUTOR LANE (thread-local): lanes finalize
        # different process sets' responses concurrently, each packing its
        # own buffer (reference: FusionBufferManager::GetBuffer per device;
        # thread_pool.cc's parallel finalization role).
        self._fusion_tls = threading.local()
        self._fusion_initial = min(cfg.fusion_threshold_bytes, 64 << 20)
        self.core.start(cfg)
        # Eager device data plane: executes responses negotiated
        # device=True as cached jitted fused XLA collectives (the NCCL-ops
        # analog; ops/device_plane.py).
        self.device_plane = DevicePlane(self.core, cfg)
        # Parallel lanes: one finalization thread per process set, so an
        # in-flight host collective on one set cannot head-of-line-block
        # independent traffic on another.  Requires per-set data channels
        # (NativeCore); the pure-Python fallback finalizes inline.
        self._use_lanes = (
            getattr(self.core, "parallel_lanes", False) and cfg.size > 1
            and get_bool("HOROVOD_EXECUTOR_LANES", True))
        self._lanes: Dict[int, "_ExecutorLane"] = {}
        # Live cockpit (HOROVOD_COCKPIT, rank 0 only): loopback HTTP
        # endpoint streaming the fleet's step attribution; None when off.
        from .cockpit import maybe_start_cockpit
        self.cockpit = maybe_start_cockpit(self)
        self._executor = threading.Thread(
            target=self._executor_loop, name="hvd-executor", daemon=True
        )
        self._executor.start()

    @property
    def _fusion(self) -> _FusionBuffer:
        buf = getattr(self._fusion_tls, "buf", None)
        if buf is None:
            buf = _FusionBuffer(self._fusion_initial)
            self._fusion_tls.buf = buf
        return buf

    # -- lifecycle ----------------------------------------------------------
    @classmethod
    def instance(cls) -> "HorovodContext":
        inst = cls._instance
        if inst is None:
            raise ValueError(
                "Horovod has not been initialized; run hvd.init() first."
            )
        return inst

    @classmethod
    def initialized(cls) -> bool:
        return cls._instance is not None

    @classmethod
    def init(cls, cfg: Optional[Config] = None) -> "HorovodContext":
        with cls._instance_lock:
            if cls._instance is not None:
                return cls._instance
            cls._instance = HorovodContext(cfg or Config.from_env())
            return cls._instance

    @classmethod
    def shutdown(cls) -> None:
        with cls._instance_lock:
            inst, cls._instance = cls._instance, None
        if inst is None:
            return
        inst._shutdown.set()
        inst._executor.join(timeout=5.0)
        if getattr(inst, "cockpit", None) is not None:
            inst.cockpit.stop()
        inst.core.shutdown()
        # Fail any still-pending handles so blocked synchronize() callers
        # wake with an error instead of hanging forever.
        with inst._entries_lock:
            pending = [e for e in inst._entries.values() if not e.done.is_set()]
        for e in pending:
            e.error = "Horovod has been shut down"
            e.done.set()
        for fn in list(_shutdown_callbacks):
            try:
                fn()
            except Exception as exc:  # noqa: BLE001 - shutdown must finish
                log.warning("shutdown callback %r failed: %s", fn, exc)

    # -- enqueue ------------------------------------------------------------
    def enqueue(
        self,
        array,
        op: OpType,
        name: Optional[str] = None,
        reduce_op: ReduceOp = ReduceOp.SUM,
        root_rank: int = 0,
        splits=None,
        process_set_id: int = 0,
        prescale_factor: float = 1.0,
        postscale_factor: float = 1.0,
        group_key: str = "",
        group_size: int = 0,
    ) -> int:
        # A span on the caller's thread, on the profiler's clock (a flag test
        # outside a profiling session): adopt, entry, hand-over to the core.
        with TraceAnnotation("hvd_enqueue") as span:
            # Device-plane capability first: a device-resident jax.Array whose
            # op the plane serves never touches the host — the entry carries a
            # zero-memory shape/dtype proxy for negotiation metadata only, and
            # the announced device bit tells the coordinator this rank can
            # dispatch the jitted collective.
            dev_arr = self.device_plane.adopt(array, op, reduce_op, process_set_id)
            if dev_arr is not None:
                np_arr = np.broadcast_to(
                    np.zeros((), numpy_dtype(wire_dtype(dev_arr.dtype))),
                    tuple(dev_arr.shape))
                was_jax, orig_dtype = True, dev_arr.dtype
            else:
                np_arr, was_jax, orig_dtype = _to_host(array)
            dtype = wire_dtype(np_arr.dtype if orig_dtype is None else orig_dtype)
            if name is None:
                name = f"{op.name.lower()}.noname.{next(self._noname_counter)}"
            if dtype in _INT_TYPES:
                if reduce_op == ReduceOp.AVERAGE and op in (
                        OpType.ALLREDUCE, OpType.REDUCESCATTER):
                    raise ValueError(
                        "hvd.Average is not supported for integer tensors; use hvd.Sum"
                    )
                if prescale_factor != 1.0 or postscale_factor != 1.0:
                    raise ValueError("pre/postscale not supported for integer tensors")
            if splits is not None:
                splits = np.ascontiguousarray(np.asarray(splits, dtype=np.int64))

            handle = next(self._handle_counter)
            span.set_metadata(handle=handle)
            entry = TensorEntry(
                handle=handle,
                name=name,
                op=op,
                array=np_arr,
                dtype=dtype,
                reduce_op=reduce_op,
                root_rank=root_rank,
                splits=splits,
                process_set_id=process_set_id,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                was_jax=was_jax,
                orig_dtype=orig_dtype,
                group_key=group_key,
                group_size=group_size,
                device_array=dev_arr,
            )
            with self._entries_lock:
                self._entries[handle] = entry
                if name in self._inflight_names:
                    # Reference semantics: a second op with an in-flight name
                    # queues behind the first (the negotiation layer keys by
                    # name, so it is submitted once the first completes — safe
                    # because every rank orders instances the same way).
                    self._deferred.setdefault(name, []).append(entry)
                    return handle
                self._inflight_names.add(name)
            self.core.enqueue(entry)
            return handle

    def group_key_for(self, name: Optional[str]) -> str:
        """Negotiation key for one grouped_* call (group_table.cc analog).
        Must match across ranks: named groups key on the name; unnamed ones
        on the deterministic grouped-call counter."""
        if name:
            return f"g.{name}"
        return f"g.anon.{next(self._group_counter)}"

    # -- completion ---------------------------------------------------------
    def poll(self, handle: int) -> bool:
        with self._entries_lock:
            entry = self._entries.get(handle)
        if entry is None:
            raise ValueError(f"unknown handle {handle}")
        return entry.done.is_set()

    def synchronize(self, handle: int):
        with self._entries_lock:
            entry = self._entries.get(handle)
        if entry is None:
            raise ValueError(f"unknown handle {handle}")
        # How long the caller's loop stands still for negotiation + execution.
        with TraceAnnotation("hvd_wait", handle=handle):
            entry.done.wait()
        with self._entries_lock:
            self._entries.pop(handle, None)
        if entry.error is not None:
            raise HorovodInternalError(entry.error)
        result = entry.result
        if entry.op == OpType.ALLTOALL:
            return _from_host(result, entry), entry.recv_splits
        return _from_host(result, entry)

    # -- executor / data plane ----------------------------------------------
    def _executor_loop(self) -> None:
        """Dispatcher: pop negotiated responses and either finalize inline
        (serial mode) or hand each to its process set's lane."""
        while not self._shutdown.is_set():
            resp = self.core.pop_response(timeout=0.05)
            if resp is None:
                # The host-alive mark: twenty a second while nothing is
                # negotiated, so a profiler trace of a compiled loop says of
                # a gap of the device whether this process's host threads
                # ran in it (docs/observability.md, "Stalls").
                with TraceAnnotation("hvd_alive"):
                    pass
                continue
            # Join-state transitions must follow the GLOBAL negotiated
            # order, which only the dispatcher sees: stamp the current
            # joined flag on each response, and clear it when the JOIN
            # itself dispatches — a later lane finalizing an
            # earlier-negotiated collective still zero-participates.
            with self._entries_lock:
                resp.joined_at_dispatch = self._joined
                if resp.op == OpType.JOIN and not resp.error:
                    self._joined = False
            if resp.device and self._use_lanes:
                # ALL device-plane responses share ONE lane: XLA executes
                # collectives in per-device enqueue order, so every host
                # must enqueue them in the same (negotiated) global order —
                # two concurrent lanes whose rank meshes share devices
                # could otherwise enqueue in opposite orders on different
                # hosts and deadlock the ICI ring.  A dedicated lane (not
                # inline dispatch) also keeps a program-cache-miss compile
                # from head-of-line-blocking other sets' host traffic
                # behind the dispatcher.
                self._lane_for(_DEVICE_LANE).submit(resp)
            elif self._use_lanes:
                self._lane_for(resp.process_set_id).submit(resp)
            else:
                self._process_response(resp)
        for lane in list(self._lanes.values()):
            lane.stop()

    def _lane_for(self, psid: int) -> "_ExecutorLane":
        lane = self._lanes.get(psid)
        if lane is None:
            lane = _ExecutorLane(self, psid)
            self._lanes[psid] = lane
        return lane

    def remove_process_set(self, psid: int) -> None:
        """Remove a set from the core AND retire its executor lane (ids are
        never reused, so a leaked lane thread would accumulate forever)."""
        self.core.remove_process_set(psid)
        self.device_plane.invalidate(psid)
        lane = self._lanes.pop(psid, None)
        if lane is not None:
            lane.stop()

    def _process_response(self, resp: FusedResponse) -> None:
        """Finalize one response: collect entries, run the data plane, set
        completion.  Runs on the dispatcher (serial mode) or a lane thread
        (per-process-set lanes; ordering holds within each lane)."""
        self.core.set_current_seq(resp.seq)
        entries = []
        with self._entries_lock:
            for h in resp.handles:
                e = self._entries.get(h)
                if e is not None:
                    entries.append(e)
        if not entries:
            # Joined rank (hvd.join): no local tensors, but ring
            # collectives need every member — participate with zeros.
            # The dispatch-time stamp (not the live flag) decides: the
            # live flag may already be cleared by a JOIN that was
            # negotiated AFTER this response but dispatched to a faster
            # lane.
            if resp.joined_at_dispatch and not resp.error:
                try:
                    self._participate_absent(resp)
                except Exception as exc:  # noqa: BLE001
                    log.warning("zero-participation failed: %s", exc)
            return
        try:
            if resp.error:
                raise HorovodInternalError(resp.error)
            # One fused response through the data plane, on this thread.
            with TraceAnnotation("hvd_execute", seq=resp.seq,
                                 tensors=len(entries)):
                self._execute(resp, entries)
            for e in entries:
                e.done.set()
        except Exception as exc:  # noqa: BLE001 - propagate via handle
            if resp.op == OpType.JOIN:
                # A failed join (e.g. a peer shut down mid-join) must
                # not leave this rank zero-participating forever.
                with self._entries_lock:
                    self._joined = False
            for e in entries:
                e.error = str(exc)
                e.done.set()
        self._release_names(entries)

    def _release_names(self, entries: List[TensorEntry]) -> None:
        """After a name's instance completes, submit its next queued
        instance (duplicate-name queueing) or free the name."""
        to_enqueue = []
        with self._entries_lock:
            for e in entries:
                queued = self._deferred.get(e.name)
                if queued:
                    to_enqueue.append(queued.pop(0))
                    if not queued:
                        del self._deferred[e.name]
                else:
                    self._inflight_names.discard(e.name)
        for nxt in to_enqueue:
            self.core.enqueue(nxt)

    def _execute(self, resp: FusedResponse, entries: List[TensorEntry]) -> None:
        op = resp.op
        psid = resp.process_set_id
        if resp.device:
            # Negotiated device plane: EVERY rank announced capability, so
            # every rank dispatches the same cached jitted collective here.
            self.device_plane.execute(resp, entries)
            return
        # Host plane.  Negotiation may have demoted device-resident entries
        # (a host tensor or joined rank elsewhere): materialize their bytes
        # now — the only place an eager device array crosses to the host.
        for e in entries:
            if e.device_array is not None:
                e.array = _contig(np.asarray(e.device_array))
                self.device_plane.note_host_fallback(e.name)
        if op == OpType.ALLREDUCE:
            self._exec_allreduce(entries, psid)
        elif op == OpType.ALLGATHER:
            self._exec_allgather(entries, psid)
        elif op == OpType.BROADCAST:
            self._exec_broadcast(entries[0], psid)
        elif op == OpType.ALLTOALL:
            self._exec_alltoall(entries[0], psid)
        elif op == OpType.REDUCESCATTER:
            self._exec_reducescatter(entries[0], psid)
        elif op == OpType.BARRIER:
            self.core.barrier(psid)
            for e in entries:
                e.result = e.array
        elif op == OpType.JOIN:
            # Completion of the join itself: every rank joined; no data
            # moves.  The result is the last rank to join (reference:
            # join() return value).
            with self._entries_lock:
                self._joined = False
            for e in entries:
                e.result = np.int64(resp.last_joined)
        else:
            raise HorovodInternalError(f"unsupported op {op}")

    def _participate_absent(self, resp: FusedResponse) -> None:
        """Walk a collective this rank submitted nothing for (it joined):
        zero contribution for sum/average allreduce, plain participation
        for barriers.  The coordinator guarantees only these op types become
        ready while ranks are joined."""
        psid = resp.process_set_id
        if self.cfg.rank not in self.core.process_set_ranks(psid):
            return
        if resp.device:
            # Unreachable: the coordinator demotes every via-join response
            # to the host plane (socket_controller.cc CoordinatorCycle).
            raise HorovodInternalError(
                "joined rank received a device-plane response")
        if resp.op == OpType.ALLREDUCE:
            count = int(sum(resp.counts or []))
            zeros = np.zeros(count, numpy_dtype(resp.dtype))
            self.core.allreduce_buffer(zeros, psid, ReduceOp.SUM)
        elif resp.op == OpType.BARRIER:
            self.core.barrier(psid)
        elif resp.op == OpType.JOIN:
            pass  # our own join entry always exists locally
        else:
            raise HorovodInternalError(
                f"op {resp.op} cannot proceed with joined ranks")

    def _ps_size(self, psid: int) -> int:
        return len(self.core.process_set_ranks(psid))

    def _exec_allreduce(self, entries: List[TensorEntry], psid: int) -> None:
        # MemcpyInFusionBuffer analog: pack members into one contiguous buffer.
        dtype = entries[0].array.dtype
        reduce_op = entries[0].reduce_op
        if len(entries) == 1 and reduce_op != ReduceOp.ADASUM:
            # Single-tensor fast path: the fusion pack/unpack would be two
            # pure-overhead copies.  One owned copy (the user's input must
            # not be mutated; the plane reduces in place) is all that's
            # needed.
            e = entries[0]
            buf = np.array(e.array, dtype=dtype, copy=True, order="C")
            flat = buf.reshape(-1)
            if e.prescale_factor != 1.0:
                flat = _scale(flat, e.prescale_factor)
            wire_op = ReduceOp.SUM if reduce_op == ReduceOp.AVERAGE \
                else reduce_op
            flat = self.core.allreduce_buffer(flat, psid, wire_op)
            if reduce_op == ReduceOp.AVERAGE:
                n = self._ps_size(psid)
                if n > 1:
                    flat = _scale(flat, 1.0 / n)
            if e.postscale_factor != 1.0:
                flat = _scale(flat, e.postscale_factor)
            e.result = flat.reshape(e.array.shape)
            return
        # Pack into the preallocated fusion buffer — no per-cycle allocation.
        total = sum(e.array.size for e in entries)
        fused = self._fusion.view(dtype, total)
        off = 0
        for e in entries:
            n = e.array.size
            np.copyto(fused[off:off + n], e.array.ravel(), casting="no")
            off += n
        pre = entries[0].prescale_factor
        if pre != 1.0:
            fused = _scale(fused, pre)
        if reduce_op == ReduceOp.ADASUM and self._ps_size(psid) > 1:
            # Host-path Adasum: allgather every rank's fused buffer, then a
            # deterministic local pairwise-tree combine — every rank computes
            # the identical result (reference: adasum_mpi.cc uses MPI
            # point-to-point VHDD; the allgather form trades bandwidth for
            # the simpler host plane, fine at CPU-negotiation scale).
            # The combine runs PER TENSOR segment: adasum's dot/norm
            # coefficients are per-tensor in the reference too —
            # adasum(concat(a1,a2), ...) != concat(adasum(a1,...), ...).
            stacked, _ = self.core.allgather_buffer(
                fused.reshape(1, -1), psid)
            vectors = np.asarray(stacked, dtype=np.float64)
            segments = []
            offset = 0
            for e in entries:
                seg = vectors[:, offset:offset + e.array.size]
                segments.append(_adasum_tree(seg))
                offset += e.array.size
            fused = np.concatenate(segments).astype(dtype)
        else:
            wire_op = ReduceOp.SUM \
                if reduce_op in (ReduceOp.AVERAGE, ReduceOp.ADASUM) \
                else reduce_op
            fused = self.core.allreduce_buffer(fused, psid, wire_op)
            if reduce_op == ReduceOp.AVERAGE:
                n = self._ps_size(psid)
                if n > 1:
                    fused = _scale(fused, 1.0 / n)
        post = entries[0].postscale_factor
        if post != 1.0:
            fused = _scale(fused, post)
        # MemcpyOutFusionBuffer analog: results must own their memory — the
        # fusion buffer is reused by the next response.
        offset = 0
        for e in entries:
            n = e.array.size
            e.result = fused[offset:offset + n].reshape(e.array.shape).copy()
            offset += n

    def _exec_allgather(self, entries: List[TensorEntry], psid: int) -> None:
        if len(entries) == 1:
            e = entries[0]
            stacked, counts = self.core.allgather_buffer(
                _rows2d(e.array), psid)
            rest = e.array.shape[1:] if e.array.ndim else ()
            e.result = np.asarray(stacked).reshape(
                (int(np.sum(counts)),) + tuple(rest))
            return
        # Fused allgather (reference: AllgatherOp rides the fusion buffer
        # too): pack members length-prefixed into one payload, gather once,
        # then split each rank's block back into per-tensor segments.  The
        # prefix is required because allgather first dims vary per rank, so
        # the response metas cannot describe remote segment sizes.
        parts = []
        for e in entries:
            raw = np.ascontiguousarray(e.array).view(np.uint8).ravel()
            parts.append(np.frombuffer(
                np.int64(raw.nbytes).tobytes(), np.uint8))
            parts.append(raw)
        # Rows of one byte: rank blocks are ragged (per-rank first dims), so
        # the per-rank counts must come back in bytes, not in my-row units.
        packed = np.concatenate(parts)
        stacked, counts = self.core.allgather_buffer(
            packed.reshape(-1, 1), psid)
        flat = np.asarray(stacked).view(np.uint8).ravel()
        per_entry: List[List[np.ndarray]] = [[] for _ in entries]
        off = 0
        for rank_bytes in counts:
            end = off + int(rank_bytes)
            for i, e in enumerate(entries):
                n = int(flat[off:off + 8].view(np.int64)[0])
                off += 8
                per_entry[i].append(flat[off:off + n])
                off += n
            if off != end:
                raise HorovodInternalError(
                    "fused allgather block framing desynced")
        for i, e in enumerate(entries):
            rest = tuple(e.array.shape[1:]) if e.array.ndim else ()
            row_bytes = int(np.prod(rest, dtype=np.int64)) * e.array.itemsize \
                if rest else e.array.itemsize
            blob = np.concatenate(per_entry[i]) if per_entry[i] else \
                np.empty(0, np.uint8)
            total_rows = blob.nbytes // max(row_bytes, 1)
            e.result = blob.view(e.array.dtype).reshape(
                (total_rows,) + rest)

    def _exec_broadcast(self, e: TensorEntry, psid: int) -> None:
        e.result = self.core.broadcast_buffer(e.array, e.root_rank, psid)

    def _exec_alltoall(self, e: TensorEntry, psid: int) -> None:
        n = self._ps_size(psid)
        splits = validate_alltoall_splits(e.splits, e.array.shape[0], n)
        buf = _rows2d(e.array)
        out, recv_splits = self.core.alltoall_buffer(buf, splits, psid)
        rest = e.array.shape[1:]
        e.result = np.asarray(out).reshape((int(np.sum(recv_splits)),) + tuple(rest))
        e.recv_splits = np.asarray(recv_splits, dtype=np.int64)

    def _exec_reducescatter(self, e: TensorEntry, psid: int) -> None:
        # True ring reduce-scatter ((m-1)/m of the buffer on the wire,
        # half the allreduce-then-slice this used to do): the plane
        # reduces each rank's slice in place and we keep ours.  Slicing
        # rule matches the reference (ReducescatterOp): the first
        # (d0 % size) ranks receive one extra row.
        n = self._ps_size(psid)
        fused = e.array.ravel().copy()
        pre = e.prescale_factor
        if pre != 1.0:
            fused = _scale(fused, pre)
        wire_op = ReduceOp.SUM if e.reduce_op == ReduceOp.AVERAGE else e.reduce_op
        d0 = e.array.shape[0]
        row = fused.size // d0 if d0 else 0
        ranks = self.core.process_set_ranks(psid)
        my_pos = ranks.index(self.core.rank()) if self.core.rank() in ranks else 0
        base, extra = divmod(d0, n)
        slice_rows = [base + (1 if p < extra else 0) for p in range(n)]
        fused = self.core.reducescatter_buffer(
            fused, psid, wire_op, [r * row for r in slice_rows])
        start = (my_pos * base + min(my_pos, extra)) * row
        mine = fused[start:start + slice_rows[my_pos] * row]
        if e.reduce_op == ReduceOp.AVERAGE:
            mine = _scale(mine, 1.0 / max(n, 1))
        if e.postscale_factor != 1.0:
            mine = _scale(mine, e.postscale_factor)
        e.result = mine.reshape((slice_rows[my_pos],) + e.array.shape[1:])


def _adasum_pair(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Scale-invariant pairwise combine (reference: adasum/adasum.h):
    adasum(a, b) = (1 - a.b/(2|a|^2)) a + (1 - a.b/(2|b|^2)) b."""
    dot = float(np.dot(a, b))
    na = max(float(np.dot(a, a)), 1e-300)
    nb = max(float(np.dot(b, b)), 1e-300)
    return (1.0 - dot / (2.0 * na)) * a + (1.0 - dot / (2.0 * nb)) * b


def _adasum_tree(vectors: np.ndarray) -> np.ndarray:
    """Pairwise-tree Adasum over rank-major rows; handles non-power-of-two
    counts by passing the odd row through to the next level."""
    rows = [vectors[i].ravel() for i in range(vectors.shape[0])]
    while len(rows) > 1:
        nxt = [_adasum_pair(rows[i], rows[i + 1])
               for i in range(0, len(rows) - 1, 2)]
        if len(rows) % 2:
            nxt.append(rows[-1])
        rows = nxt
    return rows[0]


def _contig(a: np.ndarray) -> np.ndarray:
    # np.ascontiguousarray promotes 0-d to 1-d; preserve scalar shape.
    return a.copy() if a.ndim == 0 else np.ascontiguousarray(a)


def _to_host(array):
    """Convert a framework array to a contiguous host numpy buffer."""
    was_jax = False
    orig_dtype = None
    if not isinstance(array, np.ndarray):
        try:
            import jax

            if isinstance(array, jax.Array):
                was_jax = True
                orig_dtype = array.dtype  # bfloat16 survives via ml_dtypes
                return _contig(np.asarray(array)), was_jax, orig_dtype
        except ImportError:  # pragma: no cover
            pass
        array = np.asarray(array)
    return _contig(array), was_jax, orig_dtype


def _from_host(result: np.ndarray, entry: TensorEntry):
    if entry.device_array is not None and not isinstance(result, np.ndarray):
        return result  # device plane: already a device-resident jax.Array
    if not entry.was_jax:
        return result
    import jax.numpy as jnp

    return jnp.asarray(result, dtype=entry.orig_dtype)
