"""Eager device data plane: cached jitted fused collectives.

TPU-native analog of the reference's NCCL ops layer for the EAGER path
(reference: horovod/common/ops/nccl_operations.cc — NCCLAllreduce/
NCCLBroadcast execute ON the accelerator and the fused buffer stays
device-resident; SURVEY.md §2.2 and §7's design stance "the ops layer
compiles and caches jitted fused collectives").  Where the traced path
(``horovod_tpu.ops.collectives``) serves code already inside jit/shard_map,
this module serves *eager* enqueues of device-resident ``jax.Array``s: the
executor dispatches a cached, jitted fused collective over a
one-device-per-rank mesh instead of copying to host and riding the TCP
plane.

Correctness across ranks is negotiated, exactly like the reference decides
NCCL vs CPU ops from the request's device id: every enqueue announces a
``device`` capability bit, the coordinator ANDs the bits, and the response's
``device`` flag tells every rank which plane to dispatch — so a host numpy
on one rank demotes the collective to the host plane for all, and a
response flagged ``device`` is dispatched as the same XLA program in the
same negotiated order on every host (ICI moves the bytes).

Program caching (SURVEY.md §7 "Hard parts" #1): the collective program is
keyed by (mesh, reduce op, dtype, padded bucket length); fused buckets are
padded up to a small set of size classes ({1, 1.25, 1.5, 1.75}·2^k
elements) so steady-state cycles reuse compiled programs even when the
fusion composition varies cycle to cycle.  Pack/unpack are ordinary jits
cached by jax on member shapes.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from ..exceptions import HorovodInternalError
from ..parallel import mesh as _mesh
from ..utils.env import get_bool
from ..utils.logging import get_logger
from ..wire import DataType, OpType, ReduceOp, validate_alltoall_splits

log = get_logger()

AXIS = "hvdev"

_MIN_BUCKET = 1024


_SUPPORTED_REDUCE = (ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.MIN,
                     ReduceOp.MAX, ReduceOp.PRODUCT)


def bucket_len(n: int) -> int:
    """Pad a flat element count up to the {1, 1.25, 1.5, 1.75}·2^k size-class
    set (<= 25% padding, ~4 compiled programs per octave)."""
    if n <= _MIN_BUCKET:
        return _MIN_BUCKET
    base = 1 << (int(n).bit_length() - 1)  # largest pow2 <= n
    for num in (4, 5, 6, 7, 8):
        cls = base * num // 4
        if n <= cls:
            return cls
    return base * 2


class DevicePlane:
    """Executes negotiated ``device=True`` responses as jitted XLA
    collectives over a one-device-per-rank mesh."""

    def __init__(self, core, cfg):
        self._core = core
        self._cfg = cfg
        mode = os.environ.get("HOROVOD_DEVICE_PLANE", "auto").strip().lower()
        self._enabled = mode not in ("off", "0", "false", "no")
        self._lock = threading.Lock()
        # psid -> (mesh, ranks, my_device) or None (not buildable)
        self._meshes: Dict[int, Optional[tuple]] = {}
        self._programs: Dict[tuple, Any] = {}
        self._pack_fn = None
        self._unpack_fn = None
        self._scale_fn = None
        self.stats = {
            "allreduce": 0,       # fused device allreduce dispatches
            "broadcast": 0,       # device broadcast dispatches
            "reducescatter": 0,   # device reducescatter dispatches
            "allgather": 0,       # device allgather dispatches
            "alltoall": 0,        # device alltoall dispatches
            "identity": 0,        # single-member identity completions
            "quantized": 0,       # fused allreduces that rode the int8 ring
            "programs_built": 0,  # collective compile-cache misses
            "host_fallback": 0,   # device-resident entries demoted to host
            "late_device_put": 0,  # stale cache-replayed device=1 on a host entry
        }

    def _cached_program(self, key, build):
        """Double-checked program-cache access shared by every collective
        builder; ``build()`` runs outside the lock (jit/shard_map
        construction is slow) and ties break toward the first insert."""
        with self._lock:
            fn = self._programs.get(key)
        if fn is not None:
            return fn
        fn = build()
        with self._lock:
            if key not in self._programs:
                self._programs[key] = fn
                self.stats["programs_built"] += 1
            return self._programs[key]

    # -- enqueue-side capability -------------------------------------------
    def adopt(self, array, op: OpType, reduce_op: ReduceOp,
              psid: int):
        """The device-resident jax.Array behind ``array`` if this enqueue
        can ride the device plane, else None (host path).  This decides the
        rank's announced ``device`` capability bit, so it must only return
        an array when execute() is guaranteed to succeed locally."""
        if not self._enabled:
            return None
        if op == OpType.ALLREDUCE:
            if reduce_op not in _SUPPORTED_REDUCE:
                return None
        elif op == OpType.ALLGATHER:
            # Gathered first dims may differ per rank; the device program
            # pads to the max (counts are exchanged as metadata at execute
            # time — bytes stay on device).  Scalars ride the host plane.
            if getattr(array, "ndim", 0) == 0:
                return None
        elif op == OpType.ALLTOALL:
            if getattr(array, "ndim", 0) == 0:
                return None
        elif op == OpType.REDUCESCATTER:
            # Device reducescatter serves Sum/Average on evenly divisible
            # first dims (psum_scatter needs uniform chunks); the host
            # plane's extra-row slicing covers the remainder case.  Shape
            # equality across ranks is already negotiation-validated, so
            # the divisibility check agrees on every rank.
            if reduce_op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
                return None
            k = len(self._members(psid))
            d0 = array.shape[0] if getattr(array, "ndim", 0) else 0
            if k == 0 or d0 == 0 or d0 % k != 0:
                return None
        elif op != OpType.BROADCAST:
            return None
        try:
            import jax
        except ImportError:  # pragma: no cover
            return None
        if not isinstance(array, jax.Array) or isinstance(array, jax.core.Tracer):
            return None
        if not array.is_fully_addressable:
            # A multi-process global array is the SAME logical tensor on
            # every rank — not the per-rank contribution eager collectives
            # are defined over.
            return None
        if array.dtype == bool:
            return None  # the host plane's logical and/or semantics apply
        if not self.ready(psid):
            return None
        return array

    def note_host_fallback(self, name: str) -> None:
        """A device-resident tensor was demoted to the host plane by
        negotiation (a host tensor, unsupported op, or joined rank
        somewhere).  On TPU that means a chip->PCIe->TCP round-trip per
        collective — warn once so the perf trap is visible."""
        with self._lock:
            self.stats["host_fallback"] += 1
            warned = getattr(self, "_fallback_warned", False)
            self._fallback_warned = True
        if not warned:
            try:
                import jax

                on_tpu = jax.default_backend() == "tpu"
            except Exception:  # pragma: no cover
                on_tpu = False
            if on_tpu:
                log.warning(
                    "eager collective %r has a device-resident input but was "
                    "negotiated onto the HOST data plane (another rank "
                    "submitted a host tensor, an unsupported op/dtype, or a "
                    "rank is joined) — gradients will cross PCIe + host TCP. "
                    "Prefer jit/shard_map training steps, or keep every "
                    "rank's inputs device-resident. (warned once)", name)

    def ready(self, psid: int) -> bool:
        if self._core.size() == 1:
            return True
        return self._mesh_for(psid) is not None

    def invalidate(self, psid: int) -> None:
        with self._lock:
            self._meshes.pop(psid, None)
            for key in [k for k in self._programs if k[0] == psid]:
                self._programs.pop(key, None)

    # -- mesh / program construction ---------------------------------------
    def _mesh_for(self, psid: int):
        """(mesh, ranks, my_device) for the process set, or None when the
        jax runtime does not span its ranks (single-process jax with np>1,
        or a rank whose process owns no device)."""
        with self._lock:
            if psid in self._meshes:
                return self._meshes[psid]
        import jax
        from jax.sharding import Mesh

        result = None
        why = "this rank's jax process owns no addressable device"
        try:
            ranks = self._core.process_set_ranks(psid)
            by_rank: Dict[int, Any] = {}
            for d in jax.devices():
                by_rank.setdefault(_mesh.rank_of_process(d.process_index), d)
            devs = [by_rank[r] for r in ranks]
            my = by_rank.get(self._core.rank())
            # hvd rank <-> jax process mapping is learned in basics.init
            # (process_allgather of the ranks); if the runtime was wired
            # differently, "my" device may not be addressable — then the
            # plane cannot place local shards.
            if my is not None and my in jax.local_devices():
                mesh = Mesh(np.asarray(devs), (AXIS,))
                result = (mesh, list(ranks), my)
        except Exception as exc:  # noqa: BLE001 - capability probe
            why = f"{type(exc).__name__}: {exc}"
        if result is None:
            msg = (f"device plane unavailable for set {psid}: the jax "
                   f"runtime ({jax.process_count()} process(es), "
                   f"{jax.device_count()} device(s)) does not span its "
                   f"ranks ({why})")
            if get_bool("HOROVOD_JAX_DISTRIBUTED", False):
                # The launcher was told to join the ranks into one jax
                # runtime; a runtime that does not span them is a broken
                # launch, not a reason to ship tensors over host TCP.
                raise HorovodInternalError(msg)
            log.debug(msg)
        if result is not None:
            # Cache successes only: a transient probe failure (e.g. the
            # jax distributed runtime still connecting at first enqueue)
            # must not demote the set to the host plane for the whole job.
            with self._lock:
                self._meshes[psid] = result
        return result

    def _device_codec(self, rop: ReduceOp, dtype, length: int,
                      k: int) -> str:
        """The configured block-scaled codec (``int8``/``int4``)
        when this fused bucket should ride the quantized ring, else
        ``"none"``.  Demotion rules mirror the traced path (fp32 Sum/
        Average, payload >= HOROVOD_WIRE_COMPRESSION_MIN_BYTES, k > 1); the
        codec comes from config, which negotiation keeps rank-uniform, so
        every member picks the same program."""
        from . import quantize as _qz

        codec = getattr(self._cfg, "wire_compression_device", "none")
        if codec not in _qz.DEVICE_WIRE_CODECS or codec == "none":
            return "none"
        if k <= 1 or rop not in (ReduceOp.SUM, ReduceOp.AVERAGE):
            return "none"
        if np.dtype(dtype) != np.float32:
            return "none"
        min_bytes = int(getattr(self._cfg, "wire_compression_min_bytes",
                                1 << 16))
        if length * 4 < min_bytes:
            return "none"
        return codec

    def _device_schedule(self, k: int) -> str:
        """Resolved ring schedule (``ring``/``bidi``/``torus``) for a
        ``k``-member plane — config's ``device_schedule`` (``auto`` picks
        from the member count) with infeasible choices demoted, so the
        value is a pure function of rank-uniform state."""
        from .collectives import resolve_device_schedule

        sched = getattr(self._cfg, "device_schedule", "auto")
        return resolve_device_schedule(k, sched)

    def _collective(self, psid: int, mesh, rop: ReduceOp, dtype, length: int,
                    codec: str = "none", schedule: str = "ring"):
        """Cached jitted fused-allreduce program over (k, L) global arrays:
        every member's [1, L] shard in, every member's reduced [1, L] shard
        out (out_specs stay device-varying so one program shape serves all
        reduce ops).  A block-scaled ``codec`` swaps the psum for the
        quantized ring under the resolved ``schedule`` (ops.quantize
        semantics; callers pre-filter via _device_codec /
        _device_schedule)."""
        key = (psid, "ar", int(rop), str(np.dtype(dtype)), length, codec,
               schedule, tuple(d.id for d in mesh.devices.flat))

        def build():
            import jax
            from jax import lax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            from .collectives import ensure_varying

            k = int(mesh.devices.size)

            def inner(x):  # [1, L]: this member's shard
                if codec != "none":
                    from .collectives import _quantized_ring_allreduce_sum

                    out = _quantized_ring_allreduce_sum(
                        x[0], AXIS, None, codec, schedule)[None]
                    if rop == ReduceOp.AVERAGE:
                        out = out / k
                elif rop == ReduceOp.SUM:
                    out = lax.psum(x, AXIS)
                elif rop == ReduceOp.AVERAGE:
                    out = lax.psum(x, AXIS) / k
                elif rop == ReduceOp.MIN:
                    out = lax.pmin(x, AXIS)
                elif rop == ReduceOp.MAX:
                    out = lax.pmax(x, AXIS)
                elif rop == ReduceOp.PRODUCT:
                    g = lax.all_gather(x, AXIS, axis=0, tiled=True)
                    out = jax.numpy.prod(g, axis=0, keepdims=True)
                else:  # pragma: no cover - adopt() filters
                    raise HorovodInternalError(
                        f"unsupported device reduce {rop}")
                return ensure_varying(out, AXIS)

            return jax.jit(shard_map(inner, mesh=mesh,
                                     in_specs=P(AXIS, None),
                                     out_specs=P(AXIS, None)))

        return self._cached_program(key, build)

    def _reducescatter_program(self, psid: int, mesh, rop: ReduceOp, dtype,
                               count: int, pre: float, post: float):
        """Cached jitted reducescatter over (k, N) global arrays: every
        member's full flat [1, N] in, its reduced [1, N/k] chunk out —
        lowered to psum_scatter ((k-1)/k of the bytes on the wire)."""
        key = (psid, "rs", int(rop), str(np.dtype(dtype)), count, pre, post,
               tuple(d.id for d in mesh.devices.flat))

        def build():
            import jax
            import jax.numpy as jnp
            from jax import lax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            from .collectives import ensure_varying

            k = int(mesh.devices.size)

            def inner(x):  # [1, N]: this member's full contribution
                flat = x[0]
                if pre != 1.0:
                    flat = flat * jnp.asarray(pre, flat.dtype)
                out = lax.psum_scatter(flat, AXIS, scatter_dimension=0,
                                      tiled=True)
                if rop == ReduceOp.AVERAGE:
                    out = out / k
                if post != 1.0:
                    out = out * jnp.asarray(post, out.dtype)
                return ensure_varying(out, AXIS)[None]

            return jax.jit(shard_map(inner, mesh=mesh,
                                     in_specs=P(AXIS, None),
                                     out_specs=P(AXIS, None)))

        return self._cached_program(key, build)

    def _broadcast_program(self, psid: int, mesh, dtype, shape, root_pos: int):
        key = (psid, "bc", str(np.dtype(dtype)), tuple(shape), root_pos,
               tuple(d.id for d in mesh.devices.flat))

        def build():
            import jax
            import jax.numpy as jnp
            from jax import lax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            from .collectives import ensure_varying

            def inner(x):  # [1, ...]: this member's value
                idx = lax.axis_index(AXIS)
                contrib = jnp.where(idx == root_pos, x, jnp.zeros_like(x))
                return ensure_varying(lax.psum(contrib, AXIS), AXIS)

            spec = P(AXIS, *([None] * len(shape)))
            return jax.jit(shard_map(inner, mesh=mesh, in_specs=spec,
                                     out_specs=spec))

        return self._cached_program(key, build)

    def _allgather_program(self, psid: int, mesh, dtype, counts: tuple,
                           rest: tuple):
        """Cached jitted allgather over (k, maxn, R) global arrays: every
        member's first-dim-padded [1, maxn, R] shard in, the full
        concatenation [1, total, R] out on every member.  ``counts`` (the
        per-member true first dims) is static — ragged gathers compile per
        counts signature, steady-state shapes hit the cache."""
        key = (psid, "ag", str(np.dtype(dtype)), counts, rest,
               tuple(d.id for d in mesh.devices.flat))

        def build():
            import jax
            import jax.numpy as jnp
            from jax import lax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            from .collectives import ensure_varying

            k = int(mesh.devices.size)

            def inner(x):  # [1, maxn, R]: this member's padded rows
                g = lax.all_gather(x[0], AXIS, axis=0)     # [k, maxn, R]
                parts = [g[i, :counts[i]] for i in range(k) if counts[i]]
                out = (jnp.concatenate(parts, axis=0) if parts
                       else g[:, :0].reshape((0,) + g.shape[2:]))
                return ensure_varying(out, AXIS)[None]     # [1, total, R]

            return jax.jit(shard_map(inner, mesh=mesh,
                                     in_specs=P(AXIS, None, None),
                                     out_specs=P(AXIS, None, None)))

        return self._cached_program(key, build)

    def _alltoall_program(self, psid: int, mesh, dtype, splits_mat: tuple,
                          restprod: int):
        """Cached jitted alltoall over (k, d0max, R) global arrays.
        ``splits_mat`` (row r = member r's per-destination send counts) is
        static.  Uniform splits lower to one tiled lax.all_to_all; ragged
        splits pad each (src, dst) chunk to the max count, exchange
        uniformly, then re-pack — extra wire bytes, but the payload stays
        on device (the host plane's ragged exchange is the alternative).
        Output is [1, recvmax, R] per member, sliced to the true receive
        count by the caller."""
        key = (psid, "a2a", str(np.dtype(dtype)), splits_mat, restprod,
               tuple(d.id for d in mesh.devices.flat))

        def build():
            import jax
            import jax.numpy as jnp
            from jax import lax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            from .collectives import ensure_varying

            k = int(mesh.devices.size)
            rows = [list(r) for r in splits_mat]
            recv_counts = [[rows[src][dst] for src in range(k)]
                           for dst in range(k)]
            recv_tot = [sum(rc) for rc in recv_counts]
            recvmax = max(max(recv_tot), 1)
            uniform = len({c for r in rows for c in r}) == 1

            if uniform:
                c = rows[0][0]

                def inner(x):  # [1, d0max, R]; d0max == k*c here
                    swapped = lax.all_to_all(      # row i <- member i's chunk
                        x[0].reshape(k, c, -1), AXIS, split_axis=0,
                        concat_axis=0, tiled=False)
                    out = swapped.reshape(k * c, -1)
                    return ensure_varying(out, AXIS)[None]

                return jax.jit(shard_map(inner, mesh=mesh,
                                         in_specs=P(AXIS, None, None),
                                         out_specs=P(AXIS, None, None)))

            cmax = max(max(c for r in rows for c in r), 1)

            def pack_for(r):
                offs = np.concatenate([[0], np.cumsum(rows[r])])

                def pack(x):  # [d0max, R] -> [k, cmax, R] padded chunks
                    chunks = []
                    for j in range(k):
                        seg = x[int(offs[j]):int(offs[j + 1])]
                        pad = cmax - seg.shape[0]
                        if pad:
                            z = ensure_varying(
                                jnp.zeros((pad,) + seg.shape[1:], seg.dtype),
                                AXIS)
                            seg = jnp.concatenate([seg, z])
                        chunks.append(seg)
                    return jnp.stack(chunks)

                return pack

            def unpack_for(me):
                def unpack(g):  # [k, cmax, R] rows from each src, padded
                    parts = [g[src, :recv_counts[me][src]]
                             for src in range(k) if recv_counts[me][src]]
                    out = (jnp.concatenate(parts, axis=0) if parts
                           else g[:, :0].reshape((0,) + g.shape[2:]))
                    pad = recvmax - out.shape[0]
                    if pad:
                        z = ensure_varying(
                            jnp.zeros((pad,) + out.shape[1:], out.dtype),
                            AXIS)
                        out = jnp.concatenate([out, z])
                    return out

                return unpack

            def inner(x):  # [1, d0max, R]
                me = lax.axis_index(AXIS)
                packed = lax.switch(
                    me, [lambda _, r=r: pack_for(r)(x[0]) for r in range(k)],
                    None)
                swapped = lax.all_to_all(packed, AXIS, split_axis=0,
                                         concat_axis=0, tiled=False)
                out = lax.switch(
                    me, [lambda g, r=r: unpack_for(r)(g) for r in range(k)],
                    swapped)
                return ensure_varying(out, AXIS)[None]    # [1, recvmax, R]

            return jax.jit(shard_map(inner, mesh=mesh,
                                     in_specs=P(AXIS, None, None),
                                     out_specs=P(AXIS, None, None)))

        return self._cached_program(key, build)

    def _pack(self):
        """Jitted fuse: concat member tensors flat, optional prescale, pad
        to the bucket length (MemcpyInFusionBuffer analog, on device).
        Scale factors are static (compile-time constants): an eager
        ``jnp.asarray(pre)`` would be a host->device scalar transfer, which
        the no-host-copy guarantee (and its transfer-guard test) forbids."""
        if self._pack_fn is None:
            import jax
            import jax.numpy as jnp

            def pack(arrays, pre, length):
                flat = (jnp.concatenate([a.ravel() for a in arrays])
                        if len(arrays) > 1 else arrays[0].ravel())
                if pre != 1.0:
                    flat = flat * jnp.asarray(pre, flat.dtype)
                pad = length - flat.size
                if pad:
                    flat = jnp.concatenate(
                        [flat, jnp.zeros((pad,), flat.dtype)])
                return flat.reshape(1, length)

            self._pack_fn = jax.jit(pack, static_argnums=(1, 2))
        return self._pack_fn

    def _unpack(self):
        """Jitted unfuse: slice the reduced flat bucket back into member
        shapes, optional postscale (MemcpyOutFusionBuffer analog)."""
        if self._unpack_fn is None:
            import jax

            import jax.numpy as jnp

            def unpack(row, post, shapes):
                flat = row.reshape(-1)
                outs = []
                off = 0
                for shp in shapes:
                    n = int(np.prod(shp)) if shp else 1
                    seg = flat[off:off + n].reshape(shp)
                    if post != 1.0:
                        seg = seg * jnp.asarray(post, seg.dtype)
                    outs.append(seg)
                    off += n
                return outs

            self._unpack_fn = jax.jit(unpack, static_argnums=(1, 2))
        return self._unpack_fn

    def _scale(self):
        if self._scale_fn is None:
            import jax
            import jax.numpy as jnp

            def scale(x, a, b):
                if a != 1.0:
                    x = x * jnp.asarray(a, x.dtype)
                if b != 1.0:
                    x = x * jnp.asarray(b, x.dtype)
                return x

            self._scale_fn = jax.jit(scale, static_argnums=(1, 2))
        return self._scale_fn

    # -- execution ---------------------------------------------------------
    def execute(self, resp, entries: Sequence) -> None:
        """Run a negotiated ``device=True`` response; fills entry results
        with device-resident jax.Arrays (no host copies anywhere in the
        steady state).

        A response-cache replay carries the bit of the ORIGINAL
        negotiation, so a tensor that flipped device->host since then can
        arrive here without a device array — place its host bytes on
        device explicitly (one slow step, correct result; the response
        cache evicts/re-learns the signature only when metadata changes,
        not the plane)."""
        import jax

        for e in entries:
            if e.device_array is None:
                e.device_array = jax.device_put(np.ascontiguousarray(e.array))
                with self._lock:
                    self.stats["late_device_put"] += 1
        if resp.op == OpType.ALLREDUCE:
            self._exec_allreduce(resp, entries)
        elif resp.op == OpType.BROADCAST:
            self._exec_broadcast(resp, entries[0])
        elif resp.op == OpType.REDUCESCATTER:
            self._exec_reducescatter(resp, entries[0])
        elif resp.op == OpType.ALLGATHER:
            self._exec_allgather(resp, entries)
        elif resp.op == OpType.ALLTOALL:
            self._exec_alltoall(resp, entries[0])
        else:
            raise HorovodInternalError(
                f"op {resp.op} is not served by the device plane")

    def _members(self, psid: int) -> List[int]:
        return self._core.process_set_ranks(psid)

    def _exec_allreduce(self, resp, entries: Sequence) -> None:
        import jax
        import jax.numpy as jnp

        psid = resp.process_set_id
        rop = entries[0].reduce_op
        pre = entries[0].prescale_factor
        post = entries[0].postscale_factor
        if len(self._members(psid)) == 1:
            # Single-member set: every supported reduce op is the identity
            # (modulo scale factors) — complete without any data movement,
            # preserving each input's sharding.
            for e in entries:
                x = e.device_array
                if pre != 1.0 or post != 1.0:
                    x = self._scale()(x, float(pre), float(post))
                e.result = x
            with self._lock:
                self.stats["identity"] += len(entries)
            return

        # The three dispatches of a fused device all-reduce, each a host
        # span on this lane's thread (on the profiler's clock).
        mesh, ranks, my_dev = self._mesh_for(psid)
        with TraceAnnotation("hvd_pack"):
            arrays = [jax.device_put(e.device_array, my_dev) for e in entries]
            dtype = arrays[0].dtype
            total = int(sum(a.size for a in arrays))
            length = bucket_len(total)
            packed = jax.device_put(
                self._pack()(tuple(arrays), float(pre), length), my_dev)
            garr = self._to_global(mesh, [packed])
        with TraceAnnotation("hvd_collective"):
            codec = self._device_codec(rop, dtype, length, len(ranks))
            schedule = self._device_schedule(len(ranks))
            out = self._collective(psid, mesh, rop, dtype, length, codec,
                                   schedule)(garr)
        with TraceAnnotation("hvd_unpack"):
            row = self._shard_on(out, my_dev)
            shapes = tuple(tuple(e.device_array.shape) for e in entries)
            results = self._unpack()(row, float(post), shapes)
        for e, r in zip(entries, results):
            e.result = r
        if codec != "none":
            from . import quantize as _qz

            _qz.note_device_bytes(
                *_qz.ring_bytes(length, len(ranks), codec, schedule))
        with self._lock:
            self.stats["allreduce"] += 1
            if codec != "none":
                self.stats["quantized"] += 1

    def _exec_reducescatter(self, resp, entry) -> None:
        import jax

        psid = resp.process_set_id
        members = self._members(psid)
        pre = float(entry.prescale_factor)
        post = float(entry.postscale_factor)
        if len(members) == 1:
            # One member keeps the whole reduced buffer (host-plane
            # semantics at n=1): identity modulo scales.
            x = entry.device_array
            if pre != 1.0 or post != 1.0:
                x = self._scale()(x, pre, post)
            entry.result = x
            with self._lock:
                self.stats["identity"] += 1
            return
        mesh, ranks, my_dev = self._mesh_for(psid)
        k = len(ranks)
        x = jax.device_put(entry.device_array, my_dev)
        row = x.reshape(1, -1)
        garr = self._to_global(mesh, [row])
        fn = self._reducescatter_program(psid, mesh, entry.reduce_op,
                                         x.dtype, row.shape[1], pre, post)
        out = fn(garr)
        chunk_rows = x.shape[0] // k
        entry.result = self._shard_on(out, my_dev).reshape(
            (chunk_rows,) + tuple(x.shape[1:]))
        with self._lock:
            self.stats["reducescatter"] += 1

    def _exec_allgather(self, resp, entries: Sequence) -> None:
        """Device allgather: per-rank first dims are exchanged as int64
        METADATA over the host ctrl plane (same channel negotiation uses —
        a few bytes), then the payload rides one cached XLA all_gather.
        Ragged first dims pad to the max and slice inside the program."""
        import jax

        psid = resp.process_set_id
        members = self._members(psid)
        if len(members) == 1:
            for e in entries:
                e.result = e.device_array
            with self._lock:
                self.stats["identity"] += len(entries)
            return
        mesh, ranks, my_dev = self._mesh_for(psid)
        k = len(ranks)
        dims = np.ascontiguousarray(
            [int(e.device_array.shape[0]) for e in entries], dtype=np.int64)
        stacked, _ = self._core.allgather_buffer(dims, psid)
        per_rank = np.asarray(stacked, dtype=np.int64).reshape(k, len(entries))
        for j, e in enumerate(entries):
            counts = tuple(int(c) for c in per_rank[:, j])
            maxn = max(max(counts), 1)
            x = jax.device_put(e.device_array, my_dev)
            rest = tuple(x.shape[1:])
            # Explicit row width: a -1 reshape is ambiguous for zero-row
            # contributions (size 0), which the ragged program supports.
            restprod = int(np.prod(rest, dtype=np.int64)) if rest else 1
            row = x.reshape((1, x.shape[0], restprod))
            if x.shape[0] < maxn:
                row = self._pad_rows()(row, maxn)
            garr = self._to_global(mesh, [row])
            fn = self._allgather_program(psid, mesh, x.dtype, counts, rest)
            out = fn(garr)
            e.result = self._shard_on(out, my_dev).reshape(
                (int(sum(counts)),) + rest)
        with self._lock:
            self.stats["allgather"] += 1

    def _exec_alltoall(self, resp, entry) -> None:
        """Device alltoall: split vectors are exchanged as metadata (as in
        allgather), then a cached program performs the exchange — one tiled
        lax.all_to_all when splits are uniform, a pad-to-max exchange when
        ragged.  Mirrors the host plane's validation and recv_splits."""
        import jax

        psid = resp.process_set_id
        members = self._members(psid)
        k = len(members)
        x = entry.device_array
        splits = validate_alltoall_splits(entry.splits, x.shape[0], k)
        if k == 1:
            entry.result = x
            entry.recv_splits = splits.copy()
            with self._lock:
                self.stats["identity"] += 1
            return
        mesh, ranks, my_dev = self._mesh_for(psid)
        my_pos = ranks.index(self._core.rank())
        stacked, _ = self._core.allgather_buffer(splits, psid)
        mat = np.asarray(stacked, dtype=np.int64).reshape(k, k)
        if int(mat.sum()) == 0:  # nothing moves anywhere
            entry.result = x[:0]
            entry.recv_splits = np.zeros((k,), dtype=np.int64)
            with self._lock:
                self.stats["alltoall"] += 1
            return
        splits_mat = tuple(tuple(int(c) for c in row) for row in mat)
        rest = tuple(x.shape[1:])
        x = jax.device_put(x, my_dev)
        restprod = int(np.prod(rest, dtype=np.int64)) if rest else 1
        row = x.reshape((1, x.shape[0], restprod))
        d0max = max(int(mat.sum(axis=1).max()), 1)
        if row.shape[1] < d0max:
            row = self._pad_rows()(row, d0max)
        garr = self._to_global(mesh, [row])
        fn = self._alltoall_program(psid, mesh, x.dtype, splits_mat,
                                    int(row.shape[2]))
        out = fn(garr)
        recv = [int(mat[src, my_pos]) for src in range(k)]
        entry.result = self._shard_on(out, my_dev)[0, :sum(recv)].reshape(
            (sum(recv),) + rest)
        entry.recv_splits = np.asarray(recv, dtype=np.int64)
        with self._lock:
            self.stats["alltoall"] += 1

    def _pad_rows(self):
        """Jitted zero-pad of a [1, n, R] row to [1, target, R] (device-side
        — the no-host-copy guarantee holds through ragged paths too)."""
        if getattr(self, "_pad_fn", None) is None:
            import jax
            import jax.numpy as jnp

            def pad(row, target):
                n = row.shape[1]
                z = jnp.zeros((1, target - n, row.shape[2]), row.dtype)
                return jnp.concatenate([row, z], axis=1)

            self._pad_fn = jax.jit(pad, static_argnums=(1,))
        return self._pad_fn

    def _exec_broadcast(self, resp, entry) -> None:
        import jax

        psid = resp.process_set_id
        members = self._members(psid)
        if len(members) == 1:
            entry.result = entry.device_array
            with self._lock:
                self.stats["identity"] += 1
            return
        mesh, ranks, my_dev = self._mesh_for(psid)
        root_pos = ranks.index(entry.root_rank)
        x = jax.device_put(entry.device_array, my_dev)
        garr = self._to_global(mesh, [x[None]])
        fn = self._broadcast_program(psid, mesh, x.dtype, x.shape, root_pos)
        out = fn(garr)
        entry.result = self._shard_on(out, my_dev).reshape(x.shape)
        with self._lock:
            self.stats["broadcast"] += 1

    # -- global-array plumbing (shared with the simulation tests) ----------
    def _to_global(self, mesh, rows: List):
        """Assemble per-member [1, ...] rows into the (k, ...) global array.
        In production ``rows`` holds this process's single shard; the
        simulation tests (and the dryrun gate) pass one row per mesh device
        of a local mesh — the same code path either way, zero-copy."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        row0 = rows[0]
        k = int(mesh.devices.size)
        sharding = NamedSharding(mesh, P(AXIS, *([None] * (row0.ndim - 1))))
        gshape = (k,) + tuple(row0.shape[1:])
        if len(rows) > 1:
            # Simulation: commit row i to mesh device i.
            rows = [jax.device_put(r, d)
                    for r, d in zip(rows, list(mesh.devices.flat))]
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, rows)

    @staticmethod
    def _shard_on(garr, device):
        """The [1, ...] result shard residing on ``device``."""
        for s in garr.addressable_shards:
            if s.device == device:
                return s.data
        raise HorovodInternalError(
            "device plane result has no shard on the local mesh device")
