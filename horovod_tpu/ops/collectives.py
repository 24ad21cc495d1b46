"""In-jit (traced) collective implementations: the XLA/ICI data plane.

This is the TPU-native replacement for the reference's NCCL ops layer
(horovod/common/ops/nccl_operations.cc — NCCLAllreduce/NCCLAllgather/
NCCLBroadcast/NCCLAlltoall; SURVEY.md §2.2): where NCCL launches ring
kernels on a CUDA stream, here each collective is a ``jax.lax`` primitive
over a named mesh axis that XLA lowers onto ICI — fusion, overlap, and
scheduling come from the compiler rather than hand-managed streams.

These functions are called by ``horovod_tpu.mpi_ops`` when the input is a
JAX tracer (i.e. inside ``jit``/``shard_map``), and may also be used
directly in SPMD training code.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..wire import ReduceOp

AxisName = Union[str, Sequence[str]]


def axis_size(axis_name: AxisName) -> int:
    return lax.axis_size(axis_name)


shard_map = jax.shard_map


def _axes_tuple(axis_name: AxisName):
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def ensure_varying(x, axis_name: AxisName):
    """Cast ``x`` to 'varying' over every requested axis (shard_map vma).

    Classic collective semantics treat the input as this shard's value;
    psum of a replicated value multiplies by the axis size, pmean is the
    identity.  JAX's vma typing instead *rejects* collectives over axes the
    value is invariant on — this cast restores the classic behavior at the
    public API boundary.  (Gradient reduction wants different semantics for
    invariant leaves — see optimizer._tree_allreduce.)
    """
    try:
        vma = jax.typeof(x).vma
    except Exception:
        return x
    missing = tuple(a for a in _axes_tuple(axis_name) if a not in vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def vary_like(a, b):
    """``a``, varying over every mesh axis ``b`` varies over: a scan's carry
    keeps its type and a custom_vjp returns each cotangent in its argument's
    type, so an operand that ``shard_map`` holds replicated is cast before
    it meets a chip's own values, and the cast's transpose sums the chips'
    parts of its gradient."""
    return ensure_varying(a, sorted(jax.typeof(b).vma))


class _Subset:
    """Static geometry of a rank subset over ONE mesh axis — the traced
    process-set bridge (reference: process_set.cc communicator subsetting;
    SURVEY.md §2.1).

    XLA exposes no subgroup collectives through shard_map in current jax
    (``axis_index_groups`` raises NotImplementedError), so subset
    collectives lower onto FULL-axis collectives with identity-masked
    contributions — on ICI the full-axis psum is bandwidth-optimal anyway,
    and every rank of the mesh executes the same SPMD program as shard_map
    requires.  Semantics: member ranks get the set's result; non-member
    ranks pass through unchanged where shapes allow (allreduce, broadcast,
    alltoall), keep their own leading s0/k chunk where the output shape
    shrinks (reducescatter), and receive the set's result where it must be
    uniform (allgather).
    """

    def __init__(self, axis_name: AxisName, member_ranks: Sequence[int]):
        if not isinstance(axis_name, str):
            raise ValueError(
                "process_set collectives run over a single mesh axis; got "
                f"axis_name={axis_name!r}")
        self.axis = axis_name
        self.n = axis_size(axis_name)
        self.members = sorted(set(int(r) for r in member_ranks))
        if not self.members:
            raise ValueError("process set has no members")
        if self.members[0] < 0 or self.members[-1] >= self.n:
            raise ValueError(
                f"process set ranks {self.members} out of range for axis "
                f"{axis_name!r} of size {self.n} (ranks map to axis indices)")
        self.k = len(self.members)
        idx = lax.axis_index(axis_name)
        mset = set(self.members)
        self.is_member = jnp.asarray(
            [i in mset for i in range(self.n)])[idx]
        # Position of this rank within the set (0 for non-members — only
        # ever used behind an is_member select).
        self.pos = jnp.asarray(
            [self.members.index(i) if i in mset else 0
             for i in range(self.n)])[idx]

    def masked(self, x, identity):
        """This rank's contribution: x for members, the op identity else."""
        return jnp.where(self.is_member, x, identity)

    def passthrough(self, result, x):
        """Set result for members; x unchanged for non-members."""
        return jnp.where(self.is_member, result, x)


def allreduce(x, axis_name: AxisName, op: ReduceOp = ReduceOp.AVERAGE,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0,
              member_ranks: Optional[Sequence[int]] = None):
    x = ensure_varying(x, axis_name)
    if member_ranks is not None:
        # Scales apply to the set's result only; non-members pass through
        # UNCHANGED (the documented subset semantics).
        return _subset_allreduce(x, axis_name, op, member_ranks,
                                 prescale_factor, postscale_factor)
    if (op in (ReduceOp.SUM, ReduceOp.AVERAGE)
            and prescale_factor == 1.0 and postscale_factor == 1.0):
        # Device-plane codec auto-dispatch (HOROVOD_WIRE_COMPRESSION
        # device=int8|int4): eligible fp32 payloads ride the
        # block-scaled ring under the configured schedule; everything else
        # falls through bit-identically.  No recursion:
        # quantized_allreduce only calls back here when the same
        # eligibility test fails.
        codec, min_bytes = _device_codec_defaults()
        if _codec_enabled(codec):
            axes = ((axis_name,) if isinstance(axis_name, str)
                    else tuple(axis_name))
            if len(axes) == 1 and quantized_allreduce_eligible(
                    x, axis_size(axes[0]), min_bytes):
                return quantized_allreduce(x, axes[0], op=op,
                                           min_bytes=min_bytes,
                                           codec=codec)
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, dtype=x.dtype)
    if op == ReduceOp.AVERAGE:
        out = lax.pmean(x, axis_name)
    elif op == ReduceOp.SUM:
        out = lax.psum(x, axis_name)
    elif op == ReduceOp.MIN:
        out = lax.pmin(x, axis_name)
    elif op == ReduceOp.MAX:
        out = lax.pmax(x, axis_name)
    elif op == ReduceOp.PRODUCT:
        out = jnp.prod(lax.all_gather(x, axis_name, axis=0), axis=0)
    elif op == ReduceOp.ADASUM:
        out = adasum(x, axis_name)
    else:
        raise ValueError(f"unsupported reduce op {op}")
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, dtype=out.dtype)
    return out


def _reduce_identity(x, op: ReduceOp):
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.ADASUM):
        return jnp.zeros_like(x)
    if op == ReduceOp.PRODUCT:
        return jnp.ones_like(x)
    if x.dtype == jnp.bool_:
        # bool Min == AND (identity True), bool Max == OR (identity False)
        if op == ReduceOp.MIN:
            return jnp.ones_like(x)
        if op == ReduceOp.MAX:
            return jnp.zeros_like(x)
        raise ValueError(f"unsupported reduce op {op}")
    info = (jnp.finfo if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.iinfo)(x.dtype)
    if op == ReduceOp.MIN:
        return jnp.full_like(x, info.max)
    if op == ReduceOp.MAX:
        return jnp.full_like(x, info.min)
    raise ValueError(f"unsupported reduce op {op}")


def _subset_allreduce(x, axis_name: str, op: ReduceOp,
                      member_ranks: Sequence[int],
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0):
    sub = _Subset(axis_name, member_ranks)
    xs = x
    if prescale_factor != 1.0:
        xs = xs * jnp.asarray(prescale_factor, dtype=x.dtype)
    if op == ReduceOp.ADASUM:
        out = adasum(xs, axis_name, member_ranks=sub.members)
    else:
        contrib = sub.masked(xs, _reduce_identity(xs, op))
        if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
            out = lax.psum(contrib, axis_name)
            if op == ReduceOp.AVERAGE:
                out = out / sub.k
        elif op == ReduceOp.MIN:
            out = lax.pmin(contrib, axis_name)
        elif op == ReduceOp.MAX:
            out = lax.pmax(contrib, axis_name)
        elif op == ReduceOp.PRODUCT:
            out = jnp.prod(lax.all_gather(contrib, axis_name, axis=0),
                           axis=0)
        else:
            raise ValueError(f"unsupported reduce op {op}")
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, dtype=out.dtype)
    return sub.passthrough(out, x)


def allgather(x, axis_name: AxisName,
              member_ranks: Optional[Sequence[int]] = None):
    """Concatenate along dim 0 across the axis (Horovod allgather semantics).

    With ``member_ranks``, only the members' shards are concatenated (in
    set order); every rank of the mesh receives that concatenation (the
    output shape must be uniform across the SPMD program)."""
    x = ensure_varying(x, axis_name)
    if member_ranks is None:
        codec, min_bytes = _device_codec_defaults()
        if (_codec_enabled(codec) and isinstance(axis_name, str)
                and getattr(x, "ndim", 0) >= 1
                and quantized_collective_eligible(
                    x, axis_size(axis_name), min_bytes)):
            return quantized_allgather(x, axis_name, min_bytes=min_bytes,
                                       codec=codec)
        return lax.all_gather(x, axis_name, axis=0, tiled=True)
    sub = _Subset(axis_name, member_ranks)
    # One full-axis psum of a [k, s0, ...] buffer in which each member
    # deposits its own shard at its set position (non-members contribute
    # zeros): the rows are disjoint, so the sum IS the member concatenation.
    # Memory and wire bytes are O(k*s0) — not the O(n*s0) of the previous
    # full-axis all_gather + row select, an n/k blowup exactly when the set
    # is small relative to the mesh — and psum's vma semantics make the
    # result axis-invariant (replicated), so out_specs expecting
    # replication keep working.
    row = sub.masked(x, jnp.zeros_like(x))
    # psum converts bool inputs to integers; round-trip through int32 so
    # the output dtype matches the input (as the reference's allgather does).
    calc_dtype = jnp.int32 if x.dtype == jnp.bool_ else x.dtype
    contrib = jnp.zeros((sub.k,) + x.shape, calc_dtype)
    contrib = lax.dynamic_update_slice(
        contrib, row[None].astype(calc_dtype), (sub.pos,) + (0,) * x.ndim)
    full = lax.psum(contrib, axis_name)                # [k, s0, ...]
    return full.reshape(
        (sub.k * x.shape[0],) + x.shape[1:]).astype(x.dtype)


def broadcast(x, root_rank: int, axis_name: AxisName,
              member_ranks: Optional[Sequence[int]] = None):
    """Every member receives root's value (``root_rank`` is the GLOBAL
    rank / axis index, as in the reference's process-set broadcast —
    socket_controller.cc resolves it within the member list).

    Implemented as a masked psum — one collective, no gather of the full
    axis — which XLA lowers to an ICI broadcast-like pattern.
    """
    x = ensure_varying(x, axis_name)
    if member_ranks is None:
        codec, min_bytes = _device_codec_defaults()
        if (_codec_enabled(codec) and isinstance(axis_name, str)
                and quantized_collective_eligible(
                    x, axis_size(axis_name), min_bytes)):
            return quantized_broadcast(x, root_rank, axis_name,
                                       min_bytes=min_bytes, codec=codec)
    idx = lax.axis_index(axis_name)
    sub = None
    if member_ranks is not None:
        sub = _Subset(axis_name, member_ranks)
        if int(root_rank) not in sub.members:
            raise ValueError(
                f"broadcast root {root_rank} is not in the process set "
                f"{sub.members}")
    # where() (not multiply-by-mask) so NaN/Inf in non-root shards are
    # discarded rather than propagated through the sum.
    contribution = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
    out = lax.psum(contribution, axis_name)
    return out if sub is None else sub.passthrough(out, x)


def alltoall(x, axis_name: AxisName,
             member_ranks: Optional[Sequence[int]] = None):
    """Equal-splits alltoall: first dim is split across the axis and the
    received chunks are concatenated along dim 0 (lax.all_to_all).

    With ``member_ranks``, dim 0 is split |set| ways and exchanged among
    the members only; non-members pass through unchanged."""
    x = ensure_varying(x, axis_name)
    if member_ranks is None:
        codec, min_bytes = _device_codec_defaults()
        if (_codec_enabled(codec) and isinstance(axis_name, str)
                and getattr(x, "ndim", 0) >= 1
                and quantized_collective_eligible(
                    x, axis_size(axis_name), min_bytes,
                    divisor=axis_size(axis_name))):
            return quantized_alltoall(x, axis_name, min_bytes=min_bytes,
                                      codec=codec)
        return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)
    sub = _Subset(axis_name, member_ranks)
    s0 = x.shape[0]
    if s0 % sub.k:
        raise ValueError(
            f"alltoall dim 0 ({s0}) must divide by the process-set size "
            f"({sub.k})")
    c = s0 // sub.k
    n = sub.n
    # k ppermute rounds of one [c, ...] chunk each — total bytes moved
    # equal the baseline alltoall (a full-axis all_gather here would be an
    # n-times memory blowup).  In round t, the member at set position p
    # sends its chunk (p+t)%k to the member at position (p+t)%k, who
    # stores it at slot p = (recv_pos - t) % k.  Non-members self-send
    # and are patched through at the end.
    out = jnp.zeros_like(x)
    for t in range(sub.k):
        send_start = ((sub.pos + t) % sub.k) * c
        chunk = lax.dynamic_slice_in_dim(x, send_start, c, axis=0)
        if t == 0:
            moved = chunk
        else:
            pair = {sub.members[p]: sub.members[(p + t) % sub.k]
                    for p in range(sub.k)}
            perm = [(i, pair.get(i, i)) for i in range(n)]
            moved = lax.ppermute(chunk, axis_name, perm)
        recv_start = ((sub.pos - t) % sub.k) * c
        out = lax.dynamic_update_slice_in_dim(out, moved, recv_start,
                                              axis=0)
    return sub.passthrough(out, x)


def reducescatter(x, axis_name: AxisName, op: ReduceOp = ReduceOp.SUM,
                  prescale_factor: float = 1.0, postscale_factor: float = 1.0,
                  member_ranks: Optional[Sequence[int]] = None):
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("in-jit reducescatter supports Sum and Average")
    x = ensure_varying(x, axis_name)
    if member_ranks is not None:
        sub = _Subset(axis_name, member_ranks)
        s0 = x.shape[0]
        if s0 % sub.k:
            raise ValueError(
                f"reducescatter dim 0 ({s0}) must divide by the "
                f"process-set size ({sub.k})")
        c = s0 // sub.k
        xs = x
        if prescale_factor != 1.0:
            xs = xs * jnp.asarray(prescale_factor, dtype=x.dtype)
        summed = lax.psum(sub.masked(xs, jnp.zeros_like(xs)), axis_name)
        out = lax.dynamic_slice_in_dim(summed, sub.pos * c, c, axis=0)
        if op == ReduceOp.AVERAGE:
            out = out / sub.k
        if postscale_factor != 1.0:
            out = out * jnp.asarray(postscale_factor, dtype=out.dtype)
        # Non-members keep their own leading chunk UNSCALED (shape-uniform
        # pass-through analog).
        return jnp.where(sub.is_member, out,
                         lax.slice_in_dim(x, 0, c, axis=0))
    if prescale_factor == 1.0 and postscale_factor == 1.0:
        codec, min_bytes = _device_codec_defaults()
        if (_codec_enabled(codec) and isinstance(axis_name, str)
                and getattr(x, "ndim", 0) >= 1
                and quantized_collective_eligible(
                    x, axis_size(axis_name), min_bytes,
                    divisor=axis_size(axis_name))):
            return quantized_reducescatter(x, axis_name, op=op,
                                           min_bytes=min_bytes, codec=codec)
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, dtype=x.dtype)
    out = lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)
    if op == ReduceOp.AVERAGE:
        out = out / axis_size(axis_name)
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, dtype=out.dtype)
    return out


def adasum(x, axis_name: AxisName,
           member_ranks: Optional[Sequence[int]] = None):
    """Adasum scale-invariant reduction over a mesh axis.

    TPU-native version of the reference's recursive vector-halving/distance-
    doubling Adasum (horovod/common/ops/adasum/adasum.h; SURVEY.md §2.2):
    log2(n) rounds of pairwise combination, each round exchanging partners
    via ``ppermute`` over ICI.  For a pair (a, b):

        adasum(a, b) = (1 - a.b / (2|a|^2)) a + (1 - a.b / (2|b|^2)) b

    Requires the axis size to be a power of two (as the reference does for
    its pure Adasum path).  With ``member_ranks`` the pairwise rounds run
    among the members only (|set| must be a power of two); non-members
    ppermute to themselves, and adasum(a, a) = a leaves them unchanged.
    """
    n = axis_size(axis_name)
    if member_ranks is not None:
        members = sorted(set(int(r) for r in member_ranks))
    else:
        members = list(range(n))
    m = len(members)
    if m & (m - 1) != 0:
        raise ValueError(f"Adasum requires a power-of-two size, got {m}")
    rounds = m.bit_length() - 1
    out = x
    for k in range(rounds):
        stride = 1 << k
        # Pair set-positions p <-> p^stride, mapped back to global axis
        # indices; everyone else exchanges with itself.
        pair = {members[p]: members[p ^ stride] for p in range(m)}
        perm = [(i, pair.get(i, i)) for i in range(n)]
        other = lax.ppermute(out, axis_name, perm)
        a, b = out, other
        dot = jnp.vdot(a, b).astype(jnp.float32)
        na = jnp.vdot(a, a).astype(jnp.float32)
        nb = jnp.vdot(b, b).astype(jnp.float32)
        eps = jnp.asarray(1e-30, jnp.float32)
        ca = (1.0 - dot / (2.0 * jnp.maximum(na, eps))).astype(x.dtype)
        cb = (1.0 - dot / (2.0 * jnp.maximum(nb, eps))).astype(x.dtype)
        combined = ca * a + cb * b
        # Both members of a pair compute the same combined vector (the
        # formula is symmetric), so no extra exchange is needed.
        out = combined
    return out


def barrier(axis_name: AxisName):
    """A collective no-op that forces synchronisation across the axis."""
    token = jnp.zeros((), dtype=jnp.float32)
    return lax.psum(token, axis_name)


# --- Quantized (block-scaled) collectives ----------------------------------

def _device_codec_defaults():
    """(codec, min_bytes) from the live context when initialized, else from
    the environment — trace-time only, never inside the compiled program."""
    try:
        from ..context import HorovodContext
        if HorovodContext.initialized():
            cfg = HorovodContext.instance().cfg
            return (getattr(cfg, "wire_compression_device", "none"),
                    getattr(cfg, "wire_compression_min_bytes", 1 << 16))
    except Exception:
        pass
    from ..utils.env import get_int, get_wire_compression_planes
    return (get_wire_compression_planes()[1],
            get_int("HOROVOD_WIRE_COMPRESSION_MIN_BYTES", 1 << 16))


def _device_schedule_default() -> str:
    """Configured ring schedule (HOROVOD_DEVICE_SCHEDULE / context cfg),
    unresolved — :func:`resolve_device_schedule` turns 'auto' into a
    concrete schedule for a given world size."""
    try:
        from ..context import HorovodContext
        if HorovodContext.initialized():
            return getattr(HorovodContext.instance().cfg,
                           "device_schedule", "auto")
    except Exception:
        pass
    from ..utils.env import get_device_schedule
    return get_device_schedule()


def _codec_enabled(codec: str) -> bool:
    from . import quantize as qz
    return codec != "none" and codec in qz.DEVICE_WIRE_CODECS


def resolve_device_schedule(world: int, schedule: Optional[str] = None) -> str:
    """Resolve a schedule request to a concrete {ring, bidi, torus} for a
    ``world``-rank axis.  ``None`` reads the configured default.

    - ``torus`` demotes to ``bidi`` when ``world`` has no 2-D
      factorization (prime or < 4) — deterministic, never an error;
    - ``auto`` selects from the mesh shape: ``torus`` when a near-square
      factorization with major axis >= 4 exists (pod-slice shapes, where
      O(a+b) chunk-hops beat the 1-D ring's O(n)), ``bidi`` for rings of
      4+ (both ICI directions carry half chunks), plain ``ring``
      otherwise.
    """
    from . import quantize as qz

    if schedule is None:
        schedule = _device_schedule_default()
    s = (schedule or "auto").lower()
    f = qz.torus_factors(world)
    if s == "torus" and f is None:
        s = "bidi"
    if s == "auto":
        if f is not None and f[0] >= 4:
            s = "torus"
        elif world >= 4:
            s = "bidi"
        else:
            s = "ring"
    if s not in ("ring", "bidi", "torus"):
        s = "ring"
    return s


def quantized_collective_eligible(x, world: int, min_bytes: int,
                                  divisor: int = 1) -> bool:
    """Shared demotion rule for every device-plane quantized collective,
    used by the traced path, the optimizer's error-feedback gate, and the
    eager device plane so every layer falls the same way: fp32 only
    (quantizing low-precision or integer payloads either loses exactness
    or gains nothing), at least ``min_bytes`` of payload (small tensors
    are latency-bound and the per-block scale overhead erodes the ratio),
    and a real ring to run on.  ``divisor`` adds the leading-dim
    divisibility requirement of reducescatter/alltoall.
    """
    dtype = getattr(x, "dtype", None)
    shape = tuple(getattr(x, "shape", ()))
    size = 1
    for d in shape:  # static under jit
        size *= int(d)
    if divisor > 1 and (not shape or int(shape[0]) % int(divisor)):
        return False
    return (world > 1 and dtype == jnp.float32
            and size * 4 >= int(min_bytes))


def quantized_allreduce_eligible(x, world: int, min_bytes: int) -> bool:
    """Allreduce instance of :func:`quantized_collective_eligible` (kept
    as its own name — the optimizer and device plane import it)."""
    return quantized_collective_eligible(x, world, min_bytes)


def _tree_permute(payload, axis_name: str, perm):
    """ppermute both leaves of a (codes, scales) payload."""
    return jax.tree_util.tree_map(
        lambda a: lax.ppermute(a, axis_name, perm), payload)


def _ring_reduce_scatter(chunks, axis_name: str, size: int, pos, off: int,
                         d: int, perm, codec: str,
                         interpret: Optional[bool]):
    """Generic quantized ring reduce-scatter over one logical ring.

    ``chunks`` is [size, c] fp32; ``pos`` is this rank's (traced) position
    on the ring; ``perm`` is the ppermute pattern realizing pos -> pos+d.
    The rank at position p starts the partial for row (p + off) % size and
    adds row (p + off - d*t) % size at hop t; after size-1 hops the
    fully-summed row (p + off + d) % size lands on position p.  Each hop
    quantizes the running partial (cpp/wire_codec.h semantics exactly),
    moves codes + scales, and accumulates in fp32 against the receiver's
    own contribution — the ring never adds quantized values together.
    """
    from . import quantize as qz

    c = chunks.shape[1]
    acc = lax.dynamic_index_in_dim(chunks, jnp.mod(pos + off, size), 0,
                                   keepdims=False)
    for t in range(size - 1):
        payload = qz.quantize(acc, codec, interpret)
        payload = _tree_permute(payload, axis_name, perm)
        own = lax.dynamic_index_in_dim(
            chunks, jnp.mod(pos + off - d * (t + 1), size), 0,
            keepdims=False)
        acc = qz.dequantize(payload[0], payload[1], c, codec,
                            interpret) + own
    return acc


def _ring_all_gather(payload, axis_name: str, size: int, pos,
                     owned_off: int, d: int, perm, chunk: int, codec: str,
                     interpret: Optional[bool]):
    """Gather phase: the position-p rank owns the fully-summed row
    (p + owned_off) % size, already ENCODED in ``payload``; encodings are
    forwarded verbatim around the ring, so every rank dequantizes
    identical bytes — the result is bit-identical across ranks (the same
    verbatim-forwarding rule the host codec uses).  Returns [size, chunk]
    fp32."""
    from . import quantize as qz

    out = ensure_varying(jnp.zeros((size, chunk), jnp.float32), axis_name)
    cur = payload
    for t in range(size):
        piece = qz.dequantize(cur[0], cur[1], chunk, codec, interpret)
        out = lax.dynamic_update_index_in_dim(
            out, piece, jnp.mod(pos - d * t + owned_off, size), 0)
        if t < size - 1:
            cur = _tree_permute(cur, axis_name, perm)
    return out


def _ring_all_gather_payload(payload, axis_name: str, size: int, pos,
                             owned_off: int, d: int, perm):
    """Gather ENCODED payloads without decoding: every leaf gains a
    leading ``size`` dim where slot s holds the encoding of ring row s
    (the position-p rank owns row (p + owned_off) % size).  Used by the
    torus schedule to forward stage-2 encodings verbatim through the
    stage-1 gather."""
    def init(leaf):
        return ensure_varying(
            jnp.zeros((size,) + leaf.shape, leaf.dtype), axis_name)

    out = jax.tree_util.tree_map(init, payload)
    cur = payload
    for t in range(size):
        slot = jnp.mod(pos - d * t + owned_off, size)
        out = jax.tree_util.tree_map(
            lambda o, l: lax.dynamic_update_index_in_dim(o, l, slot, 0),
            out, cur)
        if t < size - 1:
            cur = _tree_permute(cur, axis_name, perm)
    return out


def _ring_allreduce_sum(flat, axis_name: str, codec: str,
                        interpret: Optional[bool]):
    """Unidirectional ring: reduce-scatter then all-gather, world-1
    ``ppermute`` hops each, one chunk of ceil(len/world) per hop."""
    from . import quantize as qz

    n = axis_size(axis_name)
    length = flat.shape[0]
    chunk = -(-length // n)
    x = (jnp.pad(flat, (0, n * chunk - length))
         if n * chunk != length else flat)
    chunks = x.reshape(n, chunk)
    me = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = _ring_reduce_scatter(chunks, axis_name, n, me, 0, +1, perm,
                               codec, interpret)
    payload = qz.quantize(acc, codec, interpret)
    out = _ring_all_gather(payload, axis_name, n, me, +1, +1, perm, chunk,
                           codec, interpret)
    return out.reshape(-1)[:length]


def _bidi_ring_allreduce_sum(flat, axis_name: str, codec: str,
                             interpret: Optional[bool]):
    """Bidirectional ring: each chunk splits into a front half riding the
    forward ring and a back half riding the backward ring, so both ICI
    directions of the torus link carry half the bytes per hop
    concurrently (the two streams are data-independent, letting XLA
    overlap them).  Same hop count and per-rank byte totals as the
    unidirectional ring; per-link-direction bytes halve."""
    from . import quantize as qz

    n = axis_size(axis_name)
    length = flat.shape[0]
    chunk = -(-length // n)
    x = (jnp.pad(flat, (0, n * chunk - length))
         if n * chunk != length else flat)
    chunks = x.reshape(n, chunk)
    front = chunk // 2
    me = lax.axis_index(axis_name)
    perm_f = [(i, (i + 1) % n) for i in range(n)]
    perm_b = [(i, (i - 1) % n) for i in range(n)]
    acc_f = _ring_reduce_scatter(chunks[:, :front], axis_name, n, me, 0,
                                 +1, perm_f, codec, interpret)
    acc_b = _ring_reduce_scatter(chunks[:, front:], axis_name, n, me, 0,
                                 -1, perm_b, codec, interpret)
    pf = qz.quantize(acc_f, codec, interpret)
    pb = qz.quantize(acc_b, codec, interpret)
    out_f = _ring_all_gather(pf, axis_name, n, me, +1, +1, perm_f, front,
                             codec, interpret)
    out_b = _ring_all_gather(pb, axis_name, n, me, -1, -1, perm_b,
                             chunk - front, codec, interpret)
    out = jnp.concatenate([out_f, out_b], axis=1)
    return out.reshape(-1)[:length]


def _torus_allreduce_sum(flat, axis_name: str, a: int, b: int, codec: str,
                         interpret: Optional[bool]):
    """2-D torus decomposition over an a x b logical mesh (rank = i*b + j;
    a = major axis, b = minor axis): reduce-scatter along the minor axis
    (rings of size b within each major row), then along the major axis
    (rings of size a within each column), gather in reverse.  O(a+b)
    chunk-hops instead of the 1-D ring's O(ab), per the MLPerf TPU-pod
    schedule.

    Quantization points: every reduce-scatter hop re-encodes its running
    fp32 partial (stage 1 on ceil(len/b) chunks, stage 2 on
    ceil(len/(a*b))-ish sub-chunks); the globally-summed sub-chunk is
    then encoded ONCE and both gather phases forward that encoding
    verbatim — the stage-1 gather moves the stacked stage-2 payloads as
    opaque bytes — so every rank decodes identical bytes and the result
    is bit-identical across all a*b ranks."""
    from . import quantize as qz

    n = a * b
    length = flat.shape[0]
    me = lax.axis_index(axis_name)
    row_pos = jnp.mod(me, b)       # position on the minor-axis ring (j)
    col_pos = me // b              # position on the major-axis ring (i)
    c1 = -(-length // b)
    x = (jnp.pad(flat, (0, b * c1 - length))
         if b * c1 != length else flat)
    rows = x.reshape(b, c1)
    perm_row = [(g, (g // b) * b + ((g % b) + 1) % b) for g in range(n)]
    perm_col = [(g, ((g // b + 1) % a) * b + (g % b)) for g in range(n)]

    # Stage 1: minor-axis reduce-scatter; rank (i, j) ends with minor
    # chunk (j+1) % b summed over its major row.
    acc1 = _ring_reduce_scatter(rows, axis_name, b, row_pos, 0, +1,
                                perm_row, codec, interpret)
    # Stage 2: major-axis reduce-scatter of that chunk; rank (i, j) ends
    # with sub-chunk (i+1) % a of minor chunk (j+1) % b, globally summed.
    c2 = -(-c1 // a)
    y = jnp.pad(acc1, (0, a * c2 - c1)) if a * c2 != c1 else acc1
    sub_rows = y.reshape(a, c2)
    acc2 = _ring_reduce_scatter(sub_rows, axis_name, a, col_pos, 0, +1,
                                perm_col, codec, interpret)

    # Gather in reverse, forwarding encodings verbatim.
    payload2 = qz.quantize(acc2, codec, interpret)
    stacked2 = _ring_all_gather_payload(payload2, axis_name, a, col_pos,
                                        +1, +1, perm_col)
    stacked1 = _ring_all_gather_payload(stacked2, axis_name, b, row_pos,
                                        +1, +1, perm_row)
    # stacked1 leaves are [b, a, ...]: slot (m, s) = the encoding of
    # sub-chunk s of minor chunk m.
    pieces = []
    for m in range(b):
        for s in range(a):
            leaf = jax.tree_util.tree_map(
                lambda l, _m=m, _s=s: l[_m, _s], stacked1)
            pieces.append(qz.dequantize(leaf[0], leaf[1], c2, codec,
                                        interpret))
    out = jnp.stack(pieces).reshape(b, a * c2)[:, :c1]
    return out.reshape(-1)[:length]


def _quantized_ring_allreduce_sum(flat, axis_name: str,
                                  interpret: Optional[bool] = None,
                                  codec: str = "int8",
                                  schedule: str = "ring"):
    """Block-scaled allreduce of a flat fp32 vector over ONE mesh axis
    (the traced mirror of the host ring's wire codecs), dispatching on
    ``schedule`` — 'ring' (unidirectional), 'bidi', or 'torus'.  Demotes
    deterministically: torus -> bidi when the world has no 2-D
    factorization, bidi -> ring when chunks are too short to split
    (mirrored by quantize.ring_bytes so byte accounting stays exact)."""
    from . import quantize as qz

    n = axis_size(axis_name)
    if schedule == "torus":
        f = qz.torus_factors(n)
        if f is None:
            schedule = "bidi"
        else:
            return _torus_allreduce_sum(flat, axis_name, f[0], f[1],
                                        codec, interpret)
    chunk = -(-flat.shape[0] // n)
    if schedule == "bidi" and chunk >= 2:
        return _bidi_ring_allreduce_sum(flat, axis_name, codec, interpret)
    return _ring_allreduce_sum(flat, axis_name, codec, interpret)


def quantized_allreduce(x, axis_name: AxisName,
                        op: ReduceOp = ReduceOp.SUM,
                        min_bytes: Optional[int] = None,
                        codec: Optional[str] = None,
                        schedule: Optional[str] = None,
                        interpret: Optional[bool] = None):
    """Allreduce through the block-scaled ring when ``x`` is eligible;
    otherwise demotes to the plain (uncompressed) collective, bit-identical
    to :func:`allreduce`.

    ``min_bytes=None`` reads HOROVOD_WIRE_COMPRESSION_MIN_BYTES (context
    config when initialized); ``codec=None`` reads the configured device
    codec (falling back to int8 when the config says none — an explicit
    call asks for quantization); ``schedule=None`` reads
    HOROVOD_DEVICE_SCHEDULE and resolves 'auto' from the axis size.  Byte
    accounting (``data_plane_stats()['device_raw'/'device_encoded']``) is
    recorded per trace — under ``jax.jit`` cache reuse the program moves
    the same bytes every call, so the per-trace note is the per-call wire
    cost.
    """
    from . import quantize as qz

    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"quantized_allreduce supports Sum and Average, got {op}")
    if min_bytes is None:
        min_bytes = _device_codec_defaults()[1]
    axes = _axes_tuple(axis_name)
    world = 1
    for a in axes:
        world *= axis_size(a)
    if (len(axes) != 1
            or not quantized_allreduce_eligible(x, world, min_bytes)):
        return allreduce(x, axis_name, op=op)
    if codec is None:
        codec = _device_codec_defaults()[0]
    if not _codec_enabled(codec):
        codec = "int8"
    sched = resolve_device_schedule(world, schedule)
    x = ensure_varying(x, axes[0])
    out = _quantized_ring_allreduce_sum(
        x.reshape(-1).astype(jnp.float32), axes[0], interpret, codec,
        sched)
    raw, encoded = qz.ring_bytes(x.size, world, codec, sched)
    qz.note_device_bytes(raw, encoded)
    if op == ReduceOp.AVERAGE:
        out = out / world
    return out.reshape(x.shape)


def _resolve_explicit_codec(codec: Optional[str]) -> str:
    """Codec for a direct quantized_* call: the configured device codec,
    falling back to int8 when the config says none (calling a quantized
    collective explicitly asks for quantization)."""
    if codec is None:
        codec = _device_codec_defaults()[0]
    if not _codec_enabled(codec):
        codec = "int8"
    return codec


def quantized_allgather(x, axis_name: AxisName,
                        min_bytes: Optional[int] = None,
                        codec: Optional[str] = None,
                        interpret: Optional[bool] = None):
    """Allgather with block-scaled encoding: each rank quantizes its shard
    ONCE, the encoded (codes, scales) payload rides ``lax.all_gather``,
    and every rank — including the owner — dequantizes all world shards
    from the same bytes, so the result is bit-identical across ranks.
    Ineligible inputs demote to :func:`allgather` bit-identically."""
    from . import quantize as qz

    if min_bytes is None:
        min_bytes = _device_codec_defaults()[1]
    axes = _axes_tuple(axis_name)
    world = 1
    for a in axes:
        world *= axis_size(a)
    if (len(axes) != 1 or getattr(x, "ndim", 0) < 1
            or not quantized_collective_eligible(x, world, min_bytes)):
        return allgather(x, axis_name)
    codec = _resolve_explicit_codec(codec)
    ax = axes[0]
    x = ensure_varying(x, ax)
    flat = x.reshape(-1)
    length = flat.shape[0]
    payload = qz.quantize(flat, codec, interpret)
    gathered = jax.tree_util.tree_map(
        lambda l: lax.all_gather(l, ax, axis=0), payload)
    shards = []
    for r in range(world):
        pr = jax.tree_util.tree_map(lambda l, _r=r: l[_r], gathered)
        shards.append(qz.dequantize(pr[0], pr[1], length, codec,
                                    interpret))
    out = jnp.stack(shards)                       # [world, length]
    qz.note_device_bytes((world - 1) * length * 4,
                         (world - 1) * qz.encoded_nbytes(length, codec))
    return out.reshape((world * x.shape[0],) + x.shape[1:])


def quantized_broadcast(x, root_rank: int, axis_name: AxisName,
                        min_bytes: Optional[int] = None,
                        codec: Optional[str] = None,
                        interpret: Optional[bool] = None):
    """Broadcast of the root's block-scaled encoding: the root quantizes,
    a masked psum moves the encoded payload (only the root contributes,
    so the summed codes/scales ARE the root's bytes — no overflow), and
    every rank — the root included — dequantizes the same encoding.  The
    result is bit-identical across ranks and within one quantization step
    (<= scale/2 per element) of the root's value, EQuARX's broadcast
    semantics.  Ineligible inputs demote to :func:`broadcast`
    bit-identically."""
    from . import quantize as qz

    if min_bytes is None:
        min_bytes = _device_codec_defaults()[1]
    axes = _axes_tuple(axis_name)
    world = 1
    for a in axes:
        world *= axis_size(a)
    if (len(axes) != 1
            or not quantized_collective_eligible(x, world, min_bytes)):
        return broadcast(x, root_rank, axis_name)
    codec = _resolve_explicit_codec(codec)
    ax = axes[0]
    x = ensure_varying(x, ax)
    flat = x.reshape(-1)
    length = flat.shape[0]
    idx = lax.axis_index(ax)
    payload = qz.quantize(flat, codec, interpret)
    payload = jax.tree_util.tree_map(
        lambda l: lax.psum(
            jnp.where(idx == root_rank, l, jnp.zeros_like(l)), ax),
        payload)
    out = qz.dequantize(payload[0], payload[1], length, codec, interpret)
    qz.note_device_bytes(length * 4, qz.encoded_nbytes(length, codec))
    return out.reshape(x.shape)


def quantized_alltoall(x, axis_name: AxisName,
                       min_bytes: Optional[int] = None,
                       codec: Optional[str] = None,
                       interpret: Optional[bool] = None):
    """Alltoall with block-scaled encoding — the MoE dispatch/combine
    path.  Each rank quantizes its world destination chunks separately
    (so every chunk decodes from its own scales), the stacked encodings
    ride ``lax.all_to_all``, and each received chunk is dequantized on
    arrival: exactly one quantization step end to end.  Ineligible inputs
    (wrong dtype, too small, or dim 0 not divisible by the axis size)
    demote to :func:`alltoall` bit-identically."""
    from . import quantize as qz

    if min_bytes is None:
        min_bytes = _device_codec_defaults()[1]
    axes = _axes_tuple(axis_name)
    world = 1
    for a in axes:
        world *= axis_size(a)
    if (len(axes) != 1 or getattr(x, "ndim", 0) < 1
            or not quantized_collective_eligible(x, world, min_bytes,
                                                 divisor=world)):
        return alltoall(x, axis_name)
    codec = _resolve_explicit_codec(codec)
    ax = axes[0]
    x = ensure_varying(x, ax)
    rows = x.reshape(world, -1)                   # destination chunks
    c = rows.shape[1]
    payloads = [qz.quantize(rows[r], codec, interpret)
                for r in range(world)]
    stacked = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *payloads)
    swapped = jax.tree_util.tree_map(
        lambda l: lax.all_to_all(l, ax, split_axis=0, concat_axis=0,
                                 tiled=True),
        stacked)
    parts = []
    for r in range(world):
        pr = jax.tree_util.tree_map(lambda l, _r=r: l[_r], swapped)
        parts.append(qz.dequantize(pr[0], pr[1], c, codec, interpret))
    out = jnp.stack(parts).reshape(-1)[:x.size]
    qz.note_device_bytes((world - 1) * c * 4,
                         (world - 1) * qz.encoded_nbytes(c, codec))
    return out.reshape(x.shape)


def quantized_reducescatter(x, axis_name: AxisName,
                            op: ReduceOp = ReduceOp.SUM,
                            min_bytes: Optional[int] = None,
                            codec: Optional[str] = None,
                            interpret: Optional[bool] = None):
    """Reduce-scatter through the block-scaled ring: the reduce-scatter
    half of the quantized allreduce (world-1 hops, fp32 accumulation
    between hops), offset so rank r ends owning its own leading-dim
    chunk.  Sum and Average only; ineligible inputs demote to
    :func:`reducescatter` bit-identically."""
    from . import quantize as qz

    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"quantized_reducescatter supports Sum and Average, got {op}")
    if min_bytes is None:
        min_bytes = _device_codec_defaults()[1]
    axes = _axes_tuple(axis_name)
    world = 1
    for a in axes:
        world *= axis_size(a)
    if (len(axes) != 1 or getattr(x, "ndim", 0) < 1
            or not quantized_collective_eligible(x, world, min_bytes,
                                                 divisor=world)):
        return reducescatter(x, axis_name, op=op)
    codec = _resolve_explicit_codec(codec)
    ax = axes[0]
    x = ensure_varying(x, ax)
    rows = x.reshape(world, -1).astype(jnp.float32)
    c = rows.shape[1]
    me = lax.axis_index(ax)
    perm = [(i, (i + 1) % world) for i in range(world)]
    # off=-1: rank r starts the partial for row (r-1) % world, so after
    # world-1 hops the fully-summed row r lands on rank r — its own
    # scatter chunk.
    acc = _ring_reduce_scatter(rows, ax, world, me, -1, +1, perm, codec,
                               interpret)
    qz.note_device_bytes((world - 1) * c * 4,
                         (world - 1) * qz.encoded_nbytes(c, codec))
    if op == ReduceOp.AVERAGE:
        acc = acc / world
    return acc.reshape((x.shape[0] // world,) + x.shape[1:])
