"""A selective scan (Mamba-1's recurrence) as Pallas TPU kernels, forward and
backward, on the model's own channel-minor ``[B, S, C]`` layout.

With ``u``, ``dt`` [B, S, C], ``A`` [C, N], ``B``, ``C`` [B, S, N], ``D`` [C]
and a state ``s`` [C, N] a sequence that starts at zero::

    s_t = exp(dt_t (x) A) * s_(t-1) + (dt_t * u_t) (x) B_t
    y_t = s_t . C_t + D * u_t

The decay is one number a channel **and** a state, so the recurrence does not
turn into matrix products the way a scan with one decay a head does: it is
elementwise (VPU) and exponential (EUP) work over the state, a time step
after the other.  Written with ``lax.associative_scan`` it would keep ``[S,
C, N]`` float32 arrays in HBM; here the state never leaves VMEM.

The scheme.  The grid is (batch, time chunks, channel blocks), channels
innermost: a grid step runs :data:`CHUNK` time steps of ``block`` channels
(whole 128-lane tiles).  The state lies **states on sublanes, channels on
lanes** (``[N, 128]`` a lane tile: two vregs at N = 16), is carried through a
chunk in registers and from a chunk to the next in a VMEM scratch that holds
every block's.  ``dt_t`` and ``u_t`` are rows of the arrays as they come,
spread over the sublanes; ``B_t`` and ``C_t`` have to be spread over the
*lanes*, which is done once a chunk (the first channel block's step fills
two ``[chunk, N, 128]`` scratches from the chunk of ``B^T``, ``C^T`` and the
other blocks read them).  A chunk's ``y`` rows gather in a float32 scratch
and leave in ``u``'s dtype, ``D * u`` added, in one pass.

The forward's one residual is **the state at each chunk's start** (``S /
chunk x C x N`` float32: 5 MB at 16,384 x 1,280 x 16 and chunks of 256).  The
backward walks the chunks last to first: it rebuilds a chunk's states and
decays from that start into VMEM, then walks the chunk in reverse with the
adjoint state carried the other way.  ``dA`` and ``dD`` gather in float32
across chunks in their (resident) output blocks; ``dB`` and ``dC`` are sums
over the channels, which lie on the lanes, so the kernel writes them
lane-wise (``[B, S, N, 128]`` float32, the channel blocks added in the
resident block) and the last sum over 128 lanes is XLA's.

Off the TPU :func:`selective_scan` is :func:`selective_scan_reference`, a
``lax.scan`` over time that autodiff differentiates; ``interpret=True`` runs
the kernels through the Pallas interpreter (``tests/single/
test_selective_scan.py``).  The calls are named ``hvd_ssm_scan_fwd`` and
``hvd_ssm_scan_bwd`` under the scope ``hvd_ssm_scan`` (what XLA does around
them, the transposes of ``B`` and ``C`` and the last sums, under
``hvd_ssm_mix``) and declare ``vma`` on their outputs, so they run inside
``shard_map``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .collectives import vary_like as _vary_like
from .grouped_matmul import _out_struct

LANES = 128
SUBLANES = 8
# Time steps a grid step.  The backward keeps a chunk's states and decays in
# VMEM: 2 x chunk x N x block x 4 bytes (21 MB at 128, 16, 1280).
CHUNK = 128
# The most channels a grid step; a call takes the largest whole number of
# lane tiles under it that divides its channels.  On a v5e at [1, 16384,
# 1280] x 16 states the blocks from 256 to 1280 read within 4 % of each
# other and 128 reads 12 % slower (PERF.md section 6, PR 47).
BLOCK = 1280
_VMEM_LIMIT = 64 * 1024 * 1024
_F32 = jnp.float32


def selective_scan_reference(u, dt, A, B, C, D):
    """The recurrence as a ``lax.scan`` over time, float32 throughout; ``y``
    in ``u``'s dtype."""
    f = lambda x: x.astype(_F32)  # noqa: E731
    u32, dt32, A, D = f(u), f(dt), f(A), f(D)

    def step(state, row):
        u_t, dt_t, b_t, c_t = row             # [B, C] [B, C] [B, N] [B, N]
        state = (jnp.exp(dt_t[..., None] * A) * state
                 + (dt_t * u_t)[..., None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + D * u_t

    rows = tuple(jnp.swapaxes(x, 0, 1) for x in (u32, dt32, f(B), f(C)))
    start = _vary_like(jnp.zeros((u.shape[0], *A.shape), _F32), u)
    _, y = lax.scan(step, start, rows)
    return jnp.swapaxes(y, 0, 1).astype(u.dtype)


def _spread_over_lanes(src_ref, dst_ref, chunk: int):
    """``dst[t, n, :] = src[0, n, t]``: each time step's column of the chunk
    of ``B^T`` (or ``C^T``) spread over the 128 lanes."""
    n = src_ref.shape[1]
    for first in range(0, chunk, LANES):
        width = min(LANES, chunk - first)
        tile = src_ref[0, :, first:first + width].astype(_F32)
        for t in range(width):
            dst_ref[first + t] = jnp.broadcast_to(tile[:, t:t + 1],
                                                  (n, LANES))


def _lanes(m: int) -> slice:
    return slice(m * LANES, (m + 1) * LANES)


def _rows(ref, base, tiles):
    """Eight rows of a float32 ``[chunk, block]`` scratch from ``base`` on,
    a lane tile each."""
    return [ref[pl.ds(base, SUBLANES), _lanes(m)] for m in range(tiles)]


def _over_sublanes(rows8, j: int, n: int):
    """Row ``j`` of an ``[8, 128]`` tile spread over ``n`` sublanes."""
    return jnp.broadcast_to(rows8[j:j + 1, :], (n, LANES))


def _row_into(tile, j: int, row):
    """``tile`` [8, 128] with its row ``j`` set to ``row`` [1, 128]: a
    chunk's rows are stored eight at a time, a whole tile (Mosaic stores no
    single row at an offset it does not know)."""
    spread = jnp.broadcast_to(row, tile.shape)
    rows = lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.where(rows == j, spread, tile)


def _fwd_kernel(u_ref, dt_ref, at_ref, bt_ref, ct_ref, d_ref, y_ref, hs_ref,
                h_ref, bb_ref, cb_ref, dtf_ref, x_ref, ys_ref, *,
                chunk: int, tiles: int):
    i, k = pl.program_id(1), pl.program_id(2)
    n = at_ref.shape[0]

    @pl.when(k == 0)
    def _new_chunk():
        _spread_over_lanes(bt_ref, bb_ref, chunk)
        _spread_over_lanes(ct_ref, cb_ref, chunk)

    @pl.when(i == 0)
    def _new_sequence():
        h_ref[k] = jnp.zeros(h_ref.shape[1:], _F32)

    hs_ref[0, 0, 0] = h_ref[k]
    u = u_ref[0].astype(_F32)
    dtf_ref[...] = dt_ref[0].astype(_F32)
    x_ref[...] = dtf_ref[...] * u
    a = [at_ref[:, _lanes(m)].astype(_F32) for m in range(tiles)]

    def group(g, h):
        base = pl.multiple_of(g * SUBLANES, SUBLANES)
        dt8, x8 = _rows(dtf_ref, base, tiles), _rows(x_ref, base, tiles)
        y8 = [jnp.zeros((SUBLANES, LANES), _F32)] * tiles
        for j in range(SUBLANES):
            bb, cb = bb_ref[base + j], cb_ref[base + j]
            new = []
            for m in range(tiles):
                hm = (jnp.exp(_over_sublanes(dt8[m], j, n) * a[m]) * h[m]
                      + bb * _over_sublanes(x8[m], j, n))
                y8[m] = _row_into(y8[m], j, jnp.sum(hm * cb, axis=0,
                                                    keepdims=True))
                new.append(hm)
            h = tuple(new)
        for m in range(tiles):
            ys_ref[pl.ds(base, SUBLANES), _lanes(m)] = y8[m]
        return h

    h = lax.fori_loop(0, chunk // SUBLANES, group,
                      tuple(h_ref[k, :, _lanes(m)] for m in range(tiles)))
    for m in range(tiles):
        h_ref[k, :, _lanes(m)] = h[m]
    y_ref[0] = (ys_ref[...] + d_ref[...].astype(_F32) * u).astype(y_ref.dtype)


def _bwd_kernel(u_ref, dt_ref, at_ref, bt_ref, ct_ref, d_ref, dy_ref, hs_ref,
                du_ref, ddt_ref, da_ref, dd_ref, dbp_ref, dcp_ref,
                q_ref, bb_ref, cb_ref, hh_ref, aa_ref, dtf_ref, x_ref,
                dyf_ref, gb_ref, wa_ref, *, chunk: int, tiles: int):
    i, k = pl.program_id(1), pl.program_id(2)
    n = at_ref.shape[0]
    groups = chunk // SUBLANES

    @pl.when(k == 0)
    def _new_chunk():
        _spread_over_lanes(bt_ref, bb_ref, chunk)
        _spread_over_lanes(ct_ref, cb_ref, chunk)
        dbp_ref[...] = jnp.zeros(dbp_ref.shape, _F32)
        dcp_ref[...] = jnp.zeros(dcp_ref.shape, _F32)

    @pl.when(i == 0)        # the sequence's last chunk: nothing comes back
    def _new_sequence():
        q_ref[k] = jnp.zeros(q_ref.shape[1:], _F32)
        da_ref[0, k] = jnp.zeros(da_ref.shape[2:], _F32)
        dd_ref[0, k] = jnp.zeros(dd_ref.shape[2:], _F32)

    u = u_ref[0].astype(_F32)
    dtf_ref[...] = dt_ref[0].astype(_F32)
    x_ref[...] = dtf_ref[...] * u
    dyf_ref[...] = dy_ref[0].astype(_F32)
    a = [at_ref[:, _lanes(m)].astype(_F32) for m in range(tiles)]

    # The chunk's states and decays again, from the state at its start:
    # hh[t] is the state before step t, hh[t + 1] after it.  (A loop's
    # carry starts from scratch or from zeros, never from an operand's or a
    # result's ref: under ``shard_map`` those are typed as varying over the
    # mesh and a kernel's own arithmetic is not.)
    hh_ref[0] = hs_ref[0, 0, 0]

    def rebuild(g, h):
        base = pl.multiple_of(g * SUBLANES, SUBLANES)
        dt8, x8 = _rows(dtf_ref, base, tiles), _rows(x_ref, base, tiles)
        for j in range(SUBLANES):
            bb = bb_ref[base + j]
            new = []
            for m in range(tiles):
                decay = jnp.exp(_over_sublanes(dt8[m], j, n) * a[m])
                hm = decay * h[m] + bb * _over_sublanes(x8[m], j, n)
                aa_ref[base + j, :, _lanes(m)] = decay
                hh_ref[base + j + 1, :, _lanes(m)] = hm
                new.append(hm)
            h = tuple(new)
        return h

    lax.fori_loop(0, groups, rebuild,
                  tuple(hh_ref[0, :, _lanes(m)] for m in range(tiles)))

    # In reverse.  g_t = C_t (x) dy_t + q_(t+1), q_t = decay_t * g_t is what
    # the step before adds to its own; d decay_t = g_t * s_(t-1).
    def reverse(r, carry):
        q, da = carry
        base = pl.multiple_of((groups - 1 - r) * SUBLANES, SUBLANES)
        dt8, x8 = _rows(dtf_ref, base, tiles), _rows(x_ref, base, tiles)
        dy8 = _rows(dyf_ref, base, tiles)
        wa8 = [jnp.zeros((SUBLANES, LANES), _F32)] * tiles
        gb8 = list(wa8)
        for j in reversed(range(SUBLANES)):
            t = base + j
            bb, cb = bb_ref[t], cb_ref[t]
            new_q, new_da, part_b, part_c = [], [], None, None
            for m in range(tiles):
                dyb = _over_sublanes(dy8[m], j, n)
                gm = cb * dyb + q[m]
                qm = aa_ref[t, :, _lanes(m)] * gm
                wm = qm * hh_ref[t, :, _lanes(m)]
                new_da.append(da[m] + wm * _over_sublanes(dt8[m], j, n))
                wa8[m] = _row_into(wa8[m], j, jnp.sum(wm * a[m], axis=0,
                                                      keepdims=True))
                gb8[m] = _row_into(gb8[m], j, jnp.sum(gm * bb, axis=0,
                                                      keepdims=True))
                pb = gm * _over_sublanes(x8[m], j, n)
                pc = hh_ref[t + 1, :, _lanes(m)] * dyb
                part_b = pb if part_b is None else part_b + pb
                part_c = pc if part_c is None else part_c + pc
                new_q.append(qm)
            dbp_ref[0, t] += part_b
            dcp_ref[0, t] += part_c
            q, da = tuple(new_q), tuple(new_da)
        for m in range(tiles):
            wa_ref[pl.ds(base, SUBLANES), _lanes(m)] = wa8[m]
            gb_ref[pl.ds(base, SUBLANES), _lanes(m)] = gb8[m]
        return q, da

    q, da = lax.fori_loop(
        0, groups, reverse,
        (tuple(q_ref[k, :, _lanes(m)] for m in range(tiles)),
         (jnp.zeros((n, LANES), _F32),) * tiles))
    for m in range(tiles):
        q_ref[k, :, _lanes(m)] = q[m]
        da_ref[0, k, :, _lanes(m)] += da[m]
    # gb is d (dt * u): through the product to both, and D * u's own.
    dy = dyf_ref[...]
    du_ref[0] = (gb_ref[...] * dtf_ref[...]
                 + d_ref[...].astype(_F32) * dy).astype(du_ref.dtype)
    ddt_ref[0] = (wa_ref[...] + gb_ref[...] * u).astype(ddt_ref.dtype)
    dd_ref[0, k] += jnp.sum(dy * u, axis=0, keepdims=True)


def plan(seq: int, channels: int, chunk: Optional[int],
          block: Optional[int]) -> tuple:
    """``(chunk, padded length, block)``: whole 128-lane tiles a block,
    whole 128-step tiles a chunk unless one chunk holds the sequence."""
    if channels % LANES:
        raise ValueError(
            f"selective_scan: channels = {channels} is no multiple of "
            f"{LANES}: a channel block is whole lane tiles")
    if block is None:
        block = max(b for b in range(LANES, min(BLOCK, channels) + 1, LANES)
                    if channels % b == 0)
    if block % LANES or channels % block:
        raise ValueError(
            f"selective_scan: block = {block} has to be a multiple of "
            f"{LANES} that divides channels = {channels}")
    chunk = chunk or CHUNK
    if seq <= chunk:
        chunk = -(-seq // SUBLANES) * SUBLANES
    elif chunk % LANES:
        raise ValueError(f"selective_scan: chunk = {chunk} has to be a "
                         f"multiple of {LANES} (sequence of {seq})")
    return chunk, -(-seq // chunk) * chunk, block


def _pad_time(x, axis: int, length: int):
    """Zeros after the sequence: ``dt = 0`` leaves the state as it is."""
    short = length - x.shape[axis]
    if not short:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, short)
    return jnp.pad(x, pad)


def _operands(u, dt, A, B, C, D, padded: int):
    return (_pad_time(u, 1, padded), _pad_time(dt, 1, padded), A.T,
            _pad_time(jnp.swapaxes(B, 1, 2), 2, padded),
            _pad_time(jnp.swapaxes(C, 1, 2), 2, padded), D.reshape(1, -1))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _in_specs(chunk, block, n, time):
    """u, dt, A^T, B^T, C^T, D; ``time(i)`` is the chunk grid step i takes."""
    rows = pl.BlockSpec((1, chunk, block), lambda b, i, k: (b, time(i), k))
    cols = pl.BlockSpec((1, n, chunk), lambda b, i, k: (b, 0, time(i)))
    return [rows, rows, pl.BlockSpec((n, block), lambda b, i, k: (0, k)),
            cols, cols, pl.BlockSpec((1, block), lambda b, i, k: (0, k))]


# The two calls are jitted and NOT inlined: a model of many layers traces
# and lowers each kernel once a shape, not once a call (a kernel's body is
# some ten thousand equations: 39 calls of them were 38 s of a step's
# lowering, PERF.md section 6, PR 47).
@functools.partial(jax.jit, static_argnames=("chunk", "block", "interpret"))
def _fwd_call(*ops, chunk: int, block: int, interpret: bool):
    u, a_t = ops[0], ops[2]
    batch, padded, channels = u.shape
    n = a_t.shape[0]
    chunks, blocks, tiles = padded // chunk, channels // block, block // LANES
    wide = lambda: pltpu.VMEM((chunk, block), _F32)  # noqa: E731
    with jax.named_scope("hvd_ssm_scan"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, chunk=chunk, tiles=tiles),
            name="hvd_ssm_scan_fwd",
            grid=(batch, chunks, blocks),
            in_specs=_in_specs(chunk, block, n, lambda i: i),
            out_specs=[
                pl.BlockSpec((1, chunk, block), lambda b, i, k: (b, i, k)),
                pl.BlockSpec((1, 1, 1, n, block),
                             lambda b, i, k: (b, i, k, 0, 0))],
            out_shape=[
                _out_struct((batch, padded, channels), u.dtype, *ops),
                _out_struct((batch, chunks, blocks, n, block), _F32, *ops)],
            scratch_shapes=[
                pltpu.VMEM((blocks, n, block), _F32),
                pltpu.VMEM((chunk, n, LANES), _F32),
                pltpu.VMEM((chunk, n, LANES), _F32), wide(), wide(), wide()],
            compiler_params=_params(), interpret=interpret)(*ops)


@functools.partial(jax.jit, static_argnames=("chunk", "block", "interpret"))
def _bwd_call(*ops, chunk: int, block: int, interpret: bool):
    u, dt, a_t = ops[:3]
    batch, padded, channels = u.shape
    n = a_t.shape[0]
    chunks, blocks, tiles = padded // chunk, channels // block, block // LANES
    back = lambda i: chunks - 1 - i  # noqa: E731
    rows = pl.BlockSpec((1, chunk, block), lambda b, i, k: (b, back(i), k))
    held = lambda *shape: pl.BlockSpec(  # noqa: E731
        (1, *shape), lambda b, i, k: (b,) + (0,) * len(shape))
    lanewise = pl.BlockSpec((1, chunk, n, LANES),
                            lambda b, i, k: (b, back(i), 0, 0))
    wide = lambda: pltpu.VMEM((chunk, block), _F32)  # noqa: E731
    with jax.named_scope("hvd_ssm_scan"):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, chunk=chunk, tiles=tiles),
            name="hvd_ssm_scan_bwd",
            grid=(batch, chunks, blocks),
            in_specs=_in_specs(chunk, block, n, back) + [
                rows, pl.BlockSpec((1, 1, 1, n, block),
                                   lambda b, i, k: (b, back(i), k, 0, 0))],
            out_specs=[rows, rows, held(blocks, n, block),
                       held(blocks, 1, block), lanewise, lanewise],
            out_shape=[
                _out_struct((batch, padded, channels), u.dtype, *ops),
                _out_struct((batch, padded, channels), dt.dtype, *ops),
                _out_struct((batch, blocks, n, block), _F32, *ops),
                _out_struct((batch, blocks, 1, block), _F32, *ops),
                _out_struct((batch, padded, n, LANES), _F32, *ops),
                _out_struct((batch, padded, n, LANES), _F32, *ops)],
            scratch_shapes=[
                pltpu.VMEM((blocks, n, block), _F32),
                pltpu.VMEM((chunk, n, LANES), _F32),
                pltpu.VMEM((chunk, n, LANES), _F32),
                pltpu.VMEM((chunk + 1, n, block), _F32),
                pltpu.VMEM((chunk, n, block), _F32),
                wide(), wide(), wide(), wide(), wide()],
            compiler_params=_params(), interpret=interpret)(*ops)


def _scan_fwd(u, dt, A, B, C, D, chunk, block, interpret):
    seq, channels = u.shape[1:]
    chunk, padded, block = plan(seq, channels, chunk, block)
    with jax.named_scope("hvd_ssm_mix"):
        ops = _operands(u, dt, A, B, C, D, padded)
    y, starts = _fwd_call(*ops, chunk=chunk, block=block,
                          interpret=interpret)
    return y[:, :seq], starts


def _scan_bwd(u, dt, A, B, C, D, starts, dy, chunk, block, interpret):
    seq, channels = u.shape[1:]
    n = A.shape[1]
    chunk, padded, block = plan(seq, channels, chunk, block)
    with jax.named_scope("hvd_ssm_mix"):
        ops = _operands(u, dt, A, B, C, D, padded) + (
            _pad_time(dy, 1, padded), starts)
    du, ddt, da, dd, dbp, dcp = _bwd_call(*ops, chunk=chunk, block=block,
                                          interpret=interpret)
    with jax.named_scope("hvd_ssm_mix"):
        # What is left of the sums: the batch (dA, dD), the 128 lanes.
        d_a = jnp.sum(da, axis=0).transpose(1, 0, 2).reshape(n, channels).T
        d_d = jnp.sum(dd, axis=0).reshape(channels)
        d_b, d_c = (jnp.sum(p[:, :seq], axis=-1) for p in (dbp, dcp))
    return (du[:, :seq], ddt[:, :seq], d_a.astype(A.dtype),
            d_b.astype(B.dtype), d_c.astype(C.dtype), d_d.astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(u, dt, A, B, C, D, chunk, block, interpret):
    return _scan_fwd(u, dt, A, B, C, D, chunk, block, interpret)[0]


def _scan_forward(u, dt, A, B, C, D, chunk, block, interpret):
    y, starts = _scan_fwd(u, dt, A, B, C, D, chunk, block, interpret)
    return y, (u, dt, A, B, C, D, starts)


def _scan_backward(chunk, block, interpret, saved, dy):
    return _scan_bwd(*saved, dy, chunk, block, interpret)


_scan.defvjp(_scan_forward, _scan_backward)


def selective_scan(u, dt, A, B, C, D, *, interpret: Optional[bool] = None):
    """``y`` [B, S, C] in ``u``'s dtype of the recurrence above; the state
    and every step's arithmetic are float32 whatever the operands' dtypes.

    Args:
      u, dt: [B, S, C]; ``dt`` is the step as the recurrence takes it (after
        its softplus).  A, D: [C, N], [C].  B, C: [B, S, N].  C has to be
        whole 128-lane tiles; :func:`plan` says what a grid step takes.
      interpret: None runs the kernels on a TPU and
        :func:`selective_scan_reference` elsewhere; True runs the kernels
        through the Pallas interpreter (tests).
    """
    # A chip's own rows meet parameters that ``shard_map`` holds replicated:
    # cast first, so that each cotangent comes back in its argument's type.
    dt, A, B, C, D = (_vary_like(x, u) for x in (dt, A, B, C, D))
    if interpret is None:
        if jax.default_backend() != "tpu":
            with jax.named_scope("hvd_ssm_scan"):
                return selective_scan_reference(u, dt, A, B, C, D)
        interpret = False
    return _scan(u, dt, A, B, C, D, None, None, interpret)
