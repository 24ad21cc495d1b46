"""Per-head RMSNorm of a projection followed by rotary positions, as one op
on the flash kernels' own layout.

A Qwen3-style attention block norms each head of q and of k (``x /
sqrt(mean(x^2) + eps) * scale`` over the head's ``D`` entries, one learnt
``scale [D]`` for all heads) and then turns the head by its position
(Qwen's half-split rotary: the pairs are ``(i, i + D/2)``).  Written as
``RMSNorm`` and ``rotary`` on ``[B, S, H, D]`` that is a dozen passes over q
in the compiled step: a relayout into a heads-by-lanes tiling, the mean
square, the normed head **in float32**, the two rotated halves each padded to
a lane tile, their concatenation, and a relayout back to the ``[B, S, H * D]``
that ``ops/flash_attention.py``'s kernels read; the backward as many again.

:func:`qk_norm_rope` reads and writes ``[B, S, H * D]`` itself, what a flat
projection produces and what the flash kernels take: **one pass forward
(read x, write out) and one backward (read x and the cotangent, write dx)**,
the arithmetic in float32 inside VMEM and one rounding to ``x.dtype`` at the
end.  With ``D`` = 128 a head is one lane tile and the rotary is ``y * [cos,
cos] + roll(y, D/2 lanes) * [-sin, sin]``.  The mean over a head's lanes and
the roll are products with a constant [128, 128] matrix on the MXU, which
has nothing else to do here, and not the cross-lane unit's: a lane
reduction and a lane rotate in a head's chain of four vregs wait on each
other (the first form of these kernels read 40 cycles a vreg on a v5e where
the memory allows 9, and reached the memory's rate only at chunks of 256
rows; this form does at any).  Both are exact: a bfloat16 ``x`` times a
permutation is ``x``, and its square is the sum of two bfloat16 numbers
whose products accumulate in float32.

The kernels (``hvd_qk_norm_rope_fwd`` / ``_bwd`` in a trace) take a tile of
rows by **all** heads a grid step, whole rows of the tensor (contiguous in
HBM), and walk it in chunks of :data:`_CHUNK` rows, head by head: a chunk's
slice of the ``[S, D]`` cos / sin tables is loaded once for all its heads.
The backward recomputes the normed head from ``x`` (the residuals are ``x``,
``scale`` and ``positions``: what ``jax.checkpoint`` of the plain form keeps),
un-rotates the cotangent, and leaves ``dscale`` as one ``[8, D]`` float32
partial sum a grid step, summed outside.

Off a TPU, and for a head that is not one lane tile, the op is the plain
``jax.numpy`` form (:func:`dense_qk_norm_rope`, identical math) under the
same ``custom_vjp``; the kernels are unit-tested in interpret mode
(``tests/single/test_qk_norm_rope.py``), compiled for a described chip in
``tests/single/test_tpu_compile.py`` and validated on hardware by
``chip_smoke.py --qk-norm-rope``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .collectives import vary_like as _vary_like
from .flash_attention import LANES, _divisor, _out_struct

# Rows a pass of the kernels' inner loop: [_CHUNK, 128] in float32 is four
# vregs, so the backward's dozen live values of a head stay in registers.
_CHUNK = 32
# What one operand's block of a grid step may hold (256 rows of 32 heads of
# 128 in bfloat16), and what the calls ask Mosaic for: the backward holds
# three such blocks twice over, and the tables.
_BLOCK_BYTES = 2 * 1024 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=_VMEM_LIMIT)


class _Spec(NamedTuple):
    """What is static in a call.  ``interpret`` None: the ``jax.numpy``
    form; else the kernels, compiled (False) or interpreted (what
    ``pallas_call`` takes under that name)."""
    heads: int
    head_dim: int
    eps: float
    theta: float
    interpret: Any


def _tables(positions, head_dim: int, theta: float):
    """``([cos, cos], [-sin, sin])`` of the rotary angles at ``positions``
    [S], each [S, D] float32: ``y * cos + roll(y, D/2) * sin`` is the
    half-split rotary of ``y``."""
    half = head_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return (jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1))


def _dense(x, scale, positions, spec: _Spec):
    heads = x.reshape(*x.shape[:-1], spec.heads, spec.head_dim).astype(
        jnp.float32)
    y = heads * lax.rsqrt(jnp.mean(heads * heads, axis=-1, keepdims=True)
                          + spec.eps) * scale
    cos, sin = _tables(positions, spec.head_dim, spec.theta)
    out = (y * cos[:, None, :]
           + jnp.roll(y, spec.head_dim // 2, axis=-1) * sin[:, None, :])
    return out.astype(x.dtype).reshape(x.shape)


def _lane_mean_matrix():
    """[128, 128] of 1/128: ``v @ this`` is the mean of each row of ``v`` on
    every lane."""
    return jnp.full((LANES, LANES), 1.0 / LANES, jnp.bfloat16)


def _swap_matrix():
    """The permutation that swaps a row's two halves: ``v @ this`` is ``v``
    rolled by ``D / 2`` lanes."""
    row = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    col = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    return (row == (col + LANES // 2) % LANES).astype(jnp.bfloat16)


def _mean_square(x, xf, mean):
    """The mean of each row of ``xf * xf`` ([rows, 128] float32, ``x`` in its
    own dtype) on every lane, on the MXU, which is idle here, and not as a
    lane reduction (the module's docstring).  The square goes in bfloat16
    parts that sum to its float32 value (two are the whole of a bfloat16
    ``x``'s square, 16 significant bits; three of a float32's), the products
    accumulate in float32."""
    parts = 2 if x.dtype == jnp.bfloat16 else 3
    rest, dots = xf * xf, []
    for i in range(parts):
        part = rest.astype(jnp.bfloat16)
        if i + 1 < parts:
            rest = rest - part.astype(jnp.float32)
        dots.append(jnp.dot(part, mean, preferred_element_type=jnp.float32))
    return functools.reduce(jnp.add, dots)


def _swap_halves(x, xf, swap):
    """``xf`` rolled by ``D / 2`` lanes: of a bfloat16 ``x`` a product with
    the permutation (exact: one term a sum), else the cross-lane unit's."""
    if x.dtype == jnp.bfloat16:
        return jnp.dot(x, swap, preferred_element_type=jnp.float32)
    return pltpu.roll(xf, LANES // 2, 1)


def _walk(tile_rows: int, body, carry=None):
    """``body(rows, carry)`` over the tile's chunks of :data:`_CHUNK` rows."""
    def chunk(c, carry):
        return body(pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK), carry)

    return lax.fori_loop(0, tile_rows // _CHUNK, chunk, carry)


def _fwd_kernel(x_ref, scale_ref, cos_ref, sin_ref, o_ref, *, spec: _Spec):
    scale = scale_ref[...]                                  # [1, D]
    mean, swap = _lane_mean_matrix(), _swap_matrix()
    # out = y cos + roll(y) sin with y = x r scale is, r being one number a
    # row, (x (cos scale) + roll(x) (sin roll(scale))) r: the scale goes
    # into a chunk's tables once for all its heads, the roll takes x itself.
    scale_swapped = pltpu.roll(scale, LANES // 2, 1)

    def chunk(rows, _):
        cos, sin = cos_ref[rows, :] * scale, sin_ref[rows, :] * scale_swapped
        for h in range(spec.heads):
            lanes = slice(h * LANES, (h + 1) * LANES)
            x = x_ref[rows, lanes]
            xf = x.astype(jnp.float32)
            r = lax.rsqrt(_mean_square(x, xf, mean) + spec.eps)
            o_ref[rows, lanes] = (
                (xf * cos + _swap_halves(x, xf, swap) * sin) * r
            ).astype(o_ref.dtype)

    _walk(x_ref.shape[0], chunk)


def _bwd_kernel(x_ref, g_ref, scale_ref, cos_ref, sin_ref, dx_ref,
                dscale_ref, *, spec: _Spec):
    scale = scale_ref[...]
    mean, swap = _lane_mean_matrix(), _swap_matrix()

    def chunk(rows, dscale):
        cos, sin = cos_ref[rows, :], sin_ref[rows, :]
        for h in range(spec.heads):
            lanes = slice(h * LANES, (h + 1) * LANES)
            x, g = x_ref[rows, lanes], g_ref[rows, lanes]
            xf, gf = x.astype(jnp.float32), g.astype(jnp.float32)
            r = lax.rsqrt(_mean_square(x, xf, mean) + spec.eps)
            n = xf * r
            # The rotary's transpose: out_j = y_j cos_j + y_{j +- D/2} sin_j
            # and sin_{j +- D/2} = -sin_j.
            dy = gf * cos - _swap_halves(g, gf, swap) * sin
            dn = dy * scale
            dx_ref[rows, lanes] = (r * (dn - n * jnp.mean(
                dn * n, axis=-1, keepdims=True))).astype(dx_ref.dtype)
            part = dy * n
            # Rows onto eight sublanes: adds of whole vregs, no reduction.
            dscale = dscale + sum(part[i:i + 8] for i in range(0, _CHUNK, 8))
        return dscale

    dscale_ref[...] = _walk(x_ref.shape[0], chunk,
                            jnp.zeros(dscale_ref.shape, jnp.float32))


def _tile_rows(seq: int, width: int, itemsize: int) -> int:
    """Rows a grid step: the most chunks a block of :data:`_BLOCK_BYTES`
    holds that divide ``seq`` (a multiple of the chunk)."""
    cap = max(1, _BLOCK_BYTES // (width * itemsize * _CHUNK))
    return _CHUNK * _divisor(seq // _CHUNK, cap)


# Inlined jits, as the flash kernels': a model calls these once a layer with
# the same shapes, and each kernel is then traced once a process.
@functools.partial(jax.jit, static_argnums=(4,), inline=True)
def _forward(x, scale, cos, sin, spec: _Spec):
    b, s, width = x.shape
    tile = _tile_rows(s, width, x.dtype.itemsize)
    rows = pl.BlockSpec((None, tile, width), lambda b, i: (b, i, 0))
    table = pl.BlockSpec((tile, LANES), lambda b, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, spec=spec), grid=(b, s // tile),
        in_specs=[rows, pl.BlockSpec((1, LANES), lambda b, i: (0, 0)),
                  table, table],
        out_specs=rows, out_shape=_out_struct(x.shape, x.dtype, x),
        compiler_params=_COMPILER_PARAMS, interpret=spec.interpret,
        name="hvd_qk_norm_rope_fwd")(x, scale[None], cos, sin)


@functools.partial(jax.jit, static_argnums=(5,), inline=True)
def _backward(x, g, scale, cos, sin, spec: _Spec):
    b, s, width = x.shape
    tile = _tile_rows(s, width, x.dtype.itemsize)
    n_tiles = s // tile
    rows = pl.BlockSpec((None, tile, width), lambda b, i: (b, i, 0))
    table = pl.BlockSpec((tile, LANES), lambda b, i: (i, 0))
    dx, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, spec=spec), grid=(b, n_tiles),
        in_specs=[rows, rows, pl.BlockSpec((1, LANES), lambda b, i: (0, 0)),
                  table, table],
        out_specs=[rows, pl.BlockSpec((None, None, 8, LANES),
                                      lambda b, i: (b, i, 0, 0))],
        out_shape=[_out_struct(x.shape, x.dtype, x),
                   _out_struct((b, n_tiles, 8, LANES), jnp.float32, x)],
        compiler_params=_COMPILER_PARAMS, interpret=spec.interpret,
        name="hvd_qk_norm_rope_bwd")(x, g, scale[None], cos, sin)
    return dx, dscale.sum((0, 1, 2))


def _kernel_tables(x, positions, spec: _Spec):
    return tuple(_vary_like(t, x)
                 for t in _tables(positions, spec.head_dim, spec.theta))


def _whole_chunks(x, positions, spec: _Spec, *rows):
    """``rows`` ([B, S, W] each) padded to whole chunks of rows, and the
    tables at the positions padded alike."""
    pad = -x.shape[1] % _CHUNK
    tables = _kernel_tables(x, jnp.pad(positions, (0, pad)), spec)
    if pad:
        rows = [jnp.pad(r, [(0, 0), (0, pad), (0, 0)]) for r in rows]
    return (*rows, *tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qk_norm_rope(x, scale, positions, spec: _Spec):
    if spec.interpret is None:
        return _dense(x, scale, positions, spec)
    x_pad, cos, sin = _whole_chunks(x, positions, spec, x)
    return _forward(x_pad, scale, cos, sin, spec)[:, :x.shape[1]]


def _qk_norm_rope_fwd(x, scale, positions, spec: _Spec):
    return _qk_norm_rope(x, scale, positions, spec), (x, scale, positions)


def _qk_norm_rope_bwd(spec: _Spec, saved, g):
    x, scale, positions = saved
    if spec.interpret is None:
        _, vjp = jax.vjp(lambda x, scale: _dense(x, scale, positions, spec),
                         x, scale)
        return (*vjp(g), None)
    x_pad, g_pad, cos, sin = _whole_chunks(x, positions, spec, x, g)
    dx, dscale = _backward(x_pad, g_pad, scale, cos, sin, spec)
    return dx[:, :x.shape[1]], dscale, None


_qk_norm_rope.defvjp(_qk_norm_rope_fwd, _qk_norm_rope_bwd)


def _call(x, scale, positions, spec: _Spec):
    if (x.shape[-1] != spec.heads * spec.head_dim
            or positions.shape != x.shape[1:2]):
        raise ValueError(
            f"qk_norm_rope: x {x.shape} is not [B, S, {spec.heads} * "
            f"{spec.head_dim}] with positions {positions.shape} [S]")
    with jax.named_scope("hvd_qk_norm_rope"):
        return _qk_norm_rope(x, _vary_like(scale, x), positions, spec)


def dense_qk_norm_rope(x, scale, positions, *, heads: int, head_dim: int,
                       eps: float, theta: float):
    """:func:`qk_norm_rope` in plain ``jax.numpy`` on any backend (the dense
    oracle's companion, as ``dense_attention`` is ``flash_attention``'s)."""
    return _call(x, scale, positions,
                 _Spec(heads, head_dim, float(eps), float(theta), None))


def qk_norm_rope(x, scale, positions, *, heads: int, head_dim: int,
                 eps: float, theta: float, interpret=None):
    """Each head of ``x`` RMS-normed and turned by its position.

    Args:
      x: [B, S, heads * head_dim], a flat projection's result.
      scale: [head_dim], the norm's learnt scale (float32), one for all heads.
      positions: [S] integers, the rotary position of each row.
      eps, theta: the norm's epsilon and the rotary's base.
      interpret: None runs the Pallas kernels on a TPU and the ``jax.numpy``
        form elsewhere; True, or a ``pltpu.InterpretParams`` (the one that
        runs inside ``shard_map``), forces the kernels through a Pallas
        interpreter (tests).  A head that is not one lane tile (``head_dim``
        != 128) takes the ``jax.numpy`` form wherever it runs.

    Returns ``rotary(x_h / sqrt(mean(x_h^2) + eps) * scale)`` for each head
    ``h``, in ``x``'s shape and dtype; the arithmetic is float32.  The
    gradient is hand-written: ``dx`` in ``x.dtype``, ``dscale`` in float32,
    nothing kept for it but the arguments."""
    if head_dim != LANES:
        interpret = None
    elif interpret is None and jax.default_backend() == "tpu":
        interpret = False
    return _call(x, scale, positions, _Spec(
        heads, head_dim, float(eps), float(theta), interpret))
