"""Grouped matrix products as Pallas TPU kernels: ``rows [M, K]`` sorted by
group times ``w [G, K, N]``, each row by its own group's matrix.

It is what an expert layer's products are (``parallel/moe.py``: the rows
routed to a chip's experts, sorted by expert, through each expert's gate, up
and down kernels), and what ``jax.lax.ragged_dot`` computes.  Two things are
this module's own.  **The rows past the groups' sum cost nothing**: a dropless
layer's buffer is sized for the router's bad days and more than half of it is
empty on the others; those rows come out as zeros (forward and d rows) and add
nothing to dW, and no dot runs for them.  And the calls **declare ``vma`` on
their outputs** (:func:`_out_struct`), so they run inside ``shard_map`` under
``check_vma``, which megablox's ``gmm`` (``jax.experimental.pallas.ops.tpu.
megablox``, whose scheme this follows) does not.

The scheme: the row axis is cut into tiles of :data:`TILE_ROWS`; a grid step
is a **visit** of one tile by one group, and a tile that holds the edge
between two groups is visited by each in turn, a row mask keeping each to its
own rows.  The groups' offsets and every visit's group and tile are computed
outside the kernel (:func:`_schedule`, a few small integer ops) and reach it
as scalar-prefetched int32, so the index maps fetch the visiting group's
matrix.  The grid is static, tiles + groups visits, whatever the sizes are:
a group without rows keeps one visit (its dW is zeroed there), the tiles past
the last routed row are visited by a last, pseudo group that has no rows and
no matrix (their output blocks are written as zeros; their input blocks are
not fetched: the index maps hold the last live tile), and what is left over
visits nothing.

Three calls, named for a trace: ``hvd_moe_gmm`` is the product and, on the
matrices read transposed, its d rows; ``hvd_moe_tgmm`` is dW, ``rows^T [K, M]
. g [M, N]`` by group, gathered in a float32 accumulator over a group's
visits.  Every dot takes ``rows.dtype`` operands (bfloat16 at the MXU's
native rate) and accumulates in float32.  ``w`` may be kept in another dtype
(float32 parameters under bfloat16 activations): a group's matrix is then cast
in VMEM when its first visit fetches it, not in a pass of its own over all of
``w``, and dW leaves the accumulator in ``w``'s dtype.

A fourth call, ``hvd_moe_sum_rows`` (:func:`sum_by_token`), is the way back
from the rows to the tokens: the rows are put in token order
(:func:`token_order`, one sort a layer), a tile of :data:`SUM_TOKENS` tokens
is a group (and :data:`SUM_ROWS` rows a visit), and a group's sum by token is
``onehot [tokens, rows] . rows [rows, d]`` over its visits, ``onehot`` made in
VMEM from the rows' tokens: a 0 / 1 operand, so every product is exact, a
float32 accumulator, one rounding, each token tile written once.  A
scatter-add does the same sum as a serial read-modify-write at some 100
cycles a row (PERF.md, PR 37, PR 59).  :func:`sum_ordered_rows` is the same
kernel under a caller's name, for rows that lie in their destinations' order
already (``ops/embedding.py``: a table's gradient, its rows the destinations).

Off the TPU :func:`grouped_dot` is ``jax.lax.ragged_dot`` on ``w`` cast; the
kernels are unit-tested in interpret mode (``tests/single/
test_grouped_matmul.py``) and held against a loop over the experts on the chip
by ``chip_smoke.py --grouped-products``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .collectives import vary_like as _vary_like

LANES = 128
# Rows a visit: a 36,864-row buffer is 72 of them.  megablox's tiling at an
# expert layer's sizes, read on a v5e (PERF.md, PR 34 and PR 35).
TILE_ROWS = 512
# :func:`sum_by_token`'s tiles: rows a visit, and tokens a group (the
# one-hot's rows).  A visit's dot is the whole row tile by the whole token
# tile whatever share of either is the other's, so small tiles waste less:
# read on a v5e at the three expert cells' sizes, the kernel alone takes 0.32
# / 0.22 / 0.25 ms at 128 x 128 and 0.44 / 0.33 / 0.43 at 512 x 256; a whole
# step of ``sdar-moe-ep8-s4096`` reads the same at either (301.20 / 301.14 ms
# against 301.25 / 301.19 at two seeds; PERF.md, PR 59).
SUM_ROWS = 128
SUM_TOKENS = 128
# The prefixes of a buffer that :func:`sum_by_token`'s gather may cover: the
# rows routed are rounded up to one of them.  Against one gather of the whole
# buffer the switch is worth 5.2 of ``sdar-moe-ep8-s4096``'s 306.4 ms a step;
# eight is the only count read (PERF.md, PR 59).
SUM_SHARES = 8
# What a call's blocks may take of VMEM by :func:`_gmm_bytes` /
# :func:`_tgmm_bytes`, and what the calls ask Mosaic for.  Whole
# [2048, 768] float32 matrices fit: no contraction is cut at SDAR's sizes.
# A v5e / v6e core has 128 MiB of VMEM, a v7x core 64 MiB.
_VMEM_BUDGET = 28 * 1024 * 1024
_VMEM_LIMIT = 48 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))      # A @ B^T: contract the minor dims
_TN = (((0,), (0,)), ((), ()))      # A^T @ B: contract the major dims


def _out_struct(shape, dtype, *like):
    """ShapeDtypeStruct of a pallas_call output that varies over the mesh
    axes any of ``like`` varies over (``shard_map``'s ``check_vma`` wants it
    declared)."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# An inlined jit, as the kernels' below: its twenty small ops are traced once
# a process, not at each of a model's calls (31 ms each: with them a cell's
# set-up was 12 s longer, PERF.md PR 35).
@functools.partial(jax.jit, static_argnums=(1, 2), inline=True)
def _schedule(group_sizes, rows: int, tile: int):
    """``(offsets [G + 2], groups [V], tiles [V])``, int32, for V = tiles +
    G visits in the order both kernels walk them: tiles never fall, groups
    never fall.  ``offsets[g]:offsets[g + 1]`` are group g's rows, and the
    pseudo group G's are none.  A group with rows visits every tile it has
    rows in, a group without visits the tile it would start in, group G
    visits every tile past the last routed row, and the visits left over
    are group G's on the last tile once more (nothing to do there).  The
    sizes may add up to less than ``rows``, never to more."""
    held = group_sizes.shape[0]
    n_tiles = -(-rows // tile)
    visits = n_tiles + held
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    total = ends[-1:]
    first = jnp.minimum((ends - sizes) // tile, n_tiles - 1)
    span = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 1)
    tail_first = (total + tile - 1) // tile
    counts = jnp.concatenate([span, n_tiles - tail_first])
    firsts = jnp.concatenate([first, tail_first])
    groups = jnp.repeat(jnp.arange(held + 1, dtype=jnp.int32), counts,
                        total_repeat_length=visits)
    nth = jnp.arange(visits, dtype=jnp.int32) - (jnp.cumsum(counts)
                                                 - counts)[groups]
    tiles = jnp.minimum(firsts[groups] + nth, n_tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends, total])
    return offsets, groups, tiles


def _rows_of(v, offsets, groups, tiles, tile: int):
    """Visit v's rows ``[lo, hi)`` (none where ``hi <= lo``), its group,
    its tile's first row."""
    g, first_row = groups[v], tiles[v] * tile
    return (jnp.maximum(offsets[g], first_row),
            jnp.minimum(offsets[g + 1], first_row + tile), g, first_row)


def _input_tile(v, offsets, tiles, tile: int, held: int):
    """The row tile a visit reads: its own, or the last tile with routed
    rows where it lies past them (a block whose index stands is not fetched
    again)."""
    return jnp.minimum(tiles[v], jnp.maximum(offsets[held] - 1, 0) // tile)


def _gmm_kernel(offsets, groups, tiles, lhs_ref, rhs_ref, out_ref, *cast_ref,
                tile: int, dims):
    """One visit of the product: ``out[rows of the group in this tile] =
    lhs[those rows] . rhs[group]``.  Grid (column tiles, visits)."""
    v = pl.program_id(1)
    before = jnp.maximum(v - 1, 0)
    lo, hi, g, first_row = _rows_of(v, offsets, groups, tiles, tile)

    @pl.when((v == 0) | (tiles[before] != tiles[v]))
    def _new_tile():
        out_ref[...] = jnp.zeros_like(out_ref)

    if cast_ref:
        # A matrix kept in another dtype is cast where it arrives, once a
        # group (a group with rows has some in every visit it makes).
        @pl.when((hi > lo) & ((v == 0) | (groups[before] != g)))
        def _new_group():
            cast_ref[0][...] = rhs_ref[...].astype(lhs_ref.dtype)

    @pl.when(hi > lo)
    def _visit():
        rhs = cast_ref[0][...] if cast_ref else rhs_ref[...]
        res = lax.dot_general(lhs_ref[...], rhs, dims,
                              preferred_element_type=jnp.float32)
        row = first_row + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        out_ref[...] = jnp.where((row >= lo) & (row < hi),
                                 res.astype(out_ref.dtype), out_ref[...])


def _tgmm_kernel(offsets, groups, tiles, lhs_ref, rhs_ref, out_ref, acc_ref,
                 *, tile: int, held: int):
    """One visit of dW: ``out[group] += lhs[its rows in this tile]^T .
    rhs[those rows]``.  Grid (k tiles, n tiles, visits); the pseudo group's
    visits go on the last group's block and add nothing to it."""
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    lo, hi, g, first_row = _rows_of(v, offsets, groups, tiles, tile)
    g = jnp.minimum(g, held - 1)

    @pl.when((v == 0) | (jnp.minimum(groups[jnp.maximum(v - 1, 0)],
                                     held - 1) != g))
    def _new_group():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(hi > lo)
    def _visit():
        # Both operands masked: a row of another group, or past them all,
        # may hold anything.
        row = first_row + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        mine = (row >= lo) & (row < hi)
        lhs, rhs = lhs_ref[...], rhs_ref[...]
        acc_ref[...] += lax.dot_general(
            jnp.where(mine, lhs, jnp.zeros_like(lhs)),
            jnp.where(mine, rhs, jnp.zeros_like(rhs)), _TN,
            preferred_element_type=jnp.float32)

    @pl.when((v == last) | (jnp.minimum(groups[jnp.minimum(v + 1, last)],
                                        held - 1) != g))
    def _group_done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _divisors(n: int) -> list:
    """The block sizes a dimension of ``n`` may take, largest first: its
    divisors in whole lane tiles, or ``n`` alone where it is not one."""
    if n % LANES:
        return [n]
    return [d for d in range(n, 0, -LANES) if n % d == 0]


def _gmm_bytes(tile, c, o, itemsize, w_itemsize) -> int:
    """VMEM of a product's visit: the row tile, the group's matrix and the
    output tile double-buffered by the pipeline, the float32 result, the
    cast matrix where ``w`` is kept in another dtype."""
    return (2 * (tile * c * itemsize + c * o * w_itemsize
                 + tile * o * itemsize) + tile * o * 4
            + (c * o * itemsize if w_itemsize != itemsize else 0))


def _tgmm_bytes(tile, k, n, itemsize, out_itemsize) -> int:
    """VMEM of a dW visit: both row tiles double-buffered and once more
    masked, the output block double-buffered, the float32 accumulator."""
    return (3 * tile * (k + n) * itemsize + 2 * k * n * out_itemsize
            + k * n * 4)


def _plan_error(what, *sizes):
    return ValueError(f"grouped_dot: no block of {what} at sizes {sizes} "
                      f"fits {_VMEM_BUDGET} bytes of VMEM")


def _tile_rows(rows: int) -> int:
    return min(TILE_ROWS, rows)


# Inlined jits, as the flash kernels': traced once a process for a layer's
# shapes, and an op keeps the scope of the layer that made it.
@functools.partial(jax.jit, static_argnums=(5, 6), inline=True)
def _gmm(lhs, rhs, offsets, groups, tiles, transposed: bool, interpret: bool):
    """``[M, C] . [G, C, O] -> [M, O]`` by group; ``transposed``: the
    matrices are ``[G, O, C]`` and read as their transposes."""
    (m, c), held = lhs.shape, rhs.shape[0]
    o = rhs.shape[1] if transposed else rhs.shape[2]
    tile = _tile_rows(m)
    itemsize, w_itemsize = lhs.dtype.itemsize, rhs.dtype.itemsize
    block = next((d for d in _divisors(o) if _gmm_bytes(
        tile, c, d, itemsize, w_itemsize) <= _VMEM_BUDGET), None)
    if block is None:
        raise _plan_error("the product", m, c, o)

    def group_of(v, groups):
        return jnp.minimum(groups[v], held - 1)

    if transposed:
        rhs_spec = pl.BlockSpec(
            (None, block, c), lambda n, v, offsets, groups, tiles:
            (group_of(v, groups), n, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, c, block), lambda n, v, offsets, groups, tiles:
            (group_of(v, groups), 0, n))
    cast = rhs.dtype != lhs.dtype
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tile=tile,
                          dims=_NT if transposed else _NN),
        name="hvd_moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(o // block, groups.shape[0]),
            in_specs=[
                pl.BlockSpec((tile, c), lambda n, v, offsets, groups, tiles:
                             (_input_tile(v, offsets, tiles, tile, held), 0)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (tile, block), lambda n, v, offsets, groups, tiles:
                (tiles[v], n)),
            scratch_shapes=[pltpu.VMEM(rhs_spec.block_shape[1:], lhs.dtype)]
            if cast else []),
        out_shape=_out_struct((m, o), lhs.dtype, lhs, rhs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(offsets, groups, tiles, lhs, rhs)


@functools.partial(jax.jit, static_argnums=(5, 6, 7), inline=True)
def _tgmm(lhs, rhs, offsets, groups, tiles, held: int, dtype, interpret: bool):
    """``[M, K]^T . [M, N] -> [G, K, N]`` by group, in ``dtype``."""
    (m, k), n = lhs.shape, rhs.shape[1]
    tile = _tile_rows(m)
    itemsize, out_itemsize = lhs.dtype.itemsize, jnp.dtype(dtype).itemsize
    blocks = [(bk, bn) for bk in _divisors(k) for bn in _divisors(n)
              if _tgmm_bytes(tile, bk, bn, itemsize, out_itemsize)
              <= _VMEM_BUDGET]
    if not blocks:
        raise _plan_error("dW", m, k, n)
    bk, bn = max(blocks, key=lambda b: (b[0] * b[1], b[1]))

    def row_tile(v, offsets, tiles):
        return _input_tile(v, offsets, tiles, tile, held)

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tile=tile, held=held),
        name="hvd_moe_tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(k // bk, n // bn, groups.shape[0]),
            in_specs=[
                pl.BlockSpec((tile, bk), lambda i, j, v, offsets, groups,
                             tiles: (row_tile(v, offsets, tiles), i)),
                pl.BlockSpec((tile, bn), lambda i, j, v, offsets, groups,
                             tiles: (row_tile(v, offsets, tiles), j))],
            out_specs=pl.BlockSpec(
                (None, bk, bn), lambda i, j, v, offsets, groups, tiles:
                (jnp.minimum(groups[v], held - 1), i, j)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)]),
        out_shape=_out_struct((held, k, n), dtype, lhs, rhs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(offsets, groups, tiles, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_dot(rows, w, schedule, interpret):
    return _gmm(rows, w, *schedule, False, interpret)


def _grouped_dot_fwd(rows, w, schedule, interpret):
    return _grouped_dot(rows, w, schedule, interpret), (rows, w, schedule)


def _grouped_dot_bwd(interpret, saved, g):
    rows, w, schedule = saved
    return (_gmm(g, w, *schedule, True, interpret),
            _tgmm(rows, g, *schedule, w.shape[0], w.dtype, interpret), None)


_grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


def grouped_dot(rows, w, group_sizes, *, interpret=None):
    """``out[i] = rows[i] . w[group of row i]`` for rows sorted by group.

    Args:
      rows: [M, K], the first ``group_sizes[0]`` rows of group 0, the next
        ``group_sizes[1]`` of group 1, ...; the sizes may add up to less
        than M (never to more).
      w: [G, K, N], in ``rows.dtype`` or not: the product reads it cast to
        ``rows.dtype`` and accumulates in float32.
      group_sizes: [G] integers.
      interpret: None runs the Pallas kernels on a TPU and ``jax.lax.
        ragged_dot`` elsewhere; True, or a ``pltpu.InterpretParams``, forces
        the kernels through a Pallas interpreter (tests; only the latter
        runs scalar prefetch inside ``shard_map``).

    Returns [M, N] in ``rows.dtype``; by the kernels, the rows past the
    groups' sum are zeros, in the result and in d rows, and add nothing to
    dW (in ``w.dtype``), whatever they hold."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return lax.ragged_dot(rows, w.astype(rows.dtype), group_sizes)
        interpret = False
    schedule = _schedule(group_sizes, rows.shape[0], _tile_rows(rows.shape[0]))
    return _grouped_dot(_vary_like(rows, w), _vary_like(w, rows), schedule,
                        interpret)


def grouped_dot_grads(rows, w, group_sizes, g, *, interpret=None):
    """``(d rows, dW)`` of ``grouped_dot(rows, w, group_sizes)`` under the
    cotangent ``g`` [M, N], for a caller that kept the product's operands and
    writes its own backward (``parallel/moe.py``): the two products that
    ``grouped_dot``'s own reverse mode runs, and no forward product."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            def product(rows, w):
                return lax.ragged_dot(rows, w.astype(rows.dtype), group_sizes)
            return (*jax.linear_transpose(lambda r: product(r, w), rows)(g),
                    *jax.linear_transpose(lambda k: product(rows, k), w)(g))
        interpret = False
    operands = (rows, w, g)
    rows, w, g = (functools.reduce(_vary_like, operands, a) for a in operands)
    schedule = _schedule(group_sizes, rows.shape[0], _tile_rows(rows.shape[0]))
    return _grouped_dot_bwd(interpret, (rows, w, schedule), g)[:2]


# ---------------------------------------------------------------------------
# The way back: rows summed into their tokens
# ---------------------------------------------------------------------------

_PAST = jnp.iinfo(jnp.int32).max        # the key of a row that is no token's


class TokenOrder(NamedTuple):
    """A row buffer's rows by their token (:func:`token_order`)."""
    rows: jax.Array         # [C] int32: the rows, those of no token last
    tokens: jax.Array       # [C] int32: their tokens, ascending; then _PAST


def token_order(token, n, *, interpret=None):
    """The first ``n`` rows of a buffer ordered by their token (token [C]
    int32, n int32): one stable sort of C keys, which :func:`sum_by_token`
    starts from and a layer makes once for both its passes.  The rows past
    ``n`` keep their order behind the others.  None off the TPU unless
    ``interpret`` is given: there the sum is a scatter-add in row order
    (``parallel/moe.py:add_rows``) and asks for no order."""
    if interpret is None and jax.default_backend() != "tpu":
        return None
    at = jnp.arange(token.shape[0], dtype=jnp.int32)
    key = jnp.where(at < n, token.astype(jnp.int32), _PAST)
    tokens, rows = lax.sort((key, _vary_like(at, key)), num_keys=1,
                            is_stable=True)
    return TokenOrder(rows, tokens)


def _sum_kernel(offsets, groups, tiles, token_ref, rows_ref, out_ref, acc_ref,
                *, tile: int, held: int, span: int):
    """One visit of the sum: ``out[token tile] += onehot . rows[its rows in
    this row tile]``, ``onehot[t, r] = (row r's token is the tile's t-th)``.
    Grid (column tiles, visits), walked as ``_tgmm_kernel`` walks dW's: the
    pseudo group's visits go on the last token tile and add nothing."""
    v, last = pl.program_id(1), pl.num_programs(1) - 1
    lo, hi, g, first_row = _rows_of(v, offsets, groups, tiles, tile)
    g = jnp.minimum(g, held - 1)

    @pl.when((v == 0) | (jnp.minimum(groups[jnp.maximum(v - 1, 0)],
                                     held - 1) != g))
    def _new_group():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(hi > lo)
    def _visit():
        # A row of another token tile has no 1 in this one-hot; one past
        # them all, or past the buffer's end, may hold anything: zeros.
        row = first_row + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        rows = rows_ref[...]
        rows = jnp.where((row >= lo) & (row < hi), rows, jnp.zeros_like(rows))
        nth = lax.broadcasted_iota(jnp.int32, (span, tile), 0)
        onehot = (token_ref[...] - g * span == nth).astype(rows.dtype)
        # float32 rows are summed as float32: the 0 / 1 operand is exact in
        # any precision, the rows only at the highest.
        acc_ref[...] += lax.dot_general(
            onehot, rows, _NN, preferred_element_type=jnp.float32,
            precision=(lax.Precision.HIGHEST if rows.dtype == jnp.float32
                       else None))

    @pl.when((v == last) | (jnp.minimum(groups[jnp.minimum(v + 1, last)],
                                        held - 1) != g))
    def _group_done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _sum_bytes(tile, span, n, itemsize) -> int:
    """VMEM of a visit of the sum: the row tile double-buffered and once
    more masked, the one-hot, the output block double-buffered, the float32
    accumulator."""
    return ((3 * tile * n + span * tile + 2 * span * n) * itemsize
            + span * n * 4)


@functools.partial(jax.jit, static_argnums=(2, 3, 4), inline=True)
def _sum_rows(rows, tokens_of, tokens: int, interpret: bool,
              name: str = "hvd_moe_sum_rows"):
    """``[tokens, d]``: rows ``[M, d]`` in the order of their tokens
    ``tokens_of`` [M] (ascending, ``_PAST`` for a row of none) summed by
    token.  ``name`` is the kernel's in a trace."""
    m, d = rows.shape
    tile, span = min(SUM_ROWS, m), min(SUM_TOKENS, tokens)
    held = -(-tokens // span)
    sizes = jnp.sum(jax.nn.one_hot(tokens_of // span, held, dtype=jnp.int32),
                    axis=0)
    offsets, groups, tiles = _schedule(sizes, m, tile)
    itemsize = rows.dtype.itemsize
    block = next((b for b in _divisors(d) if _sum_bytes(
        tile, span, b, itemsize) <= _VMEM_BUDGET), None)
    if block is None:
        raise _plan_error("the sum by token", m, tokens, d)

    def row_tile(v, offsets, tiles):
        return _input_tile(v, offsets, tiles, tile, held)

    out = pl.pallas_call(
        functools.partial(_sum_kernel, tile=tile, held=held, span=span),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(d // block, groups.shape[0]),
            in_specs=[
                pl.BlockSpec((1, tile), lambda j, v, offsets, groups, tiles:
                             (0, row_tile(v, offsets, tiles))),
                pl.BlockSpec((tile, block), lambda j, v, offsets, groups,
                             tiles: (row_tile(v, offsets, tiles), j))],
            out_specs=pl.BlockSpec(
                (span, block), lambda j, v, offsets, groups, tiles:
                (jnp.minimum(groups[v], held - 1), j)),
            scratch_shapes=[pltpu.VMEM((span, block), jnp.float32)]),
        out_shape=_out_struct((held * span, d), rows.dtype, rows, tokens_of),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(offsets, groups, tiles, tokens_of[None, :], rows)
    return out[:tokens]       # (the last token tile may be a partial one)


def sum_ordered_rows(rows, tokens_of, tokens: int, *, name: str,
                     interpret: bool = False):
    """:func:`sum_by_token` for rows ``[M, d]`` that lie in their tokens'
    order already, every one of them some token's (``tokens_of`` [M]
    ascending in ``[0, tokens)``; a row whose token lies past them is
    dropped): the kernel alone, under ``name`` in a trace
    (``ops/embedding.py``: a table's rows are the tokens)."""
    return _sum_rows(_vary_like(rows, tokens_of), _vary_like(tokens_of, rows),
                     tokens, interpret, name)


def _covers(capacity: int) -> list:
    """The lengths of a ``capacity``-row buffer's prefix that
    :func:`sum_by_token` may gather: every :data:`SUM_SHARES`-th of it in
    whole row tiles, and all of it."""
    tile = min(SUM_ROWS, capacity)
    return sorted({min(capacity,
                       -(-capacity * i // (SUM_SHARES * tile)) * tile)
                   for i in range(1, SUM_SHARES + 1)})


def sum_by_token(rows, order: TokenOrder, tokens: int, *, interpret=None):
    """``[tokens, d]``: row t is the sum of the buffer's rows ``rows`` [C, d]
    whose token is t, for the rows that ``order`` (:func:`token_order`)
    counts as some token's, and zeros where a token has none: what
    ``zeros.at[token[:n]].add(rows[:n])`` is, summed in float32 and rounded
    once to ``rows.dtype``, each token tile written once.  The rows are
    gathered into token order (the cost that is left: XLA's gather takes some
    30 ns a row of a large buffer on a v5e wherever the row lies) and summed
    by ``hvd_moe_sum_rows``, which never fetches a row of no token.  So that
    the gather follows the rows routed as well, and not the buffer, it covers
    the shortest of a few prefixes of the order (:func:`_covers`) that holds
    them all, chosen while the step runs (a ``lax.switch``: one side runs),
    and what lies past it is zeros that nothing reads.  ``interpret`` as
    :func:`grouped_dot`'s, None being the compiled kernel."""
    capacity = rows.shape[0]

    def gather(cover):
        return lambda rows, at: jnp.pad(
            rows.at[at[:cover]].get(mode="promise_in_bounds",
                                    unique_indices=True),
            ((0, capacity - cover), (0, 0)))

    covers = _covers(capacity)
    routed = jnp.sum(order.tokens != _PAST)
    operands = (rows, *order)
    rows, at, tokens_of = (functools.reduce(_vary_like, operands, a)
                           for a in operands)
    by_token = lax.switch(sum(routed > cover for cover in covers[:-1]),
                          list(map(gather, covers)), rows, at)
    return _sum_rows(by_token, tokens_of, tokens, interpret or False)
