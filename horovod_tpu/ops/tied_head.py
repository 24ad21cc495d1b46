"""The logits of a language model's head and their row statistics as one
Pallas TPU kernel: ``x [T, d] . table [V, d]^T`` in float32 and each row's
log-sum-exp, from one pass.

It is the first of the three products of a block of the blocked head in
``models/losses.py``: ``tied_head_cross_entropy``, whose table is the
embedding (ZAYA's, Jamba's), and ``head_cross_entropy``, whose table is a
head's own kernel ``[d, V]`` cast and turned to ``[V, d]`` in one pass a step
(Laguna's, JoyAI's).  Left to XLA the product writes
a block's float32 logits (1.07 GB at 2,048 tokens x 131,136 rows) and a
second pass reads them whole for the row maxima and the sums of the
exponentials; here a tile of the logits is folded into a running maximum and
a running sum while it is still in VMEM, as the flash forward folds its
scores (``ops/flash_attention.py``), and the logits are written once and not
read again until ``d logits`` is made of them.

The scheme: a grid over token tiles x vocabulary tiles, the vocabulary
innermost, and **the logits leave transposed**, ``[V, T]`` tiles.  A step is
``table_tile . x_tile^T``, the whole ``d`` in one dot (operands in
``x.dtype``, bfloat16 at the MXU's native rate, accumulated in float32): the
token tile, the whole block where it fits, is the operand that stands (the
table is then read once a call) and the table's rows stream past it.  The
tile's float32 product is stored as it is and folded into the pair ``(m, l)``
of its tokens, kept **by sublane** (``[8, T]``: row r holds the maximum and
the sum over the table's rows that are r modulo 8), so that a step costs the
VPU a maximum, a subtraction, an addition and one ``exp`` a logit and no
reduction inside a vreg.  The last vocabulary tile folds the 8 rows into
``lse = max + log(sum)``, a lane-dense ``[1, T]``.  Where ``V`` is no multiple
of the tile the last tile's rows past ``V`` count as -inf (an iota against
``V``) and are not written.

Why transposed: XLA, left to choose, keeps a block's logits and ``d logits``
token-minor (``{0,1}``: 131,136 is no multiple of 128, 2,048 is) and its two
backward products read them so; ``[V, T]`` row-major is that layout, and the
``.T`` handed back is a bitcast.  Row-major ``[T, V]`` tiles were written and
timed too (PERF.md, PR 53): no copy appeared, XLA's consumers turned to the
kernel's layout, but the product itself ran at 59 % of the peak where this
form runs at 93 %, since there the table's tile is the standing operand and
changes every step.

The running form differs from the two-pass one by float32 rounding of the
sum only: every ``exp`` takes an argument <= 0, so logits of any size lose
nothing.

One call, named ``hvd_head_logits`` for a trace.  Off the TPU
:func:`head_logits` returns None and the caller keeps its ``jax.numpy``; the
kernel is unit-tested in interpret mode (``tests/single/test_tied_head.py``),
compiled for a described v5e at the four cells' shapes (``tests/single/
test_tpu_compile.py``) and held against the ``jax.numpy`` form on the chip by
``chip_smoke.py --tied-head``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF
from .grouped_matmul import LANES, _NT, _out_struct

# Table rows a grid step: a step's dot is [TILE_VOCAB, d] . [T, d]^T.  256 to
# 1,024 read alike on a v5e at a block of 2,048 tokens (PERF.md, PR 53).
TILE_VOCAB = 512
# What a call's blocks may take of VMEM by :func:`_vmem_bytes`, and what the
# call asks Mosaic for.  A v5e / v6e core has 128 MiB of VMEM, a v7x 64 MiB.
_VMEM_BUDGET = 40 * 1024 * 1024
_VMEM_LIMIT = 56 * 1024 * 1024


class HeadPlan(NamedTuple):
    """A grid step's tile of the logits: ``vocab`` rows of the table by
    ``tokens`` rows of ``x``."""
    tokens: int
    vocab: int


def _vmem_bytes(tokens: int, vocab: int, d: int, itemsize: int) -> int:
    """VMEM of a grid step: the token tile, the table's tile and the float32
    output tile double-buffered by the pipeline, the float32 product once
    more, the two running statistics."""
    return (2 * ((tokens + vocab) * d * itemsize + vocab * tokens * 4)
            + vocab * tokens * 4 + 2 * 8 * tokens * 4)


def plan(tokens: int, d: int, rows: int, itemsize: int) -> Optional[HeadPlan]:
    """The tiles for ``[tokens, d] . [rows, d]^T``, chosen by the operands'
    shapes alone, or None where the kernel does not take them (the caller
    keeps its ``jax.numpy``): the whole block of tokens stands where it
    fits, half of it and so on where not."""
    if tokens % LANES or d % LANES or rows < 16:
        return None
    vocab = min(TILE_VOCAB, rows // 16 * 16)
    return next((HeadPlan(tile, vocab)
                 for tile in range(tokens, 0, -LANES) if tokens % tile == 0
                 and _vmem_bytes(tile, vocab, d, itemsize) <= _VMEM_BUDGET),
                None)


def _kernel(x_ref, table_ref, logits_ref, lse_ref, m_ref, l_ref, *,
            rows: int):
    """One grid step (token tile i, vocabulary tile j): the tile's logits,
    transposed, and their fold into the tokens' running maximum and sum by
    sublane."""
    j, last = pl.program_id(1), pl.num_programs(1) - 1
    vocab, tokens = logits_ref.shape

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    s = lax.dot_general(table_ref[...], x_ref[...], _NT,
                        preferred_element_type=jnp.float32)
    logits_ref[...] = s
    if rows % vocab:
        # The last tile's rows past V hold whatever the fetch left there.
        row = j * vocab + lax.broadcasted_iota(jnp.int32, (vocab, 1), 0)
        s = jnp.where(row < rows, s, NEG_INF)
    s = s.reshape(vocab // 8, 8, tokens)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=0))
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * jnp.exp(m - m_new) + jnp.sum(
        jnp.exp(s - m_new[None]), axis=0)

    @pl.when(j == last)
    def _flush():
        m = m_ref[...]
        top = jnp.max(m, axis=0, keepdims=True)
        total = jnp.sum(l_ref[...] * jnp.exp(m - top), axis=0, keepdims=True)
        lse_ref[...] = top + jnp.log(total)


# An inlined jit, as the grouped products': traced once a process for a
# head's shapes, and the call keeps the scope of the head that made it.
@functools.partial(jax.jit, static_argnums=(2, 3), inline=True)
def _head_logits(x, table, plan: HeadPlan, interpret: bool):
    """``(logits^T [V, T], lse [1, T])``, both float32."""
    (tokens, d), rows = x.shape, table.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows),
        name="hvd_head_logits",
        grid=(tokens // plan.tokens, pl.cdiv(rows, plan.vocab)),
        in_specs=[pl.BlockSpec((plan.tokens, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((plan.vocab, d), lambda i, j: (j, 0))],
        out_specs=[pl.BlockSpec((plan.vocab, plan.tokens),
                                lambda i, j: (j, i)),
                   pl.BlockSpec((1, plan.tokens), lambda i, j: (0, i))],
        out_shape=[_out_struct((rows, tokens), jnp.float32, x, table),
                   _out_struct((1, tokens), jnp.float32, x, table)],
        scratch_shapes=[pltpu.VMEM((8, plan.tokens), jnp.float32),
                        pltpu.VMEM((8, plan.tokens), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(x, table)


def head_logits(x, table, *, interpret=None):
    """``(logits, lse)`` of a head: ``x [T, d] . table [V, d]^T`` as
    float32 ``[T, V]`` (operands in their own dtype, the same for both,
    accumulated in float32; the transpose of what the kernel wrote, which
    XLA reads as a layout and does not copy) and float32 ``[T, 1]``, each
    row's maximum plus the log of its sum of ``exp(logits - maximum)``.

    ``interpret``: None runs the kernel on a TPU and returns None elsewhere,
    and for operands :func:`plan` does not take (the caller keeps its
    ``jax.numpy`` form); True, or a ``pltpu.InterpretParams``, forces the
    kernel through a Pallas interpreter (tests; only the latter runs inside
    ``shard_map``)."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return None
        interpret = False
    tiles = plan(*x.shape, table.shape[0], x.dtype.itemsize)
    if tiles is None or x.dtype != table.dtype:
        return None
    logits, lse = _head_logits(x, table, tiles, interpret)
    return logits.T, lse.reshape(-1, 1)
