"""Lightning attention (causal linear attention under one decay a head) as
Pallas TPU kernels: chunked matrix products on the MXU with a ``[D, D]``
state a head carried along the sequence in VMEM.

With ``q``, ``k``, ``v`` [B, S, H, D], a slope ``a_h >= 0`` a head (``lambda_h
= exp(-a_h)``) and a ``scale``, no softmax anywhere::

    o_t = scale * sum_{s <= t} lambda_h^(t - s) (q_t . k_s) v_s

(Lightning Attention-2, arXiv:2401.04658).  The decay is **one scalar a
head**, so the recurrence ``S_t = lambda S_(t-1) + k_t^T v_t``, ``o_t = q_t
S_t`` turns into matrix products a chunk of C rows at a time, which nothing in
``ops/selective_scan.py`` (one decay a channel and a state: VPU work) does::

    O_i = ((Q_i K_i^T) * D) V_i + L * (Q_i S_(i-1))
    S_i = lambda^C S_(i-1) + (K_i * R)^T V_i
    D_rs = scale lambda^(r - s) (r >= s, else 0)
    L_r = scale lambda^(r + 1)        R_r = lambda^(C - 1 - r)

``S_(i-1)`` is the sum over every earlier row decayed to the last row of chunk
i - 1, unscaled, float32, in a VMEM scratch from one grid step to the next.
**Every decay power is made in float32 from the slope itself**, ``exp(-a (r -
s))`` a pair and ``exp(-a (C - 1 - r))`` a row, never as a quotient of two
powers: the steepest head of the published slopes has ``lambda^256`` = 3e-91,
which float32 cannot hold, while every power that is used lies in [0, 1].

The backward is two walks.  ``dq`` is the forward itself on other operands,
``dq = lightning(dO, v, k)`` (its state is ``S^T``), the kernel named
``hvd_lightning_dq``.  ``dk`` and ``dv`` walk the chunks last to first with
the adjoint state ``dS_i = lambda^C dS_(i+1) + (Q_i * lambda^r)^T dO_i``
(``hvd_lightning_dkv``)::

    dV_i = ((K_i Q_i^T) * D^T) dO_i + scale lambda^(C - r) * (K_i dS_(i+1))
    dK_i = ((V_i dO_i^T) * D^T) Q_i + scale lambda^(C - r) * (V_i dS_(i+1)^T)

Layout: the kernels read and write the model's own ``[B, S, H x D]`` (a view
of [B, S, H, D]), a head a grid row, ``D`` whole 128-lane tiles; the grid is
(batch, heads, blocks of the sequence), a grid step holds a **block** of up
to :data:`BLOCK_ROWS` rows and walks its chunks in a loop, so the fixed price
of a grid step is paid a few times a head and not once a chunk.  Products
take their operands in the dtype they arrive in (bfloat16 at the MXU's rate)
and accumulate in float32; the decayed score tile is rounded to that dtype
for its second product as a flash kernel rounds ``p``, and so is the state
where it is an operand.  The slopes reach the kernels as a scalar-prefetch
operand.

Off the TPU :func:`lightning_attention` is :func:`lightning_attention_scan`,
the same chunked recurrence as a ``lax.scan`` in ``jax.numpy`` that autodiff
differentiates; ``interpret=True`` runs the kernels through the Pallas
interpreter (``tests/single/test_lightning_attention.py``).
:func:`lightning_attention_quadratic` is the definition, ``((Q K^T) * D) V``
over the whole sequence, for tests.  The output carries the checkpoint name
:data:`CHECKPOINT_NAME`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .collectives import vary_like
from .flash_attention import LANES, _NT, _TN, _out_struct

# Rows of a chunk (the square score tile's side) and of a grid step's block.
# Read on a v5e at 8 heads of 128 over 16,384 rows in bfloat16 (PERF.md §6,
# PR 58).
CHUNK = 256
BLOCK_ROWS = 2048
# What the output is named for ``jax.checkpoint``'s ``save_only_these_names``.
CHECKPOINT_NAME = "hvd_lightning_out"

_VMEM_LIMIT = 32 * 1024 * 1024


def plan(seq: int, chunk: Optional[int] = None,
         block: Optional[int] = None) -> tuple:
    """``(chunk, block)`` rows of a call over ``seq`` rows: the chunk
    :data:`CHUNK` or the whole sequence if shorter, the block the largest
    whole number of chunks under :data:`BLOCK_ROWS` that divides the
    sequence.  A sequence that is no whole number of chunks is refused by
    name: a padded tail would decay the state it carries."""
    chunk = min(chunk or CHUNK, seq)
    if seq % chunk:
        raise ValueError(
            f"lightning_attention: a sequence of {seq} rows is not a whole "
            f"number of chunks of {chunk}; pad the sequence or pass a chunk "
            "that divides it")
    chunks = seq // chunk
    if block is None:
        per = max(d for d in range(1, chunks + 1)
                  if chunks % d == 0 and d * chunk <= max(BLOCK_ROWS, chunk))
        block = per * chunk
    if block % chunk or seq % block:
        raise ValueError(
            f"lightning_attention: a block of {block} rows must be whole "
            f"chunks of {chunk} and divide the sequence of {seq}")
    return chunk, block


def _powers(a, shape, axis: int, first: int, step: int = 1):
    """``exp(-a (first + step x index along axis))`` as a float32 ``shape``
    tile: the decay powers of a chunk's rows, from the slope itself."""
    n = lax.broadcasted_iota(jnp.int32, shape, axis)
    return jnp.exp(-a * (first + step * n).astype(jnp.float32))


def _pair_decay(a, chunk: int, scale: float, transposed: bool = False):
    """``D`` [C, C]: ``scale x lambda^(r - s)`` where the query row r is at
    or after the key row s, else zero (``transposed``: keys on the rows)."""
    r = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1 if transposed else 0)
    s = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0 if transposed else 1)
    gap = r - s
    # The power of a pair above the diagonal is never made: its exponent
    # would be positive, and exp of it inf at a steep head.
    return jnp.where(gap >= 0, scale * jnp.exp(
        -a * jnp.maximum(gap, 0).astype(jnp.float32)), 0.0)


def _fwd_kernel(slopes_ref, q_ref, k_ref, v_ref, o_ref, state_ref, *,
                chunk: int, scale: float):
    """grid (B, H, S / block): a block of one head's rows, its chunks walked
    first to last; ``state_ref`` [D, D] float32 carries ``S`` from a grid
    step to the next."""
    a = slopes_ref[pl.program_id(1)]
    width = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_ref[:] = jnp.zeros_like(state_ref)

    pair = _pair_decay(a, chunk, scale)
    out_decay = scale * _powers(a, (chunk, width), 0, 1)       # L_r
    key_decay = _powers(a, (chunk, width), 0, chunk - 1, -1)   # R_r
    chunk_decay = _powers(a, (width, width), 0, chunk, 0)      # lambda^C

    def step(i, state):
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        q, k, v = q_ref[rows, :], k_ref[rows, :], v_ref[rows, :]
        scores = lax.dot_general(q, k, _NT,
                                 preferred_element_type=jnp.float32) * pair
        out = jnp.dot(scores.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
        out = out + out_decay * jnp.dot(
            q, state.astype(q.dtype), preferred_element_type=jnp.float32)
        o_ref[rows, :] = out.astype(o_ref.dtype)
        decayed = (k.astype(jnp.float32) * key_decay).astype(k.dtype)
        return chunk_decay * state + lax.dot_general(
            decayed, v, _TN, preferred_element_type=jnp.float32)

    state_ref[:] = lax.fori_loop(0, q_ref.shape[0] // chunk, step,
                                 state_ref[:])


def _dkv_kernel(slopes_ref, q_ref, k_ref, v_ref, do_ref, dk_ref, dv_ref,
                state_ref, *, chunk: int, scale: float):
    """grid (B, H, S / block), the blocks and their chunks walked last to
    first; ``state_ref`` [D, D] float32 carries ``dS`` (rows q's lanes,
    columns dO's)."""
    a = slopes_ref[pl.program_id(1)]
    width = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_ref[:] = jnp.zeros_like(state_ref)

    pair = _pair_decay(a, chunk, scale, transposed=True)   # [keys, queries]
    in_decay = scale * _powers(a, (chunk, width), 0, chunk, -1)  # lambda^(C-r)
    query_decay = _powers(a, (chunk, width), 0, 0)             # lambda^r
    chunk_decay = _powers(a, (width, width), 0, chunk, 0)
    chunks = q_ref.shape[0] // chunk

    def step(n, state):
        i = chunks - 1 - n
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        q, k, v, do = (q_ref[rows, :], k_ref[rows, :], v_ref[rows, :],
                       do_ref[rows, :])
        carried = state.astype(q.dtype)
        p = lax.dot_general(k, q, _NT,
                            preferred_element_type=jnp.float32) * pair
        dv = jnp.dot(p.astype(do.dtype), do,
                     preferred_element_type=jnp.float32)
        dv = dv + in_decay * jnp.dot(k, carried,
                                     preferred_element_type=jnp.float32)
        dp = lax.dot_general(v, do, _NT,
                             preferred_element_type=jnp.float32) * pair
        dk = jnp.dot(dp.astype(q.dtype), q,
                     preferred_element_type=jnp.float32)
        dk = dk + in_decay * lax.dot_general(
            v, carried, _NT, preferred_element_type=jnp.float32)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[rows, :] = dv.astype(dv_ref.dtype)
        decayed = (q.astype(jnp.float32) * query_decay).astype(q.dtype)
        return chunk_decay * state + lax.dot_general(
            decayed, do, _TN, preferred_element_type=jnp.float32)

    state_ref[:] = lax.fori_loop(0, chunks, step, state_ref[:])


# The three calls by name (a literal where ``pallas_call`` takes it: what a
# trace shows, docs/observability.md).
_NAMED = {
    "fwd": lambda *a, **kw: pl.pallas_call(*a, name="hvd_lightning_fwd", **kw),
    "dq": lambda *a, **kw: pl.pallas_call(*a, name="hvd_lightning_dq", **kw),
    "dkv": lambda *a, **kw: pl.pallas_call(*a, name="hvd_lightning_dkv", **kw),
}


def _call(kernel, name, slopes, operands, outputs, *, chunk, block, scale,
          interpret, reverse=False):
    """One of the kernels over operands [B, S, H x D]: a ``[block, D]`` block
    of a head's lanes a grid step, the blocks first to last or (``reverse``)
    last to first."""
    batch, seq, lanes = operands[0].shape
    width = lanes // slopes.shape[0]
    blocks = seq // block

    def index(b, h, i, slopes_ref):
        return b, (blocks - 1 - i) if reverse else i, h

    spec = pl.BlockSpec((None, block, width), index)
    return _NAMED[name](
        functools.partial(kernel, chunk=chunk, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, slopes.shape[0], blocks),
            in_specs=[spec] * len(operands), out_specs=[spec] * outputs,
            scratch_shapes=[pltpu.VMEM((width, width), jnp.float32)]),
        out_shape=[_out_struct(operands[0].shape, operands[0].dtype,
                               operands[0])] * outputs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(slopes, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _lightning(q, k, v, slopes, scale, chunk, block, interpret):
    return _call(_fwd_kernel, "fwd", slopes, (q, k, v), 1,
                 chunk=chunk, block=block, scale=scale,
                 interpret=interpret)[0]


def _lightning_fwd(q, k, v, slopes, scale, chunk, block, interpret):
    out = checkpoint_name(
        _lightning(q, k, v, slopes, scale, chunk, block, interpret),
        "hvd_lightning_out")
    return out, (q, k, v, slopes)


def _lightning_bwd(scale, chunk, block, interpret, saved, do):
    q, k, v, slopes = saved
    sizes = dict(chunk=chunk, block=block, scale=scale, interpret=interpret)
    dq, = _call(_fwd_kernel, "dq", slopes, (do, v, k), 1,
                **sizes)
    dk, dv = _call(_dkv_kernel, "dkv", slopes, (q, k, v, do), 2,
                   reverse=True, **sizes)
    return dq, dk, dv, jnp.zeros_like(slopes)


_lightning.defvjp(_lightning_fwd, _lightning_bwd)


def lightning_attention_quadratic(q, k, v, slopes, scale: float):
    """The definition: ``((Q K^T) * D) V`` over the whole sequence, float32,
    [B, S, H, D] in and out.  For tests and small sizes: it holds [S, S] a
    head."""
    seq = q.shape[1]
    gap = jnp.arange(seq)[:, None] - jnp.arange(seq)[None, :]
    decay = jnp.where(gap >= 0, jnp.exp(
        -slopes.astype(jnp.float32)[:, None, None]
        * jnp.maximum(gap, 0).astype(jnp.float32)), 0.0)       # [H, S, S]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * decay[None] * scale
    return jnp.einsum("bhqk,bkhd->bqhd", scores, v.astype(jnp.float32))


def lightning_attention_scan(q, k, v, slopes, scale: float,
                             chunk: Optional[int] = None):
    """The kernels' chunked recurrence in ``jax.numpy``: a ``lax.scan`` over
    the chunks with the float32 state carried, the products in the operands'
    dtype accumulated in float32.  [B, S, H, D] in, ``q``'s dtype out."""
    batch, seq, heads, width = q.shape
    chunk, _ = plan(seq, chunk)
    a = slopes.astype(jnp.float32)[:, None, None]              # [H, 1, 1]
    r = jnp.arange(chunk, dtype=jnp.float32)
    gap = r[:, None] - r[None, :]
    pair = jnp.where(gap >= 0, scale * jnp.exp(-a * jnp.maximum(gap, 0)), 0.0)
    out_decay = scale * jnp.exp(-a * (r[None, :, None] + 1))   # [H, C, 1]
    key_decay = jnp.exp(-a * (chunk - 1 - r[None, :, None]))
    chunk_decay = jnp.exp(-a * chunk)

    def by_chunk(x):            # [B, S, H, D] -> [chunks, B, H, C, D]
        return x.reshape(batch, seq // chunk, chunk, heads, width).transpose(
            1, 0, 3, 2, 4)

    def step(state, qkv):
        q, k, v = qkv
        scores = jnp.einsum("bhrd,bhsd->bhrs", q, k,
                            preferred_element_type=jnp.float32) * pair
        out = jnp.einsum("bhrs,bhsd->bhrd", scores.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        out = out + out_decay * jnp.einsum(
            "bhrd,bhde->bhre", q, state.astype(q.dtype),
            preferred_element_type=jnp.float32)
        decayed = (k.astype(jnp.float32) * key_decay).astype(k.dtype)
        state = chunk_decay * state + jnp.einsum(
            "bhsd,bhse->bhde", decayed, v, preferred_element_type=jnp.float32)
        return state, out.astype(q.dtype)

    state = vary_like(jnp.zeros((batch, heads, width, width), jnp.float32), q)
    _, out = lax.scan(step, state, (by_chunk(q), by_chunk(k), by_chunk(v)))
    return out.transpose(1, 0, 3, 2, 4).reshape(batch, seq, heads, width)


def lightning_attention(q, k, v, slopes, scale: Optional[float] = None, *,
                        chunk: Optional[int] = None,
                        block: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """``o_t = scale sum_{s <= t} exp(-slopes_h (t - s)) (q_t . k_s) v_s`` over
    q, k, v [batch, seq, heads, head_dim] and slopes [heads] (float32, not
    negative; no gradient reaches them); ``scale`` defaults to ``head_dim **
    -0.5``.  On a TPU the Pallas kernels (``head_dim`` whole 128-lane tiles,
    ``seq`` a whole number of chunks); elsewhere the scan form, unless
    ``interpret=True`` forces the kernels through the interpreter."""
    batch, seq, heads, width = q.shape
    scale = width ** -0.5 if scale is None else scale
    slopes = lax.stop_gradient(slopes.astype(jnp.float32))
    if interpret is None:
        if jax.default_backend() != "tpu":
            return checkpoint_name(lightning_attention_scan(
                q, k, v, slopes, scale, chunk), "hvd_lightning_out")
        interpret = False
    if width % LANES:
        raise ValueError(
            f"lightning_attention: heads of {width} lanes; the kernels take "
            f"whole {LANES}-lane tiles a head")
    chunk, block = plan(seq, chunk, block)
    flat = lambda x: x.reshape(batch, seq, heads * width)  # noqa: E731
    out = _lightning(flat(q), flat(k), flat(v), slopes, float(scale), chunk,
                     block, bool(interpret))
    return out.reshape(batch, seq, heads, width)
