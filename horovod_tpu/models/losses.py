"""The one softmax cross-entropy of the models' loss functions
(``gpt.lm_loss``, ``bert.mlm_loss`` / ``nsp_loss``, ``mlp.xent_loss``,
``sdar.block_diffusion_loss``), and the same with the head's product inside,
a block of tokens at a time (at the end): :func:`tied_head_cross_entropy` for
a head tied to the embedding ``[V, d]`` (``zaya.Zaya.loss``,
``jamba.Jamba.loss``) and :func:`head_cross_entropy` for a head's own kernel
``[d, V]`` (``laguna.Laguna.loss``, ``joyai.JoyAI.loss``), which is the first
on the kernel turned; on a TPU a block's logits and their log-sum-exp are
one kernel's, ``ops/tied_head.py``.

``log_softmax`` followed by ``take_along_axis`` writes a log-probability for
every class though the loss reads one a row, and autodiff keeps that array
as the residual: a second float32 array of the logits' size (1.65 GB at
GPT-2-medium's 8 x 1023 x 50304), written in the forward and read in the
backward.  The gradient needs only the logits, each row's maximum and
log-sum and the labels, so the backward is written by hand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.collectives import vary_like as _vary_like
from ..ops.tied_head import head_logits


@jax.custom_vjp
def softmax_cross_entropy(logits, labels):
    """Negative log-likelihood of ``labels`` [...] (integers) under
    ``softmax(logits)`` over the last axis, ``logits`` [..., V]: float32
    [...], one value a row, ``logsumexp(logits) - logits[label]``.

    Reverse mode only (``jax.grad``, ``jax.vjp``): ``jax.jvp``, ``jacfwd``,
    ``hessian`` and ``linearize`` of it raise.  That is the price of
    ``custom_vjp``, and ``custom_jvp`` would not do: partial evaluation of
    a JVP rule keeps ``softmax - onehot`` in float32 as the residual, which
    is the second array of the logits' size that this function exists to
    avoid.  The forward creates no array of the logits' shape; the backward
    is one elementwise pass over the logits (kept in the caller's dtype)
    and returns the gradient in that dtype.
    """
    return _forward(logits, labels)[0]


def _forward(logits, labels):
    # log_softmax's own arithmetic, for the picked class alone: shifted by
    # the row's maximum before anything is summed or subtracted, so logits
    # of any size lose nothing (lse = max + log_sum would round to lse's ulp).
    x = logits.astype(jnp.float32)
    top = jnp.max(x, axis=-1, keepdims=True)
    log_sum = jnp.log(jnp.sum(jnp.exp(x - top), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    nll = log_sum - (picked.astype(jnp.float32) - top)
    return nll[..., 0], (logits, top, log_sum, labels)


def _backward(residuals, g):
    logits, top, log_sum, labels = residuals
    softmax = jnp.exp(logits.astype(jnp.float32) - top - log_sum)
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
    onehot = (classes == labels[..., None]).astype(jnp.float32)
    dlogits = g[..., None].astype(jnp.float32) * (softmax - onehot)
    return dlogits.astype(logits.dtype), None


softmax_cross_entropy.defvjp(_forward, _backward)


# ---------------------------------------------------------------------------
# A head and its cross-entropy, a block of tokens at a time
# ---------------------------------------------------------------------------

# What the float32 logits of one block may take, in bytes; nothing else of
# that shape lives.  A block reads the head's matrix three times and the
# gradient's accumulator once each way, so fewer, larger blocks cost fewer
# bytes: 131,136 rows (ZAYA's tied table) make it 2,048 tokens of 16,384, and
# a head of 12,544 to 16,384 rows takes all 16,384 tokens as one block, with
# no scan (in their cells Laguna's and JoyAI's steps read 6.0 and 2.4 ms
# shorter so than in blocks of 2,048, and compile to 1.5 and 0.5 GB less:
# PERF.md section 6, PR 57).  The logits themselves are written once (by the
# product that makes them, which on a TPU folds the row statistics as it
# goes) and read once by each of the two backward products, which make
# ``d logits`` of them on the way in.
HEAD_BLOCK_BYTES = 1100 * 1000 * 1000


def _head_blocks(tokens: int, rows: int) -> int:
    """The fewest blocks that divide ``tokens`` evenly, each one's float32
    logits over ``rows`` classes within ``HEAD_BLOCK_BYTES``."""
    most = max(1, HEAD_BLOCK_BYTES // (4 * rows))
    return next(n for n in range(-(-tokens // most), tokens + 1)
                if tokens % n == 0)


def _block_nll(x, table, labels):
    """One block: float32 logits ``x . table^T`` (``table`` [V, d], the
    product in ``x.dtype``, accumulated in float32), each row's negative
    log-likelihood, and what the gradient needs of the softmax, the row's
    log-sum-exp.  On a TPU the logits and the log-sum-exp are one kernel's
    (``hvd_head_logits``, ``ops/tied_head.py``): the product's tiles are
    folded into the rows' statistics before they leave VMEM.  Elsewhere the
    product is XLA's and the statistics a second pass over its logits."""
    made = head_logits(x, table)
    if made is not None:
        logits, lse = made
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)
        return (lse - picked)[:, 0], logits, lse
    logits = jax.lax.dot_general(x, table, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    top = jnp.max(logits, axis=-1, keepdims=True)
    log_sum = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)
    return (log_sum - (picked - top))[:, 0], logits, top + log_sum


def _in_blocks(rows: int, *per_token):
    """Each ``[T, ...]`` array as ``[blocks, T / blocks, ...]``."""
    blocks = _head_blocks(per_token[0].shape[0], rows)
    return tuple(a.reshape(blocks, a.shape[0] // blocks, *a.shape[1:])
                 for a in per_token)


def tied_head_cross_entropy(x, embedding, labels, weights):
    """``sum_t weights[t] x nll_t`` where ``nll_t`` is the negative
    log-likelihood of ``labels[t]`` under ``softmax(x[t] . embedding^T)``:
    a head tied to the embedding ``[V, d]`` and :func:`softmax_cross_entropy`
    in one, over ``x`` [T, d], a block of tokens at a time (a ``lax.scan``;
    a block's float32 logits take at most ``HEAD_BLOCK_BYTES``), so that no
    ``[T, V]`` array ever lives: at 16,384 tokens and 131,136 rows the
    float32 logits would be 8.6 GB.

    The products run in ``x.dtype`` (the embedding is cast to it once) and
    accumulate in float32; the logits of a block are float32.  ``weights``
    [T] float32 are constants of the loss (a mask over the positions that
    predict, over their number); no gradient flows to them.

    Reverse mode only, as :func:`softmax_cross_entropy`.  Under ``jax.grad``
    the forward makes the gradients too, block by block beside the loss
    (``d logits = weights x (softmax - onehot)``, then ``dx = d logits .
    E`` and ``dE += d logits^T . x``), so the logits are never computed
    twice: three products a block.  The backward scales them by the
    cotangent.  ``dE`` is float32 ``[V, d]``, the embedding's gradient as
    the head sees it; autodiff adds the gather's.

    A block's passes.  (1) The logits' product reads the block's ``x`` and
    the embedding and writes the float32 logits; on a TPU it is the kernel
    ``hvd_head_logits`` (``ops/tied_head.py``), which folds each tile into
    the rows' running maximum and sum before it leaves VMEM and hands back
    the log-sum-exp too, so nothing reads the logits for their statistics;
    elsewhere it is XLA's product and a second pass over the logits
    (maximum, ``exp``, sum).  (2) One scalar a row is gathered from the
    logits (the label's).  (3) and (4) The two backward products each read
    the logits once, with the log-sum-exp, the labels and the weights, and
    make ``d logits`` in ``x.dtype`` of them on the way in: XLA's own, as
    is the scan over the blocks."""
    return _tied(x, _vary_like(embedding, x), _vary_like(labels, x),
                 _vary_like(weights, x))


def head_cross_entropy(x, kernel, labels, weights):
    """:func:`tied_head_cross_entropy` for a head of its own: ``nll_t`` under
    ``softmax(x[t] . kernel)``, ``kernel`` [d, V] as ``laguna.Laguna`` and
    ``joyai.JoyAI`` store their ``lm_head``.  It is the same function of the
    kernel turned: the float32 master is cast to ``x.dtype`` and turned to
    the ``[V, d]`` rows the blocks read in one pass a call, and ``d kernel``
    is the float32 ``[V, d]`` sum turned back once (a layout of the product
    that writes it, where there is one block), never rounded to ``x.dtype``.
    The same blocks, the same passes, the same arithmetic.  What it
    replaces: the whole float32 logits from XLA's product handed to
    :func:`softmax_cross_entropy`, which reads them again for the sums of
    the exponentials and hands the two backward products a float32
    ``d logits``."""
    return tied_head_cross_entropy(x, kernel.T, labels, weights)


@jax.custom_vjp
def _tied(x, embedding, labels, weights):
    table = embedding.astype(x.dtype)

    def block(total, part):
        xb, lb, wb = part
        return total + jnp.sum(wb * _block_nll(xb, table, lb)[0]), None

    return jax.lax.scan(block, _vary_like(jnp.zeros((), jnp.float32), x),
                        _in_blocks(table.shape[0], x, labels, weights))[0]


def _tied_forward(x, embedding, labels, weights):
    table = embedding.astype(x.dtype)

    def block(carry, part):
        total, d_table = carry
        xb, lb, wb = part
        nll, logits, lse = _block_nll(xb, table, lb)
        classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        d_logits = (wb[:, None] * (jnp.exp(logits - lse)
                                   - (classes == lb[:, None]))).astype(x.dtype)
        dx = jnp.dot(d_logits, table, preferred_element_type=jnp.float32)
        d_table = d_table + jax.lax.dot_general(
            d_logits, xb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (total + jnp.sum(wb * nll), d_table), dx.astype(x.dtype)

    start = (_vary_like(jnp.zeros((), jnp.float32), x),
             _vary_like(jnp.zeros(embedding.shape, jnp.float32), x))
    (total, d_table), dx = jax.lax.scan(
        block, start, _in_blocks(table.shape[0], x, labels, weights))
    return total, (dx.reshape(x.shape), d_table.astype(embedding.dtype))


def _tied_backward(residuals, g):
    dx, d_table = residuals
    return ((g * dx).astype(dx.dtype), (g * d_table).astype(d_table.dtype),
            None, None)


_tied.defvjp(_tied_forward, _tied_backward)
