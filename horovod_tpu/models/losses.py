"""The one softmax cross-entropy of the models' loss functions
(``gpt.lm_loss``, ``bert.mlm_loss`` / ``nsp_loss``, ``mlp.xent_loss``).

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
