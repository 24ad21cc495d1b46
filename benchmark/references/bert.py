"""BERT pretraining written out in plain float32 ``jax.numpy``.

A reference, not a model to train with: no flax, no Pallas, no bfloat16, no
fused projections beyond the parameter layout it reads.  It takes the
parameter tree of ``horovod_tpu.models.BertForPreTraining`` (so that both
run on the same seeded weights) and computes what Devlin et al.,
arXiv:1810.04805 (section 3, appendix A.2) and the paper's own
``modeling.py`` / ``run_pretraining.py`` describe: word + position +
token-type embeddings, a layer norm, L post-layer-norm blocks (masked
softmax attention written out, GELU feed-forward), the masked positions
gathered before the transform, the decoder tied to the word embeddings plus
a bias, a ``tanh`` pooler over position 0 with a 2-way classifier, and the
sum of the masked-LM mean and the next-sentence mean.  Gradients are
``jax.grad`` of these functions.  Call them under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise one bfloat16 pass.

Departures from the paper, all shared with the system under test:

- layer-norm epsilon 1e-6 (flax's default) where the paper's code has 1e-12;
- no dropout (the published 0.1 is a training-time choice; every cell of
  the benchmark runs with 0.0);
- GELU in its tanh form, as the paper's ``modeling.py`` writes it
  (HuggingFace's port uses the erf form);
- padding is at the tail: a sequence's real tokens are its first
  ``lengths[b]``, and a padded key gets -1e30 added where the paper's code
  adds -10000;
- the embedding matrix may hold more rows than the vocabulary (padding to
  whole tiles): only the first ``vocab_size`` rows are decoded, so padded
  rows take no part in the softmax and get a zero gradient.

This file is the one text of it (the benchmark carries its own reference):
``benchmark/families/bert.py`` and ``tests/single/test_bert_reference.py``
import it from here.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

LN_EPS = 1e-6
MASKED = -1e30


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def attention(x, p, lengths):
    """Multi-head self-attention of one block.  ``qkv`` is [H, 3, A, D] and
    ``out`` [A, D, H], the layout of the system's fused projections."""
    seq = x.shape[1]
    hidden, _, heads, head_dim = p["qkv"]["kernel"].shape
    w = p["qkv"]["kernel"].reshape(hidden, 3 * heads * head_dim)
    qkv = (x @ w).reshape(*x.shape[:2], 3, heads, head_dim) + p["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]        # [B, S, A, D]
    scores = jnp.einsum("bqad,bkad->baqk", q, k) / math.sqrt(head_dim)
    real_key = jnp.arange(seq)[None, :] < lengths[:, None]     # [B, S]
    scores = scores + jnp.where(real_key, 0.0, MASKED)[:, None, None, :]
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    context = jnp.einsum("baqk,bkad->bqad", probs, v)
    return (context.reshape(*x.shape[:2], heads * head_dim)
            @ p["out"]["kernel"].reshape(heads * head_dim, hidden)
            + p["out"]["bias"])


def block(x, p, lengths):
    """One post-layer-norm transformer block."""
    x = layer_norm(x + attention(x, p["attention"], lengths), p["ln_attn"])
    h = dense(gelu(dense(x, p["mlp_in"])), p["mlp_out"])
    return layer_norm(x + h, p["ln_mlp"])


def encoder(p, input_ids, token_type_ids, lengths):
    """[B, S] ids -> [B, S, H] hidden states of the last block."""
    seq = input_ids.shape[1]
    x = (p["word_embeddings"]["embedding"][input_ids]
         + p["position_embeddings"]["embedding"][jnp.arange(seq)][None]
         + p["token_type_embeddings"]["embedding"][token_type_ids])
    x = layer_norm(x, p["ln_embed"])
    layer = 0
    while f"layer_{layer}" in p:
        x = block(x, p[f"layer_{layer}"], lengths)
        layer += 1
    return x


def transformed(params, hidden, masked_positions):
    """The masked positions' hidden states, gathered, through the head's
    dense + GELU: [B, P, H], what the head's layer norm takes."""
    batch = jnp.arange(hidden.shape[0])[:, None]
    return gelu(dense(hidden[batch, masked_positions],
                      params["mlm_transform"]))


def decode(params, vocab_size, h, decoder=None):
    """Layer norm, then the decoder tied to the word embeddings plus its
    bias: [..., H] -> [..., vocab_size].  ``decoder`` stands in for the
    word-embedding matrix here alone, for a test that takes the tied
    matrix's two uses apart."""
    if decoder is None:
        decoder = params["encoder"]["word_embeddings"]["embedding"]
    return (layer_norm(h, params["mlm_ln"]) @ decoder[:vocab_size].T
            + params["mlm_bias"][:vocab_size])


def pretraining_logits(params, vocab_size, input_ids, token_type_ids,
                       lengths, masked_positions, decoder=None):
    """``(masked-LM logits [B, P, vocab_size], next-sentence logits [B, 2])``
    of ``params`` (the ``"params"`` tree of ``BertForPreTraining``)."""
    hidden = encoder(params["encoder"], input_ids, token_type_ids, lengths)
    mlm_logits = decode(params, vocab_size,
                        transformed(params, hidden, masked_positions),
                        decoder)
    pooled = jnp.tanh(dense(hidden[:, 0], params["pooler"]))
    return mlm_logits, dense(pooled, params["nsp_head"])


def log_softmax(logits):
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    return shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1,
                                     keepdims=True))


def loss_sums(mlm_logits, nsp_logits, mlm_labels, mlm_weights, nsp_labels):
    """``(weighted masked-LM log-loss summed, weights summed, next-sentence
    log-loss summed)``: the sums a loss over micro-batches adds up."""
    mlm = -jnp.take_along_axis(log_softmax(mlm_logits),
                               mlm_labels[..., None], axis=-1)[..., 0]
    nsp = -jnp.take_along_axis(log_softmax(nsp_logits),
                               nsp_labels[:, None], axis=-1)[:, 0]
    weights = mlm_weights.astype(jnp.float32)
    return jnp.sum(mlm * weights), jnp.sum(weights), jnp.sum(nsp)


def pretraining_loss(params, vocab_size, input_ids, token_type_ids, lengths,
                     masked_positions, mlm_labels, mlm_weights, nsp_labels):
    """The paper's objective on one batch: the masked-LM mean over the
    weighted positions plus the next-sentence mean over the sequences."""
    mlm_logits, nsp_logits = pretraining_logits(
        params, vocab_size, input_ids, token_type_ids, lengths,
        masked_positions)
    mlm, weight, nsp = loss_sums(mlm_logits, nsp_logits, mlm_labels,
                                 mlm_weights, nsp_labels)
    return mlm / jnp.maximum(weight, 1.0) + nsp / input_ids.shape[0]
