"""``models.BertForPreTraining`` (the published head: gathered positions,
tied decoder, pooler, next-sentence classifier; a key length per sequence)
against the plain float32 ``jax.numpy`` reference, on seeded weights at
``BERT_TINY`` sizes.  The vocabulary is 1000, so that the 1024-row embedding
matrix has padding rows to keep out of the softmax."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.common import l2_rel_err as _l2, rel_err as _rel
from benchmark.references import bert as bert_ref
from horovod_tpu import models

CFG = dataclasses.replace(models.BERT_TINY, vocab_size=1000,
                          dtype=jnp.float32)
BATCH, SEQ, PREDICTIONS = 4, 48, 6
LENGTHS = (48, 17, 32, 5)

# System and reference are the same float32 arithmetic in another order
# (fused projections, flax's layer norm, XLA's softmax).  Read on the CPU:
# logits 0.8e-6 to 1.3e-6 of their largest, loss the same to the last bit,
# gradients 0.2e-6 to 1.5e-6 as an L2 error over a leaf; the bounds are some
# ten times that.  With bf16 activations the same numbers read 1.6e-2 to
# 3.1e-2, 4.6e-4 and 1.5e-2 to 2.0e-2
# (``test_bf16_activations_would_fail_these_bounds``).
TOL_LOGITS = 2e-5      # max |a - b| / max |b|
TOL_LOSS = 2e-6        # relative
TOL_GRAD = 2e-5        # ||a - b|| / ||b|| over a leaf


@pytest.fixture(scope="module")
def batch():
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    return dict(
        input_ids=jax.random.randint(ks[0], (BATCH, SEQ), 0, CFG.vocab_size),
        token_type_ids=jax.random.randint(ks[1], (BATCH, SEQ), 0, 2),
        lengths=lengths,
        masked_positions=jax.random.randint(
            ks[2], (BATCH, PREDICTIONS), 0, 1 << 20) % lengths[:, None],
        mlm_labels=jax.random.randint(ks[3], (BATCH, PREDICTIONS), 0,
                                      CFG.vocab_size),
        mlm_weights=(jnp.arange(PREDICTIONS)[None, :]
                     < jnp.asarray([6, 3, 5, 1])[:, None]).astype(jnp.float32),
        nsp_labels=jax.random.randint(ks[4], (BATCH,), 0, 2))


@pytest.fixture(scope="module")
def params(batch):
    """Seeded weights with every bias and layer-norm offset off zero, so
    that none of them can be dropped unseen."""
    variables = models.BertForPreTraining(CFG).init(
        jax.random.PRNGKey(7), batch["input_ids"], batch["token_type_ids"],
        masked_positions=batch["masked_positions"])
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def _system(cfg, params, b, **changed):
    b = {**b, **changed}
    mlm, nsp = models.BertForPreTraining(cfg).apply(
        {"params": params}, b["input_ids"], b["token_type_ids"],
        lengths=b["lengths"], masked_positions=b["masked_positions"])
    loss = models.pretraining_loss(mlm, nsp, b["mlm_labels"],
                                   b["mlm_weights"], b["nsp_labels"])
    return loss, (mlm, nsp)


def _reference(params, b, **changed):
    b = {**b, **changed}
    args = (CFG.vocab_size, b["input_ids"], b["token_type_ids"],
            b["lengths"], b["masked_positions"])
    loss = bert_ref.pretraining_loss(params, *args, b["mlm_labels"],
                                     b["mlm_weights"], b["nsp_labels"])
    return loss, bert_ref.pretraining_logits(params, *args)


def _named(grads):
    """The leaves the benchmark's ``correct`` names, and the last block's."""
    enc = grads["encoder"]
    return {"word_embeddings": enc["word_embeddings"]["embedding"],
            "layer_0.qkv": enc["layer_0"]["attention"]["qkv"]["kernel"],
            "layer_1.mlp_out": enc["layer_1"]["mlp_out"]["kernel"],
            "nsp_head": grads["nsp_head"]["kernel"],
            "mlm_bias": grads["mlm_bias"]}


@pytest.fixture(scope="module")
def reference(params, batch):
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            _reference, has_aux=True)(params, batch)
    return loss, logits, grads


@pytest.mark.parametrize("use_flash", [True, False],
                         ids=["flash_fallback", "dense_mask"])
def test_system_matches_the_plain_reference(params, batch, reference,
                                            use_flash):
    """Both logits, the loss and the named gradients; off-TPU ``use_flash``
    takes ``dense_attention(kv_lens=...)``, the other path the module's own
    masked einsum."""
    cfg = dataclasses.replace(CFG, use_flash=use_flash)
    (loss, (mlm, nsp)), grads = jax.value_and_grad(
        lambda p: _system(cfg, p, batch), has_aux=True)(params)
    ref_loss, (ref_mlm, ref_nsp), ref_grads = reference
    assert mlm.shape == (BATCH, PREDICTIONS, CFG.padded_vocab_size)
    assert mlm.dtype == nsp.dtype == jnp.float32
    # The padding rows of the vocabulary are out of the softmax.
    assert float(jnp.max(mlm[..., CFG.vocab_size:])) <= -1e30
    assert _rel(mlm[..., :CFG.vocab_size], ref_mlm) < TOL_LOGITS
    assert _rel(nsp, ref_nsp) < TOL_LOGITS
    assert abs(float(loss) / float(ref_loss) - 1) < TOL_LOSS
    for (name, got), want in zip(_named(grads).items(),
                                 _named(ref_grads).values()):
        assert _l2(got, want) < TOL_GRAD, name
    assert not np.asarray(_named(grads)["word_embeddings"])[
        CFG.vocab_size:].any()


def test_bf16_activations_would_fail_these_bounds(params, batch, reference):
    """The bounds above hold float32 to float32: the same module with bf16
    activations is outside each of them."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    (loss, (mlm, nsp)), grads = jax.value_and_grad(
        lambda p: _system(cfg, p, batch), has_aux=True)(params)
    ref_loss, (ref_mlm, _), ref_grads = reference
    assert _rel(mlm[..., :CFG.vocab_size], ref_mlm) > 10 * TOL_LOGITS
    assert abs(float(loss) / float(ref_loss) - 1) > 10 * TOL_LOSS
    for name in ("word_embeddings", "layer_0.qkv", "nsp_head"):
        assert _l2(_named(grads)[name],
                   _named(ref_grads)[name]) > 10 * TOL_GRAD, name


@pytest.mark.parametrize("which", ["system_flash_fallback",
                                   "system_dense_mask", "reference"])
def test_tokens_beyond_a_sequences_length_change_nothing(params, batch,
                                                         which):
    """Other ids and token types in the padding: the same logits, loss and
    gradient, every leaf of it."""
    real = jnp.arange(SEQ)[None, :] < batch["lengths"][:, None]
    other = dict(
        input_ids=jnp.where(real, batch["input_ids"],
                            (batch["input_ids"] + 1) % CFG.vocab_size),
        token_type_ids=jnp.where(real, batch["token_type_ids"],
                                 1 - batch["token_type_ids"]))
    assert not np.array_equal(other["input_ids"], batch["input_ids"])
    if which == "reference":
        def fn(p, **kw):
            return _reference(p, batch, **kw)
    else:
        cfg = dataclasses.replace(CFG, use_flash=which.endswith("fallback"))

        def fn(p, **kw):
            return _system(cfg, p, batch, **kw)

    (loss_a, logits_a), grads_a = jax.value_and_grad(fn, has_aux=True)(params)
    (loss_b, logits_b), grads_b = jax.value_and_grad(
        lambda p: fn(p, **other), has_aux=True)(params)
    # Exactly the same arithmetic on the real rows; the word-embedding
    # gradient sums the same terms into other rows, hence not bitwise.
    assert float(loss_a) == float(loss_b)
    for a, b in zip(logits_a, logits_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(grads_a),
                    jax.tree_util.tree_leaves(grads_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-6)


def test_the_tied_embeddings_gradient_is_the_sum_of_its_two_uses(
        params, batch, reference):
    """The reference can take the decoder's matrix apart from the lookup's:
    the gradients of the two uses, each non-zero, add up to the tied one,
    the reference's and the system's."""
    table = params["encoder"]["word_embeddings"]["embedding"]

    def two_uses(lookup, decoder):
        p = jax.tree_util.tree_map(lambda x: x, params)
        p["encoder"]["word_embeddings"]["embedding"] = lookup
        b = batch
        mlm, nsp = bert_ref.pretraining_logits(
            p, CFG.vocab_size, b["input_ids"], b["token_type_ids"],
            b["lengths"], b["masked_positions"], decoder=decoder)
        mlm_sum, weight, nsp_sum = bert_ref.loss_sums(
            mlm, nsp, b["mlm_labels"], b["mlm_weights"], b["nsp_labels"])
        return mlm_sum / weight + nsp_sum / BATCH

    with jax.default_matmul_precision("highest"):
        by_lookup, by_decoder = jax.grad(two_uses, argnums=(0, 1))(table,
                                                                   table)
    assert np.linalg.norm(by_lookup) > 0 and np.linalg.norm(by_decoder) > 0
    tied_ref = _named(reference[2])["word_embeddings"]
    assert _l2(by_lookup + by_decoder, tied_ref) < 1e-6
    tied = _named(jax.grad(lambda p: _system(CFG, p, batch)[0])(params))[
        "word_embeddings"]
    assert _l2(tied, by_lookup + by_decoder) < TOL_GRAD
    # Neither use alone is the gradient.
    assert _l2(tied, by_lookup) > 0.1 and _l2(tied, by_decoder) > 0.1


def test_every_position_call_is_the_gathered_one_at_every_position(params,
                                                                  batch):
    """Without ``masked_positions`` the module returns the masked-LM logits
    at every position, as it always did: the same head, not another."""
    model = models.BertForPreTraining(CFG)
    every = model.apply({"params": params}, batch["input_ids"],
                        batch["token_type_ids"], lengths=batch["lengths"])
    assert every.shape == (BATCH, SEQ, CFG.padded_vocab_size)
    gathered, _ = model.apply(
        {"params": params}, batch["input_ids"], batch["token_type_ids"],
        lengths=batch["lengths"],
        masked_positions=batch["masked_positions"])
    picked = jnp.take_along_axis(
        every, batch["masked_positions"][..., None], axis=1)
    np.testing.assert_allclose(np.asarray(gathered), np.asarray(picked),
                               rtol=1e-5, atol=1e-5)
    # A boolean tail-padding mask means the same as the lengths it sums to
    # wherever a loss may read: the mask takes the dense path, whose padded
    # rows are not zeroed.
    real = jnp.arange(SEQ)[None, :] < batch["lengths"][:, None]
    by_mask = model.apply({"params": params}, batch["input_ids"],
                          batch["token_type_ids"], real)
    np.testing.assert_allclose(np.asarray(by_mask)[np.asarray(real)],
                               np.asarray(every)[np.asarray(real)],
                               rtol=1e-5, atol=1e-5)


def test_a_mask_that_is_not_tail_padding_is_honoured_key_by_key(params,
                                                               batch):
    """``use_flash`` is on by default and the kernels know lengths only: a
    mask alone (left padding here) must not be read as its row sums."""
    dense = models.BertForPreTraining(dataclasses.replace(CFG,
                                                          use_flash=False))
    flash = models.BertForPreTraining(dataclasses.replace(CFG,
                                                          use_flash=True))
    lengths = batch["lengths"]
    left = jnp.arange(SEQ)[None, :] >= (SEQ - lengths)[:, None]
    assert bool(jnp.any(left != (jnp.arange(SEQ)[None, :]
                                 < lengths[:, None])))
    args = ({"params": params}, batch["input_ids"], batch["token_type_ids"])
    want = np.asarray(dense.apply(*args, left))
    np.testing.assert_array_equal(np.asarray(flash.apply(*args, left)), want)
    # ... and read as row sums it would have been another result.
    as_tail = np.asarray(flash.apply(*args, lengths=lengths))
    keep = np.asarray(left & (jnp.arange(SEQ)[None, :] < lengths[:, None]))
    assert np.max(np.abs(as_tail[keep] - want[keep])) > 1e-2
