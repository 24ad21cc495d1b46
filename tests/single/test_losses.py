"""``models.softmax_cross_entropy`` (forward without a log-probability
array, backward written by hand) against the plain ``log_softmax`` +
``take_along_axis`` form it replaced, and the four loss functions built on it
against their text before the change, which is kept here as the reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import models
from horovod_tpu.models import softmax_cross_entropy

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
SHAPES = {"2d": (16, 40), "3d": (3, 5, 40)}
# Against the plain form in the logits' own dtype, value and gradient
# (cotangents up to 2): float32 rounding of a handful of operations; in
# bfloat16 the plain form rounds every log-probability to eight bits, the
# new form computes in float32 and rounds the gradient once.
TOLS = {"float32": (1e-6, 2e-6), "bfloat16": (6e-2, 1.6e-2)}


def plain_nll(logits, labels):
    """What every caller wrote before: a log-probability for every class,
    one of them picked."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def _draw(shape, dtype, seed=0, scale=3.0):
    k_logits, k_labels, k_cot = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = (scale * jax.random.normal(k_logits, shape)).astype(dtype)
    labels = jax.random.randint(k_labels, shape[:-1], 0, shape[-1])
    cotangent = jax.random.uniform(k_cot, shape[:-1], minval=0.5, maxval=2.0)
    return logits, labels, cotangent


def _value_and_grad(nll, logits, labels, cotangent):
    value, vjp = jax.vjp(lambda l: nll(l, labels).astype(jnp.float32),
                         logits)
    return value, vjp(cotangent)[0]


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
@pytest.mark.parametrize("dtype", DTYPES.keys())
def test_value_and_gradient_match_the_plain_form(dtype, shape):
    logits, labels, cotangent = _draw(shape, DTYPES[dtype])
    value, grad = _value_and_grad(softmax_cross_entropy, logits, labels,
                                  cotangent)
    assert value.dtype == jnp.float32 and value.shape == shape[:-1]
    # The same mathematics in float32 on the same (exactly widened) logits:
    # the value to float32 rounding, the gradient to one rounding into the
    # logits' dtype.
    exact_value, exact_grad = _value_and_grad(
        plain_nll, logits.astype(jnp.float32), labels, cotangent)
    np.testing.assert_allclose(value, exact_value, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(
        grad.astype(jnp.float32), exact_grad,
        atol=1e-6, rtol=float(jnp.finfo(DTYPES[dtype]).eps))
    # The plain form in the logits' own dtype, as the callers ran it.
    value_tol, grad_tol = TOLS[dtype]
    same_value, same_grad = _value_and_grad(plain_nll, logits, labels,
                                            cotangent)
    np.testing.assert_allclose(value, same_value, atol=value_tol)
    np.testing.assert_allclose(grad.astype(jnp.float32),
                               same_grad.astype(jnp.float32), atol=grad_tol)


@pytest.mark.parametrize("dtype", DTYPES.keys())
def test_gradient_has_the_logits_dtype(dtype):
    logits, labels, _ = _draw(SHAPES["3d"], DTYPES[dtype])
    grad = jax.grad(lambda l: softmax_cross_entropy(l, labels).mean())(logits)
    assert grad.dtype == DTYPES[dtype] and grad.shape == logits.shape


@pytest.mark.parametrize("dtype", DTYPES.keys())
def test_rows_with_a_zero_cotangent_get_a_zero_gradient(dtype):
    logits, labels, cotangent = _draw(SHAPES["2d"], DTYPES[dtype], seed=1)
    cotangent = cotangent.at[::3].set(0.0)
    _, grad = _value_and_grad(softmax_cross_entropy, logits, labels,
                              cotangent)
    grad = np.asarray(grad.astype(jnp.float32))
    assert not grad[::3].any()
    assert np.abs(grad[1::3]).min() > 0.0


@pytest.mark.parametrize("dtype", DTYPES.keys())
def test_padded_columns_at_minus_1e30_weigh_nothing(dtype):
    """BERT's padded vocabulary: exp(-1e30 - lse) is 0 exactly, so those
    columns add nothing to a row's sum and get a gradient of exactly 0."""
    shape, real = SHAPES["3d"], 33
    logits, labels, cotangent = _draw(shape, DTYPES[dtype], seed=2)
    labels = labels % real
    padded = jnp.where(jnp.arange(shape[-1]) < real, logits,
                       jnp.asarray(-1e30, DTYPES[dtype]))
    value, grad = _value_and_grad(softmax_cross_entropy, padded, labels,
                                  cotangent)
    short_value, short_grad = _value_and_grad(
        softmax_cross_entropy, logits[..., :real], labels, cotangent)
    np.testing.assert_allclose(value, short_value, atol=1e-6, rtol=1e-6)
    grad = np.asarray(grad.astype(jnp.float32))
    assert not grad[..., real:].any()
    np.testing.assert_allclose(grad[..., :real],
                               short_grad.astype(jnp.float32),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("dtype", DTYPES.keys())
def test_logits_of_1e4_neither_overflow_nor_vanish(dtype):
    shape = SHAPES["2d"]
    signs = jnp.where(jnp.arange(shape[-1]) % 2 == 0, 1e4, -1e4)
    logits = jnp.broadcast_to(signs, shape).astype(DTYPES[dtype])
    labels = jnp.arange(shape[0]) % shape[-1]
    value, grad = _value_and_grad(softmax_cross_entropy, logits, labels,
                                  jnp.ones(shape[:-1]))
    value, grad = np.asarray(value), np.asarray(grad.astype(jnp.float32))
    assert np.isfinite(value).all() and np.isfinite(grad).all()
    # Twenty columns tie at +1e4: a label among them costs log(20), one at
    # -1e4 costs the gap (2e4 as the dtype rounds it) more, and a row's
    # gradient still sums to zero.
    even = np.asarray(labels) % 2 == 0
    tie = float(np.log(shape[-1] // 2))
    gap = float(logits[0, 0].astype(jnp.float32)
                - logits[0, 1].astype(jnp.float32))
    np.testing.assert_allclose(value[even], tie, rtol=1e-6)
    np.testing.assert_allclose(value[~even], gap + tie, rtol=1e-6)
    np.testing.assert_allclose(grad.sum(-1), 0.0, atol=2e-2)


# The four loss functions as models/{gpt,bert,mlp}.py had them before they
# became one-liners over softmax_cross_entropy.
def _old_lm_loss(logits, input_ids):
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    tgt = input_ids[:, 1:]
    ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return -ll.mean()


def _old_mlm_loss(logits, labels, label_weights):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    w = label_weights.astype(jnp.float32)
    return -(ll * w).sum() / jnp.maximum(w.sum(), 1.0)


def _old_nsp_loss(nsp_logits, nsp_labels):
    logp = jax.nn.log_softmax(nsp_logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, nsp_labels[:, None],
                                         axis=-1))


def _old_xent_loss(logits, labels):
    logp = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), labels[:, None], axis=-1)
    return -logp.mean()


# name: (the function, its text before the change, its arguments out of
# drawn logits [4, 9, 40], labels [4, 9] and 0 / 1 weights [4, 9]).
WRAPPERS = {
    "lm_loss": (models.lm_loss, _old_lm_loss, lambda l, y, w: (l, y)),
    "mlm_loss": (models.mlm_loss, _old_mlm_loss, lambda l, y, w: (l, y, w)),
    "mlm_loss-no-weight": (models.mlm_loss, _old_mlm_loss,
                           lambda l, y, w: (l, y, 0 * w)),
    "nsp_loss": (models.nsp_loss, _old_nsp_loss,
                 lambda l, y, w: (l[:, 0, :2], y[:, 0] % 2)),
    "xent_loss": (models.xent_loss, _old_xent_loss,
                  lambda l, y, w: (l[:, 0], y[:, 0])),
}


@pytest.mark.parametrize("name", WRAPPERS.keys())
def test_wrapper_equals_its_text_before_the_change(name):
    new, old, arguments = WRAPPERS[name]
    logits, labels, _ = _draw((4, 9, 40), jnp.float32, seed=3)
    weights = jnp.broadcast_to(jnp.arange(9) % 3 != 0, (4, 9)).astype(
        jnp.int32)
    args = arguments(logits, labels, weights)
    value, grad = jax.value_and_grad(new)(*args)
    old_value, old_grad = jax.value_and_grad(old)(*args)
    assert value.shape == () and value.dtype == jnp.float32
    np.testing.assert_allclose(value, old_value, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(grad, old_grad, atol=1e-6, rtol=1e-6)


def _under_jit(loss, logits, labels):
    return jax.jit(jax.value_and_grad(loss))(logits, labels)


def _under_vmap(loss, logits, labels):
    """The loss of each leading slice alone, then their mean."""
    def mean_of_rows(logits, labels):
        return jax.vmap(loss)(logits, labels).mean()
    return jax.value_and_grad(mean_of_rows)(logits, labels)


def _under_shard_map(loss, logits, labels):
    """The rows over a 2-device mesh, the mean taken across it."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("hvd",))

    def local(logits, labels):
        value, grad = jax.value_and_grad(loss)(logits, labels)
        return jax.lax.pmean(value, "hvd"), grad / 2

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("hvd"), P("hvd")),
        out_specs=(P(), P("hvd"))))(logits, labels)


@pytest.mark.parametrize("transform", [_under_jit, _under_vmap,
                                       _under_shard_map],
                         ids=["jit", "vmap", "shard_map"])
def test_goes_through(transform):
    logits, labels, _ = _draw((4, 6, 40), jnp.float32, seed=4)

    def loss(logits, labels):
        return softmax_cross_entropy(logits, labels).mean()

    value, grad = transform(loss, logits, labels)
    want_value, want_grad = jax.value_and_grad(
        lambda l, y: plain_nll(l, y).mean())(logits, labels)
    np.testing.assert_allclose(value, want_value, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(grad, want_grad, atol=1e-6, rtol=1e-6)


def test_forward_mode_is_refused():
    """custom_vjp, not custom_jvp (the docstring says why): reverse mode is
    what there is, and forward mode fails loudly, not with a wrong number."""
    logits, labels, _ = _draw(SHAPES["2d"], jnp.float32)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda l: softmax_cross_entropy(l, labels), (logits,),
                (jnp.ones_like(logits),))
