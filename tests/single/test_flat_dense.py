"""``FlatDenseGeneral``: ``nn.DenseGeneral``'s parameters and values, one
flat matmul (models/flat_dense.py), and the attention modules that use it."""

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu import models
from horovod_tpu.models.flat_dense import FlatDenseGeneral

# (input shape, DenseGeneral's features and axis): the projection into
# heads, the one out of them, and a plain Dense.
CASES = {"qkv": ((2, 5, 24), (3, 4, 6), -1),
         "out": ((2, 5, 4, 6), 24, (-2, -1)),
         "dense": ((3, 24), 7, -1)}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_same_parameters_and_values_as_dense_general(case, dtype):
    shape, features, axis = case
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    flat = FlatDenseGeneral(features, axis=axis, dtype=dtype)
    dense = nn.DenseGeneral(features, axis=axis, dtype=dtype)
    params = dense.init(jax.random.PRNGKey(1), x)
    ours = flat.init(jax.random.PRNGKey(1), x)
    assert jax.tree.structure(ours) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a bias that is not zero, so that its flattening is held too
    params = jax.tree.map(lambda leaf: leaf + 0.5, params)
    want = dense.apply(params, x)
    got = flat.apply(params, x)
    assert got.dtype == want.dtype
    lead = x.shape[:x.ndim - np.atleast_1d(axis).size]
    assert got.shape == lead + (int(np.prod(features)),)     # flat
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32).reshape(got.shape), rtol=tol, atol=tol)


def test_only_trailing_axes_flatten():
    x = jnp.ones((2, 3, 4))
    with pytest.raises(ValueError, match="trailing"):
        FlatDenseGeneral(5, axis=(0,)).init(jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("family", ["gpt", "bert"])
def test_attention_parameters_keep_their_shapes(family):
    """Checkpoints and the benchmark's references name these leaves."""
    ids = jnp.zeros((1, 8), jnp.int32)
    if family == "gpt":
        cfg, model = models.GPT_TINY, models.GPT(models.GPT_TINY)
        block = "h_0", "attn"
    else:
        cfg, model = models.BERT_TINY, models.BertEncoder(models.BERT_TINY)
        block = "layer_0", "attention"
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    attn = params[block[0]][block[1]]
    heads, dim = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    assert attn["qkv"]["kernel"].shape == (cfg.hidden_size, 3, heads, dim)
    assert attn["qkv"]["bias"].shape == (3, heads, dim)
    assert attn["out"]["kernel"].shape == (heads, dim, cfg.hidden_size)
    assert attn["out"]["bias"].shape == (cfg.hidden_size,)
