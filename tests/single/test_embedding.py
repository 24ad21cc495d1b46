"""``ops/embedding.py:embed_lookup``: ``nn.Embed``'s value bit for bit, and
a table gradient that on a TPU is the segment sum in id order
(``embed_grad_sum_rows``, interpreted here) and off it the scatter-add it
was.  Small shapes; the chip's compiler sees the cells' in
``test_tpu_compile.py`` and the chip in ``chip_smoke.py --embed-grad``."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import embedding
from horovod_tpu.ops.embedding import KERNEL_NAME, embed_lookup

D = 128


@pytest.fixture(autouse=True)
def as_a_large_table(request, monkeypatch):
    """These small tables take the path of a large one, their rows taken
    from the table as it is kept and cast after; a case named ``small-table``
    keeps the bound and its table is cast first."""
    if "small-table" not in request.node.name:
        monkeypatch.setattr(embedding, "CAST_FIRST_BYTES", 0)


def _table(rows):
    return jax.random.normal(jax.random.PRNGKey(rows), (rows, D), jnp.float32)


def _zipf(rows, m):
    ranks = np.random.default_rng(0).zipf(1.3, size=m)
    return jnp.asarray((ranks - 1) % rows, jnp.int32)


# (rows of the table, ids): what a step's ids may look like.  The first four
# share their shapes, so one compiled program serves them (_table_gradient).
CASES = {
    "uniform": lambda: (1024, jax.random.randint(
        jax.random.PRNGKey(1), (512,), 0, 1024)),
    "all-one-id": lambda: (1024, jnp.full((512,), 700, jnp.int32)),
    "zipf-skewed": lambda: (1024, _zipf(1024, 512)),
    "whole-tiles-without-an-id": lambda: (1024, jnp.concatenate([
        jax.random.randint(jax.random.PRNGKey(2), (256,), 0, 100),
        jax.random.randint(jax.random.PRNGKey(3), (256,), 900, 1024)])),
    "rows-no-whole-number-of-tiles": lambda: (1000, jax.random.randint(
        jax.random.PRNGKey(4), (4, 128), 0, 1000)),
    "ids-no-whole-number-of-tiles": lambda: (1024, jax.random.randint(
        jax.random.PRNGKey(5), (300,), 0, 1024)),
    "small-table-cast-first": lambda: (256, jax.random.randint(
        jax.random.PRNGKey(13), (2, 192), 0, 256)),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16",
                                   "bfloat16-small-table-cast-first"])
def test_the_value_is_nn_embed_s_bit_for_bit(dtype):
    rows, dtype = 1000, dtype.partition("-")[0]
    table = _table(rows)
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 150), 0, rows)
    want = nn.Embed(rows, D, dtype=jnp.dtype(dtype)).apply(
        {"params": {"embedding": table}}, ids)
    cast_first = "convert_element_type" in str(jax.make_jaxpr(
        lambda t: embed_lookup(t, ids, dtype))(table)).split("custom_vjp")[0]
    assert cast_first == (embedding.CAST_FIRST_BYTES > 0
                          and dtype == "bfloat16")
    got = jax.jit(lambda t: embed_lookup(t, ids, dtype))(table)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@functools.lru_cache(None)
def _table_gradient(dtype):
    """The lookup's table gradient by the interpreted kernel, jitted once a
    dtype: cases of one shape share the compiled program."""
    def gradient(table, ids, g):
        return jax.vjp(lambda t: embed_lookup(
            t, ids, dtype, interpret=pltpu.InterpretParams()), table)[1](g)[0]
    return jax.jit(gradient)


@pytest.mark.parametrize("case,dtype", [
    *((case, "bfloat16") for case in CASES),
    ("all-one-id", "float32")])
def test_the_table_gradient_by_the_kernel_is_the_sum_in_float32(case, dtype):
    """``dE[v]`` = the cotangent rows of id v summed in float32 and rounded
    once to the cotangent's dtype, then cast to the table's; a tile of the
    table no id fell in is written as zeros (the interpreter's memory starts
    as NaN)."""
    rows, ids = CASES[case]()
    table = _table(rows)
    g = jax.random.normal(jax.random.PRNGKey(8), (*ids.shape, D),
                          jnp.dtype(dtype))
    if case == "uniform":
        assert KERNEL_NAME in str(
            jax.make_jaxpr(_table_gradient(dtype))(table, ids, g))
    got = _table_gradient(dtype)(table, ids, g)
    want = np.zeros((rows, D), np.float32)
    np.add.at(want, np.asarray(ids).reshape(-1),
              np.asarray(g, np.float32).reshape(-1, D))
    assert (got.dtype, got.shape) == (table.dtype, table.shape)
    np.testing.assert_allclose(
        got, want, rtol=2.0 ** -7 if dtype == "bfloat16" else 2e-5, atol=1e-6)


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["scatter-add", "kernel"])
def test_a_tied_use_sums_both_gradients(interpret):
    """The lookup and a head on the same table: the table's gradient is the
    lookup's plus the product's, on either path."""
    table = _table(256)
    ids = jax.random.randint(jax.random.PRNGKey(9), (96,), 0, 256)
    x = jax.random.normal(jax.random.PRNGKey(10), (D,), jnp.float32)

    def loss(table, lookup):
        return (jnp.sum(lookup(table) ** 2) + jnp.sum(jnp.sin(table @ x)))

    got = jax.jit(jax.grad(lambda t: loss(t, lambda t: embed_lookup(
        t, ids, jnp.float32, interpret=interpret))))(table)
    want = jax.grad(lambda t: loss(t, lambda t: t[ids]))(table)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_off_the_tpu_the_backward_is_the_scatter_add():
    """``interpret=None`` on the tests' backend: no kernel, the cotangent
    rows added into zeros in their own dtype, a duplicate at a time, and no
    cotangent for the ids."""
    table = _table(256)
    ids = jax.random.randint(jax.random.PRNGKey(11), (2, 64), 0, 256)
    g = jax.random.normal(jax.random.PRNGKey(12), (2, 64, D), jnp.bfloat16)
    _, vjp = jax.vjp(lambda t: embed_lookup(t, ids, jnp.bfloat16), table)
    text = str(jax.make_jaxpr(vjp)(g))
    assert "scatter-add" in text and "pallas_call" not in text
    want = jnp.zeros((256, D), jnp.bfloat16).at[ids.reshape(-1)].add(
        g.reshape(-1, D)).astype(jnp.float32)
    np.testing.assert_array_equal(vjp(g)[0], want)
    grads = jax.grad(lambda t, i: jnp.sum(embed_lookup(t, i)), argnums=(0, 1),
                     allow_int=True)(table, ids)
    assert grads[1].dtype == jax.dtypes.float0
