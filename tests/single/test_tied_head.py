"""``ops/tied_head.py``: a blocked head's logits and their log-sum-exp from
one kernel (``hvd_head_logits``), run by the Pallas interpreter, against the
``jax.numpy`` form of ``models/losses.py:_block_nll``; and
``tied_head_cross_entropy`` and ``head_cross_entropy`` (the same blocks for a
table ``[V, d]`` and for a head's own kernel ``[d, V]``) with the kernel
forced on and off against every logit alive."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import losses
from horovod_tpu.ops import tied_head as th

TILE = 128
# (tokens, table rows, token tile): by a vocabulary tile of 128.
CASES = {"whole-tiles": (128, 384, 128), "a-last-tile-of-64": (128, 320, 128),
         "one-tile": (256, 128, 256), "one-tile-in-part": (128, 72, 128),
         "two-token-tiles": (256, 200, 128)}
TOL = 2e-5


def _operands(case, dtype, d=128):
    tokens, rows, _ = CASES[case]
    keys = jax.random.split(jax.random.key(rows), 3)
    x = jax.random.normal(keys[0], (tokens, d), dtype)
    table = (jax.random.normal(keys[1], (rows, d)) / 4).astype(dtype)
    # Labels all over the table, and its last row for some: in the last,
    # partial tile where there is one.
    labels = jax.random.randint(keys[2], (tokens,), 0, rows).at[::5].set(
        rows - 1)
    return x, table, labels


def _by_kernel(case, x, table):
    tiles = th.HeadPlan(CASES[case][2], min(TILE, table.shape[0]))
    logits, lse = th._head_logits(x, table, tiles, True)
    return logits.T, lse.reshape(-1, 1)


@pytest.fixture
def forced(monkeypatch):
    """The head takes the kernel, interpreted, in tiles of 128 rows."""
    monkeypatch.setattr(th, "TILE_VOCAB", TILE)
    monkeypatch.setattr(losses, "head_logits", functools.partial(
        th.head_logits, interpret=True))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_logits_and_lse_against_jax_numpy(case, dtype):
    x, table, labels = _operands(case, jnp.dtype(dtype))
    _, want_logits, want_lse = losses._block_nll(x, table, labels)
    logits, lse = _by_kernel(case, x, table)
    assert logits.dtype == lse.dtype == jnp.float32
    assert logits.shape == want_logits.shape and lse.shape == want_lse.shape
    np.testing.assert_allclose(logits, want_logits, rtol=0, atol=TOL)
    np.testing.assert_allclose(lse, want_lse, rtol=0, atol=TOL)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["whole-tiles", "a-last-tile-of-64",
                                  "one-tile"])
def test_a_block_s_nll_by_the_kernel(case, dtype, forced, monkeypatch):
    x, table, labels = _operands(case, jnp.dtype(dtype))
    got = losses._block_nll(x, table, labels)
    monkeypatch.setattr(losses, "head_logits", lambda x, table: None)
    want = losses._block_nll(x, table, labels)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=0, atol=TOL)


@pytest.mark.parametrize("where", ["first-tile", "last-tile"])
@pytest.mark.parametrize("size", [80.0, -80.0])
def test_logits_of_eighty_wherever_the_maximum_sits(where, size):
    """Rows of logits near +-80 whose largest sits in the first or in the
    last tile (the partial one): the running maximum moves or stands, and no
    ``exp`` overflows where the two-pass form's does not."""
    tokens, rows, d = 128, 320, 128
    x = jnp.zeros((tokens, d)).at[:, 0].set(1.0)
    column = jnp.full((rows,), size).at[2 if where == "first-tile"
                                        else rows - 3].add(7.5)
    column = column + jnp.linspace(0.0, 1.0, rows)
    table = jnp.zeros((rows, d)).at[:, 0].set(column)
    logits, lse = th._head_logits(x, table, th.HeadPlan(tokens, TILE), True)
    want = np.asarray(column, np.float64)
    want_lse = want.max() + np.log(np.exp(want - want.max()).sum())
    assert np.isfinite(np.asarray(lse)).all()
    np.testing.assert_allclose(logits.T, np.tile(want, (tokens, 1)),
                               rtol=1e-6)
    np.testing.assert_allclose(lse[0], np.full(tokens, want_lse), rtol=1e-6)


@pytest.mark.parametrize("shape,want", [
    ((2048, 2048, 131136, 2), th.HeadPlan(2048, 512)),     # ZAYA's block
    ((2048, 2560, 16384, 2), th.HeadPlan(2048, 512)),      # Jamba's
    ((2048, 2048, 200, 2), th.HeadPlan(2048, 192)),
    ((8192, 4096, 131136, 2), th.HeadPlan(1024, 512)),     # cut to fit VMEM
    ((96, 2048, 131136, 2), None),                         # no lane tiles
    ((2048, 2000, 131136, 2), None),
    ((2048, 2048, 8, 2), None)],
    ids=["zaya", "jamba", "a-short-table", "a-block-too-large", "96-tokens",
         "d-of-2000", "8-rows"])
def test_the_tiles_follow_the_shapes(shape, want):
    assert th.plan(*shape) == want
    if want is not None:
        tokens, d, _, itemsize = shape
        assert th._vmem_bytes(*want, d, itemsize) <= th._VMEM_BUDGET
        assert tokens % want.tokens == 0


def test_off_the_tpu_the_caller_keeps_its_jax_numpy():
    x, table, _ = _operands("whole-tiles", jnp.bfloat16)
    assert th.head_logits(x, table) is None
    assert th.head_logits(x, table.astype(jnp.float32),
                          interpret=True) is None       # two dtypes
    assert th.head_logits(x[:96], table, interpret=True) is None


@pytest.mark.parametrize("tokens,rows", [(384, 384), (256, 320), (128, 200)],
                         ids=["three-blocks", "a-last-tile-of-64",
                              "one-block"])
def test_the_head_s_gradients_with_the_kernel_on(tokens, rows, forced,
                                                 monkeypatch):
    """Value, d x and both gradients of the tied matrix (the gather's and
    the head's) against every logit alive: ``tests/single/test_zaya.py``'s
    reference, the logits and their statistics by the kernel."""
    monkeypatch.setattr(losses, "HEAD_BLOCK_BYTES", 4 * rows * 128)
    keys = jax.random.split(jax.random.key(tokens + rows), 5)
    table = jax.random.normal(keys[0], (rows, 128)) / 4
    ids = jax.random.randint(keys[1], (tokens,), 0, rows)
    labels = jax.random.randint(keys[2], (tokens,), 0, rows).at[::7].set(
        rows - 1)
    weights = jax.random.uniform(keys[3], (tokens,)) / tokens
    mix = jax.random.normal(keys[4], (128, 128)) / 5

    def blocked(table, mix):
        x = table[ids] @ mix            # the embedding is gathered and tied
        return 3.0 * losses.tied_head_cross_entropy(x, table, labels,
                                                    weights)

    def whole(table, mix):
        x = table[ids] @ mix
        return 3.0 * jnp.sum(weights * losses.softmax_cross_entropy(
            x @ table.T, labels))

    assert "hvd_head_logits" in str(jax.make_jaxpr(jax.grad(blocked))(
        table, mix))
    got, got_grads = jax.value_and_grad(blocked, argnums=(0, 1))(table, mix)
    want, want_grads = jax.value_and_grad(whole, argnums=(0, 1))(table, mix)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(blocked(table, mix)) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("tokens,rows,blocks", [
    (16384, 131136, 8), (16384, 16384, 1), (16384, 12544, 1),
    (16384, 16160, 1), (16384, 262144, 16), (8 * 1023, 50304, 2),
    (100, 10 ** 9, 100)],
    ids=["zaya", "jamba", "laguna", "joyai", "twice-zaya-s-rows",
         "gpt2-s-tokens", "a-row-past-the-budget"])
def test_a_block_is_as_many_tokens_as_its_logits_bytes_allow(tokens, rows,
                                                             blocks):
    """The fewest blocks that divide the tokens evenly with a block's
    float32 logits inside ``HEAD_BLOCK_BYTES``: by the shapes alone."""
    assert losses._head_blocks(tokens, rows) == blocks
    assert tokens % blocks == 0
    assert (4 * rows * (tokens // blocks) <= losses.HEAD_BLOCK_BYTES
            or blocks == tokens)


UNTIED = {"three-blocks": (384, 384), "a-last-tile-of-64": (256, 320),
          "one-block": (128, 200)}


def _untied_operands(tokens, rows, d=128):
    keys = jax.random.split(jax.random.key(tokens * rows), 5)
    kernel = jax.random.normal(keys[0], (d, rows)) / 4
    hidden = jax.random.normal(keys[1], (tokens, d))
    labels = jax.random.randint(keys[2], (tokens,), 0, rows).at[::7].set(
        rows - 1)
    weights = jax.random.uniform(keys[3], (tokens,)) / tokens
    mix = jax.random.normal(keys[4], (d, d)) / 5
    return kernel, hidden, labels, weights, mix


@pytest.mark.parametrize("kernel_on", [True, False],
                         ids=["kernel", "jax.numpy"])
@pytest.mark.parametrize("tokens,rows", UNTIED.values(), ids=UNTIED.keys())
def test_a_head_of_its_own_against_whole_logits(tokens, rows, kernel_on,
                                                forced, monkeypatch):
    """``head_cross_entropy``: value, ``dx`` (through ``mix``) and the
    gradient of the kernel ``[d, V]`` against ``softmax_cross_entropy(x .
    kernel)`` in float32, a block of 128 tokens at a time, at a ``V`` that is
    no multiple of the vocabulary tile and a ``T`` of one block and of
    several, with the logits and their statistics by the kernel and by
    ``jax.numpy``."""
    if not kernel_on:
        monkeypatch.setattr(losses, "head_logits", lambda x, table: None)
    monkeypatch.setattr(losses, "HEAD_BLOCK_BYTES", 4 * rows * 128)
    assert losses._head_blocks(tokens, rows) == tokens // 128
    kernel, hidden, labels, weights, mix = _untied_operands(tokens, rows)

    def blocked(kernel, mix):
        return 3.0 * losses.head_cross_entropy(hidden @ mix, kernel, labels,
                                               weights)

    def whole(kernel, mix):
        return 3.0 * jnp.sum(weights * losses.softmax_cross_entropy(
            jnp.dot(hidden @ mix, kernel), labels))

    text = str(jax.make_jaxpr(jax.grad(blocked))(kernel, mix))
    assert ("hvd_head_logits" in text) == kernel_on
    got, got_grads = jax.value_and_grad(blocked, argnums=(0, 1))(kernel, mix)
    want, want_grads = jax.value_and_grad(whole, argnums=(0, 1))(kernel, mix)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(blocked(kernel, mix)) == pytest.approx(float(want), rel=1e-6)
    assert got_grads[0].shape == kernel.shape
    assert got_grads[0].dtype == jnp.float32
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_a_head_of_its_own_in_bfloat16_keeps_a_float32_gradient(forced,
                                                               monkeypatch):
    """The models' call: ``x`` in bfloat16, the kernel a float32 master cast
    once.  ``dx`` comes back in bfloat16, ``d kernel`` float32 ``[d, V]`` and
    not rounded to bfloat16 on the way (the whole-logits form's cast rounds
    it), each within bfloat16's reach of the float32 reference."""
    tokens, rows = 256, 320
    monkeypatch.setattr(losses, "HEAD_BLOCK_BYTES", 4 * rows * 128)
    kernel, hidden, labels, weights, _ = _untied_operands(tokens, rows)
    x = hidden.astype(jnp.bfloat16)
    dx, dkernel = jax.grad(losses.head_cross_entropy, argnums=(0, 1))(
        x, kernel, labels, weights)
    want_dx, want_dkernel = jax.grad(
        lambda x, k: jnp.sum(weights * losses.softmax_cross_entropy(
            jnp.dot(x, k), labels)), argnums=(0, 1))(
                x.astype(jnp.float32), kernel)
    assert dx.dtype == jnp.bfloat16 and dkernel.dtype == jnp.float32
    assert dkernel.shape == kernel.shape
    assert np.any(dkernel != dkernel.astype(jnp.bfloat16).astype(jnp.float32))
    for got, want in ((dx, want_dx), (dkernel, want_dkernel)):
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < (
            2e-2 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("loss", ["tied_head_cross_entropy",
                                  "head_cross_entropy"])
def test_forward_mode_raises(loss):
    """Reverse mode only, as ``softmax_cross_entropy``."""
    kernel, hidden, labels, weights, _ = _untied_operands(128, 200)
    matrix = kernel.T if loss.startswith("tied") else kernel
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda x: getattr(losses, loss)(x, matrix, labels, weights),
                (hidden,), (hidden,))


def test_inside_a_jitted_shard_map_step_under_check_vma(forced, monkeypatch):
    from jax import shard_map
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(losses, "HEAD_BLOCK_BYTES", 4 * 200 * 128)
    # Of the two interpreters only this one runs inside ``shard_map``.
    monkeypatch.setattr(losses, "head_logits", functools.partial(
        th.head_logits, interpret=pltpu.InterpretParams()))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("hvd",))
    table = jax.random.normal(jax.random.key(0), (200, 128)) / 4
    x = jax.random.normal(jax.random.key(1), (2 * 256, 128))
    labels = jax.random.randint(jax.random.key(2), (2 * 256,), 0, 200)
    weights = jnp.full((2 * 256,), 1 / 512)

    def step(x, table, labels, weights):
        loss, grads = jax.value_and_grad(
            losses.tied_head_cross_entropy, argnums=(0, 1))(
                x, table, labels, weights)
        return jax.lax.psum(loss, "hvd"), grads[0], grads[1]

    loss, dx, dtable = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("hvd"), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P("hvd"), P())))(x, table, labels, weights)
    want, (want_dx, want_dtable) = jax.value_and_grad(
        lambda x, t: jnp.sum(weights * losses.softmax_cross_entropy(
            x @ t.T, labels)), argnums=(0, 1))(x, table)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(dx, want_dx, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(dtable, want_dtable, rtol=1e-4, atol=1e-7)
