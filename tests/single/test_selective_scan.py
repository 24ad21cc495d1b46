"""``ops/selective_scan.py``: the two Pallas kernels in interpret mode
against a ``lax.scan`` over time, values and all six gradients, float32 and
bfloat16 operands, a length that is no multiple of the chunk, several chunks
and channel blocks (``_scan`` takes a chunk and a block; ``selective_scan``
takes the plan's own), and what the plan refuses, by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops import selective_scan as ss

NAMES = ("u", "dt", "A", "B", "C", "D")


def _operands(batch, seq, channels, states, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.key(seed), 7)
    u = jax.random.normal(keys[0], (batch, seq, channels)).astype(dtype)
    dt = jax.nn.softplus(
        jax.random.normal(keys[1], (batch, seq, channels)) - 1).astype(dtype)
    a = -jnp.exp(0.5 * jax.random.normal(keys[2], (channels, states)))
    b = jax.random.normal(keys[3], (batch, seq, states)).astype(dtype)
    c = jax.random.normal(keys[4], (batch, seq, states)).astype(dtype)
    d = jax.random.normal(keys[5], (channels,))
    weight = jax.random.normal(keys[6], (batch, seq, channels))
    return (u, dt, a, b, c, d), weight


def _by_hand(u, dt, a, b, c, d):
    """The recurrence a step at a time in numpy, float64."""
    u, dt, a, b, c, d = (np.asarray(x, np.float64) for x in (u, dt, a, b, c,
                                                             d))
    y = np.zeros_like(u)
    for i in range(u.shape[0]):
        state = np.zeros_like(a)
        for t in range(u.shape[1]):
            state = (np.exp(dt[i, t][:, None] * a) * state
                     + (dt[i, t] * u[i, t])[:, None] * b[i, t][None])
            y[i, t] = state @ c[i, t] + d * u[i, t]
    return y


def test_the_scan_over_time_is_the_recurrence_by_hand():
    ops, _ = _operands(2, 12, 128, 4)
    np.testing.assert_allclose(ss.selective_scan_reference(*ops),
                               _by_hand(*ops), rtol=2e-5, atol=2e-5)


# batch, length, channels, states, chunk, block: one chunk that the length
# does not fill; several chunks, the last not full, two blocks; one block of
# two lane tiles and a state of 8.
SHAPES = {"one-chunk": (2, 40, 256, 16, None, 128),
          "300-in-chunks-of-128": (1, 300, 256, 16, 128, 128),
          "two-tiles-a-block": (1, 256, 256, 8, 128, 256)}


@pytest.fixture(scope="module", params=SHAPES.values(), ids=SHAPES.keys())
def both(request):
    batch, seq, channels, states, chunk, block = request.param
    ops, weight = _operands(batch, seq, channels, states)

    def total(scan):
        return lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * weight)

    kernels = lambda *a: ss._scan(*a, chunk, block, True)  # noqa: E731
    got = jax.value_and_grad(total(kernels), argnums=range(6))(*ops)
    want = jax.value_and_grad(total(ss.selective_scan_reference),
                              argnums=range(6))(*ops)
    return kernels(*ops), ss.selective_scan_reference(*ops), got, want


def test_values_against_the_scan_over_time(both):
    y, want, _, _ = both
    assert y.shape == want.shape and y.dtype == want.dtype
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("leaf", range(6), ids=NAMES)
def test_each_gradient_against_the_scan_over_time(both, leaf):
    _, _, (_, got), (_, want) = both
    assert got[leaf].shape == want[leaf].shape
    assert got[leaf].dtype == want[leaf].dtype
    error = float(jnp.linalg.norm(got[leaf] - want[leaf])
                  / jnp.linalg.norm(want[leaf]))
    assert error < 2e-6, (NAMES[leaf], error)


def test_bfloat16_operands_keep_a_float32_state():
    """The operands' dtypes are the results': y in ``u``'s; the state and
    every step's arithmetic are float32, so the kernels agree with the
    float32 scan of the same rounded operands far inside bfloat16's own
    step."""
    ops, weight = _operands(1, 200, 128, 16, jnp.bfloat16)

    def total(scan):
        return lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * weight)

    kernels = lambda *a: ss.selective_scan(  # noqa: E731
        *a, interpret=True)
    y = kernels(*ops)
    assert y.dtype == jnp.bfloat16
    wide = tuple(x.astype(jnp.float32) for x in ops)
    want = ss.selective_scan_reference(*wide)
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - want))
                 / jnp.max(jnp.abs(want))) < 2 ** -8
    got = jax.grad(total(kernels), argnums=range(6))(*ops)
    want = jax.grad(total(ss.selective_scan_reference), argnums=range(6))(
        *wide)
    for name, a, b in zip(NAMES, got, want):
        assert a.dtype == (jnp.float32 if name in "AD" else jnp.bfloat16)
        error = float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                      / jnp.linalg.norm(b))
        assert error < 2 ** -7, (name, error)


@pytest.mark.parametrize("channels,chunk,block,named", [
    (200, None, None, "channels = 200"), (256, None, 96, "block = 96"),
    (384, None, 256, "block = 256"), (128, 100, None, "chunk = 100")])
def test_what_the_plan_refuses_is_refused_by_name(channels, chunk, block,
                                                  named):
    ops, _ = _operands(1, 300, channels, 4)
    with pytest.raises(ValueError, match=named):
        ss._scan(*ops, chunk, block, True)


def test_a_call_takes_the_most_lane_tiles_that_divide_its_channels():
    assert ss.plan(16384, 1280, None, None) == (ss.CHUNK, 16384, 1280)
    assert ss.plan(300, 384, None, None) == (ss.CHUNK, 384, 384)
    assert ss.plan(40, 2560, None, None) == (40, 40, 1280)
    assert ss.plan(41, 128, 128, None) == (48, 48, 128)


def test_off_the_tpu_the_scan_over_time_runs():
    ops, _ = _operands(1, 16, 128, 4)
    np.testing.assert_array_equal(ss.selective_scan(*ops),
                                  ss.selective_scan_reference(*ops))


def test_inside_shard_map_parameters_held_whole_get_summed_gradients():
    """``A`` and ``D``, which ``shard_map`` holds replicated, are cast to
    the rows' type before the scan, so the chips' parts of their gradients
    are summed.  (The Pallas interpreter cannot run a kernel under
    ``shard_map``'s types; the kernels inside ``shard_map`` are compiled for
    a described v5e in ``test_tpu_compile.py``.)"""
    ops, weight = _operands(2, 24, 128, 4)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("hvd",))

    def total(u, dt, a, b, c, d, w):
        y = ss.selective_scan(u, dt, a, b, c, d)
        return jax.lax.psum(jnp.sum(y * w), "hvd")

    rows, whole = P("hvd"), P()
    specs = (rows, rows, whole, rows, rows, whole)
    got = jax.jit(shard_map(
        jax.grad(total, argnums=range(6)), mesh=mesh,
        in_specs=(*specs, rows), out_specs=specs))(*ops, weight)
    want = jax.grad(lambda *a: jnp.sum(
        ss.selective_scan_reference(*a) * weight), argnums=range(6))(*ops)
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)
