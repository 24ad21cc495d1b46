"""In-jit collective semantics over an 8-device virtual mesh.

This exercises the actual TPU data plane (XLA collectives over a named mesh
axis) that multi-chip runs use — the analog of the reference's NCCL op tests,
but compiled (SURVEY.md §2.2, §2.8).  The quantized collectives are in
test_ops_jit_quantized_*.py, their ring schedules in test_ops_jit_schedule*.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from _jit_helpers import N_DEV, _mesh, _run_per_rank

pytestmark = pytest.mark.usefixtures("hvd_single")


def test_allreduce_average_jit():
    x = jnp.arange(N_DEV * 4, dtype=jnp.float32).reshape(N_DEV, 4)

    def fn(shard):
        return hvd.allreduce(shard, axis_name="hvd")

    out = _run_per_rank(fn, x)
    expected = np.broadcast_to(np.asarray(x).mean(axis=0), (N_DEV, 4))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)


def test_allreduce_sum_min_max_jit():
    x = jnp.asarray(np.random.RandomState(0).randn(N_DEV, 8), dtype=jnp.float32)
    for op, ref in [(hvd.Sum, np.sum), (hvd.Min, np.min), (hvd.Max, np.max)]:
        def fn(shard):
            return hvd.allreduce(shard, op=op, axis_name="hvd")

        out = _run_per_rank(fn, x)
        expected = np.broadcast_to(ref(np.asarray(x), axis=0), (N_DEV, 8))
        np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_allreduce_product_jit():
    x = jnp.asarray(np.random.RandomState(1).rand(N_DEV, 4) + 0.5,
                    dtype=jnp.float32)

    def fn(shard):
        return hvd.allreduce(shard, op=hvd.Product, axis_name="hvd")

    out = _run_per_rank(fn, x)
    expected = np.broadcast_to(np.prod(np.asarray(x), axis=0), (N_DEV, 4))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-4)


def test_allgather_jit():
    x = jnp.arange(N_DEV * 2, dtype=jnp.float32).reshape(N_DEV, 2)

    def fn(shard):
        return hvd.allgather(shard, axis_name="hvd")

    mesh = _mesh()
    out = shard_map(fn, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"))(x)
    # each rank receives the full concatenation; sharded output stacks to the
    # full array repeated once per rank slot along dim0
    np.testing.assert_allclose(np.asarray(out)[:N_DEV], np.asarray(x))


def test_broadcast_jit():
    x = jnp.arange(N_DEV * 3, dtype=jnp.float32).reshape(N_DEV, 3)
    root = 5

    def fn(shard):
        return hvd.broadcast(shard, root_rank=root, axis_name="hvd")

    out = _run_per_rank(fn, x)
    expected = np.broadcast_to(np.asarray(x)[root], (N_DEV, 3))
    np.testing.assert_allclose(np.asarray(out), expected)


def test_alltoall_jit():
    # per-rank shard is (N_DEV, 1): row j is the chunk destined for rank j
    x = jnp.arange(N_DEV * N_DEV, dtype=jnp.float32).reshape(N_DEV * N_DEV, 1)
    mesh = _mesh()

    def fn(shard):
        return hvd.alltoall(shard, axis_name="hvd")

    out = shard_map(fn, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"))(x)
    full = np.asarray(x).reshape(N_DEV, N_DEV)  # row r = rank r's sends
    expected = full.T.reshape(N_DEV * N_DEV, 1)  # rank r receives column r
    np.testing.assert_allclose(np.asarray(out), expected)


def test_reducescatter_jit():
    x = jnp.asarray(np.random.RandomState(2).randn(N_DEV, N_DEV * 2),
                    dtype=jnp.float32)

    def fn(shard):
        # shard: (1, 16) per rank -> reshape to (16,) rows, scatter over ranks
        return hvd.reducescatter(shard[0], op=hvd.Sum, axis_name="hvd")[None]

    out = _run_per_rank(fn, x)
    expected = np.sum(np.asarray(x), axis=0).reshape(N_DEV, 2)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_adasum_jit_two_equal_vectors():
    # adasum(a, a) = a for identical vectors (scale-invariance sanity check)
    x = jnp.ones((N_DEV, 6), dtype=jnp.float32) * 2.5

    def fn(shard):
        return hvd.allreduce(shard, op=hvd.Adasum, axis_name="hvd")

    out = _run_per_rank(fn, x)
    np.testing.assert_allclose(np.asarray(out), 2.5, rtol=1e-5)


def test_adasum_jit_orthogonal_vectors_sum():
    # for orthogonal vectors adasum reduces to plain sum
    base = np.zeros((N_DEV, N_DEV), dtype=np.float32)
    np.fill_diagonal(base, np.arange(1, N_DEV + 1, dtype=np.float32))
    x = jnp.asarray(base)

    def fn(shard):
        return hvd.allreduce(shard, op=hvd.Adasum, axis_name="hvd")

    out = _run_per_rank(fn, x)
    expected = np.broadcast_to(base.sum(axis=0), (N_DEV, N_DEV))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


@pytest.mark.skipif(not hasattr(jax, "typeof"),
                    reason="pre-vma shard_map re-psums the psum cotangent "
                           "(extra factor of axis size)")
def test_allreduce_inside_jit_with_grad():
    # collectives must be differentiable for DistributedOptimizer-style use
    mesh = _mesh()
    x = jnp.arange(N_DEV, dtype=jnp.float32)

    def loss_fn(shard):
        red = hvd.allreduce(shard, op=hvd.Sum, axis_name="hvd")
        return jnp.sum(red * red)

    def per_rank(shard):
        g = jax.grad(lambda s: loss_fn(s))(shard)
        return g

    out = shard_map(per_rank, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"))(x)
    total = np.sum(np.asarray(x))
    # d/dx_i sum((psum x)^2) = 2 * psum(x) ... allreduced gradient
    np.testing.assert_allclose(np.asarray(out), 2 * total, rtol=1e-5)
