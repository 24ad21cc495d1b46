"""Universal quantized collectives under the block-scaled codecs
(docs/compression.md): allgather and broadcast.

Each case compiles one program (``_jit_helpers._smap``) and takes about a
second.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd
import horovod_tpu.ops.collectives as hvd_ops
import horovod_tpu.ops.quantize as qz
from _jit_helpers import N_DEV, _DEV_CODECS, _Q_BOUND, _smap

pytestmark = pytest.mark.usefixtures("hvd_single")


@pytest.mark.parametrize("codec", _DEV_CODECS)
def test_quantized_allgather_value_and_cross_rank(codec):
    rng = np.random.RandomState(31)
    x = jnp.asarray(rng.randn(N_DEV, 4096), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_allgather(shard, "hvd", min_bytes=0,
                                           codec=codec)

    qz.reset_device_byte_counters()
    out = np.asarray(_smap(fn)(x))          # [N_DEV * N_DEV, 4096]
    raw, enc = qz.device_byte_counters()
    assert raw > 0 and enc < raw
    assert enc / raw <= (0.20 if codec == "int4" else 0.35)
    per_rank = out.reshape(N_DEV, N_DEV, 4096)
    # Every rank decodes the same gathered bytes: bit-identical results.
    for r in range(1, N_DEV):
        np.testing.assert_array_equal(per_rank[r], per_rank[0])
    # One quantization step from the source values.
    assert np.max(np.abs(per_rank[0] - np.asarray(x))) < _Q_BOUND[codec]


def test_quantized_allgather_demotion_bit_identical():
    rng = np.random.RandomState(32)
    x = jnp.asarray(rng.randn(N_DEV, 64), dtype=jnp.float32)

    def quant(shard):
        return hvd_ops.quantized_allgather(shard, "hvd",
                                           min_bytes=1 << 20)

    def plain(shard):
        return hvd.allgather(shard, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant)(x)),
                                  np.asarray(_smap(plain)(x)))
    # non-fp32 demotes regardless of size
    xi = jnp.asarray(rng.randint(-9, 9, size=(N_DEV, 8192)), dtype=jnp.int32)

    def quant_i(shard):
        return hvd_ops.quantized_allgather(shard, "hvd", min_bytes=0)

    def plain_i(shard):
        return hvd.allgather(shard, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant_i)(xi)),
                                  np.asarray(_smap(plain_i)(xi)))


@pytest.mark.parametrize("codec", _DEV_CODECS)
def test_quantized_broadcast_value_and_cross_rank(codec):
    rng = np.random.RandomState(33)
    x = jnp.asarray(rng.randn(N_DEV, 4096), dtype=jnp.float32)
    root = 3

    def fn(shard):
        return hvd_ops.quantized_broadcast(shard, root, "hvd",
                                           min_bytes=0, codec=codec)

    qz.reset_device_byte_counters()
    out = np.asarray(_smap(fn)(x))
    raw, enc = qz.device_byte_counters()
    assert raw > 0 and enc < raw
    for r in range(1, N_DEV):
        np.testing.assert_array_equal(out[r], out[0])
    assert np.max(np.abs(out[0] - np.asarray(x)[root])) < _Q_BOUND[codec]


def test_quantized_broadcast_demotion_bit_identical():
    rng = np.random.RandomState(34)
    x = jnp.asarray(rng.randn(N_DEV, 64), dtype=jnp.float32)

    def quant(shard):
        return hvd_ops.quantized_broadcast(shard, 5, "hvd",
                                           min_bytes=1 << 20)

    def plain(shard):
        return hvd.broadcast(shard, root_rank=5, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant)(x)),
                                  np.asarray(_smap(plain)(x)))
