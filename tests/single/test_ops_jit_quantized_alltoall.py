"""Universal quantized collectives under the block-scaled codecs
(docs/compression.md): alltoall.

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
def test_quantized_alltoall_value(codec):
    # per-rank shard (N_DEV, 4096): row j is the chunk destined to rank j.
    rng = np.random.RandomState(35)
    x = jnp.asarray(rng.randn(N_DEV * N_DEV, 4096), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_alltoall(shard, "hvd", min_bytes=0,
                                          codec=codec)

    def plain(shard):
        return hvd.alltoall(shard, axis_name="hvd")

    qz.reset_device_byte_counters()
    out = np.asarray(_smap(fn)(x))
    raw, enc = qz.device_byte_counters()
    assert raw > 0 and enc < raw
    expected = np.asarray(_smap(plain)(x))
    # exactly one quantization step end to end, chunk-local scales
    assert np.max(np.abs(out - expected)) < _Q_BOUND[codec]


def test_quantized_alltoall_demotion_bit_identical():
    rng = np.random.RandomState(36)
    # below the byte floor -> demote to the plain collective
    x = jnp.asarray(rng.randn(N_DEV * N_DEV, 64), dtype=jnp.float32)

    def quant(shard):
        return hvd_ops.quantized_alltoall(shard, "hvd",
                                          min_bytes=1 << 20)

    def plain(shard):
        return hvd.alltoall(shard, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant)(x)),
                                  np.asarray(_smap(plain)(x)))
    # non-fp32 demotes regardless of size
    xi = jnp.asarray(rng.randint(-9, 9, size=(N_DEV * N_DEV, 1024)),
                     dtype=jnp.int32)

    def quant_i(shard):
        return hvd_ops.quantized_alltoall(shard, "hvd", min_bytes=0)

    def plain_i(shard):
        return hvd.alltoall(shard, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant_i)(xi)),
                                  np.asarray(_smap(plain_i)(xi)))
