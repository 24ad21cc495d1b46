"""Universal quantized collectives under the block-scaled codecs
(docs/compression.md): reducescatter.

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
def test_quantized_reducescatter_value(codec):
    rng = np.random.RandomState(37)
    x = jnp.asarray(rng.randn(N_DEV * N_DEV, 2048), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_reducescatter(shard, "hvd", op=hvd.Sum,
                                               min_bytes=0, codec=codec)

    qz.reset_device_byte_counters()
    out = np.asarray(_smap(fn)(x))          # [N_DEV, 2048]
    raw, enc = qz.device_byte_counters()
    assert raw > 0 and enc < raw
    full = np.asarray(x).reshape(N_DEV, N_DEV, 2048)
    expected = full.sum(axis=0)             # row r -> rank r
    # world-1 accumulation hops, each within scale/2
    assert np.max(np.abs(out - expected)) < N_DEV * _Q_BOUND[codec]


def test_quantized_reducescatter_demotion_bit_identical():
    rng = np.random.RandomState(38)
    x = jnp.asarray(rng.randn(N_DEV * N_DEV, 16), dtype=jnp.float32)

    def quant(shard):
        return hvd_ops.quantized_reducescatter(shard, "hvd", op=hvd.Sum,
                                               min_bytes=1 << 20)

    def plain(shard):
        return hvd.reducescatter(shard, op=hvd.Sum, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant)(x)),
                                  np.asarray(_smap(plain)(x)))
