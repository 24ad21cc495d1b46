"""Quantized device-plane allreduce, bit for bit: every rank holds the same
bytes, a demoted call is the plain collective, and the traced program agrees
with the eager one (docs/compression.md).  Values and byte counts are in
test_ops_jit_quantized_allreduce.py.

Each case compiles one program (``_jit_helpers._smap``) and takes about a
second, but for the eager half of the traced-against-eager case.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd
import horovod_tpu.ops.collectives as hvd_ops
from _jit_helpers import N_DEV, _smap, _smap_eager

pytestmark = pytest.mark.usefixtures("hvd_single")


def test_quantized_allreduce_cross_rank_bit_identical():
    # Every rank must hold byte-identical results (the all-gather phase
    # forwards one quantized image; no rank re-quantizes received data).
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(N_DEV, 3000), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Sum,
                                           min_bytes=0)[None]

    out = np.asarray(_smap(fn)(x))
    for r in range(1, N_DEV):
        np.testing.assert_array_equal(out[r], out[0])


def test_quantized_allreduce_demotion_bit_identical():
    # Below the byte floor (and for non-fp32 dtypes) the call must demote
    # to the plain collective — bit-identical, not merely close.
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(N_DEV, 64), dtype=jnp.float32)

    def quant_fn(shard):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Sum,
                                           min_bytes=1 << 20)[None]

    def plain_fn(shard):
        return hvd.allreduce(shard, op=hvd.Sum, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant_fn)(x)),
                                  np.asarray(_smap(plain_fn)(x)))
    # non-fp32 demotes regardless of size
    xi = jnp.asarray(rng.randint(-1000, 1000, size=(N_DEV, 32768)),
                     dtype=jnp.int32)

    def quant_i32(shard):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Sum,
                                           min_bytes=0)[None]

    def plain_i32(shard):
        return hvd.allreduce(shard, op=hvd.Sum, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant_i32)(xi)),
                                  np.asarray(_smap(plain_i32)(xi)))


def test_quantized_allreduce_traced_vs_eager_parity():
    # shard_map alone executes op-by-op; jax.jit(shard_map) compiles one
    # program.  Both must produce bit-identical results (the kernels use
    # only exactly-rounded elementwise ops; scales divide outside Pallas).
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(N_DEV, 2048), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Sum,
                                           min_bytes=0)[None]

    eager = np.asarray(_smap_eager(fn)(x))
    traced = np.asarray(_smap(fn)(x))
    # On TPU both paths run the same Pallas kernels and agree bit-for-bit;
    # the CPU stand-in's whole-program fusion may contract mul+add into an
    # FMA, so allow 1-ulp-scale drift there.
    np.testing.assert_allclose(traced, eager, rtol=1e-6, atol=2e-6)
