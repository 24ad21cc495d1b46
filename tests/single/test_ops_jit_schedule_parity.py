"""Quantized allreduce: each codec's three ring schedules against the exact
fp32 psum, one schedule a case and the psum made once a codec.  int8's three
run compiled at eight ranks; int4's three run un-jitted (``_smap_eager`` says
why) at four, the smallest world that has all three schedules: a bidi ring
with two chunks and a 2 x 2 torus."""

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd
import horovod_tpu.ops.collectives as hvd_ops
import horovod_tpu.ops.quantize as qz
from _jit_helpers import N_DEV, _smap, _smap_eager

pytestmark = pytest.mark.usefixtures("hvd_single")

QMAX = {"int8": 127.0, "int4": 7.0}
WORLD = {"int8": N_DEV, "int4": 4}


@pytest.fixture(scope="module", params=["int8", "int4"])
def payload(request):
    """A payload every hop quantizes exactly, and its plain fp32 psum.  Made
    in a module-scoped fixture, so before the function-scoped ``hvd_single``
    of the first case: hence its own init."""
    # Differential parity of bidi / torus vs the unidirectional ring:
    # block-constant payloads valued sign * qmax * 2^k quantize EXACTLY at
    # every hop (every partial sum is m * qmax * 2^k; its scale m * 2^k
    # and codes +-qmax reproduce the value bit-for-bit), so all three
    # schedules must equal the plain fp32 psum exactly, not approximately.
    codec = request.param
    qmax = QMAX[codec]
    per = 32768                              # 128 blocks per shard
    nblk = per // qz.WIRE_BLOCK
    rng = np.random.RandomState(42)
    k = rng.randint(-3, 4, size=nblk)        # per-block exponent, shared
    sign = rng.choice([-1.0, 1.0], size=(WORLD[codec], nblk))
    vals = (sign * qmax * np.exp2(k)[None, :]).astype(np.float32)
    x = jnp.asarray(np.repeat(vals, qz.WIRE_BLOCK, axis=1))

    def plain(shard):
        return hvd.allreduce(shard, op=hvd.Sum, axis_name="hvd")

    hvd.init()
    try:
        expected = np.asarray(_smap(plain, world=WORLD[codec])(x))
    finally:
        hvd.shutdown()
    return codec, x, expected


@pytest.mark.parametrize("schedule", ["ring", "bidi", "torus"])
def test_schedule_differential_parity_exact(payload, schedule):
    codec, x, expected = payload

    def fn(shard, _s=schedule):
        return hvd_ops.quantized_allreduce(
            shard[0], "hvd", op=hvd.Sum, min_bytes=0, codec=codec,
            schedule=_s)[None]

    # Compiled, int4's scale is a multiply by a rounded 1/7 and not exact.
    run = _smap_eager if codec == "int4" else _smap
    out = np.asarray(run(fn, world=WORLD[codec])(x))
    np.testing.assert_array_equal(
        out, expected,
        err_msg=f"{codec}/{schedule} diverged from exact psum")
