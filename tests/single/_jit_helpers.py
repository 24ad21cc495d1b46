"""What the in-jit collective tests share: the 8-device ``hvd`` mesh, the
``shard_map`` wrappers over it, the device codecs' error bounds, and the
bodies of the two schedule tests that run a codec a file (``--dist loadfile``
gives a file to one pytest-xdist worker, and one codec's three schedules are
minutes of eager ``shard_map``).  Not a test file; the ``test_ops_jit*``
files and ``test_convergence_ef.py`` import it (pytest puts ``tests/single``
on the path; ``tests/conftest.py`` registers it for assertion rewriting)."""

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
import horovod_tpu.ops.collectives as hvd_ops
import horovod_tpu.ops.quantize as qz

N_DEV = 8
_DEV_CODECS = ("int8", "int4", "int8g")
_Q_BOUND = {"int8": 0.5, "int4": 8.0, "int8g": 0.5}  # scale/2 per element


def _mesh():
    return Mesh(np.asarray(jax.devices()[:N_DEV]), ("hvd",))


def _run_per_rank(fn, x_per_rank, out_spec=P("hvd")):
    """Run fn under shard_map: x_per_rank has leading dim N_DEV, each shard
    sees one rank's slice (rank-major), like one Horovod process per device."""
    mesh = _mesh()
    return shard_map(fn, mesh=mesh, in_specs=P("hvd"), out_specs=out_spec)(
        x_per_rank)


def _smap(fn, in_specs=P("hvd"), out_specs=P("hvd")):
    return shard_map(fn, mesh=_mesh(), in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def codec_schedule_case(codec, schedule):
    # Every codec x schedule combination: close to psum and bit-identical
    # across ranks (the gather phases forward encodings verbatim).
    rng = np.random.RandomState(41)
    x = jnp.asarray(rng.randn(N_DEV, 32768), dtype=jnp.float32)

    def fn(shard, _c=codec, _s=schedule):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Sum,
                                           min_bytes=0, codec=_c,
                                           schedule=_s)[None]

    out = np.asarray(_smap(fn)(x))
    expected = np.asarray(x).sum(axis=0)
    assert np.max(np.abs(out - expected[None])) < _Q_BOUND[codec] * N_DEV
    for r in range(1, N_DEV):
        np.testing.assert_array_equal(out[r], out[0])


def exact_payload(qmax):
    """A payload every hop quantizes exactly, and its plain fp32 psum.  Made
    in a module-scoped fixture, so before the function-scoped ``hvd_single``
    of the first case: hence its own init."""
    # Differential parity of bidi / torus vs the unidirectional ring:
    # block-constant payloads valued sign * qmax * 2^k quantize EXACTLY at
    # every hop (every partial sum is m * qmax * 2^k; its scale m * 2^k
    # and codes +-qmax reproduce the value bit-for-bit), so all three
    # schedules must equal the plain fp32 psum exactly, not approximately.
    per = 32768                              # 128 blocks per shard
    nblk = per // qz.WIRE_BLOCK
    rng = np.random.RandomState(42)
    k = rng.randint(-3, 4, size=nblk)        # per-block exponent, shared
    sign = rng.choice([-1.0, 1.0], size=(N_DEV, nblk))
    vals = (sign * qmax * np.exp2(k)[None, :]).astype(np.float32)
    x = jnp.asarray(np.repeat(vals, qz.WIRE_BLOCK, axis=1))

    def plain(shard):
        return hvd.allreduce(shard, op=hvd.Sum, axis_name="hvd")

    hvd.init()
    try:
        expected = np.asarray(_smap(plain)(x))
    finally:
        hvd.shutdown()
    return x, expected


def schedule_parity_case(codec, x, expected, schedule):
    def fn(shard, _s=schedule):
        return hvd_ops.quantized_allreduce(
            shard[0], "hvd", op=hvd.Sum, min_bytes=0, codec=codec,
            schedule=_s)[None]

    out = np.asarray(_smap(fn)(x))
    np.testing.assert_array_equal(
        out, expected,
        err_msg=f"{codec}/{schedule} diverged from exact psum")
