"""Quantized allreduce schedules: int4 acceptance, differential parity
between schedules, schedule resolution, and the gspmd demotion.

Split from test_ops_jit.py, which one pytest-xdist worker (``--dist
loadfile``) could not finish inside the tier-1 time limit on its own: these
cases compile the quantized rings with the Pallas codecs in the interpreter
and take a minute or more each.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
import horovod_tpu.ops.collectives as hvd_ops
import horovod_tpu.ops.quantize as qz

pytestmark = pytest.mark.usefixtures("hvd_single")

N_DEV = 8
_DEV_CODECS = ("int8", "int4", "int8g")


def _mesh():
    return Mesh(np.asarray(jax.devices()[:N_DEV]), ("hvd",))


def _smap(fn, in_specs=P("hvd"), out_specs=P("hvd")):
    return shard_map(fn, mesh=_mesh(), in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def test_quantized_allreduce_int4_acceptance_64k():
    # ISSUE acceptance: int4 on a >= 64 KiB fp32 payload moves <= 0.16x
    # the raw bytes, counter-verified.
    L = 16384
    rng = np.random.RandomState(39)
    x = jnp.asarray(rng.randn(N_DEV, L), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Sum,
                                           min_bytes=0, codec="int4")[None]

    qz.reset_device_byte_counters()
    out = np.asarray(jax.jit(_smap(fn))(x))
    raw, enc = qz.device_byte_counters()
    assert raw >= L * 4
    assert enc / raw <= 0.16, f"int4 encoded/raw {enc / raw:.4f} > 0.16"
    expected = np.asarray(x).sum(axis=0)
    # int4 scale = max|partial|/7: much coarser than int8 but bounded
    assert np.max(np.abs(out - expected[None])) < 8.0


@pytest.mark.parametrize("codec,qmax", [("int8", 127.0), ("int4", 7.0)])
def test_schedule_differential_parity_exact(codec, qmax):
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

    expected = np.asarray(_smap(plain)(x))
    for schedule in ("ring", "bidi", "torus"):
        def fn(shard, _s=schedule):
            return hvd_ops.quantized_allreduce(
                shard[0], "hvd", op=hvd.Sum, min_bytes=0, codec=codec,
                schedule=_s)[None]

        out = np.asarray(_smap(fn)(x))
        np.testing.assert_array_equal(
            out, expected,
            err_msg=f"{codec}/{schedule} diverged from exact psum")


def test_resolve_device_schedule_rules():
    r = hvd_ops.resolve_device_schedule
    assert r(2, "auto") == "ring"            # no factorization, tiny ring
    assert r(4, "auto") == "bidi"            # 2x2 torus has major axis 2
    assert r(16, "auto") == "torus"          # 4x4
    assert r(7, "torus") == "bidi"           # prime demotes
    assert r(8, "torus") == "torus"
    assert r(8, "ring") == "ring"
    assert r(8, "nonsense") == "ring"


def test_gspmd_plane_demotes_alongside_quantized_ring():
    """A quantized device codec owns the traced reduction (the explicit
    ppermute ring above): an explicit gspmd request alongside it demotes
    to eager and says so in the counter, while the silent auto probe makes
    the same decision without reading as a demotion stream (PR 17)."""
    from horovod_tpu.ops import gspmd_plane as gp

    gp.reset_plane_counters()
    try:
        plane, mesh = gp.resolve_plane("gspmd", device_codec="int8")
        assert (plane, mesh) == ("eager", None)
        assert gp.plane_counters() == {"demote_quantized": 1}
        plane, _ = gp.resolve_plane("auto", device_codec="int8", count=False)
        assert plane == "eager"
        assert gp.plane_counters() == {"demote_quantized": 1}
    finally:
        gp.reset_plane_counters()
