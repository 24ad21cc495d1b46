"""Quantized allreduce schedules: int4 acceptance, schedule resolution, and
the gspmd demotion.  Every codec under every schedule is in
test_ops_jit_schedule_matrix.py, the schedules' differential parity in
test_ops_jit_schedule_parity.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
import horovod_tpu.ops.collectives as hvd_ops
import horovod_tpu.ops.quantize as qz
from _jit_helpers import N_DEV, _smap

pytestmark = pytest.mark.usefixtures("hvd_single")


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
