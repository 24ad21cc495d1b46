"""Quantized allreduce: every device codec under every ring schedule, at the
eight ranks of the virtual mesh and on sub-meshes of it: 4 is the benchmark's
four-chip host (``torus_factors`` (2, 2), ``auto`` -> bidi), 6 a non-square
torus (2, 3), 3 and 5 the odd rings where ``torus`` demotes to ``bidi`` and
the chunks do not divide into whole blocks."""

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd
import horovod_tpu.ops.collectives as hvd_ops
from _jit_helpers import _DEV_CODECS, _Q_BOUND, _smap

pytestmark = pytest.mark.usefixtures("hvd_single")


@pytest.mark.parametrize("world", [8, 4, 6, 3, 5])
@pytest.mark.parametrize("schedule", ["ring", "bidi", "torus"])
@pytest.mark.parametrize("codec", _DEV_CODECS)
def test_quantized_allreduce_codec_schedule_matrix(codec, schedule, world):
    # Every codec x schedule combination: close to psum and bit-identical
    # across ranks (the gather phases forward encodings verbatim).
    rng = np.random.RandomState(41)
    x = jnp.asarray(rng.randn(world, 32768), dtype=jnp.float32)

    def fn(shard, _c=codec, _s=schedule):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Sum,
                                           min_bytes=0, codec=_c,
                                           schedule=_s)[None]

    out = np.asarray(_smap(fn, world=world)(x))
    expected = np.asarray(x).sum(axis=0)
    assert np.max(np.abs(out - expected[None])) < _Q_BOUND[codec] * world
    for r in range(1, world):
        np.testing.assert_array_equal(out[r], out[0])
