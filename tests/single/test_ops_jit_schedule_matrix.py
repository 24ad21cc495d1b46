"""Quantized allreduce: every device codec under every ring schedule.

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

pytestmark = pytest.mark.usefixtures("hvd_single")

N_DEV = 8
_DEV_CODECS = ("int8", "int4", "int8g")
_Q_BOUND = {"int8": 0.5, "int4": 8.0, "int8g": 0.5}  # scale/2 per element


def _mesh():
    return Mesh(np.asarray(jax.devices()[:N_DEV]), ("hvd",))


def _smap(fn, in_specs=P("hvd"), out_specs=P("hvd")):
    return shard_map(fn, mesh=_mesh(), in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


@pytest.mark.parametrize("schedule", ["ring", "bidi", "torus"])
@pytest.mark.parametrize("codec", _DEV_CODECS)
def test_quantized_allreduce_codec_schedule_matrix(codec, schedule):
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
