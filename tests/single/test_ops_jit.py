"""In-jit collective semantics over an 8-device virtual mesh.

This exercises the actual TPU data plane (XLA collectives over a named mesh
axis) that multi-chip runs use — the analog of the reference's NCCL op tests,
but compiled (SURVEY.md §2.2, §2.8).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
try:
    from jax import shard_map
except ImportError:  # pre-0.5 layout
    from jax.experimental.shard_map import shard_map

import horovod_tpu as hvd

pytestmark = pytest.mark.usefixtures("hvd_single")

N_DEV = 8


def _mesh():
    return Mesh(np.asarray(jax.devices()[:N_DEV]), ("hvd",))


def _run_per_rank(fn, x_per_rank, out_spec=P("hvd")):
    """Run fn under shard_map: x_per_rank has leading dim N_DEV, each shard
    sees one rank's slice (rank-major), like one Horovod process per device."""
    mesh = _mesh()
    return shard_map(fn, mesh=mesh, in_specs=P("hvd"), out_specs=out_spec)(
        x_per_rank)


def test_allreduce_average_jit():
    x = jnp.arange(N_DEV * 4, dtype=jnp.float32).reshape(N_DEV, 4)

    def fn(shard):
        return hvd.allreduce(shard, axis_name="hvd")

    out = _run_per_rank(fn, x)
    expected = np.broadcast_to(np.asarray(x).mean(axis=0), (N_DEV, 4))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)


def test_allreduce_sum_min_max_jit():
    x = jnp.asarray(np.random.RandomState(0).randn(N_DEV, 8), dtype=jnp.float32)
    for op, ref in [(hvd.Sum, np.sum), (hvd.Min, np.min), (hvd.Max, np.max)]:
        def fn(shard):
            return hvd.allreduce(shard, op=op, axis_name="hvd")

        out = _run_per_rank(fn, x)
        expected = np.broadcast_to(ref(np.asarray(x), axis=0), (N_DEV, 8))
        np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_allreduce_product_jit():
    x = jnp.asarray(np.random.RandomState(1).rand(N_DEV, 4) + 0.5,
                    dtype=jnp.float32)

    def fn(shard):
        return hvd.allreduce(shard, op=hvd.Product, axis_name="hvd")

    out = _run_per_rank(fn, x)
    expected = np.broadcast_to(np.prod(np.asarray(x), axis=0), (N_DEV, 4))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-4)


def test_allgather_jit():
    x = jnp.arange(N_DEV * 2, dtype=jnp.float32).reshape(N_DEV, 2)

    def fn(shard):
        return hvd.allgather(shard, axis_name="hvd")

    mesh = _mesh()
    out = shard_map(fn, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"))(x)
    # each rank receives the full concatenation; sharded output stacks to the
    # full array repeated once per rank slot along dim0
    np.testing.assert_allclose(np.asarray(out)[:N_DEV], np.asarray(x))


def test_broadcast_jit():
    x = jnp.arange(N_DEV * 3, dtype=jnp.float32).reshape(N_DEV, 3)
    root = 5

    def fn(shard):
        return hvd.broadcast(shard, root_rank=root, axis_name="hvd")

    out = _run_per_rank(fn, x)
    expected = np.broadcast_to(np.asarray(x)[root], (N_DEV, 3))
    np.testing.assert_allclose(np.asarray(out), expected)


def test_alltoall_jit():
    # per-rank shard is (N_DEV, 1): row j is the chunk destined for rank j
    x = jnp.arange(N_DEV * N_DEV, dtype=jnp.float32).reshape(N_DEV * N_DEV, 1)
    mesh = _mesh()

    def fn(shard):
        return hvd.alltoall(shard, axis_name="hvd")

    out = shard_map(fn, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"))(x)
    full = np.asarray(x).reshape(N_DEV, N_DEV)  # row r = rank r's sends
    expected = full.T.reshape(N_DEV * N_DEV, 1)  # rank r receives column r
    np.testing.assert_allclose(np.asarray(out), expected)


def test_reducescatter_jit():
    x = jnp.asarray(np.random.RandomState(2).randn(N_DEV, N_DEV * 2),
                    dtype=jnp.float32)

    def fn(shard):
        # shard: (1, 16) per rank -> reshape to (16,) rows, scatter over ranks
        return hvd.reducescatter(shard[0], op=hvd.Sum, axis_name="hvd")[None]

    out = _run_per_rank(fn, x)
    expected = np.sum(np.asarray(x), axis=0).reshape(N_DEV, 2)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_adasum_jit_two_equal_vectors():
    # adasum(a, a) = a for identical vectors (scale-invariance sanity check)
    x = jnp.ones((N_DEV, 6), dtype=jnp.float32) * 2.5

    def fn(shard):
        return hvd.allreduce(shard, op=hvd.Adasum, axis_name="hvd")

    out = _run_per_rank(fn, x)
    np.testing.assert_allclose(np.asarray(out), 2.5, rtol=1e-5)


def test_adasum_jit_orthogonal_vectors_sum():
    # for orthogonal vectors adasum reduces to plain sum
    base = np.zeros((N_DEV, N_DEV), dtype=np.float32)
    np.fill_diagonal(base, np.arange(1, N_DEV + 1, dtype=np.float32))
    x = jnp.asarray(base)

    def fn(shard):
        return hvd.allreduce(shard, op=hvd.Adasum, axis_name="hvd")

    out = _run_per_rank(fn, x)
    expected = np.broadcast_to(base.sum(axis=0), (N_DEV, N_DEV))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


@pytest.mark.skipif(not hasattr(jax, "typeof"),
                    reason="pre-vma shard_map re-psums the psum cotangent "
                           "(extra factor of axis size)")
def test_allreduce_inside_jit_with_grad():
    # collectives must be differentiable for DistributedOptimizer-style use
    mesh = _mesh()
    x = jnp.arange(N_DEV, dtype=jnp.float32)

    def loss_fn(shard):
        red = hvd.allreduce(shard, op=hvd.Sum, axis_name="hvd")
        return jnp.sum(red * red)

    def per_rank(shard):
        g = jax.grad(lambda s: loss_fn(s))(shard)
        return g

    out = shard_map(per_rank, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"))(x)
    total = np.sum(np.asarray(x))
    # d/dx_i sum((psum x)^2) = 2 * psum(x) ... allreduced gradient
    np.testing.assert_allclose(np.asarray(out), 2 * total, rtol=1e-5)


# ---------------------------------------------------------------------------
# Quantized device-plane allreduce (HOROVOD_WIRE_COMPRESSION=device=int8):
# int8 block-scaled ring reduce-scatter + all-gather around lax.ppermute,
# fp32 accumulation, wire_codec.h block semantics (docs/compression.md).
# ---------------------------------------------------------------------------

import horovod_tpu.ops.collectives as hvd_ops
import horovod_tpu.ops.quantize as qz


def _smap(fn, in_specs=P("hvd"), out_specs=P("hvd")):
    mesh = _mesh()
    try:
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_rep=False)
    except TypeError:  # newer jax renamed the kwarg
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


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

    eager = np.asarray(_smap(fn)(x))
    traced = np.asarray(jax.jit(_smap(fn))(x))
    # On TPU both paths run the same Pallas kernels and agree bit-for-bit;
    # the CPU stand-in's whole-program fusion may contract mul+add into an
    # FMA, so allow 1-ulp-scale drift there.
    np.testing.assert_allclose(traced, eager, rtol=1e-6, atol=2e-6)


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


# ---------------------------------------------------------------------------
# Universal quantized collectives: allgather / broadcast / alltoall /
# reducescatter under the block-scaled codecs, plus the bidi / torus ring
# schedules (docs/compression.md).
# ---------------------------------------------------------------------------

_DEV_CODECS = ("int8", "int4", "int8g")
_Q_BOUND = {"int8": 0.5, "int4": 8.0, "int8g": 0.5}  # scale/2 per element


@pytest.mark.parametrize("codec", _DEV_CODECS)
def test_quantized_allgather_value_and_cross_rank(codec):
    rng = np.random.RandomState(31)
    x = jnp.asarray(rng.randn(N_DEV, 4096), dtype=jnp.float32)

    def fn(shard):
        return hvd_ops.quantized_allgather(shard, "hvd", min_bytes=0,
                                           codec=codec)

    qz.reset_device_byte_counters()
    out = np.asarray(_smap(fn)(x))          # [N_DEV * N_DEV, 4096]
    raw, enc = qz.device_byte_counters()
    assert raw > 0 and enc < raw
    assert enc / raw <= (0.20 if codec == "int4" else 0.35)
    per_rank = out.reshape(N_DEV, N_DEV, 4096)
    # Every rank decodes the same gathered bytes: bit-identical results.
    for r in range(1, N_DEV):
        np.testing.assert_array_equal(per_rank[r], per_rank[0])
    # One quantization step from the source values.
    assert np.max(np.abs(per_rank[0] - np.asarray(x))) < _Q_BOUND[codec]


def test_quantized_allgather_demotion_bit_identical():
    rng = np.random.RandomState(32)
    x = jnp.asarray(rng.randn(N_DEV, 64), dtype=jnp.float32)

    def quant(shard):
        return hvd_ops.quantized_allgather(shard, "hvd",
                                           min_bytes=1 << 20)

    def plain(shard):
        return hvd.allgather(shard, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant)(x)),
                                  np.asarray(_smap(plain)(x)))
    # non-fp32 demotes regardless of size
    xi = jnp.asarray(rng.randint(-9, 9, size=(N_DEV, 8192)), dtype=jnp.int32)

    def quant_i(shard):
        return hvd_ops.quantized_allgather(shard, "hvd", min_bytes=0)

    def plain_i(shard):
        return hvd.allgather(shard, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant_i)(xi)),
                                  np.asarray(_smap(plain_i)(xi)))


@pytest.mark.parametrize("codec", _DEV_CODECS)
def test_quantized_broadcast_value_and_cross_rank(codec):
    rng = np.random.RandomState(33)
    x = jnp.asarray(rng.randn(N_DEV, 4096), dtype=jnp.float32)
    root = 3

    def fn(shard):
        return hvd_ops.quantized_broadcast(shard, root, "hvd",
                                           min_bytes=0, codec=codec)

    qz.reset_device_byte_counters()
    out = np.asarray(_smap(fn)(x))
    raw, enc = qz.device_byte_counters()
    assert raw > 0 and enc < raw
    for r in range(1, N_DEV):
        np.testing.assert_array_equal(out[r], out[0])
    assert np.max(np.abs(out[0] - np.asarray(x)[root])) < _Q_BOUND[codec]


def test_quantized_broadcast_demotion_bit_identical():
    rng = np.random.RandomState(34)
    x = jnp.asarray(rng.randn(N_DEV, 64), dtype=jnp.float32)

    def quant(shard):
        return hvd_ops.quantized_broadcast(shard, 5, "hvd",
                                           min_bytes=1 << 20)

    def plain(shard):
        return hvd.broadcast(shard, root_rank=5, axis_name="hvd")

    np.testing.assert_array_equal(np.asarray(_smap(quant)(x)),
                                  np.asarray(_smap(plain)(x)))


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
