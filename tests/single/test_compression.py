"""Gradient-compression casts (horovod_tpu/compression.py).

The load-bearing case: float64 tensors must never be routed through
float16, whose 5-bit exponent silently turns anything past 65504 into
inf.  FP16Compressor reroutes float64 through bfloat16 (fp32 exponent
range), and BF16Compressor works on plain numpy arrays via ml_dtypes.
"""

import numpy as np
import pytest

from horovod_tpu.compression import Compression


def test_fp16_float32_round_trip():
    x = np.linspace(-4.0, 4.0, 64, dtype=np.float32)
    wire, ctx = Compression.fp16.compress(x)
    assert str(wire.dtype) == "float16"
    back = Compression.fp16.decompress(wire, ctx)
    assert str(back.dtype) == "float32"
    np.testing.assert_allclose(back, x, atol=1e-2)


def test_fp16_float64_routed_through_bf16():
    # 1e30 overflows float16 (max 65504) but is comfortably in bf16 range.
    x = np.array([1e30, -2.5e12, 1.0, -65504.0, 7e-20], dtype=np.float64)
    wire, ctx = Compression.fp16.compress(x)
    assert str(wire.dtype) == "bfloat16", (
        "float64 must not be cast to float16 (silent overflow to inf)")
    back = np.asarray(Compression.fp16.decompress(wire, ctx))
    assert str(back.dtype) == "float64"
    assert np.all(np.isfinite(back))
    np.testing.assert_allclose(back, x, rtol=1 / 128.0)


def test_bf16_numpy_float32():
    x = np.array([3.14159, -1e35, 2.0, 0.0], dtype=np.float32)
    wire, ctx = Compression.bf16.compress(x)
    assert str(wire.dtype) == "bfloat16"
    back = np.asarray(Compression.bf16.decompress(wire, ctx))
    assert str(back.dtype) == "float32"
    assert np.all(np.isfinite(back))
    np.testing.assert_allclose(back, x, rtol=1 / 128.0)
    # Exactly-representable values survive bit-for-bit.
    exact = np.array([1.0, -0.5, 1024.0, 0.0078125], dtype=np.float32)
    wire, ctx = Compression.bf16.compress(exact)
    np.testing.assert_array_equal(
        np.asarray(Compression.bf16.decompress(wire, ctx)), exact)


def test_bf16_float64_round_trip():
    x = np.array([1e300 / 1e270, -42.42, 3e-20], dtype=np.float64)
    wire, ctx = Compression.bf16.compress(x)
    assert str(wire.dtype) == "bfloat16"
    back = np.asarray(Compression.bf16.decompress(wire, ctx))
    assert str(back.dtype) == "float64"
    np.testing.assert_allclose(back, x, rtol=1 / 128.0)


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float16])
def test_non_compressible_dtypes_pass_through(dtype):
    x = np.arange(8).astype(dtype)
    wire, ctx = Compression.fp16.compress(x)
    assert wire is x and ctx is None
    assert Compression.fp16.decompress(wire, ctx) is x


def test_none_compressor_identity():
    x = np.ones(4, dtype=np.float64)
    wire, ctx = Compression.none.compress(x)
    assert wire is x and ctx is None
    assert Compression.none.decompress(wire, ctx) is x


# ---------------------------------------------------------------------------
# Device-plane int8 block codec (horovod_tpu/ops/quantize.py).
#
# quantize.py is a traced-math mirror of cpp/wire_codec.h's WireEncode /
# WireDecodeRange(kInt8); these tests pin the edge-case semantics against a
# plain-numpy transliteration of the C++ loops and check the two dispatch
# modes (jnp fallback vs the Pallas interpreter) stay bit-identical.
# ---------------------------------------------------------------------------

import jax.numpy as jnp

import horovod_tpu.ops.quantize as qz


def _np_quantize(flat):
    """numpy transliteration of WireEncode(kInt8) on a flat fp32 array."""
    flat = np.asarray(flat, dtype=np.float32)
    n = flat.size
    nblocks = max(1, -(-n // qz.WIRE_BLOCK))
    xb = np.zeros((nblocks, qz.WIRE_BLOCK), np.float32)
    xb.reshape(-1)[:n] = flat
    absx = np.abs(xb)
    absx[np.isnan(absx)] = 0.0  # `a > maxabs` scan: NaN never wins
    maxabs = absx.max(axis=1, keepdims=True)
    scale = (maxabs / 127.0).astype(np.float32)
    ok = (scale > 0.0) & np.isfinite(scale)
    inv = np.where(ok, np.float32(1.0) / np.where(ok, scale, 1.0),
                   0.0).astype(np.float32)
    with np.errstate(invalid="ignore"):
        v = np.rint(xb * inv)
        # std::max(-127, std::min(127, v)) operand order: NaN lands on +127
        v = np.where(v < 127.0, v, 127.0)
        v = np.where(v > -127.0, v, -127.0)
    codes = np.where(inv > 0.0, v, 0.0).astype(np.int8)
    return codes, scale


@pytest.mark.parametrize("interpret", [None, True])
def test_int8_all_zero_block(interpret):
    x = np.zeros(qz.WIRE_BLOCK * 2, dtype=np.float32)
    codes, scales = qz.quantize(jnp.asarray(x), interpret=interpret)
    assert np.all(np.asarray(codes) == 0)
    assert np.all(np.asarray(scales) == 0.0)
    back = np.asarray(qz.dequantize(codes, scales, x.size,
                                    interpret=interpret))
    np.testing.assert_array_equal(back, x)


@pytest.mark.parametrize("interpret", [None, True])
def test_int8_nonfinite_blocks(interpret):
    # Block 0: contains +inf -> scale inf, codes all zero (decode flags the
    # block as NaN via inf*0 rather than inventing values).
    # Block 1: all NaN -> scale 0 (NaN never wins the maxabs scan), codes 0.
    # Block 2: one NaN inside a finite block -> that element clamps to +127.
    x = np.ones(qz.WIRE_BLOCK * 3, dtype=np.float32)
    x[3] = np.inf
    x[qz.WIRE_BLOCK:2 * qz.WIRE_BLOCK] = np.nan
    x[2 * qz.WIRE_BLOCK + 5] = np.nan
    codes, scales = qz.quantize(jnp.asarray(x), interpret=interpret)
    codes = np.asarray(codes)
    scales = np.asarray(scales).reshape(-1)
    assert np.isinf(scales[0]) and np.all(codes[0] == 0)
    assert scales[1] == 0.0 and np.all(codes[1] == 0)
    assert np.isfinite(scales[2]) and scales[2] > 0
    assert codes[2, 5] == 127
    ref_codes, ref_scales = _np_quantize(x)
    np.testing.assert_array_equal(codes, ref_codes)
    np.testing.assert_array_equal(scales, ref_scales.reshape(-1))


@pytest.mark.parametrize("interpret", [None, True])
def test_int8_short_last_block(interpret):
    # 600 = 2 full blocks + 88: zero padding cannot raise max|x|, so the
    # short block quantizes exactly as the byte-stream codec quantizes it.
    rng = np.random.RandomState(7)
    x = rng.randn(600).astype(np.float32) * 3.0
    codes, scales = qz.quantize(jnp.asarray(x), interpret=interpret)
    ref_codes, ref_scales = _np_quantize(x)
    np.testing.assert_array_equal(np.asarray(codes), ref_codes)
    np.testing.assert_array_equal(np.asarray(scales), ref_scales)
    back = np.asarray(qz.dequantize(codes, scales, x.size,
                                    interpret=interpret))
    # Round-to-nearest: per-element error bounded by scale/2.
    bound = np.repeat(ref_scales.reshape(-1), qz.WIRE_BLOCK)[:x.size] / 2
    assert np.all(np.abs(back - x) <= bound + 1e-7)


def test_int8_dispatch_modes_bit_identical():
    # The jnp fallback and the Pallas interpreter must agree bit-for-bit
    # (scales/inv are computed outside the kernel precisely for this).
    rng = np.random.RandomState(11)
    x = (rng.randn(qz.WIRE_BLOCK * 4 + 17) * 50).astype(np.float32)
    x[0] = np.inf
    x[5] = np.nan
    c_jnp, s_jnp = qz.quantize(jnp.asarray(x), interpret=None)
    c_int, s_int = qz.quantize(jnp.asarray(x), interpret=True)
    np.testing.assert_array_equal(np.asarray(c_jnp), np.asarray(c_int))
    np.testing.assert_array_equal(np.asarray(s_jnp), np.asarray(s_int))
    d_jnp = np.asarray(qz.dequantize(c_jnp, s_jnp, x.size, interpret=None))
    d_int = np.asarray(qz.dequantize(c_int, s_int, x.size, interpret=True))
    np.testing.assert_array_equal(d_jnp, d_int)


def test_int8_fake_quantize_residual_semantics():
    rng = np.random.RandomState(13)
    x = (rng.randn(16, 40) * 2).astype(np.float32)
    fq = np.asarray(qz.fake_quantize(jnp.asarray(x)))
    assert fq.shape == x.shape
    codes, scales = qz.quantize(jnp.asarray(x.reshape(-1)))
    expect = np.asarray(qz.dequantize(codes, scales,
                                      x.size)).reshape(x.shape)
    np.testing.assert_array_equal(fq, expect)
    # all-zero input is a fixed point: residual identically zero
    z = np.zeros((4, 4), np.float32)
    np.testing.assert_array_equal(np.asarray(qz.fake_quantize(jnp.asarray(z))),
                                  z)


def test_encoded_nbytes_and_ring_bytes():
    # WireEncodedBytes(kInt8, n) = ceil(n/256)*4 + n, short block included.
    assert qz.encoded_nbytes(qz.WIRE_BLOCK) == qz.WIRE_SCALE_BYTES + 256
    assert qz.encoded_nbytes(1) == qz.WIRE_SCALE_BYTES + 1
    assert qz.encoded_nbytes(600) == 3 * qz.WIRE_SCALE_BYTES + 600
    raw, enc = qz.ring_bytes(16384, 8)
    # 2*(8-1) hops of one 2048-element chunk each
    assert raw == 14 * 2048 * 4
    assert enc == 14 * qz.encoded_nbytes(2048)
    assert enc / raw <= 0.30
    assert qz.ring_bytes(1024, 1) == (0, 0)


# ---------------------------------------------------------------------------
# int4 packed-nibble codec: a numpy transliteration of WireEncode(kInt4), same
# edge-case contract as the int8 cases above.
# ---------------------------------------------------------------------------

def _np_quantize_int4(flat):
    """numpy transliteration of WireEncode(kInt4): block scale over qmax=7,
    codes clamped to [-7, 7], two codes packed per byte (element 2i in the
    low nibble)."""
    flat = np.asarray(flat, dtype=np.float32)
    n = flat.size
    nblocks = max(1, -(-n // qz.WIRE_BLOCK))
    xb = np.zeros((nblocks, qz.WIRE_BLOCK), np.float32)
    xb.reshape(-1)[:n] = flat
    absx = np.abs(xb)
    absx[np.isnan(absx)] = 0.0
    maxabs = absx.max(axis=1, keepdims=True)
    scale = (maxabs / np.float32(qz.WIRE_INT4_MAX)).astype(np.float32)
    ok = (scale > 0.0) & np.isfinite(scale)
    inv = np.where(ok, np.float32(1.0) / np.where(ok, scale, 1.0),
                   0.0).astype(np.float32)
    qmax = float(qz.WIRE_INT4_MAX)
    with np.errstate(invalid="ignore"):
        v = np.rint(xb * inv)
        v = np.where(v < qmax, v, qmax)     # std::min: NaN lands on +qmax
        v = np.where(v > -qmax, v, -qmax)
    codes = np.where(inv > 0.0, v, 0.0).astype(np.int8)
    u = codes.astype(np.uint8)
    packed = ((u[:, 0::2] & 0x0F) | ((u[:, 1::2] & 0x0F) << 4)).astype(np.int8)
    return packed, scale


@pytest.mark.parametrize("interpret", [None, True])
def test_int4_matches_numpy_transliteration(interpret):
    rng = np.random.RandomState(21)
    # 3 full blocks + a short one; block 0 holds an inf (scale inf, codes
    # 0), one NaN element inside finite block 1 clamps to +7.
    x = (rng.randn(qz.WIRE_BLOCK * 3 + 77) * 5).astype(np.float32)
    x[3] = np.inf
    x[qz.WIRE_BLOCK + 9] = np.nan
    codes, scales = qz.quantize(jnp.asarray(x), codec="int4",
                                interpret=interpret)
    ref_codes, ref_scales = _np_quantize_int4(x)
    np.testing.assert_array_equal(np.asarray(codes), ref_codes)
    np.testing.assert_array_equal(np.asarray(scales), ref_scales)
    # Decode: packed bytes are half-width, values bounded by scale/2 on
    # finite blocks; the inf block decodes to NaN (inf * 0), not numbers.
    assert codes.shape == (4, qz.WIRE_BLOCK // 2)
    back = np.asarray(qz.dequantize(codes, scales, x.size, codec="int4",
                                    interpret=interpret))
    assert np.all(np.isnan(back[:qz.WIRE_BLOCK]))
    fin = slice(2 * qz.WIRE_BLOCK, 3 * qz.WIRE_BLOCK)
    bound = float(ref_scales[2, 0]) / 2
    assert np.all(np.abs(back[fin] - x[fin]) <= bound + 1e-7)


@pytest.mark.parametrize("interpret", [None, True])
def test_int4_pack_unpack_round_trip(interpret):
    rng = np.random.RandomState(22)
    x = (rng.randn(qz.WIRE_BLOCK * 2) * 3).astype(np.float32)
    codes, scales = qz.quantize(jnp.asarray(x), codec="int4",
                                interpret=interpret)
    unpacked = np.asarray(qz._unpack_int4(codes))
    assert unpacked.min() >= -qz.WIRE_INT4_MAX
    assert unpacked.max() <= qz.WIRE_INT4_MAX
    repacked = np.asarray(qz._pack_int4(jnp.asarray(unpacked)))
    np.testing.assert_array_equal(repacked, np.asarray(codes))


def test_int4_fake_quantize_and_dispatch_bit_identical():
    rng = np.random.RandomState(25)
    x = (rng.randn(4096 + 3 * qz.WIRE_BLOCK + 11) * 9).astype(np.float32)
    codec = "int4"
    c_jnp, s_jnp = qz.quantize(jnp.asarray(x), codec=codec, interpret=None)
    c_int, s_int = qz.quantize(jnp.asarray(x), codec=codec, interpret=True)
    np.testing.assert_array_equal(np.asarray(c_jnp), np.asarray(c_int))
    np.testing.assert_array_equal(np.asarray(s_jnp), np.asarray(s_int))
    fq = np.asarray(qz.fake_quantize(jnp.asarray(x), codec=codec))
    expect = np.asarray(qz.dequantize(c_jnp, s_jnp, x.size, codec=codec))
    np.testing.assert_array_equal(fq, expect)


def test_encoded_nbytes_new_codecs_and_schedules():
    # int4: ceil(n/256) scales + ceil(n/2) packed bytes.
    assert qz.encoded_nbytes(qz.WIRE_BLOCK, "int4") == 4 + 128
    assert qz.encoded_nbytes(1, "int4") == 4 + 1
    assert qz.encoded_nbytes(16384, "int4") == 64 * 4 + 8192
    # The ISSUE acceptance floor: int4 on a 64 KiB fp32 payload.
    assert qz.encoded_nbytes(16384, "int4") / (4 * 16384) <= 0.16
    # bidi moves the same totals as ring (each hop splits the chunk across
    # the two directions; 2048 splits on block boundaries, so exactly).
    raw_r, enc_r = qz.ring_bytes(16384, 8, "int8", "ring")
    raw_b, enc_b = qz.ring_bytes(16384, 8, "int8", "bidi")
    assert raw_b == raw_r
    assert abs(enc_b - enc_r) <= 14 * qz.WIRE_SCALE_BYTES
    # torus on 8 = 2x4: 2(b-1) hops of count/b plus 2(a-1) of count/(ab).
    raw_t, _ = qz.ring_bytes(16384, 8, "int8", "torus")
    assert raw_t == 4 * (6 * 4096 + 2 * 2048)
    # Same per-rank byte total as the 1-D ring here; the torus win is
    # 8 chunk-hops of latency instead of 14, not bytes.
    assert raw_t == raw_r
    # Prime world: torus demotes to bidi.
    assert (qz.ring_bytes(16384, 7, "int8", "torus")
            == qz.ring_bytes(16384, 7, "int8", "bidi"))
    # Factorization helper.
    assert qz.torus_factors(8) == (2, 4)
    assert qz.torus_factors(16) == (4, 4)
    assert qz.torus_factors(12) == (3, 4)
    assert qz.torus_factors(7) is None
    assert qz.torus_factors(2) is None
