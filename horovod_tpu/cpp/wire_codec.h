// Wire-compression codecs for the cross-host chunk ring (docs/compression.md).
//
// Three codecs, all fp32-in / fp32-out with full-precision accumulation on
// the receive side (the ring never adds quantized values together):
//
//  - bf16: truncate each fp32 to its high 16 bits.  The exponent field is
//    copied exactly, so no bf16-encodable magnitude can overflow; values
//    already representable in bf16 round-trip bit-exactly.
//  - int8: per-256-element block scale (EQuARX-style).  Block layout on the
//    wire is [4-byte little-endian fp32 scale][one int8 per element]; the
//    last block of a tensor may be short.  scale = max|x|/127, so the
//    per-element error is bounded by scale/2 (round-to-nearest).
//  - int4: the same 256-element block scale with 4-bit codes, two per byte
//    (element 2i in the low nibble, 2i+1 in the high nibble).  scale =
//    max|x|/7; per-element error bounded by scale/2.  ~0.13x the raw bytes.
//
// The encoded stream is position-independent per element: byte offsets are
// pure functions of the element index, so a receiver can decode any prefix
// of elements as chunks arrive (WireDecodableElems / WireDecodeRange) and
// the allgather phase can forward encoded bytes verbatim for cross-rank
// bit-identity.
//
// Shared edge semantics for every block-scaled codec: the max|x| scan uses
// `a > maxabs`, so NaN elements never win (an all-NaN block keeps
// scale 0 and encodes zeros); a block whose max is inf stores a
// non-finite scale with zero codes (decode yields NaN via inf*0 rather
// than inventing values); a NaN element inside an otherwise-finite block
// clamps to the positive code bound (std::min/std::max operand order).
//
// Header-only so the selftests link it without extra objects.

#ifndef HVD_TPU_WIRE_CODEC_H_
#define HVD_TPU_WIRE_CODEC_H_

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <cstring>

namespace hvdtpu {

enum class WireCodec : int32_t {
  kNone = 0,
  kBf16 = 1,
  kInt8 = 2,
  kInt4 = 3,
};

// The last id a peer may name (socketio.cc refuses a response beyond it).
constexpr int32_t kWireCodecMax = static_cast<int32_t>(WireCodec::kInt4);

// Block geometry: one scale record per 256 elements (int8/int4), 4-bit codes
// clamp to +/-kWireInt4Max.  Mirrored as traced math by
// horovod_tpu/ops/quantize.py (WIRE_BLOCK / WIRE_SCALE_BYTES /
// WIRE_INT4_MAX / WIRE_CODEC_IDS) for the device-plane quantized ring;
// tools/hvd_lint.py enforces the two stay in sync.
constexpr int64_t kWireBlock = 256;
constexpr int64_t kWireScaleBytes = 4;
constexpr int64_t kWireInt4Max = 7;

// Encoded size in bytes of `count` fp32 elements under `codec`.
inline int64_t WireEncodedBytes(WireCodec codec, int64_t count) {
  switch (codec) {
    case WireCodec::kBf16:
      return 2 * count;
    case WireCodec::kInt8: {
      const int64_t blocks = (count + kWireBlock - 1) / kWireBlock;
      return blocks * kWireScaleBytes + count;
    }
    case WireCodec::kInt4: {
      const int64_t blocks = (count + kWireBlock - 1) / kWireBlock;
      return blocks * kWireScaleBytes + (count + 1) / 2;
    }
    case WireCodec::kNone:
    default:
      return 4 * count;
  }
}

namespace wire_internal {

// NaN-proof max|x| over [src, src+n): `a > maxabs` never lets NaN win.
inline float MaxAbs(const float* src, int64_t n) {
  float maxabs = 0.0f;
  for (int64_t i = 0; i < n; ++i) {
    const float a = std::fabs(src[i]);
    if (a > maxabs) maxabs = a;
  }
  return maxabs;
}

}  // namespace wire_internal

// Encode `count` fp32 elements from `src` into `dst`
// (WireEncodedBytes(codec, count) bytes).
inline void WireEncode(WireCodec codec, const float* src, int64_t count,
                       char* dst) {
  if (codec == WireCodec::kBf16) {
    uint16_t* out = reinterpret_cast<uint16_t*>(dst);
    for (int64_t i = 0; i < count; ++i) {
      uint32_t bits;
      std::memcpy(&bits, src + i, 4);
      out[i] = static_cast<uint16_t>(bits >> 16);
    }
    return;
  }
  if (codec == WireCodec::kInt8) {
    for (int64_t b0 = 0; b0 < count; b0 += kWireBlock) {
      const int64_t n = std::min(kWireBlock, count - b0);
      const float maxabs = wire_internal::MaxAbs(src + b0, n);
      const float scale = maxabs / 127.0f;
      std::memcpy(dst, &scale, kWireScaleBytes);
      int8_t* q = reinterpret_cast<int8_t*>(dst + kWireScaleBytes);
      if (scale > 0.0f && std::isfinite(scale)) {
        const float inv = 1.0f / scale;
        for (int64_t i = 0; i < n; ++i) {
          const float v = std::nearbyintf(src[b0 + i] * inv);
          q[i] = static_cast<int8_t>(
              std::max(-127.0f, std::min(127.0f, v)));
        }
      } else {
        // All-zero block (or non-finite scale from inf/nan input: encode
        // zeros rather than propagate garbage — matching the clamp above).
        std::memset(q, 0, static_cast<size_t>(n));
      }
      dst += kWireScaleBytes + n;
    }
    return;
  }
  if (codec == WireCodec::kInt4) {
    const float qmax = static_cast<float>(kWireInt4Max);
    for (int64_t b0 = 0; b0 < count; b0 += kWireBlock) {
      const int64_t n = std::min(kWireBlock, count - b0);
      const float maxabs = wire_internal::MaxAbs(src + b0, n);
      const float scale = maxabs / qmax;
      std::memcpy(dst, &scale, kWireScaleBytes);
      uint8_t* q = reinterpret_cast<uint8_t*>(dst + kWireScaleBytes);
      const int64_t nbytes = (n + 1) / 2;
      if (scale > 0.0f && std::isfinite(scale)) {
        const float inv = 1.0f / scale;
        std::memset(q, 0, static_cast<size_t>(nbytes));
        for (int64_t i = 0; i < n; ++i) {
          const float v = std::nearbyintf(src[b0 + i] * inv);
          const int code = static_cast<int>(std::max(-qmax, std::min(qmax, v)));
          const uint8_t nib = static_cast<uint8_t>(code) & 0x0F;
          q[i / 2] |= (i & 1) ? static_cast<uint8_t>(nib << 4) : nib;
        }
      } else {
        std::memset(q, 0, static_cast<size_t>(nbytes));
      }
      dst += kWireScaleBytes + nbytes;
    }
    return;
  }
  std::memcpy(dst, src, static_cast<size_t>(4 * count));
}

// Decode elements [elem_lo, elem_hi) of an encoded stream.  `src` points at
// the START of the encoded stream (not at elem_lo); `dst` receives
// elem_hi - elem_lo fp32 values.
inline void WireDecodeRange(WireCodec codec, const char* src,
                            int64_t elem_lo, int64_t elem_hi, float* dst) {
  if (codec == WireCodec::kBf16) {
    const uint16_t* in = reinterpret_cast<const uint16_t*>(src) + elem_lo;
    for (int64_t i = 0; i < elem_hi - elem_lo; ++i) {
      const uint32_t bits = static_cast<uint32_t>(in[i]) << 16;
      std::memcpy(dst + i, &bits, 4);
    }
    return;
  }
  if (codec == WireCodec::kInt8) {
    for (int64_t e = elem_lo; e < elem_hi;) {
      const int64_t blk = e / kWireBlock;
      const int64_t in_blk = e % kWireBlock;
      const int64_t blk_end = std::min((blk + 1) * kWireBlock, elem_hi);
      const char* base =
          src + blk * (kWireScaleBytes + kWireBlock) + kWireScaleBytes;
      float scale;
      std::memcpy(&scale,
                  src + blk * (kWireScaleBytes + kWireBlock), 4);
      const int8_t* q = reinterpret_cast<const int8_t*>(base) + in_blk;
      for (int64_t i = 0; e + i < blk_end; ++i) {
        dst[e + i - elem_lo] = scale * static_cast<float>(q[i]);
      }
      e = blk_end;
    }
    return;
  }
  if (codec == WireCodec::kInt4) {
    const int64_t per_block = kWireScaleBytes + kWireBlock / 2;
    for (int64_t e = elem_lo; e < elem_hi;) {
      const int64_t blk = e / kWireBlock;
      const int64_t blk_end = std::min((blk + 1) * kWireBlock, elem_hi);
      float scale;
      std::memcpy(&scale, src + blk * per_block, 4);
      const uint8_t* q = reinterpret_cast<const uint8_t*>(
          src + blk * per_block + kWireScaleBytes);
      for (int64_t i = e; i < blk_end; ++i) {
        const int64_t in_blk = i % kWireBlock;
        const uint8_t b = q[in_blk / 2];
        const int nib = (in_blk & 1) ? (b >> 4) & 0x0F : b & 0x0F;
        // Sign-extend the nibble: [-8, 7] (codes only use [-7, 7]).
        const int code = (nib ^ 8) - 8;
        dst[i - elem_lo] = scale * static_cast<float>(code);
      }
      e = blk_end;
    }
    return;
  }
  std::memcpy(dst, src + 4 * elem_lo,
              static_cast<size_t>(4 * (elem_hi - elem_lo)));
}

// How many leading elements of a `total_elems`-element encoded stream are
// fully decodable once `bytes_received` prefix bytes have arrived.  Used by
// the ring's incremental consume path (chunk boundaries are byte-, not
// block-aligned).
inline int64_t WireDecodableElems(WireCodec codec, int64_t bytes_received,
                                  int64_t total_elems) {
  int64_t n;
  switch (codec) {
    case WireCodec::kBf16:
      n = bytes_received / 2;
      break;
    case WireCodec::kInt8: {
      const int64_t per_block = kWireScaleBytes + kWireBlock;
      const int64_t full = bytes_received / per_block;
      const int64_t rem = bytes_received % per_block;
      n = full * kWireBlock +
          std::max<int64_t>(0, rem - kWireScaleBytes);
      break;
    }
    case WireCodec::kInt4: {
      const int64_t per_block = kWireScaleBytes + kWireBlock / 2;
      const int64_t full = bytes_received / per_block;
      const int64_t rem = bytes_received % per_block;
      n = full * kWireBlock +
          std::max<int64_t>(0, (rem - kWireScaleBytes) * 2);
      break;
    }
    case WireCodec::kNone:
    default:
      n = bytes_received / 4;
      break;
  }
  return std::min(n, total_elems);
}

}  // namespace hvdtpu

#endif  // HVD_TPU_WIRE_CODEC_H_
