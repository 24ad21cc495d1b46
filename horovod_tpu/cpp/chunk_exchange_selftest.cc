// Fuzz harness for ChunkedDuplexExchange, the chunk-pipelined duplex
// primitive under the ring/chain data plane (socketio.cc).
//
// Two threads on a socketpair run randomized-geometry exchanges — payload
// lengths from 0 to several MiB (remainder chunks, empty streams), chunk
// sizes differing per side (mixed HOROVOD_RING_CHUNK_BYTES interop), both
// recv modes (direct-dest and scratch + on_chunk) — and every received
// byte is verified against the sender's pattern.  Error paths are driven
// explicitly: header mismatch, and cancellation mid-stream (no hang).
//
// Reference analog (SURVEY.md §5, sanitizers/selftests): mechanical
// validation of the wire primitive apart from the full controller.

#include <sys/socket.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <cmath>
#include <cstdio>
#include <cstring>
#include <random>
#include <thread>
#include <vector>

#include "socketio.h"
#include "wire_codec.h"

namespace hvdtpu {
int GetLogLevel() { return 4; }  // errors only
void SetLogLevel(int) {}
}  // namespace hvdtpu

using namespace hvdtpu;

namespace {

std::atomic<int> failures{0};

void Fail(const char* what, int round) {
  std::fprintf(stderr, "FAIL round %d: %s\n", round, what);
  failures.fetch_add(1);
}

// Deterministic per-(seed, offset) byte pattern both sides can compute.
char PatternByte(unsigned seed, int64_t off) {
  return static_cast<char>((seed * 131 + off * 7 + (off >> 9)) & 0xFF);
}

std::vector<char> MakePattern(unsigned seed, int64_t n) {
  std::vector<char> v(static_cast<size_t>(n));
  for (int64_t i = 0; i < n; ++i) v[static_cast<size_t>(i)] =
      PatternByte(seed, i);
  return v;
}

bool CheckPattern(const char* data, unsigned seed, int64_t n) {
  for (int64_t i = 0; i < n; ++i) {
    if (data[i] != PatternByte(seed, i)) return false;
  }
  return true;
}

struct SidePlan {
  int64_t send_len;
  int64_t chunk;
  bool direct_dest;  // receive straight into the buffer vs on_chunk scratch
};

void RunSide(Socket* sock, unsigned my_seed, unsigned peer_seed,
             const SidePlan& mine, const SidePlan& theirs,
             const std::string& header, int round) {
  std::vector<char> out = MakePattern(my_seed, mine.send_len);
  std::vector<char> in(static_cast<size_t>(theirs.send_len));
  int64_t consumed = 0;
  ChunkExchangeError err;
  bool ok;
  if (mine.direct_dest) {
    ok = ChunkedDuplexExchange(*sock, out.data(), mine.send_len, *sock,
                               theirs.send_len, mine.chunk, header,
                               in.data(), nullptr, nullptr, &err);
  } else {
    ok = ChunkedDuplexExchange(
        *sock, out.data(), mine.send_len, *sock, theirs.send_len, mine.chunk,
        header, nullptr,
        [&](int64_t off, const char* data, int64_t n) {
          if (off != consumed) Fail("out-of-order chunk", round);
          std::memcpy(in.data() + off, data, static_cast<size_t>(n));
          consumed += n;
        },
        nullptr, &err);
  }
  if (!ok) return Fail("exchange returned false", round);
  if (err.kind != ChunkExchangeError::kNone) {
    return Fail("err.kind set on success", round);
  }
  if (!mine.direct_dest && consumed != theirs.send_len) {
    return Fail("on_chunk did not consume the full stream", round);
  }
  if (!CheckPattern(in.data(), peer_seed, theirs.send_len)) {
    return Fail("payload corrupted", round);
  }
}

bool MakePair(Socket* a, Socket* b) {
  int fds[2];
  if (::socketpair(AF_UNIX, SOCK_STREAM, 0, fds) != 0) return false;
  *a = Socket(fds[0]);
  *b = Socket(fds[1]);
  return true;
}

void FuzzRounds() {
  std::mt19937 rng(0xC0FFEE);
  auto rand_len = [&](int64_t lo, int64_t hi) {
    return std::uniform_int_distribution<int64_t>(lo, hi)(rng);
  };
  for (int round = 0; round < 40; ++round) {
    Socket a, b;
    if (!MakePair(&a, &b)) return Fail("socketpair", round);
    // Geometry mix: tiny chunks over big payloads, chunk > payload,
    // zero-length streams in either/both directions, uneven sides.
    SidePlan pa{rand_len(0, 3) == 0 ? 0 : rand_len(1, 3 << 20),
                rand_len(1, 4) == 1 ? rand_len(100, 5000)
                                    : rand_len(1 << 14, 1 << 20),
                (rng() & 1) != 0};
    SidePlan pb{rand_len(0, 3) == 0 ? 0 : rand_len(1, 3 << 20),
                rand_len(1, 4) == 1 ? rand_len(100, 5000)
                                    : rand_len(1 << 14, 1 << 20),
                (rng() & 1) != 0};
    std::string header = "hdr" + std::to_string(round);
    unsigned sa = rng(), sb = rng();
    std::thread ta(RunSide, &a, sa, sb, pa, pb, header, round);
    RunSide(&b, sb, sa, pb, pa, header, round);
    ta.join();
  }
}

void HeaderMismatch() {
  Socket a, b;
  if (!MakePair(&a, &b)) return Fail("socketpair", -1);
  std::vector<char> pay(1 << 16, 'x');
  auto side = [&](Socket* s, const std::string& hdr) {
    std::vector<char> in(pay.size());
    ChunkExchangeError err;
    bool ok = ChunkedDuplexExchange(*s, pay.data(), (int64_t)pay.size(), *s,
                                    (int64_t)pay.size(), 1 << 12, hdr,
                                    in.data(), nullptr, nullptr, &err);
    if (ok) Fail("header mismatch not detected", -1);
    if (err.kind != ChunkExchangeError::kHeaderMismatch) {
      Fail("wrong error kind for header mismatch", -1);
    }
  };
  std::thread t(side, &a, std::string("AAAA9999"));
  side(&b, std::string("BBBB9999"));
  t.join();
}

void Cancellation() {
  Socket a, b;
  if (!MakePair(&a, &b)) return Fail("socketpair", -2);
  // Peer never sends: the side must notice the cancel flag and abort
  // within a poll interval instead of hanging.
  std::vector<char> in(1 << 16);
  std::atomic<bool> cancel{false};
  std::thread flipper([&] {
    std::this_thread::sleep_for(std::chrono::milliseconds(150));
    cancel = true;
  });
  ChunkExchangeError err;
  bool ok = ChunkedDuplexExchange(a, nullptr, 0, a, (int64_t)in.size(),
                                  1 << 12, "h", in.data(), nullptr,
                                  [&] { return cancel.load(); }, &err);
  flipper.join();
  if (ok) Fail("cancelled exchange reported success", -2);
  if (err.kind != ChunkExchangeError::kTransport) {
    Fail("wrong error kind for cancellation", -2);
  }
}

// ---- wire_codec.h: the codec layer the compressed ring rides ------------

// bf16 truncation is exact for values already representable in bf16
// (mantissa fits in 7 bits): round-tripping them must be bit-identical.
void CodecBf16RoundTrip() {
  const float vals[] = {0.0f,     -0.0f, 1.0f,      -1.0f,   0.5f,
                        2.0f,     -2.5f, 1024.0f,   -0.125f, 3.140625f,
                        65536.0f, 0x1p100f, -0x1p-100f, 0.0078125f};
  const int64_t n = sizeof(vals) / sizeof(vals[0]);
  std::vector<char> enc(
      static_cast<size_t>(WireEncodedBytes(WireCodec::kBf16, n)));
  std::vector<float> dec(static_cast<size_t>(n));
  WireEncode(WireCodec::kBf16, vals, n, enc.data());
  WireDecodeRange(WireCodec::kBf16, enc.data(), 0, n, dec.data());
  for (int64_t i = 0; i < n; ++i) {
    if (std::memcmp(&dec[i], &vals[i], 4) != 0) {
      Fail("bf16 round-trip not exact for representable value", -3);
      return;
    }
  }
  // Non-representable values still land within one bf16 ulp (truncation:
  // error < 2^-7 relative).
  const float odd[] = {3.14159265f, 1.0001f, -123.456f, 7.7777e-5f};
  const int64_t m = sizeof(odd) / sizeof(odd[0]);
  WireEncode(WireCodec::kBf16, odd, m, enc.data());
  WireDecodeRange(WireCodec::kBf16, enc.data(), 0, m, dec.data());
  for (int64_t i = 0; i < m; ++i) {
    if (std::fabs(dec[i] - odd[i]) > std::fabs(odd[i]) * (1.0f / 128.0f)) {
      Fail("bf16 truncation error exceeds one ulp bound", -3);
      return;
    }
  }
}

// int8 block scaling: |decode(encode(x)) - x| <= scale/2 per element,
// where scale = blockmax/127; partial last blocks and random-access
// decode (block-unaligned ranges) must agree with a full decode.
void CodecInt8ErrorBound() {
  std::mt19937 rng(0xBEEF);
  std::uniform_real_distribution<float> mag(-50.f, 50.f);
  // 3 full blocks + a partial one, plus an all-zero block in the middle.
  const int64_t n = 3 * kWireBlock + 77;
  std::vector<float> src(static_cast<size_t>(n));
  for (auto& v : src) v = mag(rng);
  for (int64_t i = kWireBlock; i < 2 * kWireBlock; ++i) src[i] = 0.0f;
  std::vector<char> enc(
      static_cast<size_t>(WireEncodedBytes(WireCodec::kInt8, n)));
  WireEncode(WireCodec::kInt8, src.data(), n, enc.data());
  std::vector<float> dec(static_cast<size_t>(n));
  WireDecodeRange(WireCodec::kInt8, enc.data(), 0, n, dec.data());
  for (int64_t b0 = 0; b0 < n; b0 += kWireBlock) {
    const int64_t bn = std::min(kWireBlock, n - b0);
    float maxabs = 0.f;
    for (int64_t i = 0; i < bn; ++i) {
      maxabs = std::max(maxabs, std::fabs(src[b0 + i]));
    }
    const float scale = maxabs / 127.0f;
    for (int64_t i = 0; i < bn; ++i) {
      if (std::fabs(dec[b0 + i] - src[b0 + i]) > scale * 0.5f + 1e-12f) {
        Fail("int8 block-scale error exceeds scale/2", -4);
        return;
      }
    }
  }
  // Incremental decode (the ring's consume path): byte-level prefixes +
  // block-unaligned ranges must reproduce the full decode exactly.
  int64_t decoded = 0;
  std::vector<float> inc(static_cast<size_t>(n));
  for (int64_t bytes = 0; bytes <= WireEncodedBytes(WireCodec::kInt8, n);
       bytes += 97) {
    const int64_t avail = WireDecodableElems(WireCodec::kInt8, bytes, n);
    if (avail < decoded) {
      Fail("WireDecodableElems not monotone", -4);
      return;
    }
    if (avail > decoded) {
      WireDecodeRange(WireCodec::kInt8, enc.data(), decoded, avail,
                      inc.data() + decoded);
      decoded = avail;
    }
  }
  const int64_t tail = WireDecodableElems(
      WireCodec::kInt8, WireEncodedBytes(WireCodec::kInt8, n), n);
  if (tail > decoded) {
    WireDecodeRange(WireCodec::kInt8, enc.data(), decoded, tail,
                    inc.data() + decoded);
    decoded = tail;
  }
  if (decoded != n ||
      std::memcmp(inc.data(), dec.data(), static_cast<size_t>(4 * n)) != 0) {
    Fail("incremental int8 decode diverges from full decode", -4);
  }
}

// fp32 ring accumulation: simulating the reduce-scatter phase (each hop
// contributes decode(encode(x_i)) into an fp32 accumulator), the total
// error stays within hops x the single-quantization bound — the property
// that makes the compressed ring's error linear in ring size instead of
// compounding (re-quantizing partial sums would square it away).
void CodecRingAccumulationBound() {
  std::mt19937 rng(0x5EED);
  std::uniform_real_distribution<float> mag(-10.f, 10.f);
  const int hops = 7;  // ring of 8: 7 reduce-scatter contributions
  const int64_t n = 2 * kWireBlock + 33;
  std::vector<double> exact(static_cast<size_t>(n), 0.0);
  std::vector<float> acc(static_cast<size_t>(n), 0.0f);
  std::vector<double> bound(static_cast<size_t>(n), 0.0);
  std::vector<char> enc(
      static_cast<size_t>(WireEncodedBytes(WireCodec::kInt8, n)));
  std::vector<float> dec(static_cast<size_t>(n));
  for (int h = 0; h < hops; ++h) {
    std::vector<float> x(static_cast<size_t>(n));
    for (auto& v : x) v = mag(rng);
    WireEncode(WireCodec::kInt8, x.data(), n, enc.data());
    WireDecodeRange(WireCodec::kInt8, enc.data(), 0, n, dec.data());
    for (int64_t i = 0; i < n; ++i) {
      exact[i] += x[i];
      acc[i] += dec[i];  // fp32 accumulate of the decoded contribution
    }
    for (int64_t b0 = 0; b0 < n; b0 += kWireBlock) {
      const int64_t bn = std::min(kWireBlock, n - b0);
      float maxabs = 0.f;
      for (int64_t i = 0; i < bn; ++i) {
        maxabs = std::max(maxabs, std::fabs(x[b0 + i]));
      }
      for (int64_t i = 0; i < bn; ++i) {
        bound[b0 + i] += maxabs / 127.0 * 0.5;  // scale/2 per hop
      }
    }
  }
  for (int64_t i = 0; i < n; ++i) {
    // Tiny slack for the fp32 summation itself (7 adds of ~10-magnitude
    // values: machine-epsilon territory next to the quantization bound).
    if (std::fabs(acc[i] - exact[i]) > bound[i] + 1e-4) {
      Fail("ring accumulation error exceeds hops x scale/2", -5);
      return;
    }
  }
}

// int4 block scaling: |decode(encode(x)) - x| <= scale/2 per element with
// scale = blockmax/kWireInt4Max, nibble pack/unpack exact, and the
// incremental consume path agreeing with a full decode byte-for-byte.
void CodecInt4ErrorBound() {
  std::mt19937 rng(0xCAFE);
  std::uniform_real_distribution<float> mag(-50.f, 50.f);
  // Full blocks + an ODD-length partial block (a lone low nibble in the
  // last packed byte), plus an all-zero block.
  const int64_t n = 3 * kWireBlock + 77;
  std::vector<float> src(static_cast<size_t>(n));
  for (auto& v : src) v = mag(rng);
  for (int64_t i = kWireBlock; i < 2 * kWireBlock; ++i) src[i] = 0.0f;
  std::vector<char> enc(
      static_cast<size_t>(WireEncodedBytes(WireCodec::kInt4, n)));
  WireEncode(WireCodec::kInt4, src.data(), n, enc.data());
  std::vector<float> dec(static_cast<size_t>(n));
  WireDecodeRange(WireCodec::kInt4, enc.data(), 0, n, dec.data());
  for (int64_t b0 = 0; b0 < n; b0 += kWireBlock) {
    const int64_t bn = std::min(kWireBlock, n - b0);
    float maxabs = 0.f;
    for (int64_t i = 0; i < bn; ++i) {
      maxabs = std::max(maxabs, std::fabs(src[b0 + i]));
    }
    const float scale = maxabs / static_cast<float>(kWireInt4Max);
    for (int64_t i = 0; i < bn; ++i) {
      if (std::fabs(dec[b0 + i] - src[b0 + i]) > scale * 0.5f + 1e-12f) {
        Fail("int4 block-scale error exceeds scale/2", -4);
        return;
      }
    }
  }
  // Incremental decode across byte-level prefixes (nibble-granular tail).
  int64_t decoded = 0;
  std::vector<float> inc(static_cast<size_t>(n));
  for (int64_t bytes = 0; bytes <= WireEncodedBytes(WireCodec::kInt4, n);
       bytes += 13) {
    const int64_t avail = WireDecodableElems(WireCodec::kInt4, bytes, n);
    if (avail < decoded) {
      Fail("int4 WireDecodableElems not monotone", -4);
      return;
    }
    if (avail > decoded) {
      WireDecodeRange(WireCodec::kInt4, enc.data(), decoded, avail,
                      inc.data() + decoded);
      decoded = avail;
    }
  }
  const int64_t tail = WireDecodableElems(
      WireCodec::kInt4, WireEncodedBytes(WireCodec::kInt4, n), n);
  if (tail > decoded) {
    WireDecodeRange(WireCodec::kInt4, enc.data(), decoded, tail,
                    inc.data() + decoded);
    decoded = tail;
  }
  if (decoded != n ||
      std::memcmp(inc.data(), dec.data(), static_cast<size_t>(4 * n)) != 0) {
    Fail("incremental int4 decode diverges from full decode", -4);
  }
}

// A response names its wire codec by id, and the id is input from outside:
// one past the last codec must come back as an error on the op, not as a
// WireCodec the ring would frame its bytes by.
void ResponseCodecOutOfRange() {
  for (int32_t id : {kWireCodecMax, kWireCodecMax + 1, 255}) {
    Response sent;
    sent.wire_comp = id;
    Writer w;
    SerializeResponse(sent, &w);
    Reader r(w.data());
    const Response got = DeserializeResponse(&r);
    const bool refused = !got.error.empty() && got.wire_comp == 0;
    if (refused != (id > kWireCodecMax)) {
      Fail("wire codec id range check on a response", id);
    }
  }
}

}  // namespace

int main() {
  FuzzRounds();
  HeaderMismatch();
  Cancellation();
  CodecBf16RoundTrip();
  CodecInt8ErrorBound();
  CodecInt4ErrorBound();
  CodecRingAccumulationBound();
  ResponseCodecOutOfRange();
  if (failures.load() != 0) {
    std::fprintf(stderr, "%d failure(s)\n", failures.load());
    return 1;
  }
  std::printf("PASS chunk_exchange_selftest\n");
  return 0;
}
