// The rendezvous port of a selftest phase, drawn the way
// runner/util.py:find_free_port draws a launcher's (and in step with it:
// the claim below is the same abstract unix socket).
//
// A selftest hands one port to hundreds of rank threads, and the coordinator
// among them binds it a moment later.  A port the kernel gave out for
// bind(0) and took back is anyone's in between: another process's bind(0)
// or outgoing connection lands on it, rank 0 cannot bind, and every other
// rank waits out its connect budget.  So the port lies below the kernel's
// ephemeral range, where neither can land; the walk starts at a point
// spread by the pid, so that neighbouring processes seldom meet; and a port
// is claimed before it is returned, so that where they meet the second
// goes on.  A claim lasts as long as its process.
//
// Header-only, like wire_codec.h: the selftests link it without an object.

#ifndef HVD_TPU_SELFTEST_PORT_H_
#define HVD_TPU_SELFTEST_PORT_H_

#include <netinet/in.h>
#include <sys/socket.h>
#include <sys/un.h>
#include <unistd.h>

#include <cstddef>
#include <cstdint>
#include <cstdio>
#include <mutex>

namespace hvdtpu {

// -1 when nothing below the ephemeral range can be claimed.
inline int ClaimFreePort() {
  // runner/util.py's _PORT_FLOOR and _PORT_STRIDE.
  constexpr int64_t kFloor = 10000, kStride = 7;
  static std::mutex mu;
  static int64_t cursor = -1;
  std::lock_guard<std::mutex> lock(mu);
  int low = 32768;
  if (FILE* f = std::fopen("/proc/sys/net/ipv4/ip_local_port_range", "r")) {
    int v;
    if (std::fscanf(f, "%d", &v) == 1) low = v;
    std::fclose(f);
  }
  const int64_t span = low - kFloor;
  if (cursor < 0 && span > 0) {
    // The golden-ratio sequence: neighbouring pids start far apart.
    const uint64_t pid = static_cast<uint64_t>(::getpid());
    cursor = static_cast<int64_t>(
        ((pid * 2654435761ULL) % (1ULL << 32)) * span >> 32);
  }
  for (int64_t tried = 0; tried < span; ++tried) {
    const int port = static_cast<int>(kFloor + cursor % span);
    cursor = cursor % span + kStride;
    const int claim = ::socket(AF_UNIX, SOCK_STREAM | SOCK_CLOEXEC, 0);
    if (claim < 0) return -1;
    sockaddr_un name{};
    name.sun_family = AF_UNIX;
    const int len = std::snprintf(name.sun_path + 1, sizeof(name.sun_path) - 1,
                                  "horovod_tpu.port.%d", port);
    if (::bind(claim, reinterpret_cast<sockaddr*>(&name),
               static_cast<socklen_t>(offsetof(sockaddr_un, sun_path) + 1 +
                                      len)) != 0) {
      ::close(claim);  // a live process was handed this port
      continue;
    }
    const int probe = ::socket(AF_INET, SOCK_STREAM | SOCK_CLOEXEC, 0);
    sockaddr_in sa{};
    sa.sin_family = AF_INET;
    sa.sin_port = htons(static_cast<uint16_t>(port));
    sa.sin_addr.s_addr = htonl(INADDR_ANY);  // where the coordinator binds
    const bool is_free =
        probe >= 0 &&
        ::bind(probe, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) == 0;
    if (probe >= 0) ::close(probe);
    if (!is_free) {
      std::fprintf(stderr, "selftest port %d is taken: trying the next\n",
                   port);
      ::close(claim);
      continue;
    }
    return port;  // `claim` stays open until the process ends
  }
  return -1;
}

}  // namespace hvdtpu

#endif  // HVD_TPU_SELFTEST_PORT_H_
