// In-process multi-rank controller selftest: negotiation + ring data plane
// + join + clean shutdown, with every rank on its own thread.
//
// Reference analog (SURVEY.md §5 "race detection"): the reference's thread
// safety is by design (single background thread owns comm state) and
// validated under load; this harness makes that checkable mechanically —
// built plain it is a C++ integration test, built with -fsanitize=thread
// (`make tsan_selftest`) it is the race detector over the controller,
// socket, and duplex-exchange paths.  Run by tests/single/test_tsan.py.

#include <atomic>
#include <cstdio>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

#include "metrics.h"
#include "selftest_port.h"
#include "socket_controller.h"

namespace hvdtpu {
int GetLogLevel() { return 4; }  // errors only
void SetLogLevel(int) {}
}  // namespace hvdtpu

using namespace hvdtpu;

namespace {

constexpr int kRanks = 3;
constexpr int kCycles = 25;

std::atomic<int> failures{0};

void Fail(const char* what, int rank) {
  std::fprintf(stderr, "FAIL rank %d: %s\n", rank, what);
  failures.fetch_add(1);
}

void RankMain(int rank, int port) {
  CoreConfig cfg;
  cfg.rank = rank;
  cfg.size = kRanks;
  cfg.rendezvous_addr = "127.0.0.1";
  cfg.rendezvous_port = port;
  SocketController ctl(cfg);
  Status s = ctl.Initialize();
  if (!s.ok()) return Fail(s.reason.c_str(), rank);

  for (int cycle = 0; cycle < kCycles; ++cycle) {
    TensorRequest req;
    req.name = "t" + std::to_string(cycle);
    req.op = OpType::ALLREDUCE;
    req.dtype = DataType::FLOAT32;
    req.nbytes = 1024 * 4;
    req.shape = {1024};
    std::vector<TensorRequest> reqs{req};
    std::vector<Response> resps;
    s = ctl.ComputeResponses(reqs, &resps);
    if (!s.ok()) return Fail(s.reason.c_str(), rank);
    for (auto& r : resps) {
      if (!r.error.empty()) return Fail(r.error.c_str(), rank);
      ctl.SetCurrentSeq(r.seq);
      std::vector<float> buf(1024, static_cast<float>(rank + 1));
      s = ctl.AllreduceBuffer(buf.data(), 1024, DataType::FLOAT32,
                              ReduceOp::SUM, 0);
      if (!s.ok()) return Fail(s.reason.c_str(), rank);
      if (buf[0] != 6.0f || buf[1023] != 6.0f) {
        return Fail("wrong allreduce result", rank);
      }
      s = ctl.Barrier(0);
      if (!s.ok()) return Fail(s.reason.c_str(), rank);
    }
    // Empty cycles interleave (the steady state of a real job).
    std::vector<TensorRequest> none;
    s = ctl.ComputeResponses(none, &resps);
    if (!s.ok()) return Fail(s.reason.c_str(), rank);
  }
  ctl.Farewell();
  ctl.Shutdown();
}

}  // namespace

int main() {
  // Pick a free port for the rendezvous.
  const int port = ClaimFreePort();
  if (port < 0) {
    std::fprintf(stderr, "no free port\n");
    return 2;
  }
  // Metrics stay ON for the whole run: the rank threads increment the
  // global registry (ring hops from ChunkedStep, shm fence waits from
  // SockBarrier's >= kTagShmSize tags) while a dumper thread concurrently
  // snapshots it — the increment-while-dump and fence-observe paths the
  // TSan build must prove race-free.  The registry is relaxed atomics end
  // to end, so zero reports is the designed outcome, not luck.
  GlobalMetrics().enabled.store(true, std::memory_order_relaxed);
  std::atomic<bool> stop_dumper{false};
  std::atomic<long long> dumps{0};
  std::thread dumper([&] {
    while (!stop_dumper.load(std::memory_order_relaxed)) {
      std::string json = GlobalMetrics().DumpJson(/*rank=*/0, "");
      if (json.empty() || json.front() != '{' || json.back() != '}' ||
          json.find("\"shm_fence_us\"") == std::string::npos) {
        Fail("malformed concurrent metrics dump", -1);
        return;
      }
      dumps.fetch_add(1, std::memory_order_relaxed);
    }
  });
  std::vector<std::thread> threads;
  threads.reserve(kRanks);
  for (int r = 0; r < kRanks; ++r) {
    threads.emplace_back(RankMain, r, port);
  }
  for (auto& t : threads) t.join();
  stop_dumper.store(true, std::memory_order_relaxed);
  dumper.join();
  if (dumps.load() == 0) Fail("dumper thread never completed a dump", -1);
  // The data plane must have observed latency somewhere: shm fences when
  // the same-host shm plane engaged, ring hops when it fell back to TCP.
  const auto observed =
      GlobalMetrics().shm_fence_us.count.load(std::memory_order_relaxed) +
      GlobalMetrics().ring_hop_us.count.load(std::memory_order_relaxed);
  if (observed == 0) Fail("metrics-enabled run observed no fence/hop", -1);
  if (failures.load() != 0) {
    std::printf("FAIL (%d)\n", failures.load());
    return 1;
  }
  std::printf("PASS\n");
  return 0;
}
