// Chaos selftest: the full SocketController stack driven with
// HOROVOD_FAULT_INJECT armed, one scenario per named protocol site.
//
// Each scenario asserts the robustness contract of the fast-abort design
// (docs/elastic.md "Failure detection & bounds"): injected drops,
// truncations, and corrupted tags make every rank fail FAST with a
// culprit-naming reason — never hang — while benign injections (delays)
// and healed ones (rendezvous drop + backoff retry) leave results
// bit-correct.  Built plain it is an integration test; built with
// -fsanitize=thread/address/undefined (`make tsan_chaos_selftest` etc.) it
// proves the abort paths themselves are race- and UB-free, which matters
// because they run concurrently with executor lanes mid-collapse.  Run by
// tests/single/test_native_selftests.py.
//
// Hit indices for the data-plane sites are CALIBRATED, not hardcoded: a
// clean run with a never-firing rule armed counts how many times each site
// fires during Initialize (the shm-verdict handshake runs barrier fences
// even when shm is disabled), and later scenarios target `base + 0`, the
// first post-init hit.  This keeps the selftest correct when the init
// handshake gains or loses a fence.

#include <atomic>
#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <string>
#include <thread>
#include <vector>

#include "fault_injection.h"
#include "flight_recorder.h"
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

std::atomic<int> failures{0};

void Fail(const char* scenario, int rank, const std::string& what) {
  std::fprintf(stderr, "FAIL [%s] rank %d: %s\n", scenario, rank,
               what.c_str());
  failures.fetch_add(1);
}

struct RankOutcome {
  bool init_ok = false;
  bool completed = false;  // every cycle finished cleanly
  std::string reason;      // abort reason (failure paths) / init error
  double handshake_s = 0;  // failed data op -> reason latched
  int64_t base_hits[kNumFaultSites] = {0};  // own-slot hits after init
};

// One in-process rank.  The failure path mirrors core_api.cc exactly: a
// failed data op is followed by one more ComputeResponses (the abort
// handshake — worker FIN / coordinator sweep + broadcast), and the reason
// the Python layer would surface comes from WaitAbortReason().
void ChaosRank(const char* scenario, int rank, int size, int port, int cycles,
               bool do_barrier, RankOutcome* out) {
  CoreConfig cfg;
  cfg.rank = rank;
  cfg.size = size;
  cfg.rendezvous_addr = "127.0.0.1";
  cfg.rendezvous_port = port;
  SocketController ctl(cfg);
  Status s = ctl.Initialize();
  if (!s.ok()) {
    out->reason = s.reason;
    return;
  }
  out->init_ok = true;
  auto& inj = GlobalFaultInjector();
  for (int site = 0; site < kNumFaultSites; ++site) {
    out->base_hits[site] =
        inj.hits[site][rank].load(std::memory_order_relaxed);
  }
  for (int cycle = 0; s.ok() && cycle < cycles; ++cycle) {
    TensorRequest req;
    req.name = "c" + std::to_string(cycle);
    req.op = OpType::ALLREDUCE;
    req.dtype = DataType::FLOAT32;
    req.nbytes = 1024 * 4;
    req.shape = {1024};
    std::vector<TensorRequest> reqs{req};
    std::vector<Response> resps;
    s = ctl.ComputeResponses(reqs, &resps);
    for (size_t i = 0; s.ok() && i < resps.size(); ++i) {
      Response& r = resps[i];
      if (!r.error.empty()) {
        s = Status::Error(StatusCode::ABORTED, r.error);
        break;
      }
      ctl.SetCurrentSeq(r.seq);
      std::vector<float> buf(1024, static_cast<float>(rank + 1));
      s = ctl.AllreduceBuffer(buf.data(), 1024, DataType::FLOAT32,
                              ReduceOp::SUM, 0);
      const float want = static_cast<float>(size * (size + 1) / 2);
      if (s.ok() && (buf[0] != want || buf[1023] != want)) {
        Fail(scenario, rank, "wrong allreduce result");
        s = Status::Error(StatusCode::ABORTED, "wrong allreduce result");
      }
      if (s.ok() && do_barrier) s = ctl.Barrier(0);
    }
  }
  if (s.ok()) {
    ctl.Farewell();
    ctl.Shutdown();
    out->completed = true;
    return;
  }
  const double t0 = MonotonicSeconds();
  std::vector<TensorRequest> none;
  std::vector<Response> ignored;
  ctl.ComputeResponses(none, &ignored);
  out->reason = ctl.WaitAbortReason();
  if (out->reason.empty()) out->reason = s.reason;
  out->handshake_s = MonotonicSeconds() - t0;
  ctl.Shutdown();
}

std::vector<RankOutcome> RunScenario(const char* name, const std::string& spec,
                                     int cycles, bool do_barrier,
                                     int size = kRanks) {
  std::vector<RankOutcome> out(size);
  ::setenv("HOROVOD_FAULT_INJECT", spec.c_str(), 1);
  std::string err = InitFaultInjection();
  if (!err.empty()) {
    Fail(name, -1, "unexpected spec error: " + err);
    return out;
  }
  int port = ClaimFreePort();
  if (port < 0) {
    Fail(name, -1, "no free port");
    return out;
  }
  std::vector<std::thread> threads;
  threads.reserve(size);
  for (int r = 0; r < size; ++r) {
    threads.emplace_back(ChaosRank, name, r, size, port, cycles, do_barrier,
                         &out[r]);
  }
  for (auto& t : threads) t.join();
  return out;
}

void ExpectAllAborted(const char* name,
                      const std::vector<RankOutcome>& out,
                      double bound_s) {
  for (int r = 0; r < static_cast<int>(out.size()); ++r) {
    if (out[r].completed) {
      Fail(name, r, "completed cleanly despite the injected fault");
    } else if (out[r].reason.empty()) {
      Fail(name, r, "aborted without a reason");
    } else if (out[r].init_ok && out[r].handshake_s > bound_s) {
      Fail(name, r,
           "abort handshake took " + std::to_string(out[r].handshake_s) +
               "s (bound " + std::to_string(bound_s) + "s)");
    }
  }
}

}  // namespace

int main() {
  // Force the TCP ring so the ring-send/ring-recv/frame-header sites are
  // on the data path (the shm handshake still runs and votes no), shrink
  // the abort bound and rendezvous backoff to keep the run fast, and keep
  // metrics ON so the abort counters/histogram are exercised concurrently
  // with the collapsing planes (what the sanitizer builds must prove safe).
  ::setenv("HOROVOD_SHM_DISABLE", "1", 1);
  ::setenv("HOROVOD_ABORT_PROPAGATION_TIMEOUT", "1", 1);
  ::setenv("HOROVOD_RENDEZVOUS_BACKOFF_BASE_MS", "10", 1);
  GlobalMetrics().enabled.store(true, std::memory_order_relaxed);

  // --- spec parser: valid accepted, malformed rejected with a message ----
  if (!ParseFaultSpec("ring-send:*:1:delay:250,frame-header:3:0:corrupt-tag",
                      nullptr)
           .empty()) {
    Fail("parse", -1, "valid spec rejected");
  }
  const char* bad[] = {
      "nosite:*:*:drop",        "ring-send:*:*",
      "ring-send:x:*:drop",     "ring-send:*:x:drop",
      "ring-send:*:*:explode",  "ring-send:*:*:delay",
      "ring-send:*:*:drop:arg",
  };
  for (const char* b : bad) {
    if (ParseFaultSpec(b, nullptr).empty()) {
      Fail("parse", -1, std::string("malformed spec accepted: ") + b);
    }
  }

  // --- calibration: armed-but-never-firing rule, clean lockstep run ------
  auto cal = RunScenario("calibrate", "frame-header:200000000:0:drop",
                         /*cycles=*/3, /*do_barrier=*/true);
  for (int r = 0; r < kRanks; ++r) {
    if (!cal[r].completed) {
      Fail("calibrate", r, "did not complete: " + cal[r].reason);
    }
  }
  if (failures.load() != 0) {
    std::printf("FAIL (%d)\n", failures.load());
    return 1;
  }
  const int64_t rs1 = cal[1].base_hits[kFaultRingSend];
  const int64_t rr2 = cal[2].base_hits[kFaultRingRecv];
  const int64_t fh1 = cal[1].base_hits[kFaultFrameHeader];
  const int64_t sf1 = cal[1].base_hits[kFaultShmFence];
  if (rs1 <= 0 || fh1 <= 0) {
    Fail("calibrate", 1, "init fences never hit the ring/frame hooks");
  }

  // --- rendezvous-accept drop: the worker's backoff retry heals it -------
  auto rz = RunScenario("rendezvous", "rendezvous-accept:0:1:drop",
                        /*cycles=*/2, /*do_barrier=*/false);
  for (int r = 0; r < kRanks; ++r) {
    if (!rz[r].completed) {
      Fail("rendezvous", r, "did not recover from the dropped HELLO: " +
                                rz[r].reason);
    }
  }

  // --- delay: benign, results stay bit-correct, counter observes it ------
  const int64_t faults_before =
      GlobalMetrics().faults_injected_total.load(std::memory_order_relaxed);
  auto dl = RunScenario(
      "delay", "ring-send:" + std::to_string(rs1) + ":1:delay:100",
      /*cycles=*/2, /*do_barrier=*/false);
  for (int r = 0; r < kRanks; ++r) {
    if (!dl[r].completed) {
      Fail("delay", r, "delay injection broke the job: " + dl[r].reason);
    }
  }
  if (GlobalMetrics().faults_injected_total.load(std::memory_order_relaxed) <=
      faults_before) {
    Fail("delay", -1, "faults_injected_total never incremented");
  }

  // --- corrupt-tag: every rank fails fast, bounded, no hang --------------
  ExpectAllAborted(
      "corrupt-tag",
      RunScenario("corrupt-tag",
                  "frame-header:" + std::to_string(fh1) + ":1:corrupt-tag",
                  /*cycles=*/2, /*do_barrier=*/false),
      /*bound_s=*/6.0);

  // --- ring-recv drop: dead data socket mid-ring -------------------------
  ExpectAllAborted(
      "ring-recv",
      RunScenario("ring-recv",
                  "ring-recv:" + std::to_string(rr2) + ":2:drop",
                  /*cycles=*/2, /*do_barrier=*/false),
      /*bound_s=*/6.0);

  // --- coordinator-recv drop: the ABORT broadcast names the culprit ------
  const int64_t prop_before =
      GlobalMetrics().abort_propagation_us.count.load(
          std::memory_order_relaxed);
  auto cd = RunScenario("coordinator-recv", "coordinator-recv:0:1:drop",
                        /*cycles=*/2, /*do_barrier=*/false);
  ExpectAllAborted("coordinator-recv", cd, /*bound_s=*/6.0);
  if (cd[2].init_ok && cd[2].reason.find("rank 1") == std::string::npos) {
    Fail("coordinator-recv", 2,
         "survivor's reason does not name the culprit: " + cd[2].reason);
  }
  if (GlobalMetrics().abort_propagation_us.count.load(
          std::memory_order_relaxed) <= prop_before) {
    Fail("coordinator-recv", -1,
         "abort_propagation_us never observed the broadcast latency");
  }

  // --- shm-fence drop: the dissemination fence collapses -----------------
  ExpectAllAborted(
      "shm-fence",
      RunScenario("shm-fence",
                  "shm-fence:" + std::to_string(sf1) + ":1:drop",
                  /*cycles=*/2, /*do_barrier=*/true),
      /*bound_s=*/6.0);

  // --- leader-recv drop: v9 leader tree, a host leader (NOT the
  // coordinator) loses its child mid-cycle.  np=4 over 2 fake hosts puts
  // ranks {2,3} on host 1 with rank 2 as their leader; dropping child 3's
  // cycle frame at leader 2 kills that link, the leader's FIN climbs to
  // the coordinator with the culprit, and every rank — including the
  // orphaned child, which drains the direct ABORT off its coordinator
  // link — aborts bounded with rank 3 named through the tree.
  ::setenv("HOROVOD_HIER_FAKE_HOSTS", "2", 1);
  ::setenv("HOROVOD_CONTROL_TREE", "on", 1);
  auto lr = RunScenario("leader-recv", "leader-recv:0:3:drop",
                        /*cycles=*/2, /*do_barrier=*/false, /*size=*/4);
  ::unsetenv("HOROVOD_CONTROL_TREE");
  ::unsetenv("HOROVOD_HIER_FAKE_HOSTS");
  ExpectAllAborted("leader-recv", lr, /*bound_s=*/6.0);
  if (lr[1].init_ok && lr[1].reason.find("rank 3") == std::string::npos) {
    Fail("leader-recv", 1,
         "worker on the healthy host does not name the culprit through "
         "the tree: " + lr[1].reason);
  }
  if (lr[3].init_ok && lr[3].reason.empty()) {
    Fail("leader-recv", 3, "orphaned child aborted without a reason");
  }

  // --- migration: forensic planes written concurrently with a collapse --
  // A hammer thread drives NoteMigration (replication refreshes plus a
  // migration's manifest/transfer/reassemble phases) while an injected
  // ring drop collapses the job.  The sanitizer builds prove the type-14
  // flight path and the hvd_migrate_* counters are race- and UB-free
  // against the abort machinery (exactly the moment a real migration
  // observes); the plain build asserts the events landed with the
  // documented a/b encoding.
  InitFlightRecorder(true, 4096, "", 0);
  const int64_t mig_before =
      GlobalMetrics().migrate_events_total.load(std::memory_order_relaxed);
  std::atomic<bool> mig_stop{false};
  std::thread mig_hammer([&mig_stop] {
    int64_t n = 0;
    while (!mig_stop.load(std::memory_order_relaxed)) {
      NoteMigration(kMigrateReplicate, 4096, -1);
      NoteMigration(kMigrateManifest, 3, -1);
      NoteMigration(kMigrateTransfer, 4096, static_cast<int>(n % kRanks));
      NoteMigration(kMigrateReassemble, 4096, 1);
      ++n;
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
    }
  });
  ExpectAllAborted(
      "migrate",
      RunScenario("migrate", "ring-recv:" + std::to_string(rr2) + ":2:drop",
                  /*cycles=*/2, /*do_barrier=*/false),
      /*bound_s=*/6.0);
  mig_stop.store(true);
  mig_hammer.join();
  NoteMigration(kMigrateFallback, 0, -1);
  MetricsRegistry& mm = GlobalMetrics();
  if (mm.migrate_events_total.load(std::memory_order_relaxed) <= mig_before) {
    Fail("migrate", -1, "migrate_events_total never advanced");
  }
  if (mm.migrate_bytes_total.load(std::memory_order_relaxed) <= 0) {
    Fail("migrate", -1, "migrate_bytes_total never accumulated");
  }
  if (mm.migrate_fallbacks_total.load(std::memory_order_relaxed) < 1) {
    Fail("migrate", -1, "migrate_fallbacks_total missed the fallback");
  }
  std::vector<FlightEvent> mig_tail;
  FlightTail(4096, &mig_tail);
  bool saw_transfer = false;
  for (const FlightEvent& e : mig_tail) {
    if (e.type != kFlightMigrate) continue;
    const int phase = e.a >> 8;
    const int src = (e.a & 0xFF) - 1;
    if (phase < kMigrateReplicate || phase > kMigrateFallback) {
      Fail("migrate", -1, "type-14 event with out-of-range phase " +
                              std::to_string(phase));
    }
    if (phase == kMigrateTransfer && src >= 0 && e.b == 4096) {
      saw_transfer = true;
    }
  }
  if (!saw_transfer) {
    Fail("migrate", -1, "no transfer-phase type-14 event recorded");
  }
  ResetFlightRecorderForTest();

  ::unsetenv("HOROVOD_FAULT_INJECT");
  InitFaultInjection();
  if (failures.load() != 0) {
    std::printf("FAIL (%d)\n", failures.load());
    return 1;
  }
  std::printf("PASS\n");
  return 0;
}
