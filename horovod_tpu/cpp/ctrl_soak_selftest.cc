// Control-plane soak: np in-process ranks (threads + loopback sockets)
// driving the negotiation lock-step with CoreConfig.ctrl_only, which skips
// the O(n^2) data mesh / shm / hierarchy so one machine can hold np=1024.
//
// Default geometry is np=256 over 16 fake hosts (HOROVOD_HIER_FAKE_HOSTS);
// CTRL_SOAK_NP=1024 CTRL_SOAK_HOSTS=64 is the pod-scale acceptance row.
// The arm grid covers the v12 adaptive-depth tree end to end:
//
//   flat / tree       coordinator msgs/cycle drops O(n) -> O(fanout): flat
//                     is >= 8x tree, and tree inbound matches the model of
//                     ComputeCtrlTree exactly (auto depth).
//   tree+d2 / tree+d3 forced HOROVOD_CONTROL_TREE_DEPTH shapes: depth 2 is
//                     bit-identical to the v9 two-level tree, depth 3
//                     inserts super-leaders and keeps coordinator fan-in
//                     <= fanout + local slack.
//   tree+migrate      np concurrent NoteMigration writers against the live
//                     plane leave the msgs/cycle shape unperturbed.
//   tree+sketch       fleet-telemetry sketches at the auto depth: exactly
//                     one stored source per direct child, and the fleet
//                     sum stays within the replace-not-add bound.
//   tree+churn        tenant churn: every rank re-registers a fresh
//                     process set each cycle and retires last cycle's,
//                     with requests riding the churned set.
//   tree+evict        autopilot-style eviction mid-soak: one whole host
//                     (leader + workers) departs cleanly between cycles at
//                     depth 3; survivors renegotiate on a survivor set and
//                     finish — the BYE-releases-the-subtree contract.
//   chaos+*           fault-injected death at every tree level (worker,
//                     mid-level leader via the v12 super-recv site, super-
//                     leader, and the depth-2 host leader): every rank
//                     aborts bounded and survivors outside the dead branch
//                     name the exact culprit rank + host.
//
// Rendezvous runs with HOROVOD_RENDEZVOUS_ACCEPTORS=8 so the HELLO herd
// also soaks the sharded acceptor path.  Built with the sanitizer matrix
// (`make tsan_ctrl_soak_selftest` etc.) this proves the leader cycle,
// super-leader aggregate merge, abort relay, and counter paths race-free
// at scale.  CTRL_SOAK_ARMS=pod trims to the acceptance-critical arms
// (adaptive shape, sketch merge, mid-level death) for the TSan pod row.
// Run by tests/single/test_native_selftests.py and `make selftest`.

#include <sys/resource.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstdio>
#include <cstdlib>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include "fleet_telemetry.h"
#include "fault_injection.h"
#include "metrics.h"
#include "selftest_port.h"
#include "socket_controller.h"

namespace hvdtpu {
int GetLogLevel() { return 4; }  // errors only
void SetLogLevel(int) {}
}  // namespace hvdtpu

using namespace hvdtpu;

namespace {

int failures = 0;

void Fail(const char* phase, int rank, const std::string& what) {
  std::fprintf(stderr, "FAIL [%s] rank %d: %s\n", phase, rank, what.c_str());
  ++failures;
}

// When set, every rank notes one replication refresh per negotiation cycle
// — the soak's migration-aware row: np concurrent NoteMigration writers
// against the live control plane.
std::atomic<bool> g_migrate{false};
// When set, every rank registers a fresh process set at the top of each
// cycle, announces on it, and removes the previous cycle's set — the
// tenant-churn row (per-rank tables mutate symmetrically, so ids agree).
std::atomic<bool> g_churn{false};
// When set, every rank seeds one negotiation-wait observation before the
// first cycle, so fleet sketches carry real counts (the soak bypasses the
// core_api queue where the histogram is normally fed).
std::atomic<bool> g_observe{false};

// Mirror of ComputeCtrlTree's host grouping + clustering pass (pure
// function of the geometry), so every arm can compute the coordinator's
// expected fan-in and pick chaos targets without asking the controller.
struct TreeModel {
  std::vector<int> leaders;      // first rank of each fake host
  std::map<int, int> parent_of;  // non-root leader -> parent (0 = coord)
  int depth = 2;
  int coord_children = 0;  // host-0 workers + coordinator's agg children
};

TreeModel ModelTree(int np, int hosts, int fanout, int forced_depth) {
  TreeModel m;
  const int per = np / hosts;
  for (int h = 0; h < hosts; ++h) m.leaders.push_back(h * per);
  std::vector<int> top = m.leaders;
  int levels = 1;
  while (true) {
    const int non_root = static_cast<int>(top.size()) - 1;
    const bool grow = (forced_depth > 0)
                          ? (levels < forced_depth - 1 && non_root > 1)
                          : (non_root > fanout);
    if (!grow) break;
    const int n_clusters = (non_root + fanout - 1) / fanout;
    std::vector<int> next = {0};
    for (int c = 0; c < n_clusters; ++c) {
      const int lo = 1 + static_cast<int>(
                             static_cast<int64_t>(c) * non_root / n_clusters);
      const int hi = 1 + static_cast<int>(static_cast<int64_t>(c + 1) *
                                          non_root / n_clusters);
      const int head = top[lo];
      next.push_back(head);
      for (int i = lo + 1; i < hi; ++i) m.parent_of[top[i]] = head;
    }
    top.swap(next);
    ++levels;
  }
  for (size_t i = 1; i < top.size(); ++i) m.parent_of[top[i]] = 0;
  m.depth = levels + 1;
  m.coord_children = (per - 1) + (static_cast<int>(top.size()) - 1);
  return m;
}

void SetDepthEnv(int depth) {
  if (depth <= 0) {
    ::unsetenv("HOROVOD_CONTROL_TREE_DEPTH");
  } else {
    ::setenv("HOROVOD_CONTROL_TREE_DEPTH", std::to_string(depth).c_str(), 1);
  }
}

// Reusable rendezvous-style barrier: the main thread participates so it can
// snapshot the coordinator's counters while every rank thread is parked
// between negotiation phases (no cycle in flight).
class Barrier {
 public:
  explicit Barrier(int n) : n_(n) {}
  void Wait() {
    std::unique_lock<std::mutex> lk(mu_);
    const int gen = gen_;
    if (++count_ == n_) {
      count_ = 0;
      ++gen_;
      cv_.notify_all();
    } else {
      cv_.wait(lk, [&] { return gen != gen_; });
    }
  }

 private:
  std::mutex mu_;
  std::condition_variable cv_;
  const int n_;
  int count_ = 0;
  int gen_ = 0;
};

struct Phase {
  Barrier init, start, done, exit_;
  explicit Phase(int n) : init(n), start(n), done(n), exit_(n) {}
};

// One lock-step allreduce negotiation on `ctl`; "" on success.
std::string OneCycle(SocketController* ctl, const std::string& name,
                     int psid) {
  TensorRequest req;
  req.name = name;
  req.op = OpType::ALLREDUCE;
  req.dtype = DataType::FLOAT32;
  req.nbytes = 4 * 16;
  req.shape = {16};
  req.process_set_id = psid;
  std::vector<TensorRequest> reqs{req};
  std::vector<Response> resps;
  Status s = ctl->ComputeResponses(reqs, &resps);
  if (!s.ok()) return s.reason;
  if (resps.size() != 1 || !resps[0].error.empty()) {
    return resps.empty() ? "no response" : "bad response: " + resps[0].error;
  }
  return "";
}

void SoakRank(const char* phase_name, int rank, int size, int port,
              int cycles, Phase* ph, SocketController** slot,
              std::string* err) {
  CoreConfig cfg;
  cfg.rank = rank;
  cfg.size = size;
  cfg.rendezvous_addr = "127.0.0.1";
  cfg.rendezvous_port = port;
  cfg.ctrl_only = true;
  SocketController ctl(cfg);
  *slot = &ctl;
  Status s = ctl.Initialize();
  if (!s.ok()) {
    *err = "init: " + s.reason;
    *slot = nullptr;
  }
  if (g_observe.load(std::memory_order_relaxed)) {
    GlobalMetrics().negotiation_wait_us.ObserveUs(100 + rank % 7);
  }
  ph->init.Wait();
  ph->start.Wait();
  if (err->empty()) {
    std::vector<int> world(size);
    for (int r = 0; r < size; ++r) world[r] = r;
    int prev_psid = -1;
    for (int cycle = 0; cycle < cycles; ++cycle) {
      int psid = 0;
      if (g_churn.load(std::memory_order_relaxed)) {
        // Tenant churn: register this cycle's set before announcing on it,
        // retire the previous cycle's after.  Every rank runs the same
        // sequence, so the per-rank tables assign identical ids.
        psid = ctl.process_sets().Add(world);
      }
      std::string e =
          OneCycle(&ctl, "soak" + std::to_string(cycle), psid);
      if (!e.empty()) {
        *err = "cycle " + std::to_string(cycle) + ": " + e;
        break;
      }
      if (g_churn.load(std::memory_order_relaxed)) {
        if (prev_psid > 0) ctl.process_sets().Remove(prev_psid);
        prev_psid = psid;
      }
      if (g_migrate.load(std::memory_order_relaxed)) {
        NoteMigration(kMigrateReplicate, 4 * 16, -1);
      }
    }
  }
  ph->done.Wait();
  ph->exit_.Wait();
  if (err->empty()) ctl.Farewell();
  ctl.Shutdown();
  *slot = nullptr;
}

// Runs one negotiation phase at `size` ranks and returns the coordinator's
// inbound control messages per cycle (measured between two full-quiescence
// barriers, so rendezvous and farewell traffic never pollute the number).
// `fleet_sources` / `fleet_sum_count`, when non-null, receive the
// coordinator's stored fleet-sketch source count and live fleet-sum
// negotiation count at the same quiescent point.
int64_t RunPhase(const char* name, const char* tree_mode, int size,
                 int cycles, int* fleet_sources = nullptr,
                 int64_t* fleet_sum_count = nullptr) {
  ::setenv("HOROVOD_CONTROL_TREE", tree_mode, 1);
  const int port = ClaimFreePort();
  if (port < 0) {
    Fail(name, -1, "no free port");
    return -1;
  }
  Phase ph(size + 1);
  std::vector<SocketController*> ctls(size, nullptr);
  std::vector<std::string> errs(size);
  std::vector<std::thread> threads;
  threads.reserve(size);
  for (int r = 0; r < size; ++r) {
    threads.emplace_back(SoakRank, name, r, size, port, cycles, &ph,
                         &ctls[r], &errs[r]);
  }
  ph.init.Wait();
  int64_t ms0 = 0, mr0 = 0, bs0 = 0, br0 = 0;
  if (ctls[0]) ctls[0]->CtrlPlaneStats(&ms0, &mr0, &bs0, &br0);
  ph.start.Wait();
  ph.done.Wait();
  int64_t ms1 = 0, mr1 = 0, bs1 = 0, br1 = 0;
  if (ctls[0]) ctls[0]->CtrlPlaneStats(&ms1, &mr1, &bs1, &br1);
  if (fleet_sources != nullptr && ctls[0]) {
    *fleet_sources = ctls[0]->FleetSourceCountForTest();
  }
  if (fleet_sum_count != nullptr && ctls[0]) {
    *fleet_sum_count = ctls[0]->FleetSumNegCountForTest();
  }
  ph.exit_.Wait();
  for (auto& t : threads) t.join();
  for (int r = 0; r < size; ++r) {
    if (!errs[r].empty()) Fail(name, r, errs[r]);
  }
  if (failures != 0) return -1;
  const int64_t recv_per_cycle = (mr1 - mr0) / cycles;
  std::printf(
      "[%s] np=%d cycles=%d coordinator: recv %lld msgs/cycle "
      "(%lld bytes/cycle), sent %lld msgs/cycle\n",
      name, size, cycles, static_cast<long long>(recv_per_cycle),
      static_cast<long long>((br1 - br0) / cycles),
      static_cast<long long>((ms1 - ms0) / cycles));
  return recv_per_cycle;
}

// ---------------------------------------------------------------------------
// Eviction arm: one whole fake host departs cleanly between cycles.
// ---------------------------------------------------------------------------

// Rank body for the eviction phase: everyone runs `pre` cycles on the
// global set; evicted ranks then Farewell (the autopilot's eviction is a
// clean departure) while survivors run `post` more cycles on a pre-agreed
// survivor process set.
void EvictRank(int rank, int size, int port, int pre, int post,
               int evict_host_lo, int evict_host_hi, Phase* ph,
               std::string* err) {
  CoreConfig cfg;
  cfg.rank = rank;
  cfg.size = size;
  cfg.rendezvous_addr = "127.0.0.1";
  cfg.rendezvous_port = port;
  cfg.ctrl_only = true;
  SocketController ctl(cfg);
  Status s = ctl.Initialize();
  if (!s.ok()) *err = "init: " + s.reason;
  const bool evicted = rank >= evict_host_lo && rank < evict_host_hi;
  int surv_psid = -1;
  if (err->empty()) {
    // Survivor set registered up front on EVERY rank (symmetric
    // registration is the process-set contract), so post-eviction cycles
    // have a set whose readiness never waits on departed ranks.
    std::vector<int> survivors;
    for (int r = 0; r < size; ++r) {
      if (r < evict_host_lo || r >= evict_host_hi) survivors.push_back(r);
    }
    surv_psid = ctl.process_sets().Add(survivors);
  }
  ph->init.Wait();
  ph->start.Wait();
  if (err->empty()) {
    for (int c = 0; c < pre && err->empty(); ++c) {
      std::string e = OneCycle(&ctl, "soak" + std::to_string(c), 0);
      if (!e.empty()) *err = "pre cycle " + std::to_string(c) + ": " + e;
    }
    if (err->empty() && evicted) {
      // Clean mid-soak departure: BYE up the tree.  The leader's own BYE
      // releases the whole subtree at the coordinator, so workers' BYEs
      // left unread by their departing leader cannot wedge survivors.
      ctl.Farewell();
    }
    if (!evicted) {
      for (int c = 0; c < post && err->empty(); ++c) {
        std::string e =
            OneCycle(&ctl, "surv" + std::to_string(c), surv_psid);
        if (!e.empty()) *err = "post cycle " + std::to_string(c) + ": " + e;
      }
    }
  }
  ph->done.Wait();
  ph->exit_.Wait();
  if (err->empty() && !evicted) ctl.Farewell();
  ctl.Shutdown();
}

void RunEvictPhase(const char* name, int size, int hosts, int evict_host) {
  ::setenv("HOROVOD_CONTROL_TREE", "on", 1);
  const int port = ClaimFreePort();
  if (port < 0) {
    Fail(name, -1, "no free port");
    return;
  }
  const int per = size / hosts;
  const int lo = evict_host * per, hi = lo + per;
  Phase ph(size + 1);
  std::vector<std::string> errs(size);
  std::vector<std::thread> threads;
  threads.reserve(size);
  for (int r = 0; r < size; ++r) {
    threads.emplace_back(EvictRank, r, size, port, /*pre=*/2, /*post=*/2,
                         lo, hi, &ph, &errs[r]);
  }
  ph.init.Wait();
  ph.start.Wait();
  ph.done.Wait();
  ph.exit_.Wait();
  for (auto& t : threads) t.join();
  for (int r = 0; r < size; ++r) {
    if (!errs[r].empty()) Fail(name, r, errs[r]);
  }
  if (failures == 0) {
    std::printf("[%s] np=%d evicted host %d (ranks %d..%d), survivors "
                "finished\n",
                name, size, evict_host, lo, hi - 1);
  }
}

// ---------------------------------------------------------------------------
// Chaos arms: fault-injected death at a chosen tree level.
// ---------------------------------------------------------------------------

struct ChaosOutcome {
  bool init_ok = false;
  bool completed = false;
  std::string reason;
  double handshake_s = 0;
};

void ChaosSoakRank(int rank, int size, int port, int cycles,
                   ChaosOutcome* out) {
  CoreConfig cfg;
  cfg.rank = rank;
  cfg.size = size;
  cfg.rendezvous_addr = "127.0.0.1";
  cfg.rendezvous_port = port;
  cfg.ctrl_only = true;
  SocketController ctl(cfg);
  Status s = ctl.Initialize();
  if (!s.ok()) {
    out->reason = "init: " + s.reason;
    return;
  }
  out->init_ok = true;
  for (int c = 0; s.ok() && c < cycles; ++c) {
    std::string e = OneCycle(&ctl, "soak" + std::to_string(c), 0);
    if (!e.empty()) s = Status::Error(StatusCode::ABORTED, e);
  }
  if (s.ok()) {
    ctl.Farewell();
    ctl.Shutdown();
    out->completed = true;
    return;
  }
  // Mirror core_api's failure path: one more ComputeResponses runs the
  // abort handshake, and the reason the Python layer would surface comes
  // from WaitAbortReason — both bounded by the abort-propagation budget.
  const double t0 = MonotonicSeconds();
  std::vector<TensorRequest> none;
  std::vector<Response> ignored;
  ctl.ComputeResponses(none, &ignored);
  out->reason = ctl.WaitAbortReason();
  if (out->reason.empty()) out->reason = s.reason;
  out->handshake_s = MonotonicSeconds() - t0;
  ctl.Shutdown();
}

// Arms `spec`, runs `size` ranks for `cycles`, and asserts: nobody
// completes, nobody hangs (abort handshake bounded), and `witness` — a
// rank outside the dead branch — names the exact culprit rank + host.
void RunChaosPhase(const char* name, int depth, const std::string& spec,
                   int size, int hosts, int cycles, int witness,
                   int culprit) {
  ::setenv("HOROVOD_CONTROL_TREE", "on", 1);
  SetDepthEnv(depth);
  ::setenv("HOROVOD_FAULT_INJECT", spec.c_str(), 1);
  std::string perr = InitFaultInjection();
  if (!perr.empty()) {
    Fail(name, -1, "spec error: " + perr);
    return;
  }
  const int port = ClaimFreePort();
  if (port < 0) {
    Fail(name, -1, "no free port");
    return;
  }
  std::vector<ChaosOutcome> out(size);
  std::vector<std::thread> threads;
  threads.reserve(size);
  for (int r = 0; r < size; ++r) {
    threads.emplace_back(ChaosSoakRank, r, size, port, cycles, &out[r]);
  }
  for (auto& t : threads) t.join();
  ::unsetenv("HOROVOD_FAULT_INJECT");
  InitFaultInjection();
  SetDepthEnv(0);
  // The configured propagation bound is 2 s (set in main); the slack on
  // top covers sanitizer + thousand-thread scheduler noise, same policy
  // as tests/parallel/test_ctrl_tree_np8.py.
  const double bound_s = 2.0 + 13.0;
  int aborted = 0;
  for (int r = 0; r < size; ++r) {
    if (out[r].completed) {
      Fail(name, r, "completed cleanly despite the injected fault");
    } else if (out[r].reason.empty()) {
      Fail(name, r, "aborted without a reason");
    } else if (out[r].init_ok && out[r].handshake_s > bound_s) {
      Fail(name, r,
           "abort handshake took " + std::to_string(out[r].handshake_s) +
               "s (bound " + std::to_string(bound_s) + "s)");
    } else {
      ++aborted;
    }
  }
  // Exact culprit attribution, checked on a rank whose only signal is the
  // coordinator's direct ABORT broadcast (the dead branch may latch its
  // leader's synthesized reason first, which is also correct but vaguer).
  const std::string want =
      "culprit rank " + std::to_string(culprit) + ", host fakehost-" +
      std::to_string(static_cast<int64_t>(culprit) * hosts / size);
  if (witness >= 0 && witness < size && out[witness].init_ok &&
      out[witness].reason.find(want) == std::string::npos) {
    Fail(name, witness,
         "witness reason does not name '" + want + "': " +
             out[witness].reason);
  }
  if (failures == 0) {
    std::printf("[%s] np=%d depth=%d: %d ranks aborted bounded, witness "
                "%d named culprit %d\n",
                name, size, depth, aborted, witness, culprit);
  }
}

}  // namespace

int main() {
  // np in-process ranks keep both ends of every control socket in one
  // process; don't depend on the caller's `ulimit -n`.
  struct rlimit rl;
  if (::getrlimit(RLIMIT_NOFILE, &rl) == 0 && rl.rlim_cur < rl.rlim_max) {
    rl.rlim_cur = rl.rlim_max;
    ::setrlimit(RLIMIT_NOFILE, &rl);
  }
  // A wedged abort path would otherwise hang `make selftest` forever; the
  // chaos arms' whole claim is that nothing ever blocks unbounded.
  ::alarm(1500);
  // CTRL_SOAK_NP / CTRL_SOAK_HOSTS select the geometry; CI runs both the
  // 256/16 default and the np=1024/64 pod-scale acceptance row.
  // CTRL_SOAK_ARMS=pod trims to the acceptance-critical arms for the
  // sanitizer pod rows.
  int np = 256;
  int hosts = 16;
  if (const char* env = ::getenv("CTRL_SOAK_NP")) np = std::atoi(env);
  if (const char* env = ::getenv("CTRL_SOAK_HOSTS")) hosts = std::atoi(env);
  const char* arms_env = ::getenv("CTRL_SOAK_ARMS");
  const bool pod_only = arms_env != nullptr && std::string(arms_env) == "pod";
  if (np < 16 || hosts < 2 || np % hosts != 0) {
    std::fprintf(stderr, "bad soak geometry np=%d hosts=%d\n", np, hosts);
    return 1;
  }
  ::setenv("HOROVOD_HIER_FAKE_HOSTS", std::to_string(hosts).c_str(), 1);
  ::setenv("HOROVOD_RENDEZVOUS_ACCEPTORS", "8", 1);
  ::setenv("HOROVOD_RENDEZVOUS_BACKOFF_BASE_MS", "10", 1);
  ::setenv("HOROVOD_ABORT_PROPAGATION_TIMEOUT", "2", 1);
  SetDepthEnv(0);

  const int cycles = 3;
  const int per = np / hosts;
  const int fanout = 32;  // mirror of the HOROVOD_CTRL_TREE_FANOUT default
  const TreeModel auto_model = ModelTree(np, hosts, fanout, 0);
  const TreeModel d2_model = ModelTree(np, hosts, fanout, 2);
  const TreeModel d3_model = ModelTree(np, hosts, fanout, 3);

  // --- flat vs adaptive tree: the O(n) -> O(fanout) acceptance bar -------
  if (!pod_only) {
    const int64_t flat = RunPhase("flat", "off", np, cycles);
    const int64_t tree = RunPhase("tree", "on", np, cycles);
    if (failures == 0 && (flat < 0 || tree <= 0)) {
      Fail("soak", -1, "phase produced no measurement");
    }
    if (failures == 0) {
      // Flat: one frame from each of the other np-1 ranks per cycle.
      if (flat < np - 1) {
        Fail("flat", 0,
             "coordinator saw " + std::to_string(flat) +
                 " msgs/cycle, expected >= " + std::to_string(np - 1));
      }
      if (tree != auto_model.coord_children) {
        Fail("tree", 0,
             "coordinator saw " + std::to_string(tree) +
                 " msgs/cycle, expected " +
                 std::to_string(auto_model.coord_children));
      }
      // The acceptance bar: O(n) -> O(fanout) is at least an 8x cut here.
      if (tree > 0 && flat < 8 * tree) {
        Fail("soak", -1,
             "flat/tree ratio " + std::to_string(flat) + "/" +
                 std::to_string(tree) + " is below the required 8x");
      }
    }
  } else {
    // Pod row: the adaptive shape assert without the flat baseline burn.
    const int64_t tree = RunPhase("tree", "on", np, cycles);
    if (failures == 0 && tree != auto_model.coord_children) {
      Fail("tree", 0,
           "coordinator saw " + std::to_string(tree) +
               " msgs/cycle, expected " +
               std::to_string(auto_model.coord_children));
    }
  }
  // At any geometry the adaptive tree must hold the tentpole fan-in bound:
  // coordinator inbound <= fanout clusters + its own host's workers.
  if (failures == 0 && auto_model.coord_children > fanout + (per - 1)) {
    Fail("tree", 0,
         "adaptive depth left coordinator fan-in " +
             std::to_string(auto_model.coord_children) + " above fanout " +
             std::to_string(fanout) + " + local " + std::to_string(per - 1));
  }

  // --- forced-depth shapes: d2 == the v9 tree, d3 inserts super-leaders --
  if (failures == 0 && !pod_only) {
    SetDepthEnv(2);
    const int64_t d2 = RunPhase("tree+d2", "on", np, cycles);
    if (d2 != d2_model.coord_children ||
        d2 != (per - 1) + (hosts - 1)) {
      Fail("tree+d2", 0,
           "depth-2 coordinator saw " + std::to_string(d2) +
               " msgs/cycle, expected the v9 shape " +
               std::to_string((per - 1) + (hosts - 1)));
    }
    SetDepthEnv(3);
    const int64_t d3 = RunPhase("tree+d3", "on", np, cycles);
    if (d3 != d3_model.coord_children) {
      Fail("tree+d3", 0,
           "depth-3 coordinator saw " + std::to_string(d3) +
               " msgs/cycle, expected " +
               std::to_string(d3_model.coord_children));
    }
    if (d3_model.depth >= 3 && d3 >= (per - 1) + (hosts - 1)) {
      Fail("tree+d3", 0,
           "super-leader layer did not reduce coordinator fan-in: " +
               std::to_string(d3) + " vs v9 " +
               std::to_string((per - 1) + (hosts - 1)));
    }
    SetDepthEnv(0);
  }

  // --- migration-aware row: forensic noting under the adaptive tree ------
  if (failures == 0 && !pod_only) {
    GlobalMetrics().enabled.store(true, std::memory_order_relaxed);
    const int64_t mig0 =
        GlobalMetrics().migrate_events_total.load(std::memory_order_relaxed);
    g_migrate.store(true, std::memory_order_relaxed);
    const int64_t tree_mig = RunPhase("tree+migrate", "on", np, cycles);
    g_migrate.store(false, std::memory_order_relaxed);
    const int64_t mig_delta =
        GlobalMetrics().migrate_events_total.load(std::memory_order_relaxed) -
        mig0;
    if (mig_delta < static_cast<int64_t>(np) * cycles) {
      Fail("tree+migrate", -1,
           "migrate_events_total advanced " + std::to_string(mig_delta) +
               ", expected >= " + std::to_string(np * cycles));
    }
    if (tree_mig != auto_model.coord_children) {
      Fail("tree+migrate", 0,
           "replication noting perturbed the control plane: " +
               std::to_string(tree_mig) + " msgs/cycle, expected " +
               std::to_string(auto_model.coord_children));
    }
  }

  // --- fleet-telemetry row (protocol v11 sketches at v12 depth) ----------
  // Asserts the sketch sections do not perturb the per-cycle shape, the
  // coordinator stored exactly one cumulative sketch per direct source
  // (subtree sums arrive pre-merged, so sources stay O(fanout) at any
  // depth), and the fleet sum respects the replace-not-add bound: all np
  // threads snapshot the SAME global registry, so the sum can only exceed
  // np x the registry's own count if some subtree was double-merged.
  // (Per-rank bucket exactness is covered by the multi-process parallel
  // tests, where every rank has its own registry.)
  if (failures == 0) {
    GlobalMetrics().enabled.store(true, std::memory_order_relaxed);
    GlobalFleetTelemetry().enabled.store(true, std::memory_order_relaxed);
    const int64_t merged0 = GlobalMetrics().fleet_sketches_merged_total.load(
        std::memory_order_relaxed);
    int fleet_sources = -1;
    int64_t fleet_sum = -1;
    g_observe.store(true, std::memory_order_relaxed);
    const int64_t tree_sk =
        RunPhase("tree+sketch", "on", np, cycles, &fleet_sources, &fleet_sum);
    g_observe.store(false, std::memory_order_relaxed);
    if (tree_sk != auto_model.coord_children) {
      Fail("tree+sketch", 0,
           "sketch sections perturbed the control plane: " +
               std::to_string(tree_sk) + " msgs/cycle, expected " +
               std::to_string(auto_model.coord_children));
    }
    if (fleet_sources != auto_model.coord_children) {
      Fail("tree+sketch", 0,
           "coordinator stored " + std::to_string(fleet_sources) +
               " fleet sources, expected " +
               std::to_string(auto_model.coord_children));
    }
    const int64_t merged =
        GlobalMetrics().fleet_sketches_merged_total.load(
            std::memory_order_relaxed) -
        merged0;
    if (merged < auto_model.coord_children) {
      Fail("tree+sketch", 0,
           "fleet_sketches_merged_total advanced " + std::to_string(merged) +
               ", expected >= " +
               std::to_string(auto_model.coord_children));
    }
    const int64_t reg_count =
        GlobalMetrics().negotiation_wait_us.count.load(
            std::memory_order_relaxed);
    if (fleet_sum <= 0 || fleet_sum > static_cast<int64_t>(np) * reg_count) {
      Fail("tree+sketch", 0,
           "fleet sum count " + std::to_string(fleet_sum) +
               " outside the replace-not-add bound (0, " +
               std::to_string(static_cast<int64_t>(np) * reg_count) + "]");
    }
  }

  // --- tenant churn: per-cycle process-set re-registration ---------------
  if (failures == 0 && !pod_only) {
    SetDepthEnv(3);
    g_churn.store(true, std::memory_order_relaxed);
    const int64_t churn = RunPhase("tree+churn", "on", np, cycles);
    g_churn.store(false, std::memory_order_relaxed);
    if (churn != d3_model.coord_children) {
      Fail("tree+churn", 0,
           "set churn perturbed the control plane: " + std::to_string(churn) +
               " msgs/cycle, expected " +
               std::to_string(d3_model.coord_children));
    }
    SetDepthEnv(0);
  }

  // --- chaos + eviction grid: deaths and departures at every level -------
  // Targets mirror ComputeCtrlTree: S = the first super-leader at forced
  // depth 3, L = the first host leader clustered under S, W = a worker on
  // L's host.  All must sit below the fault injector's 64 tracked-rank
  // slots so per-(site, rank) hit indices stay exact.
  int S = -1, L = -1, W = -1;
  for (const auto& kv : d3_model.parent_of) {
    if (kv.second > 0) {
      S = kv.second;
      L = kv.first;
      break;
    }
  }
  if (L >= 0 && per > 1) W = L + 1;
  const bool chaos_ok =
      d3_model.depth >= 3 && S > 0 && L > S && W > L && W < 63 && per > 1;
  if (failures == 0 && !chaos_ok) {
    Fail("chaos", -1,
         "geometry np=" + std::to_string(np) + " hosts=" +
             std::to_string(hosts) +
             " cannot place depth-3 chaos targets under the 64-slot limit");
  }
  if (failures == 0 && chaos_ok) {
    if (!pod_only) {
      // Depth 2: a host leader dies — detected by the coordinator's own
      // gather, culprit named directly (the v9 contract, re-proven at the
      // soak geometry after the v12 refactor).
      RunChaosPhase("chaos+d2+leader", 2,
                    "coordinator-recv:1:" + std::to_string(per) + ":drop",
                    np, hosts, cycles, /*witness=*/1, /*culprit=*/per);
      // Depth 3, leaf level: a worker dies; its host leader FINs up
      // through the super-leader chain.
      RunChaosPhase("chaos+d3+worker", 3,
                    "leader-recv:1:" + std::to_string(W) + ":drop", np,
                    hosts, cycles, /*witness=*/1, /*culprit=*/W);
      // Depth 3, top level: a super-leader dies; the coordinator's gather
      // detects it and the direct broadcast releases the orphan subtree.
      RunChaosPhase("chaos+d3+super", 3,
                    "coordinator-recv:1:" + std::to_string(S) + ":drop", np,
                    hosts, cycles, /*witness=*/1, /*culprit=*/S);
    }
    // Depth 3, mid level (the acceptance row): a clustered host leader
    // dies; its super-leader's gather trips the v12 super-recv site and
    // the FIN relays up with the culprit intact.
    RunChaosPhase("chaos+d3+leader", 3,
                  "super-recv:1:" + std::to_string(L) + ":drop", np, hosts,
                  cycles, /*witness=*/1, /*culprit=*/L);
    // Adaptive depth: the same mid-level death wherever auto placed the
    // super layer; at small host counts auto stays depth 2 and the death
    // degrades to the coordinator-detected leader case.
    if (auto_model.depth >= 3) {
      RunChaosPhase("chaos+adapt", 0,
                    "super-recv:1:" + std::to_string(L) + ":drop", np, hosts,
                    cycles, /*witness=*/1, /*culprit=*/L);
    } else if (!pod_only) {
      RunChaosPhase("chaos+adapt", 0,
                    "coordinator-recv:1:" + std::to_string(per) + ":drop",
                    np, hosts, cycles, /*witness=*/1, /*culprit=*/per);
    }
  }
  // Autopilot-style eviction at depth 3: the host of the first clustered
  // leader under S departs cleanly mid-soak; survivors finish on the
  // survivor set (the BYE-releases-the-subtree contract, clean twin of
  // the chaos+d3+leader death).
  if (failures == 0 && chaos_ok && !pod_only) {
    SetDepthEnv(3);
    RunEvictPhase("tree+evict", np, hosts, /*evict_host=*/L / per);
    SetDepthEnv(0);
  }

  if (failures != 0) {
    std::printf("FAIL (%d)\n", failures);
    return 1;
  }
  std::printf("PASS\n");
  return 0;
}
