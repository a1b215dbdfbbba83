// Simulated large-world harness: stand up a 64-256-rank world as
// thread-per-rank controllers in ONE process, connected over the same
// socketpair machinery as ring_selftest.cc — no TCP rendezvous, no
// ephemeral-port exhaustion, no process fleet. The point is control-
// plane CHARACTERIZATION (docs/scale.md): every rank runs the real
// Controller negotiation (flat star or HOROVOD_CONTROL_TREE bundles)
// and the real DataPlane ring allreduce, so the per-phase latency
// profile (ControlPhase histograms, metrics.h) measured here is the
// same code that runs at production scale — only the transport hops
// are loopback.
//
// Topology budget: the control star is O(N) socketpairs; the data
// plane is a full mesh up to kFullMeshRanks (matching the selftest)
// and ring-neighbors-only above it — the ring allreduce touches only
// neighbors, and a neighbors-only probe sweep still converges on the
// dead set (it just names fewer witnesses). RLIMIT_NOFILE is raised
// toward the hard limit before building.
//
// Reference analog: none upstream — Horovod's scalability was proved
// on real clusters (arXiv:1802.05799 §5); the characterization-first
// discipline here follows arXiv:1810.11112 (profile the phases at
// target scale, then fix what the curves indict).

#include <sys/resource.h>
#include <sys/socket.h>

#include <algorithm>
#include <atomic>
#include <cstdarg>
#include <cstdio>
#include <cstring>
#include <mutex>
#include <thread>
#include <vector>

#include "common.h"
#include "controller.h"
#include "events.h"
#include "logging.h"
#include "message.h"
#include "metrics.h"
#include "ring_ops.h"
#include "wire.h"

extern "C" int hvdtpu_is_initialized();

namespace hvdtpu {
namespace {

// Above this, the data plane is ring-neighbors-only (fd budget: a full
// mesh is N^2 fds; 256 ranks would need ~65k).
constexpr int kFullMeshRanks = 32;

// One simulated world run at a time: the harness resets the
// control-phase histograms for a clean profile.
std::mutex g_simworld_mutex;

struct SimWorld {
  int size = 0;
  int fanout = 0;
  // Per-rank fd sets, handed to InitializeFromFds (owned there).
  std::vector<std::vector<int>> control_fds;
  std::vector<std::vector<int>> peer_fds;
  std::vector<int> tree_parent_fd;
  std::vector<std::vector<std::pair<int, int>>> tree_children;
  bool full_mesh = false;

  bool Build(int ranks, int tree_fanout) {
    size = ranks;
    fanout = tree_fanout;
    control_fds.assign(ranks, {});
    peer_fds.assign(ranks, std::vector<int>(ranks, -1));
    tree_parent_fd.assign(ranks, -1);
    tree_children.assign(ranks, {});
    control_fds[0].assign(ranks, -1);

    // Control star: coordinator side in control_fds[0][r], worker side
    // as the worker's single entry. Both ends register their peer rank
    // (unique fd numbers in one process) so EOF/timeout statuses name
    // the casualty exactly like the TCP bootstrap's registrations.
    for (int r = 1; r < ranks; r++) {
      int sv[2];
      if (socketpair(AF_UNIX, SOCK_STREAM, 0, sv) != 0) return false;
      control_fds[0][r] = sv[0];
      control_fds[r].assign(1, sv[1]);
      RegisterFdRank(sv[0], r);
      RegisterFdRank(sv[1], 0);
    }
    // Tree edges between two WORKERS (edges touching rank 0 reuse the
    // star, exactly as the TCP path shares them).
    if (tree_fanout >= 2) {
      for (int r = 1; r < ranks; r++) {
        int parent = (r - 1) / tree_fanout;
        if (parent == 0) continue;
        int sv[2];
        if (socketpair(AF_UNIX, SOCK_STREAM, 0, sv) != 0) return false;
        tree_children[parent].emplace_back(r, sv[0]);
        tree_parent_fd[r] = sv[1];
        RegisterFdRank(sv[0], r);
        RegisterFdRank(sv[1], parent);
      }
      // Children must be in rank order (the gather iterates in order).
      for (auto& kids : tree_children) {
        std::sort(kids.begin(), kids.end());
      }
    }
    // Data plane: full mesh small, ring neighbors large.
    full_mesh = ranks <= kFullMeshRanks;
    for (int i = 0; i < ranks; i++) {
      for (int j = i + 1; j < ranks; j++) {
        bool neighbor = (j == i + 1) || (i == 0 && j == ranks - 1);
        if (!full_mesh && !neighbor) continue;
        int sv[2];
        if (socketpair(AF_UNIX, SOCK_STREAM, 0, sv) != 0) return false;
        peer_fds[i][j] = sv[0];
        peer_fds[j][i] = sv[1];
        RegisterFdRank(sv[0], j);
        RegisterFdRank(sv[1], i);
      }
    }
    return true;
  }

  // Close everything NOT yet handed to a controller (build failure).
  void CloseAll() {
    for (auto& row : control_fds) {
      for (int fd : row) TcpClose(fd);
    }
    for (auto& row : peer_fds) {
      for (int fd : row) TcpClose(fd);
    }
    for (int fd : tree_parent_fd) TcpClose(fd);
    for (auto& kids : tree_children) {
      for (auto& kv : kids) TcpClose(kv.second);
    }
  }
};

// Raise the fd soft limit toward the hard limit when the build needs
// more than we have. Returns false when even the hard limit is short.
bool EnsureFdBudget(int64_t needed) {
  rlimit rl{};
  if (getrlimit(RLIMIT_NOFILE, &rl) != 0) return true;  // best effort
  if ((int64_t)rl.rlim_cur >= needed) return true;
  if ((int64_t)rl.rlim_max < needed &&
      rl.rlim_max != RLIM_INFINITY) {
    return false;
  }
  rlimit want = rl;
  want.rlim_cur = (rl.rlim_max == RLIM_INFINITY)
                      ? (rlim_t)needed
                      : std::min<rlim_t>((rlim_t)needed, rl.rlim_max);
  return setrlimit(RLIMIT_NOFILE, &want) == 0 ||
         (int64_t)rl.rlim_cur >= needed;
}

struct RankResult {
  bool ok = false;           // every round completed
  bool data_ok = true;       // allreduce results verified
  bool fault_typed = false;  // ended with a typed PeerFailure
  int fault_rank = -1;
  std::string reason;
  int rounds_done = 0;
};

void RunRank(int rank, SimWorld& w, int64_t elems, int rounds,
             int kill_rank, int kill_round, std::atomic<int>* up,
             std::atomic<int>* init_failed,
             std::vector<int64_t>* round_us, RankResult* res) {
  ControllerConfig cfg;
  cfg.rank = rank;
  cfg.size = w.size;
  cfg.tree_fanout = w.fanout;
  Controller ctl(cfg);
  Status st = ctl.InitializeFromFds(
      std::move(w.control_fds[rank]), std::move(w.peer_fds[rank]),
      w.tree_parent_fd[rank], std::move(w.tree_children[rank]));
  if (!st.ok()) {
    res->reason = st.reason();
    init_failed->fetch_add(1);
    return;
  }
  up->fetch_add(1);
  std::vector<float> buf((size_t)elems);
  const double expect = (double)w.size * (w.size + 1) / 2.0;
  for (int round = 0; round < rounds; round++) {
    if (rank == kill_rank && round == kill_round) {
      // Simulated SIGKILL: scope exit closes every fd this rank owns
      // (controller star/tree + data plane) — peers see EOF, the
      // certain-attribution path, exactly like a dead process.
      res->rounds_done = round;
      res->reason = "killed";
      return;
    }
    Request req;
    req.request_rank = rank;
    req.request_type = RequestType::ALLREDUCE;
    req.tensor_type = DataType::HVDTPU_FLOAT32;
    req.tensor_name = "simworld.grad";
    req.tensor_shape = {elems};
    const int64_t t0 = MetricsNowUs();
    ResponseList out;
    st = ctl.ComputeResponseList({req}, false, &out);
    if (!st.ok()) {
      res->fault_typed = st.peer_failure();
      res->fault_rank = st.fault_rank();
      res->reason = st.reason();
      res->rounds_done = round;
      return;
    }
    for (auto& resp : out.responses) {
      if (resp.response_type == Response::ResponseType::ERROR) {
        res->reason = resp.error_message;
        res->rounds_done = round;
        return;
      }
      if (resp.response_type != Response::ResponseType::ALLREDUCE ||
          elems == 0) {
        continue;
      }
      std::fill(buf.begin(), buf.end(), (float)(rank + 1));
      st = ctl.data_plane()->Allreduce(buf.data(), elems,
                                       DataType::HVDTPU_FLOAT32,
                                       ReduceOp::SUM, 1.0);
      if (!st.ok()) {
        res->fault_typed = st.peer_failure();
        res->fault_rank = st.fault_rank();
        res->reason = st.reason();
        res->rounds_done = round;
        return;
      }
      if (buf[0] != (float)expect ||
          buf[(size_t)elems - 1] != (float)expect) {
        res->data_ok = false;
      }
    }
    if (rank == 0) round_us->push_back(MetricsNowUs() - t0);
    res->rounds_done = round + 1;
  }
  res->ok = true;
}

// Measure-then-format (the shared AppendFmtV, metrics.h): a fixed
// stack buffer here would silently truncate — corrupt — the report
// JSON the moment a row outgrew it.
void AppendJson(std::string& out, const char* fmt, ...) {
  va_list args;
  va_start(args, fmt);
  AppendFmtV(out, fmt, args);
  va_end(args);
}

}  // namespace
}  // namespace hvdtpu

using namespace hvdtpu;

extern "C" {

// Run one simulated world: `ranks` thread-per-rank controllers over
// socketpairs, `rounds` negotiation+allreduce cycles of an
// `elems`-float32 gradient, optionally killing `kill_rank` at the top
// of `kill_round`. tree_fanout >= 2 selects the tree-structured
// negotiation gather (HOROVOD_CONTROL_TREE); 0 = flat star baseline.
//
// Writes a JSON report into json_out (truncated to json_cap):
// standup/round latency plus the per-phase control-plane profile
// (ControlPhase histograms — reset at entry for a clean curve, which
// is why a live in-process core refuses the run). Returns:
//   0 ok   -1 bad args   -2 socketpair/fd budget   -3 a rank failed
//   -4 allreduce mismatch   -5 core already initialized
//   -6 kill injected but no survivor saw a typed fault
int hvdtpu_simworld_run(int ranks, int tree_fanout, int64_t elems,
                        int rounds, int kill_rank, int kill_round,
                        char* json_out, int64_t json_cap) {
  if (ranks < 2 || ranks > 1024 || elems < 0 || rounds < 1 ||
      tree_fanout < 0 || kill_rank >= ranks ||
      (kill_rank >= 0 && (kill_round < 0 || kill_round >= rounds))) {
    return -1;
  }
  if (hvdtpu_is_initialized()) return -5;  // would stomp the profile
  std::lock_guard<std::mutex> lock(g_simworld_mutex);

  const bool full_mesh = ranks <= kFullMeshRanks;
  int64_t needed = 4 * (int64_t)ranks +
                   (full_mesh ? (int64_t)ranks * ranks : 4 * (int64_t)ranks)
                   + 256;
  if (!EnsureFdBudget(needed)) return -2;

  // Clean per-phase profile for THIS world size (the whole point of
  // the harness); rendezvous is recorded below as world standup.
  for (auto& h : GlobalMetrics().control_phase_us) h.Reset();
  GlobalMetrics().gather_frames.store(0);

  SimWorld w;
  if (!w.Build(ranks, tree_fanout)) {
    w.CloseAll();
    return -2;
  }

  const int64_t standup_t0 = MetricsNowUs();
  int64_t standup_us = 0;
  std::atomic<int> up{0}, init_failed{0};
  std::vector<int64_t> round_us;
  std::vector<RankResult> results(ranks);
  {
    std::vector<std::thread> threads;
    threads.reserve(ranks);
    for (int r = 0; r < ranks; r++) {
      threads.emplace_back(RunRank, r, std::ref(w), elems, rounds,
                           kill_rank, kill_round, &up, &init_failed,
                           &round_us, &results[r]);
    }
    // Standup = every controller constructed and fd-connected (the
    // TCP analog is the rendezvous fan-in; recorded on its phase).
    while (up.load() + init_failed.load() < ranks) {
      std::this_thread::yield();
    }
    standup_us = MetricsNowUs() - standup_t0;
    RecordControlPhase(kPhaseRendezvous, standup_us);
    for (auto& t : threads) t.join();
  }

  // Probe sweep once on the surviving coordinator-side view is not
  // possible here (planes are gone); the sweep is profiled by the live
  // ranks' elastic path instead. Summarize results.
  int rc = 0;
  bool data_ok = true;
  std::string first_reason;
  int typed_faults = 0, fault_rank_seen = -1;
  for (int r = 0; r < ranks; r++) {
    if (r == kill_rank) continue;
    if (!results[r].data_ok) data_ok = false;
    if (kill_rank < 0) {
      if (!results[r].ok && first_reason.empty()) {
        first_reason = results[r].reason;
        rc = -3;
      }
    } else {
      if (results[r].fault_typed) {
        typed_faults++;
        if (fault_rank_seen < 0) fault_rank_seen = results[r].fault_rank;
      }
    }
  }
  if (rc == 0 && !data_ok) rc = -4;
  if (rc == 0 && kill_rank >= 0 && typed_faults == 0) rc = -6;

  // Round stats (coordinator wall time per negotiation+allreduce).
  int64_t rmin = 0, rmax = 0, rsum = 0;
  for (size_t i = 0; i < round_us.size(); i++) {
    rmin = i == 0 ? round_us[i] : std::min(rmin, round_us[i]);
    rmax = std::max(rmax, round_us[i]);
    rsum += round_us[i];
  }
  std::string json = "{";
  AppendJson(json, "\"ranks\":%d,\"tree_fanout\":%d,\"elems\":%lld,"
                   "\"rounds\":%d,\"data_mesh\":\"%s\",",
             ranks, tree_fanout, (long long)elems, rounds,
             full_mesh ? "full" : "ring");
  AppendJson(json, "\"standup_us\":%lld,", (long long)standup_us);
  AppendJson(json, "\"round_us\":{\"count\":%lld,\"mean\":%lld,"
                   "\"min\":%lld,\"max\":%lld},",
             (long long)round_us.size(),
             (long long)(round_us.empty() ? 0
                                          : rsum / (int64_t)round_us.size()),
             (long long)rmin, (long long)rmax);
  json += "\"phases\":{";
  {
    bool first = true;
    for (int i = 0; i < kPhaseCount; i++) {
      if (GlobalMetrics().control_phase_us[i].count() == 0) continue;
      AppendJson(json, "%s\"%s\":", first ? "" : ",",
                 ControlPhaseName(i));
      json += GlobalMetrics().control_phase_us[i].Json();
      first = false;
    }
  }
  json += "},";
  AppendJson(json, "\"gather_frames\":%lld,",
             (long long)GlobalMetrics().gather_frames.load());
  AppendJson(json, "\"allreduce_ok\":%s,", data_ok ? "true" : "false");
  if (kill_rank >= 0) {
    AppendJson(json, "\"fault\":{\"injected_rank\":%d,\"typed_faults\":"
                     "%d,\"named_rank\":%d},",
               kill_rank, typed_faults, fault_rank_seen);
  }
  // Escape-free by construction: reasons carry rank numbers and fixed
  // text; quotes are stripped to keep the report parseable regardless.
  std::string reason = first_reason.substr(0, 200);
  reason.erase(std::remove(reason.begin(), reason.end(), '"'),
               reason.end());
  reason.erase(std::remove(reason.begin(), reason.end(), '\\'),
               reason.end());
  AppendJson(json, "\"error\":\"%s\",\"rc\":%d}", reason.c_str(), rc);

  if (json_out != nullptr && json_cap > 0) {
    size_t n = std::min((size_t)(json_cap - 1), json.size());
    std::memcpy(json_out, json.data(), n);
    json_out[n] = '\0';
  }
  return rc;
}

}  // extern "C"
