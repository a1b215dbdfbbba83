#include "controller.h"

#include <algorithm>
#include <cstdio>
#include <cstring>
#include <sstream>

#include "logging.h"
#include "events.h"
#include "metrics.h"
#include "wire.h"

namespace hvdtpu {

namespace {

// Hello exchanged at bootstrap: rank + data-plane listen address, plus
// the membership epoch — the coordinator refuses hellos from any other
// epoch, so a half-dead rank of a previous ring generation (or a
// blacklisted straggler retrying its old assignment) can never join the
// re-formed ring.
struct Hello {
  int32_t rank;
  int32_t epoch_lo;  // low/high halves keep the struct packing simple
  int32_t epoch_hi;
  char addr[64];
  int32_t port;
  // Control-tree listen port of this rank (HOROVOD_CONTROL_TREE):
  // interior workers accept their tree children here. 0 = not an
  // interior worker (leaf, rank 0, or tree mode off).
  int32_t tree_port;
};

// Bundle format for the tree gather: one wire frame holding a sequence
// of [u32 LE len][serialized RequestList] entries. A relay appends its
// children's bundles VERBATIM after its own entry — no re-parse on the
// way up; only the coordinator unpacks.
void AppendBundleEntry(std::string* bundle, const std::string& frame) {
  uint32_t len = (uint32_t)frame.size();
  bundle->append(reinterpret_cast<const char*>(&len), sizeof(len));
  bundle->append(frame);
}

bool SplitBundle(const std::string& bundle,
                 std::vector<std::string>* frames) {
  size_t off = 0;
  while (off < bundle.size()) {
    if (off + sizeof(uint32_t) > bundle.size()) return false;
    uint32_t len;
    std::memcpy(&len, bundle.data() + off, sizeof(len));
    off += sizeof(len);
    if (off + len > bundle.size()) return false;
    frames->emplace_back(bundle.substr(off, len));
    off += len;
  }
  return true;
}

void SetHelloEpoch(Hello* h, int64_t epoch) {
  h->epoch_lo = (int32_t)(epoch & 0xffffffff);
  h->epoch_hi = (int32_t)(epoch >> 32);
}

int64_t HelloEpoch(const Hello& h) {
  return ((int64_t)h.epoch_hi << 32) | (uint32_t)h.epoch_lo;
}

bool ShapesMatch(const std::vector<int64_t>& a, const std::vector<int64_t>& b,
                 bool ignore_first_dim) {
  if (a.size() != b.size()) return false;
  for (size_t i = ignore_first_dim ? 1 : 0; i < a.size(); i++) {
    if (a[i] != b[i]) return false;
  }
  return true;
}

// Byte size of a cached single-tensor response.
int64_t CachedEntryBytes(const Response& r) { return ShapesTotalBytes(r); }

// Scope-exit cleanup for the bootstrap's many error returns: failed
// rendezvous attempts (reinit retries especially) must not leak the
// data-plane listen socket or half-built peer connections.
struct Cleanup {
  std::function<void()> fn;
  ~Cleanup() {
    if (fn) fn();
  }
  void release() { fn = nullptr; }
};

// Shared fusion predicate for the cached and freshly-negotiated allreduce
// paths — one site so the two fusion paths cannot diverge.
bool FusableAllreducePair(DataType dtype_a, int32_t ps_a, ReduceOp op_a,
                          int32_t dev_a, DataType dtype_b, int32_t ps_b,
                          ReduceOp op_b, int32_t dev_b) {
  // Host and device tensors never share a fused group: the former moves
  // through the host ring, the latter through one XLA program.
  return dtype_a == dtype_b && ps_a == ps_b && op_a == op_b &&
         dev_a == dev_b;
}

}  // namespace

Controller::Controller(ControllerConfig cfg) : cfg_(std::move(cfg)) {
  shutdown_flags_.assign(cfg_.size, false);
  last_stall_check_ = std::chrono::steady_clock::now();
  cache_.SetCapacity(cfg_.cache_capacity);
}

Controller::~Controller() {
  for (int fd : control_fds_) TcpClose(fd);
  for (int fd : tree_owned_fds_) TcpClose(fd);
}

std::vector<int> Controller::TreeChildren(int r) const {
  std::vector<int> out;
  if (cfg_.tree_fanout < 2) return out;
  for (int i = 1; i <= cfg_.tree_fanout; i++) {
    int c = r * cfg_.tree_fanout + i;
    if (c < cfg_.size) out.push_back(c);
  }
  return out;
}

int Controller::SubtreeSize(int r) const {
  int n = 1;
  for (int c : TreeChildren(r)) n += SubtreeSize(c);
  return n;
}

Status Controller::Initialize() {
  const int rank = cfg_.rank, size = cfg_.size;
  if (size == 1) {
    data_plane_ = std::make_unique<DataPlane>(0, 1, std::vector<int>{-1});
    return Status::OK();
  }
  // Rendezvous fan-in is the first O(N) control-plane suspect on the
  // scaling profile (docs/scale.md) — time the whole bootstrap.
  const int64_t rdzv_start_us = MetricsNowUs();

  if (cfg_.use_external_transport) {
    // Bare-MPI mode: no rendezvous, no sockets. Ranks and sizes come
    // from the launcher env; both planes address peers through the
    // registered message transport (control = tag 0, data = tag 1).
    if (!ExternalTransportActive()) {
      return Status::Error(
          "HOROVOD_CONTROLLER=mpi but no external transport registered "
          "(the frontend registers mpi4py callbacks before init)");
    }
    if (rank == 0) {
      control_fds_.assign(size, -1);
      for (int i = 1; i < size; i++) control_fds_[i] = ExtFd(i, 0);
    } else {
      control_fds_.assign(1, ExtFd(0, 0));
    }
    std::vector<int> peers(size, -1);
    for (int j = 0; j < size; j++) {
      if (j != rank) peers[j] = ExtFd(j, 1);
    }
    data_plane_ = std::make_unique<DataPlane>(rank, size,
                                              std::move(peers));
    LOG_DEBUG("rank %d: external-transport planes up (size=%d)", rank,
              size);
    return Status::OK();
  }

  // 1) Data-plane listen socket (ephemeral port), plus the control-tree
  // listen socket when this rank is an interior tree worker (its tree
  // children connect here; the port rides the hello/address book).
  int data_port = 0;
  int data_listen = TcpListen(&data_port);
  if (data_listen < 0) return Status::Error("failed to open data-plane port");
  std::string my_addr = LocalAddress();
  const bool tree = TreeEnabled();
  std::vector<int> my_tree_children = tree ? TreeChildren(rank)
                                           : std::vector<int>();
  int tree_port = 0, tree_listen = -1;
  if (tree && rank != 0 && !my_tree_children.empty()) {
    tree_listen = TcpListen(&tree_port);
    if (tree_listen < 0) {
      TcpClose(data_listen);
      return Status::Error("failed to open control-tree port");
    }
  }
  // Full-mesh peer fds, filled in step 3 (declared here so the error
  // cleanup covers every return below; -1 entries are no-ops to close).
  // Channel 0 is `peers`; stripe channels 1..K-1 (HOROVOD_WIRE_-
  // CHANNELS) live in `extra_peers[c-1]` — same mesh, K sockets per
  // pair, the channel id riding the data-plane hello.
  const int wire_channels =
      std::min(std::max(cfg_.wire_channels, 1), kMaxWireChannels);
  std::vector<int> peers(size, -1);
  std::vector<std::vector<int>> extra_peers(
      wire_channels - 1, std::vector<int>(size, -1));
  // Tree edges built in step 4; owned here until handoff.
  std::vector<int> tree_fds;
  Cleanup cleanup{[&] {
    TcpClose(data_listen);
    TcpClose(tree_listen);
    for (int fd : peers) TcpClose(fd);
    for (auto& chan : extra_peers) {
      for (int fd : chan) TcpClose(fd);
    }
    for (int fd : tree_fds) TcpClose(fd);
  }};

  // 2) Control-plane rendezvous + address-book broadcast. Bootstrap
  // I/O runs under the start timeout (launch stragglers are expected);
  // hellos are validated against the current epoch so stale-generation
  // ranks are turned away at the door instead of corrupting the book.
  const int64_t start_ms = cfg_.start_timeout_ms;
  const auto start_deadline =
      std::chrono::steady_clock::now() + std::chrono::milliseconds(start_ms);
  auto remaining_ms = [&]() -> int64_t {
    auto left = std::chrono::duration_cast<std::chrono::milliseconds>(
                    start_deadline - std::chrono::steady_clock::now())
                    .count();
    return left > 0 ? left : 1;  // past-deadline accepts fail fast
  };
  std::vector<Hello> book(size);
  if (rank == 0) {
    int port = cfg_.controller_port;
    int lfd = TcpListen(&port);
    if (lfd < 0) {
      return Status::Error("coordinator failed to listen on port " +
                           std::to_string(cfg_.controller_port));
    }
    control_fds_.assign(size, -1);
    Hello mine{0, 0, 0, {0}, data_port, 0};
    SetHelloEpoch(&mine, cfg_.epoch);
    snprintf(mine.addr, sizeof(mine.addr), "%s", my_addr.c_str());
    book[0] = mine;
    int accepted = 0;
    while (accepted < size - 1) {
      // Deadline-bound: a member dying before it connects must FAIL
      // the rendezvous (reinit returns -4), never hang the acceptor.
      int fd = TcpAcceptTimeout(lfd, remaining_ms());
      if (fd < 0) {
        TcpClose(lfd);
        return Status::Error(
            "coordinator rendezvous timed out with " +
            std::to_string(size - 1 - accepted) +
            " member(s) missing (HOROVOD_START_TIMEOUT)");
      }
      Hello h{};
      // remaining_ms, not the full budget: a connector that never
      // sends its hello must not extend the rendezvous past the
      // configured deadline.
      Status s = RecvAll(fd, &h, sizeof(h), remaining_ms());
      if (!s.ok()) {
        TcpClose(fd);
        continue;  // connector vanished mid-hello; keep waiting
      }
      if (HelloEpoch(h) != cfg_.epoch) {
        LOG_WARN("rejecting hello from rank %d at stale epoch %lld "
                 "(current %lld)",
                 h.rank, (long long)HelloEpoch(h), (long long)cfg_.epoch);
        TcpClose(fd);
        continue;
      }
      if (h.rank < 1 || h.rank >= size || control_fds_[h.rank] != -1) {
        LOG_WARN("rejecting bad/duplicate hello rank %d", h.rank);
        TcpClose(fd);
        continue;
      }
      control_fds_[h.rank] = fd;
      RegisterFdRank(fd, h.rank);
      book[h.rank] = h;
      accepted++;
    }
    TcpClose(lfd);
    for (int i = 1; i < size; i++) {
      Status s = SendAll(control_fds_[i], book.data(), sizeof(Hello) * size,
                         remaining_ms());
      if (!s.ok()) return s;
    }
  } else {
    int fd = TcpConnect(cfg_.controller_addr, cfg_.controller_port,
                        (int)start_ms);
    if (fd < 0) {
      return Status::Error("worker failed to reach coordinator at " +
                           cfg_.controller_addr + ":" +
                           std::to_string(cfg_.controller_port));
    }
    RegisterFdRank(fd, 0);
    Hello mine{(int32_t)rank, 0, 0, {0}, data_port, tree_port};
    SetHelloEpoch(&mine, cfg_.epoch);
    snprintf(mine.addr, sizeof(mine.addr), "%s", my_addr.c_str());
    Status s = SendAll(fd, &mine, sizeof(mine), remaining_ms());
    if (s.ok()) {
      s = RecvAll(fd, book.data(), sizeof(Hello) * size, remaining_ms());
    }
    if (!s.ok()) {
      TcpClose(fd);
      return s;
    }
    control_fds_.assign(1, fd);
  }

  // 3) Full-mesh data plane: rank i accepts from all j > i, connects to
  // all j < i — K times per pair (one connection per stripe channel).
  // Each connection is identified by a (rank, epoch, channel) hello;
  // the channel id is what lets both ends bind socket k to stripe k,
  // so the chunk round-robin schedules agree end to end.
  auto chan_slot = [&](int c, int r) -> int* {
    return c == 0 ? &peers[r] : &extra_peers[c - 1][r];
  };
  for (int j = 0; j < rank; j++) {
    for (int c = 0; c < wire_channels; c++) {
      int fd = TcpConnect(book[j].addr, book[j].port, (int)remaining_ms());
      if (fd < 0) {
        return Status::Error("data-plane connect to rank " +
                             std::to_string(j) + " channel " +
                             std::to_string(c) + " failed");
      }
      *chan_slot(c, j) = fd;  // owned by the cleanup guard from here on
      int64_t me[3] = {(int64_t)rank, cfg_.epoch, (int64_t)c};
      Status s = SendAll(fd, me, sizeof(me), remaining_ms());
      if (!s.ok()) return s;
      RegisterFdRank(fd, j, c);
    }
  }
  int connected = 0;
  const int expect = (size - 1 - rank) * wire_channels;
  while (connected < expect) {
    int fd = TcpAcceptTimeout(data_listen, remaining_ms());
    if (fd < 0) {
      return Status::Error(
          "data-plane rendezvous timed out with " +
          std::to_string(expect - connected) +
          " connection(s) missing (HOROVOD_START_TIMEOUT)");
    }
    int64_t who[3] = {-1, -1, -1};
    Status s = RecvAll(fd, who, sizeof(who), remaining_ms());
    if (!s.ok()) {
      TcpClose(fd);
      continue;
    }
    if (who[1] != cfg_.epoch || who[0] <= rank || who[0] >= size ||
        who[2] < 0 || who[2] >= wire_channels ||
        *chan_slot((int)who[2], (int)who[0]) != -1) {
      LOG_WARN("rejecting data-plane hello from rank %lld epoch %lld "
               "channel %lld",
               (long long)who[0], (long long)who[1], (long long)who[2]);
      TcpClose(fd);
      continue;
    }
    *chan_slot((int)who[2], (int)who[0]) = fd;
    RegisterFdRank(fd, (int)who[0], (int)who[2]);
    connected++;
  }
  // 4) Control-tree edges (HOROVOD_CONTROL_TREE). Edges touching rank
  // 0 reuse the star sockets; a deeper child connects to its parent's
  // tree port from the book. Children connect upward, parents accept —
  // acyclic, so no connect/accept deadlock.
  if (tree) {
    const int parent = TreeParent(rank);
    if (rank != 0) {
      if (parent == 0) {
        tree_parent_fd_ = control_fds_[0];  // shared with the star
      } else {
        int fd = TcpConnect(book[parent].addr, book[parent].tree_port,
                            (int)remaining_ms());
        if (fd < 0) {
          return Status::Error("control-tree connect to rank " +
                               std::to_string(parent) + " failed");
        }
        tree_fds.push_back(fd);
        int64_t me[2] = {(int64_t)rank, cfg_.epoch};
        Status s = SendAll(fd, me, sizeof(me), remaining_ms());
        if (!s.ok()) return s;
        RegisterFdRank(fd, parent);
        tree_parent_fd_ = fd;
      }
    }
    if (rank == 0) {
      for (int c : my_tree_children) {
        tree_children_.emplace_back(c, control_fds_[c]);
      }
    } else {
      std::vector<int> child_fd(size, -1);
      int accepted = 0;
      while (accepted < (int)my_tree_children.size()) {
        int fd = TcpAcceptTimeout(tree_listen, remaining_ms());
        if (fd < 0) {
          return Status::Error(
              "control-tree rendezvous timed out with " +
              std::to_string((int)my_tree_children.size() - accepted) +
              " child(ren) missing (HOROVOD_START_TIMEOUT)");
        }
        int64_t who[2] = {-1, -1};
        Status s = RecvAll(fd, who, sizeof(who), remaining_ms());
        bool expected = s.ok() && who[1] == cfg_.epoch;
        if (expected) {
          expected = false;
          for (int c : my_tree_children) expected |= c == (int)who[0];
          expected = expected && child_fd[who[0]] == -1;
        }
        if (!expected) {
          LOG_WARN("rejecting control-tree hello from rank %lld epoch "
                   "%lld", (long long)who[0], (long long)who[1]);
          TcpClose(fd);
          continue;
        }
        child_fd[who[0]] = fd;
        tree_fds.push_back(fd);
        RegisterFdRank(fd, (int)who[0]);
        accepted++;
      }
      for (int c : my_tree_children) {
        tree_children_.emplace_back(c, child_fd[c]);
      }
    }
  }
  cleanup.release();  // mesh complete: the DataPlane owns the fds now
  tree_owned_fds_ = std::move(tree_fds);  // closed by the destructor
  TcpClose(data_listen);
  TcpClose(tree_listen);
  data_plane_ = std::make_unique<DataPlane>(rank, size, std::move(peers));
  if (wire_channels > 1) {
    data_plane_->AdoptExtraChannelFds(std::move(extra_peers));
  }
  RecordControlPhase(kPhaseRendezvous, MetricsNowUs() - rdzv_start_us);
  LOG_DEBUG("rank %d: control+data planes up (size=%d, epoch=%lld, "
            "tree_fanout=%d)", rank, size, (long long)cfg_.epoch,
            cfg_.tree_fanout);
  return Status::OK();
}

Status Controller::InitializeFromFds(
    std::vector<int> control_fds, std::vector<int> peer_fds,
    int tree_parent_fd, std::vector<std::pair<int, int>> tree_children) {
  control_fds_ = std::move(control_fds);
  if (TreeEnabled()) {
    if (cfg_.rank == 0) {
      for (int c : TreeChildren(0)) {
        tree_children_.emplace_back(c, control_fds_[c]);
      }
    } else {
      if (tree_parent_fd >= 0) {
        tree_parent_fd_ = tree_parent_fd;
        tree_owned_fds_.push_back(tree_parent_fd);
      } else {
        tree_parent_fd_ = control_fds_[0];  // parent is the coordinator
      }
      tree_children_ = std::move(tree_children);
      for (auto& kv : tree_children_) tree_owned_fds_.push_back(kv.second);
    }
  }
  data_plane_ = std::make_unique<DataPlane>(cfg_.rank, cfg_.size,
                                            std::move(peer_fds));
  return Status::OK();
}

std::vector<int32_t> Controller::MembersOf(int32_t process_set_id) const {
  if (process_set_id == 0 || cfg_.process_sets == nullptr) {
    std::vector<int32_t> all(cfg_.size);
    for (int i = 0; i < cfg_.size; i++) all[i] = i;
    return all;
  }
  return cfg_.process_sets->Ranks(process_set_id);
}

void Controller::MaybePromote(const std::string& key, PendingTensor& pt) {
  if (pt.queued) return;
  std::vector<int32_t> members =
      MembersOf(pt.requests.front().process_set_id);
  // Unknown/removed set, or a submitter outside the set: promote
  // immediately so BuildResponse can surface an ERROR instead of the
  // tensor silently pending forever (set members would never cover it).
  if (!members.empty()) {
    bool foreign = false;
    for (int32_t seen : pt.ranks_seen) {
      bool member = false;
      for (int32_t r : members) member = member || r == seen;
      foreign = foreign || !member;
    }
    if (!foreign) {
      for (int32_t r : members) {
        if (!pt.ranks_seen.count(r) && !joined_ranks_.count(r)) return;
      }
    }
  }
  pt.queued = true;
  const Request& first = pt.requests.front();
  // Ranks disagreeing on the grouping must surface BuildResponse's
  // mismatch ERROR, not sit in group_table_ waiting for members that
  // will never arrive — promote such keys directly.
  for (const auto& req : pt.requests) {
    if (req.group_id != first.group_id ||
        req.group_size != first.group_size) {
      ready_queue_.push_back(key);
      return;
    }
  }
  if (first.group_id >= 0 && first.group_size > 1) {
    // Hold group members until the whole group is ready, then release
    // them contiguously so FuseResponses emits one pure group response.
    std::string gkey = std::to_string(first.process_set_id) + ':' +
                       std::to_string(first.group_id);
    GroupState& gs = group_table_[gkey];
    gs.size = first.group_size;
    gs.ready_keys.push_back(key);
    if ((int32_t)gs.ready_keys.size() >= gs.size) {
      for (auto& k : gs.ready_keys) ready_queue_.push_back(k);
      group_table_.erase(gkey);
    }
    return;
  }
  ready_queue_.push_back(key);
}

// Negotiation state is keyed by (process set, name) so disjoint sets can
// run same-named collectives concurrently — the reference gets this from
// per-process-set controllers (process_set.h). '\x1f' cannot appear in a
// Python-supplied tensor name.
std::string Controller::TableKey(const Request& req) {
  return req.tensor_name + '\x1f' + std::to_string(req.process_set_id);
}

void Controller::HandleRequestList(const RequestList& list, int from_rank) {
  if (list.shutdown) shutdown_flags_[from_rank] = true;
  bool new_join = false;
  for (const auto& req : list.requests) {
    if (req.request_type == RequestType::JOIN) {
      // Reference analog: controller.cc join accounting (EnqueueJoin).
      if (!joined_ranks_.count(req.request_rank)) {
        joined_ranks_.insert(req.request_rank);
        last_joined_rank_ = req.request_rank;
        new_join = true;
      }
      continue;
    }
    auto& pt = message_table_[TableKey(req)];
    if (pt.ranks_seen.empty()) {
      pt.first_seen = std::chrono::steady_clock::now();
      pt.first_round = round_;
    }
    if (pt.ranks_seen.count(req.request_rank)) continue;  // duplicate
    pt.ranks_seen.insert(req.request_rank);
    pt.requests.push_back(req);
    bool was_queued = pt.queued;
    MaybePromote(TableKey(req), pt);
    if (!was_queued && pt.queued && pt.ranks_seen.size() > 1 &&
        round_ > pt.first_round) {
      // This request completed readiness in a LATER round than the
      // first arrival: its rank genuinely kept the tensor waiting, and
      // first->last spread is the negotiation skew. Same-round
      // completions are not attributable (the gather's fixed rank
      // order would masquerade as lateness). Aggregated per rank this
      // is the coordinator's live straggler table (the trace-merge
      // report computes the same offline).
      GlobalMetrics().RecordStraggler(
          req.request_rank,
          std::chrono::duration_cast<std::chrono::microseconds>(
              std::chrono::steady_clock::now() - pt.first_seen)
              .count());
    }
  }
  if (new_join) {
    // A new join can complete readiness for any pending tensor.
    for (auto& kv : message_table_) MaybePromote(kv.first, kv.second);
  }
}

Response Controller::BuildResponse(const std::string& key) {
  auto& pt = message_table_[key];
  const Request& first = pt.requests.front();
  Response res;
  res.tensor_names = {first.tensor_name};
  res.tensor_type = first.tensor_type;
  res.reduce_op = first.reduce_op;
  res.root_rank = first.root_rank;
  res.process_set_id = first.process_set_id;
  res.device = first.device;
  res.group_id = first.group_id;
  res.tensor_shapes.push_back((int64_t)first.tensor_shape.size());
  res.tensor_shapes.insert(res.tensor_shapes.end(),
                           first.tensor_shape.begin(),
                           first.tensor_shape.end());
  std::vector<int32_t> members = MembersOf(first.process_set_id);
  if (members.empty()) {
    res.response_type = Response::ResponseType::ERROR;
    res.error_message =
        "tensor " + first.tensor_name + ": unknown process set " +
        std::to_string(first.process_set_id) +
        " (add_process_set must complete on every rank first)";
    return res;
  }
  for (const auto& req : pt.requests) {
    bool member = false;
    for (int32_t r : members) member = member || r == req.request_rank;
    if (!member) {
      res.response_type = Response::ResponseType::ERROR;
      res.error_message =
          "tensor " + first.tensor_name + ": rank " +
          std::to_string(req.request_rank) + " is not a member of process "
          "set " + std::to_string(first.process_set_id);
      return res;
    }
  }
  // A member not in ranks_seen can only be covered by a join; alltoall
  // needs real splits from every member, so that combination is an error.
  bool member_joined = false;
  for (int32_t r : members) {
    if (!pt.ranks_seen.count(r)) member_joined = true;
  }
  if (member_joined && first.request_type == RequestType::ALLTOALL) {
    res.response_type = Response::ResponseType::ERROR;
    res.error_message = "tensor " + first.tensor_name +
                        ": alltoall is not supported with joined ranks";
    return res;
  }

  // Cross-rank validation.
  // Reference analog: Controller::ConstructResponse error paths.
  std::string err;
  for (const auto& req : pt.requests) {
    if (req.request_type != first.request_type) {
      err = "mismatched collective types across ranks";
    } else if (req.tensor_type != first.tensor_type) {
      err = "mismatched tensor dtypes across ranks";
    } else if (req.process_set_id != first.process_set_id) {
      err = "mismatched process sets across ranks";
    } else if (req.device != first.device) {
      err = "mismatched device placement across ranks";
    } else if (req.group_id != first.group_id ||
               req.group_size != first.group_size) {
      err = "mismatched allreduce grouping across ranks (grouped calls "
            "must happen in the same order on every rank)";
    } else if (req.request_type == RequestType::ALLREDUCE ||
               req.request_type == RequestType::BROADCAST ||
               req.request_type == RequestType::REDUCESCATTER) {
      if (!ShapesMatch(req.tensor_shape, first.tensor_shape, false)) {
        err = "mismatched tensor shapes across ranks";
      }
      if (req.request_type == RequestType::BROADCAST &&
          req.root_rank != first.root_rank) {
        err = "mismatched broadcast root ranks";
      }
    } else if (req.request_type == RequestType::ALLGATHER ||
               req.request_type == RequestType::ALLTOALL) {
      if (!ShapesMatch(req.tensor_shape, first.tensor_shape, true)) {
        err = "mismatched tensor shapes (non-first dims) across ranks";
      }
      // Device alltoall is equal-split (one static XLA program): every
      // rank must contribute the same first dim too.
      if (req.request_type == RequestType::ALLTOALL && first.device == 1 &&
          !ShapesMatch(req.tensor_shape, first.tensor_shape, false)) {
        err = "device alltoall requires identical shapes on every rank "
              "(ragged splits ride the host path)";
      }
    }
    if (!err.empty()) break;
  }
  if (!err.empty()) {
    res.response_type = Response::ResponseType::ERROR;
    res.error_message = "tensor " + first.tensor_name + ": " + err;
    return res;
  }

  switch (first.request_type) {
    case RequestType::ALLREDUCE:
      res.response_type = Response::ResponseType::ALLREDUCE;
      break;
    case RequestType::ALLGATHER: {
      res.response_type = Response::ResponseType::ALLGATHER;
      // Per-member first-dim sizes in set order (joined members stay 0).
      std::vector<int32_t> members = MembersOf(first.process_set_id);
      res.tensor_sizes.assign(members.size(), 0);
      for (const auto& req : pt.requests) {
        for (size_t i = 0; i < members.size(); i++) {
          if (members[i] == req.request_rank) {
            res.tensor_sizes[i] =
                req.tensor_shape.empty() ? 1 : req.tensor_shape[0];
          }
        }
      }
      break;
    }
    case RequestType::BROADCAST:
      res.response_type = Response::ResponseType::BROADCAST;
      break;
    case RequestType::ALLTOALL:
      res.response_type = Response::ResponseType::ALLTOALL;
      break;
    case RequestType::REDUCESCATTER:
      res.response_type = Response::ResponseType::REDUCESCATTER;
      break;
    case RequestType::BARRIER:
      res.response_type = Response::ResponseType::BARRIER;
      break;
    case RequestType::JOIN:
      // JOIN never reaches BuildResponse: HandleRequestList diverts it to
      // joined_ranks_ and FuseResponses emits the JOIN response directly.
      res.response_type = Response::ResponseType::ERROR;
      res.error_message = "internal: JOIN request in BuildResponse";
      break;
  }
  return res;
}

ResponseList Controller::FuseResponses() {
  ResponseList list;
  while (!ready_queue_.empty()) {
    std::string key = ready_queue_.front();
    ready_queue_.pop_front();
    Response res = BuildResponse(key);
    const Request& first = message_table_[key].requests.front();
    int64_t bytes = 1;
    for (auto d : first.tensor_shape) bytes *= d;
    bytes *= DataTypeSize(first.tensor_type);
    // Tensor fusion: keep folding subsequent ready ALLREDUCEs of the same
    // dtype/process-set into this response while under the threshold.
    // Reference analog: Controller::FuseResponses + fusion_buffer_manager.
    // Adasum is per-gradient (the combine normalizes per tensor), so those
    // responses stay unfused. Reference analog: adasum.h takes per-tensor
    // counts inside the fused buffer; we keep v1 simpler.
    if (res.response_type == Response::ResponseType::ALLREDUCE &&
        first.reduce_op != ReduceOp::ADASUM) {
      while (!ready_queue_.empty()) {
        const std::string& next_key = ready_queue_.front();
        auto& npt = message_table_[next_key];
        const Request& nreq = npt.requests.front();
        // Atomic groups fuse completely (no threshold) and stay PURE —
        // never mixed with other tensors — so the response is exactly
        // the group and can be skipped by the cache as a unit.
        bool same_group = first.group_id >= 0 &&
                          nreq.group_id == first.group_id &&
                          nreq.process_set_id == first.process_set_id;
        if (first.group_id >= 0 && !same_group) break;
        if (first.group_id < 0 && nreq.group_id >= 0) break;
        if (nreq.request_type != RequestType::ALLREDUCE ||
            !FusableAllreducePair(nreq.tensor_type, nreq.process_set_id,
                                  nreq.reduce_op, nreq.device,
                                  first.tensor_type, first.process_set_id,
                                  first.reduce_op, first.device)) {
          break;
        }
        Response nres = BuildResponse(next_key);
        if (nres.response_type == Response::ResponseType::ERROR) break;
        int64_t nbytes = 1;
        for (auto d : nreq.tensor_shape) nbytes *= d;
        nbytes *= DataTypeSize(nreq.tensor_type);
        if (!same_group &&
            (bytes >= cfg_.fusion_threshold_bytes ||
             bytes + nbytes > cfg_.fusion_threshold_bytes)) {
          break;
        }
        res.tensor_names.push_back(nreq.tensor_name);
        res.tensor_shapes.push_back((int64_t)nreq.tensor_shape.size());
        res.tensor_shapes.insert(res.tensor_shapes.end(),
                                 nreq.tensor_shape.begin(),
                                 nreq.tensor_shape.end());
        bytes += nbytes;
        message_table_.erase(next_key);
        ready_queue_.pop_front();
      }
    }
    message_table_.erase(key);
    list.responses.push_back(std::move(res));
  }
  // All ranks joined: complete every rank's pending join.
  // Reference analog: controller.cc join completion (last_joined_rank).
  if ((int)joined_ranks_.size() == cfg_.size) {
    Response join;
    join.response_type = Response::ResponseType::JOIN;
    join.tensor_names = {"__join__"};
    join.last_joined_rank = last_joined_rank_;
    list.responses.push_back(std::move(join));
    joined_ranks_.clear();
    last_joined_rank_ = -1;
  }
  return list;
}

RequestList Controller::BuildRequestList(std::vector<Request> requests,
                                         bool should_shutdown) {
  RequestList my_list;
  my_list.shutdown = should_shutdown;
  if (!resubmit_.empty()) {
    // Requests whose cached position was evicted mid-flight renegotiate now.
    requests.insert(requests.begin(),
                    std::make_move_iterator(resubmit_.begin()),
                    std::make_move_iterator(resubmit_.end()));
    resubmit_.clear();
  }
  for (auto& req : requests) {
    if (req.request_type == RequestType::JOIN) {
      my_list.requests.push_back(std::move(req));
      continue;
    }
    int32_t pos = -1;
    switch (cache_.Lookup(req, &pos)) {
      case ResponseCache::LookupResult::HIT:
        my_list.cache_hits.push_back(pos);
        inflight_hits_[pos] = std::move(req);
        break;
      case ResponseCache::LookupResult::INVALID:
        my_list.cache_invalid.push_back(pos);
        my_list.requests.push_back(std::move(req));
        break;
      case ResponseCache::LookupResult::MISS:
        my_list.requests.push_back(std::move(req));
        break;
    }
  }
  return my_list;
}

void Controller::HandleCacheBits(const RequestList& list, int from_rank,
                                 std::vector<int64_t>* evictions) {
  for (int64_t pos : list.cache_invalid) {
    if (std::find(evictions->begin(), evictions->end(), pos) ==
        evictions->end()) {
      evictions->push_back(pos);
    }
    bit_table_.erase((int32_t)pos);
  }
  for (int64_t pos : list.cache_hits) {
    // Stale bits (position evicted this cycle, or by an earlier eviction the
    // sender raced with) are dropped; the sender resubmits a full request
    // when it processes the broadcast eviction.
    if (!cache_.Has((int32_t)pos)) continue;
    if (std::find(evictions->begin(), evictions->end(), pos) !=
        evictions->end()) {
      continue;
    }
    auto& pb = bit_table_[(int32_t)pos];
    if (pb.ranks.empty()) {
      pb.first_seen = std::chrono::steady_clock::now();
      pb.first_round = round_;
    }
    if (pb.ranks.insert(from_rank).second) pb.last_rank = from_rank;
  }
}

void Controller::CollectCacheHits(ResponseList* list) {
  if (bit_table_.empty()) return;
  std::vector<int32_t> pending;
  pending.reserve(bit_table_.size());
  for (auto& kv : bit_table_) pending.push_back(kv.first);
  std::sort(pending.begin(), pending.end());
  std::vector<int32_t> completed;
  for (int32_t pos : pending) {
    const Response& r = cache_.Get(pos);
    bool done = true;
    for (int32_t m : MembersOf(r.process_set_id)) {
      if (!bit_table_[pos].ranks.count(m) && !joined_ranks_.count(m)) {
        done = false;
        break;
      }
    }
    if (done) {
      completed.push_back(pos);
      const PendingBits& pb = bit_table_[pos];
      if (pb.ranks.size() > 1 && round_ > pb.first_round) {
        // Steady-state (bitvector) stragglers matter most: a training
        // loop spends nearly every cycle here, so skew measured only on
        // full negotiations would go blind after warmup.
        GlobalMetrics().RecordStraggler(
            pb.last_rank,
            std::chrono::duration_cast<std::chrono::microseconds>(
                std::chrono::steady_clock::now() - pb.first_seen)
                .count());
      }
    }
  }
  // Group consecutive fusable allreduce hits; every rank rebuilds the same
  // fused Response from the group. Reference analog: cached responses join
  // the same FuseResponses path (controller.cc); here the coordinator owns
  // the grouping so the fusion threshold needs no cross-rank sync.
  size_t i = 0;
  while (i < completed.size()) {
    const Response& r0 = cache_.Get(completed[i]);
    int64_t group = 1;
    if (r0.response_type == Response::ResponseType::ALLREDUCE) {
      int64_t bytes = CachedEntryBytes(r0);
      while (i + group < completed.size()) {
        const Response& rn = cache_.Get(completed[i + group]);
        if (rn.response_type != Response::ResponseType::ALLREDUCE ||
            !FusableAllreducePair(rn.tensor_type, rn.process_set_id,
                                  rn.reduce_op, rn.device, r0.tensor_type,
                                  r0.process_set_id, r0.reduce_op,
                                  r0.device)) {
          break;
        }
        int64_t nb = CachedEntryBytes(rn);
        if (bytes + nb > cfg_.fusion_threshold_bytes) break;
        bytes += nb;
        group++;
      }
    }
    for (int64_t k = 0; k < group; k++) {
      list->cache_hit_positions.push_back(completed[i + k]);
      bit_table_.erase(completed[i + k]);
    }
    list->cache_hit_group_sizes.push_back(group);
    i += group;
  }
}

void Controller::ApplyCacheVerdicts(ResponseList* out) {
  for (int64_t pos : out->cache_evictions) {
    cache_.Evict((int32_t)pos);
    auto it = inflight_hits_.find((int32_t)pos);
    if (it != inflight_hits_.end()) {
      resubmit_.push_back(std::move(it->second));
      inflight_hits_.erase(it);
    }
  }
  std::vector<Response> hit_responses;
  size_t idx = 0;
  for (int64_t gs : out->cache_hit_group_sizes) {
    if (idx + (size_t)gs > out->cache_hit_positions.size()) break;
    int32_t pos0 = (int32_t)out->cache_hit_positions[idx];
    if (!cache_.Has(pos0)) {  // cannot happen with consistent caches
      idx += gs;
      continue;
    }
    Response merged = cache_.Get(pos0);
    inflight_hits_.erase(pos0);
    for (int64_t k = 1; k < gs; k++) {
      int32_t pos = (int32_t)out->cache_hit_positions[idx + k];
      const Response& nxt = cache_.Get(pos);
      merged.tensor_names.push_back(nxt.tensor_names[0]);
      merged.tensor_shapes.insert(merged.tensor_shapes.end(),
                                  nxt.tensor_shapes.begin(),
                                  nxt.tensor_shapes.end());
      inflight_hits_.erase(pos);
    }
    idx += gs;
    hit_responses.push_back(std::move(merged));
  }
  // Fresh negotiated responses become cache entries for the next cycle —
  // identical insertion order on every rank (driven by the broadcast bytes).
  cache_.InsertFromResponses(out->responses);
  if (!hit_responses.empty()) {
    // Execution order: steady-state hits first, then new negotiations.
    hit_responses.insert(hit_responses.end(),
                         std::make_move_iterator(out->responses.begin()),
                         std::make_move_iterator(out->responses.end()));
    out->responses = std::move(hit_responses);
  }
}

void Controller::CheckForStalledTensors() {
  if (!cfg_.stall_check_enabled) return;
  auto now = std::chrono::steady_clock::now();
  // Check at half the configured warning time (capped at 10s) so a
  // sub-10s HOROVOD_STALL_CHECK_TIME fires on schedule instead of
  // silently rounding up to the next 10s boundary. Floored at 100ms:
  // a zero/tiny warning time must not turn the sweep into a per-cycle
  // log flood (default cycle time is 1ms).
  double interval =
      std::min(10.0, std::max(0.1, cfg_.stall_warning_secs / 2.0));
  if (std::chrono::duration<double>(now - last_stall_check_).count() <
      interval) {
    return;
  }
  last_stall_check_ = now;
  for (auto& kv : message_table_) {
    double waited =
        std::chrono::duration<double>(now - kv.second.first_seen).count();
    if (waited > cfg_.stall_warning_secs) {
      std::ostringstream missing;
      int n_missing = 0;
      for (int32_t r :
           MembersOf(kv.second.requests.front().process_set_id)) {
        if (!kv.second.ranks_seen.count(r) && !joined_ranks_.count(r)) {
          missing << r << " ";
          n_missing++;
        }
      }
      GlobalEvents().Record(EventType::kStall, (int32_t)waited,
                            n_missing);
      LOG_WARN(
          "Stall detected: tensor %s has waited %.0fs; missing ranks: %s"
          " (one or more ranks did not submit this collective)",
          kv.second.requests.front().tensor_name.c_str(), waited,
          missing.str().c_str());
    }
  }
  // Cache-hit bits stall the same way full requests do.
  for (auto& kv : bit_table_) {
    double waited =
        std::chrono::duration<double>(now - kv.second.first_seen).count();
    if (waited > cfg_.stall_warning_secs && cache_.Has(kv.first)) {
      const Response& r = cache_.Get(kv.first);
      std::ostringstream missing;
      int n_missing = 0;
      for (int32_t m : MembersOf(r.process_set_id)) {
        if (!kv.second.ranks.count(m) && !joined_ranks_.count(m)) {
          missing << m << " ";
          n_missing++;
        }
      }
      // Steady-state (cache-bit) stalls are the common production
      // case — they must reach the flight recorder like full-request
      // stalls do.
      GlobalEvents().Record(EventType::kStall, (int32_t)waited,
                            n_missing);
      LOG_WARN(
          "Stall detected: cached tensor %s has waited %.0fs; missing "
          "ranks: %s (one or more ranks did not submit this collective)",
          r.tensor_names[0].c_str(), waited, missing.str().c_str());
    }
  }
}

Status Controller::ComputeResponseList(std::vector<Request> requests,
                                       bool should_shutdown,
                                       ResponseList* out) {
  if (cfg_.size == 1) {
    RequestList my_list;
    my_list.requests = std::move(requests);
    my_list.shutdown = should_shutdown;
    HandleRequestList(my_list, 0);
    *out = FuseResponses();
    out->shutdown = should_shutdown;
    out->epoch = cfg_.epoch;
    return Status::OK();
  }

  RequestList my_list = BuildRequestList(std::move(requests), should_shutdown);
  my_list.epoch = cfg_.epoch;
  my_list.rank = cfg_.rank;
  // Control-plane deadline: the per-cycle gather/bcast round IS the
  // heartbeat (idle workers still send an empty list every cycle), so
  // bounding each frame bounds failure detection.
  const int64_t hb_ms = cfg_.heartbeat_timeout_ms > 0
                            ? cfg_.heartbeat_timeout_ms
                            : WireTimeoutMs();
  // A worker waiting for the broadcast is implicitly waiting on EVERY
  // other rank's frame reaching the coordinator first — the sequential
  // gather may legitimately take up to (size-1) per-peer deadlines
  // with benign stragglers, so the worker's recv budget scales with
  // size (a spurious coordinator-death verdict here would tear down a
  // healthy ring).
  const int64_t worker_recv_ms = hb_ms <= 0 ? 0 : hb_ms * cfg_.size;

  if (cfg_.rank == 0) {
    round_++;
    std::vector<int64_t> evictions;
    HandleCacheBits(my_list, 0, &evictions);
    HandleRequestList(my_list, 0);
    // The gather is THE O(N) coordinator suspect at large worlds:
    // per-cycle latency lands on the control_phase profile either way,
    // so the flat-vs-tree scaling curves come from one instrumentation
    // site (docs/scale.md).
    const int64_t gather_t0 = MetricsNowUs();
    if (TreeEnabled()) {
      Status s = TreeCoordinatorGather(hb_ms, &evictions);
      if (!s.ok()) {
        BroadcastFaultNotice(s);
        return s;
      }
    } else {
      for (int r = 1; r < cfg_.size; r++) {
        std::string frame;
        Status s = RecvFrame(control_fds_[r], &frame, hb_ms);
        RequestList rl;
        if (s.ok()) {
          GlobalMetrics().gather_frames.fetch_add(1,
                                                  std::memory_order_relaxed);
          s = ParseRequestList(frame, &rl);
          if (s.ok() && rl.epoch != cfg_.epoch) {
            s = Status::PeerFailure(
                r, "rank " + std::to_string(r) + " sent a stale-epoch " +
                       "request (epoch " + std::to_string(rl.epoch) +
                       ", current " + std::to_string(cfg_.epoch) + ")");
          }
        } else if (!s.peer_failure()) {
          s = Status::PeerFailure(r, "control-plane gather from rank " +
                                         std::to_string(r) +
                                         " failed: " + s.reason());
        }
        if (!s.ok()) {
          BroadcastFaultNotice(s);
          return s;
        }
        HandleCacheBits(rl, r, &evictions);
        HandleRequestList(rl, r);
      }
    }
    const int64_t gather_dur_us = MetricsNowUs() - gather_t0;
    CheckForStalledTensors();
    ResponseList list;
    list.epoch = cfg_.epoch;
    list.cache_evictions = std::move(evictions);
    // Hits must complete BEFORE FuseResponses: the all-ranks-joined cycle
    // clears joined_ranks_ there, and pending bits rely on join coverage the
    // same way MaybePromote does for full requests.
    CollectCacheHits(&list);
    list.responses = FuseResponses().responses;
    // Idle cycles (nothing negotiated, no cache traffic) stay on the
    // gather/broadcast latency histograms but skip the ring events —
    // the flight recorder keeps its tail for events that carry signal
    // (RecordControlPhase).
    const bool busy_cycle = !list.responses.empty() ||
                            !list.cache_hit_positions.empty() ||
                            !list.cache_evictions.empty();
    RecordControlPhase(kPhaseGather, gather_dur_us, busy_cycle);
    list.shutdown = std::all_of(shutdown_flags_.begin(), shutdown_flags_.end(),
                                [](bool b) { return b; });
    list.fusion_threshold_bytes = bcast_fusion_bytes_;
    list.cycle_time_ms = bcast_cycle_ms_;
    list.ring_chunk_bytes = bcast_ring_chunk_bytes_;
    list.wire_compression = bcast_wire_compression_;
    list.hier_split = bcast_hier_split_;
    list.wire_channels = bcast_wire_channels_;
    // Serialize before ApplyCacheVerdicts: the broadcast carries only
    // negotiated responses + cache verdicts; every rank (this one included)
    // then rebuilds hit responses and inserts new entries identically.
    std::string payload = SerializeResponseList(list);
    const int64_t bcast_t0 = MetricsNowUs();
    // Tree mode: send to the direct children only (they relay down);
    // flat mode: one frame per worker.
    std::vector<std::pair<int, int>> targets;
    if (TreeEnabled()) {
      targets = tree_children_;
    } else {
      for (int r = 1; r < cfg_.size; r++) {
        targets.emplace_back(r, control_fds_[r]);
      }
    }
    for (auto& target : targets) {
      Status s = SendFrame(target.second, payload, hb_ms);
      if (!s.ok()) {
        if (!s.peer_failure()) {
          s = Status::PeerFailure(
              target.first, "control-plane broadcast to rank " +
                                std::to_string(target.first) +
                                " failed: " + s.reason());
        }
        BroadcastFaultNotice(s);
        return s;
      }
    }
    RecordControlPhase(kPhaseBroadcast, MetricsNowUs() - bcast_t0,
                       busy_cycle);
    *out = std::move(list);
    ApplyCacheVerdicts(out);
    return Status::OK();
  }

  if (TreeEnabled()) {
    return TreeWorkerCycle(my_list, hb_ms, worker_recv_ms, out);
  }

  // Worker: one send + one receive per cycle (the gather/bcast round).
  Status s = SendFrame(control_fds_[0], SerializeRequestList(my_list),
                       hb_ms);
  if (s.ok()) {
    std::string frame;
    s = RecvFrame(control_fds_[0], &frame, worker_recv_ms);
    if (s.ok()) s = ParseResponseList(frame, out);
  }
  if (!s.ok()) {
    // The coordinator itself is the casualty (or unreachable): a
    // worker's only control peer is rank 0.
    if (!s.peer_failure()) {
      s = Status::PeerFailure(0, "control-plane round with coordinator "
                                 "failed: " + s.reason());
    }
    return s;
  }
  if (out->epoch != cfg_.epoch) {
    return Status::PeerFailure(
        0, "coordinator response at stale epoch " +
               std::to_string(out->epoch) + " (current " +
               std::to_string(cfg_.epoch) + ")");
  }
  if (!out->fault_ranks.empty()) {
    // Coordinator-relayed fault notice: fail fast with its attribution
    // instead of waiting out our own wire deadline against the broken
    // ring. The full set stays in out->fault_ranks for the caller.
    GlobalEvents().Record(EventType::kFaultNotice,
                          (int32_t)out->fault_ranks[0], 1);
    return Status::PeerFailure(
        (int)out->fault_ranks[0],
        "coordinator reported peer failure (rank " +
            std::to_string(out->fault_ranks[0]) + ") at epoch " +
            std::to_string(cfg_.epoch));
  }
  ApplyCacheVerdicts(out);
  return Status::OK();
}

Status Controller::TreeCoordinatorGather(int64_t hb_ms,
                                         std::vector<int64_t>* evictions) {
  std::vector<bool> seen(cfg_.size, false);
  seen[0] = true;
  int got = 1;
  for (auto& child : tree_children_) {
    const int crank = child.first;
    std::string bundle;
    // The child's bundle carries its whole subtree, so the deadline
    // scales with the subtree's aggregate budget (failure detection in
    // tree mode is bounded by the deepest subtree, not one frame).
    Status s = RecvFrame(child.second, &bundle,
                         hb_ms <= 0 ? hb_ms : hb_ms * SubtreeSize(crank));
    if (!s.ok()) {
      if (!s.peer_failure()) {
        s = Status::PeerFailure(
            crank, "control-tree gather from rank " +
                       std::to_string(crank) + " failed: " + s.reason());
      }
      return s;
    }
    GlobalMetrics().gather_frames.fetch_add(1, std::memory_order_relaxed);
    std::vector<std::string> frames;
    if (!SplitBundle(bundle, &frames)) {
      return Status::PeerFailure(
          crank, "malformed control-tree bundle from rank " +
                     std::to_string(crank));
    }
    for (auto& frame : frames) {
      RequestList rl;
      Status ps = ParseRequestList(frame, &rl);
      if (!ps.ok()) {
        return Status::PeerFailure(
            crank, "unparseable control-tree entry via rank " +
                       std::to_string(crank) + ": " + ps.reason());
      }
      if (rl.rank < 1 || rl.rank >= cfg_.size || seen[rl.rank]) {
        return Status::PeerFailure(
            crank, "control-tree entry with bad/duplicate origin rank " +
                       std::to_string(rl.rank) + " via rank " +
                       std::to_string(crank));
      }
      if (rl.epoch != cfg_.epoch) {
        return Status::PeerFailure(
            rl.rank, "rank " + std::to_string(rl.rank) +
                         " sent a stale-epoch request (epoch " +
                         std::to_string(rl.epoch) + ", current " +
                         std::to_string(cfg_.epoch) + ")");
      }
      seen[rl.rank] = true;
      got++;
      HandleCacheBits(rl, rl.rank, evictions);
      HandleRequestList(rl, rl.rank);
    }
  }
  if (got < cfg_.size) {
    // A relay forwarded a partial bundle (one of its children died):
    // the first absent origin IS the casualty — or its subtree root.
    int missing = 1;
    while (missing < cfg_.size && seen[missing]) missing++;
    return Status::PeerFailure(
        missing, "control-tree gather missing rank " +
                     std::to_string(missing) + " (" +
                     std::to_string(cfg_.size - got) + " absent)");
  }
  return Status::OK();
}

Status Controller::TreeWorkerCycle(const RequestList& my_list,
                                   int64_t hb_ms, int64_t worker_recv_ms,
                                   ResponseList* out) {
  // Gather: own entry first, then each child's bundle verbatim.
  std::string bundle;
  AppendBundleEntry(&bundle, SerializeRequestList(my_list));
  Status child_failure = Status::OK();
  for (auto& child : tree_children_) {
    const int crank = child.first;
    std::string child_bundle;
    Status s = RecvFrame(child.second, &child_bundle,
                         hb_ms <= 0 ? hb_ms : hb_ms * SubtreeSize(crank));
    if (!s.ok()) {
      // Keep gathering and FORWARD what arrived: the coordinator then
      // names the exact missing member instead of writing off this
      // whole subtree on a timeout.
      if (!s.peer_failure()) {
        s = Status::PeerFailure(
            crank, "control-tree gather from rank " +
                       std::to_string(crank) + " failed: " + s.reason());
      }
      child_failure = s;
      continue;
    }
    bundle += child_bundle;  // entries are self-delimiting
  }
  Status s = SendFrame(tree_parent_fd_, bundle, hb_ms);
  if (!s.ok()) {
    if (!s.peer_failure()) {
      s = Status::PeerFailure(
          TreeParent(cfg_.rank),
          "control-tree relay to parent failed: " + s.reason());
    }
    return s;
  }

  // Response: receive from the parent and relay down FIRST — even when
  // a child already failed. The coordinator answers a partial gather
  // with a fault notice (over the star for depth-1 workers, relayed
  // here for deeper ones), and the SURVIVING children are blocked on
  // this relay: returning early would starve them for a full timeout.
  std::string frame;
  s = RecvFrame(tree_parent_fd_, &frame, worker_recv_ms);
  if (s.ok()) s = ParseResponseList(frame, out);
  if (!s.ok()) {
    if (!child_failure.ok()) return child_failure;
    if (!s.peer_failure()) {
      s = Status::PeerFailure(
          TreeParent(cfg_.rank),
          "control-tree round with parent failed: " + s.reason());
    }
    return s;
  }
  Status relay_failure = Status::OK();
  for (auto& child : tree_children_) {
    // Send errors to an already-failed child are expected; the first
    // failure on a HEALTHY child is reported after local processing.
    Status rs = SendFrame(child.second, frame, hb_ms);
    if (!rs.ok() && relay_failure.ok()) {
      relay_failure = Status::PeerFailure(
          child.first, "control-tree relay to rank " +
                           std::to_string(child.first) +
                           " failed: " + rs.reason());
    }
  }
  if (!child_failure.ok()) return child_failure;
  if (out->epoch != cfg_.epoch) {
    return Status::PeerFailure(
        0, "coordinator response at stale epoch " +
               std::to_string(out->epoch) + " (current " +
               std::to_string(cfg_.epoch) + ")");
  }
  if (!out->fault_ranks.empty()) {
    GlobalEvents().Record(EventType::kFaultNotice,
                          (int32_t)out->fault_ranks[0], 1);
    return Status::PeerFailure(
        (int)out->fault_ranks[0],
        "coordinator reported peer failure (rank " +
            std::to_string(out->fault_ranks[0]) + ") at epoch " +
            std::to_string(cfg_.epoch));
  }
  if (!relay_failure.ok()) return relay_failure;
  ApplyCacheVerdicts(out);
  return Status::OK();
}

void Controller::BroadcastFaultNotice(const Status& failure) {
  // Best-effort: tell every still-reachable worker the epoch is dead so
  // they stop within one control round instead of one wire timeout.
  // Send errors are ignored — the target may be the casualty itself.
  if (cfg_.rank != 0) return;
  GlobalEvents().Record(EventType::kFaultNotice, failure.fault_rank(), 0);
  ResponseList notice;
  notice.epoch = cfg_.epoch;
  notice.fault_ranks.push_back(failure.fault_rank());
  std::string payload = SerializeResponseList(notice);
  for (int r = 1; r < cfg_.size; r++) {
    if (failure.fault_rank() == r) continue;
    // Short leash: the ring is already broken, don't stack full
    // timeouts per peer while tearing down.
    SendFrame(control_fds_[r], payload, /*timeout_ms=*/1000);
  }
}

}  // namespace hvdtpu
