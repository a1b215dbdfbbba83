// Runtime metrics registry for the native core: per-op-class counters,
// latency histograms, fusion/cycle/cache accounting, and coordinator-side
// straggler attribution, exported as one JSON snapshot through
// hvdtpu_metrics_snapshot() (operations.cc).
//
// Reference analog: none in-core — upstream Horovod's only windows are the
// Chrome timeline and the autotune log. This registry is the live-counter
// layer those artifacts lack: everything the background loop already
// computes to make decisions (response-cache verdicts, fusion packing,
// cycle pacing, arrival order at the coordinator) becomes observable.
//
// Concurrency: recorders are called from the background coordination
// thread and (enqueue timestamps aside) never from API threads; the
// snapshot reader runs on an arbitrary API thread. All counters are
// relaxed atomics — a snapshot is a consistent-enough view, not a
// linearizable one — except the per-rank straggler table, which is small
// and mutex-guarded.

#ifndef HVDTPU_METRICS_H
#define HVDTPU_METRICS_H

#include <atomic>
#include <cstdarg>
#include <cstdint>
#include <mutex>
#include <string>
#include <vector>

namespace hvdtpu {

int64_t MetricsNowUs();  // steady-clock microseconds (monotonic)

// Control-plane phases profiled for large-world scaling (docs/scale.md):
// each is an O(N) suspect in the coordinator/elastic machinery, and the
// per-phase histograms below are how the scaling curves indict (or
// clear) them at 64-256 ranks. kPhaseParoleFreeze is recorded from
// Python (common/elastic.py) through the hvdtpu_record_phase C-ABI —
// the parole door lives above the core but its latency belongs on the
// same profile.
enum ControlPhase : int32_t {
  kPhaseRendezvous = 0,  // Controller::Initialize bootstrap fan-in
  kPhaseGather,          // coordinator: per-cycle request gather
  kPhaseBroadcast,       // coordinator: per-cycle response broadcast
  kPhaseProbeSweep,      // DataPlane::ProbeDeadPeers fault sweep
  kPhaseReinit,          // hvdtpu_reinit ring re-formation
  kPhaseParoleFreeze,    // parole-door freeze/poll (python side)
  kPhaseCount
};
const char* ControlPhaseName(int phase);

// Record one phase duration into the metrics histogram AND the event
// ring (EventType::kPhase) — one call keeps the two views consistent.
// `emit_event=false` updates only the histogram: the coordinator's
// idle negotiation cycles still belong on the latency profile, but two
// ring events per cycle would lap the flight recorder in seconds and
// evict the forensic tail the black box exists to keep.
void RecordControlPhase(int phase, int64_t dur_us, bool emit_event = true);

// Measure-then-format printf append (definition rationale in
// metrics.cc): the shared primitive for every JSON producer — fixed
// stack buffers silently truncate, i.e. corrupt, the output.
void AppendFmtV(std::string& out, const char* fmt, va_list args);

// Log2-bucketed microsecond histogram: bucket i holds values in
// [2^i, 2^(i+1)). Percentiles are read off the bucket CDF at upper bucket
// bounds — exact enough for latency triage, constant memory, lock-free.
class LatencyHistogram {
 public:
  static constexpr int kBuckets = 40;  // covers ~2^39 us (~6 days)

  void Record(int64_t us);
  void Reset();
  int64_t count() const { return count_.load(std::memory_order_relaxed); }
  // {"count":..,"sum_us":..,"min_us":..,"max_us":..,"p50_us":..,
  //  "p90_us":..,"p99_us":..}
  std::string Json() const;

 private:
  int64_t Percentile(double q, const int64_t* b, int64_t total) const;

  std::atomic<int64_t> count_{0};
  std::atomic<int64_t> sum_{0};
  std::atomic<int64_t> min_{0};  // valid only when count_ > 0
  std::atomic<int64_t> max_{0};
  std::atomic<int64_t> buckets_[kBuckets] = {};
};

// Counts for one op class on one plane (host ring / device XLA).
struct OpCounters {
  std::atomic<int64_t> responses{0};  // fused responses executed
  std::atomic<int64_t> tensors{0};    // tensors covered (>= responses)
  std::atomic<int64_t> bytes{0};      // payload bytes moved
};

class Metrics {
 public:
  // Indexed by Response::ResponseType (0..7; 7 = ERROR).
  static constexpr int kOpClasses = 8;

  OpCounters host_ops[kOpClasses];
  OpCounters device_ops[kOpClasses];

  LatencyHistogram negotiation_us;  // per-cycle ComputeResponseList wall
  LatencyHistogram queue_us;        // tensor enqueue -> execution start
  LatencyHistogram wire_us;         // one host transport call (ring span)
  LatencyHistogram straggler_skew_us;  // coordinator: first->last arrival
  // Elastic: how long the failing operation ran before the typed
  // PeerFailure surfaced (EOF ~ instant; stalls ~ the wire deadline).
  LatencyHistogram fault_detect_us;
  // Per-phase control-plane latency (ControlPhase above): the scaling
  // profile the simworld harness reads to indict O(N) suspects at
  // 64-256 ranks (docs/scale.md).
  LatencyHistogram control_phase_us[kPhaseCount];
  // Frames the coordinator received in its request gathers, one after
  // the other: size-1 a cycle from the flat star, its direct children's
  // bundles from the control tree. The count behind the gather
  // histogram's growth, free of the host's load (simworld reports it).
  std::atomic<int64_t> gather_frames{0};

  std::atomic<int64_t> cycles{0};
  std::atomic<int64_t> cycle_stalls{0};      // loop overran its budget
  std::atomic<int64_t> cycle_overrun_us{0};  // total overrun beyond budget

  std::atomic<int64_t> fused_responses{0};   // multi-tensor allreduces
  std::atomic<int64_t> fusion_fill_bytes{0};     // packed payload
  std::atomic<int64_t> fusion_capacity_bytes{0};  // threshold at pack time

  std::atomic<int64_t> errors{0};  // ERROR responses surfaced

  // Elastic fault accounting (docs/elastic.md): faults the loop stopped
  // on, successful ring re-formations (hvdtpu_reinit), and ranks fenced
  // out of re-formed rings (dead peers dropped at an epoch bump).
  std::atomic<int64_t> faults_detected{0};
  std::atomic<int64_t> faults_recovered{0};
  std::atomic<int64_t> ranks_blacklisted{0};
  // Self-healing accounting (docs/elastic.md "heal vs shrink vs
  // rejoin"): transfers that resumed IN PLACE after a stall or a CRC
  // NAK-resend (no fault recorded, no epoch bump), extra patience/
  // resend windows spent getting there, chunks that failed CRC32C
  // verification (HOROVOD_WIRE_CRC), and joiner slots absorbed by a
  // grow re-formation (blacklist parole).
  std::atomic<int64_t> wire_heals{0};
  std::atomic<int64_t> wire_retries{0};
  std::atomic<int64_t> crc_errors{0};
  std::atomic<int64_t> ranks_rejoined{0};

  // Host-ring transport accounting, kept SEPARATE from the per-op-class
  // logical payload bytes above: `wire_*_bytes` is what actually
  // crossed the transport, `wire_*_logical_bytes` what the same
  // traffic would be at full tensor width. They differ exactly by the
  // wire-compression saving (bf16-on-wire halves fp32 hops) — the pair
  // telemetry needs to keep wire_goodput_gbps and byte reconciliation
  // honest when HOROVOD_WIRE_COMPRESSION is on. Note the ring moves
  // ~2(N-1)/N x payload per rank, so wire_logical != ops.bytes either.
  std::atomic<int64_t> wire_tx_bytes{0};
  std::atomic<int64_t> wire_rx_bytes{0};
  std::atomic<int64_t> wire_tx_logical_bytes{0};
  std::atomic<int64_t> wire_rx_logical_bytes{0};

  // Cross-plane slice of the wire counters above (already included in
  // them): bytes that crossed the INTER-SLICE hop of the hierarchical
  // decomposition (DataPlane wire plane 1 — the DCN-priced fabric).
  // intra = total - cross; the pair is what lets telemetry reconcile
  // per-plane logical-vs-wire exactly (docs/redistribute.md).
  std::atomic<int64_t> wire_cross_tx_bytes{0};
  std::atomic<int64_t> wire_cross_rx_bytes{0};
  std::atomic<int64_t> wire_cross_tx_logical_bytes{0};
  std::atomic<int64_t> wire_cross_rx_logical_bytes{0};

  // Per-stripe-channel slice of the wire counters (HOROVOD_WIRE_-
  // CHANNELS, docs/wire.md): channel c's share of the chunk schedule,
  // with every unstriped path booked to channel 0 — so the buckets sum
  // EXACTLY to wire_tx/rx_bytes and a dead or slow channel shows as
  // imbalance instead of averaging away. Slot count mirrors
  // kMaxWireChannels (wire.h; static_assert in metrics.cc).
  static constexpr int kWireChannelSlots = 8;
  std::atomic<int64_t> wire_chan_tx_bytes[kWireChannelSlots] = {};
  std::atomic<int64_t> wire_chan_rx_bytes[kWireChannelSlots] = {};

  // Transport syscall accounting (docs/wire.md "Syscall budget"): one
  // increment per send()/recv() INVOCATION — including short writes,
  // EAGAIN spins, and CRC control frames — because the number ROADMAP
  // item 3 (io_uring kernel-bypass) must beat is calls issued, not
  // calls that moved payload. Same slicing conventions as the byte
  // counters: cross is the plane-1 slice of the totals, per-channel
  // buckets sum exactly to them (unstriped paths book channel 0).
  std::atomic<int64_t> wire_syscalls_tx{0};
  std::atomic<int64_t> wire_syscalls_rx{0};
  std::atomic<int64_t> wire_cross_syscalls_tx{0};
  std::atomic<int64_t> wire_cross_syscalls_rx{0};
  std::atomic<int64_t> wire_chan_syscalls_tx[kWireChannelSlots] = {};
  std::atomic<int64_t> wire_chan_syscalls_rx[kWireChannelSlots] = {};

  // Hot-path inline: one relaxed fetch_add per counter touched.
  void AccountWireSyscall(int plane, int channel, bool tx) {
    auto& total = tx ? wire_syscalls_tx : wire_syscalls_rx;
    total.fetch_add(1, std::memory_order_relaxed);
    if (plane == 1) {
      auto& cross = tx ? wire_cross_syscalls_tx : wire_cross_syscalls_rx;
      cross.fetch_add(1, std::memory_order_relaxed);
    }
    if (channel < 0 || channel >= kWireChannelSlots) channel = 0;
    auto* chan = tx ? wire_chan_syscalls_tx : wire_chan_syscalls_rx;
    chan[channel].fetch_add(1, std::memory_order_relaxed);
  }

  void AccountWire(int plane, int64_t tx, int64_t rx, int64_t tx_logical,
                   int64_t rx_logical);
  void AccountWireChannels(const int64_t* tx, const int64_t* rx);
  void RecordStraggler(int rank, int64_t skew_us);
  void Reset();

  // Runtime context the snapshot embeds alongside the counters (the
  // registry itself outlives init/shutdown; these belong to GlobalState).
  struct RuntimeInfo {
    bool initialized = false;
    int rank = -1, size = 0;
    int64_t fusion_threshold_bytes = 0;
    double cycle_time_ms = 0;
    int64_t ring_chunk_bytes = 0;
    bool wire_compression = false;
    int wire_codec = 0;  // 0 off, 1 bf16, 2 int8 blockwise
    // Stripe transport: active width (autotunable) vs sockets
    // established per neighbor pair (env, fixed per process).
    int64_t wire_channels = 1;
    int64_t wire_channels_established = 1;
    bool simd = true;  // HOROVOD_SIMD vectorized reduce/codec paths
    int64_t wire_timeout_ms = 0;
    int64_t wire_retry_attempts = 0;   // healing ladder depth
    int64_t wire_retry_backoff_ms = 0;
    bool wire_crc = false;             // per-chunk CRC32C framing
    int cross_plane = 0;       // HOROVOD_CROSS_PLANE (0 auto, 1 ici,
                               // 2 ring, 3 hier)
    int64_t hier_split = 0;    // active hierarchy split (0 = flat)
    bool cross_compression = false;  // bf16 on the cross hop only
    int64_t epoch = 0;  // current membership epoch (bumped by reinit)
    int64_t cache_hits = 0, cache_misses = 0, cache_entries = 0;
    int64_t cache_hit_bytes = 0;
  };
  std::string SnapshotJson(const RuntimeInfo& info) const;

 private:
  mutable std::mutex straggler_mutex_;
  std::vector<int64_t> straggler_counts_;  // index = rank arriving last
};

// Process-wide registry; survives shutdown/re-init so counters stay
// monotonic for the lifetime of the process (scrapers diff snapshots).
Metrics& GlobalMetrics();

// Per-step overlap ledger (docs/metrics.md "Overlap ledger"):
// interval-union math over the wire spans recorded inside one step
// window [hvdtpu_step_mark(1), hvdtpu_step_mark(0)]. Per plane
// (0 intra/flat, 1 cross-slice), per step:
//
//   total    = sum of wire-span durations (the serial wire cost)
//   exposed  = the part of each wire span that ran while an API
//              thread sat BLOCKED on the core (inside hvdtpu_wait —
//              the host had nothing better to do than watch the wire)
//   hidden   = total - exposed (wire time that ran while the host
//              kept computing/dispatching — the compute/collective
//              overlap win the jit-lane fusion work exists to move;
//              docs/fusion.md)
//
// The single background execution thread runs collectives strictly
// sequentially, so wire spans themselves never overlap in wall time —
// which is why the pre-fusion definition (union overlap among wire
// spans) read hidden == 0 on every real run. Exposure is therefore
// measured against the WAIT spans hvdtpu_wait records: a bulk-
// synchronous step (issue everything, then synchronize) exposes its
// whole wire total; a fused step whose collectives drain while the
// host dispatches the next compute segment hides it.
//
// exposed + hidden == total EXACTLY by construction (both are computed
// from the same clipped interval set) — the reconciliation the
// perf-smoke/reshard-smoke lanes assert against the wire_us histogram.
// overlap_efficiency = hidden / total (0 with no wire traffic).
//
// Concurrency: spans arrive from the background loop / reduce-worker
// threads (WireTally destructors), waits from blocking API threads,
// step marks from whichever API thread drives the loop — one small
// mutex; every call is O(spans in the open step) at worst, and the
// hot paths (AddSpan/AddWait) are O(1).
class OverlapLedger {
 public:
  void StepBegin(int64_t ts_us);
  // Close the open step: computes the per-plane union accounting over
  // the spans recorded since StepBegin. Returns the step duration in
  // us, or -1 when no step was open.
  int64_t StepEnd(int64_t ts_us);
  // One completed wire span. Outside any step window the duration is
  // booked as `unattributed` (still reconcilable against wire_us).
  void AddSpan(int plane, int64_t start_us, int64_t end_us);
  // One completed API-thread blocking interval (hvdtpu_wait entry ->
  // return). Wire time under the union of these is `exposed`; waits
  // are not wire time themselves, so outside-window waits are simply
  // dropped (no unattributed contract to keep).
  void AddWait(int64_t start_us, int64_t end_us);
  void Reset();
  // The "overlap" object embedded in the snapshot's wire section:
  // {"steps":..,"unattributed_us":..,"exposed_wire_ms":..,
  //  "hidden_wire_ms":..,"overlap_efficiency":..,
  //  "intra":{exposed_us,hidden_us,total_us,overlap_efficiency,
  //           last_exposed_us,last_hidden_us,last_total_us},
  //  "cross":{...}}
  std::string Json() const;

  // Open-window span cap: beyond this, AddSpan books straight to
  // unattributed (a never-closed window must not grow without bound).
  static constexpr int64_t kMaxSpansPerPlane = 65536;

 private:
  struct PlaneLedger {
    int64_t exposed_us = 0, hidden_us = 0, total_us = 0;  // cumulative
    int64_t last_exposed_us = 0, last_hidden_us = 0,      // last step
        last_total_us = 0;
  };
  mutable std::mutex mu_;
  bool open_ = false;
  int64_t begin_us_ = 0;
  int64_t steps_ = 0;           // completed step windows
  int64_t unattributed_us_ = 0;  // span time outside any step window
  std::vector<std::pair<int64_t, int64_t>> spans_[2];  // open step
  std::vector<std::pair<int64_t, int64_t>> waits_;     // open step
  PlaneLedger planes_[2];
};

// Process-wide ledger, same lifetime contract as the registry.
OverlapLedger& GlobalLedger();

// RAII wall-clock span recorded into a histogram on destruction.
class ScopedLatency {
 public:
  explicit ScopedLatency(LatencyHistogram& h)
      : hist_(h), start_us_(MetricsNowUs()) {}
  ~ScopedLatency() { hist_.Record(MetricsNowUs() - start_us_); }
  ScopedLatency(const ScopedLatency&) = delete;
  ScopedLatency& operator=(const ScopedLatency&) = delete;

 private:
  LatencyHistogram& hist_;
  int64_t start_us_;
};

}  // namespace hvdtpu

#endif  // HVDTPU_METRICS_H
