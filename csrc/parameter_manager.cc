#include "parameter_manager.h"

#include <algorithm>
#include <cmath>

#include "logging.h"

namespace hvdtpu {

namespace {
constexpr double kMaxWindowSecs = 5.0;
}  // namespace

void ParameterManager::Initialize(int64_t fusion_bytes, double cycle_ms,
                                  const std::string& log_path,
                                  int max_samples, int64_t window_bytes,
                                  int window_cycles,
                                  int64_t ring_chunk_bytes,
                                  int wire_codec,
                                  bool tune_wire_codec,
                                  std::vector<int64_t> hier_values,
                                  int64_t hier_split,
                                  int64_t wire_channels,
                                  int64_t max_wire_channels) {
  min_window_bytes_ = std::max<int64_t>(window_bytes, 1);
  min_window_cycles_ = std::max(window_cycles, 1);
  for (int64_t v = 1 << 20; v <= (64 << 20); v *= 2) {
    fusion_values_.push_back(v);
  }
  cycle_values_ = {0.5, 1.0, 2.5, 5.0, 10.0};
  if (ring_chunk_bytes > 0) {
    chunk_values_ = {64 << 10, 256 << 10, 1 << 20, 4 << 20};
  } else {
    // The user explicitly configured the legacy bulk path (chunk
    // <= 0): it has no point on a log-scaled grid, so pin the
    // dimension rather than silently abandon an explicit choice
    // (same philosophy as the compression guard below).
    chunk_values_ = {ring_chunk_bytes};
  }
  // Compression flips numerics: only the user's enablement puts the
  // off/codec choice on the grid; otherwise the dimension is a single
  // fixed point and the GP never varies it. The tuner may settle on
  // OFF (strictly more accurate), never on a codec the user did not
  // pick.
  if (tune_wire_codec && wire_codec != 0) {
    comp_values_ = {0, wire_codec};
  } else {
    comp_values_ = {wire_codec};
  }
  // Hierarchy split point of the cross-plane allreduce: the caller
  // (operations.cc) passes the eligible splits for THIS layout — empty
  // or single-valued pins the dimension (flat-only layouts, or an
  // explicit HOROVOD_CROSS_PLANE=ring/hier choice the tuner must not
  // override beyond the split itself).
  if (!hier_values.empty()) hier_values_ = std::move(hier_values);
  // Stripe width (6th dimension): powers of two up to the sockets
  // actually established — a single-socket mesh pins it at {1}.
  chan_values_.clear();
  for (int64_t k = 1; k <= std::max<int64_t>(max_wire_channels, 1);
       k *= 2) {
    chan_values_.push_back(k);
  }
  max_samples_ = std::max(max_samples, 2);

  // Candidate grid in a normalized space: log2 of each byte/ms knob
  // scaled to [0,1] (compression is already {0,1}) so one RBF length
  // scale covers every dimension.
  std::vector<std::vector<double>> cands;
  double f_lo = std::log2((double)fusion_values_.front());
  double f_hi = std::log2((double)fusion_values_.back());
  double c_lo = std::log2(cycle_values_.front());
  double c_hi = std::log2(cycle_values_.back());
  // A pinned (single-value) dimension gets the constant coordinate 0
  // — no log2 of a possibly-non-positive pinned value.
  bool chunk_pinned = chunk_values_.size() == 1;
  double k_lo = chunk_pinned ? 0 : std::log2((double)chunk_values_.front());
  double k_hi = chunk_pinned ? 1 : std::log2((double)chunk_values_.back());
  // Hier coordinate: split index scaled to [0,1] (the split grid is
  // small and ordered flat < divisors ascending, so the index is a
  // monotone proxy for "how local the decomposition is"). Channel
  // coordinate: same index treatment over the power-of-two widths.
  bool hier_pinned = hier_values_.size() <= 1;
  bool chan_pinned = chan_values_.size() <= 1;
  for (size_t fi = 0; fi < fusion_values_.size(); fi++) {
    for (size_t ci = 0; ci < cycle_values_.size(); ci++) {
      for (size_t ki = 0; ki < chunk_values_.size(); ki++) {
        for (size_t mi = 0; mi < comp_values_.size(); mi++) {
          for (size_t hi = 0; hi < hier_values_.size(); hi++) {
            for (size_t ni = 0; ni < chan_values_.size(); ni++) {
              cands.push_back(
                  {(std::log2((double)fusion_values_[fi]) - f_lo) /
                       (f_hi - f_lo),
                   (std::log2(cycle_values_[ci]) - c_lo) / (c_hi - c_lo),
                   chunk_pinned
                       ? 0.0
                       : (std::log2((double)chunk_values_[ki]) - k_lo) /
                             (k_hi - k_lo),
                   comp_values_[mi] != 0 ? 1.0 : 0.0,
                   hier_pinned
                       ? 0.0
                       : (double)hi / (double)(hier_values_.size() - 1),
                   chan_pinned
                       ? 0.0
                       : (double)ni /
                             (double)(chan_values_.size() - 1)});
            }
          }
        }
      }
    }
  }
  opt_ = std::make_unique<BayesOpt>(std::move(cands));

  // Start from the user-provided operating point (snap onto the grids).
  fusion_idx_ = 0;
  for (size_t i = 0; i < fusion_values_.size(); i++) {
    if (fusion_values_[i] <= fusion_bytes) fusion_idx_ = i;
  }
  cycle_idx_ = 0;
  for (size_t i = 0; i < cycle_values_.size(); i++) {
    if (cycle_values_[i] <= cycle_ms) cycle_idx_ = i;
  }
  chunk_idx_ = 0;
  for (size_t i = 0; i < chunk_values_.size(); i++) {
    if (chunk_values_[i] <= ring_chunk_bytes) chunk_idx_ = i;
  }
  comp_idx_ = 0;
  for (size_t i = 0; i < comp_values_.size(); i++) {
    if (comp_values_[i] == wire_codec) comp_idx_ = i;
  }
  hier_idx_ = 0;
  for (size_t i = 0; i < hier_values_.size(); i++) {
    if (hier_values_[i] == hier_split) hier_idx_ = i;
  }
  chan_idx_ = 0;
  for (size_t i = 0; i < chan_values_.size(); i++) {
    if (chan_values_[i] <= wire_channels) chan_idx_ = i;
  }
  current_candidate_ =
      ((((fusion_idx_ * cycle_values_.size() + cycle_idx_) *
             chunk_values_.size() +
         chunk_idx_) *
            comp_values_.size() +
        comp_idx_) *
           hier_values_.size() +
       hier_idx_) *
          chan_values_.size() +
      chan_idx_;

  if (!log_path.empty()) {
    log_ = fopen(log_path.c_str(), "w");
    if (log_) {
      fprintf(log_, "fusion_threshold_bytes,cycle_time_ms,"
                    "ring_chunk_bytes,wire_compression,hier_split,"
                    "wire_channels,score_bytes_per_sec\n");
      fflush(log_);
    }
  }
  active_ = true;
}

ParameterManager::~ParameterManager() {
  if (log_) fclose(log_);
}

void ParameterManager::Log(double score) {
  if (!log_) return;
  fprintf(log_, "%lld,%.3f,%lld,%d,%lld,%lld,%.0f\n",
          (long long)fusion_threshold_bytes(), cycle_time_ms(),
          (long long)ring_chunk_bytes(), wire_codec(),
          (long long)hier_split(), (long long)wire_channels(), score);
  fflush(log_);
}

void ParameterManager::MoveTo(size_t candidate) {
  current_candidate_ = candidate;
  chan_idx_ = candidate % chan_values_.size();
  candidate /= chan_values_.size();
  hier_idx_ = candidate % hier_values_.size();
  candidate /= hier_values_.size();
  comp_idx_ = candidate % comp_values_.size();
  candidate /= comp_values_.size();
  chunk_idx_ = candidate % chunk_values_.size();
  candidate /= chunk_values_.size();
  cycle_idx_ = candidate % cycle_values_.size();
  fusion_idx_ = candidate / cycle_values_.size();
}

void ParameterManager::Score(double bytes_per_sec) {
  Log(bytes_per_sec);
  if (done_) return;
  opt_->AddSample(current_candidate_, bytes_per_sec);
  if ((int)opt_->num_samples() >= max_samples_) {
    MoveTo(opt_->Best());
    done_ = true;
    // Final log row = the CONVERGED operating point (with its mean
    // observed score), not the 20th sampled candidate — consumers
    // read rows[-1] as "what the tuner settled on".
    Log(opt_->MeanScore(current_candidate_));
    LOG_INFO("autotune converged: fusion=%lld bytes, cycle=%.2f ms, "
             "ring_chunk=%lld bytes, wire_codec=%d, hier_split=%lld, "
             "wire_channels=%lld",
             (long long)fusion_threshold_bytes(), cycle_time_ms(),
             (long long)ring_chunk_bytes(), wire_codec(),
             (long long)hier_split(), (long long)wire_channels());
    return;
  }
  MoveTo(opt_->Suggest());
}

bool ParameterManager::Update(int64_t bytes) {
  if (!active_ || done_) return false;
  auto now = std::chrono::steady_clock::now();
  if (!window_started_) {
    // A window's clock starts where the PREVIOUS window closed, not at
    // its own first enqueue: eager training traffic is bursty (a long
    // gradient-compute phase, then a flood of allreduces), and a
    // first-enqueue clock silently drops the idle phase from the
    // score. That bias made bytes/sec REWARD small cycle times —
    // windows close inside the burst where instantaneous throughput
    // is high — while the realized step time is worst exactly there
    // (seen in round 6: the per-grad lane's knob landscape
    // inverts). Wall-clock windows
    // make the score proportional to end-to-end training throughput,
    // which is the number the tuner exists to move. Exception: a
    // carried-over gap of a whole window or more is a knob-UNRELATED
    // stall (eval loop, checkpoint, re-jit) — charging it to whatever
    // candidate happens to be active would feed the optimizer a
    // near-zero garbage sample (the window would close on its first
    // Update via the kMaxWindowSecs cap), so such gaps start fresh.
    auto start = window_ended_ ? window_end_ : now;
    if (std::chrono::duration<double>(now - start).count() >=
        kMaxWindowSecs) {
      start = now;
    }
    window_start_ = start;
    window_started_ = true;
    window_bytes_ = 0;
    window_cycles_ = 0;
  }
  window_bytes_ += bytes;
  window_cycles_++;
  double secs = std::chrono::duration<double>(now - window_start_).count();
  bool window_full = (window_bytes_ >= min_window_bytes_ &&
                      window_cycles_ >= min_window_cycles_) ||
                     secs >= kMaxWindowSecs;
  if (!window_full || secs <= 0) return false;
  int64_t prev_fusion = fusion_threshold_bytes();
  double prev_cycle = cycle_time_ms();
  int64_t prev_chunk = ring_chunk_bytes();
  int prev_comp = wire_codec();
  int64_t prev_hier = hier_split();
  int64_t prev_chan = wire_channels();
  if (warmup_windows_ > 0) {
    warmup_windows_--;  // discard: startup warmup pollutes the score
  } else if (window_bytes_ >= min_window_bytes_ ||
             window_cycles_ >= min_window_cycles_) {
    Score((double)window_bytes_ / secs);
  }
  // else: a window that hit the kMaxWindowSecs cap with traffic below
  // BOTH floors is a stall artifact (a sub-cap pause carried into the
  // window start plus one or two enqueues) — discard it rather than
  // feed the optimizer a near-zero sample charged to an innocent
  // candidate. Genuinely slow workloads still score: their cap-closed
  // windows clear the cycle floor.
  window_started_ = false;
  window_end_ = now;
  window_ended_ = true;
  return fusion_threshold_bytes() != prev_fusion ||
         cycle_time_ms() != prev_cycle ||
         ring_chunk_bytes() != prev_chunk ||
         wire_codec() != prev_comp ||
         hier_split() != prev_hier ||
         wire_channels() != prev_chan;
}

}  // namespace hvdtpu
