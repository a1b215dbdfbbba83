# Build the native core runtime (csrc/ -> horovod_tpu/lib/libhvdtpu_core.so).
# Reference analog: horovod's CMake-driven per-framework extensions
# (setup.py + CMakeLists.txt). Ours is a single framework-agnostic .so
# loaded via ctypes (horovod_tpu/common/basics.py), plus an optional
# TensorFlow op library (csrc/tf_ops.cc -> libhvdtpu_tf.so) built against
# the installed TF's headers — the analog of horovod/tensorflow/mpi_ops.cc
# + xla_mpi_ops.cc.

CXX      ?= g++
CXXFLAGS ?= -O2 -g -std=c++17 -fPIC -Wall -Wextra -Wno-unused-parameter -pthread
LDFLAGS  ?= -shared -pthread

SRC := $(filter-out csrc/tf_ops.cc,$(wildcard csrc/*.cc))
HDR := $(wildcard csrc/*.h)
OUT := horovod_tpu/lib/libhvdtpu_core.so
TF_OUT := horovod_tpu/lib/libhvdtpu_tf.so

# TF build flags come from the installed wheel; empty when TF is absent.
PYTHON ?= python3

TSAN_OUT := horovod_tpu/lib/libhvdtpu_core_tsan.so
ASAN_OUT := horovod_tpu/lib/libhvdtpu_core_asan.so

.PHONY: core tf clean test test-quick test-flaky lint lint-csrc \
  model-check \
  core-tsan core-asan metrics-smoke zero-smoke elastic-smoke \
  reshard-smoke chaos-smoke obs-smoke scale-smoke perf-smoke \
  serve-smoke wire-smoke fusion-smoke fleet-obs-smoke

core: $(OUT)

$(OUT): $(SRC) $(HDR)
	@mkdir -p horovod_tpu/lib
	$(CXX) $(CXXFLAGS) $(SRC) $(LDFLAGS) -o $(OUT)

# Sanitizer builds of the core runtime (load via HVDTPU_CORE_LIB=...,
# LD_PRELOAD the matching runtime — tests/single/test_sanitizer_smoke.py
# drives a multi-threaded allreduce through the TSan build).
core-tsan: $(TSAN_OUT)
core-asan: $(ASAN_OUT)

$(TSAN_OUT): $(SRC) $(HDR)
	@mkdir -p horovod_tpu/lib
	$(CXX) -O1 -g -std=c++17 -fPIC -fsanitize=thread -pthread \
	  $(SRC) $(LDFLAGS) -fsanitize=thread -o $(TSAN_OUT)

$(ASAN_OUT): $(SRC) $(HDR)
	@mkdir -p horovod_tpu/lib
	$(CXX) -O1 -g -std=c++17 -fPIC -fsanitize=address -pthread \
	  $(SRC) $(LDFLAGS) -fsanitize=address -o $(ASAN_OUT)

# Strict-warning build of the native core: full -Wextra, warnings as
# errors, a REAL -O2 compile+link (not -fsyntax-only — optimization-
# dependent warnings like -Wmaybe-uninitialized need the middle-end).
# tf_ops.cc is excluded exactly as in the core build: it requires the
# installed TF's headers, which the lint box may not have.
lint-csrc:
	$(CXX) -O2 -std=c++17 -fPIC -Werror -Wall -Wextra -pthread \
	  $(SRC) $(LDFLAGS) -o /dev/null
	@echo "lint-csrc: clean ($(words $(SRC)) files, -Werror -Wall -Wextra)"

# hvdcheck: exhaustive protocol model checking (elastic / wire /
# serving control planes) + the seeded-mutant suite + the csrc<->Python
# ABI drift guards. Pure Python, no jax, sub-second — see
# docs/analysis.md ("hvdcheck").
model-check:
	$(PYTHON) -m horovod_tpu.analysis.model --all

# Python lint: ruff (when installed — the driver container does not
# ship it; config lives in pyproject.toml) + an hvdlint static-analysis
# pass over every shipped program + the hvdcheck protocol/ABI gate
# (see docs/analysis.md).
lint: model-check
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check horovod_tpu; \
	else \
	  echo "lint: ruff not installed; skipping style pass"; \
	fi
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.analysis.lint --all

tf: $(TF_OUT)

# The TF build flags come from ONE python probe at rule-execution time
# (tensorflow imports are multi-second; `make core` must not pay them).
$(TF_OUT): csrc/tf_ops.cc $(OUT)
	@set -e; \
	probe=$$($(PYTHON) -c "import tensorflow as tf, os; print(' '.join(tf.sysconfig.get_compile_flags())); print(' '.join(tf.sysconfig.get_link_flags())); print(os.path.join(os.path.dirname(tf.__file__), 'include'))" 2>/dev/null); \
	test -n "$$probe" || { echo "tensorflow not importable; skipping"; exit 1; }; \
	cflags=$$(printf '%s\n' "$$probe" | sed -n 1p); \
	lflags=$$(printf '%s\n' "$$probe" | sed -n 2p); \
	inc=$$(printf '%s\n' "$$probe" | sed -n 3p); \
	$(CXX) -O2 -g -std=c++17 -fPIC -Wno-deprecated-declarations \
	  csrc/tf_ops.cc $$cflags -Icsrc -I$$inc/external/highwayhash \
	  -I$$inc/external/farmhash_archive/src \
	  -shared -pthread $$lflags \
	  -Lhorovod_tpu/lib -l:libhvdtpu_core.so '-Wl,-rpath,$$ORIGIN' \
	  -o $(TF_OUT)

clean:
	rm -rf horovod_tpu/lib build

test: core
	python -m pytest tests/ -x -q

# Sub-5-minute lane: core runtime units, the multi-rank eager-ops file,
# and the elastic driver path (the full suite is ~25 min).
test-quick: core
	python -m pytest tests/ -m "quick and not slow" -x -q

# Rerun the load-flaky tests STANDALONE (serial, nothing else competing
# for the box) and in CI ORDER: the exact plugin-disable set of the
# tier-1 command (no xdist, no randomization, no cache) so collection
# order matches what CI ran — a flake that depends on which test warmed
# the core before it reproduces here or not at all. The loadflaky
# discipline: run THIS lane before blaming a diff for a shard failure —
# if it is green, the failure was load, not a regression (never
# hand-type the pytest invocation again).
test-flaky: core
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -m loadflaky -q \
	  -p no:cacheprovider -p no:xdist -p no:randomly

# Striped-wire smoke: selftest bit-identity at K in {1,4} (+ CRC +
# SIMD), exact per-channel byte reconciliation on a real 2-rank K=4
# job, and K=4 transport goodput beating the K=1 baseline at 16 MiB
# (docs/wire.md; horovod_tpu/common/wire_smoke.py; ~60 s).
wire-smoke: core
	$(PYTHON) -m horovod_tpu.common.wire_smoke

# Telemetry smoke: 2 real eager ranks, exact byte accounting in the
# metrics snapshot, cache steady state, per-rank timelines merged with
# straggler attribution (horovod_tpu/telemetry/smoke.py; ~10 s).
metrics-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.telemetry.smoke

# ZeRO-1 smoke: 2 real eager ranks drive the sharded-optimizer lane
# end to end — sharded-vs-replicated parity, 1/N per-rank optimizer
# bytes, reduce-scatter/allgather byte reconciliation (docs/zero.md;
# horovod_tpu/jax/zero_smoke.py; ~30 s).
zero-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.jax.zero_smoke

# Elastic smoke: 2 real ranks; rank 1 is killed by deterministic fault
# injection mid-step, rank 0 gets the typed recoverable error, re-forms
# a 1-rank ring in place and resumes from the last commit, with the
# fault lifecycle booked in the metrics snapshot (docs/elastic.md;
# horovod_tpu/jax/elastic_smoke.py; ~30 s).
elastic-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.jax.elastic_smoke

# Chaos-matrix smoke: the three self-healing acceptance behaviors under
# the HOROVOD_FAULT_INJECT grammar (kill|stop|reset|flip|delay) — a
# SIGSTOP stall healed in place on the retry ladder (same epoch, zero
# faults), a wire bit-flip caught by per-chunk CRC32C and NAK-resent,
# and SIGKILL + blacklist-parole rejoin regrowing N-1 -> N with the
# training trajectory pinned against an uninterrupted N-rank run
# (docs/elastic.md; tests/parallel/test_chaos_matrix.py; ~2 min).
chaos-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/parallel/test_chaos_matrix.py \
	  -q -p no:cacheprovider \
	  -k "heals_in_place or bitflip_detected or parole_rejoin"

# Observability smoke: 2 real ranks with the debug endpoint up; an
# injected stop:<ms> stall escalates to a typed fault — /healthz must
# answer on both ranks mid-run, every rank leaves a black-box event-
# ring dump, and the merged post-mortem names the stalled rank without
# declaring anyone dead (docs/metrics.md;
# horovod_tpu/telemetry/obs_smoke.py; ~20 s).
obs-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.telemetry.obs_smoke

# Fleet-observatory smoke: 2 real ranks run step-marked train loops;
# an injected stop:<ms> stall on rank 1 heals in place through the
# retry ladder while the driver polls the live /fleet aggregation on
# rank 0 — every rank's rank-seconds buckets must sum to its window to
# the microsecond (unattributed < 1%), rank 1's SLO check must breach
# stall_ms naming phase "stall" and record the typed slo_breach event,
# and report.py --fleet over the black-box dumps must surface the same
# verdict post-mortem (docs/fleet.md;
# horovod_tpu/telemetry/fleet_smoke.py; ~25 s).
fleet-obs-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.telemetry.fleet_smoke

# Step-anatomy smoke: 2 real ranks run an eager loop under a StepTimer
# (step windows + overlap ledger) with a chaos delay:<ms> straggler
# injection on rank 1 — asserts exposed + hidden == total wire time
# reconciles within 1% of the wire_us histogram, and that the
# cross-rank critical-path merge (report.py --critical-path) names the
# delayed rank with phase "stall" on exactly the injected step
# (docs/metrics.md; horovod_tpu/telemetry/perf_smoke.py; ~20 s).
perf-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.telemetry.perf_smoke

# Jit-lane fusion smoke: hvdlint C7 gate (interleaving statically
# verified on the fused step, fires on a bunched fixture), then 2 real
# ranks run hvd.make_fused_train_step under a StepTimer — asserts the
# overlap-ledger invariant (exposed + hidden == total per plane, with
# hidden > 0: wire drained while segments dispatched) and that
# HOROVOD_JIT_FUSION flips the schedule with BIT-identical loss/params
# (docs/fusion.md; horovod_tpu/jax/fusion_smoke.py; ~40 s).
fusion-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.jax.fusion_smoke

# Large-world smoke: one 64-rank simulated world (thread-per-rank over
# socketpairs, csrc/simworld.cc) runs a negotiation + allreduce round
# in BOTH gather modes (flat star vs HOROVOD_CONTROL_TREE) with the
# per-phase control-plane latency rows emitted, then an injected kill
# surfaces typed attribution on all 63 survivors and the streaming
# post-mortem merge over their dumps names the dead rank as root cause
# (docs/scale.md; horovod_tpu/simworld/scale_smoke.py; ~15 s).
scale-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.simworld.scale_smoke

# Serving chaos smoke: a 2-rank prefill/decode world serves a Poisson
# request trace with int8 paged KV shipped over the CRC-framed host
# ring; the decode rank is SIGKILLed mid-trace and every admitted
# request must complete on the survivor with greedy output
# token-identical to llama_generate — AND the latency cliff must be
# EXPLAINED: every completed rid stitches into a gap-free request span
# chain (per-phase sums == wall time exactly), the chaos victim's
# orphans carry fault_requeue spans and only they do, and
# report.py --requests renders the tail attribution over the dumps
# (docs/serving.md; horovod_tpu/serving/serve_smoke.py; ~60 s).
serve-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.serving.serve_smoke

# Cross-plane + redistribute smoke: 4 real ranks emulate 2 slices x 2
# chips under HOROVOD_CROSS_PLANE=hier — hierarchical train-step parity
# with exact per-plane wire books, a checkpoint reshard round-trip via
# hvd.redistribute plans with <1% measured-vs-predicted reconciliation,
# and the 1/local_size cross-plane byte bound (docs/redistribute.md;
# horovod_tpu/jax/reshard_smoke.py; ~20 s).
reshard-smoke: core
	JAX_PLATFORMS=cpu $(PYTHON) -m horovod_tpu.jax.reshard_smoke
