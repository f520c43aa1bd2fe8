.PHONY: test lint shard-baselines perf-baselines num-baselines chip-smoke tpu-smoke obs-smoke serve-smoke chaos-smoke wire-smoke thread-smoke blocking-smoke approx-smoke trace-smoke warmup-smoke drift-smoke perf-smoke tf-smoke scale-smoke fleet-smoke num-smoke all

# CPU oracle/golden tier: 8 virtual devices, runs anywhere.
test:
	python -m pytest tests/ -x -q

# Static analysis gate — all six layers (splink_tpu/analysis/):
#   1  jaxlint      AST pass over the package (JL001-JL012)
#   2  trace audit  jaxpr audit of the kernel registry
#   3  shard audit  SPMD partition-safety + cost budgets on the 8-device mesh
#   4  perf audit   measured runtime/memory budgets (--list-perf-kernels here;
#                   the measured gate runs in perf-smoke)
#   5  threadlint   concurrency-safety audit of the serve/obs thread fleet
#                   (TL001-TL005; dynamic half: thread-smoke)
#   6  numlint      numerical-hygiene AST pass (NL001-NL008, rides the same
#                   paths invocation; measured half --num-audit runs in
#                   num-smoke against num_baselines.json)
# Exit 1 on any unsuppressed finding, undeclared collective, cost-budget
# drift, or thread-safety hazard; tests/test_codebase_clean.py enforces the
# same gates in tier-1. (The CLI pins JAX_PLATFORMS/XLA_FLAGS itself for
# --shard-audit; set here too so the whole invocation runs the same config.)
lint:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m splink_tpu.analysis splink_tpu/ --audit --shard-audit --thread-audit
	JAX_PLATFORMS=cpu python -m splink_tpu.analysis --list-perf-kernels

# Intentional refresh of the committed per-kernel cost/collective budgets
# (splink_tpu/analysis/shard_baselines.json) after an accepted perf change
# or a new shard kernel. Review the JSON diff like a benchmark result.
shard-baselines:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m splink_tpu.analysis --shard-audit --update-baselines

# Intentional refresh of the committed MEASURED per-(tier, kernel, shape)
# runtime/memory budgets (splink_tpu/analysis/perf_baselines.json, layer 4)
# after an accepted perf change or a new kernel. Only this tier's block is
# rewritten (hardware tiers add their own); review the diff like a bench.
perf-baselines:
	JAX_PLATFORMS=cpu \
		python -m splink_tpu.analysis --perf-audit --update-perf-baselines

# Intentional refresh of the committed per-(tier, kernel) f32/f64 ulp
# budgets (splink_tpu/analysis/num_baselines.json, layer 6) after an
# accepted numerics change or a new kernel. Only this tier's block is
# rewritten (hardware tiers add their own); review the diff like a bench —
# a wider budget means the f32 error bar grew.
num-baselines:
	JAX_PLATFORMS=cpu \
		python -m splink_tpu.analysis --num-audit --update-num-baselines

# Chip tier: both targets need a TPU and FAIL without one — run them
# through the chip tool, in one call so they share the compile cache, e.g.
#   chiprun -- sh -c 'python chip_smoke.py && python -m pytest tests_tpu/ -q'
# chip-smoke drives the offline and the serve path once at the full width
# of BASELINE config 4 and checks what comes out (a "summary:" JSON line,
# "claim": null — a smoke, not a benchmark — then the verdict line
# {"ok": true, "device": {...}}); tpu-smoke is its finer-grained
# companion: real TPU lowering of the Pallas kernels + pipeline pieces.
# Separate invocations because tests/conftest.py pins its process to CPU,
# and one process at a time because a chip belongs to one process.
chip-smoke:
	python chip_smoke.py

tpu-smoke:
	python -m pytest tests_tpu/ -q

# Telemetry smoke: fixture linker run with the JSONL sink enabled (fault
# injection included), then the summarize + export-trace CLI over the
# record (docs/observability.md).
obs-smoke:
	python scripts/obs_smoke.py

# Serving smoke: build a LinkageIndex from the fixture corpus, serve 100
# queries through the micro-batching service, assert serve<->offline score
# parity (bit-identical) and zero steady-state recompiles (docs/serving.md).
serve-smoke:
	python scripts/serve_smoke.py

# Chaos smoke: run the service under EVERY registered serve fault site
# (worker death, batch exception, slow batch, breaker storm, index
# corruption, swap-validation failure — resilience/faults.py SERVE_SITES)
# and assert the resilience contract: no future hangs past its timeout, no
# exception escapes to a caller, fault/degradation events land in the
# JSONL sink, throughput recovers after each fault, and a hot-swap +
# brown-out episode stay recompile-free (docs/serving.md#resilience).
chaos-smoke:
	python scripts/chaos_smoke.py

# Wire chaos smoke: two real services behind loopback WireServers, a
# ReplicaRouter over RemoteReplica clients, driven through every wire
# fault site (resilience/faults.py WIRE_SITES — host kill mid-request,
# partition + heal, slow link tripping the hedger, torn frames, per-
# remote breaker storm) and assert the multi-host contract: no future
# hangs, no exception escapes, sheds are machine-readable, wire events
# land in the JSONL sink, remote answers stay bit-identical to local,
# and post-recovery steady state performs ZERO recompiles
# (docs/serving.md#multi-host).
wire-smoke:
	python scripts/wire_chaos_smoke.py

# Thread-safety smoke: the dynamic half of analysis layer 5. Every fleet
# lock is created through the lockwatch instrumented factories
# (SPLINK_TPU_LOCKWATCH=1), sys.setswitchinterval is lowered ~1000x, and
# a real engine + service + wire server + hedged router fleet is driven
# by concurrent submit threads, stats/health pollers and injected
# connection drops. Gates: a seeded A->B/B->A inversion IS detected
# (lock_inversion event + flight dump + lock_order_graph.json artifact),
# the real fleet shows ZERO inversions, the observed-union-declared lock
# graph stays acyclic, every future resolves, counters stay consistent,
# and steady state performs ZERO recompiles (docs/static_analysis.md#layer-5).
thread-smoke:
	python scripts/thread_smoke.py

# Device-blocking smoke: device<->host pair-set parity (the host join is
# the oracle) over sequential/null/asymmetric rules with budgeted chunked
# emission, plus zero steady-state recompiles across chunk shapes
# (docs/blocking.md).
blocking-smoke:
	python scripts/blocking_smoke.py

# Approximate-blocking smoke: minhash-LSH candidate-set determinism across
# two runs, approx_pair_budget held, zero steady-state recompiles across
# chunk shapes, and serve fallback parity with a host-side oracle —
# garbled queries return approx-tagged candidates whose scores are
# bit-identical to offline scoring of the same pairs
# (docs/blocking.md#approximate-tier).
approx-smoke:
	python scripts/approx_smoke.py

# Request-tracing smoke: the serving tier under an injected slow batch +
# breaker storm with tracing at full sample rate, asserting the
# attribution contract — per-request phase durations sum to the measured
# wall latency within 5%, every request closes exactly one span tree with
# a machine-readable outcome, the breaker storm dumps the flight recorder
# to a JSONL that round-trips through `obs summarize`, and steady-state
# recompiles stay at ZERO with tracing enabled
# (docs/observability.md#serve-tracing).
trace-smoke:
	python scripts/trace_smoke.py

# Cold-start smoke: process A builds an index + compiles the serve menu +
# commits the AOT executable sidecar; a FRESH process B restores the whole
# menu and the gate asserts zero backend compiles (jax.monitoring split
# accounting), zero persistent-cache reads, first-query scores bit-identical
# to process A, and the fused-kernel audits clean in the restored process
# (docs/serving.md#cold-start).
warmup-smoke:
	python scripts/warmup_smoke.py

# Drift smoke: build a profiled index, serve a clean query stream (quiet
# windows, zero recompiles with sketching on), then inject a skewed stream
# and assert the two-window drift alert fires, the flight recorder dumps,
# and `obs drift` + the Prometheus exposition render the captured record
# (docs/observability.md#drift).
drift-smoke:
	python scripts/drift_smoke.py

# Performance-observatory smoke: the layer-4 measured audit passes against
# the committed perf_baselines.json on this tier, steady-state traffic with
# the serve-time KernelWatch on performs zero compile requests, a
# monkeypatched slow engine trips the two-window perf alert (flight dump
# with the window snapshot inside, edge-triggered clear on recovery), and
# `obs summarize` + the Prometheus exposition render the perf series
# (docs/observability.md#perf).
perf-smoke:
	python scripts/perf_smoke.py

# Term-frequency smoke: serve<->offline TF-adjusted parity bit-identical
# (fused + unfused) on a TF-flagged model, a legacy TF-less artifact
# round-trips and serves unchanged, and a FRESH process restores the TF
# serve menu from the AOT sidecar with zero backend compiles and
# bit-identical first-query answers (docs/serving.md#term-frequency).
tf-smoke:
	python scripts/tf_smoke.py

# Offline-scale smoke: the billion-row write path's contracts — an
# out-of-core index build over a corpus larger than the configured
# working set is content-fingerprint-identical to the resident build,
# the sharded spill emission's pair set equals the ordinary path's with
# zero steady-state recompiles across chunk shapes and spill segments,
# and a build SIGKILLed mid-segment resumes from its manifest to a
# bit-identical fingerprint (docs/blocking.md#offline-scale).
scale-smoke:
	python scripts/scale_smoke.py

# Fleet observability smoke: two wire hosts + a tracing router on
# loopback under net_delay/net_partition faults — stitched cross-host
# waterfalls telescope inside the client wall, metric federation is
# bit-exact against the raw per-host exports, a partition burst
# produces one correlated incident bundle, and steady state with
# stitching on performs zero recompiles (docs/observability.md#fleet-observability).
fleet-smoke:
	python scripts/fleet_smoke.py

# Numerics smoke: the measured half of analysis layer 6. The corner-batch
# audit (NA-FIN finite outputs, NA-ULP f32/f64 divergence inside committed
# budgets, NA-MONO monotone match probabilities, NA-ORD pinned fold order)
# passes against num_baselines.json on this tier, a doctored ulp budget
# provably trips the gate, and the audit summary lands on the obs timeline
# as a num_audit flight transition (docs/static_analysis.md#layer-6).
num-smoke:
	python scripts/num_smoke.py

# Speed is measured on the chip only: BENCHMARK.json declares the cells
# and `python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
# --trace <0|1>` runs one through the chip tool (PERF.md). No target here
# measures speed; `all` is lint, the CPU tests and the behaviour smokes.
all: lint test blocking-smoke approx-smoke serve-smoke chaos-smoke wire-smoke thread-smoke trace-smoke warmup-smoke drift-smoke perf-smoke tf-smoke scale-smoke fleet-smoke num-smoke
