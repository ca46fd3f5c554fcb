#!/bin/bash
# CI driver (the reference's Jenkinsfile matrix, SURVEY §2.6/§4):
#   1. native build
#   2. chip lane IN THE BACKGROUND where JAX finds a TPU: chip_smoke.py
#      (trainer, server, flash kernel, cpu-vs-tpu op sample), and in
#      the nightly tier the full consistency registry and inference
#      scoring.  It overlaps the CPU-bound unit suite, which is pinned
#      to the CPU.  (A speed is not this script's to state:
#      benchmark/run.py measures, and the driver runs it.)
#   3. unit suite on the virtual 8-device CPU mesh
#   4. multi-process distributed + crash-recovery (local launcher)
#   5. join the chip lane
#
# Two tiers, like the reference's PR-gate vs nightly split:
#   default            — fast gate.  The unit suite deselects the one
#                        marker there is, `slow` (-m "not slow", the
#                        selection tier-1 runs: a test whose call takes
#                        more than 90 s alone on the CPU, by
#                        measurement).  Also left to the nightly: the
#                        full consistency registry, the full inference
#                        zoo, the 3-worker dist cases.
#   MXTPU_CI_FULL=1    — everything, serially (the nightly tier): the
#                        unit suite unfiltered, full consistency
#                        registry, full inference zoo, dist trio +
#                        dist_lenet at 2 and 3 workers, crash-recovery
#                        resume.
# Each stage echoes a timestamp so wall-time regressions are visible.
# Quick iteration while developing:
#   python -m pytest tests/ -x -q -k "not examples and not lowp"
set -euo pipefail
cd "$(dirname "$0")/.."

stage() { echo "=== $1 ($(date +%H:%M:%S)) ==="; }

FULL="${MXTPU_CI_FULL:-0}"

PYTEST_MARK=(-m "not slow")
if [ "$FULL" = "1" ]; then
    PYTEST_MARK=()
fi

stage "native build"
make -C native

# ---------------------------------------------------------------- chip lane
# One process holds a chip at a time: everything in this lane runs one
# command after another, and every CPU stage below pins itself to the CPU.
HAVE_CHIP=0
if python -c "import jax,sys; sys.exit(0 if jax.devices()[0].platform == 'tpu' else 1)" 2>/dev/null; then
    HAVE_CHIP=1
fi

chip_lane() {
    set -euo pipefail
    if [ "$HAVE_CHIP" != "1" ]; then
        stage "chip lane: JAX finds no TPU here, nothing to run"
        return 0
    fi
    # the fused trainer, the server, the flash kernel and a sample of
    # the cpu-vs-tpu op registry through their public entry points at
    # real width; exits non-zero on any failed phase
    stage "chip lane: chip_smoke.py"
    python chip_smoke.py
    if [ "$FULL" = "1" ]; then
        stage "chip lane: cpu-vs-tpu consistency, full registry"
        python tests/nightly/consistency.py
        stage "chip lane: inference scoring"
        python examples/image-classification/benchmark_score.py \
            --batch-sizes 32 --num-batches 20 \
            --out /tmp/infer_bench_ci.json
        python examples/image-classification/benchmark_score.py \
            --networks resnet-50 --batch-sizes 32 --num-batches 20 \
            --dtypes float32,int8 --out /tmp/infer_bench_ci_int8.json
    fi
    stage "chip lane: done"
}

CHIP_LOG="$(mktemp)"
if [ "$FULL" = "1" ]; then
    # nightly: serial, full fidelity — no overlap to keep timings clean
    chip_lane
else
    chip_lane > "$CHIP_LOG" 2>&1 &
    CHIP_PID=$!
fi

# ---------------------------------------------------------------- cpu lanes
stage "graph lint gate (trace-time, no device execution)"
# static shape/dtype/TPU-hazard analysis over the bench symbol graphs
# and their fwd+bwd jaxprs; FAILS on NEW error-severity findings vs the
# checked-in LINT_BASELINE.json (ratchet with --write-baseline) and
# prints the finding summary — docs/how_to/graph_lint.md
python tools/graph_lint.py --check

stage "compiled-program cache (zero-recompile warm restart)"
# the persisted-program drill (docs/how_to/compiled_programs.md): run
# the compile-heavy trainer + Predictor + ModelServer driver twice
# against ONE cache dir.  The first run fills the cache (compiles > 0,
# every executable persisted); the second run must deserialize every
# program — the script FAILS unless its lazy-trace count and compile
# count are both ZERO and the output fingerprints match the cold run
# bit-for-bit.  HARD timeout: a wedged deserialization must fail this
# stage, not hang the suite.
PROG_CACHE="$(mktemp -d)"
timeout -k 10 420 env JAX_PLATFORMS=cpu MXTPU_PROGRAM_CACHE="$PROG_CACHE" \
    python tests/nightly/program_warm.py --expect cold \
    --json "$PROG_CACHE/cold.json"
timeout -k 10 420 env JAX_PLATFORMS=cpu MXTPU_PROGRAM_CACHE="$PROG_CACHE" \
    python tests/nightly/program_warm.py --expect warm \
    --ref "$PROG_CACHE/cold.json"
rm -rf "$PROG_CACHE"

stage "micro-tune (surrogate search + timed A/B emits a loadable, no-worse plan)"
# the search-based autotuner's CI cut (docs/how_to/autotune.md): 2-3
# knobs, byte-cost-model + serving-EWMA surrogates, one timed trial per
# A/B side against a warm program cache — the tool itself asserts the
# warm recheck compiles ZERO programs and (--assert-no-worse) that the
# emitted plan is no worse than the defaults on the measured window;
# --verify then loads the plan back through a REAL Trainer +
# ModelServer in a fresh process and asserts every section applied.
# HARD timeout: a wedged trial server must fail this stage, not hang CI.
TUNE_TMP="$(mktemp -d)"
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    MXTPU_PROGRAM_CACHE="$TUNE_TMP/cache" \
    MXTPU_TUNE_CORPUS="$TUNE_TMP/TUNE_CORPUS.jsonl" \
    python tools/autotune.py --micro --out "$TUNE_TMP/TUNE_PLAN.json" \
        --corpus "$TUNE_TMP/TUNE_CORPUS.jsonl" --assert-no-worse
timeout -k 10 180 env JAX_PLATFORMS=cpu \
    python tools/autotune.py --verify "$TUNE_TMP/TUNE_PLAN.json"
rm -rf "$TUNE_TMP"

stage "int8 quantization gate (calibrate -> accuracy gate -> serve)"
# the calibrated-quantization workflow end to end on the planted ranker
# demo (no training loop): float forward calibration, the argmax
# agreement / top-1 accuracy gate, quantized checkpoint emission with
# the calibration digest stamped in the manifest, then a reload through
# latest_verified() + Predictor + an int8-tier ModelServer with
# predictor-vs-server agreement asserted.  The tool exits 3 (stage
# FAILS) if the gate refuses or the served tier mismatches —
# docs/how_to/quantization.md.  HARD timeout: a wedged serve check must
# fail this stage, not hang the gate.
QUANT_TMP="$(mktemp -d)"
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python tools/quantize.py --demo ranker --serve \
        --out-dir "$QUANT_TMP"
rm -rf "$QUANT_TMP"

stage "quantization suite (calibration / gate refusal / int8 storage)"
# calibration determinism + digest provenance, the gate's clipped-
# calibration refusal, quantized-checkpoint verified reload, 1-byte-
# per-elem device storage on both serve surfaces, precision-tier
# admission, plan licensing, and the dequant-unfused jaxpr pass.
# HARD timeout: a hung serve-surface test must fail, not wedge CI.
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_quant_calibration.py -q

stage "comm lint gate (static collective-communication analysis)"
# extracts the comm plan (collective, axis, dtype, predicted wire
# bytes, layer provenance) of the fused ZeRO-1+bf16 trainer step, the
# serving forward, and the shard_map'd ring-attention/pipeline
# programs, runs the comm rules (f32-wire, resharding-thrash,
# comm-budget, rank-divergent-collective), and FAILS on NEW error
# findings or a predicted-GB regression vs the checked-in
# COMM_BASELINE.json (ratchet with --write-baseline) — pure trace
# time, docs/how_to/static_analysis.md "Communication analysis"
python tools/comm_lint.py --check

stage "mem lint gate (static buffer-liveness peak-HBM analysis)"
# walks the SAME lowered programs as the comm gate and predicts the
# per-chip peak from a buffer-liveness timeline (donated state freed
# at its donation point, ZeRO-sharded optimizer state priced through
# its committed sharding, checkpointed regions at their transient
# working-set floor), then runs the mem rules (mem-budget,
# mem-capacity, remat-opportunity, donation-missed, pad-waste) and
# FAILS on NEW error findings or a predicted-GB regression vs the
# checked-in MEM_BASELINE.json (ratchet with --write-baseline) — pure
# trace time, docs/how_to/static_analysis.md "Memory analysis"
python tools/mem_lint.py --check

stage "large-model parallelism suite (sparse MoE / pipeline schedules / causal-skip ring / composed workloads)"
# the perf-path parallelism layers and their composition: sparse vs
# dense MoE dispatch value+grad parity (EXACT on integer data), top-2
# gating vs the softmax reference, causal-skip ring attention vs the
# reference at every (n_shards, causal) corner (skip is BITWISE vs
# no-skip), interleaved-vs-gpipe schedule parity vs the serial stack,
# the transformer-large kill-and-resume bit-parity drill through
# CheckpointManager, and the dropped_frac / bubble-frac / dispatch-
# byte-model contracts.  HARD timeout: a wedged collective in the
# composed step must FAIL this stage, not hang the suite —
# docs/how_to/perf.md "Large-model parallelism"
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_parallel_workloads.py -q

stage "runtime telemetry suite (metrics registry / spans / trace export)"
# the unified-observability layer: registry snapshot/merge, serving
# request + training step span trees, correlation-ID propagation
# across the scheduler thread, JSONL -> Chrome round trip, off-mode
# no-op sites, the obs_report closure gate, and the exporter-thread
# leak check.  HARD timeout: a wedged exporter thread or a future that
# never settles must FAIL this stage, not hang the suite —
# docs/how_to/observability.md
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_obs.py -q

stage "concurrency sanitizer gate (static lint + MXTPU_TSAN=1 lockset sweep)"
# half 1: the AST thread-safety rules over mxnet_tpu/ (no imports, no
# devices) gated on RACE_BASELINE.json — unnamed threads, undeclared
# daemon policy, unlocked thread-target mutation, blocking calls under
# a lock.  half 2: re-run the serving + stream-pipeline + elastic +
# mem-admission unit suites with the runtime lockset/lock-order
# recorder ON — and the
# span recorder armed too (MXTPU_OBS=1): the obs layer's locks and the
# registry mutex nest inside the subsystem locks they serve, and the
# sweep proves the discipline holds under load (new locks must keep
# RACE_BASELINE.json all-zeros) — then replay the combined event log
# and FAIL on any non-baseline finding (the committed baseline is
# all-zeros: a real race gets fixed, not baselined).  HARD timeout: an
# instrumented deadlock must fail this stage, not hang the suite.
# Measured overhead of the instrumented sweep is ~1.1x the plain run
# (well inside the 2x budget) — docs/how_to/static_analysis.md
python tools/concurrency_lint.py --check
TSAN_LOG="$(mktemp)"
timeout -k 10 840 env JAX_PLATFORMS=cpu MXTPU_TSAN=1 MXTPU_OBS=1 \
    MXTPU_TSAN_LOG="$TSAN_LOG" \
    python -m pytest tests/test_serving.py tests/test_serving_overload.py \
        tests/test_stream_pipeline.py tests/test_obs.py \
        tests/test_elastic.py tests/test_integrity.py \
        tests/test_quant_calibration.py tests/test_mem_lint.py \
        tests/test_fleet.py tests/test_parallel_workloads.py \
        -q -m "not slow"
python tools/concurrency_lint.py --no-static --replay "$TSAN_LOG" --check
rm -f "$TSAN_LOG"

stage "overlapped stream input pipeline (2-process decode ring, chunked H2D)"
# the multi-process decode ring + chunked staging + on-device augment
# suite (2 decode worker processes / preprocess_threads=2, pinned to
# the CPU backend).  HARD timeout: a deadlocked ring or queue must
# FAIL this stage, not hang the suite — docs/how_to/perf.md
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_stream_pipeline.py -q

stage "serving layer (continuous batching / AOT shape buckets / fault isolation)"
# the ModelServer suite: padding parity per bucket, zero-retrace steady
# state across mixed request shapes, per-request poison isolation and
# timeouts, multi-tenant hosting, the keyed compiled-forward cache.
# HARD timeout: a wedged scheduler thread or a future that never
# completes must FAIL this stage, not hang the suite —
# docs/how_to/serving.md
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_serving.py -q

stage "serving overload suite (admission control / breaker / drain / supervision)"
# the graceful-degradation half of the serving story: bounded-queue
# reject vs block backpressure, EWMA deadline shedding before AND
# after dispatch, request cancellation, the per-model circuit breaker,
# scheduler-crash fails-all, stop(drain_s), round-robin tenant
# fairness, and the goodput-under-overload invariant (goodput at max
# offered load >= 0.9x the 1x goodput).  HARD timeout: a wedged
# backpressure wait or a stranded future must FAIL this stage, not
# hang the suite — docs/how_to/serving.md "Overload & degradation"
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_serving_overload.py -q

stage "fleet serving suite (stats routing / failover / elastic replicas / rollout)"
# the replicated tier over ModelServer: p2c-vs-rr routing on the paced
# skewed fixture, failover on breaker-open and replica death, elastic
# shrink + warm autoheal (zero spin-up compiles), serve-role membership
# records, and the zero-downtime weight rollout (zero dropped requests,
# canary rollback restores the old weights, checkpoint watcher).  HARD
# timeout: a wedged drain or a rollout that never converges must FAIL
# this stage, not hang the suite — docs/how_to/serving.md "Fleet
# serving"
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_fleet.py -q

stage "state-integrity suite (fingerprint / replica vote / verified rollback)"
# the silent-data-corruption defense: on-device checksum determinism,
# bitflip -> vote -> rank blame on the 2-replica CPU mesh, rollback to
# the newest checkpoint that re-hashes to its manifest fingerprint,
# the consecutive-divergence cap, ZeRO-1 shard checksums, and the
# keep-N carve-out for the newest verified save.  HARD timeout: a
# wedged vote program or a rollback loop must FAIL this stage, not
# hang the suite — docs/how_to/resilience.md "Silent data corruption"
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_integrity.py -q

stage "fault-injection suite (sentinel / crash-resume / io recovery)"
# every recovery path driven on demand via MXTPU_FAULTS — step sentinel
# skip/abort, SIGKILL-faithful torn-checkpoint resume (subprocess),
# iterator retry, prefetcher error propagation; CPU-fast, runs in the
# FAST tier by design (docs/how_to/resilience.md)
python -m pytest tests/test_resilience.py -q

stage "elastic membership suite (dead-host detect / shrink / auto-resume)"
# membership epochs over the heartbeat transports, the collective-entry
# step barrier, hb_stall split-brain revocation, and the launcher-driven
# kill-shrink-resume e2e (tools/launch.py --local-elastic: 2 CPU worker
# subprocesses, rank 1 host_dead-injected, survivor shrinks to 1 and
# resumes bit-identically).  HARD timeout: a wedged barrier or a hung
# relaunch must FAIL this stage, not hang the suite —
# docs/how_to/multi_host.md "Elastic training"
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_elastic.py -q

stage "zero-1 / grad-accum / bf16-grad-comm suite (2-device CPU mesh)"
# ZeRO-1 state sharding, microbatch accumulation, and reduced-precision
# gradient comm: bitwise parity on exact arithmetic, resume parity under
# mesh+zero1, the zero-opt-state lint pass — docs/how_to/perf.md
# "Optimizer sharding"
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_zero_accum.py -q

stage "unit tests (virtual 8-device CPU mesh)"
# test_dist.py re-runs the launcher/consistency scripts below;
# test_elastic.py, test_fleet.py, test_integrity.py, test_obs.py,
# test_quant_calibration.py, test_resilience.py, test_serving.py,
# test_serving_overload.py, test_stream_pipeline.py and
# test_zero_accum.py already ran as their own stages above
python -m pytest tests/ -x -q --ignore=tests/test_dist.py \
    --ignore=tests/test_elastic.py \
    --ignore=tests/test_fleet.py \
    --ignore=tests/test_integrity.py \
    --ignore=tests/test_obs.py \
    --ignore=tests/test_quant_calibration.py \
    --ignore=tests/test_resilience.py \
    --ignore=tests/test_serving.py \
    --ignore=tests/test_serving_overload.py \
    --ignore=tests/test_stream_pipeline.py \
    --ignore=tests/test_zero_accum.py \
    ${PYTEST_MARK[@]+"${PYTEST_MARK[@]}"}

stage "distributed (2-worker local launcher)"
python tools/launch.py -n 2 --launcher local -- \
    python tests/nightly/dist_sync_kvstore.py
python tools/launch.py -n 2 --launcher local -- \
    python tests/nightly/dist_mlp.py
python tools/launch.py -n 2 --launcher local -- \
    python tests/nightly/dist_fused_mlp.py
if [ "$FULL" = "1" ]; then
    # nightly: the sum semantics must hold beyond the 2-worker case
    python tools/launch.py -n 3 --launcher local -- \
        python tests/nightly/dist_sync_kvstore.py
    # nightly: conv-net dist parity (LeNet + BatchNorm net: cross-rank
    # lockstep, BN aux-state agreement, serial parity) at 2 AND 3
    # workers — the reference's dist_lenet/multi_lenet pair
    python tools/launch.py -n 2 --launcher local -- \
        python tests/nightly/dist_lenet.py
    python tools/launch.py -n 3 --launcher local -- \
        python tests/nightly/dist_lenet.py
fi

stage "crash-restart recovery (auto-restart orchestration)"
# heartbeats over the jax.distributed coordination service (no shared
# filesystem; the file transport is unit-tested in test_health.py)
RESUME_DIR="$(mktemp -d)"
trap 'rm -rf "$RESUME_DIR" "$CHIP_LOG"' EXIT
MXTPU_HEARTBEAT_TRANSPORT=kv python tools/launch.py -n 2 --launcher local \
    --auto-restart 1 -- python tests/nightly/dist_resume.py "$RESUME_DIR"

# ---------------------------------------------------------------- join
if [ "$FULL" != "1" ]; then
    stage "waiting for the chip lane"
    CHIP_OK=0
    wait "$CHIP_PID" || CHIP_OK=$?
    cat "$CHIP_LOG"
    if [ "$CHIP_OK" != "0" ]; then
        echo "chip lane FAILED (exit $CHIP_OK)" >&2
        exit "$CHIP_OK"
    fi
fi

stage "CI OK"
