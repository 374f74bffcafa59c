#!/bin/bash
# CPU gate playbook: the count/parity gates that run on the 8-virtual-
# device CPU platform and can gate commits. Every mode pins
# JAX_PLATFORMS=cpu; none of them produces a device number. What the chip
# can show goes through the chip tool, one command per call, starting
# with `python3 chip_smoke.py`.
#
#   bash benches/playbook.sh MODE [tag]
#
#   comm-multihost
#             2-process hierarchical-collective smoke
#             (benches/comm_multihost.py): weak-scaling rows + the
#             hier-vs-psum parity gate. CPU-only and self-contained,
#             so it can gate commits.
#   check     graftcheck with the cost/sharding families
#             (`python -m parallel_cnn_tpu check --cost`): static comm
#             bytes vs the closed-form tables, peak-HBM accounting, the
#             DCN/HBM ratchet. CPU-only, gates commits like
#             comm-multihost; the report grep is the contract line.
#   obs       observability overhead gate (benches/run.py --suite obs):
#             traced-vs-untraced step throughput pairs; tracing must hold
#             >= 0.95x untraced. CPU-only and self-contained — gates
#             commits like comm-multihost; OBS_GATE is the contract line.
#   elastic   elastic-runtime gate (benches/run.py --suite elastic):
#             resize downtime / reshard-cost rows on an 8-virtual-device
#             CPU mesh, gated on the 8->4->8 resize-lap loss parity
#             (<= 1e-5) and pure-reshard bit-exactness. CPU-only and
#             self-contained — gates commits like comm-multihost;
#             ELASTIC_GATE is the contract line.
#   async     straggler-tolerant async-DP gate (benches/run.py --suite
#             comm, final leg): sync ring vs bounded-staleness (S=2) vs
#             EASGD on the virtual-clock harness, clean and under chaos
#             slow-worker@2:400, gated both ways (async holds >= 0.8x
#             clean throughput while the sync ring is asserted to
#             degrade below it) with seeded 3-step loss deltas <= 1e-2
#             and the staleness ledger <= S. CPU-only and self-contained
#             — gates commits like comm-multihost; ASYNC_GATE is the
#             contract line.
#   pipeline  1F1B pipeline-parallel gate (benches/run.py --suite
#             pipeline): stages 1/2/4 over the (stage, data) mesh on 8
#             virtual CPU devices, gated on stages=1 bit-exactness and
#             stages 2/4 <= 1e-5 parity vs the flat data ring, plus the
#             schedule-counted bubble fraction equal to the closed form
#             (S-1)/(S-1+M). CPU-only and self-contained — gates commits
#             like comm-multihost; PIPELINE_GATE is the contract line.
#   net       network front-door gate (benches/run.py --suite net):
#             cold-vs-warm AOT disk-cache cold start (warm must compile
#             nothing), wire-vs-in-process throughput, and the net
#             scenario sweep over real loopback sockets (steady /
#             slow-loris reap / supervised kill-endpoint respawn /
#             unsupervised trip / hot-swap zero-failed). CPU-only and
#             self-contained — gates commits like comm-multihost;
#             SERVE_NET_GATE is the contract line.
#   tune      autotuner gate (benches/run.py --suite autotune): the cost
#             model's predicted plan ranking vs measured throughput on
#             the 8-virtual-device CPU mesh (pairwise order gate, with
#             the doctored-inversion anti-vacuity check) plus the
#             predictive-autoscaler flash-crowd leg (first scale-up
#             carries reason=predictive and lands before any shed).
#             CPU-only and self-contained — gates commits like
#             comm-multihost; AUTOTUNE_GATE is the contract line.
#   serve-chaos
#             SLO-guarded serving gate (benches/run.py --suite serve):
#             seeded scenario suites (diurnal / flash-crowd /
#             slow-client / chaos-kill clean, chaos-slow expected-trip)
#             plus autoscaler flash-crowd recovery, judged on explicit
#             p99 / shed-rate / conservation gates. CPU-only and
#             self-contained — gates commits like comm-multihost;
#             SERVE_SLO_GATE is the contract line.
#
# All artifacts append/write under docs/ with the given tag (default: the
# UTC date), so repeated runs accumulate evidence instead of overwriting.
set -u -o pipefail
MODE="${1:?usage: playbook.sh MODE [tag]}"
TAG="${2:-$(date -u +%Y%m%d)}"
OVERALL=0
cd "$(dirname "$0")/.."
# benches/*.py import parallel_cnn_tpu; invoked as scripts their sys.path[0]
# is benches/, so the repo root must be on PYTHONPATH explicitly.
export PYTHONPATH="${PYTHONPATH:+$PYTHONPATH:}$PWD"
LOG="docs/playbook_${TAG}.log"
echo "=== playbook ${MODE} start $(date -u +%FT%TZ) ===" >> "$LOG"

if [ "$MODE" = "comm-multihost" ]; then
  echo "--- comm-multihost smoke ---" >> "$LOG"
  OUT="docs/comm_multihost_${TAG}.txt"
  timeout 900 env JAX_PLATFORMS=cpu python benches/comm_multihost.py > "$OUT" 2>&1
  RC=$?; echo "comm-multihost rc=$RC" >> "$LOG"
  # The gate line is the contract: both legs' hier-vs-psum parity <= 1e-5.
  grep -q 'COMM_MULTIHOST_GATE PASS' "$OUT" || RC=1
  [ $RC -ne 0 ] && OVERALL=1
  echo "=== playbook ${MODE} end rc=${OVERALL} $(date -u +%FT%TZ) ===" >> "$LOG"
  exit $OVERALL
fi

if [ "$MODE" = "check" ]; then
  echo "--- graftcheck --cost gate ---" >> "$LOG"
  OUT="docs/check_cost_${TAG}.txt"
  # 8 virtual devices so the zoo/hier traces (and hence the byte tables)
  # match the documented 2-host emulated mesh exactly.
  timeout 900 env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m parallel_cnn_tpu check --cost > "$OUT" 2>&1
  RC=$?; echo "check --cost rc=$RC" >> "$LOG"
  # The gate line is the contract: zero gating errors on a clean tree.
  grep -q 'graftcheck: 0 gating error(s)' "$OUT" || RC=1
  [ $RC -ne 0 ] && OVERALL=1
  echo "=== playbook ${MODE} end rc=${OVERALL} $(date -u +%FT%TZ) ===" >> "$LOG"
  exit $OVERALL
fi

if [ "$MODE" = "obs" ]; then
  echo "--- obs overhead gate ---" >> "$LOG"
  OUT="docs/obs_${TAG}.txt"
  timeout 900 env JAX_PLATFORMS=cpu \
    python benches/run.py --quick --suite obs > "$OUT" 2>&1
  RC=$?; echo "obs rc=$RC" >> "$LOG"
  # The gate line is the contract: traced throughput >= 0.95x untraced.
  grep -q 'OBS_GATE PASS' "$OUT" || RC=1
  [ $RC -ne 0 ] && OVERALL=1
  echo "=== playbook ${MODE} end rc=${OVERALL} $(date -u +%FT%TZ) ===" >> "$LOG"
  exit $OVERALL
fi

if [ "$MODE" = "elastic" ]; then
  echo "--- elastic resize gate ---" >> "$LOG"
  OUT="docs/elastic_${TAG}.txt"
  # 8 virtual devices: the lap's worlds (8 and 4) need a full-size mesh.
  timeout 900 env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benches/run.py --quick --suite elastic > "$OUT" 2>&1
  RC=$?; echo "elastic rc=$RC" >> "$LOG"
  # The gate line is the contract: lap parity <= 1e-5 + bit-exact reshard.
  grep -q 'ELASTIC_GATE PASS' "$OUT" || RC=1
  [ $RC -ne 0 ] && OVERALL=1
  echo "=== playbook ${MODE} end rc=${OVERALL} $(date -u +%FT%TZ) ===" >> "$LOG"
  exit $OVERALL
fi

if [ "$MODE" = "async" ]; then
  echo "--- async straggler gate ---" >> "$LOG"
  OUT="docs/async_${TAG}.txt"
  # 8 virtual devices: the comm suite's ring/hier legs need the full
  # emulated mesh; the async leg itself is host-side (virtual clock).
  timeout 900 env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benches/run.py --quick --suite comm > "$OUT" 2>&1
  RC=$?; echo "async rc=$RC" >> "$LOG"
  # The gate line is the contract: both-ways straggler ratios + bounded
  # loss deltas + ledger <= S.
  grep -q 'ASYNC_GATE PASS' "$OUT" || RC=1
  [ $RC -ne 0 ] && OVERALL=1
  echo "=== playbook ${MODE} end rc=${OVERALL} $(date -u +%FT%TZ) ===" >> "$LOG"
  exit $OVERALL
fi

if [ "$MODE" = "pipeline" ]; then
  echo "--- pipeline 1F1B gate ---" >> "$LOG"
  OUT="docs/pipeline_${TAG}.txt"
  # 8 virtual devices: the stages 1/2/4 sweep needs (1,8)/(2,4)/(4,2)
  # (stage, data) meshes over a full-size device set.
  timeout 900 env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benches/run.py --quick --suite pipeline > "$OUT" 2>&1
  RC=$?; echo "pipeline rc=$RC" >> "$LOG"
  # The gate line is the contract: parity (bit-exact / <= 1e-5) + the
  # schedule bubble equal to (S-1)/(S-1+M).
  grep -q 'PIPELINE_GATE PASS' "$OUT" || RC=1
  [ $RC -ne 0 ] && OVERALL=1
  echo "=== playbook ${MODE} end rc=${OVERALL} $(date -u +%FT%TZ) ===" >> "$LOG"
  exit $OVERALL
fi

if [ "$MODE" = "net" ]; then
  echo "--- serve network front-door gate ---" >> "$LOG"
  OUT="docs/serve_net_${TAG}.txt"
  # 8 virtual devices so the hot-swap leg's grown replica gets its own
  # device slot (same mesh the tests and the serve suite assume).
  timeout 900 env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benches/run.py --quick --suite net > "$OUT" 2>&1
  RC=$?; echo "net rc=$RC" >> "$LOG"
  # The gate line is the contract: zero warm-start compiles, balanced
  # wire ledgers, the loris reaped, the supervised kill ridden through,
  # the unsupervised trip proven, the hot swap zero-failed.
  grep -q 'SERVE_NET_GATE PASS' "$OUT" || RC=1
  [ $RC -ne 0 ] && OVERALL=1
  echo "=== playbook ${MODE} end rc=${OVERALL} $(date -u +%FT%TZ) ===" >> "$LOG"
  exit $OVERALL
fi

if [ "$MODE" = "tune" ]; then
  echo "--- autotune ranking + predictive-scaler gate ---" >> "$LOG"
  OUT="docs/autotune_${TAG}.txt"
  # 8 virtual devices: the measured candidates span flat data rings and
  # (stage, data) pipeline meshes over the full emulated device set.
  timeout 900 env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benches/run.py --quick --suite autotune > "$OUT" 2>&1
  RC=$?; echo "tune rc=$RC" >> "$LOG"
  # The gate line is the contract: measured ranking agrees with the
  # model, the doctored table trips, the predictive scale-up lands
  # before any shed.
  grep -q 'AUTOTUNE_GATE PASS' "$OUT" || RC=1
  [ $RC -ne 0 ] && OVERALL=1
  echo "=== playbook ${MODE} end rc=${OVERALL} $(date -u +%FT%TZ) ===" >> "$LOG"
  exit $OVERALL
fi

if [ "$MODE" = "serve-chaos" ]; then
  echo "--- serve SLO + chaos scenario gate ---" >> "$LOG"
  OUT="docs/serve_slo_${TAG}.txt"
  # 8 virtual devices so the 2-replica rows and the autoscaler's grown
  # replica each get their own device slot.
  timeout 900 env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benches/run.py --quick --suite serve > "$OUT" 2>&1
  RC=$?; echo "serve-chaos rc=$RC" >> "$LOG"
  # The gate line is the contract: clean scenarios pass their p99/shed
  # gates AND the armed slow-replica run trips its gate (anti-vacuity).
  grep -q 'SERVE_SLO_GATE PASS' "$OUT" || RC=1
  [ $RC -ne 0 ] && OVERALL=1
  echo "=== playbook ${MODE} end rc=${OVERALL} $(date -u +%FT%TZ) ===" >> "$LOG"
  exit $OVERALL
fi

echo "playbook: unknown mode '${MODE}'" >&2
echo "=== playbook ${MODE} end rc=2 $(date -u +%FT%TZ) ===" >> "$LOG"
exit 2
